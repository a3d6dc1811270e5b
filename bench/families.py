"""Modules found by name under ``bench/``: a model family's ``drivers/``,
``references/`` and ``counts/`` files (``bench/harness.py`` lists what
each exports), and the per-layer metrics' readers."""
from __future__ import annotations

import importlib.util
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def module(family: str, part: str):
    """The family's ``bench/<part>/<family>.py`` (``drivers``,
    ``references`` or ``counts``)."""
    return load_module(BENCH / part / f"{family}.py", f"bench_{part}_{family}")
