"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``launch/train.py``, ``benchmarks/run.py``
and the examples) call ``use_compile_cache()`` first thing in ``main()``;
importing a module never turns the cache on.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's own cache directory (listed in .gitignore). A fixed path:
#: the directory is part of the cache key, so one that moves never hits.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    nothing else is configured here. Otherwise the cache goes to
    ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
