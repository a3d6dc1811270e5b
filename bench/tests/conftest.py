"""Helpers for the benchmark's own tests (``pytest bench/tests``): they run
on the CPU, outside the repository's tier-1 ``testpaths``."""
import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [(w["name"], w["chips"]) for w in SPEC["workloads"]]


def tiny_checkout(dest: Path) -> Path:
    """A copy of ``BENCHMARK.json`` and ``bench/`` whose configurations and
    traffic are cut to a size the CPU runs in seconds, with ``src`` linked
    in: each configuration by its family's ``tiny``. Cells, names and
    limits are the real ones."""
    from bench import families

    shutil.copytree(ROOT / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace",
                                                  "tests"))
    os.symlink(ROOT / "src", dest / "src")
    (dest / "BENCHMARK.json").write_text(json.dumps(SPEC))
    for c in SPEC["configs"]:
        path = dest / c["file"]
        cfg = json.loads(path.read_text())
        ref = families.module(cfg["family"], "references")
        path.write_text(json.dumps(ref.tiny(cfg)))
    chips = {w["traffic"]: w["chips"] for w in SPEC["workloads"]}
    for t in (dest / "bench" / "traffic").glob("*.json"):
        traffic = json.loads(t.read_text())
        k = 4 if chips.get(t.stem, 1) > 1 else 2
        traffic.update(clients=3 * k, samples_per_client=32, batch=8,
                       cohort=k)
        t.write_text(json.dumps(traffic))
    return dest


# drives ``bench/run.py``'s main on the CPU: the host devices stand in for
# the chips, with a peak for the CPU so that the share-of-peak readers have
# a denominator
DRIVER = textwrap.dedent("""
    import json, sys
    sys.path[:0] = [{root!r}, {root!r} + "/src"]
    import jax
    import run
    from bench import harness
    run.tpu_devices = lambda chips: jax.devices()[:chips]
    harness.peak_of = lambda kind: {{"bf16_flops": 1e12}}
    {patch}
    sys.exit(run.main({argv!r}))
""")


def run_cli(checkout: Path, argv, *, devices: int = 1, patch: str = "",
            timeout: int = 600):
    """``bench/run.py`` of ``checkout`` on the CPU, in a subprocess (the
    host device count is fixed before jax starts). Returns
    (returncode, stdout lines, stderr lines)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    code = DRIVER.format(root=str(checkout), argv=list(argv),
                         patch=patch)
    proc = subprocess.run([sys.executable, "-c", code],
                          cwd=checkout / "bench", env=env, timeout=timeout,
                          capture_output=True, text=True)
    return (proc.returncode, proc.stdout.splitlines(),
            proc.stderr.splitlines())


@pytest.fixture
def checkout(tmp_path):
    return tiny_checkout(tmp_path)
