#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

The cell, its configuration, traffic, limits and metrics are found by name
from ``BENCHMARK.json`` at the root of the checkout. The run needs a TPU
with at least the cell's chips: without one it exits non-zero and prints no
result. The last line of stdout is the result as one JSON object; the last
lines of stderr are the numbers compared with the reference, each beside
its limit.

The persistent compilation cache lives in ``<checkout>/.jax_cache``, and
the traced run's profile in ``<checkout>/bench/.trace`` (the last one
only). A traced run measures its window untraced, then traces three
seconds more of it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / "bench" / ".trace"

# before jax is imported: the checkout's own compile cache, whatever the
# machine sets, so that two checkouts share nothing; and no TPU runtime
# logs written to a fixed path outside the checkout
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def tpu_devices(chips: int):
    """The TPU devices JAX finds, or None where there are fewer than
    ``chips`` of them."""
    import jax

    devices = jax.devices()
    if jax.default_backend() != "tpu" or len(devices) < chips:
        print(f"bench/run.py needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {jax.default_backend()} device(s)",
              file=sys.stderr)
        return None
    return devices


def main(argv=None) -> int:
    args = parse(argv)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    from bench import harness   # imports jax and the program

    import jax
    from repro.launch.cache import use_compile_cache

    files = harness.cell_files(ROOT, spec, args.workload)
    chips = files["cell"]["chips"]
    devices = tpu_devices(chips)
    if devices is None:
        return 2
    harness.peak_of(devices[0].device_kind)   # an unknown chip: error
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    trace_dir = None
    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        trace_dir = TRACE_DIR
    result = harness.run_cell(files, seed=args.seed, seconds=args.seconds,
                              chips=chips, trace_dir=trace_dir,
                              t_start=T_START, devices=devices)
    notes = result.pop("notes")
    print(" ".join(f"{k} {v}" for k, v in notes.items()), file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
