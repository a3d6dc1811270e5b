#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell.

    python bench/control.py --workload <cell> --seeds 1 2 3 \
        [--program] [--control] [--faults] [--witness] [--dump FILE]

For each seed it builds the cell's world, follows the lead rounds with the
float32 reference, and compares with it, in the program's place:

- with ``--program``: the program's compiled round, driven through the
  lead rounds as a run drives it (no window);
- with ``--control``: the same with the program's own mixed-precision path
  switched on (``RoundEngine(compute_dtype="bfloat16")``: bfloat16 forward
  and backward over float32 master weights), the step below the float32
  that the configuration states;
- with ``--faults``, planted in the reference: ``half_batch``, every local
  step trains on the first half of its batch; ``answer_altered``, the
  round's update of its first param leaf doubled; and on several chips
  ``no_exchange``, the fold over the first chip's clients alone, as a chip
  whose psum was left out would return it;
- with ``--witness``: the program with every product at ``highest``
  precision (``program_highest``), and the float32 reference at the
  default precision (``reference_default``), to tell rounding from a
  fault.

One JSON line of the compared numbers per seed on stdout; ``--dump`` adds,
one JSON line per seed to FILE, each side's losses and each leaf's numbers
(``bench/check.py``: the reference's norm, the gap of norms and the norm of
the difference, after the first and the last round). Needs the cell's TPU
chips, like ``bench/run.py``; the benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def faulty_references(base, chips: int):
    """Subclasses of the reference class ``base`` with a fault planted."""
    import jax

    class HalfBatch(base):
        def _local_train(self, frozen, active, state, xs, ys):
            half = xs.shape[1] // 2
            return super()._local_train(frozen, active, state,
                                        xs[:, :half], ys[:, :half])

    class AnswerAltered(base):
        def round(self, frozen, active, state, data, plans, weights):
            a, s, losses = super().round(frozen, active, state, data, plans,
                                         weights)
            leaves, tree = jax.tree.flatten(a)
            leaves[0] = 2 * leaves[0] - jax.tree.leaves(active)[0]
            return jax.tree.unflatten(tree, leaves), s, losses

    class NoExchange(base):
        def round(self, frozen, active, state, data, plans, weights):
            outs = self.clients(frozen, active, state, data, plans)
            first = len(outs) // chips
            a, s = self.fold(outs[:first], weights[:first])
            return a, s, [loss for *_, loss in outs]

    out = {"half_batch": HalfBatch, "answer_altered": AnswerAltered}
    if chips > 1:
        out["no_exchange"] = NoExchange
    return out


def default_precision(base):
    """``base`` with every product at the default precision."""
    from contextlib import nullcontext

    class Default(base):
        def _precision(self):
            return nullcontext()

    return Default


def program_rounds(files, w, *, chips: int, devices,
                   compute_dtype=None) -> list:
    """The program's lead rounds, driven as a run drives them."""
    from bench import harness

    cfg, traffic = files["config"], files["traffic"]
    driver = harness.load_module(
        harness.BENCH / "drivers" / f"{cfg['family']}.py",
        f"bench_driver_{cfg['family']}")
    mesh = None
    if chips > 1:
        from repro.launch.mesh import make_client_mesh
        mesh = make_client_mesh(chips, devices=devices[:chips])
    system = driver.System(cfg, traffic, w.frozen, w.state, w.pool_x,
                           w.labels, w.seeds, mesh=mesh,
                           compute_dtype=compute_dtype)
    system.fill_cache()
    params, state, lead = w.active, w.state, []
    for r, cohort in enumerate(w.lead_cohorts):
        params, state, losses = system.run_round(cohort, r, params, state)
        lead.append(harness.host_copy((params, state)) + (losses,))
    system.close()
    del system, params, state
    gc.collect()
    return lead


def leaf_table(side, ref):
    """Per leaf kept, as JSON: the reference's norm and ``side``'s gap of
    norms and norm of the difference, after the first and the last
    round."""
    from bench import check

    keep, median = check.kept(ref), check.median_param(ref)
    out = {}
    for which in ("first", "last"):
        nums = check.leaf_numbers(side, ref, which, keep, median)
        for k, (gap, diff) in nums.items():
            out.setdefault(k, {})[which] = [
                float(np.linalg.norm(ref[which][k])), gap, diff]
    return out


def readings(files, seed: int, *, chips: int, devices, program: bool,
             control: bool = True, faults: bool = True,
             witness: bool = False):
    """For one seed: ``{"seed", "numbers": {side: compared numbers},
    "leaves": {side: leaf_table}, "losses": {side: losses}}``."""
    import jax

    from bench import check, harness

    w = harness.World(files, seed)
    sides, times = {}, {}

    def timed(name, fn):
        t0 = time.perf_counter()
        sides[name] = fn()
        times[name] = round(time.perf_counter() - t0, 2)

    if program:
        timed("program", lambda: check.summary(w.start, program_rounds(
            files, w, chips=chips, devices=devices)))
    if control:
        timed("control", lambda: check.summary(w.start, program_rounds(
            files, w, chips=chips, devices=devices,
            compute_dtype="bfloat16")))
    if witness:
        with jax.default_matmul_precision("highest"):
            timed("program_highest", lambda: check.summary(
                w.start, program_rounds(files, w, chips=chips,
                                        devices=devices)))
    del w.frozen, w.active, w.state
    gc.collect()
    timed("reference", lambda: check.summary(w.start, w.reference_rounds()))
    others = {}
    if faults:
        for name, cls in faulty_references(w.ref.Reference, chips).items():
            others[name] = w.reference(cls=cls)
    if witness:
        others["reference_default"] = w.reference(
            cls=default_precision(w.ref.Reference))
    for name, reference in others.items():
        timed(name, lambda: check.summary(
            w.start, w.reference_rounds(reference)))
    ref = sides.pop("reference")
    numbers, leaves = {}, {}
    for name, side in sides.items():
        c = check.compare(side, ref)
        numbers[name] = {k: c[k]["value"] for k in check.NAMES}
        leaves[name] = leaf_table(side, ref)
    losses = {name: side["losses"] for name, side in sides.items()}
    losses["reference"] = ref["losses"]
    return {"seed": seed, "numbers": numbers, "seconds": times,
            "leaves": leaves, "losses": losses}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program", action="store_true")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--witness", action="store_true")
    ap.add_argument("--dump")
    args = ap.parse_args(argv)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    from bench import harness

    import jax
    from repro.launch.cache import use_compile_cache

    files = harness.cell_files(ROOT, spec, args.workload)
    chips = files["cell"]["chips"]
    devices = jax.devices()
    if jax.default_backend() != "tpu" or len(devices) < chips:
        print(f"bench/control.py needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {jax.default_backend()} device(s)",
              file=sys.stderr)
        return 2
    use_compile_cache()
    for seed in args.seeds:
        r = readings(files, seed, chips=chips, devices=devices,
                     program=args.program, control=args.control,
                     faults=args.faults,
                     witness=args.witness)
        if args.dump:
            with open(args.dump, "a") as f:
                f.write(json.dumps(dict(r, workload=args.workload)) + "\n")
        print(json.dumps({k: r[k] for k in ("seed", "numbers", "seconds")}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
