"""The comparison that decides ``correct`` fails what it must: each fault
planted in the reference put in the program's place, and a whole run with
the timed path broken underneath. On the CPU, at a tiny width, against
each cell's committed limits. The program's own bfloat16 path, the
control, is read beside them but not held to the limits: on the chip it
reads as close to the reference as the sound program does (PERF.md)."""
import json

import pytest

from conftest import CELLS, run_cli


# faults planted in the program, each as code run before the run starts
STATE_UNCHANGED = """
from repro.fl import engine
_orig = engine.RoundEngine.run_round
def _unchanged(self, clients, selected, params, state, *a, **k):
    _, _, losses = _orig(self, clients, selected, params, state, *a, **k)
    return params, state, losses
engine.RoundEngine.run_round = _unchanged
"""

# every local step trains on the first half of its batch, the mean taken
# over that half
HALF_BATCH = """
from repro.fl import engine
_orig = engine.make_fused_round
def _make(loss_fn, *a, **k):
    def half(p, f, s, b):
        return loss_fn(p, f, s, {n: v[:v.shape[0] // 2] for n, v in b.items()})
    return _orig(half, *a, **k)
engine.make_fused_round = _make
"""

# the round's update of its first param leaf comes back doubled
ANSWER_ALTERED = """
import jax
from repro.fl import engine
_orig = engine.RoundEngine.run_round
def _doubled(self, clients, selected, params, state, *a, **k):
    p, s, losses = _orig(self, clients, selected, params, state, *a, **k)
    leaves, tree = jax.tree.flatten(p)
    leaves[0] = 2 * leaves[0] - jax.tree.leaves(params)[0]
    return jax.tree.unflatten(tree, leaves), s, losses
engine.RoundEngine.run_round = _doubled
"""

# the mesh's psums left out: each chip folds its own clients alone
NO_EXCHANGE = """
import jax
_shard_map = jax.shard_map
jax.shard_map = lambda f, **k: _shard_map(f, **dict(k, check_vma=False))
jax.lax.psum = lambda x, axis_name, **k: x
"""

FAULTS = {"state_unchanged": STATE_UNCHANGED, "half_batch": HALF_BATCH,
          "answer_altered": ANSWER_ALTERED}


def _faults():
    for cell, chips in CELLS:
        for name, patch in FAULTS.items():
            yield pytest.param(cell, chips, patch, id=f"{cell}-{name}")
        if chips > 1:
            yield pytest.param(cell, chips, NO_EXCHANGE,
                               id=f"{cell}-no_exchange")


@pytest.mark.parametrize("cell,chips,patch", list(_faults()))
def test_fault_in_the_timed_path_is_not_correct(checkout, cell, chips,
                                                patch):
    rc, out, err = run_cli(checkout, ["--workload", cell, "--seed", "7",
                                      "--seconds", "1"],
                           devices=chips, patch=patch)
    assert rc == 0, "\n".join(err[-40:])
    line = json.loads(out[-1])
    assert line["correct"] is False, line["checks"]


@pytest.mark.parametrize("cell,chips", [c for c in CELLS if c[1] == 1])
def test_faults_fail_and_program_passes(checkout, cell, chips):
    """Three seeds: each fault planted in the reference in the program's
    place fails at least one limit on each; the program passes them all,
    and its bfloat16 path departs from the reference."""
    import jax

    from bench import harness

    control = harness.load_module(harness.BENCH / "control.py",
                                  "bench_control")
    spec = json.loads((checkout / "BENCHMARK.json").read_text())
    files = harness.cell_files(checkout, spec, cell)
    for seed in (1, 2, 3):
        r = control.readings(files, seed, chips=chips, devices=jax.devices(),
                             program=True)["numbers"]
        limits = files["limits"]
        for name in ("half_batch", "answer_altered"):
            assert any(r[name][k] > limits[k] for k in limits), (name, r)
        assert all(r["program"][k] <= limits[k] for k in limits), r
        assert r["control"]["grad_diff"] > 100 * r["program"]["grad_diff"]
