"""Sharded cohort execution (ISSUE 5): client-axis shard_map invariants.

Two layers:

  * in-process tests — mesh ``None`` vs a size-1 client mesh must be
    BIT-identical (the sharded path only engages at axis size > 1), and the
    sharding helpers must be identity/replicated fallbacks in degenerate
    configurations;
  * a subprocess driver (``tests/_shard_driver.py``) under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — an 8-way
    sharded round/server must match the single-device run allclose (f32)
    on params, BN state, losses, uplink bytes, and selection picks, with
    cohort-padding, tiered-cache, compressed-uplink, and
    population-divisibility edge cases. The forced-host-device flag must
    be set before jax initializes, hence the subprocess.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import freezing_cnn as fz
from repro.data.partition import dirichlet_partition
from repro.data.synthetic import SyntheticVision
from repro.fl.client import make_client_fleet
from repro.fl.engine import RoundEngine
from repro.launch.mesh import make_client_mesh
from repro.models.cnn import CNN, CNNConfig
from repro.optim import sgd

TINY = CNNConfig("tiny_resnet", "resnet", stage_sizes=(1, 1),
                 stage_channels=(8, 16), num_classes=4)


# ---------------------------------------------------------------------------
# in-process: degenerate meshes
# ---------------------------------------------------------------------------


def _world():
    sv = SyntheticVision(num_classes=4, image_size=16, seed=0)
    train = sv.sample(400, seed=1)
    parts = dirichlet_partition(train["y"], 5, alpha=1.0, seed=0)
    clients = make_client_fleet(train, parts, scenario="low", seed=0)
    model = CNN(TINY)
    params, state = model.init(jax.random.PRNGKey(0))
    return {c.client_id: c for c in clients}, model, params, state


def test_mesh_size_one_is_bit_identical():
    """A 1-device client mesh must reproduce the no-mesh trajectory
    bit-for-bit (the sharded path only engages at axis size > 1)."""
    by_id, model, params, state = _world()
    frozen, active = fz.init_cnn_stage_active(model, params, 0,
                                              jax.random.PRNGKey(1))
    sel = sorted(by_id)

    def run(mesh):
        eng = RoundEngine(loss_fn=fz.cnn_stage_loss_fn(model, 0),
                          optimizer=sgd(0.05), frozen=frozen, batch_size=32,
                          local_epochs=1, mesh=mesh)
        return eng.run_round(by_id, sel, active, state, 7)

    a0, s0, l0 = run(None)
    a1, s1, l1 = run(make_client_mesh(1))
    for x, y in zip(jax.tree.leaves((a0, s0)), jax.tree.leaves((a1, s1))):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    assert l0 == l1


def test_client_helpers_degenerate():
    from repro.dist.sharding import (client_axis_size, client_spec,
                                     shard_client_arrays)
    assert client_axis_size(None) == 1
    assert client_axis_size(make_client_mesh(1)) == 1
    # no active client axis: identity (no device_put, no copies)
    x = jnp.arange(6.0)
    assert shard_client_arrays(None, x) is x
    assert shard_client_arrays(make_client_mesh(1), x) is x
    assert tuple(client_spec(3)) == ("clients", None, None)


def test_population_shard_single_device_equal():
    """shard() on a 1-device mesh keeps kernels byte-equal (and drops the
    stage-time memo so it recomputes on the new placement)."""
    from repro.core.selector import ClientInfo, ClientPopulation
    from repro.core.selector.vectorized import assign_cache_tiers
    rng = np.random.RandomState(1)
    infos = {i: ClientInfo(i, float(rng.choice([1, 2, 4])) * 2**30, 1e9,
                           int(rng.randint(32, 256)), float(rng.rand()))
             for i in range(12)}
    pop = ClientPopulation.from_infos(infos)
    pop_s = pop.shard(make_client_mesh(1))
    rates = [4e3, 2e3, 1e3]
    assert np.array_equal(assign_cache_tiers(pop, 1e8, rates),
                          assign_cache_tiers(pop_s, 1e8, rates))
    assert np.array_equal(np.asarray(pop.stage_time()),
                          np.asarray(pop_s.stage_time()))


# ---------------------------------------------------------------------------
# subprocess: 8 forced host devices
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def shard_report():
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..", "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(__file__), "_shard_driver.py")],
        env=env, capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("JSON:")]
    assert line, proc.stdout[-2000:]
    report = json.loads(line[-1][len("JSON:"):])
    assert report["n_devices"] == 8, report
    return report


@pytest.mark.slow
def test_sharded_round_matches_single_device(shard_report):
    assert shard_report["round_params_allclose"]
    assert shard_report["round_state_allclose"]
    assert shard_report["round_losses_allclose"]
    assert shard_report["round_uplink_equal"]


@pytest.mark.slow
def test_sharded_screening(shard_report):
    """ISSUE 7: defenses armed + zero faults is BIT-identical on the mesh;
    an injected nan update is screened with a finite aggregate matching the
    single-device defended round."""
    assert shard_report["screened_zero_fault_bitwise"]
    assert shard_report["screened_fault_finite"]
    assert shard_report["screened_fault_matches_single"]
    assert shard_report["screened_fault_flagged"]


@pytest.mark.slow
def test_cohort_smaller_than_mesh_padding(shard_report):
    assert shard_report["pad_params_allclose"]
    assert shard_report["pad_losses_allclose"]


def test_padded_cohort_is_staged_once(shard_report):
    """The mesh's pad rows live in the reused staging buffers: allocated
    in the first padded round, none in the next, the same bytes put."""
    assert shard_report["pad_staged_once"]


@pytest.mark.slow
def test_tiered_cache_sharded(shard_report):
    assert shard_report["tiered_cache_allclose"]


@pytest.mark.slow
def test_mixed_tier_groups_sharded(shard_report):
    assert shard_report["mixed_groups_allclose"]


@pytest.mark.slow
def test_compressed_sharded(shard_report):
    assert shard_report["compressed_allclose"]
    assert shard_report["compressed_uplink_equal"]


@pytest.mark.slow
def test_server_sharded_trajectory(shard_report):
    assert shard_report["server_picks_equal"]
    assert shard_report["server_uplink_equal"]
    assert shard_report["server_losses_allclose"]
    assert shard_report["server_params_allclose"]
    assert shard_report["server_vtime_equal"]


@pytest.mark.slow
def test_population_sharded_kernels(shard_report):
    assert shard_report["population_picks_equal"]
    assert shard_report["admission_equal"]


@pytest.mark.slow
def test_population_nondivisible_fallback(shard_report):
    assert shard_report["nondiv_replicated"]
    assert shard_report["nondiv_admission_equal"]
