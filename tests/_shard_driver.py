"""Multi-device shard checks, run by tests/test_shard.py in a subprocess.

Forced host devices (``XLA_FLAGS=--xla_force_host_platform_device_count=8``)
must be installed BEFORE jax imports, which a normal pytest process — whose
other tests already initialized the single-device backend — cannot do. The
test module launches this script with the flag set and asserts on the JSON
report printed to stdout.
"""
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import freezing_cnn as fz
from repro.core.selector import (ClientInfo, ClientPopulation,
                                 VectorizedSelector)
from repro.core.selector.vectorized import assign_cache_tiers
from repro.data.partition import dirichlet_partition
from repro.data.synthetic import SyntheticVision
from repro.fl.client import make_client_fleet
from repro.fl.engine import RoundEngine
from repro.fl.server import SmartFreezeServer
from repro.launch.mesh import make_client_mesh
from repro.models.cnn import CNN, CNNConfig
from repro.optim import sgd

TINY = CNNConfig("tiny_resnet", "resnet", stage_sizes=(1, 1),
                 stage_channels=(8, 16), num_classes=4)


def tree_close(a, b, rtol=3e-4, atol=3e-4):
    return bool(all(
        np.allclose(np.asarray(x, np.float32), np.asarray(y, np.float32),
                    rtol=rtol, atol=atol)
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))))


def main():
    report = {"n_devices": len(jax.devices())}
    mesh = make_client_mesh(8)
    sv = SyntheticVision(num_classes=4, image_size=16, seed=0)
    train = sv.sample(600, seed=1)
    parts = dirichlet_partition(train["y"], 8, alpha=1.0, seed=0)
    clients = make_client_fleet(train, parts, scenario="low", seed=0)
    by_id = {c.client_id: c for c in clients}
    sel = sorted(by_id)
    model = CNN(TINY)
    params, state = model.init(jax.random.PRNGKey(0))

    def engine(mesh, stage=0, **kw):
        frozen, active = fz.init_cnn_stage_active(model, params, stage,
                                                  jax.random.PRNGKey(1))
        cached = feat = None
        if stage > 0:
            cached = fz.cnn_cached_stage_loss_fn(model, stage)
            feat = lambda x: fz.cnn_prefix_features(model, frozen, state, x,
                                                    stage)
        return RoundEngine(loss_fn=fz.cnn_stage_loss_fn(model, stage),
                           optimizer=sgd(0.05), frozen=frozen,
                           cached_loss_fn=cached, feature_fn=feat,
                           batch_size=32, local_epochs=1, mesh=mesh,
                           **kw), active

    # --- 8-way fused round == single-device (params, state, losses) ---
    e0, active = engine(None)
    e1, _ = engine(mesh)
    a0, s0, l0 = e0.run_round(by_id, sel, active, state, 3)
    a1, s1, l1 = e1.run_round(by_id, sel, active, state, 3)
    report["round_params_allclose"] = tree_close(a0, a1)
    report["round_state_allclose"] = tree_close(s0, s1)
    report["round_losses_allclose"] = bool(
        all(abs(l0[c] - l1[c]) < 1e-3 for c in sel))
    report["round_uplink_equal"] = (e0.last_uplink_bytes
                                    == e1.last_uplink_bytes)

    # --- update screening on the mesh: defenses armed + zero faults must be
    # BIT-identical to the undefended sharded round (ISSUE 7 acceptance), and
    # an injected nan update must be screened out with a finite aggregate that
    # matches the single-device defended round ---
    def tree_bytes(t):
        return b"".join(np.asarray(x).tobytes() for x in jax.tree.leaves(t))

    e0, active = engine(mesh)
    e1, _ = engine(mesh, screen=True)
    a0, s0, l0 = e0.run_round(by_id, sel, active, state, 3)
    a1, s1, l1 = e1.run_round(by_id, sel, active, state, 3)
    report["screened_zero_fault_bitwise"] = (tree_bytes(a0) == tree_bytes(a1)
                                             and tree_bytes(s0) == tree_bytes(s1)
                                             and l0 == l1)
    ef, _ = engine(mesh, screen=True)
    af, sf, lf = ef.run_round(by_id, sel, active, state, 3,
                              faults={sel[0]: "nan"})
    e2, _ = engine(None, screen=True)
    a2, s2, l2 = e2.run_round(by_id, sel, active, state, 3,
                              faults={sel[0]: "nan"})
    report["screened_fault_finite"] = bool(all(
        np.isfinite(np.asarray(x)).all() for x in jax.tree.leaves(af)))
    report["screened_fault_matches_single"] = tree_close(af, a2)
    report["screened_fault_flagged"] = (ef.last_screened[sel[0]] is True
                                        and e2.last_screened[sel[0]] is True)

    # --- cohort smaller than the mesh: padding must not perturb Eq. 1 ---
    e0, active = engine(None)
    e1, _ = engine(mesh)
    a0, s0, l0 = e0.run_round(by_id, sel[:3], active, state, 5)
    a1, s1, l1 = e1.run_round(by_id, sel[:3], active, state, 5)
    report["pad_params_allclose"] = tree_close(a0, a1)
    report["pad_losses_allclose"] = bool(
        all(abs(l0[c] - l1[c]) < 1e-3 for c in sel[:3]))
    # the staging buffers hold the 5 pad rows too: allocated in the first
    # padded round alone, with the bytes put unchanged
    nb = max(by_id[c].num_samples // 32 for c in sel[:3])
    x, y = by_id[sel[0]].data["x"], by_id[sel[0]].data["y"]
    rows = 8 * nb * 32
    staged = rows * (x[0].nbytes + y[0].nbytes)
    first = e1.spans.snapshot()["counters"]
    e1.spans.reset()
    e1.run_round(by_id, sel[:3], a1, s1, 6)
    second = e1.spans.snapshot()["counters"]
    report["pad_staged_once"] = (
        first.get("engine.stage_alloc_bytes") == staged
        and "engine.stage_alloc_bytes" not in second
        and first["engine.h2d_bytes"] == second["engine.h2d_bytes"]
        == staged + 8 * (4 + 4))

    # --- tiered cache gathers under shard_map (int8 dequant in-graph) ---
    e0, active1 = engine(None, stage=1)
    e1, _ = engine(mesh, stage=1)
    cache = {cid: "int8" for cid in sel}
    a0, s0, _ = e0.run_round(by_id, sel, active1, state, 2, use_cache=cache)
    a1, s1, _ = e1.run_round(by_id, sel, active1, state, 2, use_cache=cache)
    report["tiered_cache_allclose"] = tree_close(a0, a1)

    # --- mixed tier groups: each sub-cohort pads separately and the group
    # aggregates (mesh-replicated) combine through weighted_avg ---
    e0, active1 = engine(None, stage=1)
    e1, _ = engine(mesh, stage=1)
    mixed = {cid: ("int8" if i % 2 else None) for i, cid in enumerate(sel)}
    a0, s0, _ = e0.run_round(by_id, sel, active1, state, 4, use_cache=mixed)
    a1, s1, _ = e1.run_round(by_id, sel, active1, state, 4, use_cache=mixed)
    report["mixed_groups_allclose"] = tree_close(a0, a1)

    # --- compressed rounds: psum of sparse partial aggregates + EF carry ---
    e0, active = engine(None, compress_ratio=0.3)
    e1, _ = engine(mesh, compress_ratio=0.3)
    p0 = e0.run_round(by_id, sel, active, state, 0)
    p1 = e1.run_round(by_id, sel, active, state, 0)
    q0 = e0.run_round(by_id, sel, p0[0], p0[1], 1)
    q1 = e1.run_round(by_id, sel, p1[0], p1[1], 1)
    report["compressed_allclose"] = (tree_close(p0[0], p1[0])
                                     and tree_close(q0[0], q1[0], rtol=5e-4,
                                                    atol=5e-4))
    report["compressed_uplink_equal"] = (e0.last_uplink_bytes
                                         == e1.last_uplink_bytes)

    # --- full SmartFreeze server: picks / losses / uplink / params ---
    def run_server(mesh):
        srv = SmartFreezeServer(model, clients, clients_per_round=4,
                                batch_size=32, rounds_per_stage=2, seed=0,
                                mesh=mesh, cache_tiers="all",
                                pace_kwargs=dict(min_rounds=99))
        out = srv.run(params, state, schedule=[2, 2])
        return out, srv

    out0, srv0 = run_server(None)
    out1, srv1 = run_server(mesh)
    report["server_picks_equal"] = ([r.selected for r in srv0.history]
                                    == [r.selected for r in srv1.history])
    report["server_uplink_equal"] = (
        [r.uplink_bytes for r in srv0.history]
        == [r.uplink_bytes for r in srv1.history])
    report["server_losses_allclose"] = bool(np.allclose(
        [r.loss for r in srv0.history], [r.loss for r in srv1.history],
        rtol=1e-4, atol=1e-4))
    report["server_params_allclose"] = tree_close(out0["params"],
                                                  out1["params"])
    report["server_vtime_equal"] = (out0["virtual_time"]
                                    == out1["virtual_time"])

    # --- sharded population: selection picks + cache-tier admission ---
    rng = np.random.RandomState(0)
    n = 64
    infos = {i: ClientInfo(i, float(rng.choice([1, 2, 4, 8])) * 2**30,
                           float(rng.choice([1e9, 5e9])),
                           int(rng.randint(32, 512)), float(rng.rand()))
             for i in range(n)}
    comm = rng.randint(0, 4, size=n)
    pop = ClientPopulation.from_infos(infos, community_id=comm,
                                      n_communities=4)
    pop_s = pop.shard(mesh)
    vs = VectorizedSelector(epsilon=0.2, seed=3)
    picks = vs.select_arrays(pop, 16, mem_required=1.5 * 2**30, round_idx=5)
    picks_s = vs.select_arrays(pop_s, 16, mem_required=1.5 * 2**30,
                               round_idx=5)
    report["population_picks_equal"] = bool(np.array_equal(picks, picks_s))
    rates = [4e3, 2e3, 1e3]
    report["admission_equal"] = bool(np.array_equal(
        assign_cache_tiers(pop, 1e8, rates),
        assign_cache_tiers(pop_s, 1e8, rates)))

    # --- N not divisible by the device count: replicated fallback ---
    pop61 = ClientPopulation.from_infos({i: infos[i] for i in range(61)})
    p61 = pop61.shard(mesh)
    report["nondiv_replicated"] = bool(
        p61.memory_bytes.sharding.is_fully_replicated)
    report["nondiv_admission_equal"] = bool(np.array_equal(
        assign_cache_tiers(pop61, 1e8, rates),
        assign_cache_tiers(p61, 1e8, rates)))

    print("JSON:" + json.dumps(report))


if __name__ == "__main__":
    main()
