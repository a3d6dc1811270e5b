#!/usr/bin/env python3
"""Smoke run of SmartFreeze's main path on a TPU, at ResNet-18 width.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four-chip client mesh only

One chip runs two phases:

  1. ``SmartFreezeServer.run`` walks the paper's ResNet-18 (channels
     64-512, 4 freeze blocks) through all four stages on CIFAR-shaped
     synthetic data (16 Dirichlet(0.5) clients, 8 per round, batch 32, one
     local epoch, 2 rounds per stage): stage 0 trains with full backward,
     stages 1-3 on the f32 frozen-prefix cache. Per round it prints stage,
     wall seconds, mean loss and cache bytes, then the chip's peak memory.
     It checks that every loss and output is finite and on the chip, and
     that at a cached stage the logits from the cached prefix match the
     recompute path.
  2. One compressed round (``compress_ratio=0.1``) with the Pallas cohort
     fold (kernels/sparse_agg.py, compiled by Mosaic) against the same
     round with the XLA scatter.

``--chips 4`` runs only the sharded round: one ``RoundEngine`` round of
ResNet-18 over a 4-device client mesh, at stage 0 and at a cached stage,
8 clients (2 per chip), against the same rounds on one device.

The last line of stdout is ``{"ok": true, "device": {...}}``, printed only
when every check passed. Without a TPU the script exits non-zero before any
work. The phases are plain functions of their sizes, so tests run them on
the CPU at tiny width.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import freezing_cnn as fz  # noqa: E402
from repro.data.partition import dirichlet_partition  # noqa: E402
from repro.data.synthetic import SyntheticVision  # noqa: E402
from repro.fl.client import make_client_fleet  # noqa: E402
from repro.fl.engine import RoundEngine  # noqa: E402
from repro.fl.server import SmartFreezeServer  # noqa: E402
from repro.launch.cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_client_mesh  # noqa: E402
from repro.models.cnn import CNN, RESNET18, CNNConfig  # noqa: E402
from repro.optim import sgd  # noqa: E402

# cached vs recompute logits, at the backend's default precision. CPU: the
# f32 bound of tests/test_engine.py. TPU: each conv rounds its f32 inputs to
# bf16 (unit roundoff 2^-9 ~ 2e-3), and the two programs are compiled apart,
# so an activation that sits near a bf16 rounding boundary can round either
# way; 1e-2 is five units, while a stale or wrong-stage cache is off by O(1)
CACHE_TOL = {"cpu": 1e-5, "tpu": 1e-2}
# Pallas fold vs XLA scatter: same products, summed in another order
FOLD_TOL = 1e-5
# sharded vs one-device round, both at "highest" (f32) precision to keep
# bf16 input rounding out of the difference. CPU: the bound of
# tests/_shard_driver.py. TPU: the mesh compiles 2 clients per program and one device 8, so the
# batched convs sum in different orders, and ResNet-18's local steps amplify
# that; at stage 0 on a v5e the BN running stats came out 5.3e-4 apart, the
# params 8.4e-5. 2e-3 keeps a 4x margin
SHARD_TOL = {"cpu": 3e-4, "tpu": 2e-3}


def make_world(cfg: CNNConfig, *, clients: int, samples: int,
               image_size: int, alpha: float, seed: int):
    """Model, seeded random weights and a Dirichlet(alpha) fleet over
    CIFAR-shaped synthetic images."""
    sv = SyntheticVision(num_classes=cfg.num_classes, image_size=image_size,
                         seed=seed)
    data = sv.sample(samples, seed=seed + 1)
    parts = dirichlet_partition(data["y"], clients, alpha=alpha, seed=seed)
    fleet = make_client_fleet(data, parts, scenario="low", seed=seed)
    model = CNN(cfg)
    params, state = model.init(jax.random.PRNGKey(seed))
    return model, fleet, params, state


def max_rel_diff(a, b) -> float:
    """Largest |a - b| over all leaves, relative to max(1, |b|_inf)."""
    worst = 0.0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x = np.asarray(x, np.float64)
        y = np.asarray(y, np.float64)
        scale = max(1.0, float(np.max(np.abs(y)))) if y.size else 1.0
        worst = max(worst, float(np.max(np.abs(x - y))) / scale
                    if y.size else 0.0)
    return worst


def _all_finite(tree) -> bool:
    return all(bool(np.isfinite(np.asarray(x)).all())
               for x in jax.tree.leaves(tree))


def _on_backend(tree) -> bool:
    want = jax.default_backend()
    return all(d.platform == want for x in jax.tree.leaves(tree)
               for d in x.devices())


def _engine(model, stage, frozen, state, *, mesh=None, **kw) -> RoundEngine:
    """The round engine ``SmartFreezeServer`` builds for ``stage``."""
    cached = feat = None
    if stage > 0:
        cached = fz.cnn_cached_stage_loss_fn(model, stage)
        feat = (lambda x: fz.cnn_prefix_features(model, frozen, state, x,
                                                 stage))
    return RoundEngine(loss_fn=fz.cnn_stage_loss_fn(model, stage),
                       optimizer=sgd(0.05), frozen=frozen,
                       cached_loss_fn=cached, feature_fn=feat,
                       batch_size=32, local_epochs=1, mesh=mesh, **kw)


def peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def phase_stages(cfg: CNNConfig = RESNET18, *, clients: int = 16,
                 samples: int = 4096, image_size: int = 32,
                 per_round: int = 8, batch_size: int = 32,
                 rounds_per_stage: int = 2, alpha: float = 0.5,
                 seed: int = 0) -> list:
    """SmartFreeze through every stage; returns the failed checks."""
    model, fleet, params, state = make_world(
        cfg, clients=clients, samples=samples, image_size=image_size,
        alpha=alpha, seed=seed)
    n_stages = len(cfg.stage_sizes)
    srv = SmartFreezeServer(model, fleet, clients_per_round=per_round,
                            batch_size=batch_size, local_epochs=1,
                            rounds_per_stage=rounds_per_stage, seed=seed)
    marks = [time.perf_counter()]

    def stamp(merged, bn_state, stage):
        # called by the server once per round, after the round's update
        jax.block_until_ready(merged)
        marks.append(time.perf_counter())

    out = srv.run(params, state, eval_fn=stamp, eval_every=1,
                  schedule=[rounds_per_stage] * n_stages)
    jax.block_until_ready(out["params"])
    fails = []
    for r, t0, t1 in zip(srv.history, marks, marks[1:]):
        print(f"round {r.round_idx} stage {r.stage} wall_s {t1 - t0!r} "
              f"loss {r.loss!r} cache_bytes {r.cache_bytes}")
        if not np.isfinite(r.loss):
            fails.append(f"round {r.round_idx}: loss {r.loss}")
    if [r.stage for r in srv.history] != [
            s for s in range(n_stages) for _ in range(rounds_per_stage)]:
        fails.append("stages walked: "
                     f"{[r.stage for r in srv.history]}")
    if not any(r.cache_bytes for r in srv.history if r.stage > 0):
        fails.append("no cached stage held a feature cache")
    outputs = (out["params"], out["state"])
    if not _all_finite(outputs):
        fails.append("non-finite trained params or BN state")
    if not _on_backend(outputs):
        fails.append(f"outputs not on {jax.default_backend()}")

    # cached prefix vs recompute at the last stage, on the trained model,
    # through the engine's own feature cache
    stage = n_stages - 1
    frozen, active = fz.init_cnn_stage_active(
        model, out["params"], stage, jax.random.PRNGKey(seed + stage))
    client = fleet[0]
    n = min(batch_size, client.num_samples)
    x = jnp.asarray(client.data["x"][:n])

    def cached_and_full():
        # a fresh engine, so its feature cache is built at the ambient
        # matmul precision
        eng = _engine(model, stage, frozen, out["state"])
        feats = jnp.asarray(eng.features_for(client, "f32").values[:n])
        cached, _ = jax.jit(lambda a, s, h: fz.cnn_stage_forward_from_features(
            model, a, s, h, stage))(active, out["state"], feats)
        full, _ = jax.jit(lambda a, f, s, xx: fz.cnn_stage_forward(
            model, f, a, s, xx, stage))(active, frozen, out["state"], x)
        return cached, full

    cached, full = cached_and_full()
    d = max_rel_diff(cached, full)
    tol = CACHE_TOL[jax.default_backend()]
    print(f"cached_vs_recompute stage {stage} max_rel_diff {d!r} tol {tol!r}")
    if not d <= tol:
        fails.append(f"cached logits differ from recompute by {d}")
    # the same comparison with f32 products, for reference
    with jax.default_matmul_precision("highest"):
        d_hi = max_rel_diff(*cached_and_full())
    print(f"cached_vs_recompute stage {stage} precision highest "
          f"max_rel_diff {d_hi!r}")
    if not _on_backend(cached):
        fails.append(f"cached logits not on {jax.default_backend()}")
    print(f"peak_bytes_in_use {peak_bytes()}")
    return fails


def phase_pallas_fold(cfg: CNNConfig = RESNET18, *, clients: int = 8,
                      samples: int = 2048, image_size: int = 32,
                      compress_ratio: float = 0.1, seed: int = 0) -> list:
    """One compressed stage-0 round, Pallas cohort fold vs XLA scatter;
    returns the failed checks."""
    model, fleet, params, state = make_world(
        cfg, clients=clients, samples=samples, image_size=image_size,
        alpha=0.5, seed=seed)
    by_id = {c.client_id: c for c in fleet}
    frozen, active = fz.init_cnn_stage_active(model, params, 0,
                                              jax.random.PRNGKey(seed))
    outs = {}
    for use_pallas in (True, False):
        eng = _engine(model, 0, frozen, state, compress_ratio=compress_ratio,
                      use_pallas=use_pallas)
        t0 = time.perf_counter()
        p, s, losses = eng.run_round(by_id, sorted(by_id), active, state, 0)
        jax.block_until_ready((p, s))
        outs[use_pallas] = (p, s, losses, eng.ef_state())
        print(f"compressed_round use_pallas {use_pallas} wall_s "
              f"{time.perf_counter() - t0!r} uplink_bytes "
              f"{eng.last_uplink_bytes}")
    (pp, sp, lp, ep), (px, sx, lx, ex) = outs[True], outs[False]
    d = max_rel_diff((pp, sp), (px, sx))
    dr = max_rel_diff([ep[k] for k in sorted(ep)], [ex[k] for k in sorted(ex)])
    print(f"pallas_vs_xla_fold max_rel_diff {d!r} residuals {dr!r} "
          f"tol {FOLD_TOL!r}")
    fails = []
    if not (d <= FOLD_TOL and dr <= FOLD_TOL):
        fails.append(f"Pallas fold differs from XLA: {d}, residuals {dr}")
    dl = max_rel_diff(list(lp.values()), list(lx.values()))
    if not dl <= FOLD_TOL:
        fails.append(f"per-client losses differ between fold paths: {dl}")
    if not _all_finite((pp, sp, list(lp.values()))):
        fails.append("non-finite compressed round")
    return fails


def phase_sharded(cfg: CNNConfig = RESNET18, *, chips: int = 4,
                  clients: int = 8, samples: int = 2048,
                  image_size: int = 32, cached_stage: int = 2,
                  seed: int = 0) -> list:
    """One round over a ``chips``-device client mesh vs one device, at
    stage 0 and at ``cached_stage``; returns the failed checks."""
    mesh = make_client_mesh(chips)
    model, fleet, params, state = make_world(
        cfg, clients=clients, samples=samples, image_size=image_size,
        alpha=0.5, seed=seed)
    by_id = {c.client_id: c for c in fleet}
    cids = sorted(by_id)
    fails = []
    tol = SHARD_TOL[jax.default_backend()]
    # f32 products on both sides: see SHARD_TOL
    with jax.default_matmul_precision("highest"):
        for stage in (0, cached_stage):
            frozen, active = fz.init_cnn_stage_active(model, params, stage,
                                                      jax.random.PRNGKey(seed))
            cache = {cid: "f32" for cid in cids} if stage else None
            res = {}
            for name, m in (("mesh", mesh), ("single", None)):
                eng = _engine(model, stage, frozen, state, mesh=m)
                t0 = time.perf_counter()
                p, s, losses = eng.run_round(by_id, cids, active, state, 1,
                                             use_cache=cache)
                jax.block_until_ready((p, s))
                res[name] = (p, s, [losses[c] for c in cids])
                print(f"sharded_round stage {stage} {name} wall_s "
                      f"{time.perf_counter() - t0!r}")
            (pm, sm, lm), (p1, s1, l1) = res["mesh"], res["single"]
            dp, ds, dl = (max_rel_diff(pm, p1), max_rel_diff(sm, s1),
                          max_rel_diff(lm, l1))
            print(f"sharded_vs_single stage {stage} params {dp!r} bn_state "
                  f"{ds!r} losses {dl!r} tol {tol!r}")
            if not max(dp, ds, dl) <= tol:
                fails.append(f"stage {stage}: sharded round differs from one "
                             f"device ({dp}, {ds}, {dl})")
            if not _all_finite(res):
                fails.append(f"stage {stage}: non-finite round")
            if not all(len(x.devices()) == chips
                       for x in jax.tree.leaves(pm)):
                fails.append(f"stage {stage}: aggregate not replicated on "
                             f"{chips} chips")
    return fails


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip client-mesh round")
    args = ap.parse_args(argv)
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"chip_smoke.py needs a TPU; JAX found {backend!r}")
    print(f"compile cache: {use_compile_cache()}")
    dev = jax.devices()[0]
    print(f"device {dev.device_kind} x{len(jax.devices())}")
    if args.chips == 4:
        fails = phase_sharded(chips=4)
    else:
        fails = phase_stages() + phase_pallas_fold()
    for f in fails:
        print(f"FAILED: {f}", file=sys.stderr)
    if fails:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
