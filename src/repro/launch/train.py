"""End-to-end progressive federated training driver.

Runs SmartFreeze on any ``--arch``: per stage, build the (frozen, active)
split + output module, run federated rounds (pods = cross-silo clients; on
CPU this is a 1-pod debug mesh), feed the pace controller with the aggregated
active block each round, freeze on convergence, grow, repeat.

Round orchestration goes through ``fl/sim.py``'s ``FederatedLoop`` — the
same virtual-time loop the CNN servers and baselines drive — with pods as
the "clients". Checkpoints (atomic/async) every ``--ckpt-every`` rounds now
carry the pace-controller window and the data RNG stream alongside the
merged params, so ``--resume`` continues the perturbation series and data
order mid-stage instead of restarting the stage.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --reduced \
      --steps 40 --batch 8 --seq 128
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --reduced \
      --steps 8 --batch 4 --seq 64 --pods 8 --mesh-clients 8
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.checkpoint import CheckpointManager
from repro.core import freezing
from repro.core.pace import PaceController
from repro.data.synthetic import make_lm_batch
from repro.fl.sim import (FederatedLoop, pack_rng_state, tree_like,
                          unpack_rng_state)
from repro.launch.cache import use_compile_cache
from repro.models.transformer import build
from repro.optim import adamw, sgd, warmup_cosine


def train(arch: str, *, reduced: bool = True, steps: int = 40, batch: int = 8,
          seq: int = 128, local_steps: int = 1, num_pods: int = 1,
          lr: float = 3e-3, ckpt_dir: Optional[str] = None,
          ckpt_every: int = 20, resume: bool = False, remat: bool = False,
          d_model: int = 0, num_layers: int = 0, log_every: int = 5,
          pace_kwargs: Optional[dict] = None, seed: int = 0,
          compute_dtype: Optional[str] = None,
          mesh_clients: int = 0, use_pallas: bool = False) -> dict:
    cfg = configs.get(arch)
    mesh = None
    if mesh_clients and mesh_clients > 1:
        # client-axis mesh: the pod dimension (the LM loop's cross-silo
        # "clients") partitions across devices; make_fed_round_step's
        # vmap-over-pods then runs SPMD under GSPMD with replicated params.
        # Pods that don't divide the axis fall back to single-device
        # placement (the make_rules divisibility discipline). Fewer visible
        # devices than requested raises in make_client_mesh.
        from repro.launch.mesh import make_client_mesh
        mesh = make_client_mesh(mesh_clients)
        if num_pods % mesh_clients != 0:
            print(f"--mesh-clients: {num_pods} pods do not divide the "
                  f"{mesh_clients}-device client axis; running replicated")
            mesh = None
    if reduced:
        over = {}
        if d_model:
            over["d_model"] = d_model
        if num_layers:
            over["num_layers"] = num_layers
        cfg = cfg.reduced(**over)
    if compute_dtype:
        # mixed-precision tier knob: bf16 forward/backward per pod while the
        # Eq. 1 aggregation and checkpoint stream keep the param dtype
        cfg = dataclasses.replace(cfg, compute_dtype=compute_dtype)
    if use_pallas:
        # route GQA full-sequence attention through the Pallas flash kernel
        # (kernels/flash_attention.py; interpret mode off-TPU). Roofline
        # selection rationale: launch/roofline.py ranks attention as the
        # top compute-bound hot path at LM scale. XLA stays the default.
        if cfg.attention != "gqa":
            raise SystemExit("--use-pallas: only the GQA attention flavour "
                             f"has a Pallas kernel (arch uses "
                             f"{cfg.attention!r})")
        cfg = dataclasses.replace(cfg, attention_impl="pallas")
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    T = cfg.num_freeze_blocks
    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None

    rng = np.random.RandomState(seed)
    start_stage, start_in_stage = 0, 0
    restored_pace = None
    restored_active = None
    restored_global = None
    if resume and mgr is not None:
        try:
            ck = mgr.restore()
            meta = ck["metadata"]
            tree = ck["tree"]
            saved = tree.get("params", tree)  # legacy ckpts stored bare params
            params = jax.tree.map(lambda a, b: jnp.asarray(b, a.dtype), params,
                                  saved)
            if "rng" in tree:
                rng = unpack_rng_state(tree["rng"])
            restored_pace = tree.get("pace")
            restored_active = tree.get("active")  # incl. the op module
            restored_global = meta.get("global_round")
            start_stage, start_in_stage = meta["stage"], meta["round"] + 1
            if meta.get("frozen"):
                # checkpoint landed on a pace-freeze round: params already
                # carry that stage's merge — continue with the next stage
                start_stage, start_in_stage = start_stage + 1, 0
                restored_pace = restored_active = None
            print(f"resumed from stage {start_stage} round {start_in_stage}")
        except FileNotFoundError:
            pass

    history = []
    rounds_per_stage = max(steps // T, 1)
    if start_in_stage >= rounds_per_stage:
        # checkpoint landed on a stage's final round: params already carry
        # the finished stage's merge — continue with the next stage
        start_stage, start_in_stage = start_stage + 1, 0
    # prefer the checkpointed global index: stages frozen early ran fewer
    # than rounds_per_stage rounds, so recomputing from stage*rps drifts
    global_round = (restored_global + 1 if restored_global is not None
                    else start_stage * rounds_per_stage + start_in_stage)

    for stage in range(start_stage, T):
        plan = freezing.make_stage_plan(cfg, stage)
        frozen, active = freezing.init_stage_active(
            model, params, plan, jax.random.PRNGKey(seed + 100 + stage))
        opt = sgd(lr)
        step_fn = jax.jit(freezing.make_fed_round_step(
            model, plan, opt, num_pods=num_pods, local_steps=local_steps,
            remat=remat))
        pace = PaceController(**(pace_kwargs or dict(
            min_rounds=max(rounds_per_stage // 2, 3), mu=2,
            slope_lambda=5e-3)))
        r0 = start_in_stage if stage == start_stage else 0
        if r0 and restored_pace is not None:
            pace.load_state_dict(restored_pace)
            restored_pace = None
        if r0 and restored_active is not None:
            # merged params don't carry the op module — restore the full
            # active tree so mid-stage resume keeps its trained state
            active = tree_like(active, restored_active)
            restored_active = None
        t_stage = time.time()
        box = {"active": active, "stage_round": r0}

        def train_fn(cohort, r, sequential=None, _box=box, _step=step_fn,
                     _frozen=frozen):
            data = make_lm_batch(cfg, num_pods * local_steps * batch, seq,
                                 seed=rng.randint(1 << 30))
            fed = {k: jnp.asarray(v).reshape(
                (num_pods, local_steps, batch) + v.shape[1:])
                for k, v in data.items()}
            if mesh is not None:
                from repro.dist.sharding import shard_cohort
                fed = shard_cohort(mesh, fed)
            w = jnp.ones((num_pods,), jnp.float32)
            _box["active"], metrics = _step(_box["active"], _frozen, fed, w)
            loss = float(metrics["loss"])
            return {pod: loss for pod in cohort}

        def on_round(rec, _box=box, _pace=pace, _stage=stage):
            r = _box["stage_round"]
            loss = next(iter(rec.losses.values())) if rec.losses else float("nan")
            p = _pace.observe(_box["active"]["runs"])
            history.append({"stage": _stage, "round": r, "loss": loss,
                            "perturbation": p})
            if r % log_every == 0:
                print(f"stage {_stage} round {r:3d} loss {loss:.4f} "
                      f"P={p if p is None else round(p, 4)}")
            freeze = _pace.should_freeze()
            if mgr and (rec.round_idx + 1) % ckpt_every == 0:
                merged = freezing.merge_stage_params(model, params, plan,
                                                     _box["active"])
                mgr.save(rec.round_idx,
                         {"params": merged, "active": _box["active"],
                          "pace": _pace.state_dict(),
                          "rng": pack_rng_state(rng)},
                         metadata={"stage": _stage, "round": r,
                                   "global_round": rec.round_idx,
                                   "frozen": bool(freeze),
                                   "compute_dtype": cfg.compute_dtype})
            _box["stage_round"] = r + 1
            if freeze:
                print(f"stage {_stage} frozen by pace controller at round {r}")
            return freeze

        loop = FederatedLoop(select_fn=lambda r, avail: avail,
                             train_fn=train_fn,
                             client_ids=list(range(num_pods)),
                             on_round=on_round)
        done = loop.run(rounds_per_stage - r0, start_round=global_round)
        global_round += len(done)
        params = freezing.merge_stage_params(model, params, plan, box["active"])
        print(f"stage {stage} done in {time.time() - t_stage:.0f}s")

    if mgr:
        mgr.save(global_round, {"params": params,
                                "rng": pack_rng_state(rng)},
                 metadata={"stage": T - 1, "round": rounds_per_stage,
                           "global_round": global_round,
                           "compute_dtype": cfg.compute_dtype})
        mgr.wait()
    return {"params": params, "history": history, "config": cfg}


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--d-model", type=int, default=0)
    ap.add_argument("--num-layers", type=int, default=0)
    ap.add_argument("--local-steps", type=int, default=1)
    ap.add_argument("--pods", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--compute-dtype", default=None,
                    help="override the arch's compute dtype "
                         "(e.g. bfloat16 / float32)")
    ap.add_argument("--mesh-clients", type=int, default=0,
                    help="shard the pod (client) axis over this many "
                         "devices (launch.mesh.make_client_mesh); 0 = "
                         "single-device. On CPU, force host devices with "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    ap.add_argument("--use-pallas", action="store_true",
                    help="run GQA attention through the Pallas flash "
                         "kernel (kernels/); default keeps the XLA path")
    a = ap.parse_args()
    out = train(a.arch, reduced=a.reduced, steps=a.steps, batch=a.batch,
                seq=a.seq, local_steps=a.local_steps, num_pods=a.pods,
                lr=a.lr, ckpt_dir=a.ckpt_dir, resume=a.resume, remat=a.remat,
                d_model=a.d_model, num_layers=a.num_layers,
                compute_dtype=a.compute_dtype, mesh_clients=a.mesh_clients,
                use_pallas=a.use_pallas)
    losses = [h["loss"] for h in out["history"]]
    if losses:
        print(f"finished: {len(losses)} rounds, "
              f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    else:
        print("finished: nothing left to run (checkpoint already complete)")


if __name__ == "__main__":
    main()
