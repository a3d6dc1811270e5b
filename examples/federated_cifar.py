"""The paper's testbed end-to-end: SmartFreeze vs vanilla FL on a synthetic
CIFAR-like task with 20 heterogeneous clients (Dirichlet non-IID, memory +
compute heterogeneity). Prints round-by-round accuracy, the stage-freeze
points, the Eq.(4) per-stage memory model — and the virtual clock: pass
``--policy deadline`` (or ``async``) to run the same experiment under
deadline-based partial aggregation or FedBuff-style buffered async, and
``--ckpt-dir`` / ``--resume`` to checkpoint every round and continue a
killed run bit-identically (loss, perturbation and selection series all
pick up where they left off; under ``async`` the in-flight dispatches are
not checkpointed, so a resumed run re-dispatches them — sync/deadline are
the bit-identical policies).

Run:  PYTHONPATH=src python examples/federated_cifar.py [--rounds-per-stage 8]
      PYTHONPATH=src python examples/federated_cifar.py \
          --policy deadline --ckpt-dir /tmp/fed_ck        # kill it mid-run
      PYTHONPATH=src python examples/federated_cifar.py \
          --policy deadline --ckpt-dir /tmp/fed_ck --resume
"""
import argparse
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import dataclasses
import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.data.partition import dirichlet_partition
from repro.data.synthetic import SyntheticVision
from repro.fl.client import make_client_fleet
from repro.fl.server import SmartFreezeServer, cnn_stage_memory_bytes
from repro.fl.sim import (AsyncBufferedAggregation, AvailabilityTrace,
                          DeadlineAggregation, FleetTimeModel)
from repro.launch.cache import use_compile_cache
from repro.models.cnn import CNN, CNNConfig


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds-per-stage", type=int, default=8)
    ap.add_argument("--clients", type=int, default=20)
    ap.add_argument("--policy", choices=["sync", "deadline", "async"],
                    default="sync")
    ap.add_argument("--dropout", type=float, default=0.0,
                    help="per-(client, round) mid-round dropout probability")
    ap.add_argument("--link-mbps", type=float, default=0.0,
                    help=">0: uplink rate in MB/s (payload time enters the clock)")
    ap.add_argument("--cache-tiers", choices=["f32", "all"], default="f32",
                    help="feature-cache admission ladder: f32-only (exact seed "
                         "behavior) or the full f32->fp16->int8 ladder")
    ap.add_argument("--compute-dtype", default=None,
                    help="e.g. bfloat16: mixed-precision local training with "
                         "f32 master params")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=1)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    sv = SyntheticVision(num_classes=10, image_size=16)
    train_data = sv.sample(3000, seed=1)
    test = sv.sample(500, seed=2)
    parts = dirichlet_partition(train_data["y"], args.clients, alpha=1.0, seed=0)
    clients = make_client_fleet(train_data, parts, scenario="low")

    cfg = CNNConfig("resnet_mini", "resnet", stage_sizes=(1, 1, 1),
                    stage_channels=(16, 32, 64))
    model = CNN(cfg)
    params, state = model.init(jax.random.PRNGKey(0))

    print("Eq.(4) stage memory model (batch 32):")
    for s in range(3):
        mb = cnn_stage_memory_bytes(model, s, 32) / 2**20
        print(f"  stage {s}: {mb:7.1f} MiB")

    def eval_fn(p, s, stage):
        logits, _ = model.apply(p, s, jnp.asarray(test["x"]), train=False)
        return float((jnp.argmax(logits, -1) == jnp.asarray(test["y"])).mean())

    policy = {"sync": "sync",
              "deadline": DeadlineAggregation(factor=1.5),
              "async": AsyncBufferedAggregation(buffer_size=4)}[args.policy]
    time_model = None
    if args.link_mbps > 0:
        time_model = FleetTimeModel.from_clients(
            clients, link_rates=[args.link_mbps * 1e6] * len(clients))
    availability = (AvailabilityTrace(p_dropout=args.dropout)
                    if args.dropout > 0 else None)
    mgr = CheckpointManager(args.ckpt_dir, async_save=False) if args.ckpt_dir else None

    srv = SmartFreezeServer(model, clients, clients_per_round=6, local_epochs=1,
                            batch_size=32, rounds_per_stage=args.rounds_per_stage,
                            aggregation=policy, time_model=time_model,
                            availability=availability,
                            cache_tiers=("f32",) if args.cache_tiers == "f32"
                            else "all",
                            cache_time_scale=args.cache_tiers != "f32",
                            compute_dtype=args.compute_dtype,
                            pace_kwargs=dict(min_rounds=4, mu=2, slope_lambda=2e-2))
    out = srv.run(params, state, eval_fn=eval_fn, eval_every=2,
                  ckpt_manager=mgr, ckpt_every=args.ckpt_every if mgr else 0,
                  resume=args.resume)
    print(f"\n{out['rounds']} rounds, {out['virtual_time']:.2e} virtual seconds "
          f"({args.policy}):")
    for rr in out["history"]:
        acc = f" acc={rr.test_acc:.3f}" if rr.test_acc is not None else ""
        frz = "  << FROZEN" if rr.frozen else ""
        drop = f" -{len(rr.dropped)}" if rr.dropped else ""
        print(f"  r{rr.round_idx:3d} stage{rr.stage} t={rr.virtual_time:8.2e}s "
              f"loss={rr.loss:.3f}{drop}{acc}{frz}")


if __name__ == "__main__":
    main()
