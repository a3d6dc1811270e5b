"""The system under test for the hybrid LM family (Granite 4.0-H): one
``RoundEngine`` over the LM zoo's stage loss (``core/freezing.py
lm_round_loss_fn``), built from public constructors, over a pool of
``SimClient``s whose rows are token sequences. The frozen prefix runs
inside the round (the traffic's ``cache_tier`` is null). The round is
built and run at the configuration's ``matmul_precision``, unless the
caller has set one. ``compute_dtype`` is the
engine's own mixed-precision switch: the benchmark's runs leave it off,
and the control of ``bench/control.py`` turns it on."""
from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import numpy as np

from repro.configs.granite_4_0_h_micro import from_hf
from repro.core import freezing as fz
from repro.fl.client import SimClient
from repro.fl.engine import RoundEngine
from repro.models.transformer import LM
from repro.optim import sgd


def model_config(cfg: Dict):
    """The program's ``ArchConfig`` for the configuration file, float32."""
    return from_hf(cfg, name=cfg["name"],
                   num_freeze_blocks=cfg["num_freeze_blocks"],
                   param_dtype="float32", compute_dtype="float32")


def _stage(cfg: Dict, stage: int):
    model = LM(model_config(cfg))
    return model, fz.make_stage_plan(model.cfg, stage)


def check_layout(cfg: Dict, stage: int, frozen, active, state) -> None:
    """Raise unless the benchmark's trees have the program's layout and
    shapes for ``stage``."""
    model, plan = _stage(cfg, stage)

    def program_trees(key):
        fr, ac = fz.init_stage_active(model, model.init(key), plan, key)
        return fr, ac, {}

    want = jax.eval_shape(program_trees, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: (frozen, active, state))
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError(f"benchmark weights do not match the program's "
                         f"layout for {cfg['name']} stage {stage}")


class System:
    """The engine and the client pool of one cell."""

    def __init__(self, cfg: Dict, traffic: Dict, frozen, state,
                 pool_x: np.ndarray, pool_y: np.ndarray,
                 seeds: Sequence[int], mesh=None, compute_dtype=None):
        if traffic["cache_tier"] is not None:
            raise ValueError("the hybrid LM family runs its prefix in the "
                             "round: cache_tier must be null")
        model, plan = _stage(cfg, traffic["stage"])
        self.precision = (jax.config.jax_default_matmul_precision
                          or cfg["matmul_precision"])
        self.engine = RoundEngine(
            loss_fn=fz.lm_round_loss_fn(model, plan),
            optimizer=sgd(traffic["lr"]), frozen=frozen,
            batch_size=traffic["batch"], local_epochs=traffic["epochs"],
            clip_norm=traffic["clip_norm"], mesh=mesh,
            compute_dtype=compute_dtype)
        self.clients = {
            i: SimClient(client_id=i, data={"x": pool_x[i], "y": pool_y[i]},
                         memory_bytes=0.0, capability=1.0, seed=seeds[i])
            for i in range(len(pool_x))}

    def fill_cache(self) -> int:
        return 0

    def run_round(self, cohort: List[int], round_idx: int, params, state):
        with jax.default_matmul_precision(self.precision):
            p, s, losses = self.engine.run_round(self.clients, cohort,
                                                 params, state, round_idx)
        return p, s, [losses[c] for c in cohort]

    def close(self) -> None:
        self.engine = None
        self.clients = None
