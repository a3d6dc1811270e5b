"""The system under test for the CNN family: one ``RoundEngine`` built the
way ``SmartFreezeServer`` builds it for a stage, from public constructors,
over a pool of ``SimClient``s. ``compute_dtype`` is the engine's own
mixed-precision switch: the benchmark's runs leave it off, and the control
of ``bench/control.py`` turns it on."""
from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import numpy as np

from repro.core import freezing_cnn as fz
from repro.fl.client import SimClient
from repro.fl.engine import RoundEngine
from repro.models.cnn import CNN, CNNConfig
from repro.optim import sgd


def model_config(cfg: Dict) -> CNNConfig:
    return CNNConfig(cfg["name"], cfg["kind"], num_classes=cfg["num_classes"],
                     stage_sizes=tuple(cfg["stage_sizes"]),
                     stage_channels=tuple(cfg["stage_channels"]),
                     in_channels=cfg["in_channels"],
                     num_freeze_blocks=cfg["num_freeze_blocks"],
                     bn_momentum=cfg["bn_momentum"])


def check_layout(cfg: Dict, stage: int, frozen, active, state) -> None:
    """Raise unless the benchmark's trees have the program's layout and
    shapes for ``stage``."""
    model = CNN(model_config(cfg))

    def program_trees(key):
        params, st = model.init(key)
        fr, ac = fz.init_cnn_stage_active(model, params, stage, key)
        return fr, ac, st

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
        stage = traffic["stage"]
        model = CNN(model_config(cfg))
        cached_loss = feature_fn = None
        if stage > 0:
            cached_loss = fz.cnn_cached_stage_loss_fn(model, stage)
            feature_fn = (lambda x: fz.cnn_prefix_features(
                model, frozen, state, x, stage))
        self.engine = RoundEngine(
            loss_fn=fz.cnn_stage_loss_fn(model, stage),
            optimizer=sgd(traffic["lr"]), frozen=frozen,
            cached_loss_fn=cached_loss, feature_fn=feature_fn,
            batch_size=traffic["batch"], local_epochs=traffic["epochs"],
            clip_norm=traffic["clip_norm"], mesh=mesh,
            compute_dtype=compute_dtype)
        self.clients = {
            i: SimClient(client_id=i, data={"x": pool_x[i], "y": pool_y[i]},
                         memory_bytes=0.0, capability=1.0, seed=seeds[i])
            for i in range(len(pool_x))}
        tier = traffic["cache_tier"]
        self.use_cache = ({i: tier for i in self.clients}
                          if tier is not None else None)

    def fill_cache(self) -> int:
        """Push every client's shard through the frozen prefix into the
        engine's feature cache; returns the bytes cached."""
        if self.use_cache:
            for i, c in self.clients.items():
                self.engine.features_for(c, self.use_cache[i])
        return self.engine.cache_nbytes()

    def run_round(self, cohort: List[int], round_idx: int, params, state):
        p, s, losses = self.engine.run_round(self.clients, cohort, params,
                                             state, round_idx,
                                             use_cache=self.use_cache)
        return p, s, [losses[c] for c in cohort]

    def close(self) -> None:
        self.engine = None
        self.clients = None
