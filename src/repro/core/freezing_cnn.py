"""Progressive stage training for the CNN repro models (paper testbed).

Faithful to §IV-A: the stage-t submodel is [stem?, stages 0..t, output
module]; suffix stages DO NOT EXIST yet (model growth). Frozen prefix runs in
eval mode (BN running stats) under stop_gradient; only stage t (+stem at t=0)
and the output module are differentiated/optimized.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core import output_module as op_mod
from repro.models.cnn import CNN, softmax_xent
from repro.models.module import PFac, Params
from repro.optim import Optimizer, apply_updates, clip_by_global_norm


def split_cnn_params(model: CNN, params: Params, stage: int
                     ) -> Tuple[Params, Params]:
    n_stages = len(model.cfg.stage_sizes)
    frozen: Params = {"stages": {}}
    active: Params = {"stages": {}}
    if model.cfg.kind == "resnet":
        (active if stage == 0 else frozen)["stem"] = params["stem"]
    for i in range(stage):
        frozen["stages"][f"stage{i}"] = params["stages"][f"stage{i}"]
    active["stages"][f"stage{stage}"] = params["stages"][f"stage{stage}"]
    if stage == n_stages - 1:
        active["fc"] = params["fc"]
    return frozen, active


def merge_cnn_params(model: CNN, params: Params, stage: int, active: Params) -> Params:
    new = {k: v for k, v in params.items()}
    new["stages"] = dict(params["stages"])
    if "stem" in active:
        new["stem"] = active["stem"]
    new["stages"][f"stage{stage}"] = active["stages"][f"stage{stage}"]
    if "fc" in active:
        new["fc"] = active["fc"]
    return new


def init_cnn_stage_active(model: CNN, params: Params, stage: int, rng, *,
                          op_kind: str = "conv") -> Tuple[Params, Params]:
    """op_kind: conv (paper) | fc_only (ablation) | none (final stage)."""
    frozen, active = split_cnn_params(model, params, stage)
    n_stages = len(model.cfg.stage_sizes)
    if stage < n_stages - 1:
        fac = PFac(rng, dtype=jnp.float32)
        if op_kind == "conv":
            active["op"] = op_mod.cnn_op_init(fac.sub("op"), model.cfg, stage)
        elif op_kind == "fc_only":
            active["op"] = op_mod.cnn_fc_only_init(fac.sub("op"), model.cfg, stage)
    return frozen, active


def cnn_prefix_features(model: CNN, frozen: Params, bn_state: Params,
                        x: jnp.ndarray, stage: int) -> jnp.ndarray:
    """Forward of the frozen prefix only (stem + stages [0, stage)), eval
    mode, stop-gradient boundary. Within a stage the prefix params AND its
    BN running stats are fixed, so this is a pure function of ``x`` — the
    round engine computes it once per (client, stage) and caches the result
    as a fixed feature extractor (NeuLite/ProFL-style). Stage 0 has no
    frozen prefix: the identity is returned."""
    if stage == 0:
        return x
    with jax.named_scope("prefix"):
        h = x
        if model.cfg.kind == "resnet":
            h, _ = model.stem(frozen, bn_state, h, train=False)
        h, _ = model.run_stages(frozen, bn_state, h, 0, stage, train=False)
        return jax.lax.stop_gradient(h)


def cnn_stage_forward_from_features(model: CNN, active: Params,
                                    bn_state: Params, h: jnp.ndarray,
                                    stage: int, *, op_kind: str = "conv",
                                    train: bool = True):
    """Active-suffix forward: consumes frozen-prefix features (or raw images
    at stage 0) and runs active stage (+stem at stage 0) and the head/output
    module. ``cnn_stage_forward`` composes prefix+suffix, so cached-feature
    training is numerically identical to full recompute by construction."""
    cfg = model.cfg
    n_stages = len(cfg.stage_sizes)
    if stage == 0 and cfg.kind == "resnet":
        h, bn_state = model.stem(active, bn_state, h, train=train)
    h, bn_state = model.run_stages(active, bn_state, h, stage, stage + 1,
                                   train=train)
    if stage == n_stages - 1:
        logits = model.head(active, h)
    elif op_kind == "fc_only":
        logits = op_mod.cnn_fc_only_apply(active["op"], h)
    else:
        logits = op_mod.cnn_op_apply(active["op"], h, cfg, stage)
    return logits, bn_state


def cnn_stage_forward(model: CNN, frozen: Params, active: Params,
                      bn_state: Params, x: jnp.ndarray, stage: int, *,
                      op_kind: str = "conv", train: bool = True):
    h = cnn_prefix_features(model, frozen, bn_state, x, stage)
    return cnn_stage_forward_from_features(model, active, bn_state, h, stage,
                                           op_kind=op_kind, train=train)


def cnn_stage_loss_fn(model: CNN, stage: int, *, op_kind: str = "conv"):
    def loss_fn(active, frozen, bn_state, batch):
        logits, new_state = cnn_stage_forward(model, frozen, active, bn_state,
                                              batch["x"], stage, op_kind=op_kind)
        return softmax_xent(logits, batch["y"]), new_state

    return loss_fn


def cnn_cached_stage_loss_fn(model: CNN, stage: int, *, op_kind: str = "conv"):
    """Stage loss over pre-extracted frozen-prefix features: ``batch["x"]``
    holds cached activations instead of images; the frozen tree is unused."""
    def loss_fn(active, frozen, bn_state, batch):
        logits, new_state = cnn_stage_forward_from_features(
            model, active, bn_state, batch["x"], stage, op_kind=op_kind)
        return softmax_xent(logits, batch["y"]), new_state

    return loss_fn


def make_cnn_stage_step(model: CNN, stage: int, optimizer: Optimizer, *,
                        op_kind: str = "conv", clip_norm: float = 10.0):
    loss_fn = cnn_stage_loss_fn(model, stage, op_kind=op_kind)

    def step(active, frozen, bn_state, opt_state, batch):
        (loss, new_bn), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            active, frozen, bn_state, batch)
        grads, _ = clip_by_global_norm(grads, clip_norm)
        ups, opt_state = optimizer.update(grads, opt_state, active)
        active = apply_updates(active, ups)
        return active, new_bn, opt_state, loss

    return jax.jit(step)
