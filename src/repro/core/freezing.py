"""Progressive training with layer freezing (paper §IV-A) for the LM zoo.

Stage t trains ONLY block t (layers [b_t, b_{t+1})) plus the output module;
the frozen prefix runs forward-only under a boundary ``stop_gradient``, so XLA
keeps no residuals for it and the optimizer holds no state for it — both
terms of the paper's Eq. (4) memory saving are structural here, visible in
``compiled.memory_analysis()`` of the stage step.

Parameter-tree mechanics: stacked scan leaves are *sliced* at block
boundaries into a frozen tree and an active tree; the stage forward stitches
them back together in execution order. zamba2's weight-tied shared-attention
sets stay in the active tree at every stage (tying spans blocks — DESIGN.md
§5); frozen-region occurrences contribute no gradient because of the boundary
stop_gradient.

``make_fed_round_step`` wraps the stage step into a federated round: pods are
cross-silo clients — broadcast, K local steps (lax.scan), dataset-weighted
parameter aggregation over the pod dimension (Eq. 1). ``lm_round_loss_fn``
gives the same stage loss in ``fl/engine.RoundEngine``'s signature, so an LM
stage trains in the fused round the CNNs run, on each client's own token
rows.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import output_module as op_mod
from repro.models.module import PFac, Params, axes_to_tree, slice_stack
from repro.models.transformer import LM, chunked_ce_loss, layer_apply, token_loss
from repro.optim import Optimizer, apply_updates, clip_by_global_norm


class StagePlan(NamedTuple):
    stage: int
    lo: int
    hi: int
    # the embedding is in the active tree: at stage 0, and at the last stage
    # when it is tied to the head (it then trains through the head alone)
    train_embed: bool
    final: bool  # last stage: real final_norm + head instead of output module
    # execution order: list of (region, kind, seg_idx, a, b) with a/b relative
    # to the segment start; region in {"frozen", "active"}
    runs: tuple


def make_stage_plan(cfg: ArchConfig, stage: Optional[int]) -> StagePlan:
    """stage=None means full-model (vanilla) training."""
    T = cfg.num_freeze_blocks
    if stage is None:
        stage, lo, hi = T - 1, 0, cfg.num_layers
        final, train_embed = True, True
        bounds = None
    else:
        bounds = cfg.block_boundaries()
        lo, hi = bounds[stage], bounds[stage + 1]
        final = stage == T - 1
        train_embed = stage == 0 or (final and cfg.tie_embeddings)
    runs = []
    pos = 0
    for si, (kind, n) in enumerate(cfg.segments()):
        s_lo, s_hi = pos, pos + n
        pos += n
        for region, r_lo, r_hi in (("frozen", 0, lo), ("active", lo, hi)):
            a, b = max(r_lo, s_lo), min(r_hi, s_hi)
            if a < b:
                runs.append((region, kind, si, a - s_lo, b - s_lo))
    return StagePlan(stage, lo, hi, train_embed, final, tuple(runs))


# ---------------------------------------------------------------------------
# Parameter splitting
# ---------------------------------------------------------------------------


def split_stage_params(model: LM, params: Params, plan: StagePlan
                       ) -> Tuple[Params, Params]:
    """Returns (frozen, active) partial trees. Both contain a 'runs' dict
    keyed by run index. Layers past plan.hi are NOT materialized (progressive
    growth: the model literally hasn't grown them yet)."""
    frozen: Params = {"runs": {}}
    active: Params = {"runs": {}}
    (active if plan.train_embed else frozen)["embed"] = params["embed"]
    if "frontend" in params:
        (active if plan.train_embed else frozen)["frontend"] = params["frontend"]
    for ri, (region, kind, si, a, b) in enumerate(plan.runs):
        tgt = active if region == "active" else frozen
        if kind == "shared_attn":
            continue  # tied sets handled below
        tgt["runs"][str(ri)] = slice_stack(params["segments"][str(si)], a, b)
    if "shared_attn" in params:
        active["shared_attn"] = params["shared_attn"]
    if plan.final:
        active["final_norm"] = params["final_norm"]
        if "head" in params:
            active["head"] = params["head"]
    return frozen, active


def split_stage_axes(model: LM, axes_tree: Dict, plan: StagePlan
                     ) -> Tuple[Dict, Dict]:
    """Like split_stage_params but for the logical-axes tree (leaves are
    tuples; slicing a layer range does not change a leaf's axes)."""
    frozen: Dict = {"runs": {}}
    active: Dict = {"runs": {}}
    (active if plan.train_embed else frozen)["embed"] = axes_tree["embed"]
    if "frontend" in axes_tree:
        (active if plan.train_embed else frozen)["frontend"] = axes_tree["frontend"]
    for ri, (region, kind, si, a, b) in enumerate(plan.runs):
        if kind == "shared_attn":
            continue
        tgt = active if region == "active" else frozen
        tgt["runs"][str(ri)] = axes_tree["segments"][str(si)]
    if "shared_attn" in axes_tree:
        active["shared_attn"] = axes_tree["shared_attn"]
    if plan.final:
        active["final_norm"] = axes_tree["final_norm"]
        if "head" in axes_tree:
            active["head"] = axes_tree["head"]
    return frozen, active


def merge_stage_params(model: LM, params: Params, plan: StagePlan,
                       active: Params) -> Params:
    """Write the trained active slices back into the full param tree."""
    new = jax.tree.map(lambda x: x, params)  # shallow copy per leaf
    if plan.train_embed:
        new["embed"] = active["embed"]
        if "frontend" in active:
            new["frontend"] = active["frontend"]
    for ri, (region, kind, si, a, b) in enumerate(plan.runs):
        if region != "active" or kind == "shared_attn":
            continue
        sl = active["runs"][str(ri)]

        def put(full, part):
            return full.at[a:b].set(part.astype(full.dtype))

        new["segments"][str(si)] = jax.tree.map(put, new["segments"][str(si)], sl)
    if "shared_attn" in active:
        new["shared_attn"] = active["shared_attn"]
    if plan.final:
        new["final_norm"] = active["final_norm"]
        if "head" in active:
            new["head"] = active["head"]
    return new


# ---------------------------------------------------------------------------
# Stage forward/loss
# ---------------------------------------------------------------------------


def _run(model: LM, h, run_params, kind: str, cfg: ArchConfig, *, remat: bool,
         remat_policy=None):
    causal = not cfg.is_encoder_only

    def one(hh, lp):
        hh, aux = layer_apply(lp, hh, cfg, kind, causal=causal)
        return hh, aux

    if remat and remat_policy is not None:
        body = jax.checkpoint(one, policy=remat_policy)
    elif remat:
        body = jax.checkpoint(one)
    else:
        body = one

    def scan_body(carry, lp):
        hh, aux = carry
        hh2, a = body(hh, lp)
        return (hh2, aux + a), None

    (h, aux), _ = jax.lax.scan(scan_body, (h, jnp.float32(0.0)), run_params)
    return h, aux


def prefix_is_static(plan: StagePlan) -> bool:
    """True when the frozen prefix is a fixed feature extractor for the whole
    stage — i.e. its outputs can be cached. False at stage 0 (the embedding
    trains, so 'prefix' outputs move every step) and when the prefix contains
    a weight-tied shared-attention segment (zamba2): those weights live in
    the ACTIVE tree at every stage and keep updating."""
    if plan.train_embed:
        return False
    return not any(kind == "shared_attn"
                   for region, kind, si, a, b in plan.runs
                   if region == "frozen")


def stage_prefix_features(model: LM, frozen: Params, active: Params,
                          batch: Dict, plan: StagePlan):
    """Embed + frozen-prefix forward only. Returns (hidden, aux_loss_so_far).

    The plan's runs list all frozen runs before any active run (the frozen
    region is layers [0, lo) and the active region [lo, hi)), so the prefix
    is a clean split point. When ``prefix_is_static(plan)`` the result is a
    pure function of the batch and can be cached across the stage's rounds
    — the round engine (fl/engine.py) exploits exactly that. Its ops carry
    the device scope ``prefix``."""
    from repro.dist.sharding import shard_batch

    cfg = model.cfg
    src = active if plan.train_embed else frozen
    with jax.named_scope("prefix"):
        h = shard_batch(model.embed(src, batch), batch_axes=cfg.batch_axes)
        aux_total = jnp.float32(0.0)
        for ri, (region, kind, si, a, b) in enumerate(plan.runs):
            if region == "active":
                break
            if kind == "shared_attn":
                sp = active["shared_attn"][str(_shared_idx(model, si))]
                h, aux = layer_apply(sp, h, cfg, kind,
                                     causal=not cfg.is_encoder_only)
            else:
                h, aux = _run(model, h, frozen["runs"][str(ri)], kind, cfg,
                              remat=False)
            aux_total = aux_total + aux
    return h, aux_total


def stage_forward_from_features(model: LM, active: Params, h, aux_total,
                                plan: StagePlan, *, remat: bool = True,
                                remat_policy=None):
    """Active-suffix forward from (possibly cached) prefix features. Applies
    the stop-gradient memory boundary (where layers are frozen: at stage 0
    the embedding trains through its lookup), the active runs, and the
    final-norm head or output module. Returns (hidden, head_w, aux_loss)."""
    cfg = model.cfg
    if plan.lo > 0:
        h = jax.lax.stop_gradient(h)  # memory boundary: no bwd into prefix
    for ri, (region, kind, si, a, b) in enumerate(plan.runs):
        if region != "active":
            continue
        if kind == "shared_attn":
            sp = active["shared_attn"][str(_shared_idx(model, si))]
            h, aux = layer_apply(sp, h, cfg, kind, causal=not cfg.is_encoder_only)
        else:
            h, aux = _run(model, h, active["runs"][str(ri)], kind, cfg,
                          remat=remat, remat_policy=remat_policy)
        aux_total = aux_total + aux
    if plan.final:
        from repro.models.layers import norm
        h = norm(active["final_norm"], h, cfg.norm, cfg.norm_eps)
        head_w = (active["embed"].T if cfg.tie_embeddings
                  else active["head"]["w"])
    else:
        h = op_mod.lm_op_hidden(active["op"], h, cfg)
        head_w = active["op"]["head"]["w"]
    return h, head_w, aux_total


def stage_forward(model: LM, frozen: Params, active: Params, batch: Dict,
                  plan: StagePlan, *, remat: bool = True, remat_policy=None):
    """Returns (hidden, head_w, aux_loss) — the head matmul is folded into the
    chunked CE loss so [B, S, V] logits are never materialized. Composes
    ``stage_prefix_features`` + ``stage_forward_from_features`` so the cached
    path is numerically identical to full recompute by construction."""
    h, aux = stage_prefix_features(model, frozen, active, batch, plan)
    return stage_forward_from_features(model, active, h, aux, plan,
                                       remat=remat, remat_policy=remat_policy)


def _shared_idx(model: LM, seg_idx: int) -> int:
    """Tied-set index for the shared_attn segment seg_idx."""
    occ = 0
    for i, (kind, n) in enumerate(model.cfg.segments()):
        if i == seg_idx:
            break
        if kind == "shared_attn":
            occ += 1
    return occ % max(model.cfg.num_shared_attn_sets, 1)


def stage_logits(model: LM, frozen: Params, active: Params, batch: Dict,
                 plan: StagePlan, *, remat: bool = True):
    """Full logits (tests / small models only)."""
    h, head_w, aux = stage_forward(model, frozen, active, batch, plan, remat=remat)
    return h @ head_w.astype(h.dtype), aux


def stage_loss_fn(model: LM, plan: StagePlan, *, remat: bool = True,
                  remat_policy=None):
    def loss_fn(active: Params, frozen: Params, batch: Dict) -> jnp.ndarray:
        h, head_w, aux = stage_forward(model, frozen, active, batch, plan,
                                       remat=remat, remat_policy=remat_policy)
        return chunked_ce_loss(h, head_w, batch, model.cfg) + 0.01 * aux

    return loss_fn


def lm_round_loss_fn(model: LM, plan: StagePlan):
    """The stage loss in ``RoundEngine``'s signature ``(active, frozen,
    state, batch) -> (loss, state)``: ``batch["x"]`` holds a client's token
    rows [b, S], each position predicts the next token (the last predicts
    none), and ``state`` is passed through (an LM holds no batch-norm
    state)."""
    loss_fn = stage_loss_fn(model, plan)

    def fn(active: Params, frozen: Params, state, batch: Dict):
        tokens = batch["x"]
        labels = jnp.concatenate(
            [tokens[:, 1:], jnp.full_like(tokens[:, :1], -1)], axis=1)
        return loss_fn(active, frozen, {"tokens": tokens,
                                        "labels": labels}), state

    return fn


def cached_stage_loss_fn(model: LM, plan: StagePlan, *, remat: bool = True,
                         remat_policy=None):
    """Stage loss over cached prefix features: the batch carries ``h0`` (the
    prefix output) and ``aux0`` (the prefix's frozen aux loss, a constant)
    alongside the usual label/mask keys; no frozen tree is consumed."""
    def loss_fn(active: Params, batch: Dict) -> jnp.ndarray:
        h, head_w, aux = stage_forward_from_features(
            model, active, batch["h0"], batch["aux0"], plan, remat=remat,
            remat_policy=remat_policy)
        return chunked_ce_loss(h, head_w, batch, model.cfg) + 0.01 * aux

    return loss_fn


def init_stage_active(model: LM, params: Params, plan: StagePlan, rng) -> Tuple[Params, Params]:
    """(frozen, active) with a freshly-initialized output module when needed."""
    frozen, active = split_stage_params(model, params, plan)
    if not plan.final:
        fac = PFac(rng, dtype=jnp.dtype(model.cfg.param_dtype))
        active["op"] = op_mod.lm_op_init(fac.sub("op"), model.cfg, plan.stage)
    return frozen, active


# ---------------------------------------------------------------------------
# Train steps
# ---------------------------------------------------------------------------


class TrainState(NamedTuple):
    active: Params
    frozen: Params
    opt_state: Any
    step: jnp.ndarray


def make_train_step(model: LM, plan: StagePlan, optimizer: Optimizer, *,
                    remat: bool = True, clip_norm: float = 1.0):
    """Centralized (single-cohort) stage train step."""
    loss_fn = stage_loss_fn(model, plan, remat=remat)

    def step(state: TrainState, batch: Dict):
        loss, grads = jax.value_and_grad(loss_fn)(state.active, state.frozen, batch)
        grads, gnorm = clip_by_global_norm(grads, clip_norm)
        ups, opt_state = optimizer.update(grads, state.opt_state, state.active)
        active = apply_updates(state.active, ups)
        return TrainState(active, state.frozen, opt_state, state.step + 1), \
            {"loss": loss, "grad_norm": gnorm}

    return step


def make_fed_round_step(model: LM, plan: StagePlan, local_opt: Optimizer, *,
                        num_pods: int, local_steps: int, remat: bool = True,
                        clip_norm: float = 1.0, constrain_podded=None,
                        remat_policy=None):
    """One federated round (Eq. 1) with pods as cross-silo clients.

    Inputs: global active params (no pod dim), frozen params (replicated),
    batch with leading dims [num_pods, local_steps, ...] (pod-sharded), and
    per-pod example weights [num_pods].

    Broadcast -> vmap(pod-local K-step SGD scan) -> weighted parameter
    average over the pod dim (the Eq. 1 all-reduce; GSPMD lowers the mean to
    a cross-pod collective because the pod dim is sharded on the "pod" axis).
    """
    loss_fn = stage_loss_fn(model, plan, remat=remat,
                            remat_policy=remat_policy)

    def local_train(active, frozen, batches):
        opt_state = local_opt.init(active)

        def one(carry, batch):
            act, ost = carry
            loss, grads = jax.value_and_grad(loss_fn)(act, frozen, batch)
            grads, _ = clip_by_global_norm(grads, clip_norm)
            ups, ost = local_opt.update(grads, ost, act)
            return (apply_updates(act, ups), ost), loss

        (active, _), losses = jax.lax.scan(one, (active, opt_state), batches)
        return active, jnp.mean(losses)

    def round_step(active: Params, frozen: Params, batch: Dict,
                   weights: jnp.ndarray):
        podded = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (num_pods,) + x.shape), active)
        if constrain_podded is not None:
            podded = constrain_podded(podded)
        podded, losses = jax.vmap(local_train, in_axes=(0, None, 0))(
            podded, frozen, batch)
        w = (weights / jnp.sum(weights)).astype(jnp.float32)

        def agg(x):
            return jnp.einsum("p,p...->...", w, x.astype(jnp.float32)).astype(x.dtype)

        new_active = jax.tree.map(agg, podded)
        return new_active, {"loss": jnp.sum(w * losses)}

    return round_step
