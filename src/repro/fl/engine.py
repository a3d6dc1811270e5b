"""Fused federated round engine: one compiled dispatch per cohort, plus a
frozen-prefix activation cache.

The seed simulator executed each round as ``K clients x E epochs x B
minibatches`` separate jitted calls, each with a host->device batch copy and
a blocking ``float(loss)`` sync, and re-ran the frozen prefix's forward on
every one of them. This module collapses both costs:

  * ``make_fused_round`` stacks the K selected clients' minibatch sequences
    into a leading client axis and runs the whole round as ONE
    ``jax.jit(vmap(lax.scan(local_sgd_step)))`` with the Eq. 1
    dataset-weighted aggregation inside the compiled function. Clients with
    fewer local batches than the cohort maximum are masked per scan step
    (updates/losses suppressed once a client's plan is exhausted), so the
    fused result matches the sequential per-client loop exactly for fixed
    seeds.
  * ``RoundEngine`` adds the frozen-prefix feature cache: when a stage
    begins, each participating client runs the frozen prefix ONCE over its
    shard (eval mode, behind the ``stop_gradient`` boundary of
    ``cnn_stage_forward``/``stage_forward``) and local training thereafter
    consumes cached features — progressive training's later stages become
    shallow-model training (NeuLite arXiv:2408.10826, ProFL
    arXiv:2404.13349). The cache is invalidated on stage growth and is
    opt-in per client: the server checks the memory model's cache hook
    (``cnn_stage_memory_bytes(..., cache_samples=n)`` /
    ``stage_memory_bytes(..., cache_tokens=n)``) and declines it on
    memory-poor clients, who silently fall back to full recompute.

``fused=False`` is the escape hatch kept for the deadline/straggler path:
it runs the seed-identical sequential per-client loop (still optionally
consuming cached features).

The LM backend's ``make_fed_round_step`` (core/freezing.py) already fuses
pods inside one jit; ``make_lm_cached_fed_round_step`` below is its
cache-consuming sibling with ``donate_argnums`` on (active, opt_state).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.sharding import (CLIENT_AXIS, client_axis_size, replicate,
                                 shard_cohort)
from repro.fl.client import SimClient, batch_index_plan
from repro.fl.faults import (CORRUPT_KINDS, FAULT_CODE, apply_fault_to_update,
                             corrupt_codes)
from repro.fl.compression import (ingraph_compress_leaf,
                                  ingraph_sparse_aggregate, ingraph_topk,
                                  topk_keep)
from repro.fl.quant import (CACHE_TIERS, EncodedFeatures, cast_floating,
                            encode_features, feature_batch_arrays,
                            make_input_cast_loss, make_tiered_loss,
                            normalize_tier)
from repro.optim import Optimizer, apply_updates, clip_by_global_norm
from repro.spans import SpanStats

LossFn = Callable[[Any, Any, Any, Dict], Tuple[jnp.ndarray, Any]]
#   loss_fn(params, frozen, state, batch) -> (loss, new_state)


# ---------------------------------------------------------------------------
# Host-side aggregation (shared by servers/baselines; Eq. 1)
# ---------------------------------------------------------------------------


# The mul and add phases are SEPARATE jits on purpose: inside one compiled
# program XLA clones each product into the consumer fusion and the CPU
# emitter contracts mul+add into an FMA (optimization_barrier does not
# survive the duplication), which diverges from the seed's op-per-dispatch
# execution by 1 ulp. Muls alone and adds alone are bitwise exact, so the
# two-dispatch split keeps the seed fold's values while replacing K x leaves
# host-scalar dispatches with 2 (regression-tested in tests/test_quant.py).


def _weighted_avg_products(trees: Tuple, w):
    with jax.named_scope("fold"):
        return tuple(jax.tree.map(lambda x: x.astype(jnp.float32) * w[i], t)
                     for i, t in enumerate(trees))


def _weighted_avg_sum(prods: Tuple, ref):
    with jax.named_scope("fold"):
        out = prods[0]
        for p in prods[1:]:
            out = jax.tree.map(jnp.add, out, p)  # left fold, no reassociation
        return jax.tree.map(lambda a, r: a.astype(r.dtype), out, ref)


_wavg_products_jit = jax.jit(_weighted_avg_products)
_wavg_sum_jit = jax.jit(_weighted_avg_sum)


def weighted_avg(trees: Sequence, w: np.ndarray):
    """Dataset-weighted parameter average over a list of pytrees (Eq. 1) as
    a jitted weighted sum — two dispatches per call (products, then the
    left-fold accumulation), bit-identical to the seed's sequential
    ``tree.map`` loop; retraces only per (cohort size, tree structure)."""
    trees = tuple(trees)
    prods = _wavg_products_jit(trees, jnp.asarray(np.asarray(w, np.float32)))
    return _wavg_sum_jit(prods, trees[0])


# ---------------------------------------------------------------------------
# Update screening + robust aggregation (ISSUE 7: in-graph defenses)
# ---------------------------------------------------------------------------


AGGREGATORS = ("mean", "trimmed_mean", "coord_median")


def _apply_fault_codes(params, out_p, losses, codes, amplify):
    """In-graph delta-space corruption over the stacked client axis: row i
    of every leaf gets its update delta NaN'd / Inf'd / negated / scaled
    per ``codes[i]`` (0 = clean; fl/faults.FAULT_CODE). NaN/Inf rows also
    poison the reported per-client loss, mirroring what genuinely
    non-finite local gradients would do."""
    def leaf(p0, pk):
        p0f = p0.astype(jnp.float32)
        d = pk.astype(jnp.float32) - p0f[None]
        c = codes.reshape((-1,) + (1,) * (d.ndim - 1))
        d = d * jnp.where(c == FAULT_CODE["signflip"], -1.0,
                          jnp.where(c == FAULT_CODE["amplify"],
                                    jnp.float32(amplify), 1.0))
        d = jnp.where(c == FAULT_CODE["nan"], jnp.float32(jnp.nan), d)
        d = jnp.where(c == FAULT_CODE["inf"], jnp.float32(jnp.inf), d)
        # clean rows (code 0) keep their EXACT trained value — p0 + (pk -
        # p0) re-rounds, which would break zero-code bit-identity
        out = jnp.where(c == 0, pk.astype(jnp.float32), p0f[None] + d)
        return out.astype(pk.dtype)

    out_p = jax.tree.map(leaf, params, out_p)
    bad = ((codes == FAULT_CODE["nan"]) | (codes == FAULT_CODE["inf"]))
    return out_p, jnp.where(bad, jnp.float32(jnp.nan), losses)


def _delta_norms(params, out_p):
    """[K] f32 global L2 norms of each cohort row's param delta (NaN/Inf
    anywhere in a row surfaces as a non-finite norm)."""
    sq = None
    for p0, pk in zip(jax.tree.leaves(params), jax.tree.leaves(out_p)):
        d = pk.astype(jnp.float32) - p0.astype(jnp.float32)[None]
        s = jnp.sum(d * d, axis=tuple(range(1, d.ndim)))
        sq = s if sq is None else sq + s
    return jnp.sqrt(sq)


def _delta_norm_one(params, p_i):
    """Scalar f32 global L2 norm of ONE client's param delta — the
    per-client twin of ``_delta_norms`` for the unrolled / sequential
    paths (same op chain per row)."""
    sq = None
    for p0, pk in zip(jax.tree.leaves(params), jax.tree.leaves(p_i)):
        d = pk.astype(jnp.float32) - p0.astype(jnp.float32)
        s = jnp.sum(d * d)
        sq = s if sq is None else sq + s
    return jnp.sqrt(sq)


def _lower_median(sorted_vals, n_valid):
    """Lower median of the first ``n_valid`` entries of an ascending-sorted
    vector whose invalid tail is +inf (inf when nothing is valid)."""
    return sorted_vals[jnp.maximum(n_valid - 1, 0) // 2]


def _keep_mask(norms, losses, weights, mult):
    """Zero-weight screening mask (applied BEFORE the Eq. 1 normalizer):
    drop rows with a non-finite loss or delta, and rows whose delta norm
    exceeds ``mult`` x the cohort's (lower) median norm. Inert/padded rows
    (weight 0) are excluded from the median and never kept. With every row
    clean the mask is all-true and ``where(mask, w, 0)`` is bitwise ``w`` —
    the zero-fault bit-identity contract."""
    finite = jnp.isfinite(norms) & jnp.isfinite(losses)
    valid = finite & (weights > 0)
    n_v = jnp.sum(valid.astype(jnp.int32))
    med = _lower_median(jnp.sort(jnp.where(valid, norms, jnp.inf)), n_v)
    outlier = jnp.isfinite(med) & (norms > mult * med + 1e-6)
    return valid & ~outlier


def _robust_leaf(x, keep, n_valid, aggregator, trim_beta):
    """Per-coordinate robust combine of a stacked [K, ...] leaf over the
    kept rows: ``coord_median`` (average of the two middle order
    statistics) or ``trimmed_mean`` (drop floor(beta * n) from each end,
    unweighted mean of the band). Masked rows sort to +inf and the order
    statistics index only the valid prefix, so zero-weight masking composes
    exactly as it does for the weighted mean."""
    xf = x.astype(jnp.float32)
    K = x.shape[0]
    kcol = keep.reshape((K,) + (1,) * (x.ndim - 1))
    s = jnp.sort(jnp.where(kcol, xf, jnp.inf), axis=0)
    if aggregator == "coord_median":
        lo = jnp.maximum(n_valid - 1, 0) // 2
        hi = jnp.maximum(n_valid - 1, 0) - lo
        out = (jnp.take(s, lo, axis=0) + jnp.take(s, hi, axis=0)) * 0.5
    else:  # trimmed_mean
        t = jnp.floor(trim_beta * n_valid.astype(jnp.float32)).astype(jnp.int32)
        t = jnp.minimum(t, jnp.maximum(n_valid - 1, 0) // 2)
        idx = jnp.arange(K).reshape((K,) + (1,) * (x.ndim - 1))
        in_band = (idx >= t) & (idx < n_valid - t)
        out = (jnp.sum(jnp.where(in_band, s, 0.0), axis=0)
               / jnp.maximum(n_valid - 2 * t, 1).astype(jnp.float32))
    return out.astype(x.dtype)


def _recombine_kept(params, state, out_p, out_st, k_host, weights):
    """Host-side Eq. 1 over the KEPT rows of a screened fused round — the
    same ``weighted_avg`` combine the sequential path uses. Zero-weight
    masking inside the compiled aggregate would not be NaN-safe (0 x NaN =
    NaN still poisons a fold), so excluded rows are dropped before the
    combine. Only reached on rounds where screening actually fired (which
    voids the bit-identity contract anyway); with every row screened out
    the round is a no-op."""
    if not k_host.any():
        return params, state
    idx = np.nonzero(k_host)[0]
    p_host = jax.tree.map(lambda x: np.asarray(x), out_p)
    s_host = jax.tree.map(lambda x: np.asarray(x), out_st)
    kept_p = [jax.tree.map(lambda x: x[i], p_host) for i in idx]
    kept_s = [jax.tree.map(lambda x: x[i], s_host) for i in idx]
    w = np.asarray(weights, np.float64)[idx]
    w /= w.sum()
    return weighted_avg(kept_p, w), weighted_avg(kept_s, w)


# ---------------------------------------------------------------------------
# Fused multi-client round (tentpole #2)
# ---------------------------------------------------------------------------


def make_fused_round(loss_fn: LossFn, optimizer: Optimizer, *,
                     clip_norm: float = 10.0, unroll: Optional[bool] = None,
                     compress_ratio: Optional[float] = None,
                     compute_dtype: Optional[str] = None,
                     mesh=None, screen: bool = False,
                     screen_norm_mult: float = 8.0,
                     aggregator: str = "mean", trim_beta: float = 0.2,
                     inject_faults: bool = False,
                     fault_amplify: float = 50.0,
                     use_pallas: bool = False):
    """Build the single-dispatch round function.

    A minimal round — two clients, one local SGD step each on a scalar
    least-squares loss — showing the calling convention (cohort-stacked
    batches, per-client live-step counts, Eq. 1 weights):

    >>> import jax.numpy as jnp
    >>> from repro.optim import sgd
    >>> def loss_fn(params, frozen, state, batch):
    ...     err = params["w"] * batch["x"] - batch["y"]
    ...     return jnp.mean(err ** 2), state
    >>> round_fn = make_fused_round(loss_fn, sgd(0.1))
    >>> params = {"w": jnp.ones(())}
    >>> batches = {"x": jnp.ones((2, 1, 4)),   # [K=2 clients, nb=1, batch=4]
    ...            "y": jnp.zeros((2, 1, 4))}
    >>> p, st, losses = round_fn(params, {}, {}, batches,
    ...                          jnp.ones(2, jnp.int32), jnp.ones(2))
    >>> losses.shape                  # per-client mean loss
    (2,)
    >>> round(float(p["w"]), 3)       # w <- 1 - 0.1 * d/dw mean((w*x)^2)
    0.8

    Returned callable signature::

        round_fn(params, frozen, state, batches, nb_live, weights)
          params:  cohort-shared start params (no client dim)
          frozen:  replicated frozen tree (or a placeholder when unused)
          state:   cohort-shared mutable state (BN stats; {} when unused)
          batches: pytree with leading dims [K, nb, batch, ...]
          nb_live: [K] int32 — client i's real batch count (steps >= nb_live
                   are padding and masked out)
          weights: [K] float — Eq. 1 aggregation weights (|D_i|)
          -> (agg_params, agg_state, per_client_mean_loss [K])

    With ``compress_ratio`` set, the uplink is top-k sparsified INSIDE the
    same dispatch (``lax.top_k`` per leaf on each client's param delta,
    error feedback added before selection, server aggregation as a
    scatter-add over the sparse (indices, values) — zero host decompress)::

        round_fn(params, frozen, state, batches, nb_live, weights, residuals)
          residuals: params-shaped pytree of [K, leaf_size] f32 — each
                     client's carried error-feedback state
          -> (agg_params, agg_state, per_client_mean_loss [K], new_residuals)

    ``compress_ratio=1.0`` still routes through the sparse path and must
    reproduce the dense Eq. 1 aggregate (allclose; property-tested).

    Lowering strategy (``unroll``, default auto by backend):
      * accelerators: ``vmap(lax.scan(step))`` over the client axis — XLA
        lowers the per-client-weight contractions to efficient batched
        matmuls/convs and the K local trainings run data-parallel.
      * CPU (``unroll=True``): identical semantics, but the client axis is a
        statically-unrolled loop and the local steps use ``scan(unroll=True)``
        — the CPU backend executes convolutions inside ``while`` bodies on a
        ~4x slower single-threaded path and has no fast batched-weight conv,
        so the vmap form LOSES to the host loop there (measured).
      Both forms are one jit dispatch with the Eq. 1 weighted aggregation
      inside the compiled function and ONE host sync per round.

    Only the compressed round's carried residuals are donated (on
    accelerators): no output has the stacked ``batches``' shape, so XLA could
    not reuse that buffer. Params/state are NOT donated because a round may
    split into several fused cohorts (cached vs recompute groups) that share
    them.

    ``compute_dtype`` (e.g. ``"bfloat16"``) switches local training to
    mixed precision: each SGD step casts a throwaway copy of the params
    (and the replicated frozen tree + the batch's floating leaves, minus
    ``*_scale`` quantization scales) to the compute dtype for the
    forward/backward, then casts the gradients back — the carried params
    stay f32 master weights, the optimizer state is built over (and
    updated in) f32, and the Eq. 1 aggregation is the unchanged f32 sum.
    Default ``None`` is the exact seed-identical f32 loop.

    ``mesh`` (a ``launch.mesh.make_client_mesh`` mesh with a ``"clients"``
    axis of size > 1) switches to the SHARDED cohort path: the vmapped
    per-client local training is ``shard_map``-ped over the client axis —
    each device trains its cohort shard against replicated params/frozen/
    state, the Eq. 1 weight normalization and the weighted parameter/state
    sums become per-shard partial reductions joined by ONE cross-device
    ``psum`` per round (two for the compressed path: params + BN state),
    and per-client losses come back partitioned along the same axis. The
    caller pads the cohort to a multiple of the axis size with
    ``nb_live=0`` / ``weight=0`` rows (``RoundEngine`` does this), which
    contribute exactly zero to every reduction. Semantics are unchanged —
    the sharded aggregate equals the single-device vmap form up to f32
    summation order (allclose, property-tested); mesh ``None`` or a
    size-1 client axis returns the bit-identical single-device callable.

    ``screen=True`` (ISSUE 7) computes an in-graph update screen alongside
    the round: rows with a non-finite loss/delta or a delta norm past
    ``screen_norm_mult`` x the cohort median are flagged in a trailing
    ``keep`` [K] bool output, and the defended callable returns
    ``(agg_params, agg_state, losses, keep)``. While every live row passes,
    the aggregate comes from the UNTOUCHED legacy graph and is BIT-identical
    to ``screen=False`` (regression-tested; on the unrolled CPU form this
    costs a second local-training dispatch — the legacy fold's XLA
    fusion/FMA lowering shifts by 1 ulp if its graph gains any output, so
    the screen probe must be a separate jit). When screening fires, the
    kept rows are recombined host-side via ``weighted_avg`` — NaN-safe,
    unlike zero-weight masking (0 x NaN = NaN) — and the mesh path gathers
    the median statistic with one ``all_gather`` so the verdict matches the
    single-device screen. If every live row screens out, the round is a
    no-op (params/state returned unchanged).

    ``aggregator`` swaps the Eq. 1 weighted mean for a robust,
    unweighted per-coordinate combine over the kept rows:
    ``"trimmed_mean"`` (drop ``floor(trim_beta * n)`` order statistics from
    each end) or ``"coord_median"``. Robust aggregators require the full
    cohort on one device (``mesh=None``).

    ``inject_faults=True`` adds an optional trailing ``fault_codes`` [K]
    int32 argument (``fl/faults.FAULT_CODE``; pass ``None`` for a clean
    round) that corrupts the per-client deltas IN-GRAPH after local
    training, so injected corruption hits the screen exactly like a real
    byzantine update.

    ``use_pallas=True`` routes the compressed-uplink Eq. 1 fold through the
    Pallas cohort scatter-add kernel (kernels/sparse_agg.py): the vmap form
    swaps the per-leaf XLA scatter for the single-launch fold, and the
    unrolled CPU form collects every client's (idx, vals) rows and folds
    the cohort in ONE kernel at the end of the round instead of K
    incremental scatter dispatches. Selection math (top-k, error feedback)
    is shared, so residual state is identical on both paths; the default
    ``False`` keeps the exact pre-kernel XLA graphs (bit-compat escape
    hatch). Not composed with ``mesh`` (the sharded fold joins per-device
    partials via psum — a per-shard kernel would buy nothing and the
    combination is untested; raises ValueError).
    """
    if aggregator not in AGGREGATORS:
        raise ValueError(f"unknown aggregator {aggregator!r}; "
                         f"choose from {AGGREGATORS}")
    defended = screen or inject_faults or aggregator != "mean"
    if compress_ratio is not None and defended:
        raise ValueError(
            "screening / robust aggregation / fault injection do not "
            "compose with the compressed uplink (error-feedback residuals "
            "would carry the corrupted signal forward); use "
            "compress_ratio=None")
    n_shards = client_axis_size(mesh)
    if n_shards > 1 and aggregator != "mean":
        raise ValueError("robust aggregators need the full cohort on one "
                         "device; use mesh=None with aggregator=" +
                         repr(aggregator))
    if use_pallas and n_shards > 1:
        raise ValueError("use_pallas does not compose with a sharded client "
                         "mesh; use mesh=None (the sharded fold is psum-"
                         "joined per shard)")
    if unroll is None:
        unroll = n_shards <= 1 and jax.default_backend() == "cpu"
    if n_shards > 1:
        # the sharded path is the vmap form per shard — the CPU host loop
        # cannot be partitioned by shard_map
        unroll = False
    cdt = jnp.dtype(compute_dtype) if compute_dtype is not None else None
    loss_fn = make_input_cast_loss(loss_fn, compute_dtype)

    def local_train(params, frozen, state, batches, nb):
        with jax.named_scope("local_train"):
            opt_state = optimizer.init(params)  # f32 master-weight state
            if cdt is not None:
                frozen = cast_floating(frozen, cdt)

            def one(carry, batch):
                p, st, ost, t, lsum = carry
                if cdt is None:
                    (loss, st2), grads = jax.value_and_grad(
                        loss_fn, has_aux=True)(p, frozen, st, batch)
                else:
                    (loss, st2), grads = jax.value_and_grad(
                        lambda pc: loss_fn(pc, frozen, st, batch),
                        has_aux=True)(cast_floating(p, cdt))
                    grads = jax.tree.map(lambda g, m: g.astype(m.dtype),
                                         grads, p)
                    st2 = jax.tree.map(lambda a, m: a.astype(m.dtype),
                                       st2, st)
                    loss = loss.astype(jnp.float32)
                grads, _ = clip_by_global_norm(grads, clip_norm)
                ups, ost2 = optimizer.update(grads, ost, p)
                p2 = apply_updates(p, ups)
                live = t < nb

                def pick(new, old):
                    return jax.tree.map(lambda a, b: jnp.where(live, a, b),
                                        new, old)

                return (pick(p2, p), pick(st2, st), pick(ost2, ost), t + 1,
                        lsum + jnp.where(live, loss, 0.0)), None

            init = (params, state, opt_state, jnp.int32(0),
                    jnp.float32(0.0))
            if n_shards > 1:
                # under shard_map the carry starts replicated but each step
                # mixes in this shard's batch; scan needs the carry's type
                # to say up front that it varies over the client axis
                init = jax.tree.map(
                    lambda x: jax.lax.pcast(x, CLIENT_AXIS, to="varying"),
                    init)
            (p, st, _, _, lsum), _ = jax.lax.scan(one, init, batches,
                                                  unroll=True if unroll else 1)
            return p, st, lsum / jnp.maximum(nb, 1).astype(jnp.float32)

    def make_agg(w):
        def agg(x):
            with jax.named_scope("fold"):
                return jnp.einsum("k,k...->...", w,
                                  x.astype(jnp.float32)).astype(x.dtype)
        return agg

    def wsum(acc, tree, wi):
        with jax.named_scope("fold"):
            contrib = jax.tree.map(lambda b: wi * b.astype(jnp.float32), tree)
            return (contrib if acc is None
                    else jax.tree.map(jnp.add, acc, contrib))

    def cast_like(acc, ref):
        return jax.tree.map(lambda a, r: a.astype(r.dtype), acc, ref)

    def unrolled_clients(params, frozen, state, batches, nb_live):
        for i in range(nb_live.shape[0]):
            yield local_train(params, frozen, state,
                              jax.tree.map(lambda x: x[i], batches),
                              nb_live[i])

    def round_fn(params, frozen, state, batches, nb_live, weights):
        K = nb_live.shape[0]
        w = (weights / jnp.sum(weights)).astype(jnp.float32)
        if unroll:
            # incremental weighted sum: at most ONE extra model copy live at
            # a time (stacking K client trees would be an O(K) peak-memory
            # regression on the CPU path the memory model budgets for)
            agg_p = agg_st = None
            losses = []
            for i, (p_i, st_i, loss_i) in enumerate(
                    unrolled_clients(params, frozen, state, batches, nb_live)):
                agg_p = wsum(agg_p, p_i, w[i])
                agg_st = wsum(agg_st, st_i, w[i])
                losses.append(loss_i)
            return (cast_like(agg_p, params), cast_like(agg_st, state),
                    jnp.stack(losses))
        bcast = lambda x: jnp.broadcast_to(x[None], (K,) + x.shape)
        out_p, out_st, losses = jax.vmap(
            local_train, in_axes=(0, None, 0, 0, 0))(
            jax.tree.map(bcast, params), frozen, jax.tree.map(bcast, state),
            batches, nb_live)
        agg = make_agg(w)
        return jax.tree.map(agg, out_p), jax.tree.map(agg, out_st), losses

    # ----- defended variants (ISSUE 7) -----
    #
    # The defended round must satisfy two contracts at once: (a) with zero
    # faulty rows it is BIT-identical to the legacy round, and (b) a NaN
    # row never reaches the returned aggregate. Zero-weight masking alone
    # satisfies neither on its own: 0 x NaN = NaN poisons any fold, and —
    # measured — touching the unrolled CPU fold's graph in ANY way (a
    # keep-dependent weight chain, a trailing ``where`` select, even just
    # returning an extra output whose computation consumes the per-client
    # trees) perturbs XLA's fusion/FMA contraction decisions by 1 ulp.
    # The vmap/einsum form is robust to extra outputs (verified), the
    # unrolled fold is not. Hence the OBSERVE design:
    #   * vmap + sharded paths: ONE dispatch that runs the legacy weight
    #     chain + einsum/psum aggregate untouched and additionally returns
    #     the screen verdict ``keep`` and the stacked per-client outputs.
    #   * unrolled (CPU) path: the EXACT legacy jit computes the
    #     aggregate, and a separate screen-probe dispatch re-runs local
    #     training to produce (stacked outputs, keep). This doubles the
    #     local-training compute of defended unrolled rounds — the price
    #     of keeping the legacy fold's lowering byte-for-byte; defenses
    #     are opt-in and the CPU path is the small-model simulator.
    # The host wrapper accepts the legacy aggregate when every live row
    # passed, and recombines the kept rows via ``weighted_avg`` (the
    # sequential path's combine) when screening fired — faulty rounds
    # carry no bit-identity contract.

    def _verdict(norms, losses, weights):
        if screen:
            return _keep_mask(norms, losses, weights, screen_norm_mult)
        if aggregator != "mean":
            # robust aggregators always exclude non-finite rows (they
            # would poison the order statistics)
            return (jnp.isfinite(norms) & jnp.isfinite(losses)
                    & (weights > 0))
        # defenses off (fault injection only): corruption flows into the
        # mean unscreened — the benchmark's divergence arm
        return weights > 0

    def train_stacked(params, frozen, state, batches, nb_live, weights,
                      fault_codes=None):
        """Screen probe / stacked trainer: local training with the
        per-client results stacked, (optional) in-graph corruption, and
        the jitted screen verdict. No aggregation — the caller combines
        host-side."""
        K = nb_live.shape[0]
        if unroll:
            outs = list(unrolled_clients(params, frozen, state, batches,
                                         nb_live))
            losses = jnp.stack([o[2] for o in outs])
            out_p = jax.tree.map(lambda *xs: jnp.stack(xs),
                                 *[o[0] for o in outs])
            out_st = jax.tree.map(lambda *xs: jnp.stack(xs),
                                  *[o[1] for o in outs])
        else:
            bcast = lambda x: jnp.broadcast_to(x[None], (K,) + x.shape)
            out_p, out_st, losses = jax.vmap(
                local_train, in_axes=(0, None, 0, 0, 0))(
                jax.tree.map(bcast, params), frozen,
                jax.tree.map(bcast, state), batches, nb_live)
        if fault_codes is not None:
            out_p, losses = _apply_fault_codes(params, out_p, losses,
                                               fault_codes, fault_amplify)
        norms = _delta_norms(params, out_p)
        keep = _verdict(norms, losses, weights)
        return out_p, out_st, losses, keep

    def observe_vmap(params, frozen, state, batches, nb_live, weights,
                     fault_codes=None):
        """Single-dispatch defended round (vmap form): legacy einsum
        aggregate untouched + keep verdict + stacked outputs."""
        K = nb_live.shape[0]
        bcast = lambda x: jnp.broadcast_to(x[None], (K,) + x.shape)
        out_p, out_st, losses = jax.vmap(
            local_train, in_axes=(0, None, 0, 0, 0))(
            jax.tree.map(bcast, params), frozen, jax.tree.map(bcast, state),
            batches, nb_live)
        if fault_codes is not None:
            out_p, losses = _apply_fault_codes(params, out_p, losses,
                                               fault_codes, fault_amplify)
        norms = _delta_norms(params, out_p)
        keep = _verdict(norms, losses, weights)
        w = (weights / jnp.sum(weights)).astype(jnp.float32)
        agg = make_agg(w)
        return (jax.tree.map(agg, out_p), jax.tree.map(agg, out_st), losses,
                keep, out_p, out_st)

    def robust_fn(params, frozen, state, batches, nb_live, weights,
                  fault_codes=None):
        """Robust in-graph combine (``trimmed_mean``/``coord_median``) —
        no bit-identity contract, single dispatch, NaN-safe (masked rows
        sort to +inf and the order statistics index the valid prefix)."""
        out_p, out_st, losses, keep = train_stacked(
            params, frozen, state, batches, nb_live, weights, fault_codes)
        with jax.named_scope("fold"):
            n_valid = jnp.sum(keep.astype(jnp.int32))
            safe = n_valid > 0
            rob = lambda x: _robust_leaf(x, keep, n_valid, aggregator,
                                         trim_beta)
            # all rows screened out -> the round is a no-op (never average
            # NaN)
            agg_p = jax.tree.map(lambda x, p0: jnp.where(safe, rob(x), p0),
                                 out_p, params)
            agg_st = jax.tree.map(lambda x, s0: jnp.where(safe, rob(x), s0),
                                  out_st, state)
        return agg_p, agg_st, losses, keep

    def round_fn_compressed(params, frozen, state, batches, nb_live, weights,
                            residuals):
        K = nb_live.shape[0]
        w = (weights / jnp.sum(weights)).astype(jnp.float32)
        p_leaves, treedef = jax.tree.flatten(params)
        r_leaves = jax.tree.leaves(residuals)      # [K, leaf_size] each
        if unroll:
            # per-client incremental compress: only the [K, L] residual
            # state (inherent to error feedback) outlives a client's turn.
            # use_pallas instead collects every client's (idx, vals) rows
            # and folds the cohort in ONE sparse_agg kernel per leaf at the
            # end — the [K, k] row stacks are the same wire payload the
            # compressed uplink already carries, so no extra memory class.
            agg_acc = [jnp.zeros(p0.size, jnp.float32) for p0 in p_leaves]
            sent_rows = [[] for _ in p_leaves]      # use_pallas: (idx, vals)
            new_r_rows = [[] for _ in p_leaves]
            agg_st = None
            losses = []
            for i, (p_i, st_i, loss_i) in enumerate(
                    unrolled_clients(params, frozen, state, batches, nb_live)):
                for j, (p0, pi) in enumerate(zip(p_leaves,
                                                 jax.tree.leaves(p_i))):
                    delta = (pi.astype(jnp.float32).reshape(-1)
                             - p0.astype(jnp.float32).reshape(-1)
                             + r_leaves[j][i])
                    idx, vals = ingraph_topk(
                        delta, topk_keep(p0.size, compress_ratio))
                    if use_pallas:
                        sent_rows[j].append((idx, vals))
                    else:
                        with jax.named_scope("fold"):
                            agg_acc[j] = agg_acc[j].at[idx].add(w[i] * vals)
                    # residual = delta - sent: the kept entries were
                    # transmitted exactly, so they zero out
                    new_r_rows[j].append(delta.at[idx].set(0.0))
                agg_st = wsum(agg_st, st_i, w[i])
                losses.append(loss_i)
            with jax.named_scope("fold"):
                if use_pallas:
                    agg_acc = [
                        ingraph_sparse_aggregate(
                            jnp.stack([i_ for i_, _ in rows]),
                            jnp.stack([v_ for _, v_ in rows]), w, p0.size,
                            use_pallas=True)
                        for p0, rows in zip(p_leaves, sent_rows)]
                new_p = [(p0.astype(jnp.float32).reshape(-1) + acc)
                         .reshape(p0.shape).astype(p0.dtype)
                         for p0, acc in zip(p_leaves, agg_acc)]
            return (jax.tree.unflatten(treedef, new_p),
                    cast_like(agg_st, state), jnp.stack(losses),
                    jax.tree.unflatten(treedef, [jnp.stack(rows)
                                                 for rows in new_r_rows]))
        bcast = lambda x: jnp.broadcast_to(x[None], (K,) + x.shape)
        out_p, out_st, losses = jax.vmap(
            local_train, in_axes=(0, None, 0, 0, 0))(
            jax.tree.map(bcast, params), frozen, jax.tree.map(bcast, state),
            batches, nb_live)
        new_p, new_r = [], []
        for p0, pk, r in zip(p_leaves, jax.tree.leaves(out_p), r_leaves):
            with jax.named_scope("fold"):
                agg_flat, r_new, _, _ = ingraph_compress_leaf(
                    p0.astype(jnp.float32).reshape(-1),
                    pk.astype(jnp.float32).reshape(K, -1), r, w,
                    compress_ratio, use_pallas=use_pallas)
            new_p.append(agg_flat.reshape(p0.shape).astype(p0.dtype))
            new_r.append(r_new)
        # mutable state (BN stats) stays a dense server-side average — only
        # the parameter uplink is compressed
        return (jax.tree.unflatten(treedef, new_p),
                jax.tree.map(make_agg(w), out_st), losses,
                jax.tree.unflatten(treedef, new_r))

    # ----- sharded cohort path: shard_map over the client axis -----

    def psum_agg(w):
        def agg(x):
            with jax.named_scope("fold"):
                part = jnp.einsum("k,k...->...", w, x.astype(jnp.float32))
                return jax.lax.psum(part, CLIENT_AXIS).astype(x.dtype)
        return agg

    def shard_train(params, frozen, state, batches, nb_live, weights):
        """Per-device body: train this shard's K/n_shards cohort rows
        against replicated params/frozen/state. Padded rows (nb_live=0,
        weight=0) train nothing and weigh nothing, so the global Eq. 1
        normalizer — one psum of the shard weight sums — sees only real
        clients."""
        K = nb_live.shape[0]
        wsum = jax.lax.psum(jnp.sum(weights), CLIENT_AXIS)
        w = (weights / wsum).astype(jnp.float32)
        bcast = lambda x: jnp.broadcast_to(x[None], (K,) + x.shape)
        out_p, out_st, losses = jax.vmap(
            local_train, in_axes=(0, None, 0, 0, 0))(
            jax.tree.map(bcast, params), frozen, jax.tree.map(bcast, state),
            batches, nb_live)
        return out_p, out_st, losses, w

    def round_fn_sharded(params, frozen, state, batches, nb_live, weights):
        out_p, out_st, losses, w = shard_train(params, frozen, state,
                                               batches, nb_live, weights)
        agg = psum_agg(w)
        return jax.tree.map(agg, out_p), jax.tree.map(agg, out_st), losses

    def round_fn_sharded_defended(params, frozen, state, batches, nb_live,
                                  weights, fault_codes=None):
        """Defended twin of ``round_fn_sharded`` (mean aggregator only),
        observe design like ``round_fn_defended``: the legacy per-shard
        weight normalization + psum-joined Eq. 1 aggregate run untouched,
        the screen's median statistic goes global with ONE ``all_gather``
        of the per-shard delta norms plus a ``psum`` of the valid count,
        and the per-shard ``keep`` verdicts + stacked client outputs come
        back partitioned along the client axis for the caller's host-side
        recombine when screening fires."""
        out_p, out_st, losses, w = shard_train(params, frozen, state,
                                               batches, nb_live, weights)
        if fault_codes is not None:
            out_p, losses = _apply_fault_codes(params, out_p, losses,
                                               fault_codes, fault_amplify)
        norms = _delta_norms(params, out_p)
        if screen:
            valid = (jnp.isfinite(norms) & jnp.isfinite(losses)
                     & (weights > 0))
            all_n = jax.lax.all_gather(jnp.where(valid, norms, jnp.inf),
                                       CLIENT_AXIS, tiled=True)
            n_v = jax.lax.psum(jnp.sum(valid.astype(jnp.int32)), CLIENT_AXIS)
            med = _lower_median(jnp.sort(all_n), n_v)
            outlier = jnp.isfinite(med) & (norms > screen_norm_mult * med
                                           + 1e-6)
            keep = valid & ~outlier
        else:
            keep = weights > 0
        agg = psum_agg(w)
        return (jax.tree.map(agg, out_p), jax.tree.map(agg, out_st), losses,
                keep, out_p, out_st)

    def round_fn_compressed_sharded(params, frozen, state, batches, nb_live,
                                    weights, residuals):
        out_p, out_st, losses, w = shard_train(params, frozen, state,
                                               batches, nb_live, weights)
        K = nb_live.shape[0]
        p_leaves, treedef = jax.tree.flatten(params)
        new_p, new_r = [], []
        for p0, pk, r in zip(p_leaves, jax.tree.leaves(out_p),
                             jax.tree.leaves(residuals)):
            p0_flat = p0.astype(jnp.float32).reshape(-1)
            with jax.named_scope("fold"):
                agg_local, r_new, _, _ = ingraph_compress_leaf(
                    p0_flat, pk.astype(jnp.float32).reshape(K, -1), r, w,
                    compress_ratio)
                # agg_local = p0 + this shard's weighted sparse scatter-add;
                # the global Eq. 1 aggregate joins the partials with one
                # psum
                agg = p0_flat + jax.lax.psum(agg_local - p0_flat,
                                             CLIENT_AXIS)
            new_p.append(agg.reshape(p0.shape).astype(p0.dtype))
            new_r.append(r_new)
        # BN state stays a dense weighted average (params-only uplink)
        return (jax.tree.unflatten(treedef, new_p),
                jax.tree.map(psum_agg(w), out_st), losses,
                jax.tree.unflatten(treedef, new_r))

    # the carried residuals are rebuilt from per-client state every round
    # and alias the new residuals, so they are donated where the backend
    # can alias (not CPU)
    donate_res = (6,) if jax.default_backend() != "cpu" else ()
    if n_shards > 1:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        rep, csp = P(), P(CLIENT_AXIS)
        if compress_ratio is not None:
            fn = shard_map(round_fn_compressed_sharded, mesh=mesh,
                           in_specs=(rep, rep, rep, csp, csp, csp, csp),
                           out_specs=(rep, rep, csp, csp))
            return jax.jit(fn, donate_argnums=donate_res)
        if defended:
            # shard_map needs a fixed positional signature, so the codes
            # input only exists on injector-enabled builds
            if inject_faults:
                body = round_fn_sharded_defended
                in_sp = (rep, rep, rep, csp, csp, csp, csp)
            else:
                def body(p, f, s, b, nb, w):
                    return round_fn_sharded_defended(p, f, s, b, nb, w)
                in_sp = (rep, rep, rep, csp, csp, csp)
            out_sp = (rep, rep, csp, csp, csp, csp)
            smfn = jax.jit(shard_map(body, mesh=mesh, in_specs=in_sp,
                                     out_specs=out_sp))

            def sharded_defended(params, frozen, state, batches, nb_live,
                                 weights, fault_codes=None):
                args = (params, frozen, state, batches, nb_live, weights)
                if fault_codes is not None:
                    args = args + (fault_codes,)
                agg_p, agg_st, losses, keep, out_p, out_st = smfn(*args)
                k = np.asarray(keep)
                if np.any(~k & (np.asarray(weights) > 0)):
                    agg_p, agg_st = _recombine_kept(params, state, out_p,
                                                    out_st, k, weights)
                return agg_p, agg_st, losses, keep

            return sharded_defended
        fn = shard_map(round_fn_sharded, mesh=mesh,
                       in_specs=(rep, rep, rep, csp, csp, csp),
                       out_specs=(rep, rep, csp))
        return jax.jit(fn)
    if compress_ratio is not None:
        return jax.jit(round_fn_compressed, donate_argnums=donate_res)
    if defended and aggregator != "mean":
        return jax.jit(robust_fn)
    if defended and not unroll:
        observe_jit = jax.jit(observe_vmap)

        def vmap_defended(params, frozen, state, batches, nb_live, weights,
                          fault_codes=None):
            agg_p, agg_st, losses, keep, out_p, out_st = observe_jit(
                params, frozen, state, batches, nb_live, weights,
                fault_codes)
            k = np.asarray(keep)
            if np.any(~k & (np.asarray(weights) > 0)):
                agg_p, agg_st = _recombine_kept(params, state, out_p,
                                                out_st, k, weights)
            return agg_p, agg_st, losses, keep

        return vmap_defended
    if defended:
        # unrolled two-dispatch form: the screen probe always runs, and the
        # aggregate comes from the EXACT legacy jit whenever every live row
        # passed clean.
        legacy_jit = jax.jit(round_fn)
        probe_jit = jax.jit(train_stacked)

        def unrolled_defended(params, frozen, state, batches, nb_live,
                              weights, fault_codes=None):
            out_p, out_st, losses_p, keep = probe_jit(
                params, frozen, state, batches, nb_live, weights,
                fault_codes)
            k = np.asarray(keep)
            if fault_codes is None and not np.any(
                    ~k & (np.asarray(weights) > 0)):
                # every live row passed: take the untouched legacy graph's
                # aggregate — bitwise the undefended round
                agg_p, agg_st, losses = legacy_jit(params, frozen, state,
                                                   batches, nb_live, weights)
                return agg_p, agg_st, losses, keep
            # a corrupted or screened round voids the bit-identity
            # contract: combine the kept rows host-side (NaN-safe)
            agg_p, agg_st = _recombine_kept(params, state, out_p, out_st,
                                            k, weights)
            return agg_p, agg_st, losses_p, keep

        return unrolled_defended
    return jax.jit(round_fn)


# ---------------------------------------------------------------------------
# Round engine (tentpole #1 + #2 glue): cache + dispatch + grouping
# ---------------------------------------------------------------------------


@dataclass
class RoundEngine:
    """Executes federated rounds for a cohort of ``SimClient``s.

    ``loss_fn`` is the full-recompute stage loss; ``cached_loss_fn`` (when
    given) is its twin consuming pre-extracted prefix features under the
    same ``batch["x"]`` key; ``feature_fn(x) -> features`` is the frozen
    prefix itself. All three close over the current stage's frozen tree /
    plan — construct a fresh engine at every stage boundary, which is also
    what invalidates the feature cache on model growth (and, with
    compression on, resets error-feedback residuals, whose shapes follow
    the stage's active params).

    ``compress_ratio`` turns on in-graph top-k uplink sparsification with
    error feedback: residuals live on device in per-leaf [n_clients_seen,
    leaf_size] row pools (one gather on dispatch entry, one scatter on
    exit — NOT per-client stacking, which would reintroduce O(K x leaves)
    small device ops around the single fused dispatch), and come back
    updated — the round's hot path never materializes a dense per-client
    delta on host. ``last_uplink_bytes`` reports the (index, value)
    payload the round would have put on the wire.

    Feature caches are TIERED (fl/quant.py): ``use_cache`` values may be a
    tier name (``"f32"``/``"fp16"``/``"int8"``; legacy ``True`` means f32)
    and ``features_for`` quantizes on write, so a client's shard is held at
    the admitted precision from the moment it leaves the frozen prefix.
    int8 dequantization is fused into the cached-consumer loss inside the
    compiled round. ``compute_dtype`` (e.g. ``"bfloat16"``) runs local
    forward/backward in mixed precision with f32 master params/optimizer
    state and f32 Eq. 1 aggregation (``make_fused_round``).

    ``mesh`` (``launch.mesh.make_client_mesh``) switches the fused path to
    sharded cohort execution: the engine pads each per-tier group to a
    multiple of the client-axis size with inert rows (``nb_live=0``,
    ``weight=0``), partitions the stacked batches / live counts / weights /
    EF residuals along the axis, replicates params + frozen + BN state, and
    the shard_mapped dispatch joins per-device partial aggregates with one
    ``psum`` (see ``make_fused_round``). Mesh ``None`` (default) or a
    size-1 axis is the exact single-device path, bit-identical to pre-mesh
    trajectories. The sequential escape hatch ignores the mesh (it exists
    for the deadline/straggler path, which is latency- not
    throughput-bound).

    ISSUE 7 defenses: ``screen=True`` turns on the in-graph update screen
    (finite-check + ``screen_norm_mult`` x median delta-norm outlier mask,
    as zero-weight masking before Eq. 1; per-client verdicts land in
    ``last_screened``), ``aggregator`` selects
    ``"trimmed_mean"``/``"coord_median"`` robust combines, and
    ``run_round(..., faults={cid: kind})`` injects the corruption kinds of
    ``fl/faults.py`` — in-graph ``fault_codes`` on the fused dispatch,
    host-side ``apply_fault_to_update`` on the sequential path, same
    delta-space semantics. With screening on and no faults, rounds are
    bit-identical to an undefended engine (the legacy code paths are used
    verbatim whenever no defense is active). None of this composes with
    ``compress_ratio`` (error feedback would carry corrupted signal).

    The fused path stages each cohort on the host in buffers the engine
    keeps per (tier, data key) and reuses from round to round: one copy of
    each client's minibatch rows into warm pages, reallocated only when
    the cohort's shape or dtype changes. A buffer is rewritten only after
    the round that last sent it has synced.

    ``spans`` (``repro.spans.SpanStats``) times the fused round's host
    steps: ``engine.round`` (all of ``run_round``), ``engine.gather``
    (batch plans, the copy into the staging buffers, mesh pad rows; each
    buffer allocated counted in ``engine.stage_alloc_bytes``),
    ``engine.put`` (host arrays to the device, their bytes counted in
    ``engine.h2d_bytes``),
    ``engine.dispatch`` (the compiled call), ``engine.sync`` (the one
    blocking read), ``engine.combine`` (the host fold of several tier
    groups) and ``engine.features`` (a frozen-prefix extraction).
    """
    loss_fn: LossFn
    optimizer: Optimizer
    frozen: Any = None
    cached_loss_fn: Optional[LossFn] = None
    feature_fn: Optional[Callable] = None
    batch_size: int = 32
    local_epochs: int = 1
    clip_norm: float = 10.0
    fused: bool = True
    compress_ratio: Optional[float] = None
    compute_dtype: Optional[str] = None
    mesh: Any = None
    screen: bool = False
    screen_norm_mult: float = 8.0
    aggregator: str = "mean"
    trim_beta: float = 0.2
    fault_amplify: float = 50.0
    use_pallas: bool = False
    last_uplink_bytes: int = 0
    last_screened: Dict[int, bool] = field(default_factory=dict, repr=False)
    _features: Dict[int, EncodedFeatures] = field(default_factory=dict,
                                                  repr=False)
    _cache_version: int = field(default=0, repr=False)
    _cache_saved_version: int = field(default=-1, repr=False)
    _jit_cache: Dict[str, Callable] = field(default_factory=dict, repr=False)
    _res_pool: List = field(default_factory=list, repr=False)   # per leaf [cap, L]
    _res_row: Dict[int, int] = field(default_factory=dict, repr=False)
    # host staging buffers of the fused path, per (tier, data key)
    _stage: Dict[Tuple[Optional[str], str], np.ndarray] = field(
        default_factory=dict, repr=False)
    spans: SpanStats = field(default_factory=SpanStats, repr=False)

    # ----- frozen-prefix feature cache (tiered) -----

    def features_for(self, client: SimClient,
                     tier: str = "f32") -> EncodedFeatures:
        """Client's shard pushed through the frozen prefix once (eval mode)
        and encoded at ``tier`` on write; memoized until the engine (== the
        stage) is replaced. A tier change re-extracts and re-encodes (does
        not happen mid-stage: admission is decided per stage)."""
        enc = self._features.get(client.client_id)
        if enc is None or enc.tier != tier:
            fn = self._jit_cache.setdefault("feature", jax.jit(self.feature_fn))
            with self.spans.span("engine.features"):
                enc = encode_features(
                    np.asarray(fn(jnp.asarray(client.data["x"]))), tier)
            self._features[client.client_id] = enc
            self._cache_version += 1
        return enc

    def cache_nbytes(self) -> int:
        """Resident cache footprint at the ACTUAL stored dtypes (int8
        values + their f32 scale vectors count as stored, not as the f32
        equivalent)."""
        return sum(f.nbytes for f in self._features.values())

    def cache_tiers(self) -> Dict[int, str]:
        """Tier actually stored per cached client."""
        return {cid: enc.tier for cid, enc in self._features.items()}

    def cache_state(self) -> Optional[Dict[str, np.ndarray]]:
        """Per-client tier assignments + encoded features (incl. int8 quant
        scales) as checkpointable arrays — a resumed run consumes the exact
        bytes the crashed run trained on, so bit-identical resume holds
        across a tier decision. None when nothing is cached yet."""
        if not self._features:
            return None
        cids = sorted(self._features)
        out = {"ids": np.asarray(cids, np.int64),
               "tiers": np.asarray([CACHE_TIERS.index(self._features[c].tier)
                                    for c in cids], np.int64)}
        for i, cid in enumerate(cids):
            enc = self._features[cid]
            out[f"val{i}"] = np.asarray(enc.values)
            if enc.scale is not None:
                out[f"scale{i}"] = np.asarray(enc.scale)
        return out

    def cache_state_if_changed(self) -> Optional[Dict[str, np.ndarray]]:
        """``cache_state`` only when the cache changed since the last call.
        Within a stage the cache is immutable once every participant is
        encoded, so checkpoints stop re-writing identical feature bytes
        every round; a checkpoint without a ``cache`` subtree resumes by
        recomputing the features from the restored frozen tree, which is
        deterministic (bit-identical on the same backend)."""
        if not self._features or self._cache_version == self._cache_saved_version:
            return None
        self._cache_saved_version = self._cache_version
        return self.cache_state()

    def load_cache_state(self, tree: Dict[str, np.ndarray]) -> None:
        """Restore ``cache_state`` output."""
        self._features = {}
        tiers = np.asarray(tree["tiers"])
        for i, cid in enumerate(np.asarray(tree["ids"])):
            self._features[int(cid)] = EncodedFeatures(
                CACHE_TIERS[int(tiers[i])], np.asarray(tree[f"val{i}"]),
                (np.asarray(tree[f"scale{i}"]) if f"scale{i}" in tree
                 else None))
        self._cache_version += 1

    # ----- error-feedback residual state (on-device, per client) -----

    def _residual_rows(self, cids: List[int], leaves) -> np.ndarray:
        """Pool row index per client, growing the per-leaf [cap, L] pools
        (zero-filled == empty residual) as new clients appear."""
        for cid in cids:
            if cid not in self._res_row:
                self._res_row[cid] = len(self._res_row)
        need = len(self._res_row)
        if not self._res_pool:
            self._res_pool = [jnp.zeros((need, l.size), jnp.float32)
                              for l in leaves]
        elif self._res_pool[0].shape[0] < need:
            cap = max(need, 2 * self._res_pool[0].shape[0])
            self._res_pool = [
                jnp.concatenate([p, jnp.zeros((cap - p.shape[0], p.shape[1]),
                                              jnp.float32)]) for p in self._res_pool]
        return np.asarray([self._res_row[cid] for cid in cids])

    def _gather_residuals(self, cids: List[int], params):
        """Cohort residuals as a params-shaped tree of [K, L] leaves — ONE
        gather per leaf from the resident pool."""
        leaves, treedef = jax.tree.flatten(params)
        rows = self._residual_rows(cids, leaves)
        rows_dev = jnp.asarray(rows)
        return jax.tree.unflatten(treedef,
                                  [p[rows_dev] for p in self._res_pool]), rows

    def _scatter_residuals(self, rows: np.ndarray, new_residuals):
        rows_dev = jnp.asarray(rows)
        self._res_pool = [pool.at[rows_dev].set(leaf) for pool, leaf in
                          zip(self._res_pool, jax.tree.leaves(new_residuals))]

    def client_residuals(self, cid: int) -> List[jnp.ndarray]:
        """This client's per-leaf error-feedback residual vectors."""
        row = self._res_row[cid]
        return [p[row] for p in self._res_pool]

    def ef_state(self) -> Optional[Dict[str, np.ndarray]]:
        """Error-feedback residual pools + client->row map as checkpointable
        arrays (None when compression is off / nothing carried yet)."""
        if not self._res_pool:
            return None
        cids = sorted(self._res_row)
        return {"rows_ids": np.asarray(cids, np.int64),
                "rows_idx": np.asarray([self._res_row[c] for c in cids],
                                       np.int64),
                **{f"pool{i}": np.asarray(p)
                   for i, p in enumerate(self._res_pool)}}

    def load_ef_state(self, tree: Dict[str, np.ndarray]) -> None:
        """Restore ``ef_state`` output — resumed compressed runs carry the
        exact per-client un-transmitted residual signal forward."""
        self._res_row = {int(c): int(i) for c, i in
                         zip(np.asarray(tree["rows_ids"]),
                             np.asarray(tree["rows_idx"]))}
        pools = []
        i = 0
        while f"pool{i}" in tree:
            pools.append(jnp.asarray(tree[f"pool{i}"], jnp.float32))
            i += 1
        self._res_pool = pools

    def per_client_uplink_bytes(self, params) -> int:
        """One client's (index, value) payload for the current stage — what
        the time model charges against each client's uplink rate."""
        return self._uplink_bytes(params, 1)

    def residual_norms(self) -> Dict[int, float]:
        """Per-client ||error-feedback residual||_2 — feeds
        ``ClientPopulation.ef_residual_norm`` for selection policies that
        prefer clients with pent-up un-transmitted signal."""
        if not self._res_pool:
            return {}
        fn = self._jit_cache.setdefault(
            "res_norm", jax.jit(lambda pools: jnp.sqrt(
                sum(jnp.sum(p.astype(jnp.float32) ** 2, axis=1)
                    for p in pools))))
        norms = np.asarray(fn(self._res_pool))
        return {cid: float(norms[row]) for cid, row in self._res_row.items()}

    def _uplink_bytes(self, params, n_clients: int) -> int:
        """(index, value) payload per client, summed over the cohort."""
        leaves = jax.tree.leaves(params)
        if self.compress_ratio is None:
            return n_clients * sum(l.size * 4 for l in leaves)
        return n_clients * sum(topk_keep(l.size, self.compress_ratio) * 8
                               for l in leaves)

    # ----- round execution -----

    def run_round(self, clients: Dict[int, SimClient], selected: List[int],
                  params, state, round_idx: int, *,
                  use_cache: Optional[Dict[int, bool]] = None,
                  sequential: Optional[bool] = None,
                  faults: Optional[Dict[int, str]] = None
                  ) -> Tuple[Any, Any, Dict[int, float]]:
        """One federated round over ``selected``. Returns (params, state,
        per-client mean loss). Splits the cohort into per-cache-tier groups
        plus a recompute group (their batch shapes/dtypes differ), runs each
        as one fused dispatch, and combines the group aggregates by total
        weight — algebraically the same Eq. 1 average as a single flat
        cohort. ``use_cache`` values are tier names (legacy booleans still
        accepted: ``True`` == the exact f32 tier). ``faults`` maps client
        ids in the cohort to ``fl/faults.CORRUPT_KINDS`` — their trained
        updates are corrupted (delta-space) before screening/aggregation;
        crash/hang kinds never reach the engine (the aggregation policies
        drop those clients upstream)."""
        with self.spans.span("engine.round"):
            return self._run_round(clients, selected, params, state,
                                   round_idx, use_cache=use_cache,
                                   sequential=sequential, faults=faults)

    def _run_round(self, clients, selected, params, state, round_idx, *,
                   use_cache, sequential, faults):
        use_cache = use_cache or {}
        seq = (not self.fused) if sequential is None else sequential
        if self.aggregator not in AGGREGATORS:
            raise ValueError(f"unknown aggregator {self.aggregator!r}; "
                             f"choose from {AGGREGATORS}")
        faults = {int(c): k for c, k in (faults or {}).items()
                  if k in CORRUPT_KINDS} or None
        if ((self.screen or self.aggregator != "mean" or faults)
                and self.compress_ratio is not None):
            raise ValueError("screening / robust aggregation / fault "
                             "injection do not compose with compress_ratio")
        self.last_uplink_bytes = 0
        self.last_screened = {}
        groups: Dict[Optional[str], List[int]] = {}
        for cid in selected:
            tier = (normalize_tier(use_cache.get(cid))
                    if self.cached_loss_fn is not None else None)
            groups.setdefault(tier, []).append(cid)

        partials = []  # (agg_params, agg_state, group_weight)
        losses: Dict[int, float] = {}
        for tier, cids in groups.items():
            runner = self._run_sequential if seq else self._run_fused
            p_g, s_g, l_g, w_g = runner(clients, cids, params, state,
                                        round_idx, tier=tier, faults=faults)
            partials.append((p_g, s_g, w_g))
            losses.update(l_g)
        if len(partials) == 1:
            return partials[0][0], partials[0][1], losses
        w = np.asarray([p[2] for p in partials], np.float64)
        w /= w.sum()
        with self.spans.span("engine.combine"):
            return (weighted_avg([p[0] for p in partials], w),
                    weighted_avg([p[1] for p in partials], w), losses)

    # ----- fused path -----

    def _client_arrays(self, client: SimClient,
                       tier: Optional[str]) -> Dict[str, np.ndarray]:
        if tier is not None:
            data = dict(client.data)
            data.update(feature_batch_arrays(self.features_for(client, tier)))
            return data
        return client.data

    def _group_loss_fn(self, tier: Optional[str]) -> LossFn:
        """The group's loss: cached groups consume encoded features with
        dequantization fused in-graph (fl/quant.make_tiered_loss)."""
        if tier is None:
            return self.loss_fn
        return make_tiered_loss(self.cached_loss_fn, tier, self.compute_dtype,
                                use_pallas=self.use_pallas)

    def _run_fused(self, clients, cids, params, state, round_idx, *, tier,
                   faults=None):
        codes = corrupt_codes(faults, cids)
        defended = (self.screen or self.aggregator != "mean"
                    or codes is not None)
        n_shards = client_axis_size(self.mesh)
        pad = (-len(cids)) % n_shards if n_shards > 1 else 0
        with self.spans.span("engine.gather"):
            stacked, nb_live, weights = self._gather(clients, cids,
                                                     round_idx, tier, pad)
            if pad:
                # the pad rows are inert: nb_live=0 masks every local step
                # and weight=0 zeroes the Eq. 1 contribution
                nb_live = np.concatenate([nb_live, np.zeros(pad, np.int32)])
            w_in = (np.concatenate([weights, np.zeros(pad, np.float32)])
                    if pad else weights)
            if codes is not None and pad:
                codes = np.concatenate([codes, np.zeros(pad, np.int32)])
        key = "fused" if tier is None else f"fused_cached_{tier}"
        if self.use_pallas:
            key += "|pallas"
        if defended:
            # an undefended engine round keeps the LEGACY compiled fn (and
            # its bit-exact trajectory); the defended build is keyed by its
            # defense config so faulted and clean rounds don't retrace each
            # other's variant
            key += (f"|scr{int(self.screen)}|agg:{self.aggregator}"
                    f"|flt{int(codes is not None)}")
        fn = self._jit_cache.get(key)
        if fn is None:
            fn = make_fused_round(self._group_loss_fn(tier),
                                  self.optimizer, clip_norm=self.clip_norm,
                                  compress_ratio=self.compress_ratio,
                                  compute_dtype=self.compute_dtype,
                                  mesh=self.mesh,
                                  screen=self.screen if defended else False,
                                  screen_norm_mult=self.screen_norm_mult,
                                  aggregator=(self.aggregator if defended
                                              else "mean"),
                                  trim_beta=self.trim_beta,
                                  inject_faults=codes is not None,
                                  fault_amplify=self.fault_amplify,
                                  use_pallas=self.use_pallas)
            self._jit_cache[key] = fn
        cached = tier is not None
        frozen = {} if cached else (self.frozen if self.frozen is not None else {})
        # the group's buffers leave the pool until the sync: a round that
        # raises before it leaves them out, so the next allocates afresh
        in_flight = {(tier, k): self._stage.pop((tier, k)) for k in stacked}
        with self.spans.span("engine.put"):
            host = [*stacked.values(), nb_live, w_in]
            if codes is not None:
                host.append(codes)
            self.spans.add("engine.h2d_bytes", sum(a.nbytes for a in host))
            batches = {k: jnp.asarray(v) for k, v in stacked.items()}
            nb_dev, w_dev = jnp.asarray(nb_live), jnp.asarray(w_in)
            codes_dev = None if codes is None else jnp.asarray(codes)
            if n_shards > 1:
                # explicit placement: cohort-stacked rows partition along
                # the client axis, model trees replicate — no implicit
                # resharding inside the dispatch
                params, frozen, state = replicate(self.mesh,
                                                  (params, frozen, state))
                batches, nb_dev, w_dev = shard_cohort(
                    self.mesh, (batches, nb_dev, w_dev))
                if codes_dev is not None:
                    codes_dev = shard_cohort(self.mesh, codes_dev)
        args = (params, frozen, state, batches, nb_dev, w_dev)
        with self.spans.span("engine.dispatch"):
            if self.compress_ratio is not None:
                p_g, s_g, l_g = self._dispatch_compressed(fn, args, cids, pad)
            else:
                out = (fn(*args, codes_dev) if codes_dev is not None
                       else fn(*args))
                if defended:
                    # every defended build returns a uniform 4-tuple; the
                    # mean builds are host wrappers that already recombined
                    # the kept rows whenever screening fired
                    p_g, s_g, l_g, keep = out
                    if self.screen:
                        k_host = np.asarray(keep)[:len(cids)]
                        # True == this client's update was screened OUT
                        self.last_screened.update(
                            {cid: not bool(k_host[i])
                             for i, cid in enumerate(cids)})
                else:
                    p_g, s_g, l_g = out
        self.last_uplink_bytes += self._uplink_bytes(params, len(cids))
        # ONE blocking sync for the whole cohort (padded rows sliced off)
        with self.spans.span("engine.sync"):
            l_host = np.asarray(l_g)[:len(cids)]
        self._stage.update(in_flight)
        return (p_g, s_g, {cid: float(l_host[i]) for i, cid in enumerate(cids)},
                float(weights.sum()))

    def _gather(self, clients, cids, round_idx, tier, pad=0):
        """The cohort's minibatch sequences stacked along a leading client
        axis, each client's rows taken with one copy into the engine's
        staging buffer for the key (exhausted clients cycle their plan;
        their steps are masked), then ``pad`` rows repeating row 0 for the
        client mesh; with the live step counts and Eq. 1 weights."""
        bs, ep = self.batch_size, self.local_epochs
        plans = [batch_index_plan(clients[cid].num_samples, bs, ep,
                                  clients[cid].round_seed(round_idx))
                 for cid in cids]
        nb_live = np.asarray([len(plan) for plan in plans], np.int32)
        nb = max(int(nb_live.max()), 1)
        idx = np.zeros((len(cids), nb * bs), np.int64)
        for j, plan in enumerate(plans):
            if plan:
                idx[j] = np.concatenate(
                    [plan[t % len(plan)] for t in range(nb)])
        arrays = [self._client_arrays(clients[cid], tier) for cid in cids]
        stacked: Dict[str, np.ndarray] = {}
        for key in arrays[0]:
            cols = [a[key] for a in arrays]
            buf = self._stage_buffer(
                (tier, key), (len(cids) + pad, nb, bs) + cols[0].shape[1:],
                np.result_type(*{c.dtype for c in cols}))
            rows = buf.reshape((len(buf), nb * bs) + buf.shape[3:])
            for j, data in enumerate(cols):
                # the plan's indices lie below ``num_samples``, so on an
                # array that long "clip" takes the rows "raise" would,
                # without buffering ``out``
                if len(data) < clients[cids[j]].num_samples:
                    raise IndexError(f"client {cids[j]}'s {key!r} has "
                                     f"{len(data)} rows, fewer than its "
                                     f"{clients[cids[j]].num_samples} "
                                     f"samples")
                np.take(np.asarray(data, buf.dtype), idx[j], axis=0,
                        mode="clip", out=rows[j])
            buf[len(cids):] = buf[0]
            stacked[key] = buf
        weights = np.asarray([clients[cid].num_samples for cid in cids],
                             np.float32)
        return stacked, nb_live, weights

    def _stage_buffer(self, slot, shape, dtype) -> np.ndarray:
        """The staging buffer for ``slot``, allocated anew only when the
        cohort's shape or dtype changes."""
        buf = self._stage.get(slot)
        if buf is None or buf.shape != shape or buf.dtype != dtype:
            buf = np.empty(shape, dtype)
            self._stage[slot] = buf
            self.spans.add("engine.stage_alloc_bytes", buf.nbytes)
        return buf

    def _dispatch_compressed(self, fn, args, cids, pad):
        """The compressed round: gather the cohort's error-feedback rows,
        run ``fn``, scatter the new rows back to the resident pools."""
        residuals, rows = self._gather_residuals(cids, args[0])
        if pad:
            residuals = jax.tree.map(
                lambda r: jnp.concatenate(
                    [r, jnp.zeros((pad, r.shape[1]), r.dtype)]),
                residuals)
        n_shards = client_axis_size(self.mesh)
        if n_shards > 1:
            residuals = shard_cohort(self.mesh, residuals)
        p_g, s_g, l_g, new_r = fn(*args, residuals)
        if pad:
            new_r = jax.tree.map(lambda r: r[:len(cids)], new_r)
        if n_shards > 1:
            # bring the sharded residual rows back to the resident
            # single-device pools (one host round-trip per round; the
            # pools themselves are not sharded — they index by client
            # id, not cohort slot)
            new_r = jax.tree.map(lambda r: jnp.asarray(np.asarray(r)),
                                 new_r)
        self._scatter_residuals(rows, new_r)
        return p_g, s_g, l_g

    # ----- sequential escape hatch (deadline/straggler path) -----

    def _seq_step(self, tier: Optional[str]):
        key = "seq" if tier is None else f"seq_cached_{tier}"
        fn = self._jit_cache.get(key)
        if fn is None:
            loss_fn = make_input_cast_loss(self._group_loss_fn(tier),
                                           self.compute_dtype)
            cdt = (jnp.dtype(self.compute_dtype)
                   if self.compute_dtype is not None else None)

            def step(p, frozen, st, ost, batch):
                if cdt is None:
                    (loss, st2), grads = jax.value_and_grad(
                        loss_fn, has_aux=True)(p, frozen, st, batch)
                else:
                    # mixed precision mirrors make_fused_round: bf16
                    # forward/backward, f32 master params + optimizer state
                    (loss, st2), grads = jax.value_and_grad(
                        lambda pc: loss_fn(pc, cast_floating(frozen, cdt),
                                           st, batch),
                        has_aux=True)(cast_floating(p, cdt))
                    grads = jax.tree.map(lambda g, m: g.astype(m.dtype),
                                         grads, p)
                    st2 = jax.tree.map(lambda a, m: a.astype(m.dtype), st2, st)
                    loss = loss.astype(jnp.float32)
                grads, _ = clip_by_global_norm(grads, self.clip_norm)
                ups, ost2 = self.optimizer.update(grads, ost, p)
                return apply_updates(p, ups), st2, ost2, loss

            fn = self._jit_cache[key] = jax.jit(step)
        return fn

    def _seq_compress(self):
        """Per-client jitted compress step for the sequential path — same
        ``ingraph_compress_leaf`` math as the fused dispatch (K=1), so
        sequential and fused compressed rounds agree."""
        fn = self._jit_cache.get("seq_compress")
        if fn is None:
            ratio = self.compress_ratio
            use_pallas = self.use_pallas

            def comp(params, p_i, res_leaves):
                leaves, treedef = jax.tree.flatten(params)
                new_p, new_r = [], []
                for p0, pi, r in zip(leaves, jax.tree.leaves(p_i), res_leaves):
                    sent, r_new, _, _ = ingraph_compress_leaf(
                        p0.astype(jnp.float32).reshape(-1),
                        pi.astype(jnp.float32).reshape(1, -1), r[None, :],
                        jnp.ones(1, jnp.float32), ratio,
                        use_pallas=use_pallas)
                    new_p.append(sent.reshape(p0.shape).astype(p0.dtype))
                    new_r.append(r_new[0])
                return jax.tree.unflatten(treedef, new_p), new_r

            fn = self._jit_cache["seq_compress"] = jax.jit(comp)
        return fn

    def _robust_combine(self):
        """Jitted robust aggregate over ALREADY-KEPT sequential updates —
        the same ``_robust_leaf`` order statistics the fused dispatch uses
        (host screening removed the masked rows, so keep is all-true)."""
        fn = self._jit_cache.get("robust_combine")
        if fn is None:
            agg_name, beta = self.aggregator, self.trim_beta

            def comb(p_trees, s_trees):
                n = len(p_trees)
                keep = jnp.ones(n, bool)
                nv = jnp.int32(n)
                rob = lambda x: _robust_leaf(x, keep, nv, agg_name, beta)
                sp = jax.tree.map(lambda *xs: jnp.stack(xs), *p_trees)
                ss = jax.tree.map(lambda *xs: jnp.stack(xs), *s_trees)
                return jax.tree.map(rob, sp), jax.tree.map(rob, ss)

            fn = self._jit_cache["robust_combine"] = jax.jit(comb)
        return fn

    def _host_keep(self, norms, l_arr, w_arr):
        """Numpy mirror of the in-graph ``_keep_mask`` (same lower-median /
        mult semantics), so sequential and fused rounds screen the same
        clients."""
        finite = np.isfinite(norms) & np.isfinite(l_arr)
        valid = finite & (w_arr > 0)
        if not self.screen:
            # robust aggregators always exclude non-finite rows (they
            # would poison the order statistics); the plain mean without
            # screening lets corruption through — the divergence arm
            return valid if self.aggregator != "mean" else (w_arr > 0)
        n_v = int(valid.sum())
        med = np.sort(np.where(valid, norms, np.inf))[max(n_v - 1, 0) // 2]
        outlier = bool(np.isfinite(med)) & (
            norms > self.screen_norm_mult * med + 1e-6)
        return valid & ~outlier

    def _run_sequential(self, clients, cids, params, state, round_idx, *,
                        tier, faults=None):
        step = self._seq_step(tier)
        frozen = ({} if tier is not None
                  else (self.frozen if self.frozen is not None else {}))
        faults = faults or {}
        defended = (self.screen or self.aggregator != "mean"
                    or any(cid in faults for cid in cids))
        updates, weights, losses = [], [], {}
        for cid in cids:
            c = clients[cid]
            data = self._client_arrays(c, tier)
            p_i, s_i = params, state
            ost = self.optimizer.init(params)
            batch_losses = []
            for idx in batch_index_plan(c.num_samples, self.batch_size,
                                        self.local_epochs,
                                        c.round_seed(round_idx)):
                jb = {k: jnp.asarray(v[idx]) for k, v in data.items()}
                p_i, s_i, ost, loss = step(p_i, frozen, s_i, ost, jb)
                batch_losses.append(float(loss))
            if self.compress_ratio is not None:
                rows = self._residual_rows([cid], jax.tree.leaves(params))
                p_i, new_r = self._seq_compress()(
                    params, p_i, [p[rows[0]] for p in self._res_pool])
                self._res_pool = [p.at[rows[0]].set(r) for p, r in
                                  zip(self._res_pool, new_r)]
            loss_i = float(np.mean(batch_losses)) if batch_losses else 0.0
            kind = faults.get(cid)
            if kind is not None:
                # host-side twin of the in-graph fault_codes transform
                p_i = apply_fault_to_update(kind, params, p_i,
                                            amplify=self.fault_amplify)
                if kind in ("nan", "inf"):
                    loss_i = float("nan")
            updates.append((p_i, s_i))
            weights.append(c.num_samples)
            losses[cid] = loss_i
        self.last_uplink_bytes += self._uplink_bytes(params, len(cids))
        w_arr = np.asarray(weights, np.float64)
        if defended:
            norm_fn = self._jit_cache.setdefault(
                "delta_norm", jax.jit(_delta_norm_one))
            norms = np.asarray([float(norm_fn(params, u[0]))
                                for u in updates])
            l_arr = np.asarray([losses[cid] for cid in cids])
            keep = self._host_keep(norms, l_arr, w_arr)
            if self.screen:
                self.last_screened.update(
                    {cid: not bool(keep[i]) for i, cid in enumerate(cids)})
            if not keep.any():
                # every update screened out: the group is a no-op (the
                # fused path's in-graph `safe` fallback), weight unchanged
                return params, state, losses, float(w_arr.sum())
            if self.aggregator != "mean":
                kept = [u for u, k in zip(updates, keep) if k]
                p_g, s_g = self._robust_combine()([u[0] for u in kept],
                                                  [u[1] for u in kept])
                return p_g, s_g, losses, float(w_arr.sum())
            if not keep.all():
                kept_w = w_arr[keep]
                kept = [u for u, k in zip(updates, keep) if k]
                w = kept_w / kept_w.sum()
                return (weighted_avg([u[0] for u in kept], w),
                        weighted_avg([u[1] for u in kept], w), losses,
                        float(w_arr.sum()))
            # all kept + mean -> fall through to the EXACT legacy combine
            # (zero-fault bit-identity on the sequential path too)
        w = w_arr / w_arr.sum()
        return (weighted_avg([u[0] for u in updates], w),
                weighted_avg([u[1] for u in updates], w), losses,
                float(np.sum(weights)))


# ---------------------------------------------------------------------------
# LM backend: cached-prefix federated round (reuses core/freezing.py's
# pod-fused make_fed_round_step shape; consumes features instead)
# ---------------------------------------------------------------------------


def make_lm_cached_fed_round_step(model, plan, local_opt: Optimizer, *,
                                  num_pods: int, local_steps: int,
                                  remat: bool = True, clip_norm: float = 1.0,
                                  constrain_podded=None, remat_policy=None,
                                  donate: bool = True,
                                  feature_tier: str = "f32",
                                  compute_dtype: Optional[str] = None):
    """Cached sibling of ``freezing.make_fed_round_step``: the batch carries
    ``h0``/``aux0`` (frozen-prefix outputs, computed once per stage via
    ``freezing.stage_prefix_features``) with leading dims
    [num_pods, local_steps, ...]; only the active suffix is executed and
    differentiated. Jitted with ``donate_argnums`` on the active params (the
    per-pod optimizer state is born and dies inside the jit).

    ``feature_tier`` selects the cache storage precision (fl/quant.py):
    with ``"fp16"`` the batch's ``h0`` arrives f16, with ``"int8"`` it
    arrives int8 alongside ``h0_scale`` (``quantize_int8`` of the prefix
    output) and is dequantized INSIDE the compiled step — the f32/bf16
    feature tensor never exists outside the dispatch. ``compute_dtype``
    overrides the dtype the decoded features (and the active params) are
    evaluated in; default keeps the model's native compute dtype.

    Requires a static prefix — caching under a training embedding (stage 0)
    or a weight-tied shared-attention prefix (zamba2) would silently train
    on stale features, so that is rejected here."""
    from repro.core.freezing import cached_stage_loss_fn, prefix_is_static

    if not prefix_is_static(plan):
        raise ValueError(
            f"stage {plan.stage}: frozen prefix is not a fixed feature "
            "extractor (training embedding or tied shared-attention in the "
            "prefix) — use freezing.make_fed_round_step instead")

    feature_tier = normalize_tier(feature_tier) or "f32"
    base_loss = cached_stage_loss_fn(model, plan, remat=remat,
                                     remat_policy=remat_policy)
    h_dt = jnp.dtype(compute_dtype or model.cfg.compute_dtype)
    cdt = jnp.dtype(compute_dtype) if compute_dtype is not None else None

    def loss_fn(act, batch):
        if feature_tier == "f32":
            return base_loss(act, batch)
        b = dict(batch)
        if feature_tier == "int8":
            b["h0"] = (b["h0"].astype(jnp.float32)
                       * b.pop("h0_scale").astype(jnp.float32)).astype(h_dt)
        else:  # fp16
            b["h0"] = b["h0"].astype(h_dt)
        return base_loss(act, b)

    def local_train(active, batches):
        opt_state = local_opt.init(active)

        def one(carry, batch):
            act, ost = carry
            if cdt is None:
                loss, grads = jax.value_and_grad(loss_fn)(act, batch)
            else:
                loss, grads = jax.value_and_grad(loss_fn)(
                    cast_floating(act, cdt), batch)
                grads = jax.tree.map(lambda g, m: g.astype(m.dtype),
                                     grads, act)
                loss = loss.astype(jnp.float32)
            grads, _ = clip_by_global_norm(grads, clip_norm)
            ups, ost = local_opt.update(grads, ost, act)
            return (apply_updates(act, ups), ost), loss

        (active, _), losses = jax.lax.scan(one, (active, opt_state), batches)
        return active, jnp.mean(losses)

    def round_step(active, batch, weights):
        podded = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (num_pods,) + x.shape), active)
        if constrain_podded is not None:
            podded = constrain_podded(podded)
        podded, losses = jax.vmap(local_train, in_axes=(0, 0))(podded, batch)
        w = (weights / jnp.sum(weights)).astype(jnp.float32)

        def agg(x):
            return jnp.einsum("p,p...->...", w,
                              x.astype(jnp.float32)).astype(x.dtype)

        return jax.tree.map(agg, podded), {"loss": jnp.sum(w * losses)}

    donate = donate and jax.default_backend() != "cpu"
    return jax.jit(round_step, donate_argnums=(0,) if donate else ())
