"""Attention: GQA (llama/qwen/grok/hubert/...) and MLA (deepseek-v2/minicpm3).

Two execution paths per flavour:
  * full-sequence (train / prefill): causal or bidirectional, fp32 softmax;
  * decode: one new token against a KV cache (GQA: grouped-head einsum with no
    kv repeat; MLA: matrix-absorbed latent attention — scores computed in the
    compressed kv_lora space so the cache stays tiny).

Logical sharding axes used here:
  "heads"  — q-head dim (→ "model" when divisible, else unsharded)
  "qkv_in" — d_model reduction dim of the projections (fallback TP axis)
  "kv"     — kv-head dim
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models.layers import apply_rope, dense, dense_init, rmsnorm, rmsnorm_init
from repro.models.module import PFac, Params

NEG_INF = -1e9  # mask value (finite: avoids NaN rows for fully-masked queries)


def _shard_heads(x: jnp.ndarray, heads_dim: int = 2, *,
                 batch_axes=("pod", "data")) -> jnp.ndarray:
    """Constrain batch (dim0) + heads dims of attention intermediates.

    Without this, GQA with replicated kv (kv_heads < model axis) lets GSPMD
    pick *replicated* S×S attention scores — 100+ GB/device at 4k seq. The
    batch axes come from cfg.batch_axes so the constraint stays valid inside
    the federated vmap-over-pods (("data",) there — pod is consumed by vmap).
    """
    try:
        mesh = jax.sharding.get_abstract_mesh()
        names = list(mesh.axis_names)
        shape = dict(mesh.shape)
    except Exception:  # noqa: BLE001 — no ambient mesh (tests / CPU path)
        return x
    if not names:
        return x
    from jax.sharding import PartitionSpec as P

    spec = [None] * x.ndim
    baxes = tuple(a for a in batch_axes if a in names)
    if baxes:
        size = 1
        for a in baxes:
            size *= shape[a]
        if x.shape[0] % size == 0 and x.shape[0] >= size:
            spec[0] = baxes if len(baxes) > 1 else baxes[0]
    msize = shape.get("model", 0)
    H = x.shape[heads_dim]
    if msize and H % msize == 0 and H >= msize:
        spec[heads_dim] = "model"
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


# ===========================================================================
# GQA
# ===========================================================================


def gqa_init(fac: PFac, cfg: ArchConfig) -> Params:
    d, nq, nkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    return {
        "wq": dense_init(fac, "wq", d, nq * hd, ("qkv_in", "heads"), bias=cfg.qkv_bias),
        "wk": dense_init(fac, "wk", d, nkv * hd, ("qkv_in", "kv"), bias=cfg.qkv_bias),
        "wv": dense_init(fac, "wv", d, nkv * hd, ("qkv_in", "kv"), bias=cfg.qkv_bias),
        "wo": dense_init(fac, "wo", nq * hd, d, ("heads", "attn_out")),
    }


def _split_heads(x: jnp.ndarray, n: int) -> jnp.ndarray:
    return x.reshape(*x.shape[:-1], n, x.shape[-1] // n)


# ---------------------------------------------------------------------------
# Blockwise (online-softmax / flash-style) attention — pure JAX.
#
# Never materializes the S x S score matrix: outer lax.map over q blocks,
# inner lax.scan over kv blocks carrying (running max, denom, weighted acc).
# This is the XLA reference of kernels/flash_attention.py and the default
# full-sequence path for S >= ATTN_BLOCK_THRESHOLD (prefill_32k is infeasible
# without it). Causal masking is by absolute position; fully-masked kv blocks
# are computed-and-masked (structured skip belongs to the Pallas kernel).
# ---------------------------------------------------------------------------

ATTN_BLOCK_THRESHOLD = 2048
BLOCK_Q = 512
BLOCK_K = 1024


def blockwise_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                        causal: bool, scale: float,
                        block_q: int = BLOCK_Q, block_k: int = BLOCK_K
                        ) -> jnp.ndarray:
    """q: [B,S,H,dk]; k: [B,S,H,dk]; v: [B,S,H,dv] -> [B,S,H,dv]."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    bq = min(block_q, S)
    while S % bq:
        bq //= 2
    bk = min(block_k, S)
    while S % bk:
        bk //= 2
    nq, nk = S // bq, S // bk
    qb = q.reshape(B, nq, bq, H, dk).transpose(1, 0, 2, 3, 4)
    kb = k.reshape(B, nk, bk, H, dk).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nk, bk, H, dv).transpose(1, 0, 2, 3, 4)

    def one_q_block(args):
        qi, qblk = args  # qblk: [B, bq, H, dk]

        @jax.checkpoint
        def kv_step(carry, args2):
            m, l, acc = carry
            kj, kblk, vblk = args2
            s = jnp.einsum("bqhd,bkhd->bhqk", qblk, kblk).astype(jnp.float32) * scale
            if causal:
                pos_q = qi * bq + jnp.arange(bq)
                pos_k = kj * bk + jnp.arange(bk)
                s = jnp.where(pos_q[:, None] >= pos_k[None, :], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(qblk.dtype), vblk).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, bq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, H, bq), jnp.float32)
        a0 = jnp.zeros((B, H, bq, dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      (jnp.arange(nk), kb, vb))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.transpose(0, 2, 1, 3).astype(q.dtype)  # [B, bq, H, dv]

    outs = jax.lax.map(one_q_block, (jnp.arange(nq), qb))  # [nq, B, bq, H, dv]
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, S, H, dv)


def gqa_scale(cfg: ArchConfig) -> float:
    """The softmax scale: the config's ``attention_multiplier``, else
    1/sqrt(head_dim)."""
    return cfg.attention_multiplier or 1.0 / float(np.sqrt(cfg.head_dim))


def gqa_forward(p: Params, x: jnp.ndarray, cfg: ArchConfig, *,
                positions: Optional[jnp.ndarray] = None,
                causal: bool = True) -> jnp.ndarray:
    """Full-sequence attention. x: [B, S, D] -> [B, S, D]."""
    B, S, _ = x.shape
    nq, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q = _split_heads(dense(p["wq"], x), nq)
    k = _split_heads(dense(p["wk"], x), nkv)
    v = _split_heads(dense(p["wv"], x), nkv)
    if cfg.position_embedding == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    g = nq // nkv
    # broadcast kv across the q-head group (repeat keeps the head dim = nq so
    # the "heads" sharding axis stays consistent through the whole layer)
    k = _shard_heads(jnp.repeat(k, g, axis=2), batch_axes=cfg.batch_axes)
    v = _shard_heads(jnp.repeat(v, g, axis=2), batch_axes=cfg.batch_axes)
    q = _shard_heads(q, batch_axes=cfg.batch_axes)
    scale = gqa_scale(cfg)
    if getattr(cfg, "attention_impl", "xla") == "pallas":
        # kernels/flash_attention.py via its differentiable ops wrapper
        # (custom_vjp, recompute backward through the XLA reference). kv is
        # already repeated to nq heads above, so the kernel runs with
        # group size 1; interpret mode executes the body off-TPU.
        from repro.kernels import ops as kernel_ops
        out = kernel_ops.flash_attention(q, k, v, causal, scale)
    elif S >= ATTN_BLOCK_THRESHOLD:
        out = blockwise_attention(q, k, v, causal=causal, scale=scale)
    else:
        scores = jnp.einsum("bsnh,btnh->bnst", q, k).astype(jnp.float32) * scale
        scores = _shard_heads(scores, heads_dim=1, batch_axes=cfg.batch_axes)
        if causal:
            mask = jnp.tril(jnp.ones((S, S), bool))
            scores = jnp.where(mask[None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        out = jnp.einsum("bnst,btnh->bsnh", probs, v)
    return dense(p["wo"], out.reshape(B, S, nq * hd))


def gqa_init_cache(cfg: ArchConfig, batch: int, max_seq: int, dtype) -> Dict:
    nkv, hd = cfg.num_kv_heads, cfg.head_dim
    return {"k": jnp.zeros((batch, max_seq, nkv, hd), dtype),
            "v": jnp.zeros((batch, max_seq, nkv, hd), dtype)}


def gqa_decode(p: Params, x: jnp.ndarray, cache: Dict, pos: jnp.ndarray,
               cfg: ArchConfig) -> Tuple[jnp.ndarray, Dict]:
    """One-token decode. x: [B, 1, D]; pos: scalar index of the new token."""
    B = x.shape[0]
    nq, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    g = nq // nkv
    S = cache["k"].shape[1]
    positions = jnp.full((B, 1), pos, jnp.int32)
    q = _split_heads(dense(p["wq"], x), nq)
    k_new = _split_heads(dense(p["wk"], x), nkv)
    if cfg.position_embedding == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k_new = apply_rope(k_new, positions, cfg.rope_theta)
    v_new = _split_heads(dense(p["wv"], x), nkv)
    k = jax.lax.dynamic_update_slice(cache["k"], k_new.astype(cache["k"].dtype), (0, pos, 0, 0))
    v = jax.lax.dynamic_update_slice(cache["v"], v_new.astype(cache["v"].dtype), (0, pos, 0, 0))
    qg = q.reshape(B, 1, nkv, g, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg, k).astype(jnp.float32) * gqa_scale(cfg)
    valid = (jnp.arange(S) <= pos)[None, None, None, None, :]
    scores = jnp.where(valid, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v).reshape(B, 1, nq * hd)
    return dense(p["wo"], out), {"k": k, "v": v}


# ===========================================================================
# MLA (multi-head latent attention)
# ===========================================================================


def mla_init(fac: PFac, cfg: ArchConfig) -> Params:
    d, nh = cfg.d_model, cfg.num_heads
    nope, rope_d, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    p: Params = {}
    if cfg.q_lora_rank > 0:
        p["wq_a"] = dense_init(fac, "wq_a", d, cfg.q_lora_rank, ("qkv_in", None))
        p["q_norm"] = rmsnorm_init(fac, "q_norm", cfg.q_lora_rank)
        p["wq_b"] = dense_init(fac, "wq_b", cfg.q_lora_rank, nh * (nope + rope_d), (None, "heads"))
    else:
        p["wq"] = dense_init(fac, "wq", d, nh * (nope + rope_d), ("qkv_in", "heads"))
    p["wkv_a"] = dense_init(fac, "wkv_a", d, cfg.kv_lora_rank + rope_d, ("qkv_in", None))
    p["kv_norm"] = rmsnorm_init(fac, "kv_norm", cfg.kv_lora_rank)
    p["wkv_b"] = dense_init(fac, "wkv_b", cfg.kv_lora_rank, nh * (nope + vd), (None, "heads"))
    p["wo"] = dense_init(fac, "wo", nh * vd, d, ("heads", "attn_out"))
    return p


def _mla_q(p: Params, x: jnp.ndarray, cfg: ArchConfig, positions) -> Tuple[jnp.ndarray, jnp.ndarray]:
    nh, nope, rope_d = cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    if cfg.q_lora_rank > 0:
        q = dense(p["wq_b"], rmsnorm(p["q_norm"], dense(p["wq_a"], x), cfg.norm_eps))
    else:
        q = dense(p["wq"], x)
    q = q.reshape(*x.shape[:-1], nh, nope + rope_d)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    q_pe = apply_rope(q_pe, positions, cfg.rope_theta)
    return q_nope, q_pe


def _mla_kv_latent(p: Params, x: jnp.ndarray, cfg: ArchConfig, positions):
    """Compressed cache entries: normed c_kv and roped shared k_pe."""
    kv_a = dense(p["wkv_a"], x)
    c_kv = rmsnorm(p["kv_norm"], kv_a[..., :cfg.kv_lora_rank], cfg.norm_eps)
    k_pe = kv_a[..., cfg.kv_lora_rank:]  # [B, S, rope_d] single shared head
    k_pe = apply_rope(k_pe[..., None, :], positions, cfg.rope_theta)[..., 0, :]
    return c_kv, k_pe


def mla_forward(p: Params, x: jnp.ndarray, cfg: ArchConfig, *,
                positions: Optional[jnp.ndarray] = None,
                causal: bool = True) -> jnp.ndarray:
    """Full-sequence MLA with explicit k/v expansion (cheaper than absorption
    when S tokens each attend to S keys: score dim nope+rope << kv_lora)."""
    B, S, _ = x.shape
    nh, nope, rope_d, vd = cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q_nope, q_pe = _mla_q(p, x, cfg, positions)
    c_kv, k_pe = _mla_kv_latent(p, x, cfg, positions)
    kv = dense(p["wkv_b"], c_kv).reshape(B, S, nh, nope + vd)
    kv = _shard_heads(kv, batch_axes=cfg.batch_axes)
    q_nope = _shard_heads(q_nope, batch_axes=cfg.batch_axes)
    q_pe = _shard_heads(q_pe, batch_axes=cfg.batch_axes)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = 1.0 / float(np.sqrt(nope + rope_d))
    if S >= ATTN_BLOCK_THRESHOLD:
        # fold the shared rope head into the per-head k so MLA reuses the
        # same blockwise primitive: q' = [q_nope | q_pe], k' = [k_nope | k_pe]
        k_pe_b = jnp.broadcast_to(k_pe[:, :, None, :], (B, S, nh, rope_d))
        q_full = jnp.concatenate([q_nope, q_pe], axis=-1)
        k_full = jnp.concatenate([k_nope, k_pe_b], axis=-1)
        out = blockwise_attention(q_full, k_full, v, causal=causal, scale=scale)
    else:
        scores = (jnp.einsum("bsnh,btnh->bnst", q_nope, k_nope)
                  + jnp.einsum("bsnh,bth->bnst", q_pe, k_pe)).astype(jnp.float32) * scale
        scores = _shard_heads(scores, heads_dim=1, batch_axes=cfg.batch_axes)
        if causal:
            mask = jnp.tril(jnp.ones((S, S), bool))
            scores = jnp.where(mask[None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        out = jnp.einsum("bnst,btnh->bsnh", probs, v)
    return dense(p["wo"], out.reshape(B, S, nh * vd))


def mla_init_cache(cfg: ArchConfig, batch: int, max_seq: int, dtype) -> Dict:
    return {"ckv": jnp.zeros((batch, max_seq, cfg.kv_lora_rank), dtype),
            "kpe": jnp.zeros((batch, max_seq, cfg.qk_rope_dim), dtype)}


def mla_decode(p: Params, x: jnp.ndarray, cache: Dict, pos: jnp.ndarray,
               cfg: ArchConfig) -> Tuple[jnp.ndarray, Dict]:
    """Matrix-absorbed decode: attention runs in the kv_lora latent space, so
    per-step cost is O(S * (kv_lora + rope_d)) per head and the cache holds
    only the compressed latents."""
    B = x.shape[0]
    nh, nope, rope_d, vd = cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    lora = cfg.kv_lora_rank
    positions = jnp.full((B, 1), pos, jnp.int32)
    q_nope, q_pe = _mla_q(p, x, cfg, positions)  # [B,1,nh,nope],[B,1,nh,rope]
    c_new, kpe_new = _mla_kv_latent(p, x, cfg, positions)
    ckv = jax.lax.dynamic_update_slice(cache["ckv"], c_new.astype(cache["ckv"].dtype), (0, pos, 0))
    kpe = jax.lax.dynamic_update_slice(cache["kpe"], kpe_new.astype(cache["kpe"].dtype), (0, pos, 0))
    S = ckv.shape[1]
    wkv_b = p["wkv_b"]["w"].reshape(lora, nh, nope + vd).astype(x.dtype)
    wk_b, wv_b = wkv_b[..., :nope], wkv_b[..., nope:]
    # absorb k projection into q: q_lat [B,1,nh,lora]
    q_lat = jnp.einsum("bqnd,lnd->bqnl", q_nope, wk_b)
    scale = 1.0 / jnp.sqrt(jnp.float32(nope + rope_d))
    scores = (jnp.einsum("bqnl,bsl->bnqs", q_lat, ckv)
              + jnp.einsum("bqnh,bsh->bnqs", q_pe, kpe)).astype(jnp.float32) * scale
    valid = (jnp.arange(S) <= pos)[None, None, None, :]
    scores = jnp.where(valid, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out_lat = jnp.einsum("bnqs,bsl->bqnl", probs, ckv)
    out = jnp.einsum("bqnl,lnd->bqnd", out_lat, wv_b).reshape(B, 1, nh * vd)
    return dense(p["wo"], out), {"ckv": ckv, "kpe": kpe}


# ===========================================================================
# Dispatch helpers
# ===========================================================================


def attn_init(fac: PFac, cfg: ArchConfig) -> Params:
    return mla_init(fac, cfg) if cfg.attention == "mla" else gqa_init(fac, cfg)


def attn_forward(p: Params, x: jnp.ndarray, cfg: ArchConfig, **kw) -> jnp.ndarray:
    fn = mla_forward if cfg.attention == "mla" else gqa_forward
    return fn(p, x, cfg, **kw)


def attn_init_cache(cfg: ArchConfig, batch: int, max_seq: int, dtype) -> Dict:
    fn = mla_init_cache if cfg.attention == "mla" else gqa_init_cache
    return fn(cfg, batch, max_seq, dtype)


def attn_decode(p: Params, x: jnp.ndarray, cache: Dict, pos, cfg: ArchConfig):
    fn = mla_decode if cfg.attention == "mla" else gqa_decode
    return fn(p, x, cache, pos, cfg)
