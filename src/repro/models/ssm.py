"""State-space / recurrent layers: Mamba2 (SSD) and xLSTM (mLSTM + sLSTM).

Training/prefill uses *chunked* linear-attention forms (quadratic only within
a chunk, recurrent across chunks) so prefill_32k / train_4k never materialize
S×S score matrices. Decode uses O(1)-state single-step recurrences — this is
what makes long_500k runnable for the ssm/hybrid archs.

All recurrence math runs in fp32 with log-space decay (segsum) stabilizers.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.layers import (activation, causal_conv1d, causal_conv1d_init,
                                 causal_conv1d_step, dense, dense_init, rmsnorm,
                                 rmsnorm_init)
from repro.models.module import PFac, Params

SSM_CHUNK = 256


def _segsum(log_a: jnp.ndarray) -> jnp.ndarray:
    """Stable segment-sum: out[..., i, j] = sum_{k=j+1..i} log_a[..., k] for
    i >= j, -inf otherwise. log_a: [..., L] -> [..., L, L]."""
    L = log_a.shape[-1]
    cum = jnp.cumsum(log_a, axis=-1)
    diff = cum[..., :, None] - cum[..., None, :]  # i, j -> sum_{j+1..i}
    mask = jnp.tril(jnp.ones((L, L), bool), k=0)
    return jnp.where(mask, diff, -jnp.inf)


# ===========================================================================
# Mamba2 (SSD)
# ===========================================================================


def mamba2_init(fac: PFac, cfg: ArchConfig) -> Params:
    D = cfg.d_model
    d_inner = cfg.ssm_expand * D
    nheads = d_inner // cfg.ssm_head_dim
    N = cfg.ssm_state
    conv_ch = d_inner + 2 * N  # x, B, C all convolved
    return {
        "in_proj": dense_init(fac, "in_proj", D, 2 * d_inner + 2 * N + nheads,
                              ("qkv_in", "mlp")),
        "conv": causal_conv1d_init(fac, "conv", conv_ch, cfg.conv_kernel),
        "A_log": fac.param("A_log", (nheads,), (None,), init="zeros", dtype=jnp.float32),
        "D": fac.param("D", (nheads,), (None,), init="ones", dtype=jnp.float32),
        "dt_bias": fac.param("dt_bias", (nheads,), (None,), init="zeros", dtype=jnp.float32),
        "norm": rmsnorm_init(fac, "norm", d_inner),
        "out_proj": dense_init(fac, "out_proj", d_inner, D, ("mlp", "attn_out")),
    }


def _mamba2_split(p: Params, u: jnp.ndarray, cfg: ArchConfig):
    D = cfg.d_model
    d_inner = cfg.ssm_expand * D
    nheads = d_inner // cfg.ssm_head_dim
    N = cfg.ssm_state
    zxbcdt = dense(p["in_proj"], u)
    z, xbc, dt = jnp.split(zxbcdt, [d_inner, 2 * d_inner + 2 * N], axis=-1)
    return z, xbc, dt, d_inner, nheads, N


def mamba2_forward(p: Params, u: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    """u: [B, S, D] -> [B, S, D] via chunked SSD."""
    Bsz, S, D = u.shape
    z, xbc, dt, d_inner, nheads, N = _mamba2_split(p, u, cfg)
    xbc = jax.nn.silu(causal_conv1d(p["conv"], xbc))
    x, Bm, Cm = jnp.split(xbc, [d_inner, d_inner + N], axis=-1)
    hd = cfg.ssm_head_dim
    x = x.reshape(Bsz, S, nheads, hd)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # [B,S,H]
    A = -jnp.exp(p["A_log"])  # [H], negative
    log_a = (dt * A).astype(jnp.float32)  # [B,S,H] log decay per step

    with jax.named_scope("ssd"):
        y = _ssd_chunked(x, Bm, Cm, dt, log_a, chunk=min(cfg.ssm_chunk, S))
    y = y + (p["D"][:, None] * x.astype(jnp.float32)).astype(y.dtype)
    y = y.reshape(Bsz, S, d_inner)
    y = rmsnorm(p["norm"], y * jax.nn.silu(z), cfg.norm_eps)
    return dense(p["out_proj"], y)


def _ssd_chunked(x, Bm, Cm, dt, log_a, *, chunk: int):
    """SSD scan. x: [B,S,H,hd]; Bm/Cm: [B,S,N]; dt/log_a: [B,S,H].

    Returns y: [B,S,H,hd]. State h: [B,H,hd,N].
    """
    Bsz, S, H, hd = x.shape
    N = Bm.shape[-1]
    assert S % chunk == 0, (S, chunk)
    n = S // chunk
    xc = x.reshape(Bsz, n, chunk, H, hd).astype(jnp.float32)
    Bc = Bm.reshape(Bsz, n, chunk, N).astype(jnp.float32)
    Cc = Cm.reshape(Bsz, n, chunk, N).astype(jnp.float32)
    dtc = dt.reshape(Bsz, n, chunk, H)
    lac = log_a.reshape(Bsz, n, chunk, H)

    # intra-chunk (quadratic within chunk): y_intra[i] = sum_{j<=i} C_i.B_j L_ij dt_j x_j
    Lseg = _segsum(lac.transpose(0, 1, 3, 2))  # [B,n,H,c,c]
    att = jnp.einsum("bncN,bnmN->bncm", Cc, Bc)[:, :, None] * jnp.exp(Lseg)
    y_intra = jnp.einsum("bnhcm,bnmh,bnmhd->bnchd", att, dtc, xc)

    # chunk-final states: S_k = sum_j prod_{l>j} a_l dt_j x_j B_j^T
    tail = jnp.cumsum(lac, axis=2)
    tail = tail[:, :, -1:, :] - tail  # sum of log_a after position j
    w = jnp.exp(tail) * dtc  # [B,n,c,H]
    chunk_state = jnp.einsum("bnch,bnchd,bncN->bnhdN", w, xc, Bc)
    chunk_decay = jnp.exp(jnp.sum(lac, axis=2))  # [B,n,H]

    # inter-chunk recurrence over n chunks
    def body(h, inputs):
        st, dec = inputs
        h_new = dec[..., None, None] * h + st
        return h_new, h  # emit state *entering* the chunk

    h0 = jnp.zeros((Bsz, H, hd, N), jnp.float32)
    _, h_enter = jax.lax.scan(body, h0,
                              (chunk_state.transpose(1, 0, 2, 3, 4),
                               chunk_decay.transpose(1, 0, 2)))
    h_enter = h_enter.transpose(1, 0, 2, 3, 4)  # [B,n,H,hd,N]

    # inter-chunk contribution: y_inter[i] = C_i . (prod_{l<=i} a_l) h_enter
    head = jnp.cumsum(lac, axis=2)  # sum log_a up to and incl. i
    y_inter = jnp.einsum("bncN,bnch,bnhdN->bnchd", Cc, jnp.exp(head), h_enter)
    y = (y_intra + y_inter).reshape(Bsz, S, H, hd)
    return y.astype(x.dtype)


def mamba2_init_state(cfg: ArchConfig, batch: int, dtype) -> Dict:
    d_inner = cfg.ssm_expand * cfg.d_model
    nheads = d_inner // cfg.ssm_head_dim
    N = cfg.ssm_state
    conv_ch = d_inner + 2 * N
    return {"h": jnp.zeros((batch, nheads, cfg.ssm_head_dim, N), jnp.float32),
            "conv": jnp.zeros((batch, cfg.conv_kernel - 1, conv_ch), dtype)}


def mamba2_step(p: Params, u: jnp.ndarray, state: Dict, cfg: ArchConfig
                ) -> Tuple[jnp.ndarray, Dict]:
    """Single decode step. u: [B, 1, D]."""
    Bsz = u.shape[0]
    z, xbc, dt, d_inner, nheads, N = _mamba2_split(p, u[:, 0, :], cfg)
    xbc, conv_state = causal_conv1d_step(p["conv"], xbc, state["conv"])
    xbc = jax.nn.silu(xbc)
    x, Bm, Cm = jnp.split(xbc, [d_inner, d_inner + N], axis=-1)
    hd = cfg.ssm_head_dim
    x = x.reshape(Bsz, nheads, hd).astype(jnp.float32)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # [B,H]
    a = jnp.exp(dt * -jnp.exp(p["A_log"]))  # [B,H]
    h = a[..., None, None] * state["h"] + jnp.einsum(
        "bh,bhd,bN->bhdN", dt, x, Bm.astype(jnp.float32))
    y = jnp.einsum("bN,bhdN->bhd", Cm.astype(jnp.float32), h)
    y = y + p["D"][:, None] * x
    y = y.reshape(Bsz, 1, d_inner).astype(u.dtype)
    y = rmsnorm(p["norm"], y * jax.nn.silu(z[:, None, :]), cfg.norm_eps)
    return dense(p["out_proj"], y), {"h": h, "conv": conv_state}


# ===========================================================================
# mLSTM (xLSTM matrix-memory block)
# ===========================================================================


def mlstm_init(fac: PFac, cfg: ArchConfig) -> Params:
    D = cfg.d_model
    d_inner = cfg.ssm_expand * D
    nheads = max(cfg.num_heads, 1)
    return {
        "up_proj": dense_init(fac, "up_proj", D, 2 * d_inner, ("qkv_in", "mlp")),
        "conv": causal_conv1d_init(fac, "conv", d_inner, cfg.conv_kernel),
        "wq": dense_init(fac, "wq", d_inner, d_inner, (None, "heads")),
        "wk": dense_init(fac, "wk", d_inner, d_inner, (None, "heads")),
        "wv": dense_init(fac, "wv", d_inner, d_inner, (None, "heads")),
        "w_if": fac.param("w_if", (d_inner, 2 * nheads), (None, None), init="normal"),
        "b_if": fac.param("b_if", (2 * nheads,), (None,), init="zeros"),
        "norm": rmsnorm_init(fac, "norm", d_inner),
        "down_proj": dense_init(fac, "down_proj", d_inner, D, ("mlp", "attn_out")),
    }


def mlstm_forward(p: Params, u: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    """u: [B, S, D] -> [B, S, D] via chunked stabilized mLSTM."""
    Bsz, S, D = u.shape
    d_inner = cfg.ssm_expand * D
    H = max(cfg.num_heads, 1)
    hd = d_inner // H
    xz = dense(p["up_proj"], u)
    x, z = jnp.split(xz, 2, axis=-1)
    xc = jax.nn.silu(causal_conv1d(p["conv"], x))
    q = dense(p["wq"], xc).reshape(Bsz, S, H, hd)
    k = dense(p["wk"], xc).reshape(Bsz, S, H, hd) / jnp.sqrt(hd).astype(u.dtype)
    v = dense(p["wv"], x).reshape(Bsz, S, H, hd)
    gates = (xc @ p["w_if"].astype(xc.dtype) + p["b_if"].astype(xc.dtype)).astype(jnp.float32)
    log_i = gates[..., :H]  # pre-activation input gate (log-space)
    log_f = jax.nn.log_sigmoid(gates[..., H:])  # [B,S,H]

    y = _mlstm_chunked(q, k, v, log_i, log_f, chunk=min(SSM_CHUNK, S))
    y = y.reshape(Bsz, S, d_inner)
    y = rmsnorm(p["norm"], y, cfg.norm_eps) * jax.nn.silu(z)
    return dense(p["down_proj"], y)


def _mlstm_chunked(q, k, v, log_i, log_f, *, chunk: int):
    """Stabilized chunked mLSTM. q/k/v: [B,S,H,hd]; gates: [B,S,H].

    Within-chunk quadratic with decay matrix; cross-chunk recurrent matrix
    state C: [B,H,hd,hd], normalizer n: [B,H,hd]. Max-stabilizer folded into
    a per-position normalizer (denominator lower-bounded at exp(-m)·|qn|)."""
    Bsz, S, H, hd = q.shape
    n = S // chunk
    qc = q.reshape(Bsz, n, chunk, H, hd).astype(jnp.float32)
    kc = k.reshape(Bsz, n, chunk, H, hd).astype(jnp.float32)
    vc = v.reshape(Bsz, n, chunk, H, hd).astype(jnp.float32)
    lic = log_i.reshape(Bsz, n, chunk, H)
    lfc = log_f.reshape(Bsz, n, chunk, H)

    # decay matrix within chunk: D[i,j] = exp(sum_{l=j+1..i} log_f + log_i[j])
    Lseg = _segsum(lfc.transpose(0, 1, 3, 2))  # [B,n,H,c,c]
    logD = Lseg + lic.transpose(0, 1, 3, 2)[:, :, :, None, :]
    # stabilizer per query position
    m_intra = jnp.max(jnp.where(jnp.isfinite(logD), logD, -jnp.inf), axis=-1)
    head = jnp.cumsum(lfc, axis=2).transpose(0, 1, 3, 2)  # [B,n,H,c] decay to chunk start
    m = jnp.maximum(m_intra, head)  # conservative stabilizer, also covers inter term
    Dmat = jnp.exp(logD - m[..., None])
    scores = jnp.einsum("bnchd,bnmhd->bnhcm", qc, kc) * Dmat
    y_intra = jnp.einsum("bnhcm,bnmhd->bnchd", scores, vc)
    # intra normalizer: q_i . sum_j D_ij k_j == row-sum of the decayed scores
    n_intra = jnp.sum(scores, axis=-1)  # [B,n,H,c]

    # chunk-final state: C_k = sum_j exp(sum_{l>j} log_f + log_i[j]) k_j v_j^T
    tail = jnp.cumsum(lfc, axis=2)
    tail_total = tail[:, :, -1:, :]
    w = jnp.exp(tail_total - tail + lic)  # [B,n,c,H]
    chunk_C = jnp.einsum("bnch,bnchd,bnche->bnhde", w, kc, vc)
    chunk_N = jnp.einsum("bnch,bnchd->bnhd", w, kc)
    chunk_decay = jnp.exp(jnp.sum(lfc, axis=2))  # [B,n,H]

    def body(carry, inputs):
        C, Nrm = carry
        Ck, Nk, dec = inputs
        C_new = dec[..., None, None] * C + Ck
        N_new = dec[..., None] * Nrm + Nk
        return (C_new, N_new), (C, Nrm)

    C0 = jnp.zeros((Bsz, H, hd, hd), jnp.float32)
    N0 = jnp.zeros((Bsz, H, hd), jnp.float32)
    _, (C_enter, N_enter) = jax.lax.scan(
        body, (C0, N0),
        (chunk_C.transpose(1, 0, 2, 3, 4), chunk_N.transpose(1, 0, 2, 3),
         chunk_decay.transpose(1, 0, 2)))
    C_enter = C_enter.transpose(1, 0, 2, 3, 4)
    N_enter = N_enter.transpose(1, 0, 2, 3)

    inter_w = jnp.exp(head - m)  # [B,n,H,c]
    y_inter = jnp.einsum("bnchd,bnhc,bnhde->bnche", qc, inter_w, C_enter)
    n_inter = jnp.einsum("bnchd,bnhc,bnhd->bnch", qc, inter_w, N_enter)

    y = y_intra + y_inter
    denom = jnp.abs(n_intra.transpose(0, 1, 3, 2) + n_inter)  # [B,n,c,H]
    denom = jnp.maximum(denom, jnp.exp(-m.transpose(0, 1, 3, 2)))
    y = y / denom[..., None]
    return y.reshape(Bsz, S, H, hd).astype(q.dtype)


def mlstm_init_state(cfg: ArchConfig, batch: int, dtype) -> Dict:
    D = cfg.d_model
    d_inner = cfg.ssm_expand * D
    H = max(cfg.num_heads, 1)
    hd = d_inner // H
    return {"C": jnp.zeros((batch, H, hd, hd), jnp.float32),
            "n": jnp.zeros((batch, H, hd), jnp.float32),
            "m": jnp.full((batch, H), -jnp.inf, jnp.float32),
            "conv": jnp.zeros((batch, cfg.conv_kernel - 1, d_inner), dtype)}


def mlstm_step(p: Params, u: jnp.ndarray, state: Dict, cfg: ArchConfig
               ) -> Tuple[jnp.ndarray, Dict]:
    """Single decode step with the standard stabilized recurrence."""
    Bsz = u.shape[0]
    D = cfg.d_model
    d_inner = cfg.ssm_expand * D
    H = max(cfg.num_heads, 1)
    hd = d_inner // H
    xz = dense(p["up_proj"], u[:, 0, :])
    x, z = jnp.split(xz, 2, axis=-1)
    xc, conv_state = causal_conv1d_step(p["conv"], x, state["conv"])
    xc = jax.nn.silu(xc)
    q = dense(p["wq"], xc).reshape(Bsz, H, hd).astype(jnp.float32)
    k = (dense(p["wk"], xc).reshape(Bsz, H, hd) / jnp.sqrt(hd).astype(u.dtype)).astype(jnp.float32)
    v = dense(p["wv"], x).reshape(Bsz, H, hd).astype(jnp.float32)
    gates = (xc @ p["w_if"].astype(xc.dtype) + p["b_if"].astype(xc.dtype)).astype(jnp.float32)
    log_i, log_f = gates[..., :H], jax.nn.log_sigmoid(gates[..., H:])
    m_new = jnp.maximum(log_f + state["m"], log_i)
    i_s = jnp.exp(log_i - m_new)
    f_s = jnp.exp(log_f + state["m"] - m_new)
    C = f_s[..., None, None] * state["C"] + i_s[..., None, None] * jnp.einsum("bhd,bhe->bhde", k, v)
    nrm = f_s[..., None] * state["n"] + i_s[..., None] * k
    num = jnp.einsum("bhd,bhde->bhe", q, C)
    den = jnp.maximum(jnp.abs(jnp.sum(q * nrm, -1)), jnp.exp(-m_new))
    y = (num / den[..., None]).reshape(Bsz, 1, d_inner).astype(u.dtype)
    y = rmsnorm(p["norm"], y, cfg.norm_eps) * jax.nn.silu(z[:, None, :])
    return dense(p["down_proj"], y), {"C": C, "n": nrm, "m": m_new, "conv": conv_state}


# ===========================================================================
# sLSTM (xLSTM scalar-memory block; strictly sequential recurrence)
# ===========================================================================


def slstm_init(fac: PFac, cfg: ArchConfig) -> Params:
    D = cfg.d_model
    H = max(cfg.num_heads, 1)
    hd = D // H
    ff = int(D * 4 / 3 / 64) * 64 or 64  # xLSTM post-up FFN (4/3 factor)
    return {
        "conv": causal_conv1d_init(fac, "conv", D, cfg.conv_kernel),
        "w": fac.param("w", (D, 4 * D), (None, "heads"), init="normal"),
        "r": fac.param("r", (H, hd, 4 * hd), (None, None, None), init="normal", fan_in=hd),
        "b": fac.param("b", (4 * D,), (None,), init="zeros"),
        "norm": rmsnorm_init(fac, "norm", D),
        "ff_up": dense_init(fac, "ff_up", D, ff, (None, "mlp")),
        "ff_down": dense_init(fac, "ff_down", ff, D, ("mlp", None)),
    }


def _slstm_cell(p: Params, wx_t: jnp.ndarray, state, H: int, hd: int):
    """wx_t: [B, 4D] precomputed input contribution."""
    c, nrm, h, m = state
    Bsz = wx_t.shape[0]
    rh = jnp.einsum("bhd,hde->bhe", h, p["r"].astype(jnp.float32)).reshape(Bsz, 4 * H * hd)
    pre = (wx_t + rh).reshape(Bsz, H, hd, 4)
    zi, ii, fi, oi = pre[..., 0], pre[..., 1], pre[..., 2], pre[..., 3]
    log_f = jax.nn.log_sigmoid(fi)
    m_new = jnp.maximum(log_f + m, ii)
    i_s = jnp.exp(ii - m_new)
    f_s = jnp.exp(log_f + m - m_new)
    c_new = f_s * c + i_s * jnp.tanh(zi)
    n_new = f_s * nrm + i_s
    h_new = jax.nn.sigmoid(oi) * c_new / jnp.maximum(n_new, 1e-6)
    return (c_new, n_new, h_new, m_new), h_new


def slstm_forward(p: Params, u: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    Bsz, S, D = u.shape
    H = max(cfg.num_heads, 1)
    hd = D // H
    xc = jax.nn.silu(causal_conv1d(p["conv"], u))
    wx = (xc @ p["w"].astype(xc.dtype) + p["b"].astype(xc.dtype)).astype(jnp.float32)

    z0 = jnp.zeros((Bsz, H, hd), jnp.float32)
    state0 = (z0, z0, z0, jnp.full((Bsz, H, hd), -jnp.inf, jnp.float32))
    _, hs = jax.lax.scan(lambda s, w_t: _slstm_cell(p, w_t, s, H, hd),
                         state0, wx.transpose(1, 0, 2))
    y = hs.transpose(1, 0, 2, 3).reshape(Bsz, S, D).astype(u.dtype)
    y = rmsnorm(p["norm"], y, cfg.norm_eps)
    return dense(p["ff_down"], jax.nn.gelu(dense(p["ff_up"], y)))


def slstm_init_state(cfg: ArchConfig, batch: int, dtype) -> Dict:
    D = cfg.d_model
    H = max(cfg.num_heads, 1)
    hd = D // H
    z = jnp.zeros((batch, H, hd), jnp.float32)
    return {"c": z, "n": z, "h": z, "m": jnp.full((batch, H, hd), -jnp.inf, jnp.float32),
            "conv": jnp.zeros((batch, cfg.conv_kernel - 1, D), dtype)}


def slstm_step(p: Params, u: jnp.ndarray, state: Dict, cfg: ArchConfig
               ) -> Tuple[jnp.ndarray, Dict]:
    Bsz = u.shape[0]
    D = cfg.d_model
    H = max(cfg.num_heads, 1)
    hd = D // H
    xc, conv_state = causal_conv1d_step(p["conv"], u[:, 0, :], state["conv"])
    xc = jax.nn.silu(xc)
    wx = (xc @ p["w"].astype(xc.dtype) + p["b"].astype(xc.dtype)).astype(jnp.float32)
    st = (state["c"], state["n"], state["h"], state["m"])
    (c, nrm, h, m), h_out = _slstm_cell(p, wx, st, H, hd)
    y = h_out.reshape(Bsz, 1, D).astype(u.dtype)
    y = rmsnorm(p["norm"], y, cfg.norm_eps)
    y = dense(p["ff_down"], jax.nn.gelu(dense(p["ff_up"], y)))
    return y, {"c": c, "n": nrm, "h": h, "m": m, "conv": conv_state}
