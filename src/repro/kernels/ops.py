"""Jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to True off-TPU (on CPU the kernels execute their
bodies via the Pallas interpreter, for correctness validation); on a TPU
backend they compile to Mosaic.

``flash_attention`` is differentiable: custom_vjp whose backward recomputes
through the XLA blockwise reference (O(S) memory, exact).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import block_perturb, decode_attention as dec, flash_attention as fa
from repro.kernels import dequant_matmul as dqmm
from repro.kernels import sparse_agg
from repro.kernels import ssm_scan as ssd
from repro.kernels import ref


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


# ----- flash attention (differentiable) -----


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal=True, scale=None):
    return fa.flash_attention_fwd(q, k, v, causal=causal, scale=scale,
                                  interpret=_default_interpret())


def _fa_fwd(q, k, v, causal, scale):
    return flash_attention(q, k, v, causal, scale), (q, k, v)


def _fa_bwd(causal, scale, res, g):
    q, k, v = res
    _, vjp = jax.vjp(lambda q_, k_, v_: ref.flash_attention_ref(
        q_, k_, v_, causal=causal, scale=scale), q, k, v)
    return vjp(g)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# ----- flash decode -----


@jax.jit
def flash_decode(q, k, v, length):
    return dec.decode_attention(q, k, v, length,
                                interpret=_default_interpret())


# ----- ssd scan -----


@jax.jit
def ssd_scan(x, dt, log_a, Bm, Cm):
    return ssd.ssd_scan(x, dt, log_a, Bm, Cm,
                        interpret=_default_interpret())


# ----- block perturbation reductions -----


def update_sqnorm(tree_new, tree_old):
    """On-mesh half of the pace controller: fused ||new - old||^2."""
    return block_perturb.tree_diff_sqnorm(tree_new, tree_old,
                                          interpret=_default_interpret())


# ----- fused int8-dequant matmul (differentiable wrt scale and w) -----


def dequant_matmul(q, scale, w, *, block_m=256, block_n=256, block_k=256,
                   out_dtype=jnp.float32, interpret=None):
    """``(q.astype(f32) * scale) @ w`` with the per-(sample, channel) scales
    applied in-register inside the GEMM (kernels/dequant_matmul.py).

    ``q`` is cache DATA (int8 tier values) and is non-differentiable; the
    custom_vjp carries gradients for ``scale`` and ``w`` by differentiating
    the XLA reference (exact — same convention as ``flash_attention``'s
    recompute backward). ``interpret=None`` -> container-aware default
    (True off-TPU)."""
    interpret = _default_interpret() if interpret is None else interpret

    @jax.custom_vjp
    def _fn(scale_, w_):
        return dqmm.dequant_matmul_fwd(
            q, scale_, w_, block_m=block_m, block_n=block_n, block_k=block_k,
            out_dtype=out_dtype, interpret=interpret)

    def _fwd(scale_, w_):
        return _fn(scale_, w_), (scale_, w_)

    def _bwd(res, g):
        scale_, w_ = res
        _, vjp = jax.vjp(
            lambda s_, w2: ref.dequant_matmul_ref(q, s_, w2,
                                                  out_dtype=out_dtype),
            scale_, w_)
        return vjp(g)

    _fn.defvjp(_fwd, _bwd)
    return _fn(scale, w)


# ----- sparse cohort scatter-add (compressed-uplink Eq. 1 fold) -----


def sparse_cohort_add(idx, vals, weights, length, *, interpret=None):
    """One-kernel dense [length] fold of K clients' top-k (idx, vals) rows
    (kernels/sparse_agg.py). Dispatch rule: leaves whose dense block exceeds
    ``sparse_agg.MAX_VMEM_ELEMS`` fall back to the XLA scatter reference —
    the kernel keeps the whole dense output VMEM-resident, so it is only
    selected when that residency is possible."""
    if length > sparse_agg.MAX_VMEM_ELEMS:
        return ref.sparse_cohort_add_ref(idx, vals, weights, length)
    interpret = _default_interpret() if interpret is None else interpret
    return sparse_agg.sparse_cohort_add_fwd(idx, vals, weights, length,
                                            interpret=interpret)


# ----- int8 feature-cache quantization (reference entry) -----
# Per-(sample, channel) symmetric int8 for the frozen-prefix activation
# cache. No Pallas body: the op is an abs-max reduce + a broadcast multiply
# XLA already fuses into the consumer on every backend, so the jitted jnp
# form IS the kernel. Implementation lives in repro.fl.quant (imported
# lazily — kernels/ stays import-independent of fl/).


def quantize_int8(x):
    """(q int8, scale f32) — see ``repro.fl.quant.quantize_int8``."""
    from repro.fl.quant import quantize_int8 as impl
    return impl(x)


def dequantize_int8(q, scale):
    """Fused dequant — see ``repro.fl.quant.dequantize_int8``."""
    from repro.fl.quant import dequantize_int8 as impl
    return impl(q, scale)
