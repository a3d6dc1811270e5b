"""Sparse cohort scatter-add — Pallas TPU kernel for the compressed-uplink
Eq. 1 fold (fl/engine.py / fl/compression.py).

After top-k sparsification, each of K clients uploads (idx [k], vals [k])
per leaf. The XLA path densifies via one ``.at[].add`` scatter over the
[K*k] concatenation; this kernel folds the whole cohort in ONE launch.

Layout (what Mosaic accepts):
  * the cohort's entries are flattened client-major into one stream, the
    Eq. 1 weight multiplied in first (``w_i * vals`` — the same products
    the reference scatters), and padded to whole ``CHUNK``s with zeros
    added at index 0;
  * the grid walks the stream one ``CHUNK`` at a time, each chunk's
    indices and values landing in SMEM, where the scalar unit reads them;
  * the dense [L] output is held as [L/1024, 8, 128] f32 — whole (8, 128)
    vregs — in one VMEM block with a constant index map, so it stays
    resident across the grid: zeroed at step 0, then each entry does a
    read-modify-write of the one vreg it falls in, through a dynamic index
    on the leading dim and a one-hot (sublane, lane) mask.

TPU grids run sequentially on a core, so duplicate indices — within a row
or across clients — accumulate in stream order, exactly as the reference
scatter-add does (no atomics needed).

The output block (double-buffered by the pipeline) must fit the scoped
VMEM, so the public wrapper (kernels/ops.py) falls back to the XLA scatter
for leaves above ``MAX_VMEM_ELEMS`` — the documented dispatch rule
(docs/ARCHITECTURE.md). Indices must lie in [0, length); top-k selection
guarantees it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUBLANES, LANES = 8, 128
TILE = SUBLANES * LANES          # f32 elements in one (8, 128) vreg
CHUNK = 1024                     # stream entries per grid step (SMEM block)

# f32 elements per leaf the dense output block may hold: two pipeline
# buffers of 4 MiB each take half of v5e's 16 MiB default scoped VMEM.
MAX_VMEM_ELEMS = 1 << 20


def _sparse_agg_kernel(idx_ref, val_ref, o_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    pos = (jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0) * LANES
           + jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 1))

    def body(j, carry):
        at = idx_ref[j]
        tile = at // TILE
        o_ref[tile] += jnp.where(pos == at % TILE, val_ref[j], 0.0)
        return carry

    jax.lax.fori_loop(0, CHUNK, body, 0)


def sparse_cohort_add_fwd(idx: jnp.ndarray, vals: jnp.ndarray,
                          weights: jnp.ndarray, length: int, *,
                          interpret: bool = False) -> jnp.ndarray:
    """Dense [length] f32 Eq. 1 fold of K sparse client rows.

    idx: [K, k] int32 flat indices in [0, length) (duplicates allowed —
    they accumulate); vals: [K, k]; weights: [K]. Matches
    ``fl.compression.ingraph_sparse_aggregate``."""
    K, k = idx.shape
    assert vals.shape == (K, k) and weights.shape == (K,), \
        (idx.shape, vals.shape, weights.shape)
    n = K * k
    pad = -n % CHUNK
    flat_idx = jnp.pad(idx.astype(jnp.int32).reshape(-1), (0, pad))
    flat_val = jnp.pad((weights.astype(jnp.float32)[:, None]
                        * vals.astype(jnp.float32)).reshape(-1), (0, pad))
    rows = pl.cdiv(length, TILE)
    smem = pl.BlockSpec((CHUNK,), lambda c: (c,),
                        memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        _sparse_agg_kernel,
        grid=((n + pad) // CHUNK,),
        in_specs=[smem, smem],
        out_specs=pl.BlockSpec((rows, SUBLANES, LANES), lambda c: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, SUBLANES, LANES), jnp.float32),
        interpret=interpret,
    )(flat_idx, flat_val)
    return out.reshape(-1)[:length]
