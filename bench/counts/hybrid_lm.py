"""Counted FLOPs of the hybrid LM family's round (Granite 4.0-H), by
``bench/flops.py``'s rule, from the configuration's shapes: a recomputed
frozen prefix once, the trained block, the output module and the head
three times. A sample is a row of ``seq_len`` tokens.

Per layer, 2 FLOPs a multiply-add:

- projections and MLPs at every token: attention's q, k, v and o; Mamba-2's
  in and out projections; the SwiGLU MLP's three matrices;
- Mamba-2's depthwise causal conv (``mamba_d_conv`` taps a channel);
- causal attention: ``q k^T`` and ``p v`` over the pairs ``s <= t`` only,
  ``S (S + 1) / 2`` of them, for every query head;
- the SSD scan as the chunked algorithm computes it at the published
  ``mamba_chunk_size`` ``c`` (one group): ``C B^T`` and the intra-chunk
  products over the pairs ``s <= t`` of each chunk, ``c (c + 1) / 2`` a
  chunk; each chunk's state (``dt x B^T`` summed over its positions) and
  the inter-chunk output (``C h``) at every token.

The embedding lookup, norms, activations, softmax, the decay factors and
the carry between chunks are not counted, nor the remat of the trained
layers, which recomputes their forward in the backward pass. The head
counts the ``seq_len - 1`` positions that predict a token.
"""
from __future__ import annotations

from typing import Dict

from bench.flops import samples_per_round


def _mlp(d: int, width: int) -> int:
    return 3 * d * width


def attention_macs(cfg: Dict, s: int, width: int) -> int:
    """Multiply-adds of one attention layer and its MLP over a row of
    ``s`` tokens."""
    d, nq = cfg["hidden_size"], cfg["num_attention_heads"]
    nkv, hd = cfg["num_key_value_heads"], d // nq
    proj = d * (nq + 2 * nkv) * hd + nq * hd * d
    pairs = s * (s + 1) // 2
    return s * (proj + _mlp(d, width)) + pairs * nq * 2 * hd


def mamba_macs(cfg: Dict, s: int) -> int:
    """Multiply-adds of one Mamba-2 layer and its MLP over a row of ``s``
    tokens."""
    d, n = cfg["hidden_size"], cfg["mamba_d_state"]
    heads, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    di, c = cfg["mamba_expand"] * d, cfg["mamba_chunk_size"]
    proj = d * (2 * di + 2 * n + heads) + di * d
    conv = (di + 2 * n) * cfg["mamba_d_conv"]
    chunk_pairs = (s // c) * c * (c + 1) // 2
    ssd = (chunk_pairs * (n + heads * p)      # C B^T, intra-chunk products
           + s * heads * p * n * 2)           # chunk states, C h
    return s * (proj + conv + _mlp(d, cfg["shared_intermediate_size"])) + ssd


def part_macs(cfg: Dict, stage: int, s: int) -> Dict[str, int]:
    """Multiply-adds over a row of ``s`` tokens of the stage's parts:
    ``prefix`` (the frozen layers), ``active`` (the trained block) and
    ``head`` (the output module and its head, or the tied head)."""
    n, t = cfg["num_hidden_layers"], cfg["num_freeze_blocks"]
    base, rem = divmod(n, t)
    bounds = [0]
    for i in range(t):
        bounds.append(bounds[-1] + base + (1 if i < rem else 0))
    lo, hi = bounds[stage], bounds[stage + 1]
    layer = [mamba_macs(cfg, s) if k == "mamba"
             else attention_macs(cfg, s, cfg["shared_intermediate_size"])
             for k in cfg["layer_types"][:n]]
    d = cfg["hidden_size"]
    proxies = (t - stage - 1) * attention_macs(cfg, s, d)
    return {"prefix": sum(layer[:lo]), "active": sum(layer[lo:hi]),
            "head": proxies + (s - 1) * d * cfg["vocab_size"]}


def flops_per_sample(cfg: Dict, stage: int, s: int, *,
                     recompute_prefix: bool) -> float:
    """Counted FLOPs of one trained row of ``s`` tokens at ``stage``."""
    parts = part_macs(cfg, stage, s)
    prefix = 2 * parts["prefix"] if recompute_prefix else 0
    return 3.0 * 2 * (parts["active"] + parts["head"]) + prefix


def flops_per_round(cfg: Dict, traffic: Dict) -> float:
    """Counted FLOPs of one round of ``traffic``."""
    stage = traffic["stage"]
    recompute = stage > 0 and traffic["cache_tier"] is None
    # a configuration cut for the CPU tests states its own seq_len
    s = cfg.get("seq_len", traffic["seq_len"])
    return (flops_per_sample(cfg, stage, s, recompute_prefix=recompute)
            * samples_per_round(traffic))
