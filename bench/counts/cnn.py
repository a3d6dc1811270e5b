"""Counted FLOPs of the CNN family's round, by ``bench/flops.py``'s rule,
from the configuration's shapes: the convs and the dense head. A cached
stage counts no prefix, since its traffic supplies the features. Batch
norm, activations and pooling are not counted.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

from bench.flops import samples_per_round


def _taps(size: int, stride: int, k: int, full: bool) -> Tuple[int, int]:
    """(kernel taps summed over the output positions of one axis, output
    size) of a SAME-padded conv: every tap, or only those on the image."""
    o = -(-size // stride)
    if full:
        return o * k, o
    lo = max((o - 1) * stride + k - size, 0) // 2
    return sum(1 for i in range(o) for t in range(k)
               if 0 <= i * stride - lo + t < size), o


def _conv(size: int, stride: int, k: int, c_in: int, c_out: int,
          full: bool = True) -> Tuple[int, int]:
    """(multiply-adds per sample, output size) of one conv."""
    taps, o = _taps(size, stride, k, full)
    return taps * taps * c_in * c_out, o


def layer_macs(cfg: Dict, stage: int, *, full_kernel: bool = True
               ) -> Dict[str, List[int]]:
    """Multiply-adds per sample of the stage-``stage`` submodel, by part:
    ``prefix`` (stem and stages before ``stage``, frozen), ``active``
    (stage ``stage``, and the stem at stage 0) and ``head`` (output module
    or the model's head). Each part is a list of per-layer counts.

    The count takes every kernel tap, as the usual convention does;
    ``full_kernel=False`` leaves out the taps that fall on the zero
    padding, which is what XLA's cost analysis counts."""
    n = len(cfg["stage_sizes"])
    chans, resnet = cfg["stage_channels"], cfg["kind"] == "resnet"
    size = cfg["image_size"]
    parts: Dict[str, List[int]] = {"prefix": [], "active": [], "head": []}
    c_prev = cfg["in_channels"]
    if resnet:
        macs, size = _conv(size, 1, 3, c_prev, chans[0], full_kernel)
        parts["active" if stage == 0 else "prefix"].append(macs)
        c_prev = chans[0]
    for i in range(stage + 1):
        part = parts["prefix" if i < stage else "active"]
        ch = chans[i]
        for j in range(cfg["stage_sizes"][i]):
            c_in = c_prev if j == 0 else ch
            if resnet:
                stride = 2 if (j == 0 and i > 0) else 1
                m1, out = _conv(size, stride, 3, c_in, ch, full_kernel)
                m2, _ = _conv(out, 1, 3, ch, ch, full_kernel)
                part += [m1, m2]
                if c_in != ch:
                    part.append(_conv(size, stride, 1, c_in, ch,
                                       full_kernel)[0])
                size = out
            else:
                macs, size = _conv(size, 1, 3, c_in, ch, full_kernel)
                part.append(macs)
        if not resnet:
            size //= 2          # max-pool 2x2, stride 2
        c_prev = ch
    for i in range(stage + 1, n):
        macs, size = _conv(size, 2, 3, c_prev, chans[i], full_kernel)
        parts["head"].append(macs)
        c_prev = chans[i]
    parts["head"].append(c_prev * cfg["num_classes"])
    return parts


def model_forward_macs(cfg: Dict, *, full_kernel: bool = True) -> int:
    """Multiply-adds per sample of the whole model's forward (last stage's
    submodel: every stage and the model's head)."""
    parts = layer_macs(cfg, len(cfg["stage_sizes"]) - 1,
                       full_kernel=full_kernel)
    return sum(sum(v) for v in parts.values())


def flops_per_sample(cfg: Dict, stage: int, *, recompute_prefix: bool
                     ) -> float:
    """Counted FLOPs of one trained sample at ``stage``."""
    parts = layer_macs(cfg, stage)
    trained = 2 * (sum(parts["active"]) + sum(parts["head"]))
    prefix = 2 * sum(parts["prefix"]) if recompute_prefix else 0
    return 3.0 * trained + prefix


def flops_per_round(cfg: Dict, traffic: Dict) -> float:
    """Counted FLOPs of one round of ``traffic``."""
    stage = traffic["stage"]
    recompute = stage > 0 and traffic["cache_tier"] is None
    return (flops_per_sample(cfg, stage, recompute_prefix=recompute)
            * samples_per_round(traffic))
