"""Counted FLOPs of a round, from the configuration's shapes.

The count is the work the algorithm needs, whatever implements it: the
forward FLOPs (2 per multiply-add) of the trained layers times 3 (forward,
and backward for inputs and weights), plus a frozen prefix's forward once
where the traffic recomputes it. Work the traffic supplies (cached
features) is not counted; neither is masked, padded or rematerialized
work. Each model family counts its own layers in ``bench/counts/<family>.py``,
which exports ``flops_per_round(cfg, traffic)``.

A sample is what a client trains on in one row of a batch: an image, or a
sequence of ``traffic["seq_len"]`` tokens.
"""
from __future__ import annotations

from typing import Dict

from bench import families


def flops_per_round(cfg: Dict, traffic: Dict) -> float:
    """Counted FLOPs of one round of ``traffic``, by the family's count."""
    return families.module(cfg["family"], "counts").flops_per_round(
        cfg, traffic)


def samples_per_round(traffic: Dict) -> int:
    """Client samples trained in one round: K x batches x batch size."""
    steps = traffic["samples_per_client"] // traffic["batch"]
    return traffic["cohort"] * steps * traffic["epochs"] * traffic["batch"]
