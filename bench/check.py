"""The comparison that decides ``correct``.

The window's compiled round is driven from the seed through its first
rounds in set-up, and the plain reference follows the same rounds from the
same weights on the same cohorts and minibatches. Each side is reduced to a
``summary``: its per-client losses of every round, and each leaf's change
(params and batch-norm running stats) after the first and after the last
round. The numbers compared, each held to a limit of its own, come from the
two summaries. A cell compares the numbers that its
``bench/limits/<workload>.json`` gives a limit:

- ``grad_gap``: the first round's update (for SGD, the learning rate times
  the pseudo-gradient the server receives), by the gap between the
  program's and the reference's norm of each leaf, relative to the larger
  of the reference's norm of that leaf and of the median param leaf; the
  worst leaf counts;
- ``change_gap``: the same measure of the change after the last round;
- ``grad_diff``, ``change_diff``: the norm of the difference between the
  program's and the reference's change of each leaf, relative as above, so
  that a change of the right size in the wrong direction counts;
- ``loss_gap``: the gap between the program's and the reference's mean
  client loss of the first round, relative to the reference's.

Leaves whose reference update in round 1 is under a thousandth of the
median param leaf's are left out: their gradient is nought (a conv bias in
front of batch norm) or they are passed through unchanged (the running
stats of frozen and later stages), so only round-off moves them.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import numpy as np

NAMES = ("loss_gap", "grad_gap", "change_gap", "grad_diff", "change_diff")
RULE = 1e-3


def _leaves(tree) -> List[Tuple[str, np.ndarray]]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(jax.tree_util.keystr(p), np.asarray(x, np.float64))
            for p, x in flat]


def _changes(after, before) -> Dict[str, np.ndarray]:
    b = dict(_leaves(before))
    return {k: v - b[k] for k, v in _leaves(after)}


def summary(start, rounds) -> Dict:
    """What the comparison reads of one side, from the start point
    ``(params, state)`` and its rounds ``[(params, state, losses), ...]``:
    the losses, and each leaf's change after the first and the last round.
    Leaves are named by path, params under ``[0]``, state under ``[1]``."""
    return {"losses": [[float(x) for x in r[2]] for r in rounds],
            "first": _changes(rounds[0][:2], start),
            "last": _changes(rounds[-1][:2], start)}


def _norm(x: np.ndarray) -> float:
    return float(np.linalg.norm(x))


def median_param(ref: Dict) -> float:
    return float(np.median([_norm(v) for k, v in ref["first"].items()
                            if k.startswith("[0]")]))


def kept(ref: Dict) -> List[str]:
    """The leaves compared: those the reference moves in round 1."""
    median = median_param(ref)
    return [k for k, v in ref["first"].items() if _norm(v) >= RULE * median]


def leaf_numbers(prog: Dict, ref: Dict, which: str, keep: Sequence[str],
                 median: float) -> Dict[str, Tuple[float, float]]:
    """Each kept leaf's (gap of norms, norm of the difference) of the
    ``which`` change, relative to the larger of the reference's norm and
    ``median`` (inf where the program's change is not finite)."""
    out = {}
    for k in keep:
        p, r = prog[which][k], ref[which][k]
        scale = max(_norm(r), median)
        if not np.isfinite(p).all():
            out[k] = (float("inf"), float("inf"))
        else:
            out[k] = (abs(_norm(p) - _norm(r)) / scale, _norm(p - r) / scale)
    return out


def first_round_loss_gap(prog: Sequence[Sequence[float]],
                         ref: Sequence[Sequence[float]]) -> float:
    """|mean program loss - mean reference loss| / mean reference loss of
    round 1 (inf where the program's losses are not finite)."""
    p = np.asarray(prog[0], np.float64)
    r = np.asarray(ref[0], np.float64)
    if not np.isfinite(p).all():
        return float("inf")
    return float(abs(p.mean() - r.mean()) / abs(r.mean()))


def compare(prog: Dict, ref: Dict) -> Dict[str, Dict]:
    """The numbers compared, from the two sides' ``summary``; each grad or
    change number also names the leaf it is at."""
    keep = kept(ref)
    median = median_param(ref)
    out = {"loss_gap": {"value": first_round_loss_gap(prog["losses"],
                                                      ref["losses"])}}
    for stem, which in (("grad", "first"), ("change", "last")):
        per_leaf = leaf_numbers(prog, ref, which, keep, median)
        for i, kind in enumerate(("gap", "diff")):
            at = max(per_leaf, key=lambda k: per_leaf[k][i])
            out[f"{stem}_{kind}"] = {"value": per_leaf[at][i], "leaf": at}
    out["leaves_compared"] = {"value": len(keep), "of": len(ref["first"])}
    return out
