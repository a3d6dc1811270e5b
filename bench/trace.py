"""Reduce a profiler trace to what the per-layer metrics read.

``reduce_xspace`` turns the profiler's ``.xplane.pb`` into a small JSON-able
dict on one clock (nanoseconds):

- ``devices``: per device plane, its operations as ``[start, duration,
  name, self]`` (the ``XLA Ops`` line of a TPU plane; on the CPU backend,
  the host events that carry an ``hlo_op`` stat, so a CPU trace can stand
  in for tests). ``name`` is the HLO instruction's name; ``self`` is the
  duration less that of the operations nested in it (a ``while`` holds its
  body's operations);
- ``host``: the benchmark's own spans (``bench.*``) and the host events
  that compile, as ``[start, duration, name]``.

``Trace`` answers questions about such a dict within the benchmark's
``bench.window`` span: the union of busy intervals per device, the rounds
(``bench.round`` spans), collective time and the idle gaps, each gap
labelled by what the host was doing for most of it.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
ROUND_SPAN = "bench.round"
COLLECTIVE = re.compile(r"all-reduce|all_reduce|allreduce", re.I)
COMPILE = re.compile(r"compile", re.I)

Interval = Tuple[float, float]


def find_xspace(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` trace directory."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def short_name(hlo: str) -> str:
    """``fusion.3`` from ``%fusion.3 = f32[8]{0} fusion(...)``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def with_self_time(ops: List) -> List:
    """``[start, duration, name]`` events, sorted, each with its self time
    appended: nested events are subtracted from their direct parent."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    out = [[s, d, n, d] for s, d, n in ops]
    stack: List = []   # (end, index) of the open events
    for i, (s, d, _, _) in enumerate(out):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            out[stack[-1][1]][3] -= d
        stack.append((s + d, i))
    return out


def reduce_xspace(path: str) -> Dict:
    """The reduced trace of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List] = {}
    host: List = []
    cpu_ops: List = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name] = [
                        [e.start_ns, e.duration_ns, short_name(e.name)]
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench.") or COMPILE.search(e.name):
                        host.append([e.start_ns, e.duration_ns, e.name])
                    elif any(k == "hlo_op" for k, _ in e.stats):
                        cpu_ops.append([e.start_ns, e.duration_ns, e.name])
    if not devices and cpu_ops:
        devices["/host:CPU"] = cpu_ops
    host.sort()
    return {"devices": {k: with_self_time(v) for k, v in devices.items()},
            "host": host}


def union(intervals: List[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def total(intervals: List[Interval]) -> float:
    return sum(b - a for a, b in intervals)


class Trace:
    """Questions about a reduced trace, inside its measured window."""

    def __init__(self, reduced: Dict):
        self.devices = {k: [(float(o[0]), float(o[0]) + float(o[1]), o[2],
                             float(o[3] if len(o) > 3 else o[1]))
                            for o in v]
                        for k, v in reduced["devices"].items()}
        self.host = [(float(s), float(s) + float(d), n)
                     for s, d, n in reduced["host"]]
        spans = [(a, b) for a, b, n in self.host if n == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
        self.lo, self.hi = spans[-1]

    @property
    def window_ns(self) -> float:
        return self.hi - self.lo

    def rounds(self) -> List[Interval]:
        """The ``bench.round`` spans inside the window."""
        return [(a, b) for a, b, n in self.host
                if n == ROUND_SPAN and a >= self.lo and b <= self.hi]

    def _ops(self, device: str, pattern=None) -> List[Interval]:
        return [(a, b) for a, b, n, _ in self.devices[device]
                if pattern is None or pattern.search(n)]

    def busy(self, device: str) -> List[Interval]:
        """Merged intervals in which an operation ran on ``device``."""
        return clip(union(self._ops(device)), self.lo, self.hi)

    def busy_ns(self) -> float:
        """Busy time in the window, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(total(self.busy(d)) for d in self.devices) / len(
            self.devices)

    def collective_ns(self) -> Tuple[float, float]:
        """(time of all-reduce operations, the part of it with no other
        operation running beside) in the window, averaged over devices."""
        if not self.devices:
            return 0.0, 0.0
        coll = exposed = 0.0
        for d in self.devices:
            c = clip(union(self._ops(d, COLLECTIVE)), self.lo, self.hi)
            other = clip(union([(a, b) for a, b, n, _ in self.devices[d]
                                if not COLLECTIVE.search(n)]),
                         self.lo, self.hi)
            coll += total(c)
            exposed += total(c) - _overlap(c, other)
        n = len(self.devices)
        return coll / n, exposed / n

    def top_ops(self, k: int = 10) -> List[Tuple[str, float]]:
        """The ``k`` operations that took most device self time in the
        window, in seconds averaged over devices."""
        by: Dict[str, float] = {}
        for d, ops in self.devices.items():
            for a, b, n, own in ops:
                lo, hi = max(a, self.lo), min(b, self.hi)
                if hi > lo:
                    by[n] = by.get(n, 0.0) + own * (hi - lo) / (b - a)
        n_dev = max(len(self.devices), 1)
        top = sorted(by.items(), key=lambda kv: -kv[1])[:k]
        return [(n, t / n_dev * 1e-9) for n, t in top]

    def gaps(self, device: Optional[str] = None) -> List[Tuple[str, float]]:
        """Idle gaps of ``device`` (the first one by default) in the window,
        longest first, each labelled by what the host was doing for most of
        it: ``compile``, ``run_round`` or ``between_rounds``."""
        if not self.devices:
            return []
        device = device or sorted(self.devices)[0]
        edges = [self.lo] + [t for iv in self.busy(device) for t in iv] + [
            self.hi]
        rounds = union(self.rounds())
        compiles = union([(a, b) for a, b, n in self.host
                          if COMPILE.search(n)])
        out = []
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                out.append((_label((a, b), rounds, compiles),
                            (b - a) * 1e-9))
        return sorted(out, key=lambda g: -g[1])


def _overlap(a: List[Interval], b: List[Interval]) -> float:
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _label(gap: Interval, rounds: List[Interval],
           compiles: List[Interval]) -> str:
    comp = _overlap([gap], compiles)
    inside = _overlap([gap], rounds)
    share = {"compile": comp, "run_round": inside - comp,
             "between_rounds": (gap[1] - gap[0]) - inside}
    return max(share, key=share.get)
