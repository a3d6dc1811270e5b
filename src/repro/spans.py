"""Host spans and counters for the round's hot path.

``SpanStats.span(name)`` marks a stretch of host work twice over:

- as a ``jax.profiler.TraceAnnotation``, so a profiler capture shows the
  span on the host plane, on the same clock as the device's operations (it
  records nothing while no profiler runs);
- as running totals per name on ``time.perf_counter_ns``: how often the
  span closed, its summed duration and its summed self time (the duration
  less the time covered by the spans opened inside it).

``add(counter, n)`` keeps integer counters beside the spans. Memory is one
entry per name, whatever the number of spans: the profiler's trace holds
the single spans. One ``SpanStats`` belongs to one thread, since nesting
is tracked on one stack.
"""
from __future__ import annotations

import contextlib
import time

import jax


class SpanStats:
    """Per-name span totals and integer counters (see the module doc)."""

    def __init__(self):
        self._open = []       # per open span: ns covered by its children
        self.reset()

    def reset(self) -> None:
        """Clear every total and counter (spans open now still close)."""
        self._totals = {}     # name -> [count, total_ns, self_ns]
        self._counters = {}   # name -> int

    @contextlib.contextmanager
    def span(self, name: str):
        with jax.profiler.TraceAnnotation(name):
            self._open.append(0)
            t0 = time.perf_counter_ns()
            try:
                yield
            finally:
                dt = time.perf_counter_ns() - t0
                children = self._open.pop()
                if self._open:
                    self._open[-1] += dt
                tot = self._totals.setdefault(name, [0, 0, 0])
                tot[0] += 1
                tot[1] += dt
                tot[2] += dt - children

    def add(self, counter: str, n: int) -> None:
        self._counters[counter] = self._counters.get(counter, 0) + int(n)

    def snapshot(self) -> dict:
        """``{"spans": {name: {"count", "total_ns", "self_ns"}},
        "counters": {name: value}}``, a copy of the totals so far."""
        return {"spans": {n: {"count": c, "total_ns": t, "self_ns": s}
                          for n, (c, t, s) in self._totals.items()},
                "counters": dict(self._counters)}
