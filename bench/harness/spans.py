"""Host spans recorded by the benchmark around its calls into each layer.

Spans are kept in memory as ``(name, start_s, end_s)`` on
``time.perf_counter``.  While the profiler traces, each span is also written
into the trace as a ``jax.profiler.TraceAnnotation`` of the same name, so the
trace reduction can attribute the device's idle gaps to what the host was
doing.
"""
from __future__ import annotations

import time
from typing import List, Tuple

Span = Tuple[str, float, float]


class _Open:
    __slots__ = ("rec", "name", "t0", "ann")

    def __init__(self, rec: "Spans", name: str):
        self.rec, self.name, self.ann = rec, name, None

    def __enter__(self):
        if self.rec.annotate:
            from jax.profiler import TraceAnnotation

            self.ann = TraceAnnotation(self.name)
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        self.rec.spans.append((self.name, self.t0, t1))
        return False


class Spans:
    def __init__(self):
        self.spans: List[Span] = []
        #: set while the profiler traces
        self.annotate = False

    def span(self, name: str) -> _Open:
        return _Open(self, name)

    def within(self, t0: float, t1: float, name: str | None = None) -> List[Span]:
        """Spans that start inside ``[t0, t1)``, of one name if given."""
        return [s for s in self.spans
                if t0 <= s[1] < t1 and (name is None or s[0] == name)]

    def total(self, t0: float, t1: float, name: str) -> Tuple[int, float]:
        """(count, seconds) of the spans of ``name`` that start in the window."""
        hits = self.within(t0, t1, name)
        return len(hits), sum(e - s for _, s, e in hits)
