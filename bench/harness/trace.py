"""Reduction of a profiler trace to device busy time, per-program device
time, the busiest device operations, and the device's idle gaps attributed
to what the host was doing.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.ProfileData``.
Each device is a plane named ``/device:<KIND>:<n>``; its ``XLA Modules``
line holds one event per program execution (``jit_decode(<hash>)``) and its
``XLA Ops`` line the operations inside them.  The benchmark's host spans are
``TraceAnnotation`` events named ``bench.*`` on the host plane's lines, on
the same clock.  The window is the ``bench.window`` span.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
PROGRAM_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]
_HASH = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Reduced:
    window_s: float
    #: seconds in which some program ran, averaged over the devices used
    busy_s: float
    devices: int
    #: program name -> (executions, device seconds), inside the window
    programs: Dict[str, Tuple[int, float]]
    #: [(program:op, self seconds)], most first
    device_ops: List[Tuple[str, float]]
    #: [(host span, idle seconds)], most first
    idle_gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def program(self, name: str) -> Tuple[int, float]:
        """(executions, device seconds) of the programs called ``name``."""
        return self.programs.get(name, (0, 0.0))


def program_name(event_name: str) -> str:
    """``jit_decode(1412813634915374287)`` -> ``jit_decode``."""
    return _HASH.sub("", event_name)


def union(intervals: List[Interval]) -> List[Interval]:
    """Merge overlapping intervals; sorted, disjoint."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of ``[lo, hi]`` that ``busy`` (merged, clipped) leaves free."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def self_times(events: List[Tuple[str, float, float]]) -> Dict[str, float]:
    """Per name, the time of its events minus that of events nested in them
    (a ``while`` op holds its body's ops)."""
    out: Dict[str, float] = {}
    stack: List[list] = []          # [name, end, child_time]

    def close(entry, start_end):
        name, end, child, start = entry
        out[name] = out.get(name, 0.0) + (end - start) - child

    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            close(stack.pop(), None)
        if stack:
            stack[-1][2] += e - s
        stack.append([name, e, 0.0, s])
    while stack:
        close(stack.pop(), None)
    return out


def attribute(gap_list: List[Interval], spans: List[Tuple[str, float, float]]
              ) -> Dict[str, float]:
    """Charge each idle gap to the innermost host span that holds its
    midpoint, or to ``host:outside-spans`` where none does."""
    spans = sorted(spans, key=lambda x: x[1])
    starts = [s for _, s, _ in spans]
    out: Dict[str, float] = {}
    for g0, g1 in gap_list:
        mid = 0.5 * (g0 + g1)
        best: Optional[Tuple[str, float, float]] = None
        for k in range(bisect.bisect_right(starts, mid) - 1, -1, -1):
            name, s, e = spans[k]
            if e >= mid and (best is None or s >= best[1]):
                best = spans[k]
                break
        key = best[0] if best is not None else "host:outside-spans"
        out[key] = out.get(key, 0.0) + (g1 - g0)
    return out


def _load(path: str):
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def reduce(path: str, top: int = 10) -> Reduced:
    """Reduce one trace; times in the result are seconds."""
    pd = _load(path)
    host: List[Tuple[str, float, float]] = []
    devices: Dict[str, Dict[str, list]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            d = devices.setdefault(plane.name, {"programs": [], "ops": []})
            for line in plane.lines:
                key = {PROGRAM_LINE: "programs", OPS_LINE: "ops"}.get(line.name)
                if key:
                    d[key].extend((e.name, e.start_ns * 1e-9,
                                   (e.start_ns + e.duration_ns) * 1e-9)
                                  for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    windows = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span in the trace")
    lo, hi = windows[0]
    spans = [x for x in host if x[0] != WINDOW_SPAN]

    used = {k: v for k, v in devices.items()
            if clip([(s, e) for _, s, e in v["programs"]], lo, hi)}
    programs: Dict[str, Tuple[int, float]] = {}
    ops: Dict[str, float] = {}
    idle: Dict[str, float] = {}
    busy_total = 0.0
    for d in used.values():
        progs = [(program_name(n), max(s, lo), min(e, hi))
                 for n, s, e in d["programs"] if e > lo and s < hi]
        busy = union([(s, e) for _, s, e in progs])
        busy_total += sum(e - s for s, e in busy)
        for n, s, e in progs:
            c, t = programs.get(n, (0, 0.0))
            programs[n] = (c + 1, t + (e - s))
        # ops are named by the program that holds them
        pstarts = sorted((s, e, n) for n, s, e in progs)
        keys = [p[0] for p in pstarts]
        named = []
        for n, s, e in d["ops"]:
            if e <= lo or s >= hi:
                continue
            k = bisect.bisect_right(keys, s) - 1
            prog = pstarts[k][2] if k >= 0 and pstarts[k][1] >= s else "?"
            named.append((f"{prog}:{n.split(' = ')[0]}", max(s, lo), min(e, hi)))
        for n, t in self_times(named).items():
            ops[n] = ops.get(n, 0.0) + t
        for n, t in attribute(gaps(busy, lo, hi), spans).items():
            idle[n] = idle.get(n, 0.0) + t
    n_dev = max(1, len(used))
    scale = 1.0 / n_dev
    return Reduced(
        window_s=hi - lo,
        busy_s=busy_total * scale,
        devices=len(used),
        programs=programs,
        device_ops=sorted(((k, v * scale) for k, v in ops.items()),
                          key=lambda kv: -kv[1])[:top],
        idle_gaps=sorted(((k, v * scale) for k, v in idle.items()),
                         key=lambda kv: -kv[1])[:top],
    )
