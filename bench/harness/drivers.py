"""Closed-loop and open-loop drivers on the host's wall clock.

A driver knows nothing of the system: it calls what the configuration's
deployment object offers and stamps each request on ``clock``.

Closed loop (``submit(spec) -> handle``, ``finish(handle) -> ok``): keeps
``clients`` requests in flight with no think time.  ``submit`` dispatches a
request and may return before the device is done; ``finish`` blocks until its
outputs are ready on the device.  Latency runs from the start of ``submit``
to the return of ``finish``.

Open loop (``submit(spec) -> rid``, ``step() -> [(rid, tokens)]``,
``busy() -> bool``): sends every request at the time it is due, whether or
not earlier ones are done, and steps the system in between.  ``submit``
returns once the request's first token is on the host.  Time to first token
counts from the due time, so a stalled loop charges its wait to every request
behind it; how late each request was sent is kept as ``lateness``.  After the
last arrival the loop steps until every request is done, for at most
``drain_s`` seconds; a request still open then has failed.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable, List, Optional

from .traffic import Schedule


@dataclasses.dataclass
class Record:
    index: int
    sent: float                      # clock at the start of submit
    due: Optional[float] = None      # open loop: clock time the request was due
    first: Optional[float] = None    # open loop: first token on the host
    done: Optional[float] = None     # outputs ready (closed) / last token (open)
    tokens: int = 0
    ok: bool = False
    expected_tokens: Optional[int] = None

    @property
    def latency(self) -> Optional[float]:
        return None if self.done is None else self.done - self.sent

    @property
    def ttft(self) -> Optional[float]:
        return None if self.first is None else self.first - self.due

    @property
    def lateness(self) -> Optional[float]:
        return None if self.due is None else self.sent - self.due

    @property
    def tpot(self) -> Optional[float]:
        if self.done is None or self.first is None or self.tokens < 2:
            return None
        return (self.done - self.first) / (self.tokens - 1)


@dataclasses.dataclass
class Window:
    t0: float
    t1: float                        # the window closes (arrivals stop)
    records: List[Record]
    #: when the last request was settled (after the drain)
    t_drained: float = 0.0


class _NoHook:
    def poll(self, elapsed: float) -> None:
        pass


def run_closed(system, schedule: Schedule, seconds: float, *,
               clock: Callable[[], float] = time.perf_counter,
               hook=None) -> Window:
    hook = hook or _NoHook()
    inflight: collections.deque = collections.deque()
    records: List[Record] = []
    t0 = clock()
    end = t0 + seconds
    i = 0
    while True:
        now = clock()
        hook.poll(now - t0)
        if now < end and len(inflight) < schedule.clients:
            rec = Record(index=i, sent=now)
            inflight.append((rec, system.submit(schedule.spec(i))))
            records.append(rec)
            i += 1
            continue
        if not inflight:
            break
        rec, handle = inflight.popleft()
        rec.ok = bool(system.finish(handle))
        rec.done = clock()
    return Window(t0, end, records, clock())


def run_open(system, schedule: Schedule, seconds: float, *, drain_s: float,
             clock: Callable[[], float] = time.perf_counter,
             sleep: Callable[[float], None] = time.sleep,
             hook=None) -> Window:
    hook = hook or _NoHook()
    reqs = schedule.requests
    records: List[Record] = []
    live = {}
    t0 = clock()
    end = t0 + seconds
    i = 0
    while True:
        now = clock()
        hook.poll(now - t0)
        if i < len(reqs) and t0 + reqs[i].due_s <= now:
            spec = reqs[i]
            rec = Record(index=i, sent=now, due=t0 + spec.due_s,
                         expected_tokens=spec.output_tokens)
            records.append(rec)
            rid = system.submit(spec)
            rec.first = clock()
            live[rid] = rec
            i += 1
            continue
        if now > end + drain_s:
            break
        if live or system.busy():
            for rid, tokens in system.step():
                rec = live.pop(rid, None)
                if rec is not None:
                    rec.done = clock()
                    rec.tokens = tokens
                    rec.ok = True
            continue
        if i >= len(reqs):
            break
        sleep(max(0.0, t0 + reqs[i].due_s - clock()))
    return Window(t0, end, records, clock())
