"""Spans and counters inside the program, on the profiler's clock.

Tracing is on exactly while a JAX profiler session records in this process
(``jax.profiler.trace`` / ``start_trace``, or ``start_server`` plus a
capture from XProf): :func:`enabled` is ``TraceAnnotation.is_enabled``.
With it off, every site costs that one check.  There is no other switch.

A span records its name, its start and end on ``time.perf_counter``, its
parent span, the request it belongs to and a few attributes (``medium``,
``nbytes``, ``function``, ``pod``...).  It is also written as a
``jax.profiler.TraceAnnotation`` of the same name, with the attributes as
its stats, so it sits on the host plane of the profiler's trace beside the
device's operations.  A count records a name, a time and ``n``.  Records
go to an in-memory ring of :data:`RING` entries (the oldest are dropped);
:func:`records` returns them.

The request of a span is that of its parent, unless the span names one:
:func:`root` starts a request where none is open (``wf.request``,
``serve.submit``), and an invocation resumed later by the simulator names
the request it was steered under.

Names are stable; they are what the readers of the records look up:

==================  ==================================================
``wf.request``      one workflow request: all of a blocking ``run()``;
                    the enqueue alone in ``submit()``
``wf.invoke``       one inline (blocking) invocation
``wf.steer``        one steer of the control plane
``wf.handler``      one contiguous stretch of a handler's own code
``xfer.put``        ``TransferEngine.put`` (``medium``, ``nbytes``)
``xfer.get``        ``TransferEngine.get`` (``medium``, ``nbytes``)
``serve.submit``    one disaggregated request, up to its first token
``serve.prefill``   the prefill dispatch and its first-token read
                    (``tokens``, and ``padded``: the length prefilled)
``serve.insert``    the admit of a handed-over cache into a decode slot
                    (``state_bytes`` of SSM and conv states, ``kv_bytes``)
``serve.slot_wait`` a handoff parked behind a full decode batch, until
                    its admit (detached: no parent)
``serve.round``     one ``DisaggregatedServer.step``
``serve.release``   the admits of parked handoffs into the slots the
                    round freed
``serve.decode``    one pod's decode dispatch
``host.sync``       one device-to-host read; inside a round, the round's
                    one read of every pod's tokens
``host.syncs``      (count) one per device-to-host read
``prefill.tokens``  (count) a prefilled prompt's real tokens
``prefill.pad_tokens`` (count) the pad tokens prefilled after them
==================  ==================================================
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional, Union

from jax.profiler import TraceAnnotation

#: True while a profiler session records in this process: the switch
enabled = TraceAnnotation.is_enabled

#: records kept; the oldest are dropped
RING = 1 << 20

_ring: collections.deque = collections.deque(maxlen=RING)
_ids = itertools.count(1)
_now = time.perf_counter


class Span(NamedTuple):
    name: str
    start: float
    end: float
    id: int
    parent: Optional[int]
    request: Optional[int]
    attrs: Dict[str, Any]

    @property
    def seconds(self) -> float:
        return self.end - self.start


class Count(NamedTuple):
    name: str
    t: float
    n: int
    parent: Optional[int]
    request: Optional[int]

    @property
    def start(self) -> float:
        return self.t


Record = Union[Span, Count]

#: builds a record from a tuple of its fields, one Python frame short of
#: ``Span(...)``: this runs once per span while tracing is on
_record = tuple.__new__


class _Stack(threading.local):
    def __init__(self):
        self.open: List["_Open"] = []


_stack = _Stack()


class _Open:
    """A span being recorded: the context manager :func:`span` returns
    while tracing is on."""

    __slots__ = ("name", "request", "root", "attrs", "id", "parent", "start", "ann")

    def __init__(self, name: str, request: Optional[int], root: bool,
                 attrs: Dict[str, Any]):
        self.name, self.request, self.root, self.attrs = name, request, root, attrs

    def __enter__(self):
        stack = _stack.open
        top = stack[-1] if stack else None
        self.parent = None if top is None else top.id
        if top is not None and (self.request is None
                                or (self.root and top.request is not None)):
            self.request = top.request
        self.id = next(_ids)
        self._annotate()
        stack.append(self)
        self.start = _now()
        return self

    def _annotate(self) -> None:
        stats = self.attrs if self.request is None else {**self.attrs,
                                                         "request": self.request}
        self.ann = TraceAnnotation(self.name, **stats)
        self.ann.__enter__()

    def __exit__(self, *exc):
        self._close(_now(), exc)
        _stack.open.pop()
        return False

    def _close(self, end: float, exc=(None, None, None)) -> None:
        self.ann.__exit__(*exc)
        _ring.append(_record(Span, (self.name, self.start, end, self.id,
                                    self.parent, self.request, self.attrs)))


class _Detached(_Open):
    """A span that other work runs across (a wait): it has no parent, is
    kept off the stack, and ends with :meth:`end`."""

    __slots__ = ()

    def __init__(self, name: str, attrs: Dict[str, Any]):
        stack = _stack.open
        super().__init__(name, stack[-1].request if stack else None, False, attrs)
        self.parent = None
        self.id = next(_ids)
        self._annotate()
        self.start = _now()

    def end(self) -> None:
        self._close(_now())


class _Off:
    """What :func:`span` and :func:`begin` return while tracing is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def end(self) -> None:
        pass


_OFF = _Off()


def span(name: str, *, request: Optional[int] = None, **attrs):
    """A context manager that records a span of ``name`` while tracing is on;
    ``request`` names the request it belongs to (default: its parent's)."""
    if enabled():
        return _Open(name, request, False, attrs)
    return _OFF


def root(name: str, request: int, **attrs):
    """A span that starts request ``request``, or joins the request already
    open around it."""
    if enabled():
        return _Open(name, request, True, attrs)
    return _OFF


def begin(name: str, **attrs):
    """Start a detached span; call ``.end()`` on what this returns."""
    if enabled():
        return _Detached(name, attrs)
    return _OFF


def count(name: str, n: int = 1) -> None:
    """Record ``n`` of ``name`` now, while tracing is on."""
    if enabled():
        stack = _stack.open
        top = stack[-1] if stack else None
        _ring.append(_record(Count, (name, _now(), n, None if top is None else top.id,
                                     None if top is None else top.request)))


def within(name: str) -> bool:
    """Whether the innermost open span on this thread is ``name``.  A method
    that traces itself calls itself again inside its span; the inner call
    sees its own span here and runs the plain body."""
    stack = _stack.open
    return bool(stack) and stack[-1].name == name


def current_request() -> Optional[int]:
    """The request of the innermost open span on this thread."""
    stack = _stack.open
    return stack[-1].request if stack else None


def records(t0: float = float("-inf"), t1: float = float("inf")) -> List[Record]:
    """Spans that lie inside ``[t0, t1]`` and counts made in it, by start."""
    out = [r for r in list(_ring)
           if (t0 <= r.t <= t1 if type(r) is Count else t0 <= r.start and r.end <= t1)]
    out.sort(key=lambda r: r.start)
    return out


def clear() -> None:
    """Drop every record."""
    _ring.clear()
