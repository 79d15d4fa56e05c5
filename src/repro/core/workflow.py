"""Event-driven workflow engine: concurrent function DAGs on virtual time.

A workflow is a DAG of named functions.  Each function is user logic with the
signature ``handler(ctx, payload) -> payload`` where ``ctx`` exposes the XDT
API (paper Table 1): ``ctx.invoke(fn, obj)``, ``ctx.put(obj, n) -> ref``,
``ctx.get(ref) -> obj``.  Placement is delegated to the control plane
(:mod:`repro.core.scheduler`), transfers to a :class:`TransferEngine`.

Execution model
---------------
The engine runs on the discrete-event :class:`~repro.core.cluster.Simulator`:
scheduler, transfer accounting, and per-request latency records all share one
:class:`~repro.core.clock.VirtualClock`.  Many workflow *requests* can be in
flight at once (``submit`` + ``drain``), their invocations overlap in virtual
time, and cold starts gate execution exactly as the autoscaler decides.

Two handler styles:

* **Plain handlers** (``def h(ctx, payload): return ...``) run atomically at
  one virtual instant; the virtual time they owe — cold-start waits, modeled
  transfer seconds from ``ctx.get`` (puts are producer-local buffering and
  charge nothing; the through-storage round-trip is billed at the pull),
  ``ctx.sleep`` compute, the function's registered ``service_time`` —
  accrues as *debt* that the engine pays as one timeout after the handler
  body.  ``ctx.invoke`` is a blocking inline sub-invocation, as before.
* **Generator handlers** (``def h(ctx, payload): ... yield ...``) interleave
  with the rest of the cluster at every yield.  Yield a number to spend
  compute seconds, an :class:`AsyncResult` from ``ctx.call(fn, obj)`` to
  await one concurrent sub-invocation, or a list of them for fan-out/fan-in
  that actually overlaps.

Semantics (paper §4.2.2), unchanged from the synchronous engine:

* **At-most-once per invocation id** — invocation ids are issued from a
  monotonic high-watermark counter, so an id at or below the watermark can
  never be executed (re-issued) again; :class:`InvocationReplayed` guards the
  invariant without keeping every id ever issued alive in a set.
* **Producer-death recovery** — if a consumer's ``get()`` raises
  ``XDTProducerGone``, the error propagates to the *orchestrator* (the
  request process), which re-invokes the entry sub-workflow with the same
  arguments under fresh invocation ids (at-least-once at workflow level,
  at-most-once per id).
* Retries are bounded (``max_retries``), after which the error surfaces to
  the caller — identical to Step Functions fallback behaviour.

The blocking ``run(entry, payload)`` API is a thin wrapper: one ``submit``
plus driving the simulator to quiescence.

Memory at sweep scale
---------------------
``WorkflowEngine(records="columnar")`` switches invocation and request
bookkeeping to parallel arrays (:class:`InvocationLog`, :class:`RequestLog`):
O(a few dozen bytes) per invocation instead of an object each, and completed
:class:`WorkflowRequest` shells are not retained — million-request sweeps fit
in memory.  The default (``records="objects"``) keeps the legacy object lists.
"""
from __future__ import annotations

import dataclasses
from array import array
from inspect import isgeneratorfunction
from types import GeneratorType
from typing import Any, Callable, Dict, Generator, List, Optional, Sequence, Tuple

from heapq import heappush as _heappush

from .cluster import Event, Simulator
from .clock import VirtualClock
from .errors import (
    InvocationReplayed,
    MediumUnavailable,
    RetriesExhausted,
    XDTError,
    XDTProducerGone,
)
from .refs import XDTRef
from .scheduler import ControlPlane, Deployment, ScalingPolicy
from .topology import as_coord
from . import tracing
from .transfer import TransferEngine

_obj_new = object.__new__
_tracing = tracing.enabled


@dataclasses.dataclass(slots=True)
class InvocationRecord:
    invocation_id: int
    function: str
    instance_id: int
    attempt: int
    status: str  # "ok" | "error"
    error_code: Optional[str] = None
    t_start: float = 0.0              # virtual time the invocation was steered
    t_end: float = 0.0                # virtual time it completed

    def overlaps(self, other: "InvocationRecord") -> bool:
        return self.t_start < other.t_end and other.t_start < self.t_end


class InvocationLog:
    """Columnar invocation records: parallel arrays, O(1) bookkeeping.

    Supports ``len``, indexing, and iteration (materializing
    :class:`InvocationRecord` views lazily) so introspection code written
    against the object list keeps working; the hot-path aggregates the
    engine and load generator need — count, billed seconds, per-function
    tallies — are maintained incrementally.
    """

    __slots__ = (
        "invocation_ids", "functions", "instance_ids", "statuses",
        "error_codes", "t_starts", "t_ends", "billed_s",
    )

    def __init__(self):
        self.invocation_ids = array("q")
        self.functions: List[str] = []
        self.instance_ids = array("q")
        self.statuses = array("b")        # 1 = ok, 0 = error
        self.error_codes: Dict[int, str] = {}   # sparse: index -> code
        self.t_starts = array("d")
        self.t_ends = array("d")
        self.billed_s = 0.0

    def append(
        self, invocation_id: int, function: str, instance_id: int,
        status: str, error_code: Optional[str], t_start: float, t_end: float,
    ) -> None:
        if error_code is not None:
            self.error_codes[len(self.invocation_ids)] = error_code
        self.invocation_ids.append(invocation_id)
        self.functions.append(function)
        self.instance_ids.append(instance_id)
        self.statuses.append(1 if status == "ok" else 0)
        self.t_starts.append(t_start)
        self.t_ends.append(t_end)
        self.billed_s += t_end - t_start

    def __len__(self) -> int:
        return len(self.invocation_ids)

    def __getitem__(self, i: int) -> InvocationRecord:
        if i < 0:
            i += len(self.invocation_ids)   # error_codes is keyed by position
        return InvocationRecord(
            invocation_id=self.invocation_ids[i],
            function=self.functions[i],
            instance_id=self.instance_ids[i],
            attempt=0,
            status="ok" if self.statuses[i] else "error",
            error_code=self.error_codes.get(i),
            t_start=self.t_starts[i],
            t_end=self.t_ends[i],
        )

    def __iter__(self):
        for i in range(len(self.invocation_ids)):
            yield self[i]


class RequestLog:
    """Columnar end-to-end request outcomes (columnar engine mode)."""

    __slots__ = ("request_ids", "latencies_s", "ok_flags")

    def __init__(self):
        self.request_ids = array("q")
        self.latencies_s = array("d")
        self.ok_flags = array("b")

    def append(self, request_id: int, latency_s: float, ok: bool) -> None:
        self.request_ids.append(request_id)
        self.latencies_s.append(latency_s)
        self.ok_flags.append(1 if ok else 0)

    def __len__(self) -> int:
        return len(self.request_ids)


class WorkflowRequest:
    """One end-to-end workflow execution tracked by the orchestrator.

    Doubles as its own retry-driving state machine (formerly a separate
    ``_RequestTask`` object): it waits on the entry invocation's handle,
    re-invokes under fresh invocation ids on :class:`XDTProducerGone`
    (bounded by ``max_retries``), and settles itself on any other outcome —
    one allocation per request instead of two.
    """

    __slots__ = (
        "request_id", "entry", "payload", "submitted_at", "status", "result",
        "error", "started_at", "finished_at", "attempts",
        "_sim", "_done", "_eng", "_retries", "_handle",
    )

    def __init__(
        self,
        request_id: int,
        entry: str,
        payload: Any,
        submitted_at: float,
        sim: Optional[Simulator] = None,
    ):
        self.request_id = request_id
        self.entry = entry
        self.payload = payload
        self.submitted_at = submitted_at
        self.status = "pending"   # pending | running | ok | error | failed
        self.result: Any = None
        self.error: Optional[BaseException] = None
        self.started_at = 0.0
        self.finished_at = 0.0
        self.attempts = 0
        self._sim = sim
        self._done: Optional[Event] = None
        self._eng: Any = None
        self._retries = 0
        self._handle: Any = None

    @property
    def latency_s(self) -> float:
        return self.finished_at - self.submitted_at

    @property
    def done(self) -> Event:
        """Completion Event, materialized lazily: open-loop sweeps that poll
        the request log never allocate one; closed-loop clients that
        ``yield req.done`` get the exact old semantics."""
        d = self._done
        if d is None:
            d = self._done = Event(self._sim)
            if self.status in ("ok", "error", "failed"):
                d.set(self)
        return d

    def __repr__(self) -> str:
        return (
            f"WorkflowRequest(request_id={self.request_id}, "
            f"entry={self.entry!r}, status={self.status!r}, "
            f"attempts={self.attempts})"
        )

    # -- orchestration (the retry loop formerly in _RequestTask) ----------
    def _start(self, eng: "WorkflowEngine", presteered=None) -> None:
        self._eng = eng
        self.status = "running"
        self.started_at = eng.sim.now
        self._attempt(presteered)

    def _attempt(self, presteered=None) -> None:
        eng = self._eng
        while True:
            handle = _InvocationTask(eng, self.entry, self.payload,
                                     None, presteered)
            presteered = None          # retries re-steer at their own instant
            self.attempts += 1
            if not handle.fired:
                self._handle = handle
                handle._waiters.append(self)
                return
            if not self._settle(handle):
                return

    def __call__(self) -> None:
        handle, self._handle = self._handle, None
        if self._settle(handle):
            self._attempt()

    def _settle(self, handle: "AsyncResult") -> bool:
        """Consume one attempt's outcome; True means retry from the entry."""
        eng = self._eng
        err = handle.error
        if err is None:
            self.status, self.result = "ok", handle.value
        elif isinstance(err, (XDTProducerGone, MediumUnavailable)):
            if self._retries < eng.max_retries:
                # The producer instance is gone (its buffered objects died
                # with it) or the medium refused inside a degradation window.
                # Re-invoking from the entry function regenerates the objects
                # (paper §4.2.2) under fresh invocation ids.
                self._retries += 1
                eng.retry_total += 1
                if self._retries > eng.retry_max:
                    eng.retry_max = self._retries
                return True
            # Retry budget spent on transient errors: terminal *failed*
            # status in the log — priced for the work actually done — rather
            # than a raw exception aborting the whole sweep.
            self.status = "failed"
            self.error = RetriesExhausted(
                f"request {self.request_id}: retry budget "
                f"({eng.max_retries}) exhausted on {err.code}",
                cause=err,
            )
            eng.failed_requests += 1
            eng.failed_codes[err.code] = eng.failed_codes.get(err.code, 0) + 1
        else:
            self.status, self.error = "error", err
        self.finished_at = eng.sim.now
        eng._inflight_requests -= 1
        if eng._columnar:
            eng.request_log.append(
                self.request_id, self.finished_at - self.submitted_at,
                self.status == "ok",
            )
        d = self._done
        if d is not None:
            d.set(self)
        return False


class AsyncResult:
    """Handle for one concurrent sub-invocation (``ctx.call``).

    Resolution is intrinsic: the handle keeps its own ``fired`` flag and
    waiter list (state machines and fan-in counters append themselves
    directly), so the common await path allocates no :class:`Event` at all.
    ``done`` stays available for code that wants a real simulator event —
    it is materialized lazily and kept in sync with the handle.
    """

    __slots__ = ("function", "sim", "fired", "value", "error", "_waiters",
                 "_done")

    def __init__(self, sim: Simulator, function: str):
        self.function = function
        self.sim = sim
        self.fired = False
        self.value: Any = None
        self.error: Optional[BaseException] = None
        self._waiters: Optional[list] = []
        self._done: Optional[Event] = None

    @property
    def done(self) -> Event:
        """A real simulator :class:`Event` mirroring this handle (lazy)."""
        d = self._done
        if d is None:
            d = self._done = Event(self.sim)
            if self.fired:
                d.set(self)
        return d

    def _resolve(self) -> None:
        """Fire the handle: wake direct waiters via the run queue (FIFO, at
        this virtual instant — exactly the old ``done.set(handle)``)."""
        self.fired = True
        waiters = self._waiters
        self._waiters = None
        if waiters:
            ready = self.sim._ready
            for w in waiters:
                ready.append(w)
        if self._done is not None:
            self._done.set(self)


class _FanIn:
    """Countdown waiter for ``yield [handles]`` fan-in.

    One of these sits on every unresolved handle of the group; each firing
    runs it as its own run-queue event (matching the per-handle ``dec``
    events of the ``all_of`` it replaced, so ``events_processed`` and event
    order are unchanged) and the last one re-queues the owning task — which
    then executes as a separate event, exactly like the old machine wakeup.
    """

    __slots__ = ("task", "remaining")

    def __init__(self, task: "_InvocationTask", remaining: int):
        self.task = task
        self.remaining = remaining

    def __call__(self) -> None:
        self.remaining -= 1
        if self.remaining == 0:
            task = self.task
            task.sim._ready.append(task)


class ChunkStream:
    """Producer->consumer chunk mailbox of ONE streamed logical object.

    The producer interleaves compute slices with :meth:`push` (a ref per
    chunk, already ``put`` on its resolved medium) and :meth:`seal` when the
    object is complete.  Consumers drain ``refs`` by cursor and ``yield``
    the :attr:`more` event to park until the next publication; ``first``
    fires on the very first chunk — the engine lowering registers
    data-triggered activation on it, so a consumer is steered the moment
    its input starts landing instead of after the producer's orchestration
    round-trip.  After ``seal`` the ``more`` event stays fired, so a late
    consumer drains the backlog without ever parking.
    """

    __slots__ = ("sim", "refs", "media", "objs", "sealed", "first", "_more",
                 "_open_producers", "gate")

    def __init__(self, sim: Simulator, n_producers: int = 1):
        self.sim = sim
        self.refs: List[XDTRef] = []
        self.media: List[str] = []
        #: per-chunk logical-object token: chunks sharing a token are ranges
        #: of ONE object, so storage requests bill once per (token, medium)
        self.objs: List[Any] = []
        self.sealed = False
        self.first = Event(sim)
        self._more = Event(sim)
        # fan-in seal: a wave edge's consumer stream is fed by every
        # producer instance; the stream seals when the LAST producer does
        self._open_producers = n_producers
        #: credit-based backpressure hook (``Edge(max_inflight_chunks=...)``):
        #: when set, the consumer reports each drained chunk so the producer's
        #: credit window can release — ``None`` keeps the drain unconditional
        self.gate = None

    @property
    def more(self) -> Event:
        """The event the NEXT push (or seal) fires; permanently fired once
        sealed, so post-seal waits resume immediately."""
        return self._more

    def push(self, ref: XDTRef, medium: str, obj: Any) -> None:
        if self.sealed:
            raise RuntimeError("push() on a sealed ChunkStream")
        self.refs.append(ref)
        self.media.append(medium)
        self.objs.append(obj)
        if not self.first.fired:
            self.first.set()
        ev, self._more = self._more, Event(self.sim)
        ev.set()

    def push_span(self, refs: Sequence[XDTRef], medium: str, obj: Any) -> None:
        """Publish a same-instant run of chunks of ONE object with a single
        mailbox rotation: the lists extend columnar and waiting consumers
        wake once for the whole span instead of once per chunk.  Semantics
        are identical to ``push`` per ref — a parked consumer is appended to
        the run queue exactly once either way."""
        if self.sealed:
            raise RuntimeError("push_span() on a sealed ChunkStream")
        n = len(refs)
        self.refs.extend(refs)
        self.media.extend([medium] * n)
        self.objs.extend([obj] * n)
        if not self.first.fired:
            self.first.set()
        ev, self._more = self._more, Event(self.sim)
        ev.set()

    def seal(self) -> None:
        self._open_producers -= 1
        if self._open_producers > 0:
            return
        self.sealed = True
        if not self.first.fired:
            self.first.set()
        self._more.set()                # stays fired for late consumers


class CreditGate:
    """Producer-side credit window for ONE streaming edge's sender.

    ``Edge(max_inflight_chunks=w)`` bounds sender memory: at most ``w``
    instance-resident chunks may be published-but-undrained at once.  The
    producer registers each resident chunk via :meth:`publish` and parks on
    :meth:`wait` while :attr:`full`; consumers report every drained chunk
    through :meth:`on_pull`, which releases the credit once the chunk's last
    retrieval lands (broadcast chunks hold their credit until every consumer
    has pulled).  Durable chunks never register — the store, not the sender,
    holds them — so a pressure-spilled stream runs credit-free.  Refs the
    gate never registered are ignored, so consumers can report uncondition-
    ally.  Deadlock-free: a full window implies undrained chunks, and every
    streaming consumer is spawned (or data-trigger armed) before production
    starts, so someone is always able to drain.
    """

    __slots__ = ("sim", "window", "outstanding", "_event", "_pulls")

    def __init__(self, sim: Simulator, window: int):
        self.sim = sim
        self.window = window
        self.outstanding = 0
        self._event: Optional[Event] = None
        # id(ref) -> retrievals still holding the chunk's credit; keyed by
        # id because refs stay alive in the stream's columnar lists
        self._pulls: Dict[int, int] = {}

    @property
    def full(self) -> bool:
        return self.outstanding >= self.window

    def wait(self) -> Event:
        """Event firing on the next credit release; yield it while full."""
        ev = self._event
        if ev is None or ev.fired:
            ev = self._event = Event(self.sim)
        return ev

    def publish(self, ref: Any, n_retrievals: int) -> None:
        self.outstanding += 1
        self._pulls[id(ref)] = n_retrievals

    def on_pull(self, ref: Any) -> None:
        key = id(ref)
        rem = self._pulls.get(key)
        if rem is None:
            return
        if rem <= 1:
            del self._pulls[key]
            self.outstanding -= 1
            ev = self._event
            if ev is not None and not ev.fired:
                ev.set()
        else:
            self._pulls[key] = rem - 1


class Context:
    """Per-invocation SDK handle given to user handlers."""

    __slots__ = ("_engine", "_debt", "function", "attempt", "instance")

    def __init__(
        self,
        engine: "WorkflowEngine",
        function: str,
        attempt: int,
        instance=None,
    ):
        self._engine = engine
        self._debt = 0.0              # virtual seconds owed at next pay point
        self.function = function
        self.attempt = attempt
        self.instance = instance

    # -- debt ------------------------------------------------------------
    def _take_debt(self) -> float:
        d, self._debt = self._debt, 0.0
        return d

    def sleep(self, seconds: float) -> None:
        """Spend ``seconds`` of virtual compute time in this invocation."""
        self._debt += max(0.0, float(seconds))

    # XDT API (paper Table 1)
    def invoke(self, fn_name: str, obj: Any) -> Any:
        """Blocking sub-invocation: the caller stalls until the callee is
        done, and inherits the callee's virtual-time debt."""
        return self._engine._invoke_inline(fn_name, obj, parent=self)

    def call(
        self, fn_name: str, obj: Any, affinity: Optional[Tuple[int, ...]] = None
    ) -> AsyncResult:
        """Concurrent sub-invocation.  Generator handlers ``yield`` the
        handle (or a list of handles) to fan-in.

        ``affinity`` is a placement hint forwarded to the callee's
        ``Deployment.steer``: pass this invocation's own coords
        (``ctx.instance.coords``) to ask the activator to land the callee on
        the caller's node when slots allow — the graph optimizer's
        co-placement pass rides this to make XDT pulls instance-local.
        Accepts a plain tuple or a typed
        :class:`~repro.core.topology.Coord` (whose zone the steer can fall
        back to when the exact instance is busy)."""
        return _InvocationTask(self._engine, fn_name, obj, as_coord(affinity))

    def put(
        self, obj: Any, n_retrievals: int = 1, backend: Optional[str] = None
    ) -> XDTRef:
        """Buffer ``obj``; ``backend`` overrides the engine's default medium
        for this one object (per-edge routing — the ref remembers its
        medium, so the consumer's ``get`` needs no extra argument)."""
        return self._engine.transfer.put(obj, n_retrievals, backend=backend)

    def get(self, ref: XDTRef, local: bool = False) -> Any:
        """One retrieval.  ``local=True`` marks this consumer as co-placed
        with the producer (scheduling honored an affinity hint): pulls of
        instance-resident media are modeled at shared-memory speed."""
        stats = self._engine.transfer.stats
        before = stats.modeled_seconds
        obj = self._engine.transfer.get(ref, local=local)
        # the modeled pull latency becomes virtual time owed by this function
        self._debt += stats.modeled_seconds - before
        return obj

    def put_chunk(
        self,
        obj: Any,
        n_retrievals: int = 1,
        backend: Optional[str] = None,
        bill_put: bool = True,
    ) -> XDTRef:
        """Publish one chunk of a streamed logical object.

        Same medium semantics as :meth:`put`; ``bill_put=False`` suppresses
        the per-request PUT fee on service backends (multipart upload: one
        logical PUT per object, the first chunk pays it)."""
        return self._engine.transfer.put_chunk(
            obj, n_retrievals, backend=backend, bill_put=bill_put
        )

    def get_chunk(self, ref: XDTRef, local: bool = False, bill_get: bool = False) -> Any:
        """Pull one chunk; the modeled latency accrues as debt exactly like
        :meth:`get`.  ``bill_get=False`` (default) folds the request into the
        object's single ranged GET per medium — pass ``True`` on the first
        chunk pulled from each medium."""
        stats = self._engine.transfer.stats
        before = stats.modeled_seconds
        obj = self._engine.transfer.get_chunk(ref, local=local, bill_get=bill_get)
        self._debt += stats.modeled_seconds - before
        return obj

    def put_chunk_span(
        self,
        obj: Any,
        count: int,
        n_retrievals: int = 1,
        backend: Optional[str] = None,
        bill_put: bool = True,
    ) -> List[XDTRef]:
        """Publish a same-instant span of ``count`` chunks of one streamed
        object in a single kernel call (see ``TransferEngine.put_chunk_span``
        — refs built columnar, PUT billing coalesced once per span)."""
        return self._engine.transfer.put_chunk_span(
            obj, count, n_retrievals, backend=backend, bill_put=bill_put
        )

    def get_chunk_span(
        self, refs: Sequence[XDTRef], local: bool = False,
        bill_first: bool = False,
    ) -> List[Any]:
        """Drain a run of same-(object, medium) chunks in one kernel call;
        the modeled latency accrues as debt chunk by chunk (replayed from
        the kernel's per-chunk marks) so the total is bit-identical to the
        scalar drain's float-op sequence."""
        stats = self._engine.transfer.stats
        prev = stats.modeled_seconds
        marks: List[float] = []
        out = self._engine.transfer.get_chunk_span(
            refs, local=local, bill_first=bill_first, marks=marks
        )
        for m in marks:
            self._debt += m - prev
            prev = m
        return out

    # collective conveniences built from the primitives (paper §7.1)
    def scatter(self, fn_name: str, objs: Sequence[Any]) -> List[Any]:
        return [self.invoke(fn_name, o) for o in objs]

    def scatter_async(self, fn_name: str, objs: Sequence[Any]) -> List[AsyncResult]:
        """Overlapping scatter: spawn all, fan-in with ``yield handles``."""
        return [self.call(fn_name, o) for o in objs]

    def broadcast(self, fn_name: str, obj: Any, fan: int) -> List[Any]:
        ref = self.put(obj, n_retrievals=fan)
        return [self.invoke(fn_name, ref) for _ in range(fan)]

    def gather(self, refs: Sequence[XDTRef]) -> List[Any]:
        return [self.get(r) for r in refs]


class _InvocationTask(AsyncResult):
    """One control-plane-mediated invocation as a callable state machine.

    Replaces the per-invocation generator frame (steer -> cold-start wait ->
    control-plane hop -> handler -> debt -> record) on the hot path.  It
    produces the *exact* heap-entry sequence of the generator it replaced —
    the same pushes, at the same timestamps, taking the same ``seq`` numbers,
    with the separate wait/ctrl/debt timeouts kept separate (merging them
    would re-associate the float sums and shift timestamps by ulps) — so
    fixed-seed per-request latencies are bit-identical while each event costs
    no generator resume, no Process/Event wrapper, and no StopIteration.

    The task *is* its own :class:`AsyncResult`: ``ctx.call`` returns the task
    object directly, so an invocation costs one allocation, not a
    task + handle pair.  Resolution/waiter semantics are inherited unchanged.

    Generator *handlers* still interleave at every yield: the drive loop that
    used to live in ``WorkflowEngine._drive`` is inlined as phases 3-7.
    """

    __slots__ = (
        "eng", "payload", "fn", "svc_time", "invocation_id",
        "deployment", "instance", "ctx", "t0", "phase", "gen", "send",
        "throw_", "pending", "request",
    )

    # phases: what to do when the simulator calls us back
    # 0 cold-start wait elapsed -> push the ctrl hop
    # 1 ctrl hop elapsed        -> run the handler
    # 2 final debt elapsed      -> record + release + resolve the handle
    # 3 drive-loop debt elapsed -> dispatch the pending yielded value
    # 4 numeric yield elapsed   -> resume the generator handler
    # 5 awaited AsyncResult set -> resume with its value/error
    # 6 awaited fan-in group set-> resume with values/first error
    # 7 awaited raw Event set   -> resume with its value

    def __init__(self, eng: "WorkflowEngine", fn_name: str, payload: Any,
                 affinity=None, presteered=None):
        # intrinsic handle state (AsyncResult fields, inlined — no super())
        self.function = fn_name
        sim = self.sim = eng.sim
        self.fired = False
        self.value = None
        self.error = None
        self._waiters = []
        self._done = None
        # task state
        self.eng = eng
        self.payload = payload
        self.gen = None
        self.send = None
        self.throw_ = None
        self.pending = None
        try:
            entry = eng._dispatch.get(fn_name)
            if entry is None:
                raise KeyError(f"unknown function {fn_name!r}")
            self.fn, dep, self.svc_time = entry
            self.deployment = dep
            eng._invocation_watermark = iid = eng._invocation_watermark + 1
            self.invocation_id = iid
            if presteered is not None:   # batch-submitted: already steered
                self.instance, wait = presteered
            elif _tracing():
                self.instance, wait = self._steer_traced(dep, affinity)
            elif type(dep) is Deployment:
                # inlined Deployment.steer: one clock read + due-guarded
                # reap/mature + one pick — bit-identical to dep.steer(),
                # one frame cheaper per invocation
                vs = dep._vsim
                now = dep.clock() if vs is None else vs.now
                exp = dep._expiry
                if exp and exp[0][0] < now:
                    dep._reap_expired(now)
                warm = dep._warming
                if warm and warm[0][0] <= now:
                    dep._mature_warming(now)
                self.instance, wait = dep._steer_one(now, affinity)
            else:                        # custom deployment: keep the API
                self.instance, wait = dep.steer(affinity)
            self.t0 = sim.now
            if wait > 0:               # activator buffers across cold start
                self.phase = 0
                sim._seq = seq = sim._seq + 1
                _heappush(sim._heap, (sim.now + wait, seq, self))
                return
            ctrl = eng._ctrl_latency   # inlined _push_ctrl (warm common case)
            if ctrl > 0:
                self.phase = 1
                sim._seq = seq = sim._seq + 1
                _heappush(sim._heap, (sim.now + ctrl, seq, self))
            else:
                self._run_handler()
        except BaseException as e:     # pre-steer failure: nothing to record
            self.error = e
            self._resolve()

    def __call__(self) -> None:
        ph = self.phase                # ordered by observed frequency
        if ph == 1:
            self._run_handler()
        elif ph == 2:
            self._finish()
        elif ph == 5:
            h, self.pending = self.pending, None
            if h.error is not None:
                self.throw_ = h.error
            else:
                self.send = h.value
            self._drive_loop()
        elif ph == 6:
            hs, self.pending = self.pending, None
            errs = [h.error for h in hs if h.error is not None]
            if errs:
                self.throw_ = errs[0]
            else:
                self.send = [h.value for h in hs]
            self._drive_loop()
        elif ph == 3:
            y, self.pending = self.pending, None
            try:
                if not self._dispatch_yield(y):
                    return
            except BaseException as e:
                self._fail(e)
                return
            self._drive_loop()
        elif ph == 4:
            self._drive_loop()
        elif ph == 0:
            self._push_ctrl()
        else:
            ev, self.pending = self.pending, None
            self.send = ev.value
            self._drive_loop()

    def _steer_traced(self, dep, affinity):
        """The steer inside a ``wf.steer`` span (``Deployment.steer`` is the
        body inlined above); the task keeps the request it is steered under
        for the spans of its handler."""
        self.request = tracing.current_request()
        with tracing.span("wf.steer", function=self.function,
                          invocation=self.invocation_id):
            return dep.steer(affinity)

    def _handler_span(self):
        return tracing.span("wf.handler", request=getattr(self, "request", None),
                            function=self.function, invocation=self.invocation_id)

    def _push_ctrl(self) -> None:
        ctrl = self.eng._ctrl_latency
        if ctrl > 0:
            self.phase = 1
            sim = self.sim
            sim._seq = seq = sim._seq + 1
            _heappush(sim._heap, (sim.now + ctrl, seq, self))
        else:
            self._run_handler()

    def _run_handler(self) -> None:
        eng = self.eng
        # Context constructed via object.__new__ + direct stores: same five
        # assignments its __init__ would do, minus the call frame
        ctx = self.ctx = _obj_new(Context)
        ctx._engine = eng
        ctx._debt = 0.0
        ctx.function = self.function
        ctx.attempt = 0
        ctx.instance = self.instance
        try:
            if _tracing() and not isgeneratorfunction(self.fn):
                with self._handler_span():
                    out = self.fn(ctx, self.payload)
            else:
                out = self.fn(ctx, self.payload)
        except BaseException as e:
            self._fail(e)
            return
        if type(out) is GeneratorType:
            self.gen = out
            self._drive_loop()
            return
        self.pending = out
        debt = ctx._debt + self.svc_time
        ctx._debt = 0.0
        if debt > 0:
            self.phase = 2
            sim = self.sim
            sim._seq = seq = sim._seq + 1
            _heappush(sim._heap, (sim.now + debt, seq, self))
        else:
            self._finish()

    def _drive_loop(self) -> None:
        """Step the generator handler, paying debt at every yield boundary."""
        gen = self.gen
        while True:
            try:
                if _tracing():
                    yielded = self._resume_traced(gen)
                elif self.throw_ is not None:
                    t, self.throw_ = self.throw_, None
                    yielded = gen.throw(t)
                else:
                    s, self.send = self.send, None
                    yielded = gen.send(s)
            except StopIteration as stop:
                ctx = self.ctx
                debt = ctx._debt + self.svc_time
                ctx._debt = 0.0
                self.pending = stop.value
                if debt > 0:
                    self.phase = 2
                    sim = self.sim
                    sim._seq = seq = sim._seq + 1
                    _heappush(sim._heap, (sim.now + debt, seq, self))
                else:
                    self._finish()
                return
            except BaseException as e:
                self._fail(e)
                return
            ctx = self.ctx
            debt = ctx._debt
            if debt > 0:
                ctx._debt = 0.0
                self.pending = yielded
                self.phase = 3
                sim = self.sim
                sim._seq = seq = sim._seq + 1
                _heappush(sim._heap, (sim.now + debt, seq, self))
                return
            try:
                if not self._dispatch_yield(yielded):
                    return             # suspended on a heap entry or event
            except BaseException as e:
                self._fail(e)
                return

    def _resume_traced(self, gen):
        """One resumption of the generator handler, inside a span."""
        with self._handler_span():
            if self.throw_ is not None:
                t, self.throw_ = self.throw_, None
                return gen.throw(t)
            s, self.send = self.send, None
            return gen.send(s)

    def _dispatch_yield(self, yielded) -> bool:
        """Act on one value yielded by a generator handler.

        Returns True when the drive loop can continue immediately (the
        awaited event had already fired — the trampoline case of the old
        ``Simulator._step``), False when this task suspended.
        """
        sim = self.sim
        if isinstance(yielded, AsyncResult):   # most common: await a call
            if yielded.fired:
                if yielded.error is not None:
                    self.throw_ = yielded.error
                else:
                    self.send = yielded.value
                return True
            self.pending = yielded
            self.phase = 5
            yielded._waiters.append(self)
            return False
        if isinstance(yielded, (int, float)):
            v = float(yielded)
            self.phase = 4
            sim._seq = seq = sim._seq + 1
            _heappush(sim._heap, (sim.now + (v if v > 0.0 else 0.0), seq, self))
            return False
        if isinstance(yielded, (list, tuple)) and all(
            isinstance(h, AsyncResult) for h in yielded
        ):
            n_pending = 0
            for h in yielded:
                if not h.fired:
                    n_pending += 1
            if n_pending == 0:
                errs = [h.error for h in yielded if h.error is not None]
                if errs:
                    self.throw_ = errs[0]
                else:
                    self.send = [h.value for h in yielded]
                return True
            self.pending = yielded
            self.phase = 6
            fan = _FanIn(self, n_pending)
            for h in yielded:
                if not h.fired:
                    h._waiters.append(fan)
            return False
        if isinstance(yielded, Event):
            # raw simulator event: lets handlers wait on a signal that is
            # not an invocation (e.g. the streaming credit gate of
            # ``core/dag.py``, which parks a producer until a chunk is freed)
            if yielded.fired:
                self.send = yielded.value
                return True
            self.pending = yielded
            self.phase = 7
            yielded._waiters.append(self)
            return False
        raise TypeError(
            f"handler {self.ctx.function!r} yielded {type(yielded).__name__}; "
            "yield seconds, an AsyncResult, a list of AsyncResults, "
            "or a simulator Event"
        )

    def _finish(self) -> None:
        eng = self.eng
        self.value, self.pending = self.pending, None
        t1 = self.sim.now
        log = eng._ilog
        if log is not None:
            # inlined InvocationLog.append for the ok/no-error-code case:
            # same column order, no method frame or status-string compare
            log.invocation_ids.append(self.invocation_id)
            log.functions.append(self.function)
            log.instance_ids.append(self.instance.instance_id)
            log.statuses.append(1)
            log.t_starts.append(self.t0)
            log.t_ends.append(t1)
            log.billed_s += t1 - self.t0
        else:
            eng._record(
                self.invocation_id, self.function, self.instance.instance_id,
                "ok", None, self.t0, t1,
            )
        self.deployment.release(self.instance.instance_id)
        self._resolve()

    def _fail(self, e: BaseException) -> None:
        """Handler raised after steer: record the error, then surface it."""
        code = e.code if isinstance(e, XDTError) else None
        eng = self.eng
        eng._record(
            self.invocation_id, self.function, self.instance.instance_id,
            "error", code, self.t0, self.sim.now,
        )
        self.deployment.release(self.instance.instance_id)
        self.error = e
        self._resolve()


class WorkflowEngine:
    """Executes function DAGs concurrently with at-most-once semantics."""

    def __init__(
        self,
        transfer: Optional[TransferEngine] = None,
        control_plane: Optional[ControlPlane] = None,
        max_retries: int = 2,
        simulator: Optional[Simulator] = None,
        seed: int = 0,
        backend: str = "xdt",
        records: str = "objects",
    ):
        self.sim = simulator if simulator is not None else Simulator(seed=seed)
        self.clock = VirtualClock(self.sim)
        # `backend` picks the default transfer medium; pass `transfer` to
        # bring your own engine (it should share this engine's clock, or
        # GB-second accounting runs on wall time while requests run virtual).
        if transfer is not None:
            self.transfer = transfer
        else:
            # The registry's blocking flow control is wall-clock: on the
            # single-threaded virtual-time engine a blocked put() can never
            # be unblocked (the consumer that would free a slot runs on this
            # same thread), so the default 256-slot budget deadlocked sweeps
            # with a few hundred requests in flight.  Size the buffer budget
            # for sweep-scale concurrency instead; backpressure at this
            # layer is modeled in virtual time, not thread-blocked.
            from .buffers import BufferRegistry

            registry = BufferRegistry(
                max_slots=1 << 20, max_bytes=1 << 40, clock=self.clock,
                threadsafe=False,
            )
            self.transfer = TransferEngine(
                backend, registry=registry, clock=self.clock
            )
        self.control = (
            control_plane if control_plane is not None
            else ControlPlane(clock=self.clock)
        )
        self.functions: Dict[str, Callable[[Context, Any], Any]] = {}
        self.service_times: Dict[str, float] = {}
        self._deployments: Dict[str, Any] = {}   # per-function direct dispatch
        # one-hit dispatch cache: name -> (handler, deployment, service_time)
        # — the invocation hot path pays one dict probe instead of three
        self._dispatch: Dict[str, Tuple[Any, Any, float]] = {}
        self.max_retries = max_retries
        # fault/SLO observability (read by faults.SLOGuard): total retry
        # re-invocations, the worst per-request retry count, and terminal
        # failures bucketed by the transient error code that exhausted them
        self.retry_total = 0
        self.retry_max = 0
        self.failed_requests = 0
        self.failed_codes: Dict[str, int] = {}
        # high-watermark at-most-once: ids are issued monotonically; every id
        # <= the watermark is spent and can never be executed again
        self._invocation_watermark = 0
        self._request_counter = 0
        self._inflight_requests = 0
        if records not in ("objects", "columnar"):
            raise ValueError(f"records must be 'objects' or 'columnar', got {records!r}")
        self._columnar = records == "columnar"
        self.records: Any = InvocationLog() if self._columnar else []
        self.requests: List[WorkflowRequest] = []
        self.request_log = RequestLog() if self._columnar else None
        # prebound recorder: columnar appends go straight to the log with no
        # dispatch frame in between (the signatures match by construction)
        if self._columnar:
            self._record = self.records.append
        # the columnar log, or None: _finish inlines the append when set
        self._ilog = self.records if self._columnar else None
        # net constants are frozen per engine: cache the control-plane hop
        self._ctrl_latency = self.transfer.net.ctrl_plane_latency

    # -- registration ----------------------------------------------------------
    def register(
        self,
        name: str,
        handler: Callable[[Context, Any], Any],
        policy: Optional[ScalingPolicy] = None,
        service_time: float = 0.0,
        placer: Optional[Callable[[int], Tuple[int, ...]]] = None,
    ) -> None:
        """Register ``handler`` under ``name``.  ``service_time`` is the
        function's intrinsic compute duration in virtual seconds (on top of
        any ``ctx.sleep``/transfer debt it accrues).  ``placer`` maps
        instance ids to placement coords (e.g. zone-carrying
        :class:`~repro.core.topology.Coord` under a topology); default is
        the scheduler's ``(i,)``."""
        self.functions[name] = handler
        self.service_times[name] = service_time
        dep = self.control.register(
            name, policy or ScalingPolicy(max_instances=16), placer
        )
        # rate-driven autoscalers need requests-per-instance capacity before
        # the first completions exist; the registered service time is the
        # natural prior (no-op for telemetry-free legacy deployments)
        dep.seed_holding_estimate(service_time)
        self._deployments[name] = dep
        self._dispatch[name] = (handler, dep, service_time)

    # -- orchestrator ------------------------------------------------------------
    def submit(self, entry: str, payload: Any) -> WorkflowRequest:
        """Enqueue one workflow request; drive with ``drain()``/``run()``."""
        if _tracing() and not tracing.within("wf.request"):
            with tracing.root("wf.request", self._request_counter + 1, entry=entry):
                return self.submit(entry, payload)
        if entry not in self.functions:
            raise KeyError(f"unknown function {entry!r}")
        self._request_counter = rid = self._request_counter + 1
        req = WorkflowRequest(rid, entry, payload, self.sim.now, self.sim)
        self._inflight_requests += 1
        if not self._columnar:
            # columnar mode does not retain completed request shells; the
            # outcome lands in `request_log` instead
            self.requests.append(req)
        req._start(self)
        return req

    def submit_batch(self, entry: str, payloads: Sequence[Any]) -> List[WorkflowRequest]:
        """Submit many same-entry requests arriving at this virtual instant.

        The batched-arrival kernel behind the trace replay driver: one
        same-timestamp bucket of arrivals becomes one ``steer_batch`` against
        the deployment (a single reap/mature pass amortized over the bucket)
        followed by the per-request state machines.  Equivalent to calling
        :meth:`submit` once per payload — the per-request heap entries are
        identical — just cheaper per arrival.
        """
        if entry not in self.functions:
            raise KeyError(f"unknown function {entry!r}")
        # Batch-steer the whole bucket first: every request in the bucket
        # would have steered at this same instant anyway (steering happens at
        # submit time; the entry deployment is untouched in between), so one
        # reap/mature pass serves all of them and the per-arrival picks are
        # bit-identical to sequential submits.
        if _tracing():
            with tracing.span("wf.steer", function=entry, batch=len(payloads)):
                steers = self._deployments[entry].steer_batch(len(payloads))
        else:
            steers = self._deployments[entry].steer_batch(len(payloads))
        out = []
        for payload, presteered in zip(payloads, steers):
            self._request_counter = rid = self._request_counter + 1
            req = WorkflowRequest(rid, entry, payload, self.sim.now, self.sim)
            self._inflight_requests += 1
            if not self._columnar:
                self.requests.append(req)
            req._start(self, presteered)
            out.append(req)
        return out

    def drain(self) -> List[WorkflowRequest]:
        """Run the simulator until every submitted request completed."""
        self.sim.run()
        if self._inflight_requests:
            pending = [
                r for r in self.requests if r.status in ("pending", "running")
            ] or self._inflight_requests
            raise RuntimeError(f"workflow deadlock: {pending}")
        return self.requests

    def run(self, entry: str, payload: Any) -> Any:
        """Blocking wrapper: submit one request and drive it to completion;
        on XDTProducerGone the orchestrator re-invokes the entry sub-workflow
        with the original arguments, up to ``max_retries`` times."""
        if _tracing() and not tracing.within("wf.request"):
            with tracing.root("wf.request", self._request_counter + 1, entry=entry):
                return self.run(entry, payload)
        req = self.submit(entry, payload)
        self.sim.run()
        if req.error is not None:    # "error" and terminal "failed" alike
            raise req.error
        return req.result

    # -- execution ---------------------------------------------------------------
    def _next_invocation_id(self) -> int:
        invocation_id = self._invocation_watermark + 1
        if invocation_id <= self._invocation_watermark:  # pragma: no cover
            raise InvocationReplayed(f"id {invocation_id} already executed")
        self._invocation_watermark = invocation_id
        return invocation_id

    def _record(
        self, invocation_id: int, fn_name: str, instance_id: int,
        status: str, code: Optional[str], t_start: float, t_end: float,
    ) -> None:
        # objects mode only; columnar engines bind InvocationLog.append
        # directly over this method in __init__
        self.records.append(
            InvocationRecord(
                invocation_id, fn_name, instance_id, 0,
                status, code, t_start=t_start, t_end=t_end,
            )
        )

    def _spawn_invocation(
        self,
        fn_name: str,
        payload: Any,
        affinity: Optional[Tuple[int, ...]] = None,
        presteered: Optional[Tuple[Any, float]] = None,
    ) -> AsyncResult:
        """Start one control-plane-mediated invocation (state-machine task).

        The returned handle *is* the task object (an :class:`AsyncResult`
        subclass) — one allocation per invocation."""
        return _InvocationTask(self, fn_name, payload, affinity, presteered)

    def _invoke_inline(self, fn_name: str, payload: Any, parent: Context) -> Any:
        """Blocking sub-invocation from inside a running handler.

        Executes at the caller's current virtual instant; the callee's
        cold-start wait, control-plane hop, transfer debt, and service time
        are charged to the *caller's* debt (blocking-chain billing, the
        vSwarm semantics the cost model assumes).
        """
        traced = _tracing()
        if traced and not tracing.within("wf.invoke"):
            with tracing.span("wf.invoke", function=fn_name,
                              invocation=self._invocation_watermark + 1):
                return self._invoke_inline(fn_name, payload, parent)
        fn = self.functions.get(fn_name)
        if fn is None:
            raise KeyError(f"unknown function {fn_name!r}")
        invocation_id = self._next_invocation_id()
        deployment = self._deployments[fn_name]
        if traced:
            with tracing.span("wf.steer", function=fn_name, invocation=invocation_id):
                instance, wait = deployment.steer()
        else:
            instance, wait = deployment.steer()
        t0 = self.sim.now
        parent._debt += wait + self._ctrl_latency
        ctx = Context(self, fn_name, attempt=0, instance=instance)
        status, code = "ok", None
        try:
            if traced:
                with tracing.span("wf.handler", function=fn_name,
                                  invocation=invocation_id):
                    out = fn(ctx, payload)
            else:
                out = fn(ctx, payload)
            if type(out) is GeneratorType:
                raise TypeError(
                    f"generator handler {fn_name!r} cannot be invoked inline; "
                    "use ctx.call() / scatter_async() / submit()"
                )
            parent._debt += ctx._take_debt() + self.service_times[fn_name]
            return out
        except XDTError as e:
            status, code = "error", e.code
            raise
        except BaseException:
            status = "error"               # foreign errors: no stable code
            raise
        finally:
            self._record(
                invocation_id, fn_name, instance.instance_id,
                status, code, t0, self.sim.now,
            )
            deployment.release(instance.instance_id)

    # -- introspection -----------------------------------------------------------
    def executed_count(self, fn_name: Optional[str] = None) -> int:
        if self._columnar:
            if fn_name is None:
                return len(self.records)
            return self.records.functions.count(fn_name)
        return sum(
            1 for r in self.records if fn_name is None or r.function == fn_name
        )

    def billed_virtual_seconds(self) -> float:
        """Sum of per-invocation (t_end - t_start) across all records."""
        if self._columnar:
            return self.records.billed_s
        return sum(r.t_end - r.t_start for r in self.records)

    def assert_at_most_once(self) -> None:
        """Invariant: no invocation id appears twice in the records."""
        if self._columnar:
            ids = list(self.records.invocation_ids)
        else:
            ids = [r.invocation_id for r in self.records]
        assert len(ids) == len(set(ids)), "invocation id executed more than once"

    def latency_records(self) -> List[Tuple[int, float]]:
        """(request_id, end-to-end latency in virtual seconds) per request."""
        if self._columnar:
            log = self.request_log
            # the log appends in completion order; report in request-id
            # (submission) order like the legacy object list
            return sorted(zip(log.request_ids, log.latencies_s))
        return [
            (r.request_id, r.latency_s)
            for r in self.requests
            if r.status in ("ok", "error", "failed")
        ]
