"""TransferEngine: the XDT API (`invoke`/`put`/`get`) over real ``jax.Array``s.

This is the host-level data plane used by the serving engine, the data
pipeline, and the workflow engine.  Each transfer medium is a
:class:`TransferBackend` *strategy class* registered by name — adding a new
medium (see :class:`HybridBackend` for a two-tier example) is one subclass
plus :func:`register_backend`, not edits to the engine.  The paper's §2.3
taxonomy maps to:

``xdt``
    The paper's contribution.  ``put`` leaves the array **device-resident in
    its producer sharding** inside the producer's :class:`BufferRegistry`
    (zero copies) and mints an HMAC-signed :class:`XDTRef`.  ``get`` opens the
    ref provider-side and moves the bytes once, directly, to the consumer's
    sharding.  Buffers die with the producer instance (``kill_producer``).

``inline``
    The payload rides the control message.  Enforces the 6 MB cap and pays a
    host staging round-trip (the activator path).  Dies with the producer.

``s3`` / ``elasticache``
    Through-storage: device -> host copy into a :class:`ServiceStore`;
    ``get`` returns the host-resident object and defers the host -> device
    move to the consumer's first jax op (or an explicit ``sharding=``).  The
    service is **durable across producer instance death** (the baseline
    premise of through-storage designs) and can be shared by every engine in
    a cluster so consumers on other instances resolve the same keys.

``hybrid``
    Two-tier through-storage: objects below ``net.hybrid_small_cutoff`` are
    priced/modeled as cache (ElastiCache), larger ones as object storage
    (S3) — the classic cost/latency compromise the paper's taxonomy
    describes.  Functionally identical to the other service backends.

Every backend records *modeled* transfer seconds (what the transfer would
cost on the calibrated cluster) plus the cost-model accounting, so examples
and benchmarks report latency and $ per transfer without real AWS.  All
accounting timestamps go through the injected :class:`~repro.core.clock`
clock, so an engine owned by a virtual-time workflow engine integrates
GB-seconds in simulated time.

Per-object routing: ``put(obj, backend="s3")`` overrides the engine default
for one object (the DAG layer's per-edge policies resolve the medium at send
time); the chosen medium is sealed inside the ref so ``get`` dispatches to
it directly, and per-medium op counts accumulate in ``media_acct`` so
:func:`repro.core.cost.routed_workflow_cost` can price a mixed-backend run.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, ClassVar, Dict, Optional, Tuple, Type, Union

import jax
import jax.numpy as jnp
import numpy as np

from .buffers import (
    _E_NBYTES,
    _E_OBJ,
    _E_REMAINING,
    BufferRegistry,
)
from .clock import VirtualClock, ensure_clock
from .cluster import DEFAULT_NET, NetConstants, TransferAccounting
from .cost import marginal_pull_fee_usd
from .errors import (
    InlineTooLarge,
    XDTError,
    XDTObjectExhausted,
    XDTProducerGone,
)
from .registry import Registry
from .refs import (
    _NONCE_LEN,
    ObjectDescriptor,
    RefMinter,
    RefPayload,
    SealedRef,
    XDTRef,
)
from .telemetry import TelemetryHub
from . import tracing

Sharding = Any  # jax.sharding.Sharding

_obj_new = object.__new__
_tracing = tracing.enabled


def _nbytes(x) -> int:
    """Total bytes of an array or pytree of arrays."""
    nb = getattr(x, "nbytes", None)
    if nb is not None:                    # fast path: a single array
        return int(nb)
    total = 0
    for leaf in jax.tree.leaves(x):
        leaf = jnp.asarray(leaf) if not hasattr(leaf, "nbytes") else leaf
        total += int(leaf.nbytes)
    return total


def _to_host(obj):
    """Host (numpy) view of an array or pytree; zero-copy when already host.

    ``np.asarray`` triggers ``__array__`` — a corrupt object still raises
    here, before any retrieval refcount is consumed."""
    if isinstance(obj, (np.ndarray, jax.Array)):
        return np.asarray(obj)
    return jax.tree.map(np.asarray, obj)


_DTYPE_STR: Dict[Any, str] = {}


def _dtype_str(dt) -> str:
    """Cached ``str(dtype)`` — numpy's dtype name formatting is surprisingly
    expensive and sits on the per-put hot path."""
    s = _DTYPE_STR.get(dt)
    if s is None:
        s = _DTYPE_STR[dt] = str(dt)
    return s


def _describe(obj) -> Tuple[Tuple[int, ...], str]:
    """(shape, dtype-string) for the descriptor; pytrees get a summary."""
    if isinstance(obj, (jax.Array, np.ndarray)):
        return tuple(obj.shape), _dtype_str(obj.dtype)
    return (len(jax.tree.leaves(obj)),), "pytree"


@dataclasses.dataclass
class TransferStats:
    transfers: int = 0
    bytes_moved: int = 0
    modeled_seconds: float = 0.0
    #: pulls that took the co-placement shared-memory path (``get(local=True)``
    #: on an instance-resident medium): modeled at memcpy speed, not the NIC
    local_pulls: int = 0
    #: instance-resident streamed chunk bytes published but not yet fully
    #: retrieved — the sender-side memory a live stream is holding.  Durable
    #: chunks never count (a storage put frees the producer's copy).  The
    #: high-water mark is what credit-based backpressure provably bounds:
    #: with ``Edge(max_inflight_chunks=k)`` it stays <= k * chunk_bytes.
    inflight_chunk_bytes: float = 0.0
    peak_inflight_chunk_bytes: float = 0.0


# ---------------------------------------------------------------------------
# The simulated external storage service (shared per cluster)
# ---------------------------------------------------------------------------


class ServiceStore:
    """Host-resident simulated storage service (the S3/ElastiCache analogue).

    One store per *cluster*, shared by every :class:`TransferEngine` whose
    backend goes through storage: a key minted by the producer's engine
    resolves from any consumer's engine, and — crucially — objects survive
    producer instance death.  Retrieval refcounts free an object after its
    last permitted ``get``; the copy-out happens **before** the refcount is
    decremented so a failed materialization does not leak a retrieval.
    """

    def __init__(self, clock: Optional[Callable[[], float]] = None):
        self.clock = ensure_clock(clock)
        self._objects: Dict[int, Any] = {}
        self._refcount: Dict[int, int] = {}
        self._nbytes: Dict[int, int] = {}
        self._next_key = 0
        # Service-side view of residency/ops (engines keep their own too).
        self.acct = TransferAccounting()

    def put(self, host_obj: Any, n_retrievals: int, nbytes: int) -> int:
        self._next_key += 1
        key = self._next_key
        self._objects[key] = host_obj
        self._refcount[key] = n_retrievals
        self._nbytes[key] = nbytes
        self.acct.n_storage_puts += 1
        self.acct.store(self.clock(), nbytes / 1e9)
        return key

    def fetch(self, key: int) -> Any:
        """Read without consuming a retrieval (consume() after a good copy)."""
        if key not in self._objects:
            raise XDTObjectExhausted(f"service object {key} gone")
        return self._objects[key]

    def consume(self, key: int) -> bool:
        """Burn one retrieval; frees the object on the last one.

        Missing keys raise :class:`XDTObjectExhausted` (never ``KeyError``)
        so cleanup races surface as the documented error.
        """
        if key not in self._refcount:
            raise XDTObjectExhausted(f"service object {key} gone")
        self._refcount[key] -= 1
        self.acct.n_storage_gets += 1
        if self._refcount[key] <= 0:
            nbytes = self._nbytes[key]
            self.acct.free(self.clock(), nbytes / 1e9)
            self._objects.pop(key, None)
            self._refcount.pop(key, None)
            self._nbytes.pop(key, None)
            return True
        return False

    def nbytes_of(self, key: int) -> int:
        return self._nbytes.get(key, 0)

    def __len__(self) -> int:
        return len(self._objects)


# ---------------------------------------------------------------------------
# Backend strategies
# ---------------------------------------------------------------------------


class TransferBackend:
    """One transfer medium: how ``put``/``get`` move bytes, what they model.

    Subclasses implement the storage mechanics; the engine keeps the shared
    concerns (refs, stats, sharding placement, wall timing).  Register new
    media with :func:`register_backend`.
    """

    name: ClassVar[str] = ""
    #: objects survive producer instance death (through-storage services)
    durable: ClassVar[bool] = False

    def __init__(self, engine: "TransferEngine"):
        self.engine = engine

    def put(
        self, obj: Any, n_retrievals: int, nbytes: int,
        block: bool, timeout: Optional[float],
    ) -> Tuple[int, int]:
        """Store ``obj``; return (buffer_id, epoch) for the ref payload."""
        raise NotImplementedError

    def get(self, payload: RefPayload) -> Any:
        """One retrieval; returns the materialized object."""
        raise NotImplementedError

    def on_producer_death(self) -> None:
        """Producer instance died.  Durable backends keep their objects."""

    @classmethod
    def modeled_seconds(cls, nbytes: int, net: NetConstants) -> float:
        """Deterministic producer->consumer latency on the calibrated cluster."""
        raise NotImplementedError


class XDTBackend(TransferBackend):
    """Zero-copy: arrays stay device-resident in the producer's registry."""

    name = "xdt"

    def put(self, obj, n_retrievals, nbytes, block, timeout):
        return self.engine.registry.put(
            obj, n_retrievals, nbytes=nbytes, block=block, timeout=timeout
        )

    def get(self, payload):
        return self.engine.registry.get(payload.buffer_id, payload.epoch)

    @classmethod
    def modeled_seconds(cls, nbytes, net):
        return (
            net.ctrl_plane_latency
            + net.xdt_pull_rtt
            + nbytes / min(net.xdt_stream_bw, net.nic_bw * net.xdt_stream_eff)
        )


class InlineBackend(TransferBackend):
    """Payload rides the control message: 6 MB cap, host staging round-trip."""

    name = "inline"

    def put(self, obj, n_retrievals, nbytes, block, timeout):
        if nbytes > self.engine.inline_limit:
            raise InlineTooLarge(
                f"{nbytes}B exceeds inline cap {self.engine.inline_limit}B"
            )
        return self.engine.registry.put(
            _to_host(obj),                  # staged via control plane (host)
            n_retrievals, nbytes=nbytes, block=block, timeout=timeout,
        )

    def get(self, payload):
        # Host-resident result: device materialization is lazy (the
        # consumer's first jax op — or an explicit ``sharding=`` on
        # ``TransferEngine.get`` — moves the bytes), so the control path
        # never pays a device_put per retrieval.
        return _to_host(self.engine.registry.get(payload.buffer_id, payload.epoch))

    @classmethod
    def modeled_seconds(cls, nbytes, net):
        return net.ctrl_plane_latency + nbytes / net.nic_bw


class _ServiceBackend(TransferBackend):
    """Shared mechanics of through-storage backends: device -> service ->
    consumer (lazy device materialization), durable across producer death,
    exception-safe refcounting."""

    durable = True
    #: this medium's TransferAccounting on the owning engine, bound on first
    #: op (media_acct only lists media that actually performed storage ops)
    _macct: Optional[TransferAccounting] = None

    def put(self, obj, n_retrievals, nbytes, block, timeout):
        # Inlined ServiceStore.put + TransferAccounting.store x3: the
        # through-storage cells of the engine benchmark spend their time in
        # exactly this op pair, so the store/accounting frames are unrolled
        # here (semantics identical to the methods they mirror).
        host = obj if type(obj) is np.ndarray else _to_host(obj)
        eng = self.engine
        svc = eng.service
        svc._next_key = key = svc._next_key + 1
        svc._objects[key] = host
        svc._refcount[key] = n_retrievals
        svc._nbytes[key] = nbytes
        vs = eng._vsim
        now = eng.clock() if vs is None else vs.now
        gb = nbytes / 1e9
        macct = self._macct
        if macct is None:
            macct = self._macct = eng._acct_for(self.name)
        for acct in (svc.acct, eng.acct, macct):
            acct.n_storage_puts += 1
            acct.storage_gb_seconds += acct._resident_gb * (now - acct._last_t)
            acct._last_t = now
            resident = acct._resident_gb = acct._resident_gb + gb
            if resident > acct.peak_resident_gb:
                acct.peak_resident_gb = resident
        return key, 0

    def get(self, payload):
        eng = self.engine
        svc = eng.service
        key = payload.buffer_id
        host = svc._objects.get(key)
        if host is None:
            raise XDTObjectExhausted(f"service object {key} gone")
        # Materialize BEFORE consuming the retrieval: a corrupt service
        # object must not burn one of the N permitted pulls.  The result
        # stays host-resident; the device copy is lazy (the consumer's first
        # jax op, or an explicit ``sharding=`` on ``TransferEngine.get``).
        obj = host if type(host) is np.ndarray else _to_host(host)
        # inlined ServiceStore.consume + accounting (see put)
        remaining = svc._refcount[key] = svc._refcount[key] - 1
        vs = eng._vsim
        now = eng.clock() if vs is None else vs.now
        macct = self._macct
        if macct is None:
            macct = self._macct = eng._acct_for(self.name)
        svc.acct.n_storage_gets += 1
        freed = remaining <= 0
        if freed:
            nbytes = svc._nbytes[key]
            sacct = svc.acct
            sacct.storage_gb_seconds += (
                sacct._resident_gb * (now - sacct._last_t)
            )
            sacct._last_t = now
            resident = sacct._resident_gb - nbytes / 1e9
            sacct._resident_gb = resident if resident > 0.0 else 0.0
            del svc._objects[key]
            del svc._refcount[key]
            del svc._nbytes[key]
        gb = payload.desc.nbytes / 1e9
        for acct in (eng.acct, macct):
            acct.n_storage_gets += 1
            if freed:
                acct.storage_gb_seconds += (
                    acct._resident_gb * (now - acct._last_t)
                )
                acct._last_t = now
                resident = acct._resident_gb - gb
                acct._resident_gb = resident if resident > 0.0 else 0.0
        return obj


class S3Backend(_ServiceBackend):
    name = "s3"

    @classmethod
    def modeled_seconds(cls, nbytes, net):
        return (
            2 * net.s3_op_latency
            + net.ctrl_plane_latency
            + 2 * nbytes / min(net.s3_stream_bw, net.nic_bw)
        )


class ElastiCacheBackend(_ServiceBackend):
    name = "elasticache"

    @classmethod
    def modeled_seconds(cls, nbytes, net):
        return (
            2 * net.ec_op_latency
            + net.ctrl_plane_latency
            + 2 * nbytes / min(net.ec_stream_bw, net.nic_bw)
        )


class HybridBackend(_ServiceBackend):
    """Two-tier through-storage: cache for small objects, S3 for large.

    Demonstrates that a new medium is one strategy class: it reuses the
    service mechanics wholesale and only redefines the latency model (and,
    in :func:`repro.core.cost.workflow_cost`, the pricing) by object size.
    """

    name = "hybrid"

    @classmethod
    def modeled_seconds(cls, nbytes, net):
        if nbytes < net.hybrid_small_cutoff:
            return ElastiCacheBackend.modeled_seconds(nbytes, net)
        return S3Backend.modeled_seconds(nbytes, net)


_BACKEND_REGISTRY = Registry("backend")


def register_backend(cls: Type[TransferBackend]) -> Type[TransferBackend]:
    """Register a strategy class under ``cls.name`` (idempotent overwrite)."""
    return _BACKEND_REGISTRY.register(cls)


for _cls in (XDTBackend, InlineBackend, S3Backend, ElastiCacheBackend, HybridBackend):
    register_backend(_cls)


def available_backends() -> Tuple[str, ...]:
    return tuple(_BACKEND_REGISTRY)


def modeled_transfer_seconds(
    backend: str, nbytes: int, net: NetConstants = DEFAULT_NET
) -> float:
    """Deterministic latency model for one producer->consumer object move."""
    cls = _BACKEND_REGISTRY.get(backend)
    if cls is None:
        raise ValueError(backend)
    return cls.modeled_seconds(nbytes, net)


#: media whose buffers live on the producer instance — the only ones a
#: co-placed consumer can short-circuit through shared memory (a durable
#: service round-trip is the same whichever node the consumer runs on)
INSTANCE_RESIDENT_MEDIA = ("xdt", "inline")


def local_transfer_seconds(nbytes: int, net: NetConstants = DEFAULT_NET) -> float:
    """Same-node pull: producer buffer -> consumer via shared memory.

    The engine-side counterpart of :meth:`ServerlessCluster.local_pull` —
    the modeled latency charged when the graph optimizer co-placed the
    consumer on its producer's node and the object rides an
    instance-resident medium."""
    return net.local_rtt + nbytes / net.local_bw


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------


class TransferEngine:
    """One producer-side endpoint of the XDT substrate."""

    #: the paper's §2.3 taxonomy; the full set is `available_backends()`
    BACKENDS = ("xdt", "inline", "s3", "elasticache")

    def __init__(
        self,
        backend: str = "xdt",
        *,
        producer_coords: Tuple[int, ...] = (0,),
        registry: Optional[BufferRegistry] = None,
        minter: Optional[RefMinter] = None,
        net: NetConstants = DEFAULT_NET,
        inline_limit: Optional[int] = None,
        service: Optional[ServiceStore] = None,
        clock: Optional[Callable[[], float]] = None,
        telemetry: Union[TelemetryHub, None, bool] = None,
    ):
        if backend not in _BACKEND_REGISTRY:
            raise ValueError(
                f"backend must be one of {available_backends()}"
            )
        self.backend = backend
        self.producer_coords = producer_coords
        self.clock = ensure_clock(clock)
        self.registry = (
            registry if registry is not None else BufferRegistry(clock=self.clock)
        )
        self.minter = minter if minter is not None else RefMinter()
        self.net = net
        self.inline_limit = (
            net.inline_limit if inline_limit is None else inline_limit
        )
        self.stats = TransferStats()
        self.acct = TransferAccounting()
        #: per-medium accounting for through-storage ops, so a mixed-backend
        #: (per-edge routed) run can be priced by each medium's fee structure
        #: (:func:`repro.core.cost.routed_workflow_cost`).  Only media that
        #: actually performed storage ops appear here.
        self.media_acct: Dict[str, TransferAccounting] = {}
        # the simulated external service; pass one in to share it cluster-wide
        self.service = service if service is not None else ServiceStore(self.clock)
        self._backend = _BACKEND_REGISTRY[backend](self)
        # per-engine strategy instances: the default plus any media used via
        # the per-call ``backend=`` override (all share registry/service/acct)
        self._strategies: Dict[str, TransferBackend] = {backend: self._backend}
        # (medium, nbytes) -> modeled seconds and (medium, nbytes,
        # n_retrievals) -> marginal pull fee: net constants and prices are
        # fixed per engine and workloads reuse a handful of object shapes,
        # so the per-get model/fee evaluation collapses to dict hits
        self._modeled_cache: Dict[Tuple[str, int], float] = {}
        self._fee_cache: Dict[Tuple[str, int, int], float] = {}
        # (shape, dtype, nbytes, n_retrievals) -> shared ObjectDescriptor:
        # sweeps reuse a handful of object shapes, so descriptor construction
        # on the fused put path collapses to a dict hit
        self._desc_cache: Dict[tuple, ObjectDescriptor] = {}
        #: fused hot path precondition: the default medium is producer-local
        #: xdt AND the registry is in single-owner mode — then put/get may
        #: inline the registry's unlocked bookkeeping (the registry stays the
        #: owner of the semantics; this is the same code, one frame deep)
        self._fast_single_owner = (
            type(self._backend) is XDTBackend and not self.registry._threadsafe
        )
        #: fused hot path precondition for through-storage media: the default
        #: medium is a service backend that did NOT override the shared
        #: mechanics — put/get may then inline the ServiceStore + accounting
        #: bookkeeping (same ops, no strategy or describe/mint frames)
        cls = type(self._backend)
        self._fast_service = (
            isinstance(self._backend, _ServiceBackend)
            and cls.put is _ServiceBackend.put
            and cls.get is _ServiceBackend.get
        )
        #: under a VirtualClock, "read the clock" is one attribute load off
        #: the simulator — the fused paths skip the ``__call__`` frame
        self._vsim = self.clock.sim if type(self.clock) is VirtualClock else None
        #: per-medium observed latency/cost/bytes feed — the shared substrate
        #: AdaptiveRoute (and anything else) reads; when set, every ``get``
        #: records the pull's modeled seconds and its marginal fee share
        #: (the one-time put/capacity fee apportioned across the object's
        #: permitted retrievals, so an N-consumer broadcast object is not
        #: observed as N puts).  Off by default so the legacy single-backend
        #: hot path pays nothing for the observe side; pass ``True`` (or a
        #: hub to share) to opt in — ``dag.bind`` switches it on
        #: automatically when an :class:`~repro.core.dag.AdaptiveRoute`
        #: needs the feed.
        self.telemetry: Optional[TelemetryHub] = (
            TelemetryHub(self.clock) if telemetry is True
            else telemetry if isinstance(telemetry, TelemetryHub)
            else None
        )
        #: fault-injection hooks (``core.faults``).  Both stay falsy/None
        #: unless a non-empty FaultPlan is installed, so the no-fault paths
        #: below reduce to one dict truthiness test / one ``is None`` test
        #: and results stay bit-identical to a build without the harness.
        #: ``_degraded`` maps medium -> bandwidth-cut slowdown multiplier
        #: (>= 1.0) applied OUTSIDE ``_modeled_cache`` (the cache keeps base
        #: values, so closing a degradation window needs no cache flush).
        self._degraded: Dict[str, float] = {}
        #: called as ``penalty(medium, nbytes, exc)`` when a strategy get
        #: raises; may return a replacement ``XDTError`` (e.g. reclassify
        #: :class:`~repro.core.errors.XDTProducerGone` as ``Evicted`` during
        #: an eviction storm) or ``None`` to re-raise the original.
        self._fault_penalty: Optional[
            Callable[[str, int, XDTError], Optional[XDTError]]
        ] = None

    # ----------------------------------------------------- medium dispatch
    def _acct_for(self, medium: str) -> TransferAccounting:
        acct = self.media_acct.get(medium)
        if acct is None:
            acct = self.media_acct[medium] = TransferAccounting()
        return acct

    def _strategy(self, medium: str) -> TransferBackend:
        strat = self._strategies.get(medium)
        if strat is None:
            cls = _BACKEND_REGISTRY.get(medium)
            if cls is None:
                raise ValueError(
                    f"backend must be one of {available_backends()}, got {medium!r}"
                )
            strat = self._strategies[medium] = cls(self)
        return strat

    # ------------------------------------------------------------------ put
    def put(
        self,
        obj: jax.Array,
        n_retrievals: int = 1,
        *,
        block: bool = True,
        timeout: Optional[float] = None,
        backend: Optional[str] = None,
    ) -> XDTRef:
        """Buffer ``obj`` (array or pytree) and mint a reference permitting
        ``n_retrievals`` pulls.

        ``backend`` overrides the engine's default medium for this one object
        (per-edge routing): the chosen medium is sealed inside the ref, so
        ``get`` dispatches to the same medium with no side-channel state.
        """
        if _tracing() and not tracing.within("xfer.put"):
            with tracing.span("xfer.put", medium=backend or self.backend,
                              nbytes=_nbytes(obj)):
                return self.put(obj, n_retrievals, block=block, timeout=timeout,
                                backend=backend)
        if backend is None and self._fast_single_owner:
            nb = getattr(obj, "nbytes", None)
            if nb is not None and n_retrievals >= 1:
                # fused put: single array -> unlocked registry -> sealed ref,
                # with no strategy/registry/minter frames in between
                nbytes = int(nb)
                reg = self.registry
                if (
                    len(reg._entries) < reg._max_slots
                    and (reg._bytes + nbytes <= reg._max_bytes
                         or not reg._entries)
                ):
                    buffer_id = reg._next_id
                    reg._next_id = buffer_id + 1
                    reg._entries[buffer_id] = [
                        obj, nbytes, n_retrievals, reg._epoch,
                        vs.now if (vs := self._vsim) is not None
                        else reg._clock(),
                    ]
                    b = reg._bytes = reg._bytes + nbytes
                    if b > reg._high_water:
                        reg._high_water = b
                    reg._puts += 1
                else:                  # no room: the raising path stays shared
                    buffer_id, _ = reg._put_unlocked(
                        obj, n_retrievals, nbytes, block
                    )
                dkey = (obj.shape, obj.dtype, nbytes, n_retrievals)
                desc = self._desc_cache.get(dkey)
                if desc is None:
                    desc = self._desc_cache[dkey] = ObjectDescriptor(
                        shape=tuple(obj.shape),
                        dtype=_dtype_str(obj.dtype),
                        nbytes=nbytes,
                        n_retrievals=n_retrievals,
                    )
                m = self.minter
                m._nonce_counter = nonce = m._nonce_counter + 1
                # SealedRef via object.__new__ + direct stores: the same four
                # assignments its __init__ performs, minus the call frame
                ref = _obj_new(SealedRef)
                ref._minter = m
                ref._payload = RefPayload(
                    self.producer_coords, buffer_id, reg._epoch, desc, "xdt",
                )
                ref._nonce = nonce.to_bytes(_NONCE_LEN, "big")
                ref._sealed = None
                return ref
        elif backend is None and self._fast_service:
            nb = getattr(obj, "nbytes", None)
            if nb is not None and n_retrievals >= 1:
                # fused through-storage put: inlined ServiceStore.put +
                # TransferAccounting.store x3 + cached descriptor + sealed
                # ref, with no strategy/describe/mint frames in between
                # (semantics identical to _ServiceBackend.put + mint)
                nbytes = int(nb)
                host = obj if type(obj) is np.ndarray else _to_host(obj)
                svc = self.service
                svc._next_key = bid = svc._next_key + 1
                svc._objects[bid] = host
                svc._refcount[bid] = n_retrievals
                svc._nbytes[bid] = nbytes
                vs = self._vsim
                now = self.clock() if vs is None else vs.now
                gb = nbytes / 1e9
                b = self._backend
                macct = b._macct
                if macct is None:
                    macct = b._macct = self._acct_for(b.name)
                a = svc.acct
                a.n_storage_puts += 1
                a.storage_gb_seconds += a._resident_gb * (now - a._last_t)
                a._last_t = now
                r = a._resident_gb = a._resident_gb + gb
                if r > a.peak_resident_gb:
                    a.peak_resident_gb = r
                a = self.acct
                a.n_storage_puts += 1
                a.storage_gb_seconds += a._resident_gb * (now - a._last_t)
                a._last_t = now
                r = a._resident_gb = a._resident_gb + gb
                if r > a.peak_resident_gb:
                    a.peak_resident_gb = r
                a = macct
                a.n_storage_puts += 1
                a.storage_gb_seconds += a._resident_gb * (now - a._last_t)
                a._last_t = now
                r = a._resident_gb = a._resident_gb + gb
                if r > a.peak_resident_gb:
                    a.peak_resident_gb = r
                dkey = (obj.shape, obj.dtype, nbytes, n_retrievals)
                desc = self._desc_cache.get(dkey)
                if desc is None:
                    desc = self._desc_cache[dkey] = ObjectDescriptor(
                        shape=tuple(obj.shape),
                        dtype=_dtype_str(obj.dtype),
                        nbytes=nbytes,
                        n_retrievals=n_retrievals,
                    )
                m = self.minter
                m._nonce_counter = nonce = m._nonce_counter + 1
                ref = _obj_new(SealedRef)
                ref._minter = m
                ref._payload = RefPayload(
                    self.producer_coords, bid, 0, desc, self.backend,
                )
                ref._nonce = nonce.to_bytes(_NONCE_LEN, "big")
                ref._sealed = None
                return ref
        strat = self._backend if backend is None else self._strategy(backend)
        nbytes = _nbytes(obj)
        buffer_id, epoch = strat.put(obj, n_retrievals, nbytes, block, timeout)
        shape, dtype = _describe(obj)
        return self.minter.mint(
            RefPayload(
                producer=self.producer_coords,
                buffer_id=buffer_id,
                epoch=epoch,
                desc=ObjectDescriptor(
                    shape=shape,
                    dtype=dtype,
                    nbytes=nbytes,
                    n_retrievals=n_retrievals,
                ),
                medium=strat.name,
            )
        )

    # ------------------------------------------------------------------ get
    def get(
        self,
        ref: XDTRef,
        sharding: Optional[Sharding] = None,
        local: bool = False,
    ) -> jax.Array:
        """One retrieval.  Moves the object directly to the consumer sharding.

        ``local=True`` declares that this consumer runs on the producer's
        node (the graph optimizer's co-placement hint was honored by the
        scheduler): instance-resident media (xdt/inline) are then modeled at
        shared-memory speed instead of the NIC path.  Durable service media
        ignore the hint — the storage round-trip is node-independent.
        """
        if _tracing() and not tracing.within("xfer.get"):
            payload = self.minter.open(ref)
            with tracing.span("xfer.get", medium=payload.medium or self.backend,
                              nbytes=payload.desc.nbytes):
                return self.get(ref, sharding, local)
        minter = self.minter
        if type(ref) is SealedRef and ref._minter is minter:
            payload = ref._payload     # same-domain fast open (no crypto)
        else:
            payload = minter.open(ref)  # raises XDTRefInvalid on forgery
        nbytes = payload.desc.nbytes
        medium = payload.medium or self.backend
        if (
            medium == "xdt"
            and self._fast_single_owner
            and not local
            and sharding is None
        ):
            # fused get: unlocked registry retrieval + cached latency model,
            # no strategy dispatch (mirrors BufferRegistry.get exactly)
            reg = self.registry
            if payload.epoch != reg._epoch:
                raise XDTProducerGone(
                    f"producer epoch {payload.epoch} superseded by {reg._epoch}"
                )
            entry = reg._entries.get(payload.buffer_id)
            if entry is None:
                raise XDTObjectExhausted(
                    f"buffer {payload.buffer_id} not resident"
                )
            obj = entry[_E_OBJ]
            entry[_E_REMAINING] = remaining = entry[_E_REMAINING] - 1
            reg._gets += 1
            if remaining == 0:
                reg._bytes -= entry[_E_NBYTES]
                del reg._entries[payload.buffer_id]
            stats = self.stats
            stats.transfers += 1
            stats.bytes_moved += nbytes
            key = ("xdt", nbytes)
            modeled = self._modeled_cache.get(key)
            if modeled is None:
                modeled = self._modeled_cache[key] = (
                    XDTBackend.modeled_seconds(nbytes, self.net)
                )
            stats.modeled_seconds += modeled
            if self.telemetry is not None:
                n = payload.desc.n_retrievals or 1
                fkey = ("xdt", nbytes, n)
                fee = self._fee_cache.get(fkey)
                if fee is None:
                    fee = self._fee_cache[fkey] = (
                        marginal_pull_fee_usd("xdt", nbytes, n)
                    )
                self.telemetry.record_transfer("xdt", nbytes, modeled, fee)
            return obj
        if (
            self._fast_service
            and medium == self.backend
            and sharding is None
        ):
            # fused through-storage get: inlined ServiceStore fetch/consume +
            # accounting + cached latency model — mirrors _ServiceBackend.get
            # exactly (service media ignore the co-placement hint: the
            # storage round-trip is node-independent)
            svc = self.service
            bid = payload.buffer_id
            host = svc._objects.get(bid)
            if host is None:
                raise XDTObjectExhausted(f"service object {bid} gone")
            # materialize BEFORE consuming the retrieval (see backend class)
            obj = host if type(host) is np.ndarray else _to_host(host)
            remaining = svc._refcount[bid] = svc._refcount[bid] - 1
            vs = self._vsim
            now = self.clock() if vs is None else vs.now
            b = self._backend
            macct = b._macct
            if macct is None:
                macct = b._macct = self._acct_for(b.name)
            freed = remaining <= 0
            a = svc.acct
            a.n_storage_gets += 1
            if freed:
                a.storage_gb_seconds += a._resident_gb * (now - a._last_t)
                a._last_t = now
                r = a._resident_gb - svc._nbytes[bid] / 1e9
                a._resident_gb = r if r > 0.0 else 0.0
                del svc._objects[bid]
                del svc._refcount[bid]
                del svc._nbytes[bid]
            gb = nbytes / 1e9
            a = self.acct
            a.n_storage_gets += 1
            if freed:
                a.storage_gb_seconds += a._resident_gb * (now - a._last_t)
                a._last_t = now
                r = a._resident_gb - gb
                a._resident_gb = r if r > 0.0 else 0.0
            a = macct
            a.n_storage_gets += 1
            if freed:
                a.storage_gb_seconds += a._resident_gb * (now - a._last_t)
                a._last_t = now
                r = a._resident_gb - gb
                a._resident_gb = r if r > 0.0 else 0.0
            stats = self.stats
            stats.transfers += 1
            stats.bytes_moved += nbytes
            mkey = (medium, nbytes)
            modeled = self._modeled_cache.get(mkey)
            if modeled is None:
                modeled = self._modeled_cache[mkey] = (
                    b.modeled_seconds(nbytes, self.net)
                )
            stats.modeled_seconds += modeled
            if self.telemetry is not None:
                n = payload.desc.n_retrievals or 1
                fkey = (medium, nbytes, n)
                fee = self._fee_cache.get(fkey)
                if fee is None:
                    fee = self._fee_cache[fkey] = (
                        marginal_pull_fee_usd(medium, nbytes, n)
                    )
                self.telemetry.record_transfer(medium, nbytes, modeled, fee)
            return obj
        strat = (
            self._backend if medium == self.backend else self._strategy(medium)
        )
        local = local and medium in INSTANCE_RESIDENT_MEDIA
        if self._fault_penalty is not None:
            # fault plan installed: give the injector a chance to reclassify
            # the failure
            try:
                obj = strat.get(payload)
            except XDTError as e:
                repl = self._fault_penalty(medium, nbytes, e)
                if repl is not None and repl is not e:
                    raise repl from e
                raise
        else:
            obj = strat.get(payload)

        if sharding is not None:
            obj = (
                jax.device_put(obj, sharding)
                if isinstance(obj, (jax.Array, np.ndarray))
                else jax.tree.map(lambda v: jax.device_put(v, sharding), obj)
            )

        stats = self.stats
        stats.transfers += 1
        stats.bytes_moved += nbytes
        key = ("local", nbytes) if local else (medium, nbytes)
        modeled = self._modeled_cache.get(key)
        if modeled is None:
            modeled = self._modeled_cache[key] = (
                local_transfer_seconds(nbytes, self.net) if local
                else strat.modeled_seconds(nbytes, self.net)
            )
        if self._degraded and not local:
            # degradation window: bandwidth cut inflates the modeled pull
            # (co-placed shared-memory copies are unaffected by a NIC/medium
            # throttle, hence the ``not local`` guard)
            modeled *= self._degraded.get(medium, 1.0)
        if local:
            stats.local_pulls += 1
        stats.modeled_seconds += modeled
        # co-placed pulls never feed the medium's telemetry: a shared-memory
        # copy says nothing about the medium's cross-node latency, and one
        # memcpy sample in the xdt p99 window would let AdaptiveRoute route
        # NON-co-placed edges against a budget the NIC path cannot meet
        if self.telemetry is not None and not local:
            n = payload.desc.n_retrievals or 1
            fkey = (medium, nbytes, n)
            fee = self._fee_cache.get(fkey)
            if fee is None:
                fee = self._fee_cache[fkey] = (
                    marginal_pull_fee_usd(medium, nbytes, n)
                )
            self.telemetry.record_transfer(medium, nbytes, modeled, fee)
        return obj

    # ------------------------------------------------------- chunk protocol
    def _credit_storage_requests(
        self, medium: str, *, puts: int = 0, gets: int = 0
    ) -> None:
        """Roll back storage *request* counts on the op that just billed
        them (service store + engine + per-medium accounting): chunks of one
        streamed logical object share a single multipart-upload PUT and a
        single ranged GET per medium, so only the first chunk's requests
        stand.  Residency (gb-seconds) and modeled seconds stay per chunk —
        bytes really are stored and moved chunk by chunk."""
        for a in (self.service.acct, self.acct, self._acct_for(medium)):
            a.n_storage_puts -= puts
            a.n_storage_gets -= gets

    def _track_chunk_published(self, nbytes: int) -> None:
        """One instance-resident chunk now held by the producer side."""
        s = self.stats
        s.inflight_chunk_bytes = f = s.inflight_chunk_bytes + nbytes
        if f > s.peak_inflight_chunk_bytes:
            s.peak_inflight_chunk_bytes = f

    def _track_chunk_consumed(self, nbytes: int, n_retrievals: int) -> None:
        """One retrieval of an instance-resident chunk: a broadcast chunk's
        bytes release fractionally, fully freed after its last consumer."""
        s = self.stats
        f = s.inflight_chunk_bytes - nbytes / (n_retrievals or 1)
        s.inflight_chunk_bytes = f if f > 0.0 else 0.0

    def put_chunk(
        self,
        obj: jax.Array,
        n_retrievals: int = 1,
        *,
        backend: Optional[str] = None,
        bill_put: bool = True,
    ) -> XDTRef:
        """Register one chunk of a streamed logical object.

        A chunk is an ordinary ref on ``backend`` — consumers pull it with
        :meth:`get_chunk`, producer death drops un-pulled instance-resident
        chunks exactly like whole objects (:class:`XDTProducerGone` drives
        the engine's retry path).  ``bill_put=False`` marks a continuation
        chunk of an object whose first chunk already billed the storage PUT
        request on this medium (multipart-upload semantics): the request
        count is credited back while residency stays per chunk.

        ``backend="inline"`` is refused: a chunk outlives the sync handoff
        message it would have to ride (the same reason staged/external
        objects can't inline).
        """
        medium = self.backend if backend is None else backend
        if medium == "inline":
            raise ValueError(
                "streaming chunks cannot ride 'inline': a chunk outlives "
                "the sync handoff message"
            )
        ref = self.put(obj, n_retrievals, backend=backend)
        if not bill_put and isinstance(self._strategy(medium), _ServiceBackend):
            self._credit_storage_requests(medium, puts=1)
        if medium in INSTANCE_RESIDENT_MEDIA:
            self._track_chunk_published(
                ref._payload.desc.nbytes
                if type(ref) is SealedRef and ref._minter is self.minter
                else self.minter.open(ref).desc.nbytes
            )
        return ref

    def get_chunk(
        self,
        ref: XDTRef,
        *,
        local: bool = False,
        bill_get: bool = False,
    ) -> jax.Array:
        """One chunk retrieval (see :meth:`put_chunk`).

        ``bill_get=True`` marks the first chunk a consumer pulls from a
        given (object, medium) pair — that one keeps its storage GET
        request; continuation chunks ride the same ranged GET and credit
        the request count back.  Continuation chunks also shed the
        per-request latency overhead from the modeled pull time (the
        connection is already open; only the marginal stream time of the
        extra bytes remains) — mirroring the cluster lowering, which
        coalesces a batch of ready chunks into one request per medium."""
        before = self.stats.modeled_seconds
        if type(ref) is SealedRef and ref._minter is self.minter:
            payload = ref._payload
        else:
            payload = self.minter.open(ref)
        medium = payload.medium or self.backend
        obj = self.get(ref, local=local)
        if not bill_get:
            if isinstance(self._strategy(medium), _ServiceBackend):
                self._credit_storage_requests(medium, gets=1)
            delta = self.stats.modeled_seconds - before
            overhead = modeled_transfer_seconds(medium, 0, self.net)
            if overhead > 0.0 and delta > 0.0:
                self.stats.modeled_seconds -= min(overhead, delta)
        if medium in INSTANCE_RESIDENT_MEDIA:
            self._track_chunk_consumed(
                payload.desc.nbytes, payload.desc.n_retrievals
            )
        return obj

    def put_chunk_span(
        self,
        obj: jax.Array,
        count: int,
        n_retrievals: int = 1,
        *,
        backend: Optional[str] = None,
        bill_put: bool = True,
    ) -> list:
        """Mint ``count`` chunk refs for one same-instant span of a streamed
        object — the producer-side half of the coalesced chunk-event path.

        Every chunk of the span carries the same payload ``obj`` (a span is
        a run of equal-size chunks published at one virtual instant), the
        descriptor is built once and shared columnar across the refs, and
        the storage-request crediting happens once for the whole span
        instead of per chunk.  Accounting, residency, and per-chunk float
        ops are bit-for-bit what ``count`` scalar :meth:`put_chunk` calls
        produce; only the first chunk bills the PUT request (and only when
        ``bill_put=True`` — multipart-upload semantics)."""
        if count <= 0:
            return []
        medium = self.backend if backend is None else backend
        if medium == "inline":
            raise ValueError(
                "streaming chunks cannot ride 'inline': a chunk outlives "
                "the sync handoff message"
            )
        nb = getattr(obj, "nbytes", None)
        if (
            medium == "xdt"
            and self._fast_single_owner
            and nb is not None
            and n_retrievals >= 1
        ):
            # fused span put: one descriptor, one nonce counter walk, no
            # strategy/minter frames — mirrors the scalar fused xdt put
            nbytes = int(nb)
            reg = self.registry
            vs = self._vsim
            dkey = (obj.shape, obj.dtype, nbytes, n_retrievals)
            desc = self._desc_cache.get(dkey)
            if desc is None:
                desc = self._desc_cache[dkey] = ObjectDescriptor(
                    shape=tuple(obj.shape),
                    dtype=_dtype_str(obj.dtype),
                    nbytes=nbytes,
                    n_retrievals=n_retrievals,
                )
            m = self.minter
            coords = self.producer_coords
            epoch = reg._epoch
            entries = reg._entries
            refs = []
            for _ in range(count):
                if (
                    len(entries) < reg._max_slots
                    and (reg._bytes + nbytes <= reg._max_bytes
                         or not entries)
                ):
                    buffer_id = reg._next_id
                    reg._next_id = buffer_id + 1
                    entries[buffer_id] = [
                        obj, nbytes, n_retrievals, epoch,
                        vs.now if vs is not None else reg._clock(),
                    ]
                    b = reg._bytes = reg._bytes + nbytes
                    if b > reg._high_water:
                        reg._high_water = b
                    reg._puts += 1
                else:
                    buffer_id, _ = reg._put_unlocked(
                        obj, n_retrievals, nbytes, True
                    )
                m._nonce_counter = nonce = m._nonce_counter + 1
                ref = _obj_new(SealedRef)
                ref._minter = m
                ref._payload = RefPayload(coords, buffer_id, epoch, desc, "xdt")
                ref._nonce = nonce.to_bytes(_NONCE_LEN, "big")
                ref._sealed = None
                refs.append(ref)
                self._track_chunk_published(nbytes)
            return refs
        if (
            medium == self.backend
            and self._fast_service
            and nb is not None
            and n_retrievals >= 1
        ):
            # fused through-storage span put: per-chunk residency floats stay
            # in the loop (bit-identical integration), request billing is
            # credited once for the span's continuation chunks
            nbytes = int(nb)
            host = obj if type(obj) is np.ndarray else _to_host(obj)
            svc = self.service
            vs = self._vsim
            now = self.clock() if vs is None else vs.now
            gb = nbytes / 1e9
            b = self._backend
            macct = b._macct
            if macct is None:
                macct = b._macct = self._acct_for(b.name)
            dkey = (obj.shape, obj.dtype, nbytes, n_retrievals)
            desc = self._desc_cache.get(dkey)
            if desc is None:
                desc = self._desc_cache[dkey] = ObjectDescriptor(
                    shape=tuple(obj.shape),
                    dtype=_dtype_str(obj.dtype),
                    nbytes=nbytes,
                    n_retrievals=n_retrievals,
                )
            m = self.minter
            coords = self.producer_coords
            accts = (svc.acct, self.acct, macct)
            refs = []
            for _ in range(count):
                svc._next_key = bid = svc._next_key + 1
                svc._objects[bid] = host
                svc._refcount[bid] = n_retrievals
                svc._nbytes[bid] = nbytes
                for a in accts:
                    a.n_storage_puts += 1
                    a.storage_gb_seconds += a._resident_gb * (now - a._last_t)
                    a._last_t = now
                    r = a._resident_gb = a._resident_gb + gb
                    if r > a.peak_resident_gb:
                        a.peak_resident_gb = r
                m._nonce_counter = nonce = m._nonce_counter + 1
                ref = _obj_new(SealedRef)
                ref._minter = m
                ref._payload = RefPayload(coords, bid, 0, desc, self.backend)
                ref._nonce = nonce.to_bytes(_NONCE_LEN, "big")
                ref._sealed = None
                refs.append(ref)
            credit = count - 1 if bill_put else count
            if credit:
                self._credit_storage_requests(medium, puts=credit)
            return refs
        # generic media (spilled mid-stream, custom backends, wall timing):
        # the scalar path already carries the exact semantics per chunk
        return [
            self.put_chunk(
                obj, n_retrievals, backend=backend,
                bill_put=bill_put and i == 0,
            )
            for i in range(count)
        ]

    def get_chunk_span(
        self,
        refs,
        *,
        local: bool = False,
        bill_first: bool = False,
        marks: Optional[list] = None,
    ) -> list:
        """Drain one run of same-(object, medium) chunks in a single kernel
        call — the consumer-side half of the coalesced chunk-event path.

        Bit-for-bit equivalent to calling :meth:`get_chunk` per ref (same
        accounting, same float-op order on ``stats.modeled_seconds``, same
        billing coalescing) with the per-chunk call frames, medium dispatch,
        and request crediting hoisted out of the loop.  ``bill_first=True``
        keeps the first ref's storage GET request — the ranged GET for this
        (object, medium) range; continuation refs always credit theirs back
        and shed the per-request latency overhead.

        ``marks`` (when given) receives ``stats.modeled_seconds`` after each
        chunk, letting the caller replay per-chunk debt accrual with the
        exact float-op sequence of the scalar path."""
        if not refs:
            return []
        minter = self.minter
        r0 = refs[0]
        stats = self.stats
        if not (type(r0) is SealedRef and r0._minter is minter):
            out = []
            for i, r in enumerate(refs):
                out.append(
                    self.get_chunk(r, local=local,
                                   bill_get=bill_first and i == 0)
                )
                if marks is not None:
                    marks.append(stats.modeled_seconds)
            return out
        medium = r0._payload.medium or self.backend
        net = self.net
        if (
            medium == "xdt"
            and self._fast_single_owner
            and not local
        ):
            reg = self.registry
            entries = reg._entries
            cache = self._modeled_cache
            fees = self._fee_cache
            tel = self.telemetry
            overhead = modeled_transfer_seconds("xdt", 0, net)
            epoch = reg._epoch
            billed = bill_first
            out = []
            for ref in refs:
                payload = ref._payload
                nbytes = payload.desc.nbytes
                before = stats.modeled_seconds
                if payload.epoch != epoch:
                    raise XDTProducerGone(
                        f"producer epoch {payload.epoch} superseded by "
                        f"{epoch}"
                    )
                entry = entries.get(payload.buffer_id)
                if entry is None:
                    raise XDTObjectExhausted(
                        f"buffer {payload.buffer_id} not resident"
                    )
                obj = entry[_E_OBJ]
                entry[_E_REMAINING] = remaining = entry[_E_REMAINING] - 1
                reg._gets += 1
                if remaining == 0:
                    reg._bytes -= entry[_E_NBYTES]
                    del entries[payload.buffer_id]
                stats.transfers += 1
                stats.bytes_moved += nbytes
                key = ("xdt", nbytes)
                modeled = cache.get(key)
                if modeled is None:
                    modeled = cache[key] = (
                        XDTBackend.modeled_seconds(nbytes, net)
                    )
                stats.modeled_seconds += modeled
                if tel is not None:
                    n = payload.desc.n_retrievals or 1
                    fkey = ("xdt", nbytes, n)
                    fee = fees.get(fkey)
                    if fee is None:
                        fee = fees[fkey] = (
                            marginal_pull_fee_usd("xdt", nbytes, n)
                        )
                    tel.record_transfer("xdt", nbytes, modeled, fee)
                if not billed:
                    delta = stats.modeled_seconds - before
                    if overhead > 0.0 and delta > 0.0:
                        stats.modeled_seconds -= min(overhead, delta)
                billed = False
                self._track_chunk_consumed(nbytes, payload.desc.n_retrievals)
                if marks is not None:
                    marks.append(stats.modeled_seconds)
                out.append(obj)
            return out
        if (
            self._fast_service
            and medium == self.backend
        ):
            svc = self.service
            objects = svc._objects
            refcount = svc._refcount
            vs = self._vsim
            now = self.clock() if vs is None else vs.now
            b = self._backend
            macct = b._macct
            if macct is None:
                macct = b._macct = self._acct_for(b.name)
            accts = (svc.acct, self.acct, macct)
            cache = self._modeled_cache
            fees = self._fee_cache
            tel = self.telemetry
            overhead = modeled_transfer_seconds(medium, 0, net)
            billed = bill_first
            credit = 0
            out = []
            for ref in refs:
                payload = ref._payload
                nbytes = payload.desc.nbytes
                before = stats.modeled_seconds
                bid = payload.buffer_id
                host = objects.get(bid)
                if host is None:
                    raise XDTObjectExhausted(f"service object {bid} gone")
                obj = host if type(host) is np.ndarray else _to_host(host)
                remaining = refcount[bid] = refcount[bid] - 1
                freed = remaining <= 0
                gb = nbytes / 1e9
                a = svc.acct
                a.n_storage_gets += 1
                if freed:
                    a.storage_gb_seconds += (
                        a._resident_gb * (now - a._last_t)
                    )
                    a._last_t = now
                    r = a._resident_gb - svc._nbytes[bid] / 1e9
                    a._resident_gb = r if r > 0.0 else 0.0
                    del objects[bid]
                    del refcount[bid]
                    del svc._nbytes[bid]
                for a in accts[1:]:
                    a.n_storage_gets += 1
                    if freed:
                        a.storage_gb_seconds += (
                            a._resident_gb * (now - a._last_t)
                        )
                        a._last_t = now
                        r = a._resident_gb - gb
                        a._resident_gb = r if r > 0.0 else 0.0
                stats.transfers += 1
                stats.bytes_moved += nbytes
                mkey = (medium, nbytes)
                modeled = cache.get(mkey)
                if modeled is None:
                    modeled = cache[mkey] = b.modeled_seconds(nbytes, net)
                stats.modeled_seconds += modeled
                if tel is not None:
                    n = payload.desc.n_retrievals or 1
                    fkey = (medium, nbytes, n)
                    fee = fees.get(fkey)
                    if fee is None:
                        fee = fees[fkey] = (
                            marginal_pull_fee_usd(medium, nbytes, n)
                        )
                    tel.record_transfer(medium, nbytes, modeled, fee)
                if not billed:
                    credit += 1
                    delta = stats.modeled_seconds - before
                    if overhead > 0.0 and delta > 0.0:
                        stats.modeled_seconds -= min(overhead, delta)
                billed = False
                if marks is not None:
                    marks.append(stats.modeled_seconds)
                out.append(obj)
            if credit:
                self._credit_storage_requests(medium, gets=credit)
            return out
        out = []
        for i, r in enumerate(refs):
            out.append(
                self.get_chunk(r, local=local, bill_get=bill_first and i == 0)
            )
            if marks is not None:
                marks.append(stats.modeled_seconds)
        return out

    # --------------------------------------------------------------- invoke
    def invoke(
        self,
        handler: Callable[[jax.Array], Any],
        obj: jax.Array,
        *,
        consumer_sharding: Optional[Sharding] = None,
    ) -> Any:
        """Blocking 1-1 call: pass ``obj`` by value to ``handler``.

        The SDK splits the call into control (the ref) + data (the pull) and
        re-joins them at the consumer before the handler runs — paper Fig. 4.
        """
        ref = self.put(obj, n_retrievals=1)
        payload = self.get(ref, sharding=consumer_sharding)
        return handler(payload)

    # ------------------------------------------------------------ lifecycle
    def kill_producer(self) -> int:
        """Producer instance death: drops device buffers, invalidates epochs.

        Objects in durable through-storage services (s3/elasticache/hybrid)
        survive by design — only instance-resident XDT/inline buffers die.
        """
        for strat in self._strategies.values():
            strat.on_producer_death()
        return self.registry.kill_instance()

    # ------------------------------------------------- fault-injection hooks
    # Used by core.faults.FaultInjector; all are exact inverses so closing a
    # degradation window restores the engine bit-for-bit.

    def degrade_medium(self, medium: str, slowdown: float) -> None:
        """Open a bandwidth-cut window: modeled pulls on ``medium`` are
        multiplied by ``slowdown`` (>= 1.0) until :meth:`clear_degraded`."""
        if slowdown > 1.0:
            self._degraded[medium] = float(slowdown)
        else:
            self._degraded.pop(medium, None)

    def clear_degraded(self, medium: Optional[str] = None) -> None:
        """Close a degradation window (all windows when ``medium=None``)."""
        if medium is None:
            self._degraded.clear()
        else:
            self._degraded.pop(medium, None)

    def wrap_medium(
        self, medium: str, wrapper: Callable[["TransferBackend"], "TransferBackend"]
    ) -> "TransferBackend":
        """Swap ``medium``'s strategy for ``wrapper(inner)``; returns the
        inner strategy so the caller can :meth:`unwrap_medium` later.

        This is how a decorator like ``faults.DegradedBackend`` composes
        over *any* registered medium without that medium opting in.
        """
        inner = self._strategy(medium)
        wrapped = wrapper(inner)
        self._strategies[medium] = wrapped
        if medium == self.backend:
            self._backend = wrapped
        return inner

    def unwrap_medium(self, medium: str, inner: "TransferBackend") -> None:
        """Undo :meth:`wrap_medium`: reinstall the saved inner strategy."""
        self._strategies[medium] = inner
        if medium == self.backend:
            self._backend = inner

    def suspend_fast_paths(self) -> Tuple[bool, bool]:
        """Force every get through the strategy dispatch (where the fault
        hooks live) for the duration of an installed plan; returns the saved
        flags for :meth:`resume_fast_paths`."""
        saved = (self._fast_single_owner, self._fast_service)
        self._fast_single_owner = False
        self._fast_service = False
        return saved

    def resume_fast_paths(self, saved: Tuple[bool, bool]) -> None:
        self._fast_single_owner, self._fast_service = saved
