"""Control-plane analogue: activator + autoscaler + per-instance queue-proxy.

Paper §2.2: every invocation traverses the *activator* (load balancer), which
steers it to the least-loaded instance; the *autoscaler* watches per-instance
load (reported by each instance's *queue-proxy*) and scales the deployment;
cold starts buffer the invocation until a new instance is up.

XDT's core compatibility claim is that the control plane is **unchanged** —
placement decisions happen exactly here, before any bulk data moves, and the
data plane then pulls producer->chosen-consumer directly.  The workflow
engine uses this scheduler to pick function instances.

All time-dependent decisions (keep-alive reaping, cold-start gates) read the
injected clock (:mod:`repro.core.clock`): real time by default, a
:class:`~repro.core.clock.VirtualClock` under the event-driven workflow
engine — which makes autoscaler dynamics exactly assertable in tests and
fast-forwardable in load sweeps.

Scalability: ``steer()`` is O(log n) in fleet size.  Ready instances live in
a lazily-invalidated min-heap keyed ``(load, instance_id)`` (exactly the old
linear scan's ordering), booting instances in a ``ready_at`` heap that
matures them into the ready set, and keep-alive reaping is driven by
scheduled expiry times instead of sweeping every instance on every steer.
Heap entries carry the instance's version counter; any in-flight change bumps
the version, so stale entries are discarded on pop instead of being searched
for and removed — the million-steer path never scans the fleet.

Scale-up strategy is pluggable (:class:`AutoscalerPolicy`): the default
:class:`ConcurrencyPolicy` is the legacy reactive Knative-concurrency
behaviour bit-for-bit; :class:`RpsPolicy` sizes the fleet from the observed
arrival-rate window; :class:`PredictivePolicy` pre-warms from the rate trend.
Rate-driven policies read the deployment's
:class:`~repro.core.telemetry.DeploymentTelemetry` (arrival/concurrency/
cold-start windows on the injected clock).  Select per deployment via
``ScalingPolicy(autoscaler=...)`` — a registered name or a policy instance —
and register custom strategies with :func:`register_autoscaler`.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
from collections import deque
from heapq import heapify, heappop, heappush
from typing import Callable, ClassVar, Dict, List, Optional, Tuple, Type, Union

from .clock import VirtualClock, ensure_clock
from .registry import Registry
from .telemetry import DeploymentTelemetry
from .topology import as_coord


# ---------------------------------------------------------------------------
# Autoscaler policies (strategy layer)
# ---------------------------------------------------------------------------


class AutoscalerPolicy:
    """Decides *when a deployment adds instances*; steering stays shared.

    The :class:`Deployment` owns the mechanics (heaps, keep-alive reaping,
    queue-wait modeling) and consults its policy at two points of ``steer``:

    * ``desired_instances(dep, now)`` — a proactive fleet-size floor,
      evaluated per arrival when ``needs_telemetry`` is set; the deployment
      spawns (cold) up to it before picking an instance.
    * ``reactive`` — whether a request that finds no ready instance below
      the ``max_instances`` cap spawns a cold instance on the spot (the
      legacy Knative-concurrency behaviour) or queues on the booting /
      least-loaded fleet the proactive floor provisioned.

    Policies are stateless — all signals live on the deployment (its
    :class:`~repro.core.telemetry.DeploymentTelemetry`, holding-time EWMAs,
    in-flight totals) — so one policy instance can serve many deployments.
    Register new policies with :func:`register_autoscaler`; every
    ``ScalingPolicy(autoscaler=...)`` site (``WorkflowEngine.register``,
    ``dag.compile`` on either lowering, the loadgen-driven benchmarks)
    then selects them by name.
    """

    name: ClassVar[str] = ""
    #: maintain a DeploymentTelemetry (arrival/concurrency/cold-start
    #: windows) on the deployment; False keeps the steer hot path free of
    #: any telemetry work (the legacy policy pays nothing for this layer)
    needs_telemetry: ClassVar[bool] = False
    #: legacy reactive scale-up on a steer miss below the cap
    reactive: ClassVar[bool] = True
    #: proactively retire idle surplus instances when the policy's desired
    #: count falls below the live fleet (instead of waiting out keep-alive).
    #: Policies that opt in may also set ``scale_down_slack`` (a >= 1.0
    #: multiplier on the desired count: a warm buffer against rate-estimate
    #: jitter) and ``scale_down_delay_s`` (how long the surplus must persist
    #: continuously before the trim fires — the anti-flap hysteresis).
    scale_down: ClassVar[bool] = False

    def desired_instances(self, dep: "Deployment", now: float) -> int:
        return 0


class ConcurrencyPolicy(AutoscalerPolicy):
    """The legacy Knative-style concurrency autoscaler, bit-for-bit.

    Scale-up is purely reactive: an arrival that finds every instance at
    ``target_concurrency`` spawns a cold instance (below the cap).  This is
    the default and reproduces the pre-policy-layer ``Deployment`` exactly —
    the fixed-seed latency checksums in ``results/BENCH_engine.json`` and
    the differential-vs-legacy steer test both guard it.
    """

    name = "concurrency"


class RpsPolicy(AutoscalerPolicy):
    """Knative's requests-per-second autoscaling mode.

    Sizes the fleet from the observed arrival rate instead of instantaneous
    concurrency: ``desired = ceil(rate / (rps_per_instance * utilization))``.
    The per-instance capacity defaults to ``target_concurrency /
    holding_time`` using the deployment's observed (or seeded) holding-time
    EWMA, derated by the target ``utilization`` (Knative's
    target-utilization knob: sizing for 100% of capacity queues without
    bound under Poisson arrivals); until a holding estimate exists it
    provisions like the concurrency policy would (one slot per in-flight
    request).  Because scale-up is driven by the rate window rather than
    per-request misses, a load spike provisions the steady-state fleet
    instead of one instance per arrival caught mid cold-start — far fewer
    cold starts at high offered load, at the price of queueing while the
    right-sized fleet boots.
    """

    name = "rps"
    needs_telemetry = True
    reactive = False

    def __init__(
        self,
        target_rps_per_instance: Optional[float] = None,
        utilization: float = 0.7,
    ):
        self.target_rps_per_instance = target_rps_per_instance
        self.utilization = utilization

    def _capacity_rps(self, dep: "Deployment") -> Optional[float]:
        """Sustainable requests/sec of one instance, or None if unknown."""
        if self.target_rps_per_instance is not None:
            return self.target_rps_per_instance * self.utilization
        hold = dep._service_ewma
        if hold <= 0.0:
            return None
        return max(1, dep.policy.target_concurrency) / hold * self.utilization

    def _bootstrap(self, dep: "Deployment") -> int:
        """No holding-time signal yet: provision for observed concurrency."""
        slots = max(1, dep.policy.target_concurrency)
        return -(-(dep.in_flight_total + 1) // slots)

    def desired_instances(self, dep: "Deployment", now: float) -> int:
        per = self._capacity_rps(dep)
        if per is None:
            return self._bootstrap(dep)
        rate = dep.telemetry.arrival_rate(now)
        return max(1, math.ceil(rate / per))


class PredictivePolicy(RpsPolicy):
    """Pre-warms from the arrival-rate *trend* — and decays the prewarm.

    Scale-up: extrapolates the rate over the cold-start horizon (``rate +
    slope * cold_start_s``, never below the current rate) and provisions for
    the forecast with a small headroom — so a ramping load finds instances
    already booting when it arrives instead of paying the boot latency per
    request.  On flat or falling load the forecast degrades to
    :class:`RpsPolicy`.

    Scale-down: with ``scale_down`` (the default) a fleet the forecast no
    longer justifies is trimmed proactively — idle surplus instances beyond
    ``desired * scale_down_slack + scale_down_surge * sqrt(desired)`` are
    retired on arrival instead of idling out the full keep-alive window.
    Three dampers keep the trim from costing rebound cold starts: the slack
    is a warm buffer against rate-estimate jitter, the square-root staffing
    term keeps clump-absorbing capacity on small fleets (Poisson bursts are
    relatively larger there), and ``scale_down_delay_s`` requires the
    surplus to persist continuously before anything is retired (steady-load
    noise crosses back under the threshold and resets the timer; a real
    load drop does not).  ``scale_down=False`` restores the reap-only
    behaviour.
    """

    name = "predictive"

    def __init__(
        self,
        target_rps_per_instance: Optional[float] = None,
        utilization: float = 0.7,
        horizon_s: Optional[float] = None,
        headroom: float = 1.2,
        scale_down: bool = True,
        scale_down_slack: float = 1.25,
        scale_down_delay_s: float = 3.0,
        scale_down_surge: float = 2.0,
    ):
        super().__init__(target_rps_per_instance, utilization)
        self.horizon_s = horizon_s      # None: the deployment's cold_start_s
        self.headroom = headroom
        self.scale_down = scale_down
        if scale_down_slack < 1.0:
            raise ValueError("scale_down_slack must be >= 1.0")
        self.scale_down_slack = scale_down_slack
        if scale_down_delay_s < 0.0:
            raise ValueError("scale_down_delay_s must be >= 0")
        self.scale_down_delay_s = scale_down_delay_s
        if scale_down_surge < 0.0:
            raise ValueError("scale_down_surge must be >= 0")
        self.scale_down_surge = scale_down_surge

    def desired_instances(self, dep: "Deployment", now: float) -> int:
        per = self._capacity_rps(dep)
        if per is None:
            return self._bootstrap(dep)
        rate, slope = dep.telemetry.arrival_trend(now)
        horizon = dep.policy.cold_start_s if self.horizon_s is None else self.horizon_s
        forecast = max(rate, rate + slope * horizon) * self.headroom
        return max(1, math.ceil(forecast / per))


_AUTOSCALER_REGISTRY = Registry("autoscaler")


def register_autoscaler(cls: Type[AutoscalerPolicy]) -> Type[AutoscalerPolicy]:
    """Register a policy class under ``cls.name`` (idempotent overwrite)."""
    return _AUTOSCALER_REGISTRY.register(cls)


for _cls in (ConcurrencyPolicy, RpsPolicy, PredictivePolicy):
    register_autoscaler(_cls)


def available_autoscalers() -> Tuple[str, ...]:
    return tuple(_AUTOSCALER_REGISTRY)


_DEFAULT_AUTOSCALER = ConcurrencyPolicy()


def make_autoscaler(
    spec: Union[None, str, AutoscalerPolicy]
) -> AutoscalerPolicy:
    """Resolve a policy spec: None (legacy default) | name | instance."""
    if spec is None:
        return _DEFAULT_AUTOSCALER
    if isinstance(spec, AutoscalerPolicy):
        return spec
    cls = _AUTOSCALER_REGISTRY.get(spec)
    if cls is None:
        raise ValueError(
            f"autoscaler must be one of {available_autoscalers()}, got {spec!r}"
        )
    return cls()


@dataclasses.dataclass
class ScalingPolicy:
    """Per-deployment scaling knobs + the autoscaler strategy that uses them."""

    target_concurrency: int = 1       # desired in-flight per instance
    min_instances: int = 0
    max_instances: int = 64
    keep_alive_s: float = 60.0        # idle instance lifetime (paper §4.1: >> data lifetime)
    cold_start_s: float = 0.5         # instance boot latency
    #: at the max_instances cap, model the activator's queue delay from the
    #: chosen instance's residual work — the modeled completion time of the
    #: in-flight request whose finish frees this request's concurrency slot
    #: (False restores the legacy wait=0 bug)
    queue_wait_model: bool = True
    #: scale-up strategy: None (legacy concurrency autoscaler), a registered
    #: policy name ("concurrency" | "rps" | "predictive" | custom), or an
    #: AutoscalerPolicy instance
    autoscaler: Union[None, str, AutoscalerPolicy] = None


@dataclasses.dataclass(slots=True)
class Instance:
    instance_id: int
    coords: Tuple[int, ...]           # placement (e.g. pod / mesh slice)
    in_flight: int = 0
    last_used: float = 0.0
    epoch: int = 0                    # bumps when instance is recycled
    ready_at: float = 0.0             # cold-start gate
    alive: bool = True
    #: bumped on every in_flight change / death; heap entries minted against
    #: an older version are stale and discarded on pop
    version: int = 0
    #: occupancy start times of in-flight requests (FIFO; queued requests
    #: carry start = steer time + modeled wait): release() pairs them to
    #: measure holding time, and the cap-path queue model reads them to
    #: estimate this instance's residual work
    starts: deque = dataclasses.field(default_factory=deque)
    #: EWMA of THIS instance's observed request holding times; the cap queue
    #: model prefers it over the deployment-wide estimate (fresh instances
    #: fall back to the fleet's)
    service_ewma: float = 0.0
    #: this instance has a live entry in the deployment's expiry heap.  The
    #: arming discipline keeps the heap O(fleet): without it every
    #: idle-making release pushed a fresh entry, and with keep-alive longer
    #: than the run none ever popped — the heap grew per-request and its
    #: pushes dominated the steer/release path at high offered load.
    expiry_armed: bool = True

    @property
    def load(self) -> int:
        return self.in_flight


class Deployment:
    """One function's fleet of instances + its autoscaling state."""

    def __init__(
        self,
        name: str,
        policy: ScalingPolicy,
        placer: Optional[Callable[[int], Tuple[int, ...]]] = None,
        clock: Optional[Callable[[], float]] = None,
    ):
        self.name = name
        self.policy = policy
        self.autoscaler = make_autoscaler(policy.autoscaler)
        self.placer = placer or (lambda i: (i,))
        self.clock = ensure_clock(clock)
        #: under a VirtualClock, reading time is one attribute load off the
        #: simulator — steer/release skip the ``__call__`` frame per op
        self._vsim = self.clock.sim if type(self.clock) is VirtualClock else None
        #: arrival/concurrency/cold-start windows, maintained only when the
        #: autoscaler asks (the legacy policy keeps steer() telemetry-free)
        self.telemetry: Optional[DeploymentTelemetry] = (
            DeploymentTelemetry(self.clock)
            if self.autoscaler.needs_telemetry else None
        )
        #: total in-flight requests across the fleet (O(1) concurrency read)
        self.in_flight_total = 0
        self.instances: Dict[int, Instance] = {}
        self._ids = itertools.count()
        # (load, iid, version): ready instances with spare concurrency
        self._ready_heap: List[Tuple[int, int, int]] = []
        # (load, iid, version): live instances for the cap-path least-loaded
        # pick.  Maintained lazily: entries are only pushed while the fleet
        # sits at max_instances (the only time the heap is consulted) and the
        # heap is rebuilt from the live fleet when its entries go stale —
        # the un-capped common path pays nothing for it.
        self._all_heap: List[Tuple[int, int, int]] = []
        self._all_dirty = True            # heap missing below-cap mutations
        # (ready_at, iid): booting instances awaiting maturation
        self._warming: List[Tuple[float, int]] = []
        # (expire_at, iid, last_used): scheduled keep-alive expiries
        self._expiry: List[Tuple[float, int, float]] = []
        # fleet-wide EWMA of observed request holding time: the cap queue
        # model's fallback estimate for instances with no history of their own
        self._service_ewma = 0.0
        # coords -> live instance ids at that placement: the affinity lookup
        # behind steer(prefer=...).  Maintained on spawn/reap/kill only, so
        # the hint-free steer path pays nothing for it.
        self._coords_index: Dict[Tuple[int, ...], List[int]] = {}
        # zone name -> live instance ids: the same-zone fallback of
        # steer(prefer=<Coord with a zone>).  Only zone-carrying placers
        # (topology runs) ever populate it.
        self._zone_index: Dict[str, List[int]] = {}
        # scale-down hysteresis: virtual time the fleet first exceeded the
        # autoscaler's keep threshold (None while not in surplus)
        self._surplus_since: Optional[float] = None
        self.stats = {
            "cold_starts": 0, "scale_downs": 0, "steered": 0,
            "buffered": 0, "queued": 0, "prewarmed": 0, "affine_hits": 0,
        }
        for _ in range(policy.min_instances):
            self._spawn(cold=False)

    # -- autoscaler ----------------------------------------------------------
    def _spawn(self, cold: bool = True) -> Instance:
        iid = next(self._ids)
        now = self.clock()
        inst = Instance(
            instance_id=iid,
            coords=self.placer(iid),
            last_used=now,
            ready_at=now + (self.policy.cold_start_s if cold else 0.0),
        )
        if cold:
            self.stats["cold_starts"] += 1
            if self.telemetry is not None:
                self.telemetry.record_cold_start(now)
        self.instances[iid] = inst
        self._coords_index.setdefault(inst.coords, []).append(iid)
        zone = getattr(inst.coords, "zone", None)
        if zone is not None:
            self._zone_index.setdefault(zone, []).append(iid)
        if inst.ready_at <= now:
            heappush(self._ready_heap, (0, iid, 0))
        else:
            heappush(self._warming, (inst.ready_at, iid))
        self._all_dirty = True            # new instance unknown to the cap heap
        heappush(self._expiry, (now + self.policy.keep_alive_s, iid, now))
        return inst

    def _mature_warming(self, now: float) -> None:
        warming = self._warming
        while warming and warming[0][0] <= now:
            _, iid = heappop(warming)
            inst = self.instances.get(iid)
            if (
                inst is not None
                and inst.in_flight < self.policy.target_concurrency
            ):
                heappush(
                    self._ready_heap, (inst.in_flight, iid, inst.version)
                )

    def _reap_expired(self, now: float) -> None:
        """Keep-alive reaping from scheduled expiry times: O(expired), not
        O(fleet), per steer.  Matches the legacy full sweep exactly: reaps
        every idle instance past keep-alive, lowest instance_id first, never
        below ``min_instances``."""
        heap = self._expiry
        expired: List[Tuple[int, float, float]] = []
        seen = set()
        ka = self.policy.keep_alive_s
        while heap and heap[0][0] < now:
            exp_at, iid, lu = heappop(heap)
            inst = self.instances.get(iid)
            if iid in seen or inst is None:   # duplicate / instance gone
                continue
            if inst.in_flight != 0:
                # busy again: disarm, so the release that next idles this
                # instance re-arms it — at most one live entry per instance
                inst.expiry_armed = False
                continue
            if inst.last_used != lu:
                # idle, but re-used since this entry was armed: re-arm at the
                # true expiry of the latest idle period (same reap time the
                # per-release pushes used to provide)
                heappush(heap, (inst.last_used + ka, iid, inst.last_used))
                continue
            seen.add(iid)
            expired.append((iid, exp_at, lu))
        if not expired:
            return
        expired.sort()                        # legacy sweep order: by iid
        alive = len(self.instances)
        floor = self.policy.min_instances
        for iid, exp_at, lu in expired:
            if alive <= floor:
                # Floor binds: leave alive, but re-arm the entry one
                # keep-alive out instead of at its past expiry — re-pushing
                # exp_at < now would make every subsequent steer re-pop and
                # re-sort the floor-bound set forever.  The last_used stale
                # check still governs reaping whenever it does fire.
                heappush(heap, (now + self.policy.keep_alive_s, iid, lu))
                continue
            inst = self.instances.pop(iid)
            inst.alive = False
            inst.version += 1
            self._drop_coords(inst)
            alive -= 1
            self.stats["scale_downs"] += 1
            if self.telemetry is not None:
                # the reap window feeds the spill predictor: a producer
                # deployment whose idle instances keep getting reclaimed is
                # one whose staged objects should ride durable media
                self.telemetry.record_reap(now)

    def _keep_floor(self, want: int) -> int:
        """Fleet size scale-down may never trim below: the desired count
        padded by the policy's slack plus square-root staffing headroom
        (``scale_down_surge * sqrt(want)``).  The slack covers rate-estimate
        jitter; the sqrt term covers Poisson arrival clumping, which needs
        proportionally MORE headroom on small fleets — trimming a 7-instance
        fleet to 9 cold-starts on every clump a 20%% buffer absorbs at 40
        instances."""
        slack = getattr(self.autoscaler, "scale_down_slack", 1.0)
        surge = getattr(self.autoscaler, "scale_down_surge", 0.0)
        return max(
            self.policy.min_instances,
            math.ceil(want * slack + surge * math.sqrt(max(want, 0))),
            1,
        )

    def _maybe_retire(self, now: float, want: int) -> None:
        """Hysteresis gate in front of :meth:`_retire_surplus`: the fleet
        must exceed the keep threshold *continuously* for the policy's
        ``scale_down_delay_s`` before anything is trimmed.  Rate-estimator
        jitter at steady load crosses back over the threshold within the
        delay and resets the timer, so only a sustained surplus — a load
        level that actually fell — ever retires instances (flapping would
        turn every noise dip into cold starts on the rebound)."""
        if len(self.instances) <= self._keep_floor(want):
            self._surplus_since = None
            return
        if self._surplus_since is None:
            self._surplus_since = now
        delay = getattr(self.autoscaler, "scale_down_delay_s", 0.0)
        if now - self._surplus_since < delay:
            return
        self._retire_surplus(now, want)
        self._surplus_since = None

    def _retire_surplus(self, now: float, want: int) -> None:
        """Policy-driven prewarm decay: retire idle instances beyond the
        autoscaler's desired count (plus its slack buffer), newest first.

        The forecast half of scale-down: keep-alive reaping waits out the
        full idle window per instance, while a falling arrival trend already
        proves the surplus will never be used.  Busy instances are never
        touched (retiring them would drop in-flight requests), the
        ``min_instances`` floor always binds, and newest-first victim order
        preserves the longest-lived — warmest — part of the fleet.  Retired
        instances count as ``scale_downs`` and feed the telemetry reap
        window exactly like keep-alive reaps: a policy-trimmed producer is
        just as fatal to its instance-resident staged objects.
        """
        excess = len(self.instances) - self._keep_floor(want)
        if excess <= 0:
            return
        victims = sorted(
            (iid for iid, inst in self.instances.items()
             if inst.in_flight == 0),
            reverse=True,
        )
        tel = self.telemetry
        for iid in victims[:excess]:
            inst = self.instances.pop(iid)
            inst.alive = False
            inst.version += 1           # stale ready/warming entries skip it
            self._drop_coords(inst)
            self.stats["scale_downs"] += 1
            if tel is not None:
                tel.record_reap(now)

    # keep the legacy entry point (tests / external callers)
    def _reap_idle(self) -> None:
        now = self.clock()
        # the legacy sweep reaped at strictly-greater-than keep_alive idle;
        # expiry entries use last_used + keep_alive < now, the same predicate
        self._reap_expired(now)

    def _drop_coords(self, inst: Instance) -> None:
        ids = self._coords_index.get(inst.coords)
        if ids is not None:
            try:
                ids.remove(inst.instance_id)
            except ValueError:
                pass
            if not ids:
                del self._coords_index[inst.coords]
        zone = getattr(inst.coords, "zone", None)
        if zone is not None:
            zids = self._zone_index.get(zone)
            if zids is not None:
                try:
                    zids.remove(inst.instance_id)
                except ValueError:
                    pass
                if not zids:
                    del self._zone_index[zone]

    # -- activator -----------------------------------------------------------
    def _pop_affine(
        self, prefer: Tuple[int, ...], now: float
    ) -> Optional[Instance]:
        """Least-loaded READY instance at the preferred placement, or None.

        The co-placement fast path of ``steer(prefer=...)``: the hint names
        the producer's coords; an instance there with a spare concurrency
        slot is taken directly (its stale ready-heap entry is discarded
        later by the version check).  A cold/booting or saturated match is
        NOT waited for — "prefer when slots allow", never at the price of
        queueing behind the co-located node."""
        ids = self._coords_index.get(prefer)
        if not ids:
            # zone-affine fallback: a Coord hint carrying a zone settles for
            # any ready instance in the producer's zone when the exact node
            # has none — same-zone pulls skip every tier crossing even when
            # they miss shared memory
            zone = getattr(prefer, "zone", None)
            if zone is None:
                return None
            ids = self._zone_index.get(zone)
            if not ids:
                return None
        target = self.policy.target_concurrency
        best: Optional[Instance] = None
        for iid in ids:
            inst = self.instances.get(iid)
            if (
                inst is not None
                and inst.ready_at <= now
                and inst.in_flight < target
                and (best is None or inst.in_flight < best.in_flight)
            ):
                best = inst
        return best

    def _pop_ready(self) -> Optional[Instance]:
        heap = self._ready_heap
        instances = self.instances
        target = self.policy.target_concurrency
        while heap:
            load, iid, version = heap[0]
            inst = instances.get(iid)
            if (
                inst is None
                or inst.version != version
                or inst.in_flight >= target
            ):
                heappop(heap)                 # stale entry
                continue
            heappop(heap)
            return inst
        return None

    def _pop_least_loaded(self) -> Instance:
        if self._all_dirty:
            # below-cap mutations bypassed the heap: rebuild from the fleet
            heap = self._all_heap = [
                (i.in_flight, i.instance_id, i.version)
                for i in self.instances.values()
            ]
            heapify(heap)
            self._all_dirty = False
        heap = self._all_heap
        instances = self.instances
        while True:
            load, iid, version = heap[0]
            inst = instances.get(iid)
            if inst is None or inst.version != version:
                heappop(heap)                 # stale entry
                continue
            heappop(heap)
            return inst

    def steer(
        self, prefer: Optional[Tuple[int, ...]] = None
    ) -> Tuple[Instance, float]:
        """Pick an instance for one invocation — O(log n) in fleet size.

        Returns (instance, wait_s): wait_s > 0 models the activator buffering
        the request across a cold start and, at the ``max_instances`` cap,
        the queue delay implied by the chosen instance's residual work
        (modeled completion times of the in-flight requests ahead of it).

        ``prefer`` is a placement-affinity hint (the graph optimizer's
        co-placement pass emits the producer's coords): a ready instance at
        those coords with a spare slot wins over the least-loaded pick, so
        the consumer lands next to its data when slots allow.  Without the
        hint the legacy steering is bit-for-bit unchanged.  ``prefer``
        accepts a plain tuple or a typed
        :class:`~repro.core.topology.Coord`; a Coord carrying a zone adds
        the same-zone fallback of :meth:`_pop_affine`.
        """
        prefer = as_coord(prefer)
        vs = self._vsim
        now = self.clock() if vs is None else vs.now
        # guard the reap/mature calls with the heaps' own due checks: both
        # are no-ops otherwise, and the empty/not-yet-due case is the common
        # one on the per-invocation path
        exp = self._expiry
        if exp and exp[0][0] < now:
            self._reap_expired(now)
        warm = self._warming
        if warm and warm[0][0] <= now:
            self._mature_warming(now)
        return self._steer_one(now, prefer)

    def steer_batch(
        self, n: int, prefer: Optional[Tuple[int, ...]] = None
    ) -> List[Tuple[Instance, float]]:
        """Steer ``n`` same-instant arrivals — the batched arrival kernel.

        One clock read and one reap/mature pass amortized over the batch,
        then ``n`` per-arrival picks through the exact per-steer body (each
        pick observes the previous picks' in-flight bumps, and rate-driven
        policies still record every arrival), so the decisions are
        bit-identical to ``n`` sequential :meth:`steer` calls at one virtual
        instant — the repeated no-op reap/mature/clock work is what's saved.
        """
        prefer = as_coord(prefer)
        vs = self._vsim
        now = self.clock() if vs is None else vs.now
        exp = self._expiry
        if exp and exp[0][0] < now:
            self._reap_expired(now)
        warm = self._warming
        if warm and warm[0][0] <= now:
            self._mature_warming(now)
        steer_one = self._steer_one
        return [steer_one(now, prefer) for _ in range(n)]

    def _steer_one(
        self, now: float, prefer: Optional[Tuple[int, ...]] = None
    ) -> Tuple[Instance, float]:
        pol = self.policy
        tel = self.telemetry
        if tel is not None:
            # rate-driven policies: observe the arrival, then raise the fleet
            # to the policy's proactive floor before picking an instance
            tel.record_arrival(now, self.in_flight_total)
            want = min(
                self.autoscaler.desired_instances(self, now),
                pol.max_instances,
            )
            n_missing = want - len(self.instances)
            if n_missing > 0:
                for _ in range(n_missing):
                    self._spawn(cold=True)  # ready at once when cold_start_s=0
                self.stats["prewarmed"] += n_missing
                self._surplus_since = None
            elif n_missing < 0 and self.autoscaler.scale_down:
                self._maybe_retire(now, want)
            else:
                self._surplus_since = None
        inst = None
        if prefer is not None:
            inst = self._pop_affine(prefer, now)
            if inst is not None:
                self.stats["affine_hits"] += 1
        if inst is None:
            inst = self._pop_ready()
        if inst is not None:
            wait = 0.0
        elif (
            self.autoscaler.reactive and len(self.instances) < pol.max_instances
        ) or not self.instances:
            inst = self._spawn(cold=True)
            wait = max(0.0, inst.ready_at - now)
            self.stats["buffered"] += 1
        else:
            # cap reached: queue on the least-loaded instance.  The request
            # waits until a concurrency slot frees — modeled per instance
            # from its residual work: each in-flight request's occupancy
            # start (queue wait already folded in at its own steer) plus one
            # estimated holding time is its modeled completion; the new
            # request's slot opens at the k-th earliest of those, where k is
            # its queue position beyond the concurrency target.  Unlike the
            # old deployment-wide excess*EWMA model, elapsed service on the
            # requests ahead shortens the wait.
            inst = self._pop_least_loaded()
            wait = 0.0
            if pol.queue_wait_model:
                wait = max(0.0, inst.ready_at - now)
                # degenerate target_concurrency=0 makes every request excess;
                # clamp the position to the requests actually in flight
                k = min(inst.in_flight - pol.target_concurrency + 1,
                        len(inst.starts))
                if k > 0:
                    hold = inst.service_ewma or self._service_ewma
                    if hold > 0.0:
                        # starts is FIFO with a shared holding estimate, so
                        # the k-th earliest completion is starts[k-1] + hold
                        wait = max(wait, inst.starts[k - 1] + hold - now, 0.0)
                self.stats["queued"] += 1
        inst.in_flight += 1
        self.in_flight_total += 1
        inst.version += 1
        inst.last_used = now
        # occupancy starts once the modeled wait has elapsed: the holding
        # estimate must measure service time, not the queueing it feeds
        inst.starts.append(now + wait)
        iid = inst.instance_id
        if inst.in_flight < pol.target_concurrency and inst.ready_at <= now:
            heappush(self._ready_heap, (inst.in_flight, iid, inst.version))
        if not self._all_dirty:           # keep the cap heap live once built
            heappush(self._all_heap, (inst.in_flight, iid, inst.version))
        self.stats["steered"] += 1
        return inst, wait

    def release(self, instance_id: int) -> None:
        inst = self.instances.get(instance_id)
        if inst is None:
            return
        vs = self._vsim
        now = self.clock() if vs is None else vs.now
        if inst.starts:
            held = now - inst.starts.popleft()
            if held > 0.0:        # inline zero-time invocations carry no signal
                self._service_ewma = (
                    held if self._service_ewma == 0.0
                    else 0.8 * self._service_ewma + 0.2 * held
                )
                inst.service_ewma = (
                    held if inst.service_ewma == 0.0
                    else 0.8 * inst.service_ewma + 0.2 * held
                )
        if inst.in_flight > 0:
            inst.in_flight -= 1
            self.in_flight_total -= 1
        inst.version += 1
        inst.last_used = now
        iid = inst.instance_id
        if inst.in_flight == 0 and not inst.expiry_armed:
            inst.expiry_armed = True
            heappush(
                self._expiry, (now + self.policy.keep_alive_s, iid, now)
            )
        if (
            inst.in_flight < self.policy.target_concurrency
            and inst.ready_at <= now
        ):
            heappush(self._ready_heap, (inst.in_flight, iid, inst.version))
        if not self._all_dirty:           # keep the cap heap live once built
            heappush(self._all_heap, (inst.in_flight, iid, inst.version))

    def kill(self, instance_id: int) -> bool:
        """Fault injection: a node dies.  Outstanding XDT buffers die with it."""
        inst = self.instances.pop(instance_id, None)
        if inst is None:
            return False
        inst.alive = False
        inst.version += 1
        self._drop_coords(inst)
        self.in_flight_total -= inst.in_flight
        return True

    def instances_at(self, coords: Tuple[int, ...]) -> List[int]:
        """Live instance ids placed at ``coords`` (a node, in the default
        placement model).  Accepts tuples, lists, or typed
        :class:`~repro.core.topology.Coord` values."""
        return list(self._coords_index.get(as_coord(coords), ()))

    def kill_node(self, coords: Tuple[int, ...]) -> int:
        """Correlated eviction: every instance at ``coords`` dies at once.

        The spot-market failure mode — reclamation takes the *node*, not one
        instance — so all co-resident instances (and, at the transfer layer,
        every XDT buffer they held) go together.  Returns how many died.
        """
        killed = 0
        for iid in self.instances_at(coords):
            if self.kill(iid):
                killed += 1
        return killed

    def seed_holding_estimate(self, seconds: float) -> None:
        """Seed the holding-time EWMA for rate-driven autoscalers.

        Rate-based fleet sizing needs requests-per-instance capacity before
        the first completions exist; callers that know a function's
        intrinsic service time (``WorkflowEngine.register``) seed it here.
        Only telemetry-backed deployments accept the seed — the legacy
        concurrency policy's cap-path queue model keeps its
        learn-from-observation-only behaviour bit-for-bit.
        """
        if self.telemetry is None or seconds <= 0.0:
            return
        if self._service_ewma == 0.0:
            self._service_ewma = seconds

    @property
    def n_instances(self) -> int:
        return len(self.instances)


class ControlPlane:
    """The activator/autoscaler pair for a set of deployments."""

    def __init__(self, clock: Optional[Callable[[], float]] = None):
        self.clock = ensure_clock(clock)
        self.deployments: Dict[str, Deployment] = {}

    def register(
        self,
        name: str,
        policy: Optional[ScalingPolicy] = None,
        placer: Optional[Callable[[int], Tuple[int, ...]]] = None,
    ) -> Deployment:
        dep = Deployment(name, policy or ScalingPolicy(), placer, self.clock)
        self.deployments[name] = dep
        return dep

    def steer(
        self, name: str, prefer: Optional[Tuple[int, ...]] = None
    ) -> Tuple[Instance, float]:
        return self.deployments[name].steer(prefer)

    def release(self, name: str, instance_id: int) -> None:
        self.deployments[name].release(instance_id)

    def node_coords(self) -> List[Tuple[int, ...]]:
        """Every node (placement coords) currently hosting a live instance,
        across all deployments, in deterministic order."""
        seen = set()
        for dep in self.deployments.values():
            for inst in dep.instances.values():
                if inst.alive and inst.coords is not None:
                    seen.add(inst.coords)
        return sorted(seen)

    def kill_node(self, coords: Tuple[int, ...]) -> int:
        """Correlated eviction across every deployment sharing ``coords``.

        Spot reclamation is a *machine* event: all instances co-resident on
        the node die together regardless of which deployment owns them.
        Returns the total number of instances killed.
        """
        coords = as_coord(coords)
        killed = 0
        for dep in self.deployments.values():
            killed += dep.kill_node(coords)
        return killed
