"""XDT core: the paper's contribution as a composable JAX substrate.

Layers
------
* :mod:`refs`      — unforgeable capability tokens for ephemeral objects.
* :mod:`buffers`   — producer-side refcounted buffer registry + flow control.
* :mod:`clock`     — the injected time source (real or simulator-driven)
                     shared by scheduler, transfer accounting, and workflows.
* :mod:`transfer`  — the XDT API (invoke/put/get) over jax.Arrays; every
                     medium (xdt / inline / s3 / elasticache / hybrid) is a
                     TransferBackend strategy class over one ServiceStore.
* :mod:`patterns`  — 1-1 / scatter / gather / broadcast as mesh collectives.
* :mod:`telemetry` — shared observe-side substrate: per-deployment arrival/
                     concurrency/cold-start windows and per-medium
                     latency/cost/bytes feeds on the injected clock.
* :mod:`scheduler` — activator/autoscaler control plane (placement first,
                     data second — the XDT separation); scale-up strategies
                     are pluggable AutoscalerPolicy classes (concurrency /
                     rps / predictive).
* :mod:`workflow`  — event-driven function-DAG engine: concurrent requests,
                     overlapping fan-out/fan-in, at-most-once semantics,
                     all on the simulator's virtual clock.
* :mod:`dag`       — declarative workflow graphs (Stage/Edge/WorkflowDAG)
                     with per-edge transfer routing; lowered onto the cluster
                     simulator or compiled onto the workflow engine.
* :mod:`dagopt`    — graph optimizer over the declarative DAG: sync-chain
                     fusion, producer/consumer co-placement, predictive
                     spill to durable media; ``dag.optimize()`` returns the
                     rewritten graph plus a PlacementPlan both lowerings
                     honor.
* :mod:`faults`    — chaos harness: declarative FaultPlans (correlated
                     evictions, per-medium degradation windows, cold-start
                     storms) injected on the virtual clock, with SLOGuard
                     guardrails (bounded retries, availability/p99 budgets,
                     adaptive-beats-static dominance checks).
* :mod:`loadgen`   — closed/open-loop request drivers for throughput and
                     tail-latency sweeps under virtual time, plus the
                     trace-driven multi-tenant frontend (synthetic
                     Azure-shaped arrival traces replayed as batched
                     same-timestamp buckets with per-tenant attribution).
* :mod:`shard`     — deployment-sharded simulation: independent deployment
                     cells (connected components of the shared-media /
                     cross-call interaction graph) advanced on clock-synced
                     epoch barriers across in-process lanes or forked
                     workers, with a deterministic columnar merge.
* :mod:`topology`  — the edge-cloud continuum: node -> zone -> region
                     (-> edge-site) hierarchy behind ``compile(topology=)``;
                     tier crossings carry their own bandwidth/RTT and
                     egress fees, a single-zone topology is bit-identical
                     to the flat cluster.
* :mod:`registry`  — the shared name->class Registry behind
                     register_backend / register_pass / register_autoscaler.
* :mod:`cluster`   — calibrated discrete-event simulator for the paper's
                     latency/bandwidth/cost evaluation.
* :mod:`cost`      — AWS cost model (Table 2).
* :mod:`tracing`   — spans and counters of the engine, the transfer engine
                     and serving on the host's clock, recorded while a JAX
                     profiler session records (and written into its trace).
"""
from .buffers import BufferRegistry, RegistryStats
from .clock import Clock, MonotonicClock, VirtualClock
from .cluster import (
    DEFAULT_NET,
    NetConstants,
    ServerlessCluster,
    Simulator,
    TransferAccounting,
    effective_bandwidth_Bps,
    measure_pattern,
)
from .cost import (
    CostBreakdown,
    StorageOps,
    WorkflowCostInputs,
    combine_cost_inputs,
    cost_per_1k_requests,
    elasticache_storage_cost,
    lambda_compute_cost,
    marginal_pull_fee_usd,
    transfer_fee_usd,
    routed_cost_per_1k_requests,
    routed_workflow_cost,
    s3_storage_cost,
    tenant_bills,
    workflow_cost,
)
from .dag import (
    AdaptiveRoute,
    ClusterRunnable,
    DagBinding,
    Edge,
    FixedRoute,
    RoutePolicy,
    Runnable,
    SizeRoute,
    Stage,
    WorkflowDAG,
    execute_on_cluster,
)
from .dagopt import (
    CoPlacement,
    GraphPass,
    PlacementPlan,
    PredictiveSpill,
    SyncChainFusion,
    available_passes,
    register_pass,
)
from .errors import (
    Evicted,
    InlineTooLarge,
    InvocationReplayed,
    MediumUnavailable,
    RetriesExhausted,
    XDTError,
    XDTObjectExhausted,
    XDTProducerGone,
    XDTRefInvalid,
    XDTTimeout,
    XDTWouldBlock,
)
from .faults import (
    DegradedBackend,
    FaultEvent,
    FaultInjector,
    FaultPlan,
    SLOGuard,
    SLOReport,
    SLOViolation,
)
from .patterns import (
    all_to_all_shard,
    broadcast_shard,
    build_pattern_fn,
    gather_all_shard,
    gather_shard,
    p2p_shard,
    pattern_wire_bytes,
    scatter_shard,
)
from .loadgen import (
    LoadGenerator,
    LoadReport,
    TraceConfig,
    TraceReplayDriver,
    synthesize_trace,
)
from .refs import ObjectDescriptor, RefMinter, RefPayload, XDTRef
from .registry import Registry
from .topology import FLAT_TOPOLOGY, Coord, Topology, Zone, as_coord
from .shard import (
    Cell,
    CellResult,
    GroupSpec,
    MergedRun,
    ShardPlan,
    ShardRunner,
    merge_cell_results,
)
from .workloads import (
    DAGS,
    HYBRID_ROUTE,
    ROUTED_BACKENDS,
    TOPO_DAGS,
    TOPO_WORKLOADS,
    TOPOLOGIES,
    WORKLOADS,
    WorkloadResult,
    run_all,
    run_edge,
    run_geo,
    run_mr,
    run_set,
    run_vid,
)
from .scheduler import (
    AutoscalerPolicy,
    ConcurrencyPolicy,
    ControlPlane,
    Deployment,
    Instance,
    PredictivePolicy,
    RpsPolicy,
    ScalingPolicy,
    available_autoscalers,
    make_autoscaler,
    register_autoscaler,
)
from .telemetry import (
    DeploymentTelemetry,
    MediumTelemetry,
    TelemetryHub,
)
from .transfer import (
    ServiceStore,
    TransferBackend,
    TransferEngine,
    TransferStats,
    available_backends,
    modeled_transfer_seconds,
    register_backend,
)
from .workflow import AsyncResult, Context, WorkflowEngine, WorkflowRequest

__all__ = [k for k in dir() if not k.startswith("_")]
