"""Disaggregated prefill/decode serving with XDT cache handoff.

This is the paper's architecture transplanted to LLM serving:

* the **prefill pod** is the *producer function* — it computes the KV/state
  cache (the ephemeral object; 10s of MB to GBs) and ``put``s it into its
  buffer registry, minting a secure :class:`XDTRef`;
* the **control plane** picks the decode instance — placement first,
  independent of the payload — exactly like the activator steering an
  invocation;
* the **decode pod** is the *consumer* — its queue-proxy analogue ``get``s
  (pulls) the cache directly from the prefill pod's device memory and
  inserts it into a batch slot.

The handoff is expressed as a two-stage :class:`~repro.core.dag.WorkflowDAG`
(``prefill --cache--> decode``) compiled onto the event-driven
:class:`~repro.core.workflow.WorkflowEngine` via
``dag.compile(target="engine", handlers=...)``:
each handoff is a workflow invocation, so it *queues and autoscales* exactly
like any workflow function — the decode deployment's concurrency slots are
the engine's in-flight accounting, a handoff that finds every batch slot
busy waits on a free-slot event instead of crashing, and the decode slot is
held (a generator handler parked on a simulator Event) until the pod really
finishes the generation.  Placement still happens before any bulk data
moves; the pull itself goes through the server's own
:class:`~repro.core.transfer.TransferEngine`, so ``handoff_report()`` is
byte-identical to the pre-engine implementation.

Backends:

``xdt``     zero-copy put, direct pull (on hardware: one ICI/DCN traversal,
            prefill-sharding -> decode-sharding).
``staged``  the through-storage baseline: the cache is staged device ->
            host object store -> device (two extra copies + service fees),
            i.e. what S3/ElastiCache-based serving does today.

Both produce bit-identical generations (asserted in tests); they differ in
modeled latency/cost, reported via ``handoff_report()``.
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from ..core import tracing
from ..core.buffers import BufferRegistry
from ..core.clock import ensure_clock
from ..core.cluster import Event
from ..core.dag import Edge, Stage, WorkflowDAG
from ..core.refs import XDTRef
from ..core.scheduler import ScalingPolicy
from ..core.transfer import TransferEngine, modeled_transfer_seconds
from ..core.workflow import WorkflowEngine, WorkflowRequest
from ..models.config import ModelConfig
from .engine import Request, ServingEngine, host_read

PyTree = Any

#: nominal per-handoff cache size declared on the DAG edge (documentation /
#: routing metadata; the real cache's bytes are whatever prefill produced)
NOMINAL_CACHE_BYTES = 32 << 20


def disagg_dag(n_decode_pods: int, cache_bytes: int = NOMINAL_CACHE_BYTES) -> WorkflowDAG:
    """The prefill->decode handoff as a declarative two-stage workflow."""
    return WorkflowDAG(
        "disagg",
        stages=[
            Stage("prefill"),
            Stage("decode", fan=n_decode_pods),
        ],
        edges=[
            Edge("prefill", "decode", cache_bytes, label="cache",
                 handoff="sync", route="xdt"),
        ],
    )


def _handoff_bytes(cache: PyTree) -> Dict[str, int]:
    """A handed-over cache's bytes: ``state_bytes`` of SSM and conv states,
    which do not grow with the context, and ``kv_bytes`` of K and V."""
    return {"state_bytes": sum(cache[k].nbytes for k in ("ssm", "conv") if k in cache),
            "kv_bytes": sum(cache[k].nbytes for k in ("k", "v") if k in cache)}


class DisaggregatedServer:
    """One prefill pod + N decode pods over the XDT substrate."""

    def __init__(
        self,
        cfg: ModelConfig,
        params: PyTree,
        mesh=None,
        n_decode_pods: int = 2,
        max_batch: int = 4,
        max_len: int = 64,
        backend: str = "xdt",
        clock=None,
    ):
        self.cfg = cfg
        self.backend = backend
        self.clock = ensure_clock(clock)  # virtual under a simulator harness
        engine_backend = "xdt" if backend == "xdt" else "elasticache"
        self.transfer = TransferEngine(
            engine_backend,
            producer_coords=(0,),
            registry=BufferRegistry(max_slots=64, clock=self.clock),
            clock=self.clock,
        )
        # prefill pod: only needs the prefill fn — reuse an engine shell
        self.prefill_pod = ServingEngine(cfg, params, mesh, max_batch=1, max_len=max_len)
        self.decode_pods: List[ServingEngine] = [
            ServingEngine(cfg, params, mesh, max_batch=max_batch, max_len=max_len,
                          pod=k)
            for k in range(n_decode_pods)
        ]
        self.pod_of_request: Dict[int, int] = {}
        self.instance_of_request: Dict[int, int] = {}
        self.handoffs = 0
        #: request id -> workflow request of each handoff still in flight
        self.workflow_requests: Dict[int, WorkflowRequest] = {}
        # -- the handoff workflow: a DAG bound onto the event-driven engine.
        # Custom handlers move the REAL cache through self.transfer; the
        # engine contributes steering, queueing, autoscaling accounting, and
        # virtual-time records.  The decode deployment's fleet is exactly
        # the decode pods (min=max), each with max_batch concurrency slots.
        self.engine = WorkflowEngine(backend="xdt")
        self.dag = disagg_dag(n_decode_pods)
        self._completion: Dict[int, Event] = {}
        self._slot_free: Dict[int, Event] = {}

        def policy(stage: Stage) -> ScalingPolicy:
            if stage.name == "decode":
                return ScalingPolicy(
                    min_instances=n_decode_pods, max_instances=n_decode_pods,
                    target_concurrency=max_batch,
                )
            # the single real prefill pod; slots sized so concurrent
            # handoffs never queue on the producer side
            return ScalingPolicy(
                min_instances=1, max_instances=1,
                target_concurrency=n_decode_pods * max_batch + 1,
            )

        self.binding = self.dag.compile(
            target="engine",
            engine=self.engine,
            policy=policy,
            handlers={"prefill": self._prefill_handler,
                      "decode": self._decode_handler},
        )
        self.control = self.engine.control   # the activator/autoscaler pair
        # decode instance -> pod, assigned on first steer (id-independent:
        # survives an instance being recycled and respawned under a new id)
        self._pod_of_instance: Dict[int, int] = {}

    # ------------------------------------------------------------- handlers
    def _prefill_handler(self, ctx, req: Request):
        """Producer stage: compute the cache, mint the ref, invoke decode."""
        # 1. producer computes the ephemeral object
        attrs = {}
        if tracing.enabled():
            n = len(req.prompt)
            attrs = {"tokens": n, "padded": self.prefill_pod.padded_length(n)}
        with tracing.span("serve.prefill", **attrs):
            cache, first_token = self.prefill_pod.prefill_request(req)
        # 2. producer buffers it and mints the reference (data stays put)
        ref: XDTRef = self.transfer.put(cache, n_retrievals=1)
        # 3/4. control plane picks the consumer, which pulls and decodes
        result = yield ctx.call("disagg.decode", (req, ref, first_token))
        return result

    def _pod_for(self, instance_id: int) -> int:
        """Pod backing a decode instance: first-seen assignment to a free
        pod, evicting mappings of instances the deployment no longer has
        (so a recycled instance's pod becomes assignable again)."""
        pods = self._pod_of_instance
        pod_idx = pods.get(instance_id)
        if pod_idx is None:
            live = self.control.deployments["disagg.decode"].instances
            for dead in [iid for iid in pods if iid not in live]:
                del pods[dead]
            used = set(pods.values())
            pod_idx = next(
                k for k in range(len(self.decode_pods)) if k not in used
            )
            pods[instance_id] = pod_idx
        return pod_idx

    def _decode_handler(self, ctx, payload):
        """Consumer stage: pull the cache into a batch slot; hold the
        concurrency slot until the pod really finishes the generation."""
        req, ref, first_token = payload
        # placement happened at steer time — before the bulk pull below
        pod_idx = self._pod_for(ctx.instance.instance_id)
        pod = self.decode_pods[pod_idx]
        pulled = self.transfer.get(ref)
        wait = None
        while True:
            try:
                slot = pod.slots.index(None)
                break
            except ValueError:
                # every batch slot busy: the handoff queues on this pod
                # until step() frees one (instead of crashing, as the
                # pre-engine implementation did)
                if wait is None:
                    wait = tracing.begin("serve.slot_wait", pod=pod_idx)
                yield self._slot_free_event(pod_idx)
        if wait is not None:
            wait.end()
        attrs = _handoff_bytes(pulled) if tracing.enabled() else {}
        with tracing.span("serve.insert", pod=pod_idx, slot=slot, **attrs):
            pod.admit(req, pulled, first_token, slot)
        self.pod_of_request[req.request_id] = pod_idx
        self.instance_of_request[req.request_id] = ctx.instance.instance_id
        self.handoffs += 1
        # park until the real decode completes — the engine releases the
        # concurrency slot only then, which is what the autoscaler measures
        yield self._completion_event(req.request_id)
        return req.request_id

    def _completion_event(self, request_id: int) -> Event:
        ev = self._completion.get(request_id)
        if ev is None:
            ev = self._completion[request_id] = Event(self.engine.sim)
        return ev

    def _slot_free_event(self, pod_idx: int) -> Event:
        ev = self._slot_free.get(pod_idx)
        if ev is None or ev.fired:
            ev = self._slot_free[pod_idx] = Event(self.engine.sim)
        return ev

    # ----------------------------------------------------------------- serve
    def submit(self, prompt: np.ndarray, max_new_tokens: int = 16) -> int:
        """Prefill-side entry: one handoff workflow request.

        Drives the engine until the handoff either admitted into a decode
        slot or parked behind a full batch; the decode invocation stays
        in flight until the generation completes.
        """
        req = Request(next(self.prefill_pod._ids), np.asarray(prompt, np.int32),
                      max_new_tokens)
        with tracing.root("serve.submit", req.request_id,
                          prompt_tokens=len(req.prompt)):
            self.workflow_requests[req.request_id] = self.engine.submit(
                self.binding.entry, req
            )
            self.engine.sim.run()
            self._raise_failed()
        return req.request_id

    def _raise_failed(self) -> None:
        """A handoff whose handler raised (a prefill OOM, a compile error, a
        failed pull) ends as a failed workflow request: raise its error, once."""
        for rid, wreq in list(self.workflow_requests.items()):
            if wreq.status in ("ok", "error", "failed"):
                del self.workflow_requests[rid]
                if wreq.error is not None:
                    raise wreq.error

    def step(self) -> None:
        """One decode round: dispatch the step of every pod with a live slot,
        read all their tokens in one device-to-host read, let each pod
        collect its own, then release finished generations."""
        with tracing.span("serve.round"):
            busy = [pod for pod in self.decode_pods
                    if any(s is not None for s in pod.slots)]
            on_device = [pod.dispatch() for pod in busy]
            if busy:
                for pod, tokens in zip(busy, host_read(on_device)):
                    pod.host_tokens = tokens
                    pod.step()
            with tracing.span("serve.release"):
                self._release()

    def _release(self) -> None:
        """Fire the completion events of finished generations and run the
        engine: completed handoffs release their decode slots, and queued
        ones admit into the slots just freed."""
        fired = False
        for pod_idx, pod in enumerate(self.decode_pods):
            freed = False
            for rid in list(pod.completed):
                ev = self._completion.pop(rid, None)
                if ev is not None and not ev.fired:
                    ev.set()
                    fired = freed = True
            if freed:
                slot_ev = self._slot_free.pop(pod_idx, None)
                if slot_ev is not None:
                    slot_ev.set()
        if fired:
            self.engine.sim.run()

    def run_until_drained(self, max_steps: int = 10_000) -> Dict[int, Request]:
        done: Dict[int, Request] = {}
        steps = 0
        while steps < max_steps:
            if all(all(s is None for s in pod.slots) for pod in self.decode_pods):
                break
            self.step()
            steps += 1
        self._raise_failed()
        for pod in self.decode_pods:
            done.update(pod.completed)
        return done

    # ------------------------------------------------------------------ report
    def handoff_report(self) -> Dict[str, float]:
        """Modeled per-handoff latency + engine stats for this backend."""
        stats = self.transfer.stats
        nbytes = stats.bytes_moved / max(1, stats.transfers)
        return {
            "handoffs": float(self.handoffs),
            "avg_cache_bytes": nbytes,
            "modeled_latency_s_per_handoff": (
                stats.modeled_seconds / max(1, stats.transfers)
            ),
            "modeled_latency_s_if_s3": modeled_transfer_seconds("s3", int(nbytes)),
            "modeled_latency_s_if_elasticache": modeled_transfer_seconds(
                "elasticache", int(nbytes)
            ),
            "modeled_latency_s_if_xdt": modeled_transfer_seconds("xdt", int(nbytes)),
        }
