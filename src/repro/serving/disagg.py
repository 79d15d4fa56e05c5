"""Disaggregated prefill/decode serving with XDT cache handoff.

This is the paper's architecture transplanted to LLM serving:

* the **prefill pod** is the *producer function* — it computes the KV/state
  cache (the ephemeral object; 10s of MB to GBs) and ``put``s it into its
  buffer registry, minting a secure :class:`XDTRef`;
* the server picks the decode pod — placement first, independent of the
  payload: the pod with the fewest live and parked requests, ties to the
  lowest index;
* the **decode pod** is the *consumer* — it ``get``s (pulls) the cache
  directly from the prefill pod's device memory and inserts it into a free
  batch slot.

A handoff whose pod has no free slot keeps the pulled cache and parks on
that pod, in arrival order; :meth:`DisaggregatedServer.step` admits it into
the first slot that pod frees, in the round that frees it.

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

from collections import deque
from typing import Any, Deque, Dict, List, Tuple

import numpy as np

from ..core import tracing
from ..core.buffers import BufferRegistry
from ..core.transfer import TransferEngine, modeled_transfer_seconds
from ..models.config import ModelConfig
from .engine import Request, ServingEngine, host_read

PyTree = Any


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
    ):
        self.cfg = cfg
        self.backend = backend
        self.transfer = TransferEngine(
            "xdt" if backend == "xdt" else "elasticache",
            producer_coords=(0,),
            registry=BufferRegistry(max_slots=64),
        )
        # prefill pod: only needs the prefill fn — reuse an engine shell
        self.prefill_pod = ServingEngine(cfg, params, mesh, max_batch=1, max_len=max_len)
        self.decode_pods: List[ServingEngine] = [
            ServingEngine(cfg, params, mesh, max_batch=max_batch, max_len=max_len,
                          pod=k)
            for k in range(n_decode_pods)
        ]
        #: per decode pod, the handoffs parked behind its full batch, in
        #: arrival order: (request, pulled cache, first token, slot wait)
        self._parked: List[Deque[Tuple[Request, PyTree, int, Any]]] = [
            deque() for _ in range(n_decode_pods)]
        self.pod_of_request: Dict[int, int] = {}
        self.handoffs = 0

    # ----------------------------------------------------------------- serve
    def submit(self, prompt: np.ndarray, max_new_tokens: int = 16) -> int:
        """Prefill a request and hand its cache to a decode pod: admitted
        into a free slot, or parked on the pod until one frees."""
        req = Request(next(self.prefill_pod._ids), np.asarray(prompt, np.int32),
                      max_new_tokens)
        with tracing.root("serve.submit", req.request_id,
                          prompt_tokens=len(req.prompt)):
            attrs = {}
            if tracing.enabled():
                n = len(req.prompt)
                attrs = {"tokens": n, "padded": self.prefill_pod.padded_length(n)}
            with tracing.span("serve.prefill", **attrs):
                cache, first_token = self.prefill_pod.prefill_request(req)
            # the producer buffers the cache and mints the reference
            ref = self.transfer.put(cache, n_retrievals=1)
            # placement before the bulk pull
            pod_idx = min(range(len(self.decode_pods)), key=self._load)
            pulled = self.transfer.get(ref)
            if None in self.decode_pods[pod_idx].slots:
                self._admit(pod_idx, req, pulled, first_token)
            else:
                wait = tracing.begin("serve.slot_wait", pod=pod_idx)
                self._parked[pod_idx].append((req, pulled, first_token, wait))
        return req.request_id

    def _load(self, pod_idx: int) -> int:
        """A decode pod's live slots plus the handoffs parked on it."""
        live = sum(s is not None for s in self.decode_pods[pod_idx].slots)
        return live + len(self._parked[pod_idx])

    def _admit(self, pod_idx: int, req: Request, pulled: PyTree,
               first_token: int) -> None:
        pod = self.decode_pods[pod_idx]
        slot = pod.slots.index(None)
        attrs = _handoff_bytes(pulled) if tracing.enabled() else {}
        with tracing.span("serve.insert", request=req.request_id, pod=pod_idx,
                          slot=slot, **attrs):
            pod.admit(req, pulled, first_token, slot)
        self.pod_of_request[req.request_id] = pod_idx
        self.handoffs += 1

    def step(self) -> None:
        """One decode round: dispatch the step of every pod with a live slot,
        read all their tokens in one device-to-host read, let each pod
        collect its own, then admit parked handoffs into the freed slots."""
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
        """Admit each pod's parked handoffs, in arrival order, into the
        slots that pod's round freed."""
        for pod_idx, parked in enumerate(self._parked):
            slots = self.decode_pods[pod_idx].slots
            while parked and None in slots:
                req, pulled, first_token, wait = parked.popleft()
                wait.end()
                self._admit(pod_idx, req, pulled, first_token)

    def run_until_drained(self, max_steps: int = 10_000) -> Dict[int, Request]:
        done: Dict[int, Request] = {}
        steps = 0
        while steps < max_steps:
            if all(all(s is None for s in pod.slots) for pod in self.decode_pods):
                break
            self.step()
            steps += 1
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
