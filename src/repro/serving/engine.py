"""Continuous-batching serving engine.

One decode batch of ``max_batch`` slots steps in lockstep; finished/empty
slots are refilled from the request queue by running prefill and *inserting*
the resulting KV/state cache into the slot.  That insert is exactly the
ephemeral-object handoff XDT addresses — in the single-pod engine it is a
device-local dynamic-update; in :mod:`repro.serving.disagg` it crosses pods
through the XDT transfer substrate.

Greedy decoding; per-slot lengths tracked via the cache's ``pos`` vector
(decode attention masks beyond each sequence's own length, so ragged batches
are exact, not approximate).
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import tracing
from ..models import cache_shapes, make_decode_fn, make_prefill_fn
from ..models.config import ModelConfig

PyTree = Any


@dataclasses.dataclass
class Request:
    request_id: int
    prompt: np.ndarray                 # (S,) int32
    max_new_tokens: int = 16
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


def empty_cache(cfg: ModelConfig, batch: int, max_len: int) -> PyTree:
    out = {}
    for key, (shape, _axes, dtype) in cache_shapes(cfg, batch, max_len).items():
        out[key] = jnp.zeros(shape, dtype)
    return out


def host_read(x):
    """``jax.device_get(x)``: an array, or a list of arrays, read to the host
    in one device-to-host read.  Every read of the engine goes through here,
    so that tracing counts it (``host.syncs``) and times it (``host.sync``)."""
    if tracing.enabled():
        tracing.count("host.syncs")
        with tracing.span("host.sync"):
            return jax.device_get(x)
    return jax.device_get(x)


def insert_cache(batch_cache: PyTree, single_cache: PyTree, slot: int) -> PyTree:
    """Insert a prefill cache (batch=1) into decode slot ``slot``.

    Every cache leaf has the batch axis at position 1 (leaf layout
    (L, B, ...)) except ``pos`` (B,).
    """
    def ins(dst, src):
        if dst.ndim == 1:  # pos
            return dst.at[slot].set(src[0].astype(dst.dtype))
        return dst.at[:, slot].set(src[:, 0].astype(dst.dtype))

    return jax.tree.map(ins, batch_cache, single_cache)


@functools.lru_cache(maxsize=None)
def _step_fns(cfg: ModelConfig, mesh, max_len: int):
    """(jitted prefill, decode) shared by every engine with the same config,
    mesh and context budget: the pods of one server compile each shape once.
    The decode step also takes each slot's greedy token, so that one
    dispatch steps a pod: ``decode(params, cache, tokens (B, 1)) -> (next
    tokens (B, 1) int32, cache)``.  An engine runs it through
    :func:`_donating`."""
    step = make_decode_fn(cfg, mesh)

    def decode(params, cache, tokens):
        logits, cache = step(params, cache, tokens)
        return jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32), cache

    return jax.jit(make_prefill_fn(cfg, mesh, remat="none", pad_to=max_len)), decode


@functools.lru_cache(maxsize=None)
def _donating(decode):
    """``decode`` jitted with its cache donated: the step writes one row per
    slot into the buffers it was given, and the caller's cache is deleted.
    The engine rebinds its cache to the result at once and nothing else
    holds it."""
    return jax.jit(decode, donate_argnums=(1,))


class ServingEngine:
    """Single-pod continuous batching."""

    def __init__(
        self,
        cfg: ModelConfig,
        params: PyTree,
        mesh=None,
        max_batch: int = 4,
        max_len: int = 64,
        pod: int = 0,
    ):
        assert cfg.has_decode, f"{cfg.name} is encoder-only"
        self.cfg = cfg
        self.params = params
        self.mesh = mesh
        self.max_batch = max_batch
        self.max_len = max_len
        #: this pod's index among its server's, in traces
        self.pod = pod
        #: prompts are padded to a whole number of these tokens: the SSD chunk
        #: of a model with Mamba2 layers, so that prefill compiles one program
        #: per block count and never runs a partial chunk; 0 (every other
        #: model): each prompt is served at its own length
        self.prompt_block = cfg.ssm.chunk if cfg.ssm is not None and cfg.ssm.version == 2 else 0
        self.prefill, decode = _step_fns(cfg, mesh, max_len)
        self.decode = _donating(decode)
        self.cache = empty_cache(cfg, max_batch, max_len)
        self.slots: List[Optional[Request]] = [None] * max_batch
        self.last_tokens = jnp.zeros((max_batch, 1), jnp.int32)
        #: the dispatched step's tokens, read to the host with other pods'
        #: by a server, until :meth:`step` collects them
        self.host_tokens: Optional[np.ndarray] = None
        self.queue: List[Request] = []
        self._ids = itertools.count()
        self.completed: Dict[int, Request] = {}
        self.steps = 0

    # -- API ----------------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new_tokens: int = 16) -> int:
        req = Request(next(self._ids), np.asarray(prompt, np.int32), max_new_tokens)
        self.queue.append(req)
        return req.request_id

    def padded_length(self, n: int) -> int:
        """The length a prompt of ``n`` tokens is prefilled at."""
        if not self.prompt_block:
            return n
        return min(-(-n // self.prompt_block) * self.prompt_block, self.max_len)

    def prefill_request(self, req: Request) -> Tuple[PyTree, int]:
        """Run prefill for one request; returns (cache, first_token)."""
        n, padded = len(req.prompt), self.padded_length(len(req.prompt))
        if tracing.enabled():
            tracing.count("prefill.tokens", n)
            tracing.count("prefill.pad_tokens", padded - n)
        if self.prompt_block:
            tokens = np.zeros((1, padded), np.int32)
            tokens[0, :n] = req.prompt
            batch = {"tokens": jnp.asarray(tokens), "length": jnp.asarray([n], jnp.int32)}
        else:
            batch = {"tokens": jnp.asarray(req.prompt)[None]}
        logits, cache = self.prefill(self.params, batch)
        return cache, int(host_read(jnp.argmax(logits[0])))

    def admit(self, req: Request, cache: PyTree, first_token: int, slot: int) -> None:
        self.cache = insert_cache(self.cache, cache, slot)
        self.last_tokens = self.last_tokens.at[slot, 0].set(first_token)
        req.generated.append(first_token)
        self.slots[slot] = req

    def _refill(self) -> None:
        for slot in range(self.max_batch):
            if self.slots[slot] is None and self.queue:
                req = self.queue.pop(0)
                cache, tok = self.prefill_request(req)
                self.admit(req, cache, tok, slot)

    def dispatch(self) -> Optional[jax.Array]:
        """Refill free slots and dispatch one decode step.  Returns the next
        token of every slot, (max_batch, 1), still on the device, or None
        when no slot is live."""
        self._refill()
        if all(s is None for s in self.slots):
            return None
        if tracing.enabled():
            live = sum(s is not None for s in self.slots)
            with tracing.span("serve.decode", pod=self.pod, live=live):
                self._decode()
        else:
            self._decode()
        self.steps += 1
        return self.last_tokens

    def _decode(self) -> None:
        self.last_tokens, self.cache = self.decode(self.params, self.cache,
                                                   self.last_tokens)

    def collect(self, tokens: np.ndarray) -> None:
        """Append each live slot's token of the dispatched step (``tokens``,
        read to the host) and free the slots of finished requests."""
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            req.generated.append(int(tokens[slot, 0]))
            if (
                len(req.generated) >= req.max_new_tokens
                or len(req.prompt) + len(req.generated) >= self.max_len - 1
            ):
                req.done = True
                self.completed[req.request_id] = req
                self.slots[slot] = None

    def step(self) -> None:
        """One engine iteration: refill free slots, one decode step, one read
        of every slot's token, and their :meth:`collect`.  A server that
        reads the tokens of several pods at once dispatches each pod's step,
        reads them all, and leaves each pod its own in ``host_tokens``; the
        step then only collects them."""
        tokens, self.host_tokens = self.host_tokens, None
        if tokens is None:
            on_device = self.dispatch()
            if on_device is None:
                return
            tokens = host_read(on_device)
        self.collect(tokens)

    def run_until_drained(self, max_steps: int = 10_000) -> Dict[int, Request]:
        while (self.queue or any(s is not None for s in self.slots)) and self.steps < max_steps:
            self.step()
        return self.completed
