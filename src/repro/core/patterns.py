"""The paper's communication patterns as TPU-native mesh collectives.

Paper §4.2.1/§7.1 defines four inter-function patterns: producer-consumer
(1-1), scatter (map), gather (reduce), and broadcast.  On a TPU mesh the XDT
principle — *the consumer pulls exactly its bytes directly from the producer
after placement is decided* — maps onto point-to-point ``collective-permute``
(``lax.ppermute``) and, for the regular fused scatter+gather (MoE dispatch),
onto ``lax.all_to_all``.  The anti-pattern XDT replaces (staging through an
intermediary) corresponds to bouncing via host / replicating via all-gather
when only one consumer needs the bytes.

All ``*_shard`` functions are *per-shard* programs: call them inside
``jax.shard_map``.  ``build_pattern_fn`` wraps one into a jitted host-level
callable for tests and benchmarks; see each pattern for its global layout
convention.

Traffic accounting (used by the roofline): with object size ``s`` and fan
``n`` on one axis —

==============  =========================  ===========================
pattern         XDT-native lowering        bytes on the wire
==============  =========================  ===========================
1-1 / p2p       1 collective-permute       s
scatter         n-1 collective-permutes    s*(n-1)/n (one slice each)
gather-to-one   n-1 collective-permutes    (n-1)*s  (focused on dst)
gather-to-all   ring all-gather            (n-1)*s  per link
broadcast       masked psum (all-reduce)   ~2s      (ring all-reduce)
moe dispatch    all-to-all                 s*(n-1)/n per link
==============  =========================  ===========================
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map


# --------------------------------------------------------------------------
# Per-shard collective programs (call inside shard_map)
# --------------------------------------------------------------------------


def p2p_shard(x: jax.Array, axis: str, src: int, dst: int) -> jax.Array:
    """1-1: move ``x`` from rank ``src`` to rank ``dst`` along ``axis``.

    Every rank participates (SPMD); ranks not addressed by the permute
    receive zeros (``ppermute`` semantics).  Lowers to a single
    collective-permute: the direct producer->consumer pull.
    """
    if src == dst:
        return x
    return lax.ppermute(x, axis, [(src, dst)])


def scatter_shard(x_stacked: jax.Array, axis: str, src: int, n: int) -> jax.Array:
    """Scatter: rank ``src`` holds rows ``(n, ...)``; rank j receives row j.

    Lowered as n-1 point-to-point permutes (total wire bytes = (n-1)/n of the
    object, each slice moving once, directly) rather than a masked
    all-to-all (which would move n x the bytes).  This is the XDT scatter:
    each consumer pulls only its slice.  Non-``src`` ranks' input blocks are
    ignored (pass zeros).
    """
    idx = lax.axis_index(axis)
    out = x_stacked[src]  # rank ``src`` keeps its own row, no wire transfer
    for j in range(n):
        if j == src:
            continue
        piece = lax.ppermute(x_stacked[j], axis, [(src, j)])
        out = jnp.where(idx == j, piece, out)
    return out


def gather_shard(x: jax.Array, axis: str, dst: int, n: int) -> jax.Array:
    """Gather-to-one: rank ``dst`` receives the stack of every rank's shard.

    n-1 point-to-point permutes focused on ``dst`` — XDT's gather, where the
    single consumer pulls each producer's buffer.  Ranks other than ``dst``
    hold zeros in the foreign rows (only the consumer's copy is meaningful).
    """
    rows = []
    idx = lax.axis_index(axis)
    for j in range(n):
        recv = x if j == dst else lax.ppermute(x, axis, [(j, dst)])
        # row j is x's own shard only at rank dst position j == dst
        rows.append(jnp.where(idx == dst, recv, jnp.where(j == idx, x, jnp.zeros_like(x))))
    return jnp.stack(rows, axis=0)


def gather_all_shard(x: jax.Array, axis: str) -> jax.Array:
    """Gather-to-all: ring all-gather (when every rank consumes the result)."""
    return lax.all_gather(x, axis)


def broadcast_shard(x: jax.Array, axis: str, src: int) -> jax.Array:
    """Broadcast: rank ``src``'s object delivered to every rank.

    Masked psum lowers to one all-reduce, which XLA schedules as a
    bandwidth-optimal ring on ICI.
    """
    idx = lax.axis_index(axis)
    return lax.psum(jnp.where(idx == src, x, jnp.zeros_like(x)), axis)


def all_to_all_shard(x: jax.Array, axis: str) -> jax.Array:
    """All-to-all: the fused scatter+gather pattern used by MoE routing.

    Per-shard ``x`` has leading dim == axis size; row j goes to rank j.
    """
    return lax.all_to_all(x, axis, split_axis=0, concat_axis=0, tiled=False)


# --------------------------------------------------------------------------
# Host-level wrappers
# --------------------------------------------------------------------------
#
# Global-layout conventions (n = mesh.shape[axis], C = payload shape):
#   1-1        in (n, *C) sharded P(axis): row r is rank r's buffer.
#              out (n, *C): row dst == in row src, others zero.
#   scatter    in (n, n, *C) sharded P(axis): block src holds the stacked
#              object; other blocks ignored.  out (n, *C): row j == slice j.
#   gather     in (n, *C) sharded P(axis).  out (n, n, *C): block dst holds
#              the full stack.
#   gather_all in (n, *C) sharded P(axis).  out (n, n, *C): every block holds
#              the full stack.
#   broadcast  in (n, *C) sharded P(axis).  out (n, *C): every row == row src.
#   all_to_all in (n*n, *C) sharded P(axis): rank r's block row j is r's
#              message to j.  out: rank r's block row j is j's message to r.


def build_pattern_fn(
    mesh: Mesh,
    axis: str,
    pattern: str,
    *,
    src: int = 0,
    dst: int = 0,
) -> Callable[[jax.Array], jax.Array]:
    """Build a jitted shard_map callable running one pattern along ``axis``."""
    n = mesh.shape[axis]
    spec1 = P(axis)

    if pattern == "1-1":
        def fn(x):  # x: (1, *C)
            return p2p_shard(x[0], axis, src, dst)[None]
    elif pattern == "scatter":
        def fn(x):  # x: (1, n, *C)
            return scatter_shard(x[0], axis, src, n)[None]
    elif pattern == "gather":
        def fn(x):  # x: (1, *C)
            return gather_shard(x[0], axis, dst, n)[None]
    elif pattern == "gather_all":
        def fn(x):  # x: (1, *C)
            return gather_all_shard(x[0], axis)[None]
    elif pattern == "broadcast":
        def fn(x):  # x: (1, *C)
            return broadcast_shard(x[0], axis, src)[None]
    elif pattern == "all_to_all":
        def fn(x):  # x: (n, *C) — the per-rank message stack
            return all_to_all_shard(x, axis)
    else:
        raise ValueError(pattern)

    mapped = shard_map(fn, mesh=mesh, in_specs=spec1, out_specs=spec1)
    return jax.jit(mapped)


def pattern_wire_bytes(pattern: str, nbytes: int, fan: int) -> float:
    """Analytic wire-traffic model (per the table in the module docstring)."""
    if pattern == "1-1":
        return float(nbytes)
    if pattern == "scatter":
        return nbytes * (fan - 1) / max(1, fan)
    if pattern in ("gather", "gather_all"):
        return float((fan - 1) * nbytes)
    if pattern == "broadcast":
        return 2.0 * nbytes * (fan - 1) / max(1, fan)
    if pattern == "all_to_all":
        return nbytes * (fan - 1) / max(1, fan)
    raise ValueError(pattern)
