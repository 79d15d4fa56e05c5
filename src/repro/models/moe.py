"""Mixture-of-Experts layer with XDT-patterned expert-parallel dispatch.

The MoE dispatch/combine IS the paper's scatter/gather pattern (§7.1): tokens
are scattered to expert owners chosen *after* routing (placement first, data
second), and expert outputs are gathered back.  Two dispatch modes:

``replicated_ep`` (baseline)
    Activations are replicated across the model axis (Megatron-style TP);
    each model rank owns ``E / tp`` experts and processes only the tokens
    routed to *its* experts (capacity-bounded sort-free bucketing); the
    combine folds into a single ``psum`` — the same collective the dense MLP
    TP already pays, so MoE adds **zero** extra collectives.  This mirrors
    XDT's insight: the consumer (expert shard) pulls exactly its tokens from
    the producer-resident buffer instead of pushing everything through a
    central exchange.

``dense`` (oracle)
    Every expert computed for every token, combined by routing weight.  Used
    as the numerics reference in tests (with generous capacity the EP path
    must match it exactly).

Routing: top-k over a linear router, softmax over the selected logits,
switch-style load-balancing auxiliary loss.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from .config import ModelConfig, MoEConfig


def router_topk(x_flat: jax.Array, w_router: jax.Array, k: int):
    """x_flat: (T, D) -> (weights (T,k), ids (T,k), aux_loss scalar)."""
    logits = jnp.einsum("td,de->te", x_flat.astype(jnp.float32), w_router.astype(jnp.float32))
    top_logits, top_ids = lax.top_k(logits, k)
    weights = jax.nn.softmax(top_logits, axis=-1)
    # switch-transformer load-balance loss: E * sum(frac_tokens * frac_prob)
    E = w_router.shape[-1]
    probs = jax.nn.softmax(logits, axis=-1)
    frac_prob = probs.mean(axis=0)
    onehot = jax.nn.one_hot(top_ids[:, 0], E)
    frac_tok = onehot.mean(axis=0)
    aux = E * jnp.sum(frac_prob * frac_tok)
    return weights, top_ids, aux


def _expert_ffn(xs: jax.Array, wi: jax.Array, wg: jax.Array, wo: jax.Array) -> jax.Array:
    """xs: (E, C, D) -> (E, C, D) per-expert SwiGLU."""
    h = jnp.einsum("ecd,edf->ecf", xs, wi) * jax.nn.silu(
        jnp.einsum("ecd,edf->ecf", xs, wg)
    )
    return jnp.einsum("ecf,efd->ecd", h, wo)


def moe_dense_oracle(x: jax.Array, p: Dict[str, jax.Array], moe: MoEConfig):
    """Reference: all experts for all tokens (tests only)."""
    B, S, D = x.shape
    xf = x.reshape(B * S, D)
    weights, ids, aux = router_topk(xf, p["router"], moe.top_k)
    # ys: (E, T, D)
    h = jnp.einsum("td,edf->etf", xf, p["wi"]) * jax.nn.silu(
        jnp.einsum("td,edf->etf", xf, p["wg"])
    )
    ys = jnp.einsum("etf,efd->etd", h, p["wo"])
    comb = jnp.zeros_like(xf)
    for j in range(moe.top_k):
        onehot = jax.nn.one_hot(ids[:, j], p["router"].shape[-1], dtype=x.dtype)  # (T,E)
        pick = jnp.einsum("te,etd->td", onehot, ys)
        comb = comb + weights[:, j, None].astype(x.dtype) * pick
    return comb.reshape(B, S, D), aux


def _local_dispatch_ffn(
    x_flat: jax.Array,        # (T, D) tokens (replicated over model axis)
    weights: jax.Array,       # (T, k)
    ids: jax.Array,           # (T, k)
    wi: jax.Array,            # (E_loc, D, F)
    wg: jax.Array,
    wo: jax.Array,
    *,
    n_experts: int,
    capacity: int,
    rank: jax.Array,          # scalar: this shard's index on the model axis
):
    """Capacity-bounded bucketing of this rank's tokens + expert FFN.

    Token slots routed to other ranks' experts are dropped locally (they are
    served there); slots beyond capacity are dropped everywhere (standard
    switch capacity semantics).
    """
    T, k = ids.shape
    E_loc = wi.shape[0]
    flat_eid = ids.reshape(-1)                       # (T*k,)
    flat_tid = jnp.arange(T * k) // k
    flat_w = weights.reshape(-1)
    lo = rank * E_loc
    local_eid = flat_eid - lo
    is_local = (local_eid >= 0) & (local_eid < E_loc)

    # stable bucket sort by local expert id; non-local slots pushed past end
    sort_key = jnp.where(is_local, local_eid, E_loc)
    order = jnp.argsort(sort_key, stable=True)
    s_eid = sort_key[order]
    s_tid = flat_tid[order]
    s_w = flat_w[order]
    starts = jnp.searchsorted(s_eid, jnp.arange(E_loc))
    pos = jnp.arange(T * k) - starts[jnp.clip(s_eid, 0, E_loc - 1)]
    keep = (s_eid < E_loc) & (pos < capacity)

    # scatter token indices/weights into (E_loc, capacity) buffers;
    # OOB rows (dropped slots) vanish with mode="drop".
    e_idx = jnp.where(keep, s_eid, E_loc)
    p_idx = jnp.where(keep, pos, 0)
    tok_buf = jnp.zeros((E_loc, capacity), jnp.int32).at[e_idx, p_idx].set(
        s_tid.astype(jnp.int32), mode="drop"
    )
    w_buf = jnp.zeros((E_loc, capacity), x_flat.dtype).at[e_idx, p_idx].set(
        s_w.astype(x_flat.dtype), mode="drop"
    )

    xs = x_flat[tok_buf]                              # (E_loc, C, D) gather
    ys = _expert_ffn(xs, wi, wg, wo) * w_buf[..., None]
    out = jnp.zeros_like(x_flat).at[tok_buf.reshape(-1)].add(
        ys.reshape(-1, x_flat.shape[-1])
    )
    return out


def moe_layer(
    x: jax.Array,              # (B, S, D)
    p: Dict[str, jax.Array],   # router (D,E); wi/wg (E,D,F); wo (E,F,D)
    cfg: ModelConfig,
    mesh: Optional[Mesh],
) -> Tuple[jax.Array, jax.Array]:
    """Returns (out (B,S,D), aux_loss)."""
    moe = cfg.moe
    assert moe is not None
    if moe.dispatch == "dense" or mesh is None or int(mesh.shape.get("model", 1)) == 1:
        if moe.dispatch in ("replicated_ep", "a2a") and (
            mesh is None or int(mesh.shape.get("model", 1)) == 1
        ):
            # single-shard EP degenerates to rank 0 owning all experts
            return _moe_ep_single(x, p, cfg)
        return moe_dense_oracle(x, p, moe)
    if moe.dispatch == "a2a":
        return _moe_ep_a2a(x, p, cfg, mesh)
    return _moe_ep_sharded(x, p, cfg, mesh)


def _capacity(T: int, moe: MoEConfig) -> int:
    c = int(T * moe.top_k / moe.n_experts * moe.capacity_factor) + 1
    return max(moe.top_k, min(c, T * moe.top_k))


def _moe_ep_single(x, p, cfg):
    B, S, D = x.shape
    moe = cfg.moe
    xf = x.reshape(B * S, D)
    weights, ids, aux = router_topk(xf, p["router"], moe.top_k)
    out = _local_dispatch_ffn(
        xf, weights, ids, p["wi"], p["wg"], p["wo"],
        n_experts=moe.n_experts,
        capacity=_capacity(B * S, moe),
        rank=jnp.int32(0),
    )
    return out.reshape(B, S, D), aux


def _moe_ep_sharded(x, p, cfg, mesh: Mesh):
    B, S, D = x.shape
    moe = cfg.moe
    axes = tuple(mesh.shape.keys())
    batch_axes = tuple(a for a in axes if a in ("pod", "data"))
    bspec = batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None)
    n_batch = 1
    for a in batch_axes:
        n_batch *= int(mesh.shape[a])
    T_loc = (B // max(1, n_batch)) * S
    cap = _capacity(T_loc, moe)

    def local(xb, router, wi, wg, wo):
        # xb: (B_loc, S, D) replicated over model; wi/wg/wo: (E_loc, D, F)
        rank = lax.axis_index("model")
        Bl = xb.shape[0]
        xf = xb.reshape(Bl * S, D)
        weights, ids, aux = router_topk(xf, router, moe.top_k)
        out = _local_dispatch_ffn(
            xf, weights, ids, wi, wg, wo,
            n_experts=moe.n_experts, capacity=cap, rank=rank,
        )
        out = lax.psum(out, "model")  # combine expert contributions (gather)
        aux = lax.pmean(aux, axes)    # replicated scalar across the mesh
        return out.reshape(Bl, S, D), aux

    xspec = P(bspec, None, None)
    wspec = P("model", None, None)
    out, aux = shard_map(
        local,
        mesh=mesh,
        in_specs=(xspec, P(None, None), wspec, wspec, wspec),
        out_specs=(xspec, P()),
        check_vma=False,
    )(x, p["router"], p["wi"], p["wg"], p["wo"])
    return out, aux


def _moe_ep_a2a(x, p, cfg, mesh: Mesh):
    """XDT-patterned expert parallelism: tokens move, activations don't.

    The ``replicated_ep`` baseline replicates every token's activations over
    the model axis and pays a full (T_loc, D) psum per layer — the "push
    everything through a central exchange" anti-pattern.  Here each model
    rank owns T_loc/tp tokens (sequence split); after routing, each token is
    SCATTERED (all_to_all) to the rank that owns its expert, processed
    there, and GATHERED back by a second all_to_all — exactly the paper's
    scatter/gather pattern: placement (routing) first, then each consumer
    pulls only its bytes.  Wire bytes per layer drop from 2 * T_loc * D
    (all-reduce) to 2 * k * (T_loc/tp) * D * (tp-1)/tp per rank.
    """
    B, S, D = x.shape
    moe = cfg.moe
    tp = int(mesh.shape["model"])
    axes = tuple(mesh.shape.keys())
    batch_axes = tuple(a for a in axes if a in ("pod", "data"))
    bspec = batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None)
    n_batch = 1
    for a in batch_axes:
        n_batch *= int(mesh.shape[a])
    E_loc = moe.n_experts // tp
    T_own = (B // max(1, n_batch)) * (S // tp)          # tokens per model rank
    # per-destination-rank send capacity (same both directions)
    cap = max(
        moe.top_k,
        int(T_own * moe.top_k / tp * moe.capacity_factor) + 1,
    )

    def local(xb, router, wi, wg, wo):
        # xb: (B_loc, S/tp, D) — this rank's own token slice
        Bl, Sl, _ = xb.shape
        xf = xb.reshape(Bl * Sl, D)
        weights, ids, aux = router_topk(xf, router, moe.top_k)
        T, k = ids.shape

        # ---- scatter: bucket (token, k) slots by destination rank --------
        flat_eid = ids.reshape(-1)                       # (T*k,)
        flat_tid = jnp.arange(T * k) // k
        flat_w = weights.reshape(-1).astype(xf.dtype)
        dest = flat_eid // E_loc                         # destination rank
        order = jnp.argsort(dest, stable=True)
        s_dest, s_tid = dest[order], flat_tid[order]
        s_eid, s_w = flat_eid[order], flat_w[order]
        starts = jnp.searchsorted(s_dest, jnp.arange(tp))
        pos = jnp.arange(T * k) - starts[s_dest]
        keep = pos < cap                                 # capacity drop

        d_idx = jnp.where(keep, s_dest, tp)
        p_idx = jnp.where(keep, pos, 0)
        send_tok = jnp.zeros((tp, cap, D), xf.dtype).at[d_idx, p_idx].set(
            xf[s_tid], mode="drop")
        send_eid = jnp.full((tp, cap), -1, jnp.int32).at[d_idx, p_idx].set(
            (s_eid % E_loc).astype(jnp.int32), mode="drop")
        send_tid = jnp.zeros((tp, cap), jnp.int32).at[d_idx, p_idx].set(
            s_tid.astype(jnp.int32), mode="drop")
        send_w = jnp.zeros((tp, cap), xf.dtype).at[d_idx, p_idx].set(
            s_w, mode="drop")

        # ---- all_to_all #1: tokens travel to their expert's owner --------
        recv_tok = lax.all_to_all(send_tok, "model", 0, 0, tiled=False)
        recv_eid = lax.all_to_all(send_eid, "model", 0, 0, tiled=False)

        # ---- expert FFN on received tokens (one-hot per local expert) ----
        rt = recv_tok.reshape(tp * cap, D)
        re = recv_eid.reshape(tp * cap)
        onehot = (re[:, None] == jnp.arange(E_loc)[None, :])  # (tp*cap, E_loc)
        h = jnp.einsum("td,edf->etf", rt, wi) * jax.nn.silu(
            jnp.einsum("td,edf->etf", rt, wg))
        ys = jnp.einsum("etf,efd->etd", h, wo)               # (E_loc, tp*cap, D)
        out_tok = jnp.einsum("te,etd->td", onehot.astype(rt.dtype), ys)
        out_tok = out_tok.reshape(tp, cap, D)

        # ---- all_to_all #2: results travel home ---------------------------
        back = lax.all_to_all(out_tok, "model", 0, 0, tiled=False)

        # ---- combine: weighted scatter-add into this rank's tokens --------
        valid = send_eid.reshape(-1) >= 0
        contrib = back.reshape(tp * cap, D) * send_w.reshape(-1)[:, None]
        tid = jnp.where(valid, send_tid.reshape(-1), T)      # OOB -> dropped
        out = jnp.zeros_like(xf).at[tid].add(contrib, mode="drop")
        aux = lax.pmean(aux, axes)
        return out.reshape(Bl, Sl, D), aux

    xspec = P(bspec, "model", None)
    wspec = P("model", None, None)
    out, aux = shard_map(
        local,
        mesh=mesh,
        in_specs=(xspec, P(None, None), wspec, wspec, wspec),
        out_specs=(xspec, P()),
        check_vma=False,
    )(x, p["router"], p["wi"], p["wg"], p["wo"])
    return out, aux


def moe_param_shapes(cfg: ModelConfig):
    moe = cfg.moe
    D, E, F = cfg.d_model, moe.n_experts, moe.d_ff_expert
    return {
        "router": ((D, E), ("embed", None)),
        "wi": ((E, D, F), ("experts", "embed", "expert_ff")),
        "wg": ((E, D, F), ("experts", "embed", "expert_ff")),
        "wo": ((E, F, D), ("experts", "expert_ff", "embed")),
    }
