"""Transformer layer library: RMSNorm, RoPE, GQA attention (3 sharding
modes; RoPE or none, ``cfg.rope``; softmax scale ``cfg.attn_scale``), SwiGLU
MLP.

Attention sharding modes (resolved per-arch from mesh divisibility):

* ``head``   — Megatron tensor parallelism over query heads.  When the KV
  head count does not divide the model axis, KV heads are *replicated* up to
  the TP width (``kv_repeat``), which preserves GQA math exactly (each
  expanded KV head j equals original head j // r) at the cost of r x KV
  activation memory.  Requires ``n_heads % tp == 0``.
* ``seq``    — context parallelism: query positions sharded over the model
  axis inside a ``shard_map``, K/V replicated across it.  Used when heads do
  not divide the mesh (smollm's 15 heads, llama4-scout's 40 on a 16-way
  axis).
* ``decode`` — flash-decoding layout: KV cache sequence-sharded over the
  model axis, all heads local, masked softmax over the sharded axis (GSPMD
  inserts the small max/sum combines).

All attention paths share one numerics contract and are cross-checked in
tests; the Pallas kernels in :mod:`repro.kernels` implement the TPU hot
loops for the same math.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from ..distributed.sharding import ShardingRules
from .config import ModelConfig


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def rope_angles(positions: jax.Array, head_dim: int, theta: float) -> Tuple[jax.Array, jax.Array]:
    """positions: (..., S) int32 -> cos/sin of shape (..., S, head_dim//2)."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, H, hd); cos/sin: (B, S, hd//2) or (S, hd//2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:  # (S, half) -> broadcast batch
        cos_, sin_ = cos[None, :, None, :], sin[None, :, None, :]
    else:  # (B, S, half)
        cos_, sin_ = cos[:, :, None, :], sin[:, :, None, :]
    out1 = x1 * cos_ - x2 * sin_
    out2 = x2 * cos_ + x1 * sin_
    return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)


def swiglu(x: jax.Array, wi: jax.Array, wg: jax.Array, wo: jax.Array) -> jax.Array:
    h = jnp.einsum("bsd,df->bsf", x, wi) * jax.nn.silu(
        jnp.einsum("bsd,df->bsf", x, wg)
    )
    return jnp.einsum("bsf,fd->bsd", h, wo)


# ---------------------------------------------------------------------------
# attention planning
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttnPlan:
    mode: str           # "head" | "seq"
    tp: int             # size of the model axis
    kv_repeat: int      # KV replication factor in head mode
    n_heads: int
    n_kv: int           # post-expansion KV head count (head mode)

    @property
    def group(self) -> int:
        return self.n_heads // self.n_kv


def plan_attention(cfg: ModelConfig, mesh: Optional[Mesh]) -> AttnPlan:
    tp = int(mesh.shape.get("model", 1)) if mesh is not None else 1
    H, KV = cfg.n_heads, cfg.n_kv_heads
    if H % tp == 0:
        if KV % tp == 0:
            return AttnPlan("head", tp, 1, H, KV)
        r = tp // KV if tp % KV == 0 else 0
        if r and (H // KV) % r == 0:
            return AttnPlan("head", tp, r, H, KV * r)
    return AttnPlan("seq", tp, 1, H, KV)


def _causal_mask(q_pos: jax.Array, k_pos: jax.Array) -> jax.Array:
    """(..., Sq, Sk) bool: True where k may attend (k_pos <= q_pos)."""
    return k_pos[None, :] <= q_pos[:, None]


def _sdpa(
    q: jax.Array,            # (B, Sq, H, hd)
    k: jax.Array,            # (B, Sk, KV, hd)
    v: jax.Array,            # (B, Sk, KV, hd)
    mask: Optional[jax.Array],  # (Sq, Sk) or (B, 1, Sq, Sk) bool
    scale: float,
) -> jax.Array:
    """Grouped scaled-dot-product attention; f32 softmax accumulation."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg, k).astype(jnp.float32) * scale
    if mask is not None:
        m = mask if mask.ndim == 4 else mask[None, None]
        # scores: (B, KV, G, Sq, Sk); mask broadcast over KV,G
        scores = jnp.where(m[:, :, None], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", w, v)
    return out.reshape(B, Sq, H, hd)


def chunked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    q_offset: Any = 0,
    chunk: int = 512,
    scale: Optional[float] = None,
) -> jax.Array:
    """Scan over query chunks against full K/V (memory O(chunk * Sk)).

    ``q_offset`` is the absolute position of q[0] (supports seq-sharded and
    decode paths); may be a traced scalar.
    """
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    scale = (hd ** -0.5) if scale is None else scale
    chunk = min(chunk, Sq)
    if Sq % chunk != 0:  # fall back to one block (tiny/smoke shapes)
        chunk = Sq
    n_chunks = Sq // chunk
    if n_chunks == 1:
        k_pos = jnp.arange(Sk)
        q_pos = q_offset + jnp.arange(Sq)
        mask = _causal_mask(q_pos, k_pos) if causal else None
        return _sdpa(q, k, v, mask, scale)

    qc = q.reshape(B, n_chunks, chunk, H, hd).transpose(1, 0, 2, 3, 4)
    k_pos = jnp.arange(Sk)

    def body(carry, args):
        i, qi = args
        q_pos = q_offset + i * chunk + jnp.arange(chunk)
        mask = _causal_mask(q_pos, k_pos) if causal else None
        return carry, _sdpa(qi, k, v, mask, scale)

    _, out = lax.scan(body, None, (jnp.arange(n_chunks), qc))
    return out.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, hd)


# ---------------------------------------------------------------------------
# full attention layer (projections + rope + qk-norm + sdpa + out-proj)
# ---------------------------------------------------------------------------


def _project_qkv(x, p, cfg: ModelConfig):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.rms_eps)
        k = rms_norm(k, p["k_norm"], cfg.rms_eps)
    return q, k, v


def attention_layer(
    x: jax.Array,                      # (B, S, D)
    p: Dict[str, jax.Array],
    cfg: ModelConfig,
    plan: AttnPlan,
    mesh: Optional[Mesh],
    rules: Optional[ShardingRules],
    *,
    positions: Optional[jax.Array] = None,     # (S,) absolute positions
    causal: Optional[bool] = None,
    return_kv: bool = False,
):
    """Training / prefill attention.  Returns (out, (k, v) | None)."""
    B, S, D = x.shape
    causal = cfg.causal if causal is None else causal
    q, k, v = _project_qkv(x, p, cfg)
    if cfg.rope:
        pos = jnp.arange(S) if positions is None else positions
        cos, sin = rope_angles(pos, cfg.hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    kv_out = (k, v) if return_kv else None  # pre-expansion layout for cache

    if plan.mode == "head":
        if plan.kv_repeat > 1:
            k = jnp.repeat(k, plan.kv_repeat, axis=2)
            v = jnp.repeat(v, plan.kv_repeat, axis=2)
        if mesh is not None and rules is not None:
            q = lax.with_sharding_constraint(
                q, rules.named(["batch", None, "heads", None], q.shape)
            )
            k = lax.with_sharding_constraint(
                k, rules.named(["batch", None, "kv_heads", None], k.shape)
            )
            v = lax.with_sharding_constraint(
                v, rules.named(["batch", None, "kv_heads", None], v.shape)
            )
        out = chunked_attention(q, k, v, causal=causal, chunk=cfg.attn_chunk,
                                scale=cfg.attn_scale)
    else:
        out = _seq_parallel_attention(q, k, v, cfg, mesh, causal)

    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return out, kv_out


def _seq_parallel_attention(q, k, v, cfg: ModelConfig, mesh, causal: bool):
    """Context parallelism: q sequence-sharded over 'model', K/V replicated.

    Implemented in shard_map so the q-chunk scan stays shard-local.  Falls
    back to plain chunked attention when there is no model axis.
    """
    tp = int(mesh.shape.get("model", 1)) if mesh is not None else 1
    S = q.shape[1]
    if tp == 1 or S % tp != 0 or mesh is None:
        return chunked_attention(q, k, v, causal=causal, chunk=cfg.attn_chunk,
                                 scale=cfg.attn_scale)

    def local(qb, kb, vb):
        # qb: (B_loc, S/tp, H, hd); kb/vb: (B_loc, S, KV, hd)
        rank = lax.axis_index("model")
        s_loc = qb.shape[1]
        return chunked_attention(
            qb, kb, vb, causal=causal, q_offset=rank * s_loc, chunk=cfg.attn_chunk,
            scale=cfg.attn_scale,
        )

    axes = tuple(mesh.shape.keys())
    batch_axes = tuple(a for a in axes if a in ("pod", "data"))
    bspec = batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None)
    qspec = P(bspec, "model", None, None)
    kvspec = P(bspec, None, None, None)
    return shard_map(
        local,
        mesh=mesh,
        in_specs=(qspec, kvspec, kvspec),
        out_specs=qspec,
        check_vma=False,
    )(q, k, v)


# ---------------------------------------------------------------------------
# decode attention (flash-decoding layout)
# ---------------------------------------------------------------------------


def decode_attention_layer(
    x: jax.Array,                 # (B, 1, D)
    p: Dict[str, jax.Array],
    cfg: ModelConfig,
    cache_k: jax.Array,           # (B, T, KV, hd) — seq-sharded over model
    cache_v: jax.Array,
    seq_positions: jax.Array,     # (B,) current length of each sequence
):
    """One-token decode: attend over the prefix and the new token, which
    stands at ``seq_positions`` in the cache's place.  The cache is read,
    not written: the new rows are returned for :func:`write_cache_rows`.

    Returns (out (B,1,D), k_row (B,KV,hd), v_row (B,KV,hd)).
    """
    B, _, D = x.shape
    T = cache_k.shape[1]
    q, k_new, v_new = _project_qkv(x, p, cfg)
    if cfg.rope:
        cos, sin = rope_angles(seq_positions[:, None], cfg.hd, cfg.rope_theta)  # (B,1,half)
        q = apply_rope(q, cos, sin)
        k_new = apply_rope(k_new, cos, sin)
    k_new = k_new.astype(cache_k.dtype)
    v_new = v_new.astype(cache_v.dtype)

    # the new row in its position, selected inside the attention read
    here = (jnp.arange(T)[None, :] == seq_positions[:, None])[:, :, None, None]
    ck = jnp.where(here, k_new, cache_k)
    cv = jnp.where(here, v_new, cache_v)

    KV = cache_k.shape[2]
    G = cfg.n_heads // KV
    qg = q.reshape(B, KV, G, cfg.hd)  # Sq == 1 squeezed
    scores = jnp.einsum("bkgh,btkh->bkgt", qg, ck).astype(jnp.float32)
    scores *= cfg.hd ** -0.5 if cfg.attn_scale is None else cfg.attn_scale
    valid = jnp.arange(T)[None, :] <= seq_positions[:, None]  # (B, T)
    scores = jnp.where(valid[:, None, None], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bkgt,btkh->bkgh", w, cv).reshape(B, 1, cfg.n_heads * cfg.hd)
    out = jnp.einsum("bsk,kd->bsd", out, p["wo"].reshape(cfg.n_heads * cfg.hd, D))
    return out, k_new[:, 0], v_new[:, 0]


#: positions written together by :func:`write_cache_rows`: the TPU's lane
#: tile, along which a (…, T, KV, hd) cache is laid out
_ROW_BLOCK = 128

#: logical axes of a (L, B, T, KV, hd) KV cache, as ``cache_shapes`` gives them
_CACHE_AXES = ("layers", "batch", "kv_seq", None, None)


def write_cache_rows(cache: jax.Array, rows: jax.Array, positions: jax.Array,
                     rules: Optional[ShardingRules] = None):
    """Write each slot's new row of every layer into the cache in place:
    ``cache`` (L, B, T, KV, hd) gets ``rows`` (L, B, KV, hd) at
    ``[:, b, positions[b]]``; a position past the cache (a slot left empty)
    writes nothing.

    Each slot is one dynamic-update-slice of the block of ``_ROW_BLOCK``
    positions around its row, the rest of the block written back as read:
    the TPU lays the cache out with positions along its lanes, and a write
    one position wide (a scatter, or a dynamic-update-slice per row) has the
    compiler copy the whole cache into another layout on the way into the
    step and back out of it.  With the cache donated the step reads and
    writes B blocks of L x W rows, not the cache.  A cache whose length is
    not a multiple of ``_ROW_BLOCK`` is written a whole (L, 1, T) slab per
    slot.

    With ``rules`` (a mesh) the cache is laid out as they resolve
    ``_CACHE_AXES``, positions split over the mesh's ``kv_seq`` axes, and
    each device writes the rows that fall in its own shard: a write at a
    position known only at run time along a split axis would otherwise
    gather the whole cache onto every device."""
    if rules is None:
        return _write_rows_local(cache, rows, positions)
    spec = rules.resolve(list(_CACHE_AXES), cache.shape)
    seq_axes = spec[2]

    def local(c, r, p):
        if seq_axes is not None:
            p = p - lax.axis_index(seq_axes) * c.shape[2]
        return _write_rows_local(c, r, p)

    return shard_map(
        local,
        mesh=rules.mesh,
        in_specs=(spec, P(spec[0], spec[1], spec[3], spec[4]), P(spec[1])),
        out_specs=spec,
        check_vma=False,
    )(cache, rows, positions)


def _write_rows_local(cache: jax.Array, rows: jax.Array, positions: jax.Array):
    """:func:`write_cache_rows` on one device; a position outside
    ``[0, T)`` writes nothing."""
    L, B, T = cache.shape[:3]
    W = _ROW_BLOCK if T % _ROW_BLOCK == 0 else T
    offsets = jnp.arange(W)
    for b in range(B):
        start = jnp.clip(positions[b], 0, T - 1) // W * W
        block = lax.dynamic_slice(cache, (0, b, start, 0, 0), (L, 1, W) + cache.shape[3:])
        hit = (start + offsets == positions[b])[None, None, :, None, None]
        block = jnp.where(hit, rows[:, b, None, None].astype(cache.dtype), block)
        cache = lax.dynamic_update_slice(cache, block, (0, b, start, 0, 0))
    return cache


# ---------------------------------------------------------------------------
# parameter factories
# ---------------------------------------------------------------------------


def attn_param_shapes(cfg: ModelConfig, d_model: Optional[int] = None,
                      n_heads: Optional[int] = None, n_kv: Optional[int] = None,
                      ) -> Dict[str, Tuple[Tuple[int, ...], Tuple[Optional[str], ...]]]:
    """shape + logical-axes pairs for one attention block."""
    D = d_model or cfg.d_model
    H = n_heads or cfg.n_heads
    KV = n_kv or cfg.n_kv_heads
    hd = cfg.hd
    shapes = {
        "wq": ((D, H, hd), ("embed", "heads", "head_dim")),
        "wk": ((D, KV, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ((D, KV, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ((H, hd, D), ("heads", "head_dim", "embed")),
    }
    if cfg.qk_norm:
        shapes["q_norm"] = ((hd,), ("head_dim",))
        shapes["k_norm"] = ((hd,), ("head_dim",))
    return shapes


def mlp_param_shapes(cfg: ModelConfig, d_ff: Optional[int] = None):
    D, F = cfg.d_model, d_ff or cfg.d_ff
    return {
        "wi": ((D, F), ("embed", "d_ff")),
        "wg": ((D, F), ("embed", "d_ff")),
        "wo": ((F, D), ("d_ff", "embed")),
    }
