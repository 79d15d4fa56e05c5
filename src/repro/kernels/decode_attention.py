"""Single-token decode attention (flash-decoding) as a Pallas kernel.

``serve_step``'s hot loop: one query token per sequence against a 32k-512k
KV cache.  This is memory-bound (arithmetic intensity ~= 2 flops/byte), so
the kernel's job is to touch every cache byte exactly once: the grid streams
(batch, kv-head, kv-block) tiles through VMEM, computing the fused
q.K -> online-softmax -> .V pass per tile with the running (m, l, acc) state
in VMEM scratch.  All G query heads of a GQA group ride along with their
shared KV tile, so GQA directly multiplies arithmetic intensity by G.

The wrapper lays K/V out heads-first, ``(B, KV, T, hd)``, and the query as
``(B, KV, G, hd)``, so every block's last two dims are ``(rows, hd)`` — the
(sublane, lane) tile Mosaic requires.

Per-sequence lengths are prefetched to SMEM (scalar memory) and drive the
masking; fully-masked tail blocks cost one VPU pass but no MXU work.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(
    len_ref,      # SMEM (B,) int32 lengths
    q_ref,        # (1, 1, G, hd): this kv-head's query group
    k_ref,        # (1, 1, bt, hd)
    v_ref,        # (1, 1, bt, hd)
    o_ref,        # (1, 1, G, hd)
    m_ref,        # scratch (G, 1)
    l_ref,        # scratch (G, 1)
    acc_ref,      # scratch (G, hd)
    *,
    scale: float,
    block_t: int,
    n_t_blocks: int,
):
    b = pl.program_id(0)
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                   # (G, hd)
    k = k_ref[0, 0].astype(jnp.float32)                   # (bt, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (G, bt)
    t_pos = it * block_t + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(t_pos <= len_ref[b], s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(it == n_t_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "block_t", "interpret")
)
def decode_attention(
    q: jax.Array,               # (B, H, hd) one token per sequence
    k: jax.Array,               # (B, T, KV, hd)
    v: jax.Array,               # (B, T, KV, hd)
    lengths: jax.Array,         # (B,) int32; positions [0, len] attended
    *,
    scale: Optional[float] = None,
    block_t: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = (hd ** -0.5) if scale is None else scale
    block_t = min(block_t, T)
    assert T % block_t == 0, (T, block_t)
    n_t = T // block_t

    # each kv-head's G query heads are contiguous: (B, KV, G, hd)
    qg = q.reshape(B, KV, G, hd)
    kt, vt = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)

    grid = (B, KV, n_t)
    kernel = functools.partial(
        _decode_kernel, scale=float(scale), block_t=block_t, n_t_blocks=n_t
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, G, hd), lambda b, h, it, lens: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, block_t, hd), lambda b, h, it, lens: (b, h, it, 0)),
                pl.BlockSpec((1, 1, block_t, hd), lambda b, h, it, lens: (b, h, it, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, h, it, lens: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qg, kt, vt)
    return out.reshape(B, H, hd)
