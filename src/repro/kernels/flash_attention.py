"""Blockwise online-softmax attention (prefill hot loop) as a Pallas kernel.

TPU mapping: the wrapper lays the heads out in front, ``(B, H, S, hd)``, so
every block's last two dims are ``(block, hd)`` — the (sublane, lane) tile
Mosaic requires.  The grid streams (batch, q-head, q-block, kv-block) tiles
through VMEM; the innermost kv axis iterates sequentially per q-block, so the
running max / sum / accumulator live in VMEM scratch across kv steps —
Pallas double-buffers the HBM->VMEM block fetches automatically, overlapping
the next kv tile's DMA with the current tile's MXU work.  Block shapes are
MXU-aligned (q-block x head-dim and kv-block x head-dim matmuls, multiples
of 128 in production configs).

GQA is handled in the index maps: q head ``h`` reads kv head ``h // group``
— no KV replication is materialized (the kernel-level version of the
"consumer pulls exactly its bytes" principle).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref,        # (1, 1, bq, hd)
    k_ref,        # (1, 1, bk, hd)
    v_ref,        # (1, 1, bk, hd)
    o_ref,        # (1, 1, bq, hd)
    m_ref,        # scratch (bq, 1)
    l_ref,        # scratch (bq, 1)
    acc_ref,      # scratch (bq, hd)
    *,
    scale: float,
    causal: bool,
    q_offset: int,
    block_q: int,
    block_k: int,
    n_kv_blocks: int,
):
    ik = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                  # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (bq, bk)
    if causal:
        q_pos = q_offset + iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_pos = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(ik == n_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)               # fully-masked rows -> 0
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "q_offset", "block_q", "block_k", "scale", "interpret"),
)
def flash_attention(
    q: jax.Array,               # (B, Sq, H, hd)
    k: jax.Array,               # (B, Sk, KV, hd)
    v: jax.Array,               # (B, Sk, KV, hd)
    *,
    causal: bool = True,
    q_offset: int = 0,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = (hd ** -0.5) if scale is None else scale
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, block_q, Sk, block_k)
    n_q, n_k = Sq // block_q, Sk // block_k

    # heads in front: each block's trailing dims are (block, hd)
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    grid = (B, H, n_q, n_k)
    kernel = functools.partial(
        _flash_kernel,
        scale=float(scale), causal=causal, q_offset=int(q_offset),
        block_q=block_q, block_k=block_k, n_kv_blocks=n_k,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            # VMEM scratch carrying the online-softmax state across kv steps
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
