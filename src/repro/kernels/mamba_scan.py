"""Chunked Mamba-1 selective scan as a Pallas kernel.

The recurrence h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t is sequential in
time but embarrassingly parallel over channels; the TPU mapping therefore
tiles the *channel* axis (d_inner) over the grid and VPU lanes, and streams
*sequence chunks* through VMEM with the carried state in VMEM scratch:

  grid = (batch, d_blocks, n_chunks)   # chunk axis innermost => sequential

The state is held as ``(d_state, block_d)``: channels on lanes, state on
sublanes.  Within a chunk the kernel runs the recurrence with a
``fori_loop`` over the chunk's timesteps; each step reads its rows of
(x, dt) from an f32 copy of the chunk with ``pl.ds``, takes its column of
B and C by a one-hot lane reduction (Mosaic has no dynamic lane slice),
and writes its output row through a ref — on TPU each step is a few fused
VPU passes over the (d_state, block_d) tile while the next chunk's
(x, dt, B, C) tiles are being DMA'd in.  The f32 state never leaves VMEM
between chunks (this is exactly the XDT principle at register level: the
carried state stays producer-resident; only the streamed inputs move).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(
    x_ref,        # (1, chunk, bd)
    dt_ref,       # (1, chunk, bd)
    b_ref,        # (1, ds, chunk)  B transposed: state on sublanes
    c_ref,        # (1, ds, chunk)
    a_ref,        # (ds, bd)        A transposed
    d_ref,        # (1, bd)
    h0_ref,       # (1, ds, bd)
    y_ref,        # out (1, chunk, bd)
    h_out_ref,    # out (1, ds, bd)
    h_ref,        # scratch (ds, bd) f32: carried state
    x_scr,        # scratch (chunk, bd) f32
    dt_scr,       # scratch (chunk, bd) f32
    y_scr,        # scratch (chunk, bd) f32
    *,
    chunk: int,
    n_chunks: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)

    x_scr[...] = x_ref[0].astype(jnp.float32)
    dt_scr[...] = dt_ref[0].astype(jnp.float32)
    B_in = b_ref[0].astype(jnp.float32)                   # (ds, chunk)
    C_in = c_ref[0].astype(jnp.float32)
    A = a_ref[...].astype(jnp.float32)                    # (ds, bd)
    lane = jax.lax.broadcasted_iota(jnp.int32, B_in.shape, 1)

    def column(m, t):                                     # (ds, chunk) -> (ds, 1)
        return jnp.sum(jnp.where(lane == t, m, 0.0), axis=1, keepdims=True)

    def step(t, h):
        x_t = x_scr[pl.ds(t, 1), :]                       # (1, bd)
        dt_t = dt_scr[pl.ds(t, 1), :]
        a_t = jnp.exp(dt_t * A)                           # (ds, bd)
        b_t = (dt_t * x_t) * column(B_in, t)              # (ds, bd)
        h = a_t * h + b_t
        y_scr[pl.ds(t, 1), :] = jnp.sum(
            h * column(C_in, t), axis=0, keepdims=True
        )                                                 # (1, bd)
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk, step, h_ref[...])
    D = d_ref[...].astype(jnp.float32)                    # (1, bd)
    y_ref[0] = (y_scr[...] + x_scr[...] * D).astype(y_ref.dtype)

    @pl.when(ic == n_chunks - 1)
    def _finalize():
        h_out_ref[0] = h_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "block_d", "interpret"))
def mamba_scan(
    x: jax.Array,               # (B, S, d_in) post-conv/silu
    dt: jax.Array,              # (B, S, d_in) post-softplus
    B_in: jax.Array,            # (B, S, ds)
    C_in: jax.Array,            # (B, S, ds)
    A: jax.Array,               # (d_in, ds) negative
    D: jax.Array,               # (d_in,)
    h0: Optional[jax.Array] = None,    # (B, d_in, ds) f32
    *,
    chunk: int = 256,
    block_d: int = 128,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y (B,S,d_in) in x.dtype, h_last (B,d_in,ds) f32)."""
    Bsz, S, d_in = x.shape
    ds = B_in.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((Bsz, d_in, ds), jnp.float32)
    chunk = min(chunk, S)
    block_d = min(block_d, d_in)
    assert S % chunk == 0 and d_in % block_d == 0, (S, chunk, d_in, block_d)
    n_chunks, n_d = S // chunk, d_in // block_d

    grid = (Bsz, n_d, n_chunks)   # chunk innermost: state carries in scratch
    kernel = functools.partial(_scan_kernel, chunk=chunk, n_chunks=n_chunks)
    y, h_last = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b, id_, ic: (b, ic, id_)),
            pl.BlockSpec((1, chunk, block_d), lambda b, id_, ic: (b, ic, id_)),
            pl.BlockSpec((1, ds, chunk), lambda b, id_, ic: (b, 0, ic)),
            pl.BlockSpec((1, ds, chunk), lambda b, id_, ic: (b, 0, ic)),
            pl.BlockSpec((ds, block_d), lambda b, id_, ic: (0, id_)),
            pl.BlockSpec((1, block_d), lambda b, id_, ic: (0, id_)),
            pl.BlockSpec((1, ds, block_d), lambda b, id_, ic: (b, 0, id_)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b, id_, ic: (b, ic, id_)),
            pl.BlockSpec((1, ds, block_d), lambda b, id_, ic: (b, 0, id_)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, S, d_in), x.dtype),
            jax.ShapeDtypeStruct((Bsz, ds, d_in), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((ds, block_d), jnp.float32),
            pltpu.VMEM((chunk, block_d), jnp.float32),
            pltpu.VMEM((chunk, block_d), jnp.float32),
            pltpu.VMEM((chunk, block_d), jnp.float32),
        ],
        interpret=interpret,
    )(
        x, dt, B_in.swapaxes(1, 2), C_in.swapaxes(1, 2), A.T,
        D.reshape(1, d_in), h0.astype(jnp.float32).swapaxes(1, 2),
    )
    return y, h_last.swapaxes(1, 2)
