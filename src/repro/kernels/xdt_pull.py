"""The XDT data-plane hot loop: a streamed, chunked buffer pull.

On real hardware the consumer's pull of a producer-resident buffer lands in
the consumer's HBM via ICI DMA; what the *kernel* layer owns is the
"reconstruct the original request" step fused into the stream (paper §5.1.1:
the SDK re-joins control message and object before invoking the handler).
Concretely: the pulled bytes are often quantized (int8 + per-row scales, the
wire format of the compressed cross-pod path) or in the producer's compute
dtype, and the consumer needs them dequantized/cast into its own layout.

This kernel streams (block_n, block_d) tiles HBM->VMEM->HBM with the
dequant/cast fused into the copy, so the reconstruction costs zero extra
memory passes.  The row width is tiled as well as the rows, so VMEM use is
fixed by the block and does not grow with D.  Pallas double-buffers the
tile fetches, which is the kernel-level analogue of the queue-proxy
overlapping the object pull with function boot (§5.1.3).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _pull_kernel(src_ref, scale_ref, o_ref):
    x = src_ref[...].astype(jnp.float32)
    s = scale_ref[...].astype(jnp.float32)               # (block_n, 1)
    o_ref[...] = (x * s).astype(o_ref.dtype)


def _pull_kernel_noscale(src_ref, o_ref):
    o_ref[...] = src_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("out_dtype", "block_n", "block_d", "interpret")
)
def xdt_pull(
    src: jax.Array,                       # (N, D) producer-resident buffer
    scale: Optional[jax.Array] = None,    # (N,) per-row dequant scale
    *,
    out_dtype=jnp.bfloat16,
    block_n: int = 512,
    block_d: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Streamed pull of ``src`` with fused dequant/cast into ``out_dtype``."""
    N, Dm = src.shape
    block_n = min(block_n, N)
    block_d = min(block_d, Dm)
    assert N % block_n == 0 and Dm % block_d == 0, (N, block_n, Dm, block_d)
    grid = (N // block_n, Dm // block_d)
    tile = pl.BlockSpec((block_n, block_d), lambda i, j: (i, j))

    if scale is None:
        return pl.pallas_call(
            _pull_kernel_noscale,
            grid=grid,
            in_specs=[tile],
            out_specs=tile,
            out_shape=jax.ShapeDtypeStruct((N, Dm), out_dtype),
            interpret=interpret,
        )(src)

    scale2d = scale.reshape(N, 1)
    return pl.pallas_call(
        _pull_kernel,
        grid=grid,
        in_specs=[tile, pl.BlockSpec((block_n, 1), lambda i, j: (i, 0))],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((N, Dm), out_dtype),
        interpret=interpret,
    )(src, scale2d)
