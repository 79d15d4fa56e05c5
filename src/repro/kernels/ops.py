"""Public wrappers for the Pallas kernels.

Dispatch policy: on TPU the Pallas kernel is lowered by Mosaic and runs
natively; on CPU (unit tests) the same kernel body executes in interpret
mode; any other backend raises, so no device silently runs the
interpreter.  A shape the kernel's blocking cannot tile (ragged) goes to the
pure-jnp oracle instead, and every such call is counted in
:data:`FALLBACKS` under the op's name (counted per call, or per trace when
the caller is under ``jit``), so a run can assert that its shapes took the
kernel.  Numerics are identical across the three paths (asserted by the
sweep tests), so models can call these unconditionally.
"""
from __future__ import annotations

import collections
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import ref as _ref
from .decode_attention import decode_attention as _decode_kernel
from .flash_attention import flash_attention as _flash_kernel
from .mamba_scan import mamba_scan as _mamba_kernel
from .xdt_pull import xdt_pull as _pull_kernel

#: op name -> calls that fell back to the jnp oracle on a ragged shape
FALLBACKS: collections.Counter = collections.Counter()


def kernel_mode() -> str:
    """How the kernels run on the default backend: ``"mosaic"`` on TPU,
    ``"interpret"`` on CPU; any other backend has no kernel path."""
    backend = jax.default_backend()
    if backend == "tpu":
        return "mosaic"
    if backend == "cpu":
        return "interpret"
    raise RuntimeError(
        f"no Pallas kernel path for backend {backend!r}: "
        "kernels run on TPU (Mosaic) or on CPU (interpret mode)"
    )


def _interpret() -> bool:
    return kernel_mode() == "interpret"


def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    *, causal: bool = True, q_offset: int = 0, scale: Optional[float] = None,
    block_q: int = 128, block_k: int = 128,
) -> jax.Array:
    Sq, Sk = q.shape[1], k.shape[1]
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    if Sq % bq or Sk % bk or q.shape[2] % k.shape[2]:
        FALLBACKS["flash_attention"] += 1
        return _ref.flash_attention_ref(
            q, k, v, causal=causal, q_offset=q_offset, scale=scale
        )
    return _flash_kernel(
        q, k, v, causal=causal, q_offset=q_offset, scale=scale,
        block_q=bq, block_k=bk, interpret=_interpret(),
    )


def decode_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, lengths: jax.Array,
    *, scale: Optional[float] = None, block_t: int = 512,
) -> jax.Array:
    T = k.shape[1]
    bt = min(block_t, T)
    if T % bt or q.shape[1] % k.shape[2]:
        FALLBACKS["decode_attention"] += 1
        return _ref.decode_attention_ref(q, k, v, lengths, scale=scale)
    return _decode_kernel(
        q, k, v, lengths, scale=scale, block_t=bt, interpret=_interpret()
    )


def mamba_scan(
    x: jax.Array, dt: jax.Array, B_in: jax.Array, C_in: jax.Array,
    A: jax.Array, D: jax.Array, h0: Optional[jax.Array] = None,
    *, chunk: int = 256, block_d: int = 128,
) -> Tuple[jax.Array, jax.Array]:
    S, d_in = x.shape[1], x.shape[2]
    c, bd = min(chunk, S), min(block_d, d_in)
    if S % c or d_in % bd:
        FALLBACKS["mamba_scan"] += 1
        return _ref.mamba_scan_ref(x, dt, B_in, C_in, A, D, h0)
    return _mamba_kernel(
        x, dt, B_in, C_in, A, D, h0, chunk=c, block_d=bd,
        interpret=_interpret(),
    )


def _tile(dim: int, limit: int, align: int) -> Optional[int]:
    """The whole of ``dim`` if it fits in ``limit``, else the largest
    multiple of ``align`` up to ``limit`` that divides it (None: ragged)."""
    if dim <= limit:
        return dim
    for b in range(limit - limit % align, 0, -align):
        if dim % b == 0:
            return b
    return None


def xdt_pull(
    src: jax.Array, scale: Optional[jax.Array] = None,
    *, out_dtype=jnp.bfloat16, block_n: int = 512, block_d: int = 512,
) -> jax.Array:
    # rows in multiples of 32 (int8's sublane tile), columns of 128 lanes
    bn = _tile(src.shape[0], block_n, 32) if src.ndim == 2 else None
    bd = _tile(src.shape[1], block_d, 128) if src.ndim == 2 else None
    if bn is None or bd is None:
        FALLBACKS["xdt_pull"] += 1
        return _ref.xdt_pull_ref(src, scale, out_dtype=out_dtype)
    return _pull_kernel(
        src, scale, out_dtype=out_dtype, block_n=bn, block_d=bd,
        interpret=_interpret(),
    )
