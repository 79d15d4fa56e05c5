"""State-space blocks: Mamba-1 (S6 selective scan) and Mamba-2 (SSD).

Both are written in the *chunked* form that the TPU kernel
(:mod:`repro.kernels.mamba_scan`) mirrors: an outer ``lax.scan`` over
sequence chunks carrying the SSM state, with the intra-chunk work done
either by an associative scan (Mamba-1: diagonal A, state (d_inner, d_state))
or by the quadratic-in-chunk matmul form (Mamba-2 / SSD: scalar-per-head
decay, which maps onto the MXU).

Decode is the O(1) single-step recurrence over carried (conv_state,
ssm_state) — the reason SSM/hybrid archs are the ones that run the
``long_500k`` cell.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .config import ModelConfig


def _causal_conv1d(x: jax.Array, w: jax.Array, b: Optional[jax.Array]) -> jax.Array:
    """Depthwise causal conv.  x: (B, S, C); w: (W, C) -> (B, S, C)."""
    W = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for i in range(W):  # W is 4: unrolled taps beat a conv op at this width
        out = out + xp[:, i : i + x.shape[1], :] * w[i]
    if b is not None:
        out = out + b
    return out


def _conv_step(x_t: jax.Array, conv_state: jax.Array, w: jax.Array, b) -> Tuple[jax.Array, jax.Array]:
    """Single-token conv.  x_t: (B, C); conv_state: (B, W-1, C)."""
    window = jnp.concatenate([conv_state, x_t[:, None, :]], axis=1)  # (B, W, C)
    out = jnp.einsum("bwc,wc->bc", window, w)
    if b is not None:
        out = out + b
    return out, window[:, 1:, :]


# ---------------------------------------------------------------------------
# Mamba-1 (S6): diagonal A, per-channel state (d_inner, d_state)
# ---------------------------------------------------------------------------


def _s6_chunk(h0, a, b_in):
    """Associative scan within a chunk.

    h_t = a_t * h_{t-1} + b_t, carried h0.  a/b: (B, c, d_in, ds) f32.
    Returns (h_last, h_all)."""
    b0 = b_in.at[:, 0].add(a[:, 0] * h0)
    def comb(l, r):
        al, bl = l
        ar, br = r
        return al * ar, ar * bl + br
    _, h_all = lax.associative_scan(comb, (a, b0), axis=1)
    return h_all[:, -1], h_all


def mamba1_mix(
    x_in: jax.Array,              # (B, S, d_in) post-conv, post-silu
    dt: jax.Array,                # (B, S, d_in) post-softplus
    B_ssm: jax.Array,             # (B, S, ds)
    C_ssm: jax.Array,             # (B, S, ds)
    A: jax.Array,                 # (d_in, ds)  (negative)
    D: jax.Array,                 # (d_in,)
    h0: Optional[jax.Array] = None,
    chunk: int = 256,
) -> Tuple[jax.Array, jax.Array]:
    """Selective scan.  Returns (y (B,S,d_in), h_last (B,d_in,ds))."""
    Bsz, S, d_in = x_in.shape
    ds = B_ssm.shape[-1]
    f32 = jnp.float32
    if h0 is None:
        h0 = jnp.zeros((Bsz, d_in, ds), f32)
    chunk = min(chunk, S)
    if S % chunk:
        chunk = S
    n = S // chunk

    def per_chunk(h, args):
        xc, dtc, Bc, Cc = args  # (B, c, ...)
        a = jnp.exp(dtc.astype(f32)[..., None] * A)                 # (B,c,d_in,ds)
        b = (dtc * xc).astype(f32)[..., None] * Bc.astype(f32)[:, :, None, :]
        h_last, h_all = _s6_chunk(h, a, b)
        y = jnp.einsum("bcds,bcs->bcd", h_all, Cc.astype(f32))
        return h_last, y

    def split(t):
        return t.reshape(Bsz, n, chunk, *t.shape[2:]).swapaxes(0, 1)

    h_last, ys = lax.scan(
        per_chunk, h0, (split(x_in), split(dt), split(B_ssm), split(C_ssm))
    )
    y = ys.swapaxes(0, 1).reshape(Bsz, S, d_in).astype(x_in.dtype)
    y = y + x_in * D
    return y, h_last


def mamba1_block(
    x: jax.Array,                 # (B, S, D)
    p: Dict[str, jax.Array],
    cfg: ModelConfig,
    state: Optional[Dict[str, jax.Array]] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Full Mamba-1 block.  ``state`` (decode): {"conv": (B,W-1,d_in),
    "ssm": (B,d_in,ds)}.  Returns (out, new_state)."""
    s = cfg.ssm
    dtr = s.dt_rank or -(-cfg.d_model // 16)
    xz = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    x_part, z = jnp.split(xz, 2, axis=-1)

    decode = state is not None and x.shape[1] == 1
    if decode:
        conv_out, new_conv = _conv_step(x_part[:, 0], state["conv"], p["conv_w"], p.get("conv_b"))
        x_conv = jax.nn.silu(conv_out)[:, None]
    else:
        x_conv = jax.nn.silu(_causal_conv1d(x_part, p["conv_w"], p.get("conv_b")))
        new_conv = x_part[:, -(s.conv_width - 1):, :] if x.shape[1] >= s.conv_width - 1 else None

    xdb = jnp.einsum("bse,ef->bsf", x_conv, p["x_proj"])
    dt_raw, B_ssm, C_ssm = jnp.split(xdb, [dtr, dtr + s.d_state], axis=-1)
    dt = jax.nn.softplus(jnp.einsum("bsf,fe->bse", dt_raw, p["dt_proj"]) + p["dt_bias"])
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    if decode:
        h0 = state["ssm"]
        a = jnp.exp(dt[:, 0].astype(jnp.float32)[..., None] * A)
        b = (dt[:, 0] * x_conv[:, 0]).astype(jnp.float32)[..., None] * B_ssm[:, 0].astype(jnp.float32)[:, None, :]
        h = a * h0 + b
        y = jnp.einsum("bds,bs->bd", h, C_ssm[:, 0].astype(jnp.float32)).astype(x.dtype)
        y = (y + x_conv[:, 0] * p["D"])[:, None]
        new_state = {"conv": new_conv, "ssm": h}
    else:
        h0 = state["ssm"] if state is not None else None
        y, h_last = mamba1_mix(x_conv, dt, B_ssm, C_ssm, A, p["D"], h0, s.chunk)
        new_state = {"conv": new_conv, "ssm": h_last}

    y = y * jax.nn.silu(z)
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"])
    return out, new_state


# ---------------------------------------------------------------------------
# Mamba-2 (SSD): scalar-per-head decay, quadratic-in-chunk matmul form
# ---------------------------------------------------------------------------


def ssd_mix(
    x_h: jax.Array,               # (B, S, H, hd)
    dt: jax.Array,                # (B, S, H) post-softplus
    B_ssm: jax.Array,             # (B, S, ds)  (single group)
    C_ssm: jax.Array,             # (B, S, ds)
    A_log: jax.Array,             # (H,)
    D: jax.Array,                 # (H,)
    h0: Optional[jax.Array] = None,
    chunk: int = 128,
) -> Tuple[jax.Array, jax.Array]:
    """Mamba-2 SSD in chunked matmul form.  Returns (y, h_last (B,H,hd,ds)).

    A sequence that is not a whole number of chunks is padded at its end
    with steps of ``dt = 0``: each leaves the state as it is (decay
    ``exp(0) = 1``, input ``dt * x = 0``), and their outputs are dropped.
    Callers pad the same way to mask positions beyond a sequence's length.
    """
    Bsz, S, H, hd = x_h.shape
    ds = B_ssm.shape[-1]
    f32 = jnp.float32
    if h0 is None:
        h0 = jnp.zeros((Bsz, H, hd, ds), f32)
    chunk = min(chunk, S)
    pad = -S % chunk
    x_in = x_h
    if pad:
        x_h, dt, B_ssm, C_ssm = (
            jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
            for t in (x_h, dt, B_ssm, C_ssm))
    n = (S + pad) // chunk

    A = -jnp.exp(A_log.astype(f32))  # (H,) negative decay rates

    def per_chunk(h, args):
        xc, dtc, Bc, Cc = args                      # (B,c,...)
        la = dtc.astype(f32) * A                     # (B,c,H) log-decay
        cum = jnp.cumsum(la, axis=1)                 # (B,c,H)
        # intra-chunk: y_t = sum_{s<=t} C_t.B_s * exp(cum_t - cum_s) * dt_s x_s
        G = jnp.einsum("btn,bsn->bts", Cc.astype(f32), Bc.astype(f32))
        L = jnp.exp(cum[:, :, None, :] - cum[:, None, :, :])  # (B,t,s,H)
        causal = jnp.tril(jnp.ones((xc.shape[1], xc.shape[1]), bool))
        M = jnp.where(causal[None, :, :, None], G[..., None] * L, 0.0)
        xdt = xc.astype(f32) * dtc.astype(f32)[..., None]     # (B,c,H,hd)
        y = jnp.einsum("btsh,bshd->bthd", M, xdt)
        # inter-chunk: contribution of carried state
        y = y + jnp.einsum("btn,bhdn,bth->bthd", Cc.astype(f32), h, jnp.exp(cum))
        # new carried state
        decay_to_end = jnp.exp(cum[:, -1:, :] - cum)          # (B,c,H)
        h_new = h * jnp.exp(cum[:, -1])[:, :, None, None] + jnp.einsum(
            "bsn,bshd,bsh->bhdn", Bc.astype(f32), xdt, decay_to_end
        )
        return h_new, y

    def split(t):
        return t.reshape(Bsz, n, chunk, *t.shape[2:]).swapaxes(0, 1)

    h_last, ys = lax.scan(per_chunk, h0, (split(x_h), split(dt), split(B_ssm), split(C_ssm)))
    y = ys.swapaxes(0, 1).reshape(Bsz, S + pad, H, hd).astype(x_h.dtype)
    if pad:
        y = y[:, :S]
    y = y + x_in * D[None, None, :, None]
    return y, h_last


def _last_positions(x: jax.Array, length: jax.Array, n: int) -> jax.Array:
    """x[b, length[b] - n : length[b]] of each sequence of ``x`` (B, S, C);
    positions before the first are zeros, as the causal conv's padding."""
    xp = jnp.pad(x, ((0, 0), (n, 0), (0, 0)))
    return jax.vmap(lambda row, l: lax.dynamic_slice_in_dim(row, l, n))(xp, length)


def mamba2_block(
    x: jax.Array,
    p: Dict[str, jax.Array],
    cfg: ModelConfig,
    state: Optional[Dict[str, jax.Array]] = None,
    length: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Mamba-2 block.  in_proj emits [z, x, B, C, dt]; conv over (x,B,C).

    ``length`` (B,) (prefill only): each sequence's real length in a
    padded batch.  Positions from it on get ``dt = 0``, so the final state is
    the unpadded one, and the conv state is taken from the last real
    positions."""
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    H = d_in // s.head_dim
    ds = s.d_state
    proj = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    z, xbc, dt_raw = jnp.split(proj, [d_in, 2 * d_in + 2 * ds], axis=-1)

    decode = state is not None and x.shape[1] == 1
    if decode:
        conv_out, new_conv = _conv_step(xbc[:, 0], state["conv"], p["conv_w"], p.get("conv_b"))
        xbc_c = jax.nn.silu(conv_out)[:, None]
    else:
        xbc_c = jax.nn.silu(_causal_conv1d(xbc, p["conv_w"], p.get("conv_b")))
        if length is not None:
            new_conv = _last_positions(xbc, length, s.conv_width - 1)
        else:
            new_conv = xbc[:, -(s.conv_width - 1):, :] if x.shape[1] >= s.conv_width - 1 else None

    x_part, B_ssm, C_ssm = jnp.split(xbc_c, [d_in, d_in + ds], axis=-1)
    x_h = x_part.reshape(*x_part.shape[:2], H, s.head_dim)
    dt = jax.nn.softplus(dt_raw + p["dt_bias"])      # (B,S,H)
    if length is not None and not decode:
        real = jnp.arange(x.shape[1])[None, :] < length[:, None]
        dt = jnp.where(real[..., None], dt, 0)

    if decode:
        f32 = jnp.float32
        h0 = state["ssm"]                            # (B,H,hd,ds)
        la = dt[:, 0].astype(f32) * (-jnp.exp(p["A_log"].astype(f32)))
        a = jnp.exp(la)                              # (B,H)
        xdt = x_h[:, 0].astype(f32) * dt[:, 0].astype(f32)[..., None]
        h = h0 * a[:, :, None, None] + jnp.einsum("bn,bhd->bhdn", B_ssm[:, 0].astype(f32), xdt)
        y = jnp.einsum("bn,bhdn->bhd", C_ssm[:, 0].astype(f32), h).astype(x.dtype)
        y = (y + x_h[:, 0] * p["D"][None, :, None])[:, None]
        new_state = {"conv": new_conv, "ssm": h}
        y = y.reshape(x.shape[0], 1, d_in)
    else:
        h0 = state["ssm"] if state is not None else None
        y, h_last = ssd_mix(x_h, dt, B_ssm, C_ssm, p["A_log"], p["D"], h0, s.chunk)
        new_state = {"conv": new_conv, "ssm": h_last}
        y = y.reshape(x.shape[0], x.shape[1], d_in)

    from .layers import rms_norm

    y = rms_norm(y * jax.nn.silu(z), p["norm"], cfg.rms_eps)
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"])
    return out, new_state


# ---------------------------------------------------------------------------
# parameter shapes
# ---------------------------------------------------------------------------


def ssm_param_shapes(cfg: ModelConfig):
    s = cfg.ssm
    D = cfg.d_model
    d_in = s.expand * D
    if s.version == 1:
        dtr = s.dt_rank or -(-D // 16)
        return {
            "in_proj": ((D, 2 * d_in), ("embed", "ssm_inner")),
            "conv_w": ((s.conv_width, d_in), ("conv", "ssm_inner")),
            "conv_b": ((d_in,), ("ssm_inner",)),
            "x_proj": ((d_in, dtr + 2 * s.d_state), ("ssm_inner", None)),
            "dt_proj": ((dtr, d_in), (None, "ssm_inner")),
            "dt_bias": ((d_in,), ("ssm_inner",)),
            "A_log": ((d_in, s.d_state), ("ssm_inner", "ssm_state")),
            "D": ((d_in,), ("ssm_inner",)),
            "out_proj": ((d_in, D), ("ssm_inner", "embed")),
        }
    H = d_in // s.head_dim
    return {
        "in_proj": ((D, 2 * d_in + 2 * s.d_state + H), ("embed", None)),
        "conv_w": ((s.conv_width, d_in + 2 * s.d_state), ("conv", None)),
        "conv_b": ((d_in + 2 * s.d_state,), (None,)),
        "dt_bias": ((H,), ("ssm_heads",)),
        "A_log": ((H,), ("ssm_heads",)),
        "D": ((H,), ("ssm_heads",)),
        "norm": ((d_in,), ("ssm_inner",)),
        "out_proj": ((d_in, D), ("ssm_inner", "embed")),
    }


def ssm_state_shapes(cfg: ModelConfig, batch: int):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    if s.version == 1:
        return {
            "conv": ((batch, s.conv_width - 1, d_in), ("batch", None, "ssm_inner")),
            "ssm": ((batch, d_in, s.d_state), ("batch", "ssm_inner", "ssm_state")),
        }
    H = d_in // s.head_dim
    return {
        "conv": ((batch, s.conv_width - 1, d_in + 2 * s.d_state), ("batch", None, None)),
        "ssm": ((batch, H, s.head_dim, s.d_state), ("batch", "ssm_heads", None, None)),
    }
