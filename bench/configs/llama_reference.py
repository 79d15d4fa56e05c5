"""Plain reference of a Llama-style decoder (SmolLM is one): the forward
pass over a whole sequence in float32 ``jax.numpy`` at the highest matmul
precision, with no kernels, no cache and no batching.  It imports nothing of
the program under test and reads the weights as ``llama_weights`` makes them.

Follows the Hugging Face ``LlamaForCausalLM`` description: pre-norm RMSNorm
blocks, rotary embeddings on halves (``rotate_half``) with
``inv_freq = theta ** (-2i / head_dim)``, grouped-query attention with
causal softmax, SwiGLU MLP ``down(silu(gate(x)) * up(x))``, a final
RMSNorm and an output head tied to the embedding.  Departures: none in the
mathematics; padding positions after the sequence are computed and ignored.

The control of the comparison is the same pass computed in float8 (e4m3),
the precision step below the bfloat16 the deployment serves in: every
weight matrix rounded with a scale per output channel, and every matrix
product's input rounded with a scale per token.  Attention's own products
and the softmax stay in float32.
"""
from __future__ import annotations

import functools


def _rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * w


def _rope(x, pos, theta):
    import jax.numpy as jnp

    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    half = hd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


def _fp8(a, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    import jax.numpy as jnp

    amax = jnp.max(jnp.abs(a), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def forward(w, tokens, *, heads, kv_heads, head_dim, eps, theta, fp8=False):
    """Logits (S, V) in float32 for ``tokens`` (S,); with ``fp8`` every
    matrix product's input is rounded per token (weights come rounded from
    :func:`to_fp8`)."""
    import jax
    import jax.numpy as jnp

    def mm(x, m):
        return (_fp8(x, -1) if fp8 else x) @ m

    S = tokens.shape[0]
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]
    group = heads // kv_heads

    def layer(x, lw):
        h = _rms_norm(x, lw["ln1"], eps)
        q = mm(h, lw["wq"]).reshape(S, heads, head_dim)
        k = mm(h, lw["wk"]).reshape(S, kv_heads, head_dim)
        v = mm(h, lw["wv"]).reshape(S, kv_heads, head_dim)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(head_dim))
        s = jnp.where(causal[None], s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", a, v).reshape(S, heads * head_dim)
        x = x + mm(o, lw["wo"])
        h = _rms_norm(x, lw["ln2"], eps)
        x = x + mm(jax.nn.silu(mm(h, lw["gate"])) * mm(h, lw["up"]), lw["down"])
        return x, None

    x = w["embed"][tokens]
    x, _ = jax.lax.scan(layer, x, w["layers"])
    x = _rms_norm(x, w["final_norm"], eps)
    return mm(x, w["embed"].T)


def to_f32(w):
    import jax
    import jax.numpy as jnp

    return jax.tree.map(lambda a: a.astype(jnp.float32), w)


def to_fp8(w):
    """Every matrix rounded to float8 e4m3 with a per-output-channel scale
    (the embedding, which is also the output head, per row)."""
    layers = {k: (_fp8(v, 1) if v.ndim == 3 else v) for k, v in w["layers"].items()}
    return {"embed": _fp8(w["embed"], 1), "final_norm": w["final_norm"], "layers": layers}


@functools.lru_cache(maxsize=None)
def gap_fn(heads, kv_heads, head_dim, eps, theta, control: bool):
    """jit: (weights f32, tokens (S,), targets (S,)) -> per position, how far
    the target's logit lies below the reference's best.  With ``control``,
    the target at each position is what the fp8 pass puts first."""
    import jax
    import jax.numpy as jnp

    kw = dict(heads=heads, kv_heads=kv_heads, head_dim=head_dim, eps=eps, theta=theta)

    @jax.jit
    def fn(w, tokens, targets):
        with jax.default_matmul_precision("highest"):
            ref = forward(w, tokens, **kw)
            if control:
                targets = jnp.argmax(forward(to_fp8(w), tokens, fp8=True, **kw), axis=-1)
        best = jnp.max(ref, axis=-1)
        got = jnp.take_along_axis(ref, targets[:, None], axis=-1)[:, 0]
        return best - got

    return fn
