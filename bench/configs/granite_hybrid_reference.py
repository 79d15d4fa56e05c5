"""Plain reference of a Granite-4.0-H hybrid (granite-4.0-h-micro is one):
the forward pass over a whole sequence in float32 ``jax.numpy`` at the
highest matmul precision, with no kernels, no cache and no batching.  It
imports nothing of the program under test and reads the weights as
``granite_hybrid_weights`` makes them, in the type they are served in; each
layer's weights are cast to float32 where that layer is computed, so that
the float32 copy of the whole model (12.8 GB at 3.2B parameters) is never
held at once.

Follows Hugging Face's ``GraniteMoeHybridForCausalLM`` with no experts:

- embedding: ``h = embed(ids) * embedding_multiplier``;
- each layer: ``h = h + r * mixer(rms(h))``, then ``h = h + r * mlp(rms(h))``
  with ``r = residual_multiplier``; ``mlp(x) = down(silu(gate(x)) * up(x))``
  (``gate`` is the first half of ``input_linear``);
- the mixer is Mamba2 or attention, as ``layer_types`` says:
  - Mamba2: ``[z, xBC, dt] = in_proj(x)``; ``xBC = silu(conv(xBC) + b)``, a
    causal depthwise conv of ``mamba_d_conv`` taps; ``[x, B, C] = xBC``;
    ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the recurrence
    over time, head by head, ``s_t = exp(dt_t A) s_{t-1} + dt_t x_t B_t^T``,
    ``y_t = s_t C_t + D x_t``; then ``out_proj(rms(y * silu(z)) * norm)``,
    the norm over all ``d_inner`` channels (one group);
  - attention: grouped-query attention without position embedding (NoPE),
    causal softmax of ``q k^T * attention_multiplier``;
- output: ``logits = rms(h) @ embed^T / logits_scaling`` (tied head).

Departures: none in the mathematics.  The recurrence runs step by step over
time (no chunking, no padding); positions after the sequence are computed
and ignored (the pass is causal).  The logits are computed in blocks of
positions, so that the whole (positions, vocabulary) array is never held.

The control of the comparison is the same pass computed in float8 (e4m3),
the precision step below the bfloat16 the deployment serves in: every
weight matrix rounded with a scale per output channel, and every matrix
product's input rounded with a scale per token.  The conv, the recurrence,
attention's own products and the softmax stay in float32.
"""
from __future__ import annotations

import functools

#: rows of the logits computed at once
LOGIT_BLOCK = 256
#: the weights that are matrix products' right-hand sides
MATRICES = ("in_proj", "out_proj", "gate", "up", "down", "wq", "wk", "wv", "wo")


def _rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * w


def _fp8(a, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    import jax.numpy as jnp

    amax = jnp.max(jnp.abs(a), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _layer_f32(lw, fp8):
    """One layer's weights in float32; with ``fp8`` each matrix (D_in,
    D_out) rounded with a scale per output channel."""
    import jax.numpy as jnp

    out = {k: v.astype(jnp.float32) for k, v in lw.items()}
    if fp8:
        out.update({k: _fp8(out[k], 0) for k in MATRICES if k in out})
    return out


def _runs(kinds):
    """Consecutive layers of one kind: [(kind, first index in its stack, count)]."""
    out, seen = [], {"mamba": 0, "attention": 0}
    for kind in kinds:
        if out and out[-1][0] == kind:
            out[-1] = (kind, out[-1][1], out[-1][2] + 1)
        else:
            out.append((kind, seen[kind], 1))
        seen[kind] += 1
    return out


def hidden(w, tokens, *, kinds, heads, kv_heads, head_dim, m_heads, m_head_dim,
           d_state, n_groups, eps, scale, emb_mult, res_mult, fp8=False):
    """The final normed hidden state (S, D) in float32 for ``tokens`` (S,)."""
    import jax
    import jax.numpy as jnp

    def mm(x, m):
        return (_fp8(x, -1) if fp8 else x) @ m

    S = tokens.shape[0]
    d_in = m_heads * m_head_dim
    gn = n_groups * d_state
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]

    def mlp(x, lw):
        h = _rms_norm(x, lw["ln2"], eps)
        return mm(jax.nn.silu(mm(h, lw["gate"])) * mm(h, lw["up"]), lw["down"])

    def mamba(x, lw):
        lw = _layer_f32(lw, fp8)
        proj = mm(_rms_norm(x, lw["ln1"], eps), lw["in_proj"])
        z, xbc, dt = jnp.split(proj, [d_in, 2 * d_in + 2 * gn], axis=-1)
        W = lw["conv_w"].shape[0]
        xp = jnp.pad(xbc, ((W - 1, 0), (0, 0)))
        xbc = jax.nn.silu(sum(xp[k:k + S] * lw["conv_w"][k] for k in range(W))
                          + lw["conv_b"])
        xs, B, C = jnp.split(xbc, [d_in, d_in + gn], axis=-1)
        dt = jax.nn.softplus(dt + lw["dt_bias"])                  # (S, H)
        A = -jnp.exp(lw["A_log"])                                 # (H,)
        x_h = xs.reshape(S, m_heads, m_head_dim)
        per = m_heads // n_groups                                 # heads of a group
        B = jnp.repeat(B.reshape(S, n_groups, d_state), per, axis=1)   # (S, H, N)
        C = jnp.repeat(C.reshape(S, n_groups, d_state), per, axis=1)

        def step(s, t):
            x_t, B_t, C_t, dt_t = t
            s = (s * jnp.exp(dt_t * A)[:, None, None]
                 + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
            return s, (s * C_t[:, None, :]).sum(-1)

        s0 = jnp.zeros((m_heads, m_head_dim, d_state), jnp.float32)
        _, y = jax.lax.scan(step, s0, (x_h, B, C, dt))
        y = (y + x_h * lw["D"][:, None]).reshape(S, d_in)
        y = _rms_norm(y * jax.nn.silu(z), lw["norm"], eps)
        x = x + res_mult * mm(y, lw["out_proj"])
        return x + res_mult * mlp(x, lw), None

    def attention(x, lw):
        lw = _layer_f32(lw, fp8)
        h = _rms_norm(x, lw["ln1"], eps)
        q = mm(h, lw["wq"]).reshape(S, heads, head_dim)
        k = jnp.repeat(mm(h, lw["wk"]).reshape(S, kv_heads, head_dim), heads // kv_heads, 1)
        v = jnp.repeat(mm(h, lw["wv"]).reshape(S, kv_heads, head_dim), heads // kv_heads, 1)
        s = jnp.einsum("qhd,khd->hqk", q, k) * scale
        a = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", a, v).reshape(S, heads * head_dim)
        x = x + res_mult * mm(o, lw["wo"])
        return x + res_mult * mlp(x, lw), None

    x = w["embed"][tokens].astype(jnp.float32) * emb_mult
    for kind, first, n in _runs(kinds):
        stack = jax.tree.map(lambda a: a[first:first + n], w[kind])
        x, _ = jax.lax.scan(mamba if kind == "mamba" else attention, x, stack)
    return _rms_norm(x, w["final_norm"].astype(jnp.float32), eps)


def head(w, fp8=False):
    """The output head (the tied embedding) in float32, (V, D); with
    ``fp8`` rounded per row."""
    import jax.numpy as jnp

    e = w["embed"].astype(jnp.float32)
    return _fp8(e, 1) if fp8 else e


def kwargs(hf: dict) -> dict:
    """The static arguments of :func:`hidden` for the Hugging Face config."""
    heads = hf["num_attention_heads"]
    return dict(kinds=tuple(hf["layer_types"]), heads=heads,
                kv_heads=hf["num_key_value_heads"],
                head_dim=hf.get("head_dim") or hf["hidden_size"] // heads,
                m_heads=hf["mamba_n_heads"], m_head_dim=hf["mamba_d_head"],
                d_state=hf["mamba_d_state"], n_groups=hf["mamba_n_groups"],
                eps=float(hf["rms_norm_eps"]), scale=float(hf["attention_multiplier"]),
                emb_mult=float(hf["embedding_multiplier"]),
                res_mult=float(hf["residual_multiplier"]))


def logits(w, tokens, hf: dict, fp8=False):
    """Logits (S, V) in float32 for ``tokens`` (S,): the whole pass, for the
    tests at small sizes."""
    import jax

    with jax.default_matmul_precision("highest"):
        x = hidden(w, tokens, fp8=fp8, **kwargs(hf))
        m = (lambda a: a) if not fp8 else (lambda a: _fp8(a, -1))
        return m(x) @ head(w, fp8).T / float(hf["logits_scaling"])


def _by_blocks(f, *rows):
    """``f`` over LOGIT_BLOCK rows at a time of each of ``rows``, results
    joined."""
    import math

    import jax

    blk = math.gcd(rows[0].shape[0], LOGIT_BLOCK)
    split = tuple(r.reshape(-1, blk, *r.shape[1:]) for r in rows)
    return jax.lax.map(lambda a: f(*a), split).reshape(-1)


@functools.lru_cache(maxsize=None)
def gap_fn(kw_items: tuple, logits_scaling: float, control: bool):
    """jit: (weights as made, tokens (S,), targets (S,)) -> per position, how
    far the target's logit lies below the reference's best.  With
    ``control``, the target at each position is what the fp8 pass puts
    first.  ``kw_items`` is ``tuple(kwargs(hf).items())``."""
    import jax
    import jax.numpy as jnp

    kw = dict(kw_items)

    @jax.jit
    def fn(w, tokens, targets):
        with jax.default_matmul_precision("highest"):
            if control:
                e8 = head(w, fp8=True)
                targets = _by_blocks(lambda r: jnp.argmax(_fp8(r, -1) @ e8.T, axis=-1),
                                     hidden(w, tokens, fp8=True, **kw))
            e = head(w)

            def gap(r, t):
                l = r @ e.T / logits_scaling
                return jnp.max(l, axis=-1) - jnp.take_along_axis(l, t[:, None], axis=-1)[:, 0]

            return _by_blocks(gap, hidden(w, tokens, **kw), targets)

    return fn
