"""Seeded weights of a Granite-4.0-H hybrid (Mamba2 and attention layers,
each followed by a SwiGLU MLP), made on the device in one jitted program, in
the type they are served in.

The layout is the benchmark's own: the weights of each kind of layer are
stacked on a leading axis in layer order, under the layer's kind in
``layer_types`` (``mamba``, ``attention``), heads folded into the projection
widths, the MLP's input projection split into ``gate`` (the first half of Hugging
Face's ``input_linear``) and ``up``.  A deployment reshapes them into its
program's layout; the plain reference reads them as they are.  Both get
bit-identical values from the same seed.

Matrices, the embedding and the conv weights and biases are normal with
``init_std`` as std; norms are one; ``A_log``, ``dt_bias`` and ``D`` take the
Mamba2 initialisation: ``A = U[1, 16]``, ``dt = exp(U[log 1e-3, log 0.1])``
with ``dt_bias = softplus^-1(dt)``, and ``D = 1``.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple


class Dims(NamedTuple):
    mamba_layers: int
    attn_layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    m_heads: int
    m_head_dim: int
    d_state: int
    n_groups: int
    d_conv: int

    @classmethod
    def from_hf(cls, hf: dict) -> "Dims":
        kinds = hf["layer_types"]
        heads = hf["num_attention_heads"]
        return cls(kinds.count("mamba"), kinds.count("attention"), hf["hidden_size"],
                   heads, hf["num_key_value_heads"],
                   hf.get("head_dim") or hf["hidden_size"] // heads,
                   hf["shared_intermediate_size"], hf["vocab_size"],
                   hf["mamba_n_heads"], hf["mamba_d_head"], hf["mamba_d_state"],
                   hf["mamba_n_groups"], hf["mamba_d_conv"])

    @property
    def d_inner(self) -> int:
        return self.m_heads * self.m_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels of the conv: x, B and C."""
        return self.d_inner + 2 * self.n_groups * self.d_state


@functools.lru_cache(maxsize=None)
def maker(dims: Dims, std: float, dtype):
    import jax
    import jax.numpy as jnp

    d = dims
    Lm, La, D, F = d.mamba_layers, d.attn_layers, d.d_model, d.d_ff
    mlp = lambda L: {"gate": (L, D, F), "up": (L, D, F), "down": (L, F, D)}
    shapes = {
        "mamba": {"in_proj": (Lm, D, 2 * d.d_inner + 2 * d.n_groups * d.d_state + d.m_heads),
                  "conv_w": (Lm, d.d_conv, d.conv_dim), "conv_b": (Lm, d.conv_dim),
                  "out_proj": (Lm, d.d_inner, D), **mlp(Lm)},
        "attention": {"wq": (La, D, d.heads * d.head_dim),
                      "wk": (La, D, d.kv_heads * d.head_dim),
                      "wv": (La, D, d.kv_heads * d.head_dim),
                      "wo": (La, d.heads * d.head_dim, D), **mlp(La)},
    }
    normals = [(kind, name, s) for kind, group in shapes.items() for name, s in group.items()]

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(normals) + 4)
        normal = lambda k, s: (std * jax.random.normal(k, s, jnp.float32)).astype(dtype)
        w = {"embed": normal(keys[0], (d.vocab, D)),
             "final_norm": jnp.ones((D,), dtype), "mamba": {}, "attention": {}}
        for (kind, name, s), k in zip(normals, keys[4:]):
            w[kind][name] = normal(k, s)
        H = (Lm, d.m_heads)
        A = jax.random.uniform(keys[1], H, jnp.float32, 1.0, 16.0)
        dt = jnp.exp(jax.random.uniform(keys[2], H, jnp.float32,
                                        math.log(1e-3), math.log(0.1)))
        w["mamba"].update(
            A_log=jnp.log(A).astype(dtype),
            dt_bias=(dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),   # softplus^-1
            D=jnp.ones(H, dtype), norm=jnp.ones((Lm, d.d_inner), dtype),
            ln1=jnp.ones((Lm, D), dtype), ln2=jnp.ones((Lm, D), dtype))
        w["attention"].update(ln1=jnp.ones((La, D), dtype), ln2=jnp.ones((La, D), dtype))
        return w

    return make


def make_weights(hf: dict, key, dtype):
    """Weights for the Hugging Face config ``hf`` from ``key``."""
    return maker(Dims.from_hf(hf), float(hf["init_std"]), dtype)(key)
