"""Seeded weights of a Llama-style decoder, made on the device in one jitted
program, in the type they are served in.

The layout is the benchmark's own (per-layer matrices stacked on a leading
layer axis, heads folded into the projection width).  A deployment reshapes
them into its program's layout; the plain reference reads them as they are.
Both get bit-identical values from the same seed.
"""
from __future__ import annotations

import functools


@functools.lru_cache(maxsize=None)
def maker(layers, d_model, heads, kv_heads, head_dim, d_ff, vocab, std, dtype):
    import jax
    import jax.numpy as jnp

    L, D, F, V = layers, d_model, d_ff, vocab
    shapes = {
        "wq": (L, D, heads * head_dim), "wk": (L, D, kv_heads * head_dim),
        "wv": (L, D, kv_heads * head_dim), "wo": (L, heads * head_dim, D),
        "gate": (L, D, F), "up": (L, D, F), "down": (L, F, D),
    }

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(shapes) + 1)
        normal = lambda k, s: (std * jax.random.normal(k, s, jnp.float32)).astype(dtype)
        layer = {n: normal(k, s) for (n, s), k in zip(shapes.items(), keys[1:])}
        layer["ln1"] = jnp.ones((L, D), dtype)
        layer["ln2"] = jnp.ones((L, D), dtype)
        return {"embed": normal(keys[0], (V, D)),
                "final_norm": jnp.ones((D,), dtype), "layers": layer}

    return make


def make_weights(hf: dict, key, dtype):
    """Weights for the Hugging Face config ``hf`` from ``key``; normal with
    the config's ``initializer_range`` as std, norms at one."""
    heads = hf["num_attention_heads"]
    return maker(hf["num_hidden_layers"], hf["hidden_size"], heads,
                 hf["num_key_value_heads"],
                 hf.get("head_dim") or hf["hidden_size"] // heads,
                 hf["intermediate_size"], hf["vocab_size"],
                 float(hf["initializer_range"]), dtype)(key)
