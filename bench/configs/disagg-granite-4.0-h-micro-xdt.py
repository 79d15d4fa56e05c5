"""Disaggregated serving of granite-4.0-h-micro, a Mamba2/attention hybrid:
one prefill pod and two decode pods of ``DisaggregatedServer``, each
handoff carrying the SSM and conv states of the 36 Mamba2 layers beside the
KV cache of the 4 attention layers, on the xdt medium.

The window and the check are those of the SmolLM deployment
(``disagg-smollm-360m-xdt.py``), which this one extends: the window drives
``DisaggregatedServer.submit`` and ``.step``; after it, a sample of the
finished requests (the one with the most served tokens and others drawn
from the seed) is run through the plain float32 reference
(``granite_hybrid_reference.py``), and the widest gap of a served token's
logit below the reference's best is compared with ``max_logit_gap``.
What differs: the model and its weights, the handoff's size (states and KV,
computed from the shapes), set-up warming one prompt per prefill length
the program compiles (it pads prompts to whole SSD chunks), and a second
control, the decode pods admitting each cache with its states zeroed.
"""
from __future__ import annotations

from pathlib import Path
from typing import List

import numpy as np

from bench.harness.hybrid_flops import HybridShape
from bench.harness.registry import load_module

_HERE = Path(__file__).resolve().parent
weights = load_module(_HERE / "granite_hybrid_weights.py", "config")
reference = load_module(_HERE / "granite_hybrid_reference.py", "config")
_smollm = load_module(_HERE / "disagg-smollm-360m-xdt.py", "config")


class Deployment(_smollm.Deployment):
    #: the decode pods admit each handed-over cache with its SSM and conv
    #: states zeroed (the ``control_state_dropped`` control)
    drop_state = False

    @staticmethod
    def controls() -> dict:
        """Each control for ``correct`` as a patch applied before set-up:
        the plain reference computed in float8, whose greedy tokens take the
        served tokens' place; and the decode pods admitting the KV with the
        SSM and conv states zeroed, so that decoding forgets the prompt in
        every Mamba2 layer."""
        def fp8_tokens(dep):
            dep.control = True

        def state_dropped(dep):
            dep.drop_state = True

        return {"control_fp8": fp8_tokens, "control_state_dropped": state_dropped}

    def __init__(self, conf: dict, ctx):
        super().__init__(conf, ctx)
        self.shape = HybridShape.from_hf(conf)

    # ------------------------------------------------------------ set-up
    def _model_config(self):
        from repro.models.config import HybridConfig, ModelConfig, SSMConfig

        c, s = self.conf, self.shape
        if s.n_groups != 1 or s.d_inner != c["mamba_expand"] * s.d_model:
            raise ValueError("the program's Mamba2 has one group of B and C and "
                             "d_inner = mamba_expand * hidden_size")
        kinds = c["layer_types"]
        return ModelConfig(
            name=c["name"], family="hybrid", n_layers=len(kinds), d_model=s.d_model,
            n_heads=s.heads, n_kv_heads=s.kv_heads, d_ff=s.d_ff, vocab=s.vocab,
            head_dim=s.head_dim, rope=c["position_embedding_type"] != "nope",
            rope_theta=c["rope_theta"], attn_scale=c["attention_multiplier"],
            rms_eps=c["rms_norm_eps"], tie_embeddings=c["tie_word_embeddings"],
            embedding_multiplier=c["embedding_multiplier"],
            residual_multiplier=c["residual_multiplier"],
            logits_scaling=c["logits_scaling"], dtype=self.dep["served_dtype"],
            ssm=SSMConfig(d_state=s.d_state, version=2, expand=c["mamba_expand"],
                          conv_width=s.d_conv, head_dim=s.m_head_dim, chunk=s.chunk),
            hybrid=HybridConfig(attn_layers=tuple(
                i for i, k in enumerate(kinds) if k == "attention")))

    def _program_params(self, key):
        """The program's parameter layout, in one jitted program."""
        import jax.numpy as jnp

        s = self.shape
        w = weights.make_weights(self.conf, key, jnp.dtype(self.dep["served_dtype"]))
        m, a = w["mamba"], w["attention"]
        La, D, H, KV, hd = s.attn_layers, s.d_model, s.heads, s.kv_heads, s.head_dim
        mlp = lambda g: {"wi": g["up"], "wg": g["gate"], "wo": g["down"]}
        ssm = ("in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D", "norm", "out_proj")
        return {
            "embed": w["embed"], "final_norm": w["final_norm"],
            "blocks": {"ssm": {k: m[k] for k in ssm}, "ln": m["ln1"],
                       "mlp": mlp(m), "ln2": m["ln2"]},
            "attn_blocks": {
                "attn": {"wq": a["wq"].reshape(La, D, H, hd),
                         "wk": a["wk"].reshape(La, D, KV, hd),
                         "wv": a["wv"].reshape(La, D, KV, hd),
                         "wo": a["wo"].reshape(La, H, hd, D)},
                "mlp": mlp(a), "ln1": a["ln1"], "ln2": a["ln2"]},
        }

    def setup(self, schedule) -> None:
        import jax
        import jax.numpy as jnp

        from repro.serving import DisaggregatedServer

        self.key = self.ctx.jax_key(1)
        params = jax.block_until_ready(jax.jit(self._program_params)(self.key))
        d = self.dep
        self.srv = DisaggregatedServer(
            self._model_config(), params, n_decode_pods=d["decode_pods"],
            max_batch=d["max_batch"], max_len=d["max_len"], backend=d["handoff"])
        del params
        if self.drop_state:
            for pod in self.srv.decode_pods:
                def admit(req, cache, token, slot, admit=pod.admit):
                    zeroed = {k: jnp.zeros_like(cache[k]) for k in ("ssm", "conv")}
                    admit(req, {**cache, **zeroed}, token, slot)
                pod.admit = admit
        rng = self.ctx.rng(2)
        V = self.shape.vocab
        self.prompts = [rng.integers(1, V, size=r.prompt_tokens).astype(np.int32)
                        for r in schedule.requests]
        # warm one prompt of each length the program prefills at (it pads
        # prompts to whole SSD chunks), the decode step, and the admit and
        # token read of every slot of every pod; one request per slot at a
        # time, so that no handoff parks with its cache behind a full batch
        pad = self.srv.prefill_pod.padded_length
        by_padded = {pad(n): n for n in schedule.lengths("prompt_tokens")}
        lengths = sorted(by_padded.values())
        slots = d["decode_pods"] * d["max_batch"]
        warm = lengths + [lengths[0]] * max(0, slots - len(lengths))
        for k in range(0, len(warm), slots):
            for n in warm[k:k + slots]:
                self.srv.submit(rng.integers(1, V, size=n).astype(np.int32),
                                max_new_tokens=2)
            while self.busy():
                self.srv.step()
        self.warm_requests = len(warm)
        #: per pod, how many of its completed requests the window has seen
        self._seen = [len(p.completed) for p in self.srv.decode_pods]

    # ------------------------------------------------------------ check
    def cache_bytes(self) -> int:
        """One handoff: the states of every Mamba2 layer, K and V of every
        attention layer over max_len, and ``pos``."""
        return self.shape.cache_bytes(self.dep["max_len"])

    def gaps(self, indices: List[int], control: bool):
        """(widest gap, mean gap per token, tokens compared) over the
        requests ``indices``: how far each served token's logit lies below
        the float32 reference's best, or with ``control`` the token that the
        fp8 pass puts first at the same position."""
        import jax.numpy as jnp

        c = self.conf
        w = weights.make_weights(c, self.key, jnp.dtype(self.dep["served_dtype"]))
        fn = reference.gap_fn(tuple(reference.kwargs(c).items()),
                              float(c["logits_scaling"]), control)
        T = self.dep["max_len"]
        widest, total, count = 0.0, 0.0, 0
        for i in indices:
            prompt, gen = self.finished[i]
            full = np.concatenate([prompt, np.asarray(gen, np.int32)])
            P, G = len(prompt), len(gen)
            tokens = np.zeros(T, np.int32)
            tokens[:len(full) - 1] = full[:-1]
            targets = np.zeros(T, np.int32)
            targets[:len(full) - 1] = full[1:]
            g = np.asarray(fn(w, jnp.asarray(tokens), jnp.asarray(targets)))
            g = g[P - 1:P - 1 + G].astype(np.float64)
            widest = max(widest, float(g.max()))
            total += float(g.sum())
            count += G
        return widest, total / max(1, count), count
