"""Disaggregated serving of SmolLM-360M: one prefill pod and two decode pods
of ``DisaggregatedServer``, the KV cache handed over on the xdt medium.

The window drives ``DisaggregatedServer.submit`` (prefill, the handoff
workflow on the engine, the pull and the admit; it returns with the first
token on the host) and ``DisaggregatedServer.step`` (one decode step of each
busy pod).  After the window a sample of the finished requests, drawn from
the seed and holding the one with the most served tokens, is run through
the plain float32 reference over its prompt and served tokens, and gives
how far each served token's logit lies below the reference's best.  The
numbers compared are those the configuration's ``limits`` name: the widest
of these gaps, or their mean per served token.
"""
from __future__ import annotations

import gc
import sys
import time
import traceback
from pathlib import Path
from typing import List

import numpy as np

from bench.harness.flops import DecoderShape
from bench.harness.registry import load_module

_HERE = Path(__file__).resolve().parent
weights = load_module(_HERE / "llama_weights.py", "config")
reference = load_module(_HERE / "llama_reference.py", "config")


class Deployment:
    prefill_program = "jit_prefill"
    decode_program = "jit_decode"
    #: the check compares the fp8 control's greedy tokens, not the served ones
    control = False

    @staticmethod
    def controls() -> dict:
        """Each control for ``correct`` as a patch applied before set-up: the
        plain reference computed in float8 (weights and every matrix
        product's input), whose greedy token at each position of the same
        prompts and served tokens takes the served token's place."""
        def fp8_tokens(dep):
            dep.control = True

        return {"control_fp8": fp8_tokens}

    def __init__(self, conf: dict, ctx):
        self.conf, self.ctx = conf, ctx
        self.dep = conf["deployment"]
        self.shape = DecoderShape.from_hf(conf)
        self.errors: List[str] = []
        self.prefills: list = []          # (perf_counter, prompt tokens)
        self.decode_steps: list = []      # (perf_counter, [context per live slot])
        self.served: dict = {}            # rid -> (schedule index, prompt)
        self.finished: dict = {}          # schedule index -> (prompt, tokens)

    # ------------------------------------------------------------ set-up
    def _model_config(self):
        from repro.models.config import ModelConfig

        c, s = self.conf, self.shape
        return ModelConfig(
            name=c["name"], family="dense", n_layers=s.layers, d_model=s.d_model,
            n_heads=s.heads, n_kv_heads=s.kv_heads, d_ff=s.d_ff, vocab=s.vocab,
            head_dim=s.head_dim, rope_theta=c["rope_theta"], rms_eps=c["rms_norm_eps"],
            tie_embeddings=c["tie_word_embeddings"], dtype=self.dep["served_dtype"])

    def _program_params(self, key):
        """The program's parameter layout, in one jitted program."""
        import jax
        import jax.numpy as jnp

        s = self.shape
        w = weights.make_weights(self.conf, key, jnp.dtype(self.dep["served_dtype"]))
        lw = w["layers"]
        L, D, H, KV, hd = s.layers, s.d_model, s.heads, s.kv_heads, s.head_dim
        return {
            "embed": w["embed"], "final_norm": w["final_norm"],
            "blocks": {
                "attn": {"wq": lw["wq"].reshape(L, D, H, hd),
                         "wk": lw["wk"].reshape(L, D, KV, hd),
                         "wv": lw["wv"].reshape(L, D, KV, hd),
                         "wo": lw["wo"].reshape(L, H, hd, D)},
                "mlp": {"wi": lw["up"], "wg": lw["gate"], "wo": lw["down"]},
                "ln1": lw["ln1"], "ln2": lw["ln2"],
            },
        }

    def setup(self, schedule) -> None:
        import jax

        from repro.serving import DisaggregatedServer

        self.key = self.ctx.jax_key(1)
        params = jax.block_until_ready(jax.jit(self._program_params)(self.key))
        d = self.dep
        self.srv = DisaggregatedServer(
            self._model_config(), params, n_decode_pods=d["decode_pods"],
            max_batch=d["max_batch"], max_len=d["max_len"], backend=d["handoff"])
        del params
        rng = self.ctx.rng(2)
        V = self.shape.vocab
        self.prompts = [rng.integers(1, V, size=r.prompt_tokens).astype(np.int32)
                        for r in schedule.requests]
        # warm every prompt length the traffic sends (the program compiles one
        # prefill per length), the decode step, and the admit and token read
        # of every slot of every pod; one request per slot at a time, so that
        # no handoff parks with its cache behind a full batch
        lengths = schedule.lengths("prompt_tokens")
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

    # ------------------------------------------------------------ window
    def submit(self, spec):
        prompt = self.prompts[spec.index]
        t = time.perf_counter()
        try:
            with self.ctx.spans.span("bench.submit"):
                rid = self.srv.submit(prompt, max_new_tokens=spec.output_tokens)
        except Exception:
            self.errors.append(traceback.format_exc())
            return None
        self.prefills.append((t, len(prompt)))
        self.served[rid] = spec.index
        return rid

    def busy(self) -> bool:
        return any(s is not None for p in self.srv.decode_pods for s in p.slots)

    def step(self):
        t = time.perf_counter()
        for pod in self.srv.decode_pods:
            ctxs = [len(r.prompt) + len(r.generated) for r in pod.slots if r is not None]
            if ctxs:
                self.decode_steps.append((t, ctxs))
        with self.ctx.spans.span("bench.step"):
            self.srv.step()
        done = []
        for k, pod in enumerate(self.srv.decode_pods):
            ids = list(pod.completed)
            for rid in ids[self._seen[k]:]:
                if rid in self.served:
                    req = pod.completed[rid]
                    self.finished[self.served[rid]] = (req.prompt, list(req.generated))
                    done.append((rid, len(req.generated)))
            self._seen[k] = len(ids)
        return done

    def layer_data(self) -> dict:
        return {"shape": self.shape, "prefills": self.prefills,
                "decode_steps": self.decode_steps,
                "prefill_program": self.prefill_program,
                "decode_program": self.decode_program}

    # ------------------------------------------------------------ check
    def cache_bytes(self) -> int:
        """One handoff: K and V of every layer over max_len, and ``pos``."""
        return self.dep["max_len"] * self.shape.kv_bytes_per_position() + 4

    def check(self, window) -> List[tuple]:
        srv = self.srv
        st = srv.transfer.stats
        n = len(self.served) + self.warm_requests
        avg = st.bytes_moved / max(1, st.transfers)
        checks = [
            ("request_errors", len(self.errors), 0),
            ("missing_handoffs", abs(srv.handoffs - n) + abs(st.transfers - n), 0),
            ("handoff_bytes_short", abs(avg - self.cache_bytes()), 0),
            ("handoff_buffers_left", srv.transfer.registry.stats().bytes_in_use, 0),
        ]
        del self.srv, srv
        gc.collect()
        self.readings = self.gaps(self.sample(), control=self.control)
        widest, mean, tokens = self.readings
        print(f"bench: {tokens} {'fp8 control' if self.control else 'served'} tokens "
              f"compared: widest gap {widest}, mean gap {mean}", file=sys.stderr)
        # only the numbers that the configuration gives a limit are compared
        limits = self.conf["limits"]
        compared = [(n, v, limits[n]) for n, v in
                    (("max_logit_gap", widest), ("mean_logit_gap", mean))
                    if limits.get(n) is not None]
        return compared + [("compared_tokens_short", 0 if tokens > 0 else 1, 0)] + checks

    def sample(self) -> List[int]:
        """Schedule indices to compare: the finished request with the most
        served tokens, and others drawn from the seed."""
        done = sorted(self.finished)
        if not done:
            return []
        k = self.conf["limits"]["sample_requests"]
        longest = max(done, key=lambda i: (len(self.finished[i][1]), -i))
        rest = [i for i in done if i != longest]
        pick = self.ctx.rng(4).permutation(len(rest))[:max(0, k - 1)]
        return [longest] + [rest[j] for j in sorted(pick)]

    def gaps(self, indices: List[int], control: bool):
        """(widest gap, mean gap per token, tokens compared) over the
        requests ``indices``: how far each served token's logit lies below
        the float32 reference's best, or with ``control`` the token that the
        fp8 pass puts first at the same position."""
        import jax
        import jax.numpy as jnp

        c, s = self.conf, self.shape
        w = reference.to_f32(weights.make_weights(c, self.key,
                                                  jnp.dtype(self.dep["served_dtype"])))
        fn = reference.gap_fn(s.heads, s.kv_heads, s.head_dim,
                              float(c["rms_norm_eps"]), float(c["rope_theta"]), control)
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
            with jax.default_matmul_precision("highest"):
                g = np.asarray(fn(w, jnp.asarray(tokens), jnp.asarray(targets)))
            g = g[P - 1:P - 1 + G].astype(np.float64)
            widest = max(widest, float(g.max()))
            total += float(g.sum())
            count += G
        return widest, total / max(1, count), count
