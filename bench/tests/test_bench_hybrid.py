"""granite-4.0-h-micro, a Mamba2/attention hybrid, served disaggregated: the
published configuration, the program against the plain float32 reference
through ``DisaggregatedServer`` on xdt, the padded prefill, the handoff's
bytes, the per-layer readers, and ``disagg-hybrid-chat`` at a tiny size
with its controls.  All on the CPU, at small sizes, with seeded weights."""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness.flops import roofline_seconds
from bench.harness.hybrid_flops import (HybridShape, decode_step_bytes, decode_step_flops,
                                        prefill_flops)
from bench.harness.peaks import PEAKS
from bench.harness.registry import ROOT, Benchmark, load_module
from bench.harness.runner import Run, run_cell
from bench.harness.trace import Reduced
from repro.configs import get_config, smoke_config
from repro.models import cache_shapes, init_params, make_decode_fn, make_prefill_fn, param_shapes
from repro.models.ssm import ssd_mix
from repro.serving import DisaggregatedServer

from .tiny import CHAT_MIX

CELL = "disagg-hybrid-chat"
CONFIG = "disagg-granite-4.0-h-micro-xdt"
CONF = json.loads((ROOT / "bench" / "configs" / f"{CONFIG}.json").read_text())
reference = load_module(ROOT / "bench" / "configs" / "granite_hybrid_reference.py", "config")
weights = load_module(ROOT / "bench" / "configs" / "granite_hybrid_weights.py", "config")

# Mamba2 spans before, between and after two attention layers.  Weights at
# std 0.1 and the embedding at x2 (12 published) so that the layers and not
# the tied embedding decide the next token: at this width, with x12, every
# served token repeats its input with a wide margin and neither control can
# move one (both read a widest gap of 0 over seeds 2**31+5, 2**31+7, 1, 2).
HYBRID_TINY = {
    "layer_types": ["mamba", "mamba", "attention", "mamba", "attention", "mamba", "mamba"],
    "num_hidden_layers": 7, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "attention_multiplier": 0.0625,
    "intermediate_size": 128, "shared_intermediate_size": 128, "vocab_size": 2048,
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16, "mamba_chunk_size": 8,
    "init_std": 0.1, "embedding_multiplier": 2.0,
    "deployment": {"prefill_pods": 1, "decode_pods": 2, "max_batch": 2, "max_len": 64,
                   "handoff": "xdt", "served_dtype": "bfloat16", "chips": 1},
    # at this size bfloat16 serving read widest gaps of 0 to 0.0018, the fp8
    # control 0.0152 to 0.0246 and the dropped states 0.23 to 0.43 over
    # seeds 2**31+5, 2**31+7, 1, 2 on the CPU: 0.006 is the lower reading
    # times (upper / lower) ** 0.6, as the chip's limit is set
    "limits": {"max_logit_gap": 0.006, "sample_requests": 4},
}
TINY_KW = {"conf_override": HYBRID_TINY, "mix_override": CHAT_MIX}


def _run(seed=2**31 + 5, seconds=1.5, trace=False, **kw):
    return run_cell(CELL, seed, seconds, trace, t_start=time.perf_counter(),
                    require_chip=False, **{**TINY_KW, **kw})


def _deployment(conf):
    return Benchmark().deployment_module(CONFIG).Deployment(conf, None)


def _tiny_conf(served_dtype):
    conf = {**CONF, **HYBRID_TINY}
    conf["deployment"] = dict(conf["deployment"], served_dtype=served_dtype)
    return conf


def _failed(res):
    return {k for k, v in res["checks"].items() if v["value"] > v["limit"]}


# ---------------------------------------------------------------- the config


def test_published_config_is_pinned():
    cfg = get_config("granite-4.0-h-micro")
    kinds = ["attention" if i in (5, 15, 25, 35) else "mamba" for i in range(40)]
    assert [k for k, *_ in cfg.hybrid.segments(40)] == [
        "mamba", "attn", "mamba", "attn", "mamba", "attn", "mamba", "attn", "mamba"]
    assert CONF["layer_types"] == kinds
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.d_ff,
            cfg.vocab) == (40, 2048, 32, 8, 64, 8192, 100352)
    s = cfg.ssm
    assert (s.version, s.expand, s.d_state, s.head_dim, s.conv_width, s.chunk) == (
        2, 2, 128, 64, 4, 256)
    assert not cfg.rope and cfg.attn_scale == 0.015625 and cfg.tie_embeddings
    assert (cfg.embedding_multiplier, cfg.residual_multiplier, cfg.logits_scaling,
            cfg.rms_eps) == (12, 0.22, 8, 1e-5)
    n = sum(int(np.prod(shape)) for shape, _ in jax.tree.leaves(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple)))
    assert cfg.n_params() == n == HybridShape.from_hf(CONF).params() == 3_191_396_096


def test_deployment_builds_the_published_model():
    import dataclasses

    cfg = _deployment(CONF)._model_config()
    want = get_config("granite-4.0-h-micro")
    assert dataclasses.replace(cfg, name=want.name, subquadratic=True) == want


# ---------------------------------------------------------------- the program


def _record_logits(srv, cfg):
    """Wrap the server's prefill and decode programs so that each request's
    logits at every served position are kept, in order: the prefill's, then
    one per decode step.  Tokens are the greedy choice of those logits, as
    the program's own decode takes them."""
    got = {}
    firsts = []
    prefill = srv.prefill_pod.prefill

    def recorded_prefill(params, batch):
        logits, cache = prefill(params, batch)
        firsts.append(np.asarray(logits[0], np.float32))
        return logits, cache

    srv.prefill_pod.prefill = recorded_prefill
    step = jax.jit(make_decode_fn(cfg, None))
    for pod in srv.decode_pods:
        def decode(params, cache, tokens, pod=pod):
            logits, cache = step(params, cache, tokens)
            for slot, req in enumerate(pod.slots):
                if req is not None:
                    got.setdefault(req.request_id, []).append(
                        np.asarray(logits[slot], np.float32))
            return jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32), cache

        pod.decode = decode
    return firsts, got


@pytest.mark.parametrize("lengths", [(8, 13), (16, 3), (21, 32)])
def test_served_logits_match_the_reference(lengths):
    """Prefill on the prefill pod, the handoff on xdt, decode on the decode
    pods: at every served position the program's logits equal the
    reference's full-sequence ones, for prompts that are and are not a whole
    number of SSD chunks (8).  Served in float32, so the tolerance is only
    the float32 rounding of two orders of summation (the SSD in chunks
    against the step-by-step recurrence; under 2e-7 here): 2e-5 on logits
    whose spread is about 0.1.  RoPE left on moves them by 0.006, the
    softmax scale left at head_dim ** -0.5 by 0.05, a multiplier left at
    one by 0.2 or more."""
    conf = _tiny_conf("float32")
    dep = _deployment(conf)
    cfg = dep._model_config()
    key = jax.random.PRNGKey(3)
    srv = DisaggregatedServer(cfg, jax.jit(dep._program_params)(key), n_decode_pods=2,
                              max_batch=2, max_len=64, backend="xdt")
    firsts, got = _record_logits(srv, cfg)
    rng = np.random.default_rng(len(lengths) * 100 + lengths[0])
    prompts = [rng.integers(1, cfg.vocab, size=n).astype(np.int32) for n in lengths]
    rids = [srv.submit(p, max_new_tokens=6) for p in prompts]
    done = srv.run_until_drained()
    w = weights.make_weights(conf, key, jnp.float32)
    for k, (rid, prompt) in enumerate(zip(rids, prompts)):
        gen = done[rid].generated
        assert len(gen) == 6
        full = np.concatenate([prompt, np.asarray(gen, np.int32)])
        ref = np.asarray(reference.logits(w, jnp.asarray(full[:-1]), conf))
        served = np.stack([firsts[k]] + got[rid])
        P = len(prompt)
        np.testing.assert_allclose(served, ref[P - 1:P - 1 + len(gen)], atol=2e-5, rtol=0)


@pytest.mark.parametrize("n", [5, 13, 16])
def test_padded_prefill_holds_the_unpadded_state(n):
    """A prompt padded to whole chunks, with its real length passed, leaves
    the conv and SSM states, the KV of its real positions, ``pos`` and the
    first logits of the prompt prefilled as it is."""
    cfg = smoke_config("granite-4.0-h-micro")
    params = init_params(cfg, jax.random.PRNGKey(4))
    prefill = make_prefill_fn(cfg, None, remat="none", pad_to=32)
    prompt = (np.arange(1, n + 1) * 5) % cfg.vocab
    logits, cache = prefill(params, {"tokens": jnp.asarray(prompt[None])})
    tokens = np.zeros((1, 24), np.int32)
    tokens[0, :n] = prompt
    p_logits, p_cache = prefill(params, {"tokens": jnp.asarray(tokens),
                                         "length": jnp.asarray([n], jnp.int32)})
    f32 = lambda a: np.asarray(a, np.float32)
    for key in ("conv", "ssm"):
        np.testing.assert_allclose(f32(p_cache[key]), f32(cache[key]), atol=1e-6, rtol=1e-6)
    for key in ("k", "v"):
        np.testing.assert_allclose(f32(p_cache[key][:, :, :n]), f32(cache[key][:, :, :n]),
                                   atol=1e-6, rtol=1e-6)
    assert int(p_cache["pos"][0]) == int(cache["pos"][0]) == n
    np.testing.assert_allclose(f32(p_logits), f32(logits), atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("S", [13, 16, 5])
def test_ssd_mix_over_a_partial_chunk_is_the_recurrence(S):
    """``ssd_mix`` at chunk 8 over sequences that are and are not whole
    chunks gives the step-by-step recurrence's outputs and final state."""
    rng = np.random.default_rng(S)
    B, H, P, N = 2, 3, 4, 5
    x = rng.standard_normal((B, S, H, P)).astype(np.float32)
    dt = rng.uniform(0.01, 0.5, (B, S, H)).astype(np.float32)
    Bm = rng.standard_normal((B, S, N)).astype(np.float32)
    Cm = rng.standard_normal((B, S, N)).astype(np.float32)
    A_log = np.log(rng.uniform(1, 4, H)).astype(np.float32)
    D = rng.standard_normal(H).astype(np.float32)
    y, h = ssd_mix(*(jnp.asarray(a) for a in (x, dt, Bm, Cm, A_log, D)), chunk=8)
    s = np.zeros((B, H, P, N))
    ys = []
    for t in range(S):
        a = np.exp(-dt[:, t] * np.exp(A_log))                        # (B, H)
        s = s * a[..., None, None] + np.einsum("bhp,bn->bhpn", dt[:, t, :, None] * x[:, t], Bm[:, t])
        ys.append(np.einsum("bhpn,bn->bhp", s, Cm[:, t]) + x[:, t] * D[:, None])
    np.testing.assert_allclose(np.asarray(y), np.stack(ys, 1), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h), s, atol=1e-4, rtol=1e-4)


def test_handoff_moves_states_and_kv():
    """Each handoff moves the whole cache, its bytes from the shapes: at the
    published size 93,214,724 B, 81% of it state that does not grow with
    the context; at the tiny size what the server's transfers counted."""
    full = HybridShape.from_hf(CONF)
    assert full.ssm_state_bytes() == 75_497_472 and full.conv_state_bytes() == 940_032
    assert full.cache_bytes(2048) == 93_214_724
    leaves = cache_shapes(get_config("granite-4.0-h-micro"), 1, 2048)
    assert sum(int(np.prod(s)) * jnp.dtype(d).itemsize for s, _, d in leaves.values()) \
        == 93_214_724
    res = {}
    _run(inspect=lambda dep, r: res.update(checks=r["checks"], bytes=dep.cache_bytes()))
    assert res["checks"]["handoff_bytes_short"]["value"] == 0
    assert res["bytes"] == HybridShape.from_hf({**CONF, **HYBRID_TINY}).cache_bytes(64)


# ---------------------------------------------------------------- the cell


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("control", ["control_fp8", "control_state_dropped"])
def test_control_reads_not_correct(control):
    patch = Benchmark().deployment_module(CONFIG).Deployment.controls()[control]
    res = _run(patch=patch)
    assert not res["correct"] and "max_logit_gap" in _failed(res)


def test_control_script_reads_every_control_not_correct():
    from bench.control import readings

    controls = Benchmark().deployment_module(CONFIG).Deployment.controls()
    got = {kind: readings(CELL, 2**31 + 7, 1.5, kind, patch, require_chip=False, **TINY_KW)
           for kind, patch in [("program", None), *controls.items()]}
    assert got["program"]["correct"] and not got["program"]["failed_checks"]
    for kind, line in got.items():
        if kind != "program":
            assert not line["correct"] and line["failed_checks"] == ["max_logit_gap"], kind


def test_tiny_traced_run_reports_the_span_metrics():
    """The serve metrics that read spans, and the pad share from the
    prefill counts: the tiny mix's prompts (8 to 32 tokens) are padded to
    whole chunks of 8."""
    res = _run(trace=True, seconds=1.0)
    assert res["correct"] is True
    names = {"handoff_ms.serve", "token_sync_ms.serve", "decode_round_ms.serve",
             "prefill_pad_share.hybrid"}
    assert names <= set(res["metrics"])
    for n in names:
        assert res["metrics"][n]["value"] > 0
    assert res["metrics"]["prefill_pad_share.hybrid"]["value"] < 100


# ---------------------------------------------------------------- the readers


def _traced(layer):
    trace = Reduced(window_s=3.0, busy_s=1.0, devices=1,
                    programs={"jit_prefill": (2, 0.1), "jit_decode": (4, 0.08)},
                    device_ops=[], idle_gaps=[])
    return Run(cell={}, seconds=3.0, setup_s=0.0, window=None, spans=None, layer=layer,
               peaks=PEAKS["TPU v5 lite"], trace=trace, trace_bounds=(0.0, 3.0))


def test_flops_readers_on_known_numbers():
    s = HybridShape.from_hf(CONF)
    peaks = PEAKS["TPU v5 lite"]
    steps = [[600] * 16, [300] * 8]
    layer = {"shape": s, "prefills": [(1.0, 512), (2.0, 1024), (5.0, 64)],
             "decode_steps": [(1.0, steps[0]), (2.0, steps[1])],
             "prefill_program": "jit_prefill", "decode_program": "jit_decode"}
    run = _traced(layer)
    bench = Benchmark()
    want_prefill = 100 * (prefill_flops(s, 512) + prefill_flops(s, 1024)) / (
        0.1 * peaks["bf16_flops_per_s"])
    assert bench.reader("prefill_mfu.hybrid").read(run) == pytest.approx(want_prefill)
    least = sum(roofline_seconds(decode_step_flops(s, c), decode_step_bytes(s, c), peaks)
                for c in steps) / 2
    assert bench.reader("decode_step_roofline.hybrid").read(run) == pytest.approx(
        100 * least * 4 / 0.08)
    # the issue's arithmetic: 8.9 GB a step at 16 slots of 600 positions
    assert decode_step_bytes(s, steps[0]) == pytest.approx(8.91e9, rel=0.01)


@pytest.mark.parametrize("metric", ["prefill_mfu.hybrid", "decode_step_roofline.hybrid"])
def test_flops_readers_read_nothing_of_a_dense_model(metric):
    from bench.harness.flops import DecoderShape

    smollm = json.loads((ROOT / "bench" / "configs" / "disagg-smollm-360m-xdt.json").read_text())
    layer = {"shape": DecoderShape.from_hf(smollm), "prefills": [(1.0, 512)],
             "decode_steps": [(1.0, [600])], "prefill_program": "jit_prefill",
             "decode_program": "jit_decode"}
    assert Benchmark().reader(metric).read(_traced(layer)) is None
    assert Benchmark().reader(metric).read(_traced({})) is None


def test_pad_share_reader(monkeypatch):
    from repro.core import tracing
    from repro.core.tracing import Count

    recs = [Count("prefill.tokens", 1.0, 300, None, 1),
            Count("prefill.pad_tokens", 1.0, 212, None, 1),
            Count("prefill.tokens", 2.0, 100, None, 2),
            Count("prefill.pad_tokens", 2.0, 28, None, 2),
            Count("prefill.tokens", 9.0, 7, None, 3)]
    monkeypatch.setattr(tracing, "records", lambda t0, t1: [r for r in recs if t0 <= r.t <= t1])
    reader = Benchmark().reader("prefill_pad_share.hybrid")
    assert reader.read(_traced({})) == pytest.approx(100 * 240 / 400)
    monkeypatch.setattr(tracing, "records", lambda t0, t1: [])
    assert reader.read(_traced({})) is None
