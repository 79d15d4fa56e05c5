"""Serving: continuous batching engine + disaggregated XDT handoff."""
import jax
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models import init_params, make_decode_fn, make_prefill_fn
from repro.serving import DisaggregatedServer, ServingEngine

import jax.numpy as jnp


@pytest.fixture(scope="module")
def setup():
    cfg = smoke_config("smollm_360m")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _greedy_reference(cfg, params, prompt, n_new, max_len=32):
    """Sequential single-request greedy decode (no batching engine)."""
    prefill = make_prefill_fn(cfg, None, remat="none", pad_to=max_len)
    decode = make_decode_fn(cfg, None)
    logits, cache = prefill(params, {"tokens": jnp.asarray(prompt)[None]})
    toks = [int(jnp.argmax(logits[0]))]
    for _ in range(n_new - 1):
        logits, cache = decode(params, cache,
                               jnp.asarray([[toks[-1]]], jnp.int32))
        toks.append(int(jnp.argmax(logits[0])))
    return toks


def test_engine_matches_sequential_reference(setup):
    cfg, params = setup
    eng = ServingEngine(cfg, params, max_batch=2, max_len=32)
    prompt = np.arange(1, 6)
    rid = eng.submit(prompt, max_new_tokens=6)
    done = eng.run_until_drained()
    assert done[rid].generated == _greedy_reference(cfg, params, prompt, 6)


def test_continuous_batching_ragged(setup):
    """Requests of different lengths batched together stay exact."""
    cfg, params = setup
    eng = ServingEngine(cfg, params, max_batch=3, max_len=32)
    prompts = [np.arange(1, 4), np.arange(2, 10), np.arange(1, 7)]
    rids = [eng.submit(p, max_new_tokens=5) for p in prompts]
    done = eng.run_until_drained()
    for rid, p in zip(rids, prompts):
        assert done[rid].generated == _greedy_reference(cfg, params, p, 5)


def test_slot_reuse(setup):
    """More requests than slots: slots are recycled, everyone completes."""
    cfg, params = setup
    eng = ServingEngine(cfg, params, max_batch=2, max_len=32)
    rids = [eng.submit(np.arange(1, 5) + i, max_new_tokens=4) for i in range(5)]
    done = eng.run_until_drained()
    assert set(done) == set(rids)


def test_disagg_xdt_equals_staged(setup):
    """The XDT handoff and the through-storage handoff produce bit-identical
    generations — only latency/cost differ (paper's API-preserving claim)."""
    cfg, params = setup
    outs = {}
    for backend in ("xdt", "staged"):
        srv = DisaggregatedServer(cfg, params, n_decode_pods=2, max_batch=2,
                                  max_len=32, backend=backend)
        rids = [srv.submit(np.arange(1, 5) + i, max_new_tokens=5) for i in range(4)]
        done = srv.run_until_drained()
        outs[backend] = {r: done[r].generated for r in rids}
    assert outs["xdt"] == outs["staged"]


def test_disagg_matches_single_pod(setup):
    cfg, params = setup
    srv = DisaggregatedServer(cfg, params, n_decode_pods=2, max_batch=2,
                              max_len=32, backend="xdt")
    prompt = np.arange(1, 6)
    rid = srv.submit(prompt, max_new_tokens=6)
    done = srv.run_until_drained()
    assert done[rid].generated == _greedy_reference(cfg, params, prompt, 6)


def test_disagg_placement_spreads_load(setup):
    """The control plane steers consecutive handoffs to different decode
    pods (least-loaded policy) — placement decided before data moves."""
    cfg, params = setup
    srv = DisaggregatedServer(cfg, params, n_decode_pods=2, max_batch=4,
                              max_len=32, backend="xdt")
    for i in range(4):
        srv.submit(np.arange(1, 4) + i, max_new_tokens=3)
    pods = set(srv.pod_of_request.values())
    assert pods == {0, 1}


def test_disagg_handoff_report(setup):
    cfg, params = setup
    srv = DisaggregatedServer(cfg, params, n_decode_pods=1, max_batch=2,
                              max_len=32, backend="xdt")
    srv.submit(np.arange(1, 5), max_new_tokens=3)
    srv.run_until_drained()
    rep = srv.handoff_report()
    assert rep["handoffs"] == 1
    assert rep["avg_cache_bytes"] > 0
    # XDT handoff beats both storage baselines for the same cache size
    assert rep["modeled_latency_s_if_xdt"] < rep["modeled_latency_s_if_s3"]
    assert rep["modeled_latency_s_if_xdt"] <= rep["modeled_latency_s_if_elasticache"]


def test_disagg_ssm_arch():
    """The handoff also carries SSM states (falcon-mamba family)."""
    cfg = smoke_config("falcon_mamba_7b")
    params = init_params(cfg, jax.random.PRNGKey(1))
    srv = DisaggregatedServer(cfg, params, n_decode_pods=2, max_batch=2,
                              max_len=24, backend="xdt")
    prompt = np.arange(1, 6)
    rid = srv.submit(prompt, max_new_tokens=4)
    done = srv.run_until_drained()
    assert done[rid].generated == _greedy_reference(cfg, params, prompt, 4,
                                                    max_len=24)


def _failing_prefill(*_args, **_kw):
    raise RuntimeError("prefill died")


def test_disagg_surfaces_a_failed_prefill(setup, monkeypatch):
    """A handler error ends the handoff's workflow request as failed; the
    server raises it instead of reporting a request that never ran."""
    cfg, params = setup
    srv = DisaggregatedServer(cfg, params, n_decode_pods=1, max_batch=2,
                              max_len=32, backend="xdt")
    monkeypatch.setattr(srv.prefill_pod, "prefill_request", _failing_prefill)
    with pytest.raises(RuntimeError, match="prefill died"):
        srv.submit(np.arange(1, 5), max_new_tokens=3)
    assert srv.handoffs == 0


@pytest.mark.parametrize("fail_prefill,rc", [(False, 0), (True, 1)])
def test_serve_launcher_exit_code(fail_prefill, rc, monkeypatch, tmp_path, capsys):
    """``launch.serve --disagg`` exits 0 when every request completes and
    non-zero when prefill fails."""
    from repro.launch import serve

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    if fail_prefill:
        monkeypatch.setattr(ServingEngine, "prefill_request", _failing_prefill)
    assert serve.main(["--arch", "smollm_360m", "--smoke", "--disagg",
                       "--requests", "3", "--new-tokens", "2",
                       "--prompt-len", "6", "--max-len", "32"]) == rc
    out, err = capsys.readouterr()
    if fail_prefill:
        assert "FAILED" in err and "prefill died" in err
    else:
        assert "disagg[xdt]: 3 requests, 3 handoffs" in out
