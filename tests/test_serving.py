"""Serving: continuous batching engine + disaggregated XDT handoff."""
import re

import jax
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core import tracing
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


def _held(srv):
    """request id -> (pod, slot, request) of every busy decode slot."""
    return {r.request_id: (k, j, r) for k, pod in enumerate(srv.decode_pods)
            for j, r in enumerate(pod.slots) if r is not None}


def test_disagg_pods_finishing_at_different_rounds(setup):
    """Requests of different lengths on both pods: each round reads every
    pod's tokens at once, each slot is freed in the round that read its
    request's last token, a parked request takes it in that round, and the
    generations are those of one pod and of the sequential reference."""
    cfg, params = setup
    srv = DisaggregatedServer(cfg, params, n_decode_pods=2, max_batch=2,
                              max_len=32, backend="xdt")
    prompts = [np.arange(1, 5 + i) + i for i in range(5)]
    news = [3, 7, 5, 9, 4]
    rids = [srv.submit(p, max_new_tokens=n) for p, n in zip(prompts[:4], news)]
    assert {srv.pod_of_request[r] for r in rids} == {0, 1}
    late = srv.submit(prompts[4], max_new_tokens=news[4])   # every slot busy
    assert late not in srv.pod_of_request
    rids.append(late)
    before, freed_rounds, reused = _held(srv), set(), None
    rounds = 0
    while before:
        lengths = {rid: len(r.generated) for rid, (_, _, r) in before.items()}
        srv.step()
        rounds += 1
        after = _held(srv)
        freed = set()
        for rid, (k, j, req) in before.items():
            assert len(req.generated) == lengths[rid] + 1
            if len(req.generated) == req.max_new_tokens:
                assert req.done and rid in srv.decode_pods[k].completed
                assert rid not in after
                freed.add((k, j))
            else:
                assert not req.done and after[rid][:2] == (k, j)
        for rid in set(after) - set(before):
            assert rid == late and after[rid][:2] in freed
            reused = after[rid][:2]
        if freed:
            freed_rounds.add(rounds)
        before = after
    assert reused is not None and len(freed_rounds) >= 3
    done = {rid: req for pod in srv.decode_pods
            for rid, req in pod.completed.items()}
    eng = ServingEngine(cfg, params, max_batch=2, max_len=32)
    single = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, news)]
    alone = eng.run_until_drained()
    for rid, sid, p, n in zip(rids, single, prompts, news):
        assert len(done[rid].generated) == n
        assert done[rid].generated == alone[sid].generated \
            == _greedy_reference(cfg, params, p, n)


def test_engine_step_reads_its_tokens_once(setup, tmp_path):
    """Under a profiler session a step with live slots makes one
    device-to-host read, however many slots it steps; one with none, none."""
    cfg, params = setup
    eng = ServingEngine(cfg, params, max_batch=3, max_len=32)
    for i, n in enumerate([3, 6, 4]):
        eng.submit(np.arange(1, 5) + i, max_new_tokens=n)
    eng.step()                          # prefills read their first tokens here
    tracing.clear()
    live, reads = [], []
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(5):
            live.append(sum(r is not None for r in eng.slots))
            t0 = tracing._now()
            eng.step()
            reads.append(sum(c.n for c in tracing.records(t0, tracing._now())
                             if isinstance(c, tracing.Count) and c.name == "host.syncs"))
    tracing.clear()
    assert live == [3, 2, 1, 1, 0]
    assert reads == [1, 1, 1, 1, 0]


def _compiled_decode(eng):
    return eng.decode.lower(eng.params, eng.cache, eng.last_tokens).compile()


@pytest.mark.parametrize("arch", ["smollm_360m", "granite-4.0-h-micro"])
def test_engine_decode_aliases_its_whole_cache(arch):
    """The engine's decode program takes its cache donated: every byte of
    the cache it is given is the cache it returns."""
    cfg = smoke_config(arch)
    eng = ServingEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)), max_batch=2, max_len=32)
    nbytes = sum(x.nbytes for x in jax.tree.leaves(eng.cache))
    assert _compiled_decode(eng).memory_analysis().alias_size_in_bytes == nbytes


def test_engine_decode_copies_no_cache_leaf(setup):
    """No op of the optimised decode program copies a whole cache leaf."""
    cfg, params = setup
    eng = ServingEngine(cfg, params, max_batch=2, max_len=32)
    hlo_types = {jnp.dtype(jnp.bfloat16): "bf16", jnp.dtype(jnp.float32): "f32"}
    leaves = {f"{hlo_types[x.dtype]}[{','.join(map(str, x.shape))}]"
              for x in jax.tree.leaves(eng.cache) if x.ndim > 1}
    copies = re.findall(r"= (\w+\[[\d,]*\])\{[^}]*\} copy\(", _compiled_decode(eng).as_text())
    assert leaves and not leaves & set(copies)


def test_engine_step_deletes_the_previous_cache(setup):
    """A step hands its cache to the decode program, which deletes it: the
    engine holds only the cache the step returned."""
    cfg, params = setup
    eng = ServingEngine(cfg, params, max_batch=2, max_len=32)
    eng.submit(np.arange(1, 6), max_new_tokens=4)
    eng.step()                          # admits, then steps
    previous = jax.tree.leaves(eng.cache)
    eng.step()
    assert all(x.is_deleted() for x in previous)
    assert not any(x.is_deleted() for x in jax.tree.leaves(eng.cache))


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


def test_disagg_placement_and_admission_order(setup):
    """Each handoff goes to the pod with the fewest live and parked requests
    (ties to the lowest index), and one that finds its pod full waits there,
    in order, for a slot that pod frees: pod 1 is free from round 4, yet the
    last request waits for pod 0 until round 8."""
    cfg, params = setup
    srv = DisaggregatedServer(cfg, params, n_decode_pods=2, max_batch=2,
                              max_len=32, backend="xdt")
    admitted, rounds = {}, 0

    def seen():
        for rid, pod in srv.pod_of_request.items():
            admitted.setdefault(rid, (rounds, pod))

    rids = []
    for i, n in enumerate([9, 3, 7, 5, 4, 6, 3]):
        rids.append(srv.submit(np.arange(1, 5 + i) + i, max_new_tokens=n))
        seen()
    while any(s is not None for pod in srv.decode_pods for s in pod.slots):
        srv.step()
        rounds += 1
        seen()
    assert [admitted[r] for r in rids] == \
        [(0, 0), (0, 1), (0, 0), (0, 1), (6, 0), (2, 1), (8, 0)]
    assert srv.handoffs == 7


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
    """An error in prefill raises from ``submit`` itself, and no handoff is
    counted for the request that never ran."""
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
