"""In-program tracing (``repro.core.tracing``): a profiler session is the
switch; the spans and counters of the workflow engine, the transfer engine
and disaggregated serving carry the right parents and request ids; the
profiler's trace holds them, nested the same way; and virtual time is the
same with tracing on."""
import collections
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_engine_perf
from repro.configs import smoke_config
from repro.core import TransferEngine, WorkflowEngine, tracing
from repro.core.transfer import TransferStats
from repro.models import init_params
from repro.serving import DisaggregatedServer

WF_SPANS = {"wf.request", "wf.invoke", "wf.steer", "wf.handler", "xfer.put", "xfer.get"}
SERVE_SPANS = {"serve.submit", "serve.prefill", "serve.insert", "serve.slot_wait",
               "serve.round", "serve.release", "serve.decode", "host.sync",
               "xfer.put", "xfer.get"}


@pytest.fixture(autouse=True)
def _empty_ring():
    tracing.clear()
    yield
    tracing.clear()


def _mr_engine():
    """A tiny MapReduce: a driver scatters to 2 mappers (2 puts each) and
    invokes 2 reducers (2 gets each), all inline."""
    eng = WorkflowEngine(records="columnar")

    def mapper(c, x):
        return [c.put(x * 2), c.put(x * 3)]

    def reducer(c, refs):
        return sum(c.get(r) for r in refs)

    def driver(c, xs):
        refs = c.scatter("mapper", xs)
        return [c.invoke("reducer", [row[j] for row in refs]) for j in range(2)]

    for name, fn in (("mapper", mapper), ("reducer", reducer), ("driver", driver)):
        eng.register(name, fn)
    return eng


def _mr_request(eng):
    return eng.run("driver", [jnp.arange(8.0) + i for i in range(2)])


@pytest.fixture(scope="module")
def model():
    cfg = smoke_config("smollm_360m")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _serve(model):
    """Three requests on two decode pods of one slot each: the third parks
    behind a full batch until a slot frees."""
    cfg, params = model
    srv = DisaggregatedServer(cfg, params, n_decode_pods=2, max_batch=1, max_len=32)
    rids = [srv.submit(np.arange(1, 6) + i, max_new_tokens=3) for i in range(3)]
    done = srv.run_until_drained()
    return rids, {r: done[r].generated for r in rids}


def _spans(recs):
    return [r for r in recs if isinstance(r, tracing.Span)]


def _parent_names(recs):
    by_id = {s.id: s for s in _spans(recs)}
    out = collections.defaultdict(set)
    for s in _spans(recs):
        out[s.name].add(by_id[s.parent].name if s.parent in by_id else None)
    return out


@pytest.fixture(scope="module")
def traced(model, tmp_path_factory):
    """One MR request and the serving run, under a profiler session."""
    eng = _mr_engine()
    _mr_request(eng)                     # compiled before the session
    _serve(model)
    tracing.clear()
    out = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(out)):
        assert tracing.enabled()
        mr_out = _mr_request(eng)
        mr_recs = tracing.records()
        served = _serve(model)
    assert not tracing.enabled()
    recs = tracing.records()
    serve_recs = recs[len(mr_recs):]
    xplane = glob.glob(str(out / "plugins" / "profile" / "*" / "*.xplane.pb"))
    return {"mr": mr_recs, "serve": serve_recs, "xplane": xplane[0],
            "mr_out": mr_out, "served": served}


# ------------------------------------------------------------------ tracer


def test_without_a_profiler_session_nothing_is_recorded(model):
    assert not tracing.enabled()
    _mr_request(_mr_engine())
    _serve(model)
    assert tracing.records() == []
    assert tracing.span("x") is tracing.begin("x")       # the one no-op object
    tracing.count("x")
    assert tracing.records() == []


def test_ring_is_bounded_and_drops_the_oldest(monkeypatch, tmp_path):
    assert tracing._ring.maxlen == tracing.RING == 1 << 20
    monkeypatch.setattr(tracing, "_ring", collections.deque(maxlen=3))
    with jax.profiler.trace(str(tmp_path)):
        for n in range(5):
            tracing.count("c", n)
    assert [c.n for c in tracing.records()] == [2, 3, 4]


def test_records_between_bounds_and_request_of_roots(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with tracing.root("outer", 7) as outer:
            with tracing.root("inner", 9) as inner:     # joins request 7
                tracing.count("c", 2)
            with tracing.span("other", request=3):
                pass
        with tracing.root("alone", 9):
            pass
    recs = {r.name: r for r in tracing.records()}
    assert recs["inner"].request == recs["c"].request == 7
    assert recs["inner"].parent == outer.id and recs["c"].parent == inner.id
    assert recs["other"].request == 3 and recs["alone"].request == 9
    assert recs["alone"].parent is None
    lo, hi = recs["inner"].start, recs["inner"].end
    assert [r.name for r in tracing.records(lo, hi)] == ["inner", "c"]


# ------------------------------------------------------- spans of each layer


def test_mr_spans_have_their_parents_and_one_request(traced):
    recs = _spans(traced["mr"])
    assert {s.name for s in recs} == WF_SPANS
    parents = _parent_names(recs)
    assert parents["wf.request"] == {None}
    assert parents["wf.invoke"] == {"wf.handler"}
    assert parents["wf.steer"] == {"wf.request", "wf.invoke"}
    assert parents["wf.handler"] == {"wf.request", "wf.invoke"}
    assert parents["xfer.put"] == parents["xfer.get"] == {"wf.handler"}
    (root,) = [s for s in recs if s.name == "wf.request"]
    assert {s.request for s in recs} == {root.request}
    names = collections.Counter(s.name for s in recs)
    # driver + 2 mappers + 2 reducers; 4 puts and 4 gets
    assert names["wf.invoke"] == 4 and names["wf.steer"] == names["wf.handler"] == 5
    assert names["xfer.put"] == names["xfer.get"] == 4
    for s in recs:
        if s.name.startswith("xfer."):
            assert s.attrs == {"medium": "xdt", "nbytes": 32}


def test_serving_spans_have_their_parents_and_requests(traced):
    recs = _spans(traced["serve"])
    assert {s.name for s in recs} == SERVE_SPANS
    parents = _parent_names(recs)
    assert parents["serve.submit"] == parents["serve.round"] == {None}
    assert parents["serve.slot_wait"] == {None}
    assert parents["serve.prefill"] == parents["xfer.put"] == parents["xfer.get"] \
        == {"serve.submit"}
    assert parents["serve.insert"] == {"serve.submit", "serve.release"}
    assert parents["serve.decode"] == parents["serve.release"] == {"serve.round"}
    assert parents["host.sync"] == {"serve.prefill", "serve.round"}
    rids, _ = traced["served"]
    for rid in rids:
        mine = collections.Counter(s.name for s in recs if s.request == rid)
        for name in ("serve.submit", "serve.prefill", "xfer.put", "xfer.get",
                     "serve.insert"):
            assert mine[name] == 1, (rid, name, mine)
    (wait,) = [s for s in recs if s.name == "serve.slot_wait"]
    assert wait.request == rids[2]
    (insert,) = [s for s in recs if s.name == "serve.insert" and s.request == rids[2]]
    assert wait.start < wait.end <= insert.start
    # the parked handoff is admitted inside a round's release
    by_id = {s.id: s for s in recs}
    assert by_id[insert.parent].name == "serve.release"
    # round spans belong to no request
    assert {s.request for s in recs if s.name in ("serve.round", "serve.decode")} == {None}


def test_each_round_reads_every_pods_tokens_once_after_their_dispatch(traced):
    recs = traced["serve"]
    rounds = [s for s in _spans(recs) if s.name == "serve.round"]
    assert rounds
    for r in rounds:
        inside = [x for x in recs if r.start <= x.start <= r.end]
        decodes = [s for s in _spans(inside) if s.name == "serve.decode"]
        syncs = [s for s in _spans(inside) if s.name == "host.sync"]
        counts = sum(c.n for c in inside
                     if isinstance(c, tracing.Count) and c.name == "host.syncs")
        assert sum(s.attrs["live"] for s in decodes) > 0
        assert counts == len(syncs) == 1
        assert all(d.end <= syncs[0].start for d in decodes)
    # both pods step in the same round while both hold a request
    assert max(sum(s.name == "serve.decode" for s in _spans(recs)
                   if r.start <= s.start <= r.end) for r in rounds) == 2
    # and each prefill reads its first token once
    prefills = [s for s in _spans(recs) if s.name == "serve.prefill"]
    counts = [c for c in recs if isinstance(c, tracing.Count) and c.name == "host.syncs"]
    assert sum(1 for c in counts for p in prefills if p.start <= c.t <= p.end) == 3


def test_prefill_counts_and_handoff_bytes(traced, model):
    """Each prefill counts its prompt's tokens and the pads after them (none
    for a dense model, which is prefilled at the prompt's own length), and
    ``serve.prefill`` and ``serve.insert`` carry the lengths and the
    handed-over bytes (a dense model's cache is all K and V)."""
    cfg, _ = model
    recs = traced["serve"]
    counts = collections.defaultdict(list)
    for c in recs:
        if isinstance(c, tracing.Count):
            counts[c.name].append(c.n)
    assert counts["prefill.tokens"] == [5, 5, 5] and counts["prefill.pad_tokens"] == [0, 0, 0]
    prefills = [s for s in _spans(recs) if s.name == "serve.prefill"]
    assert [(s.attrs["tokens"], s.attrs["padded"]) for s in prefills] == [(5, 5)] * 3
    kv = 2 * cfg.n_layers * 32 * cfg.n_kv_heads * cfg.hd * 2     # K and V, max_len 32, bf16
    inserts = [s for s in _spans(recs) if s.name == "serve.insert"]
    assert [(s.attrs["state_bytes"], s.attrs["kv_bytes"]) for s in inserts] == [(0, kv)] * 3


def test_profiler_trace_holds_each_span_nested_as_the_records(traced):
    from jax.profiler import ProfileData

    names = WF_SPANS | SERVE_SPANS
    events = collections.defaultdict(list)
    for plane in ProfileData.from_file(traced["xplane"]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in names:
                        events[e.name].append((e.start_ns, e.start_ns + e.duration_ns))
    recs = _spans(traced["mr"]) + _spans(traced["serve"])
    assert set(events) == names
    # the k-th record of a name is the k-th event of that name
    seen = collections.Counter()
    interval = {}
    for s in sorted(recs, key=lambda s: s.start):
        interval[s.id] = sorted(events[s.name])[seen[s.name]]
        seen[s.name] += 1
    assert all(len(events[n]) == seen[n] for n in names)
    for s in recs:
        if s.parent is not None:
            (c0, c1), (p0, p1) = interval[s.id], interval[s.parent]
            assert p0 <= c0 and c1 <= p1, s


def test_results_are_the_same_with_tracing_on(traced, model):
    assert [np.asarray(o).tolist() for o in traced["mr_out"]] == \
        [np.asarray(o).tolist() for o in _mr_request(_mr_engine())]
    assert traced["served"][1] == _serve(model)[1]


def test_virtual_time_checksums_are_the_same_under_the_profiler(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        test_engine_perf.test_fixed_seed_latency_checksums_match_committed_baseline()
        assert any(r.name == "wf.steer" for r in tracing.records())


# ------------------------------------------------------------ transfer engine


def test_transfer_spans_name_medium_and_bytes(tmp_path):
    te = TransferEngine("xdt")
    x = jnp.zeros((4, 8), jnp.float32)
    tree = {"a": x, "b": jnp.zeros(3, jnp.int32)}
    with jax.profiler.trace(str(tmp_path)):
        got = [te.get(te.put(x)), te.get(te.put(tree)), te.get(te.put(x, backend="s3"))]
    assert np.asarray(got[2]).shape == (4, 8)
    recs = _spans(tracing.records())
    assert [(s.name, s.attrs["medium"], s.attrs["nbytes"]) for s in recs] == [
        ("xfer.put", "xdt", 128), ("xfer.get", "xdt", 128),
        ("xfer.put", "xdt", 140), ("xfer.get", "xdt", 140),
        ("xfer.put", "s3", 128), ("xfer.get", "s3", 128)]
    assert all(s.parent is None for s in recs)
    assert te.stats.transfers == 3


def test_wall_timing_is_gone():
    with pytest.raises(TypeError):
        TransferEngine("xdt", wall_timing=True)
    assert "wall_seconds" not in {f.name for f in TransferStats.__dataclass_fields__.values()}
