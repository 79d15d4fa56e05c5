"""The readers of the program's own spans and counters: on hand-made records
with known answers, on a program without tracing, and in a tiny traced run
of each cell."""
import sys

import pytest

from bench.harness.registry import Benchmark
from bench.harness.runner import Run
from repro.core import tracing
from repro.core.tracing import Count, Span

from .tiny import run

MR_METRICS = {"engine_self_ms.mr", "steer_ms.mr", "xfer_self_ms.mr"}
SERVE_METRICS = {"prefill_host_ms.serve", "handoff_ms.serve", "token_sync_ms.serve",
                 "host_syncs_per_round.serve"}


def _span(name, start, end, id, parent=None, request=None, **attrs):
    return Span(name, start, end, id, parent, request, attrs)


# Two MR requests inside the bounds (0, 20), one after them, and a steer
# that belongs to no request seen whole.  Request 1: 10 s in all, 1.5 s its
# own, an invoke of 4 s with 1 s its own, two steers of 0.5 s, a put and a
# get of 0.5 s.  Request 2: 2 s in all, a steer of 0.5 s.
MR = [
    _span("wf.request", 0.0, 10.0, 1, None, 1),
    _span("wf.steer", 0.5, 1.0, 2, 1, 1),
    _span("wf.handler", 1.0, 9.0, 3, 1, 1),
    _span("wf.invoke", 2.0, 6.0, 4, 3, 1),
    _span("wf.steer", 2.5, 3.0, 5, 4, 1),
    _span("wf.handler", 3.0, 5.5, 6, 4, 1),
    _span("xfer.put", 3.5, 4.0, 7, 6, 1),
    _span("xfer.get", 4.5, 5.0, 8, 6, 1),
    _span("wf.request", 11.0, 13.0, 9, None, 2),
    _span("wf.steer", 11.0, 11.5, 10, 9, 2),
    _span("wf.steer", 15.0, 16.0, 11, None, 7),
    _span("wf.request", 25.0, 30.0, 12, None, 3),
]
# Ids from 101.  Two rounds, a prefill with its first-token read, two
# handoffs (request 5 put, pulled and inserted; request 6 pulled and
# inserted), and a put of request 9, which is never inserted.
SERVE = [
    _span("serve.round", 0.0, 10.0, 101),
    _span("serve.decode", 0.0, 2.0, 102, 101, None, pod=0, live=2),
    _span("host.sync", 2.0, 5.0, 103, 101),
    Count("host.syncs", 2.0, 1, 101, None),
    _span("host.sync", 5.0, 6.0, 104, 101),
    Count("host.syncs", 5.0, 1, 101, None),
    _span("serve.release", 6.0, 10.0, 105, 101),
    _span("serve.round", 10.0, 12.0, 106),
    _span("host.sync", 10.5, 11.0, 107, 106),
    Count("host.syncs", 10.5, 1, 106, None),
    _span("serve.prefill", 13.0, 15.0, 108, None, 5),
    _span("host.sync", 14.0, 14.5, 109, 108, 5),
    Count("host.syncs", 14.0, 1, 108, 5),
    _span("xfer.put", 13.5, 13.7, 110, None, 5),
    _span("xfer.get", 16.0, 16.5, 111, None, 5),
    _span("serve.insert", 16.5, 17.0, 112, None, 5),
    _span("xfer.get", 17.5, 18.0, 113, None, 6),
    _span("serve.insert", 18.0, 18.4, 114, None, 6),
    _span("serve.prefill", 18.5, 19.5, 115, None, 9),
    _span("xfer.put", 19.5, 19.6, 116, None, 9),
]
WANT = {
    "engine_self_ms.mr": 1e3 * (1.5 + 1.0 + 0.5 + 0.5 + 1.5 + 0.5) / 2,
    "steer_ms.mr": 1e3 * 1.5 / 2,
    "xfer_self_ms.mr": 1e3 * 1.0 / 2,
    "prefill_host_ms.serve": 1e3 * (2.0 + 1.0) / 2,
    "handoff_ms.serve": 1e3 * (0.2 + 0.5 + 0.5 + 0.5 + 0.4) / 2,
    "token_sync_ms.serve": 1e3 * (3.0 + 1.0 + 0.5) / 2,
    "host_syncs_per_round.serve": 3 / 2,
}


def _run(bounds=(0.0, 20.0)):
    return Run(cell={}, seconds=20.0, setup_s=0.0, window=None, spans=None,
               layer={}, peaks=None, trace=None, trace_bounds=bounds)


def _records(recs):
    def records(t0=float("-inf"), t1=float("inf")):
        return sorted((r for r in recs if t0 <= r.start
                       and (r.t if isinstance(r, Count) else r.end) <= t1),
                      key=lambda r: r.start)
    return records


@pytest.mark.parametrize("metric", sorted(WANT))
def test_reader_on_known_records(metric, monkeypatch):
    monkeypatch.setattr(tracing, "records", _records(MR + SERVE))
    got = Benchmark().reader(metric).read(_run())
    assert got == pytest.approx(WANT[metric])


@pytest.mark.parametrize("metric", sorted(WANT))
def test_reader_finds_nothing_to_read(metric, monkeypatch):
    monkeypatch.setattr(tracing, "records", _records([]))
    reader = Benchmark().reader(metric)
    assert reader.read(_run()) is None
    assert reader.read(_run(bounds=None)) is None


@pytest.mark.parametrize("metric", sorted(WANT))
def test_reader_on_a_program_without_tracing(metric, monkeypatch):
    import repro.core

    monkeypatch.delattr(repro.core, "tracing")
    monkeypatch.setitem(sys.modules, "repro.core.tracing", None)
    assert Benchmark().reader(metric).read(_run()) is None


def test_new_metrics_are_listed_for_their_cells():
    b = Benchmark()
    assert MR_METRICS <= {m["name"] for m in b.metrics("mr-xdt-closed4", trace=True)}
    assert SERVE_METRICS <= {m["name"] for m in b.metrics("disagg-chat", trace=True)}


@pytest.mark.parametrize("cell, names", [("mr-xdt-closed4", MR_METRICS),
                                         ("disagg-chat", SERVE_METRICS)])
def test_tiny_traced_run_reports_the_new_metrics(cell, names):
    res = run(cell, trace=True, seconds=1.0)
    assert res["correct"] is True
    assert names <= set(res["metrics"])
    for n in names:
        assert res["metrics"][n]["value"] > 0
