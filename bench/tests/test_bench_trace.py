"""The trace reduction: on synthetic intervals, and on a small profiler
trace recorded on a TPU v5e (``data/mr_small.xplane.pb.gz``, one traced
half-second of the MapReduce cell)."""
from pathlib import Path

import pytest

from bench.harness import trace

DATA = Path(__file__).resolve().parent / "data" / "mr_small.xplane.pb.gz"


def test_union_and_gaps():
    busy = trace.union([(0, 2), (1, 3), (5, 6), (5.5, 5.7)])
    assert busy == [(0, 3), (5, 6)]
    assert trace.gaps(busy, -1, 8) == [(-1, 0), (3, 5), (6, 8)]
    assert trace.clip([(0, 3), (5, 6)], 1, 5.5) == [(1, 3), (5, 5.5)]


def test_self_time_takes_nested_ops_out_of_their_parent():
    ops = [("while", 0.0, 10.0), ("a", 1.0, 3.0), ("b", 4.0, 8.0), ("a", 11.0, 12.0)]
    assert trace.self_times(ops) == {"while": 4.0, "a": 3.0, "b": 4.0}


def test_idle_gaps_go_to_the_innermost_host_span():
    spans = [("bench.request", 0.0, 10.0), ("bench.put", 2.0, 3.0),
             ("bench.get", 6.0, 7.5)]
    got = trace.attribute([(2.2, 2.8), (6.5, 7.0), (4.0, 5.0), (11.0, 12.0)], spans)
    assert got == pytest.approx({"bench.put": 0.6, "bench.get": 0.5,
                                 "bench.request": 1.0, "host:outside-spans": 1.0})


def test_program_name_drops_the_hash():
    assert trace.program_name("jit_decode(1412813634915374287)") == "jit_decode"


@pytest.fixture(scope="module")
def recorded():
    return trace.reduce(str(DATA))


def test_recorded_trace_busy_and_idle(recorded):
    r = recorded
    assert r.devices == 1
    assert 0.4 < r.window_s < 0.7
    assert 0 < r.busy_s < r.window_s
    assert 0.0 < r.idle_share < 1.0
    # busy time is the union of program executions, so no more than their sum
    assert r.busy_s <= sum(s for _, s in r.programs.values()) + 1e-9


def test_recorded_trace_programs_and_ops(recorded):
    r = recorded
    n_map, t_map = r.program("jit_map_fn")
    n_red, t_red = r.program("jit_reduce_fn")
    assert n_map > 0 and n_red > 0 and t_map > t_red > 0
    assert r.program("jit_nothing") == (0, 0.0)
    names = [n for n, _ in r.device_ops]
    assert len(names) <= 10 and all(":" in n for n in names)
    secs = [s for _, s in r.device_ops]
    assert secs == sorted(secs, reverse=True)


def test_recorded_idle_gaps_are_attributed_to_host_spans(recorded):
    r = recorded
    names = {n for n, _ in r.idle_gaps}
    assert names and names <= {"bench.request", "bench.stage", "bench.put",
                               "bench.get", "host:outside-spans"}
    assert sum(s for _, s in r.idle_gaps) == pytest.approx(r.window_s - r.busy_s, rel=1e-6)
