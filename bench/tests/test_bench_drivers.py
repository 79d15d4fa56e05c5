"""The drivers on a fake clock: due-time latency, lateness, closed-loop
in-flight count."""
import time

import pytest

from bench.harness.drivers import run_closed, run_open
from bench.harness.traffic import RequestSpec, Schedule


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


class ClosedSystem:
    """Each submit costs 0.01 s of host time; each request is ready 0.05 s
    after its submission started."""

    def __init__(self, clock):
        self.clock, self.inflight, self.most = clock, 0, 0

    def submit(self, spec):
        t = self.clock.t
        self.clock.t += 0.01
        self.inflight += 1
        self.most = max(self.most, self.inflight)
        return t + 0.05

    def finish(self, ready_at):
        self.clock.t = max(self.clock.t, ready_at)
        self.inflight -= 1
        return True


def test_closed_loop_keeps_clients_in_flight_and_times_each_request():
    clock = Clock()
    sys_ = ClosedSystem(clock)
    sched = Schedule("closed", 4, None, [RequestSpec(0)])
    w = run_closed(sys_, sched, 1.0, clock=clock)
    assert sys_.most == 4
    assert all(r.ok and r.done is not None for r in w.records)
    # every request took its 0.05 s, and none less
    assert all(r.latency >= 0.05 - 1e-12 for r in w.records)
    assert w.records[0].latency == pytest.approx(0.05)
    # submissions stop when the window closes; the ones in flight drain
    assert all(r.sent < w.t1 for r in w.records)
    assert w.t_drained >= w.records[-1].done


class OpenSystem:
    """Prefill (submit) takes 0.3 s; each step takes 0.1 s and finishes a
    request after two steps."""

    def __init__(self, clock):
        self.clock, self.live, self.n = clock, {}, 0

    def submit(self, spec):
        self.clock.t += 0.3
        self.n += 1
        self.live[self.n] = 0
        return self.n

    def busy(self):
        return bool(self.live)

    def step(self):
        self.clock.t += 0.1
        done = []
        for rid in list(self.live):
            self.live[rid] += 1
            if self.live[rid] == 2:
                del self.live[rid]
                done.append((rid, 3))
        return done


def test_open_loop_counts_from_the_due_time_and_keeps_lateness():
    clock = Clock()
    reqs = [RequestSpec(i, due_s=d, output_tokens=3) for i, d in enumerate([0.0, 0.1, 0.2, 2.0])]
    w = run_open(OpenSystem(clock), Schedule("open", 0, 10.0, reqs), 3.0,
                 drain_s=5.0, clock=clock, sleep=clock.sleep)
    r0, r1, r2, r3 = w.records
    # the first is sent on time; the next two wait behind its 0.3 s prefill
    assert r0.lateness == pytest.approx(0.0)
    assert r1.lateness == pytest.approx(0.2)
    assert r2.lateness == pytest.approx(0.4)
    # time to first token counts the wait: due 0.1, first token at 0.6
    assert r1.ttft == pytest.approx(0.5)
    assert r2.ttft == pytest.approx(0.7)
    # the fourth arrives into an idle system and is on time
    assert r3.lateness == pytest.approx(0.0) and r3.ttft == pytest.approx(0.3)
    assert all(r.ok and r.tokens == 3 for r in w.records)
    assert r3.tpot == pytest.approx((r3.done - r3.first) / 2)


def test_open_loop_gives_up_after_the_drain():
    clock = Clock()

    class Stuck(OpenSystem):
        def step(self):
            self.clock.t += 0.1
            return []

    reqs = [RequestSpec(0, due_s=0.0, output_tokens=3)]
    w = run_open(Stuck(clock), Schedule("open", 0, 1.0, reqs), 1.0,
                 drain_s=2.0, clock=clock, sleep=clock.sleep)
    assert w.records[0].done is None and not w.records[0].ok
    assert w.t_drained - w.t1 >= 2.0


def test_gc_pauses_are_timed_and_set_beside_a_span():
    import gc

    from bench.harness.runner import GcPauses

    pauses = GcPauses()
    gc.callbacks.append(pauses)
    try:
        t0 = time.perf_counter()
        gc.collect()
        t1 = time.perf_counter()
    finally:
        gc.callbacks.remove(pauses)
    assert pauses.pauses and pauses.pauses[-1][0] == 2
    _, s, e = pauses.pauses[-1]
    assert t0 <= s <= e <= t1
    assert pauses.within(t0, t1) == pytest.approx(sum(e - s for _, s, e in pauses.pauses))
    assert pauses.within(t1 + 1, t1 + 2) == 0.0


def test_stall_watch_keeps_where_the_loop_stalled():
    from bench.harness.runner import STALL_S, StallWatch

    seen = []

    class Hook:
        def poll(self, elapsed):
            seen.append(elapsed)

    def stuck_in_the_system():
        time.sleep(STALL_S + 0.15)

    with StallWatch(Hook()) as watch:
        watch.poll(0.0)
        stuck_in_the_system()
        watch.poll(1.0)
    assert seen == [0.0, 1.0]
    (st,) = watch.stalls
    assert st["seconds"] >= STALL_S + 0.1
    assert st["stack"][-1].endswith("stuck_in_the_system")
