"""Host ms per request inside ``WorkflowEngine.run``, less the stage bodies
and the put/get calls that the benchmark's handlers time: the control
plane's own work (steering, invocation bookkeeping, the simulator)."""


def read(run):
    w, s = run.window, run.spans
    n, total = s.total(w.t0, w.t1, "bench.request")
    if not n:
        return None
    inner = sum(s.total(w.t0, w.t1, k)[1]
                for k in ("bench.stage", "bench.put", "bench.get"))
    return 1e3 * (total - inner) / n
