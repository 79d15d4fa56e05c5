"""Host ms per ``DisaggregatedServer.step()``: all the step time of the
window and its drain over all the steps."""


def read(run):
    w, s = run.window, run.spans
    n, total = s.total(w.t0, w.t1 + 1e9, "bench.step")
    return 1e3 * total / n if n else None
