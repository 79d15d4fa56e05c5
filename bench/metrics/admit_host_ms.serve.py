"""Host ms per request inside ``DisaggregatedServer.submit``: prefill
dispatch and its sync, the handoff workflow on the engine, the cache put and
pull, and the admit into a decode slot."""


def read(run):
    w, s = run.window, run.spans
    n, total = s.total(w.t0, w.t1 + 1e9, "bench.submit")
    return 1e3 * total / n if n else None
