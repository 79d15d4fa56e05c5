"""Host ms per request inside ``ctx.put`` and ``ctx.get`` (transfer engine
and buffer registry), timed from the benchmark's handlers."""


def read(run):
    w, s = run.window, run.spans
    n, _ = s.total(w.t0, w.t1, "bench.request")
    if not n:
        return None
    return 1e3 * sum(s.total(w.t0, w.t1, k)[1] for k in ("bench.put", "bench.get")) / n
