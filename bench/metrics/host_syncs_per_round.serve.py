"""Device-to-host reads (the ``host.syncs`` count) per decode round
(``serve.round``), from the program's records in the traced part of the
window."""


def read(run):
    try:
        from repro.core import tracing
    except ImportError:             # a program without in-program tracing
        return None
    if run.trace_bounds is None:
        return None
    recs = tracing.records(*run.trace_bounds)
    rounds = [r for r in recs if isinstance(r, tracing.Span) and r.name == "serve.round"]
    if not rounds:
        return None
    counts = [c for c in recs if isinstance(c, tracing.Count) and c.name == "host.syncs"]
    return sum(c.n for r in rounds for c in counts
               if r.start <= c.t <= r.end) / len(rounds)
