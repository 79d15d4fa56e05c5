"""Host ms per decode round (``serve.round``) spent in device-to-host reads
(``host.sync``) inside it, from the program's spans in the traced part of
the window."""


def read(run):
    try:
        from repro.core import tracing
    except ImportError:             # a program without in-program tracing
        return None
    if run.trace_bounds is None:
        return None
    spans = [r for r in tracing.records(*run.trace_bounds) if isinstance(r, tracing.Span)]
    rounds = [s for s in spans if s.name == "serve.round"]
    if not rounds:
        return None
    syncs = [s for s in spans if s.name == "host.sync"]
    total = sum(s.seconds for r in rounds for s in syncs
                if r.start <= s.start and s.end <= r.end)
    return 1e3 * total / len(rounds)
