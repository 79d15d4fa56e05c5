"""Host ms per ``serve.prefill`` span: the prefill dispatch and the read of
its first token, from the program's spans in the traced part of the
window."""


def read(run):
    try:
        from repro.core import tracing
    except ImportError:             # a program without in-program tracing
        return None
    if run.trace_bounds is None:
        return None
    got = [r.seconds for r in tracing.records(*run.trace_bounds)
           if isinstance(r, tracing.Span) and r.name == "serve.prefill"]
    return 1e3 * sum(got) / len(got) if got else None
