"""Host ms per handoff: the time in ``xfer.put``, ``xfer.get`` and
``serve.insert`` of each request admitted into a decode slot (a
``serve.insert`` span), from the program's spans in the traced part of the
window."""

HANDOFF = ("xfer.put", "xfer.get", "serve.insert")


def read(run):
    try:
        from repro.core import tracing
    except ImportError:             # a program without in-program tracing
        return None
    if run.trace_bounds is None:
        return None
    spans = [r for r in tracing.records(*run.trace_bounds) if isinstance(r, tracing.Span)]
    admitted = [s.request for s in spans if s.name == "serve.insert"]
    if not admitted:
        return None
    rids = set(admitted)
    return 1e3 * sum(s.seconds for s in spans
                     if s.name in HANDOFF and s.request in rids) / len(admitted)
