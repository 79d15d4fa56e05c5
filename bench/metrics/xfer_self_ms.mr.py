"""Host ms per workflow request inside ``TransferEngine.put`` and ``get``
(``xfer.put``, ``xfer.get``), from the program's spans in the traced part of
the window.  The inside twin of ``xfer_host_ms.mr``."""


def read(run):
    try:
        from repro.core import tracing
    except ImportError:             # a program without in-program tracing
        return None
    if run.trace_bounds is None:
        return None
    spans = [r for r in tracing.records(*run.trace_bounds) if isinstance(r, tracing.Span)]
    roots = {s.request for s in spans if s.name == "wf.request" and s.parent is None}
    if not roots:
        return None
    return 1e3 * sum(s.seconds for s in spans if s.name in ("xfer.put", "xfer.get")
                     and s.request in roots) / len(roots)
