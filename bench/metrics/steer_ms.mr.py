"""Host ms per workflow request in the control plane's steers (``wf.steer``),
from the program's spans in the traced part of the window."""


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
    return 1e3 * sum(s.seconds for s in spans
                     if s.name == "wf.steer" and s.request in roots) / len(roots)
