"""Host ms per workflow request spent in the engine's own code: the self time
(duration less the child spans) of ``wf.request``, ``wf.invoke`` and
``wf.steer``, from the program's spans in the traced part of the window.
The inside twin of ``engine_host_ms.mr``."""
import collections

ENGINE = ("wf.request", "wf.invoke", "wf.steer")


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
    inner = collections.Counter()
    for s in spans:
        if s.parent is not None:
            inner[s.parent] += s.seconds
    own = sum(s.seconds - inner[s.id] for s in spans
              if s.name in ENGINE and s.request in roots)
    return 1e3 * own / len(roots)
