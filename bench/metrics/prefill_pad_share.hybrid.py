"""Pad tokens prefilled per real prompt token, in %: the program's
``prefill.pad_tokens`` over its ``prefill.tokens`` counts in the traced part
of the window (a model with Mamba2 layers pads each prompt to whole SSD
chunks)."""


def read(run):
    try:
        from repro.core import tracing
    except ImportError:             # a program without in-program tracing
        return None
    if run.trace_bounds is None:
        return None
    n = {"prefill.tokens": 0, "prefill.pad_tokens": 0}
    for r in tracing.records(*run.trace_bounds):
        if isinstance(r, tracing.Count) and r.name in n:
            n[r.name] += r.n
    if not n["prefill.tokens"]:
        return None
    return 100.0 * n["prefill.pad_tokens"] / n["prefill.tokens"]
