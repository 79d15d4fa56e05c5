"""95th percentile over the requests of the window of (last token time -
first token time) / (tokens - 1); a request that never completed counts its
time until the load loop gave up on it, over its expected tokens."""
from bench.harness.runner import percentile


def read(run):
    w = run.window
    vals = []
    for r in w.records:
        if r.tpot is not None:
            vals.append(r.tpot)
        elif r.done is None and r.first is not None and (r.expected_tokens or 0) > 1:
            vals.append((w.t_drained - r.first) / (r.expected_tokens - 1))
    p = percentile(vals, 95)
    return None if p is None else 1e3 * p
