"""95th percentile over every request due in the window, from the time it
was due to its first token on the host; a request that never got one counts
until the load loop gave up on it."""
from bench.harness.runner import percentile


def read(run):
    w = run.window
    ttft = [r.ttft if r.ttft is not None else w.t_drained - r.due
            for r in w.records]
    p = percentile(ttft, 95)
    return None if p is None else 1e3 * p
