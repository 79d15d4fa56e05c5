"""95th percentile over every request sent in the window, from the start of
its submission to its outputs ready on the device; a request that never
completed counts until the load loop gave up on it."""
from bench.harness.runner import percentile


def read(run):
    w = run.window
    lat = [r.latency if r.latency is not None else w.t_drained - r.sent
           for r in w.records]
    p = percentile(lat, 95)
    return None if p is None else 1e3 * p
