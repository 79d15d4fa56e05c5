"""Requests whose outputs were ready inside the window, over the window."""


def read(run):
    w = run.window
    done = [r for r in w.records if r.ok and r.done is not None and r.done <= w.t1]
    return len(done) / (w.t1 - w.t0)
