"""Useful FLOPs of one pod's decode step (its live sequences, each token
attending to the positions it holds) over the step program's device time and
the chip's bf16 peak, in the traced part of the window."""
from bench.harness.flops import decode_step_flops


def read(run):
    t, b = run.trace, run.trace_bounds
    shape = run.layer.get("shape")
    if t is None or b is None or shape is None or run.peaks is None:
        return None
    steps = [ctx for ts, ctx in run.layer["decode_steps"] if b[0] <= ts < b[1]]
    execs, dev_s = t.program(run.layer["decode_program"])
    if not steps or not execs or dev_s <= 0:
        return None
    per_step = sum(decode_step_flops(shape, c) for c in steps) / len(steps)
    return 100.0 * per_step * execs / (dev_s * run.peaks["bf16_flops_per_s"])
