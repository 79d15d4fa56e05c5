"""Roofline share of one pod's decode step: the least time the chip needs
(the larger of FLOPs over the bf16 peak and bytes over the HBM peak, where
bytes are the weights plus the cache positions the live sequences hold)
over the step program's device time, in the traced part of the window."""
from bench.harness.flops import decode_step_bytes, decode_step_flops, roofline_seconds


def read(run):
    t, b = run.trace, run.trace_bounds
    shape = run.layer.get("shape")
    if t is None or b is None or shape is None or run.peaks is None:
        return None
    steps = [ctx for ts, ctx in run.layer["decode_steps"] if b[0] <= ts < b[1]]
    execs, dev_s = t.program(run.layer["decode_program"])
    if not steps or not execs or dev_s <= 0:
        return None
    least = sum(roofline_seconds(decode_step_flops(shape, c),
                                 decode_step_bytes(shape, c), run.peaks)
                for c in steps) / len(steps)
    return 100.0 * least * execs / dev_s
