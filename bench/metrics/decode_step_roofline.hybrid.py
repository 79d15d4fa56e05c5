"""Roofline share of one pod's decode step of a Mamba2/attention hybrid:
the least time the chip needs (the larger of FLOPs over the bf16 peak and
bytes over the HBM peak, where bytes are the weights, each live sequence's
SSM and conv states read and written, and the KV positions it holds) over
the step program's device time, in the traced part of the window."""
from bench.harness.flops import roofline_seconds
from bench.harness.hybrid_flops import HybridShape, decode_step_bytes, decode_step_flops


def read(run):
    t, b = run.trace, run.trace_bounds
    shape = run.layer.get("shape")
    if t is None or b is None or not isinstance(shape, HybridShape) or run.peaks is None:
        return None
    steps = [ctx for ts, ctx in run.layer["decode_steps"] if b[0] <= ts < b[1]]
    execs, dev_s = t.program(run.layer["decode_program"])
    if not steps or not execs or dev_s <= 0:
        return None
    least = sum(roofline_seconds(decode_step_flops(shape, c),
                                 decode_step_bytes(shape, c), run.peaks)
                for c in steps) / len(steps)
    return 100.0 * least * execs / dev_s
