"""Useful prefill FLOPs of a Mamba2/attention hybrid (the prompt's real
tokens, the SSD at its published chunk, causal attention) over the prefill
program's device time and the chip's bf16 peak, in the traced part of the
window."""
from bench.harness.hybrid_flops import HybridShape, prefill_flops


def read(run):
    t, b = run.trace, run.trace_bounds
    shape = run.layer.get("shape")
    if t is None or b is None or not isinstance(shape, HybridShape) or run.peaks is None:
        return None
    prompts = [n for ts, n in run.layer["prefills"] if b[0] <= ts < b[1]]
    execs, dev_s = t.program(run.layer["prefill_program"])
    if not prompts or not execs or dev_s <= 0:
        return None
    per_exec = sum(prefill_flops(shape, n) for n in prompts) / len(prompts)
    return 100.0 * per_exec * execs / (dev_s * run.peaks["bf16_flops_per_s"])
