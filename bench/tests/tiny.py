"""Tiny sizes of each cell for runs on the CPU: the same code paths as on
the chip, with the configuration and the traffic scaled down."""
import time

from bench.harness.registry import ROOT, Benchmark
from bench.harness.runner import run_cell

MR_TINY = {"input_bytes_per_mapper": 30 * 4096, "slice_bytes": 4096}

# weights at std 0.1 so that the layers and not the tied embedding decide the
# next token; at std 0.02 and this width every token repeats its input with
# a wide margin, and no rounding can flip one
CHAT_TINY = {
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 1, "intermediate_size": 256, "vocab_size": 2048,
    "initializer_range": 0.1,
    "deployment": {"prefill_pods": 1, "decode_pods": 2, "max_batch": 2,
                   "max_len": 64, "handoff": "xdt", "served_dtype": "bfloat16",
                   "chips": 1},
    # at this size bfloat16 serving read widest gaps of 0 to 0.016 and the
    # fp8 control 0.061 to 0.20 over seeds 0-3 on the CPU
    "limits": {"max_logit_gap": 0.04, "sample_requests": 4},
}
CHAT_MIX = {
    "rate_rps": 8.0,
    "prompt_tokens": {"median": 16, "sigma": 0.5, "min": 8, "max": 32},
    "output_tokens": {"median": 6, "sigma": 0.5, "min": 2, "max": 12},
}


#: per configuration, the overrides that scale it and its traffic down
TINY = {
    "vswarm-mr-xdt": {"conf_override": MR_TINY},
    "disagg-smollm-360m-xdt": {"conf_override": CHAT_TINY, "mix_override": CHAT_MIX},
}


def tiny_kw(workload, root=ROOT):
    return dict(TINY[Benchmark(root).cell(workload)["config"]])


def run(workload, seed=2**31 + 5, seconds=1.5, trace=False, **kw):
    """One run of a cell at its tiny size; returns the result object."""
    kw = {**tiny_kw(workload, kw.get("root", ROOT)), **kw}
    return run_cell(workload, seed, seconds, trace, t_start=time.perf_counter(),
                    require_chip=False, **kw)
