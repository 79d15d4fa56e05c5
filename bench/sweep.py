#!/usr/bin/env python3
"""The sweep that found an open-loop cell's knee: the highest offered rate
at which completions keep up with arrivals and the backlog does not grow.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates <r> [<r> ...]

Sets the cell up once, then offers each rate for ``--seconds`` with the
cell's traffic mix (only ``rate_rps`` changes) and prints one JSON line per
rate: requests sent and completed, completions per second inside the window,
the requests outstanding at each quarter of the window, the generator's
lateness, and time to first token and time per output token.  The cell's
own rate is then fixed in its mix file at about four fifths of the knee;
the benchmark's runs never search for one.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.harness.registry import ROOT, Benchmark  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    import os

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from bench.harness.drivers import run_open
    from bench.harness.peaks import peaks_for
    from bench.harness.runner import DRAIN_SECONDS, Context, percentile
    from bench.harness.spans import Spans
    from bench.harness.traffic import make_schedule

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 1
    bench = Benchmark()
    cell = bench.cell(args.workload)
    conf = bench.config(cell["config"])
    mix = bench.mix(cell["traffic"])
    ctx = Context(seed=args.seed, spans=Spans(), device=dev,
                  peaks=peaks_for(dev.device_kind))
    dep = bench.deployment_module(cell["config"]).Deployment(conf, ctx)
    scheds = {rate: make_schedule({**mix, "rate_rps": rate}, args.seconds)
              for rate in args.rates}
    # set-up warms every prompt length that any of the rates sends
    every = [r for sched in scheds.values() for r in sched.requests]
    t = time.perf_counter()
    dep.setup(dataclasses.replace(scheds[max(args.rates)], requests=every))
    print(f"sweep: set-up {time.perf_counter() - t} s", file=sys.stderr, flush=True)
    rng = ctx.rng(5)
    for rate, sched in scheds.items():
        dep.prompts = [rng.integers(1, dep.shape.vocab, size=r.prompt_tokens)
                       .astype("int32") for r in sched.requests]
        w = run_open(dep, sched, args.seconds, drain_s=DRAIN_SECONDS)
        recs = w.records
        S = w.t1 - w.t0

        def outstanding(at):
            return sum(1 for r in recs if r.sent <= at and (r.done is None or r.done > at))

        done_in = [r for r in recs if r.done is not None and r.done <= w.t1]
        ms = lambda v: None if v is None else 1e3 * v
        print(json.dumps({
            "rate_rps": rate, "sent": len(recs), "completed_in_window": len(done_in),
            "completed_rps": len(done_in) / S,
            "outstanding_at_quarters": [outstanding(w.t0 + q * S / 4) for q in (1, 2, 3, 4)],
            "drain_s": w.t_drained - w.t1,
            "lateness_p95_ms": ms(percentile([r.lateness for r in recs], 95)),
            "ttft_p50_ms": ms(percentile([r.ttft for r in recs if r.ttft is not None], 50)),
            "ttft_p95_ms": ms(percentile([r.ttft for r in recs if r.ttft is not None], 95)),
            "tpot_p95_ms": ms(percentile([r.tpot for r in recs if r.tpot is not None], 95)),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
