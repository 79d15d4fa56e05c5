#!/usr/bin/env python3
"""Readings that the limits of ``correct`` were set from: the program's
numbers over many seeds and each control's, at the cell's own size and load.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

The controls are those the cell's configuration names in
``Deployment.controls()``: each a patch that puts the control in the
program's place before set-up.  Every reading, the program's and each
control's, is a whole run through the harness and its own check, so a
control reads ``correct`` false there or not at all.  The benchmark's own
runs never run this script.  Prints one JSON line per seed and run: what
ran, ``correct``, and every number compared beside its limit.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.harness.registry import Benchmark  # noqa: E402
from bench.harness.runner import run_cell  # noqa: E402


def readings(workload, seed, seconds, kind, patch, require_chip=True, **kw):
    """One run of ``workload`` with ``patch`` in the program's place; the
    line this script prints for it, or None where the run may not report."""
    result = run_cell(workload, seed, seconds, False, t_start=time.perf_counter(),
                      patch=patch, require_chip=require_chip, **kw)
    if result is None:
        return None
    checks = result["checks"]
    return {"seed": seed, "kind": kind, "correct": result["correct"],
            "failed_checks": sorted(n for n, c in checks.items()
                                    if c["value"] > c["limit"]),
            "checks": {n: [c["value"], c["limit"]] for n, c in checks.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="run the controls on the first this many seeds")
    args = ap.parse_args(argv)

    bench = Benchmark()
    controls = bench.deployment_module(
        bench.cell(args.workload)["config"]).Deployment.controls()
    for k, seed in enumerate(args.seeds):
        runs = [("program", None)]
        if k < args.control_seeds:
            runs += list(controls.items())
        for kind, patch in runs:
            line = readings(args.workload, seed, args.seconds, kind, patch)
            if line is None:
                return 1
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
