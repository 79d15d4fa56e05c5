"""§Perf hillclimb harness: lower a cell under named optimization variants
and record the roofline terms for each (hypothesis -> change -> measure).

Variants are config/optimizer knobs (all default-off, so the recorded
baseline is the paper-faithful implementation):

  loss_chunk     streamed cross-entropy (no (B,S,V) logits materialization)
  zero1          ZeRO-1 optimizer-state sharding over the data/pod axes
  seq_shard      Megatron sequence parallelism for inter-block activations
  moe_a2a        all-to-all expert dispatch (the paper's scatter/gather)

Usage (needs the 512-device flag, so run as a module, NOT under pytest):

    PYTHONPATH=src python -m benchmarks.hillclimb \
        --cell llama4_scout_17b_a16e:train_4k \
        --variants baseline,+moe_a2a,+loss_chunk,+zero1,combo
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse
import dataclasses
import json
import time
from typing import Any, Dict

from repro.configs import get_config
from repro.launch import dryrun as dr
from repro.launch.input_specs import SHAPE_CELLS, input_specs
from repro.launch.mesh import make_production_mesh
from repro.optim import OptConfig
from repro.train import make_train_step

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9


def apply_variant(cfg, variant: str):
    """Returns (cfg', zero1flag).  ``variant`` is a +-joined knob list."""
    zero1 = False
    for knob in variant.split("+"):
        knob = knob.strip()
        if knob in ("", "baseline"):
            continue
        if knob == "loss_chunk":
            cfg = dataclasses.replace(cfg, loss_chunk=512)
        elif knob == "zero1":
            zero1 = True
        elif knob == "seq_shard":
            cfg = dataclasses.replace(cfg, seq_shard_acts=True)
        elif knob == "moe_a2a":
            assert cfg.moe is not None, "moe_a2a needs an MoE arch"
            cfg = dataclasses.replace(
                cfg, moe=dataclasses.replace(cfg.moe, dispatch="a2a"))
        elif knob == "fsdp":
            cfg = dataclasses.replace(cfg, fsdp_params=True)
        elif knob == "combo":
            cfg = dataclasses.replace(cfg, loss_chunk=512, fsdp_params=True)
            if cfg.moe is not None:
                cfg = dataclasses.replace(
                    cfg, moe=dataclasses.replace(cfg.moe, dispatch="a2a"))
            zero1 = True
        else:
            raise ValueError(f"unknown knob {knob!r}")
    return cfg, zero1


_ORIG_BUILD_STEP = dr.build_step


def build_step_z(cfg, kind, mesh, specs, zero1):
    if kind == "train":
        step = make_train_step(cfg, mesh, OptConfig(zero1=zero1),
                               remat="full", donate=False)
        return step, (specs["params"], specs["opt_state"], specs["batch"])
    return _ORIG_BUILD_STEP(cfg, kind, mesh, specs)


def measure(arch: str, shape: str, variant: str, multi_pod=False) -> Dict[str, Any]:
    cfg0 = get_config(arch)
    cfg, zero1 = apply_variant(cfg0, variant)
    kind = SHAPE_CELLS[shape]["kind"]
    mesh = make_production_mesh(multi_pod=multi_pod)
    specs = input_specs(cfg, shape, mesh, zero1=zero1)
    step, args = build_step_z(cfg, kind, mesh, specs, zero1)
    t0 = time.time()
    with mesh:
        lowered = step.lower(*args)
        compiled = lowered.compile()
        ma = compiled.memory_analysis()
    # depth-extrapolated cost probes with the SAME variant knobs applied:
    # monkeypatch the probe-config factory (apply knobs on top of the probe
    # reductions) and the step builder (thread the zero1 flag through).
    orig_probe_cfg, orig_build = dr._probe_cfg, dr.build_step
    dr._probe_cfg = lambda c, L, chunked=False: apply_variant(
        orig_probe_cfg(cfg0, L, chunked=chunked), variant)[0]
    dr.build_step = lambda pcfg, pkind, pmesh, pspecs: build_step_z(
        pcfg, pkind, pmesh, pspecs, zero1)
    try:
        probes = dr.run_cost_probes(cfg, kind, shape, mesh)
    finally:
        dr._probe_cfg, dr.build_step = orig_probe_cfg, orig_build

    flops = probes["flops_per_device"]
    nbytes = probes["bytes_per_device"]
    coll = probes["collective_bytes_per_device"]
    t_c, t_m = flops / PEAK_FLOPS, nbytes / HBM_BW
    t_x = sum(coll.values()) / ICI_BW
    terms = {"compute": t_c, "memory": t_m, "collective": t_x}
    return {
        "arch": arch, "shape": shape, "variant": variant,
        "t_compute_s": t_c, "t_memory_s": t_m, "t_collective_s": t_x,
        "bound_s": max(terms.values()),
        "bottleneck": max(terms, key=terms.get),
        "peak_mem_GiB": (ma.argument_size_in_bytes + ma.output_size_in_bytes
                         + ma.temp_size_in_bytes - ma.alias_size_in_bytes) / 2**30,
        "collective_bytes": coll,
        "flops_per_device": flops,
        "bytes_per_device": nbytes,
        "wall_s": round(time.time() - t0, 1),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True, help="arch:shape")
    ap.add_argument("--variants", default="baseline")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    arch, shape = args.cell.split(":")
    results = []
    for v in args.variants.split(","):
        print(f"[hillclimb] {arch}:{shape} variant={v} ...", flush=True)
        try:
            rec = measure(arch, shape, v)
        except Exception as e:
            import traceback
            rec = {"arch": arch, "shape": shape, "variant": v,
                   "error": f"{type(e).__name__}: {e}",
                   "trace": traceback.format_exc()[-1500:]}
        results.append(rec)
        if "error" in rec:
            print(f"    FAILED {rec['error'][:200]}")
        else:
            print(f"    C={rec['t_compute_s']*1e3:.0f}ms M={rec['t_memory_s']*1e3:.0f}ms "
                  f"X={rec['t_collective_s']*1e3:.0f}ms bound={rec['bottleneck']}"
                  f" peak={rec['peak_mem_GiB']:.1f}GiB", flush=True)
    # write next to the other harness artifacts (benchmarks.common.RESULTS_DIR
    # is absolute, so invocation cwd doesn't matter); imported lazily because
    # this module must set XLA_FLAGS before anything imports jax
    from .common import RESULTS_DIR

    out = args.out or os.path.join(
        RESULTS_DIR, f"hillclimb_{arch}_{shape}.json"
    )
    if os.path.dirname(out):
        os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(results, f, indent=1)
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
