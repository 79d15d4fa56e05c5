#!/usr/bin/env python3
"""Smoke run of the system's main path on a TPU, through its user entry points.

    python3 chip_smoke.py [--seed 0]       # phases A and B on one chip
    python3 chip_smoke.py --chips 4        # phase C only, on four chips

Phase A, data plane: the vSwarm MapReduce (``core/workloads.py`` MR sizes:
8 mappers x 240 MiB device-resident inputs, 8 x 8 MiB shuffle slices per
mapper, 8 reducers) through ``WorkflowEngine``, once per medium (xdt, s3,
elasticache).  Results must equal a numpy reference on every medium, no
buffer may leak, every invocation id runs at most once, and under xdt every
pulled slice must still be a ``jax.Array`` on the chip.

Phase B, serving: ``DisaggregatedServer`` with the full smollm-360m config
(2 decode pods, ``max_batch=4``, ``max_len=1024``, 8 requests of 512-token
prompts, 16 new tokens), driven through ``launch.serve.serve_disagg`` on
the xdt and staged handoffs.  Every request completes on both, the
generations are identical, each handoff moves the full KV cache, and
``kernels.ops.xdt_pull`` (the Mosaic kernel) reproduces ``kernels/ref.py`` on
one handoff's cache rows.

Phase C, four chips: a 240 MiB payload on device 0 is pulled onto each of
devices 1-3 through ``TransferEngine("xdt").get(ref, sharding=...)`` and,
for comparison, through the s3 medium; then the p2p, scatter, gather and
broadcast patterns of ``core/patterns.py`` run on a 4-device mesh with
8 MiB objects.  Bytes must match a numpy reference and each pulled array
must live on its own device.

Payloads and weights are made on the device from ``--seed``.  Times printed
are one run of a smoke, not a benchmark.  Each phase asserts its own
results; if any fails, or if JAX finds no TPU, the script exits non-zero and
prints no result line.  Otherwise the last line of stdout is one JSON object
naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

MIB = 1 << 20


class CompileStats:
    """Backend compiles (seconds, per jitted function) and persistent-cache
    hits/writes, read from ``jax.monitoring`` events."""

    def __init__(self):
        self.seconds = 0.0
        self.by_fn: dict = {}
        self.hits = 0
        self.writes = 0

    def install(self) -> "CompileStats":
        from jax import monitoring

        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)
        return self

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            n, s = self.by_fn.get(kw.get("fun_name", "?"), (0, 0.0))
            self.by_fn[kw.get("fun_name", "?")] = (n + 1, s + duration)

    def of(self, word: str):
        """(compiles, seconds) of the jitted functions whose name holds ``word``."""
        hits = [v for k, v in self.by_fn.items() if word in k]
        return sum(n for n, _ in hits), sum(s for _, s in hits)


def _check(ok, what) -> None:
    """A failed check raises (unlike ``assert``, which ``python -O`` drops)."""
    if not ok:
        raise AssertionError(what)


def _peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


# ---------------------------------------------------------------- phase A


MEDIA = ("xdt", "s3", "elasticache")


def phase_a(dev, *, input_bytes=None, slice_bytes=None, seed=0):
    """MapReduce through the workflow engine on each medium; the byte sizes
    default to the vSwarm MR deployment's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import WorkflowEngine
    from repro.core import workloads as wl

    M, R = wl.MR_M, wl.MR_R
    slice_bytes = slice_bytes or wl.MR_SLICE_BYTES
    input_bytes = input_bytes or wl.MR_INPUT_BYTES
    rows, width = input_bytes // slice_bytes, slice_bytes // 4   # int32 rows
    pad = (-rows) % R

    @jax.jit
    def make_inputs(key):
        return [jax.random.randint(k, (rows, width), 0, 1000, jnp.int32)
                for k in jax.random.split(key, M)]

    @jax.jit
    def map_fn(x):                      # row r goes to reducer r % R
        return jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, R, width).sum(0)

    @jax.jit
    def reduce_fn(*slices):
        return sum(slices[1:], slices[0])

    inputs = jax.block_until_ready(make_inputs(jax.random.PRNGKey(seed)))
    _check(all(x.devices() == {dev} for x in inputs), "inputs are not on the chip")
    host = [np.asarray(x) for x in inputs]
    want = [sum(h[j::R].sum(0, dtype=np.int64) for h in host) for j in range(R)]
    del host
    jax.block_until_ready(reduce_fn(*map_fn(inputs[0])[:M]))   # warm compiles

    results = {}
    for medium in MEDIA:
        wf = WorkflowEngine(backend=medium)
        pulled = []

        def mapper(ctx, x):
            parts = map_fn(x)
            return [ctx.put(parts[j], n_retrievals=1) for j in range(R)]

        def reducer(ctx, refs):
            slices = [ctx.get(r) for r in refs]
            pulled.extend(slices)
            return reduce_fn(*slices)

        def driver(ctx, xs):
            ref_matrix = ctx.scatter("mapper", xs)
            return [ctx.invoke("reducer", [row[j] for row in ref_matrix])
                    for j in range(R)]

        wf.register("mapper", mapper)
        wf.register("reducer", reducer)
        wf.register("driver", driver)
        t0 = time.perf_counter()
        out = jax.block_until_ready(wf.run("driver", inputs))
        wall = time.perf_counter() - t0

        got = [np.asarray(o) for o in out]
        _check(all(np.array_equal(g, w) for g, w in zip(got, want)),
               f"{medium}: reducer outputs differ from the numpy reference")
        wf.assert_at_most_once()
        _check(wf.executed_count("mapper") == M and wf.executed_count("reducer") == R,
               f"{medium}: wrong number of mapper/reducer invocations")
        leaked = wf.transfer.registry.stats().bytes_in_use
        _check(leaked == 0 and len(wf.transfer.service) == 0,
               f"{medium}: {leaked} registry bytes / "
               f"{len(wf.transfer.service)} service objects left")
        _check(len(pulled) == M * R, f"{medium}: {len(pulled)} slices pulled")
        if medium == "xdt":
            off = [p for p in pulled
                   if not isinstance(p, jax.Array) or p.devices() != {dev}]
            _check(not off, f"xdt: {len(off)} pulled slices left the device")
        results[medium] = got
        print(f"phase A [{medium}]: ok  {M} mappers x {input_bytes / MIB:g} MiB, "
              f"{M * R} slices x {slice_bytes / MIB:g} MiB, {R} reducers; "
              f"results == numpy reference; 0 bytes leaked; at-most-once; "
              f"wall {wall} s (one smoke run, not a benchmark); "
              f"peak_bytes_in_use {_peak_bytes(dev)}", flush=True)
        del pulled, out
    first = results[MEDIA[0]]
    for medium in MEDIA[1:]:
        _check(all(np.array_equal(a, b) for a, b in zip(first, results[medium])),
               f"{medium} results differ from {MEDIA[0]}")
    print(f"phase A: ok  identical results on {', '.join(MEDIA)}", flush=True)


# ---------------------------------------------------------------- phase B


def phase_b(dev, cfg, stats, *, max_len=1024, n_requests=8, prompt_len=512,
            new_tokens=16, seed=0):
    """Disaggregated serving on both handoffs, then the pull kernel."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.kernels import ref as kref
    from repro.launch.serve import make_prompts, serve_disagg
    from repro.models import cache_shapes, init_params

    params = init_params(cfg, jax.random.PRNGKey(seed))
    prompts = make_prompts(cfg.vocab, n_requests, prompt_len, seed)
    cache_bytes = sum(int(np.prod(shape)) * jnp.dtype(dt).itemsize
                      for shape, _, dt in cache_shapes(cfg, 1, max_len).values())
    gens = {}
    for backend in ("xdt", "staged"):
        t0 = time.perf_counter()
        srv, done = serve_disagg(
            cfg, params, prompts, backend=backend, decode_pods=2, max_batch=4,
            max_len=max_len, new_tokens=new_tokens,
        )
        wall = time.perf_counter() - t0
        rep = srv.handoff_report()
        _check(len(done) == n_requests, (backend, len(done)))
        _check(all(len(r.generated) == new_tokens for r in done.values()),
               f"{backend}: a request generated the wrong number of tokens")
        _check(rep["handoffs"] == n_requests, (backend, rep))
        _check(rep["avg_cache_bytes"] == cache_bytes, (rep, cache_bytes))
        gens[backend] = [done[r].generated for r in sorted(done)]
        print(f"phase B [{backend}]: ok  {cfg.name} {cfg.n_layers}L, "
              f"{n_requests} requests x {prompt_len} prompt tokens -> "
              f"{new_tokens} new tokens, all completed; {rep['handoffs']:.0f} "
              f"handoffs of {cache_bytes} B; wall {wall} s incl. compile "
              f"(one smoke run, not a benchmark); "
              f"peak_bytes_in_use {_peak_bytes(dev)}", flush=True)
    _check(gens["xdt"] == gens["staged"], "generations differ between handoffs")
    print(f"phase B: ok  xdt and staged generations identical "
          f"(first: {gens['xdt'][0]})", flush=True)
    n_pre, s_pre = stats.of("prefill")
    n_dec, s_dec = stats.of("decode")
    print(f"phase B: compiled prefill shapes {n_pre} ({s_pre} s), "
          f"decode shapes {n_dec} ({s_dec} s)", flush=True)

    # the Mosaic pull kernel on one handoff's cache rows
    _, cache = srv.prefill_pod.prefill(params, {"tokens": jnp.asarray(prompts[0])[None]})
    k = cache["k"]
    rows = k.reshape(-1, k.shape[-2] * k.shape[-1])            # (L*T, KV*hd)
    fallbacks = sum(ops.FALLBACKS.values())
    mode = ops.kernel_mode()
    if dev.platform == "tpu":
        _check(mode == "mosaic", mode)
        hlo = jax.jit(lambda r: ops.xdt_pull(r, out_dtype=jnp.float32)).lower(rows)
        _check("tpu_custom_call" in hlo.as_text(), "xdt_pull did not lower to Mosaic")
    out = ops.xdt_pull(rows, out_dtype=jnp.float32)
    _check(np.array_equal(np.asarray(out),
                          np.asarray(kref.xdt_pull_ref(rows, None, jnp.float32))),
           "xdt_pull cast differs from the reference")
    x32 = rows.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x32), axis=1) / 127.0 + 1e-12
    q = jnp.round(x32 / scale[:, None]).astype(jnp.int8)
    deq = np.asarray(ops.xdt_pull(q, scale, out_dtype=jnp.bfloat16), np.float32)
    deq_ref = np.asarray(kref.xdt_pull_ref(q, scale, jnp.bfloat16), np.float32)
    np.testing.assert_allclose(deq, deq_ref, rtol=2 ** -7, atol=0)   # one bf16 ulp
    _check(sum(ops.FALLBACKS.values()) == fallbacks, dict(ops.FALLBACKS))
    print(f"phase B: ok  ops.xdt_pull [{mode}] on one handoff's K rows "
          f"{tuple(rows.shape)} {rows.dtype}: cast == ref, int8 dequant == ref "
          f"(max |diff| {float(np.max(np.abs(deq - deq_ref)))}); "
          f"jnp fallbacks {dict(ops.FALLBACKS)}", flush=True)


# ---------------------------------------------------------------- phase C


def phase_c(devs, *, payload_bytes=240 * MIB, object_bytes=8 * MIB, seed=0):
    """Cross-chip pulls onto each device, and the collective patterns."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from repro.core.patterns import build_pattern_fn
    from repro.core.transfer import TransferEngine
    from repro.launch.mesh import make_host_mesh

    src = devs[0]
    make = jax.jit(lambda key: jax.random.bits(key, (payload_bytes // 4,), jnp.uint32),
                   out_shardings=SingleDeviceSharding(src))
    payload = jax.block_until_ready(make(jax.random.PRNGKey(seed)))
    want = np.asarray(payload)
    for medium in ("xdt", "s3"):
        eng = TransferEngine(medium)
        ref = eng.put(payload, n_retrievals=len(devs) - 1)
        for k in range(1, len(devs)):
            t0 = time.perf_counter()
            out = jax.block_until_ready(
                eng.get(ref, sharding=SingleDeviceSharding(devs[k])))
            wall = time.perf_counter() - t0
            _check(out.devices() == {devs[k]}, (medium, k, out.devices()))
            _check(np.array_equal(np.asarray(out), want), (medium, k))
            print(f"phase C [{medium}]: ok  {payload_bytes / MIB:g} MiB from "
                  f"device 0 ({src}) -> k={k}: result on {devs[k]}, bytes "
                  f"identical; wall {wall} s (one smoke run, not a benchmark)",
                  flush=True)
        _check(eng.registry.stats().bytes_in_use == 0 and len(eng.service) == 0,
               f"{medium}: payload left behind after its last pull")

    n = len(devs)
    mesh = make_host_mesh(data=1, model=n)
    # make_mesh may order the devices along the ICI ring; the set must match
    _check(set(mesh.devices.flat) == set(devs), mesh.devices)
    rows = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(seed + 1), (n, object_bytes // 4)),
        NamedSharding(mesh, P("model")))
    xs = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(seed + 2), (n, n, object_bytes // 4 // n)),
        NamedSharding(mesh, P("model")))
    h, hs = np.asarray(rows), np.asarray(xs)
    cases = {
        "1-1": (dict(src=0, dst=n - 1), rows, lambda o: np.array_equal(o[n - 1], h[0])),
        "scatter": (dict(src=0), xs, lambda o: np.array_equal(o, hs[0])),
        "gather": (dict(dst=1), rows, lambda o: np.array_equal(o[1], h)),
        "broadcast": (dict(src=2 % n), rows,
                      lambda o: all(np.array_equal(r, h[2 % n]) for r in o)),
    }
    for pattern, (kw, x, check) in cases.items():
        out = jax.block_until_ready(build_pattern_fn(mesh, "model", pattern, **kw)(x))
        _check(len(out.devices()) == n, (pattern, out.devices()))
        _check(check(np.asarray(out)), f"pattern {pattern} differs from numpy")
        print(f"phase C [{pattern}]: ok  {kw} on a {n}-device mesh, "
              f"{object_bytes / MIB:g} MiB objects == numpy", flush=True)


# ---------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip phase C")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: no accelerator: {e}", file=sys.stderr)
        return 1
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {devs[0].platform}); "
              "this smoke runs only on the chip", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX sees {len(devs)}", file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    stats = CompileStats().install()
    print(f"device: {devs[0].device_kind} x {len(devs)}; compile cache "
          f"{cache_dir} ({entries} entries at start)", flush=True)

    if args.chips == 4:
        phases = {"C": lambda: phase_c(devs[:4], seed=args.seed)}
    else:
        phases = {
            "A": lambda: phase_a(devs[0], seed=args.seed),
            "B": lambda: phase_b(devs[0], get_config("smollm_360m"), stats,
                                 seed=args.seed),
        }
    failed = []
    for name, run in phases.items():
        try:
            run()
        except Exception:
            traceback.print_exc()
            print(f"phase {name}: FAILED", flush=True)
            failed.append(name)
    top = sorted(stats.by_fn.items(), key=lambda kv: -kv[1][1])[:3]
    print(f"compile: {stats.seconds} s in backend compiles; persistent cache "
          f"{stats.hits} hits, {stats.writes} writes; longest: "
          + ", ".join(f"{fn} x{n} {sec:.3f} s" for fn, (n, sec) in top), flush=True)
    if failed:
        print(f"chip_smoke: phases {failed} failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
