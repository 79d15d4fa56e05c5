"""One run of one cell: set-up, the measured window, the check, the result.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs :func:`main`.  It refuses (exit 1, no result line) where JAX finds no
TPU, fewer chips than the cell asks for, or a device kind without published
peaks.  Otherwise the last line of stdout is the result, and the last lines
of stderr are the numbers compared, each beside its limit.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import shutil
import sys
import threading
import time
import traceback
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

from .registry import ROOT, Benchmark

#: seconds at the end of the window that a ``--trace 1`` run traces
TRACE_SECONDS = 3.0
#: open loop: how long the load loop waits, after the last arrival, for the
#: requests still in flight
DRAIN_SECONDS = 60.0
#: a load loop that has not come round for this long is stalled, and where
#: it was is printed: this many of its innermost frames
STALL_S = 0.25
STACK_FRAMES = 10


@dataclasses.dataclass
class Context:
    """What a deployment gets from the harness."""

    seed: int
    spans: Any
    device: Any
    peaks: Optional[dict]

    def rng(self, stream: int):
        import numpy as np

        return np.random.default_rng([self.seed, stream])

    def jax_key(self, stream: int):
        """A threefry key from the seed; any seed that fits 64 bits."""
        import jax.numpy as jnp
        import numpy as np

        state = np.random.SeedSequence([self.seed, stream]).generate_state(2)
        return jnp.asarray(state, dtype=jnp.uint32)


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""

    cell: dict
    seconds: float
    setup_s: float
    window: Any                   # drivers.Window
    spans: Any                    # spans.Spans
    layer: dict                   # what the deployment recorded
    peaks: Optional[dict]
    trace: Any = None             # trace.Reduced, --trace 1 only
    trace_bounds: Optional[Tuple[float, float]] = None   # on perf_counter


class Tracer:
    """Starts the profiler ``start`` seconds into the window and stops it
    ``length`` seconds later; the traced part is the ``bench.window`` span.
    The runner traces the window's last seconds, so that writing the trace
    out stalls the drain and not the window."""

    def __init__(self, spans, out_dir: Path, start: float, length: float):
        self.spans, self.dir = spans, out_dir
        self.start, self.stop_at = start, start + length
        self.state = "idle"
        self.ann = None
        self.bounds: Optional[Tuple[float, float]] = None

    def poll(self, elapsed: float) -> None:
        if self.state == "idle" and elapsed >= self.start:
            import jax
            from jax.profiler import TraceAnnotation

            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # no event per Python call
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
            self.ann = TraceAnnotation("bench.window")
            self.ann.__enter__()
            self.spans.annotate = True
            self.t0 = time.perf_counter()
            self.state = "on"
        elif self.state == "on" and elapsed >= self.stop_at:
            self.finish()

    def finish(self) -> None:
        if self.state != "on":
            return
        import jax

        self.bounds = (self.t0, time.perf_counter())
        self.spans.annotate = False
        self.ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"

    def path(self) -> str:
        hits = sorted(self.dir.glob("plugins/profile/*/*.xplane.pb"))
        if not hits:
            raise FileNotFoundError(f"no trace under {self.dir}")
        return str(hits[-1])


class GcPauses:
    """Python's garbage collections, each as ``(generation, start, end)`` on
    ``time.perf_counter``, so that a stall of the load loop can be set
    beside them."""

    def __init__(self):
        self.pauses: List[Tuple[int, float, float]] = []
        self._t: Optional[float] = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((info["generation"], self._t, time.perf_counter()))
            self._t = None

    def within(self, t0: float, t1: float) -> float:
        """Seconds of collection inside ``[t0, t1]``."""
        return sum(max(0.0, min(e, t1) - max(s, t0)) for _, s, e in self.pauses)


class StallWatch:
    """Passes the load loop's ``poll`` on to ``hook``, and keeps where the
    loop was when it stalled: a thread looks every 20 ms, and where the loop
    has not polled for ``STALL_S`` seconds, and is not in the open loop's
    own sleep, it takes the main thread's Python stack once per stall."""

    def __init__(self, hook=None):
        self.hook = hook
        self.last = time.perf_counter()
        self.stalls: List[dict] = []          # {"at", "seconds", "stack"}
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watch, daemon=True)

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()
        self.poll_end()
        return False

    def poll_end(self) -> None:
        """The loop has ended: a stall still open ends here."""
        if self.stalls and self.stalls[-1]["seconds"] is None:
            self.stalls[-1]["seconds"] = time.perf_counter() - self.stalls[-1]["at"]

    def poll(self, elapsed: float) -> None:
        now = time.perf_counter()
        if self.stalls and self.stalls[-1]["at"] == self.last:
            self.stalls[-1]["seconds"] = now - self.last
        self.last = now
        if self.hook is not None:
            self.hook.poll(elapsed)

    def _watch(self) -> None:
        main = threading.main_thread().ident
        while not self._stop.wait(0.02):
            t = self.last
            if time.perf_counter() - t < STALL_S or (self.stalls and self.stalls[-1]["at"] == t):
                continue
            frame = sys._current_frames().get(main)
            if frame is None or frame.f_code.co_name == "run_open":
                continue                      # waiting for the next arrival
            stack = [f"{Path(f.filename).name}:{f.lineno} {f.name}"
                     for f in traceback.extract_stack(frame)[-STACK_FRAMES:]]
            self.stalls.append({"at": t, "seconds": None, "stack": stack})


def percentile(values: List[float], q: float) -> Optional[float]:
    """Linear-interpolated percentile; None for no values."""
    import numpy as np

    return float(np.percentile(np.asarray(values, float), q)) if values else None


def _peak_bytes(devs) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True, root: Path = ROOT,
             conf_override: Optional[dict] = None,
             mix_override: Optional[dict] = None,
             patch=None, inspect=None,
             err=sys.stderr) -> Optional[Dict[str, Any]]:
    """Run one cell; return the result line's object, or None where the run
    must not report (no chip, too few chips, unknown device).

    ``patch(deployment)`` runs before set-up and ``inspect(deployment,
    result)`` after the check: the control script and the tests use them to
    put a control or a fault in the program's place."""
    bench = Benchmark(root)
    cell = bench.cell(workload)
    # the persistent compilation cache lives in the checkout, at a fixed path
    cache_dir = Path(root) / ".jax_cache"
    if require_chip:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
        # the TPU runtime's own logs would go to a fixed path under /tmp
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(Path(root) / "src"))
    import jax

    from .compiles import CompileStats
    from .drivers import run_closed, run_open
    from .peaks import UnknownDevice, peaks_for
    from .spans import Spans
    from .traffic import make_schedule

    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"bench: JAX finds no accelerator: {e}", file=err)
        return None
    t_devices = time.perf_counter()
    if require_chip:
        if devs[0].platform != "tpu":
            print(f"bench: no TPU (JAX sees {devs[0].platform}); the benchmark "
                  "runs only on the chip", file=err)
            return None
        if len(devs) < cell["chips"]:
            print(f"bench: {workload} needs {cell['chips']} chips, JAX sees "
                  f"{len(devs)}", file=err)
            return None
    try:
        peaks = peaks_for(devs[0].device_kind)
    except UnknownDevice as e:
        if require_chip:
            print(f"bench: {e}", file=err)
            return None
        peaks = None
    devs = devs[:cell["chips"]]

    if require_chip:
        # every program, however quick to compile, so that set-up after the
        # first run of a cell in a checkout compiles nothing
        jax.config.update("jax_compilation_cache_dir", str(cache_dir))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    stats = CompileStats().install()
    spans = Spans()
    conf = {**bench.config(cell["config"]), **(conf_override or {})}
    mix = {**bench.mix(cell["traffic"]), **(mix_override or {})}
    schedule = make_schedule(mix, seconds)
    module = bench.deployment_module(cell["config"])
    ctx = Context(seed=seed, spans=spans, device=devs[0], peaks=peaks)
    dep = module.Deployment(conf, ctx)
    if patch is not None:
        patch(dep)
    t_setup = time.perf_counter()
    dep.setup(schedule)
    setup_s = time.perf_counter() - t_start
    setup_compiles = stats.snapshot()
    print(f"bench: set-up {setup_s} s (to the devices {t_devices - t_start} s, "
          f"deployment set-up {time.perf_counter() - t_setup} s); {stats.summary()}",
          file=err)

    tracer = None
    if trace:
        length = min(TRACE_SECONDS, seconds)
        tracer = Tracer(spans, Path(root) / ".bench_trace", seconds - length, length)
    gc_pauses = GcPauses()
    gc.callbacks.append(gc_pauses)
    try:
        with StallWatch(tracer) as watch:
            if schedule.loop == "closed":
                window = run_closed(dep, schedule, seconds, hook=watch)
            else:
                window = run_open(dep, schedule, seconds, drain_s=DRAIN_SECONDS,
                                  hook=watch)
    finally:
        gc.callbacks.remove(gc_pauses)
    if tracer is not None:
        tracer.finish()
    window_compiles = stats.compiles - setup_compiles[0]
    memory_peak = _peak_bytes(devs)
    layer = dep.layer_data()
    reduced = None
    if tracer is not None:
        from .trace import reduce

        reduced = reduce(tracer.path())
    _report_window(window, schedule, window_compiles, err)
    _report_stalls(window, spans, gc_pauses, watch, devs[0], err)

    checks = dep.check(window)
    never = sum(1 for r in window.records if r.done is None)
    wrong_len = sum(1 for r in window.records if r.done is not None
                    and r.expected_tokens is not None and r.tokens != r.expected_tokens)
    checks = list(checks) + [("never_completed", never, 0),
                             ("wrong_token_counts", wrong_len, 0)]
    correct = all(v <= lim for _, v, lim in checks)

    run = Run(cell=cell, seconds=seconds, setup_s=setup_s, window=window,
              spans=spans, layer=layer, peaks=peaks, trace=reduced,
              trace_bounds=tracer.bounds if tracer else None)
    metrics = {}
    for m in bench.metrics(workload, trace):
        value = bench.reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    result: Dict[str, Any] = {
        "correct": correct,
        "attempted": len(window.records),
        "failed": sum(1 for r in window.records if not r.ok),
        "metrics": metrics,
        "device": device,
    }
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in reduced.device_ops],
            "idle_gaps": [[n, s] for n, s in reduced.idle_gaps],
        }
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    if inspect is not None:
        inspect(dep, result)
    for n, v, lim in checks:
        print(f"check {n} = {v} (limit {lim}) {'ok' if v <= lim else 'FAIL'}",
              file=err)
    return result


def _report_window(window, schedule, window_compiles, err) -> None:
    recs = window.records
    done = [r for r in recs if r.done is not None]
    print(f"bench: window {window.t1 - window.t0} s, drained "
          f"{window.t_drained - window.t1} s after; {len(recs)} requests sent, "
          f"{len(done)} done; {window_compiles} compiles inside the window",
          file=err)
    late = [r.lateness for r in recs if r.lateness is not None]
    if late:
        print(f"bench: generator lateness p50 {percentile(late, 50)} s, "
              f"p95 {percentile(late, 95)} s, max {max(late)} s "
              f"(offered {schedule.rate_rps} req/s)", file=err)


def _report_stalls(window, spans, gc_pauses, watch, device, err) -> None:
    """Where the load loop stalled, the longest host span of each name with
    the collector's share of it, and the device allocator's counts."""
    for st in sorted(watch.stalls, key=lambda st: -(st["seconds"] or 0))[:3]:
        print(f"bench: the loop stalled {st['seconds']} s at {st['at'] - window.t0} s "
              f"into the window, in {' < '.join(reversed(st['stack']))}", file=err)
    longest: Dict[str, Tuple[float, float]] = {}      # name -> (start, seconds)
    for name, s, e in spans.within(window.t0, window.t_drained):
        if e - s > longest.get(name, (0.0, -1.0))[1]:
            longest[name] = (s, e - s)
    if longest:
        print("bench: longest host spans: " + ", ".join(
            f"{name} {d} s at {s - window.t0} s ({gc_pauses.within(s, s + d)} s "
            "garbage collection)" for name, (s, d) in sorted(longest.items())), file=err)
    got = [p for p in gc_pauses.pauses if p[1] >= window.t0]
    if got:
        g, s, e = max(got, key=lambda p: p[2] - p[1])
        print(f"bench: {len(got)} garbage collections in the window and drain, "
              f"{sum(p[2] - p[1] for p in got)} s in all; the longest {e - s} s "
              f"(generation {g}) at {s - window.t0} s", file=err)
    print(f"bench: device memory {device.memory_stats()}", file=err)


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                          t_start=t_start)
    except Exception:
        traceback.print_exc()
        print("bench: the run failed; no result", file=sys.stderr)
        return 1
    if result is None:
        return 1
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
