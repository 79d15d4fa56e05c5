"""Backend compiles and persistent-cache hits, read from ``jax.monitoring``."""
from __future__ import annotations

from typing import Dict, Tuple


class CompileStats:
    """Counts backend compiles (with seconds, per jitted function) and
    persistent-cache hits and misses.  ``install`` registers the listeners;
    JAX keeps them for the life of the process."""

    def __init__(self):
        self.compiles = 0
        self.seconds = 0.0
        self.by_fn: Dict[str, Tuple[int, float]] = {}
        self.hits = 0
        self.misses = 0

    def install(self) -> "CompileStats":
        from jax import monitoring

        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)
        return self

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration
            fn = kw.get("fun_name", "?")
            n, s = self.by_fn.get(fn, (0, 0.0))
            self.by_fn[fn] = (n + 1, s + duration)

    def snapshot(self) -> Tuple[int, float]:
        return self.compiles, self.seconds

    def summary(self, top: int = 4) -> str:
        worst = sorted(self.by_fn.items(), key=lambda kv: -kv[1][1])[:top]
        return (f"{self.compiles} backend compiles, {self.seconds} s; persistent "
                f"cache {self.hits} hits, {self.misses} misses; longest: "
                + ", ".join(f"{fn} x{n} {s:.3f} s" for fn, (n, s) in worst))
