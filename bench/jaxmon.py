"""Compile counts and device memory, as JAX reports them.

`CompileCounter` is a copy of ``chip_smoke.CompileCounter``: backend
compiles and persistent-cache hits, from JAX's monitoring events.  A cache
hit still reports a (short) backend-compile event, so a window that only
loads programs from the cache shows compiles and as many hits.
"""
from __future__ import annotations


class CompileCounter:
    def __init__(self, jax):
        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.n, self.seconds, self.cache_hits

    def since(self, snap) -> str:
        n, s, h = snap
        return (f"backend compiles {self.n - n} ({self.seconds - s:.1f} s, "
                f"{self.cache_hits - h} from the persistent cache)")


def peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))
