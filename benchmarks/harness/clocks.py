"""Clocks, counters and the arithmetic of tails."""

from __future__ import annotations

import math
import time


class CompileClock:
    """Seconds XLA spent compiling (or fetching from the persistent cache) and
    the cache's hits and misses, from ``jax.monitoring``.  Copied from
    ``chip_smoke.CompileClock``; ``compiles`` counts backend compilations, so
    a window in which it does not move compiled nothing."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._evt)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _evt(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "cache_hits": self.hits, "cache_misses": self.misses}


class Phases:
    """The set-up account: wall seconds by phase, with compile seconds and
    cache traffic of each, printed on one line before the window."""

    def __init__(self, t0, clock):
        self.t0 = t0
        self.clock = clock
        self.rows = []
        self._last = t0
        self._snap = clock.snapshot()

    def mark(self, name):
        now = time.perf_counter()
        snap = self.clock.snapshot()
        self.rows.append({
            "phase": name, "wall_s": round(now - self._last, 3),
            "compile_s": round(snap["compile_s"] - self._snap["compile_s"], 3),
            "cache_hits": snap["cache_hits"] - self._snap["cache_hits"],
            "cache_misses": snap["cache_misses"] - self._snap["cache_misses"]})
        self._last, self._snap = now, snap


def percentile(values, q):
    """Nearest-rank percentile (the smallest value with at least q% at or
    below it); the tail of all requests, no interpolation."""
    if not values:
        return None
    s = sorted(values)
    return s[max(0, math.ceil(q / 100.0 * len(s)) - 1)]
