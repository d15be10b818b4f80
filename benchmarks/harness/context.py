"""One run's context: arguments, clocks, the traced span and the result line."""

from __future__ import annotations

import contextlib
import json
import os
import shutil
import sys
import time

from .clocks import CompileClock, Phases
from .spec import ROOT


class Context:
    def __init__(self, cell, seed, seconds, trace, t_start, peaks=None,
                 trace_dir=None):
        self.cell, self.seed, self.seconds = cell, int(seed), float(seconds)
        self.trace_on = bool(trace)
        self.t_start = t_start
        self.peaks = peaks
        self.clock = CompileClock()
        self.phases = Phases(t_start, self.clock)
        self.trace_dir = trace_dir or os.path.join(ROOT, ".bench_trace")
        self.setup_s = None
        self.window_t0 = None
        self._tracing = False
        self._window_span = None
        self._compiles0 = None

    # -- spans ----------------------------------------------------------------
    def annotate(self, name):
        if self._tracing:
            from jax.profiler import TraceAnnotation
            return TraceAnnotation(name)
        return contextlib.nullcontext()

    def trace_seconds(self):
        return float(self.cell.traffic.get("trace_seconds", 3.0))

    def trace_begin(self):
        """Start the profiler and open the ``bench.window`` span."""
        import jax
        from jax.profiler import TraceAnnotation
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        jax.profiler.start_trace(self.trace_dir)
        self._tracing = True
        self._window_span = TraceAnnotation("bench.window")
        self._window_span.__enter__()

    def trace_close_span(self):
        if self._window_span is not None:
            self._window_span.__exit__(None, None, None)
            self._window_span = None

    def trace_end(self):
        """Stop the profiler and reduce what it wrote; the files go."""
        import jax
        from .trace import Trace, find_xplane
        self.trace_close_span()
        jax.profiler.stop_trace()
        self._tracing = False
        try:
            return Trace(find_xplane(self.trace_dir))
        finally:
            shutil.rmtree(self.trace_dir, ignore_errors=True)

    # -- the window -------------------------------------------------------------
    def open_window(self):
        """Set-up ends here: print its account and start the clock."""
        self.phases.mark("ready")
        now = time.perf_counter()
        self.setup_s = now - self.t_start
        snap = self.clock.snapshot()
        print("setup " + json.dumps({
            "setup_s": round(self.setup_s, 3), "phases": self.phases.rows,
            "compile_s": round(snap["compile_s"], 3),
            "cache_hits": snap["cache_hits"],
            "cache_misses": snap["cache_misses"]}), flush=True)
        self._compiles0 = self.clock.compiles
        self.window_t0 = time.perf_counter()
        return self.window_t0

    def compiles_in_window(self):
        return self.clock.compiles - self._compiles0


def device_info(chips):
    """The devices as JAX reports them, with the peak on the fullest chip."""
    import jax
    devs = jax.devices()[:chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks))}


def emit(correct, attempted, failed, metrics, device, compared, breakdown=None):
    """The numbers compared on stderr, then the one result line on stdout."""
    shown = {k: {"value": v, "limit": lim} for k, v, lim in compared}
    print("compared " + json.dumps(shown), file=sys.stderr, flush=True)
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = shown
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
