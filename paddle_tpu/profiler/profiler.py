"""Profiler core (reference: python/paddle/profiler/profiler.py:349 over
paddle/fluid/platform/profiler/profiler.h:47).

The reference merges a host tracer and a CUPTI device tracer into an event
tree and exports chrome traces + summary tables. Here the host side is the
native C++ tracer (paddle_tpu.runtime.HostTracer); the device side is
jax.profiler (XLA xplane, viewable in TensorBoard/Perfetto), started and
stopped in lockstep when ``targets`` includes TPU.
"""

from __future__ import annotations

import enum
import os
from collections import defaultdict
from typing import Callable, Iterable, Optional

from .. import runtime as rt


class ProfilerState(enum.Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3  # last RECORD step of a window: collect + return


class ProfilerTarget(enum.Enum):
    CPU = 0
    GPU = 1   # accepted for API parity; maps to the XLA device tracer
    TPU = 2
    CUSTOM_DEVICE = 3


def make_scheduler(*, closed: int, ready: int, record: int,
                   repeat: int = 0, skip_first: int = 0) -> Callable:
    """State machine over step numbers (mirror of profiler.py:79).

    skip_first steps CLOSED, then cycles of [closed CLOSED, ready READY,
    record RECORD (last returns RECORD_AND_RETURN)]; ``repeat=0`` = cycle
    forever.
    """
    if closed < 0 or ready < 0 or record <= 0:
        raise ValueError("make_scheduler: closed/ready >= 0 and record >= 1")
    cycle = closed + ready + record

    def scheduler(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat > 0 and s >= repeat * cycle:
            return ProfilerState.CLOSED
        pos = s % cycle
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def _default_scheduler(step: int) -> ProfilerState:
    return ProfilerState.RECORD  # profile everything between start and stop


def export_chrome_tracing(dir_name: str,
                          worker_name: Optional[str] = None) -> Callable:
    """on_trace_ready callback factory (≙ profiler.py:215)."""
    os.makedirs(dir_name, exist_ok=True)

    def handler(prof: "Profiler"):
        name = worker_name or f"host_{os.getpid()}"
        path = os.path.join(dir_name, f"{name}_step{prof.step_num}.json")
        rt.HostTracer.export_chrome_trace(path)
        prof._exported_paths.append(path)

    return handler


def _mismatch_counter():
    from ..observability import metrics as _obs
    return _obs.get_registry().counter(
        "profiler.record_event_mismatches",
        "RecordEvent.end() calls without a matching begin() "
        "(made no-ops instead of corrupting the tracer stack)")


class RecordEvent:
    """User-scoped host range (≙ python/paddle/profiler/utils.py:38).

    Begin/end are depth-guarded: ``end()`` without a matching
    ``begin()`` (including a double-``end()`` from explicit use plus
    ``__exit__``) is a no-op that warns and bumps the
    ``profiler.record_event_mismatches`` counter — an unmatched
    ``HostTracer.end()`` would otherwise pop someone ELSE's range off
    the per-thread tracer stack and silently corrupt the trace."""

    def __init__(self, name: str, event_type: str = "UserDefined"):
        self.name = name
        self.event_type = event_type
        # one entry per OPEN range: the trace generation it was opened
        # in (a plain depth int + single gen would let a re-begin()
        # inside a new window launder a stale open across the boundary)
        self._opens: list = []

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        # exiting a with-block whose range was already closed by an
        # explicit end() is the documented early-stop idiom — close
        # only if this instance still owns an open range, never warn
        if self._opens:
            self._pop_if_same_window()
        return False

    def _pop_if_same_window(self):
        """Pop the tracer range unless a record-window boundary since
        its begin() invalidated it (popping then would close an
        unrelated range from the NEW window)."""
        from ..observability import spans as _spans
        if self._opens.pop() == _spans.current_trace_generation():
            rt.HostTracer.end()
        else:
            _mismatch_counter().inc()

    def begin(self):
        # only ranges the tracer actually opened are tracked: a
        # begin() outside a profiling window pushes nothing, so a later
        # end() INSIDE a window must not pop an unrelated range
        if rt.HostTracer.enabled:
            from ..observability import spans as _spans
            self._opens.append(_spans.current_trace_generation())
            rt.HostTracer.begin(self.name)

    def end(self):
        if self._opens:
            self._pop_if_same_window()
            return
        # depth 0 with tracing OFF is the normal un-profiled path (the
        # paired begin() counted nothing) — only an in-window unmatched
        # end() is a caller bug worth warning about
        if rt.HostTracer.enabled:
            import warnings
            _mismatch_counter().inc()
            warnings.warn(
                f"RecordEvent({self.name!r}).end() without a matching "
                f"begin(); ignored", RuntimeWarning, stacklevel=2)


class _EventStat:
    __slots__ = ("count", "total_ns", "max_ns", "min_ns", "self_ns",
                 "instants")

    def __init__(self):
        self.count = 0
        self.total_ns = 0
        self.max_ns = 0
        self.min_ns = None
        self.self_ns = 0
        self.instants = 0

    def add(self, dur: int, self_ns: int):
        self.count += 1
        self.total_ns += dur
        self.self_ns += self_ns
        self.max_ns = max(self.max_ns, dur)
        self.min_ns = dur if self.min_ns is None else min(self.min_ns, dur)


class SummaryView:
    """Aggregated per-name host event table (≙ profiler_statistic.py).

    ``total`` for a name sums its ranges INCLUSIVE of children (so a
    parent scope double-counts its nested ranges there — that is the
    chrome-trace convention); ``self`` subtracts each range's DIRECT
    children, so the self column partitions wall time without double
    counting.  Instant events are tallied per name as zero-duration
    occurrences instead of being dropped.  Span attr suffixes
    (``name;k=v`` from ``observability.spans``) are stripped before
    aggregation, so 100 ``serving.prefill`` spans with distinct request
    ids land in ONE row, not 100."""

    def __init__(self, events):
        from ..observability.spans import parse_span_name
        self.stats = defaultdict(_EventStat)
        per_tid = defaultdict(list)
        for kind, t0, t1, tid, value, name in events:
            name = parse_span_name(name)[0]
            if kind == 0:  # range
                per_tid[tid].append((t0, t1, name))
            elif kind == 1:  # instant
                self.stats[name].instants += 1
        for ranges in per_tid.values():
            # sweep in start order (ties: widest first = parent first);
            # a stack entry is [t1, child_ns, t0, name] and child time
            # is charged to the DIRECT parent only
            stack = []

            def close(entry):
                t1, child_ns, t0, name = entry
                dur = t1 - t0
                self.stats[name].add(dur, max(dur - child_ns, 0))

            for t0, t1, name in sorted(ranges,
                                       key=lambda r: (r[0], -r[1])):
                while stack and stack[-1][0] <= t0:
                    close(stack.pop())
                if stack:
                    stack[-1][1] += t1 - t0
                stack.append([t1, 0, t0, name])
            while stack:
                close(stack.pop())

    def rows(self):
        out = []
        for name, s in sorted(self.stats.items(),
                              key=lambda kv: -kv[1].total_ns):
            out.append({
                "name": name, "calls": s.count,
                "total_ms": s.total_ns / 1e6,
                "self_ms": s.self_ns / 1e6,
                "avg_ms": (s.total_ns / s.count / 1e6) if s.count else 0.0,
                "max_ms": s.max_ns / 1e6,
                "min_ms": (s.min_ns or 0) / 1e6,
                "instants": s.instants,
            })
        return out

    def table(self) -> str:
        rows = self.rows()
        header = f"{'Name':<40}{'Calls':>8}{'Total(ms)':>12}" \
                 f"{'Self(ms)':>12}{'Avg(ms)':>12}" \
                 f"{'Max(ms)':>12}{'Min(ms)':>12}"
        lines = [header, "-" * len(header)]
        for r in rows:
            lines.append(
                f"{r['name'][:39]:<40}{r['calls']:>8}{r['total_ms']:>12.3f}"
                f"{r['self_ms']:>12.3f}"
                f"{r['avg_ms']:>12.3f}{r['max_ms']:>12.3f}{r['min_ms']:>12.3f}")
        return "\n".join(lines)


def load_profiler_result(path: str):
    """Load an exported chrome trace back as a list of event dicts."""
    import json
    with open(path) as f:
        return json.load(f)["traceEvents"]


class DeviceSummaryView:
    """Per-op DEVICE-time statistics parsed from the jax.profiler capture
    (analogue of ``python/paddle/profiler/profiler_statistic.py``'s
    kernel/op summary tables).  Aggregates the XLA op events on the
    device lanes of the chrome trace that jax writes next to the xplane
    dump."""

    def __init__(self, trace_dir: str):
        self.trace_dir = trace_dir
        self._events = self._load(trace_dir)

    @staticmethod
    def _load(trace_dir):
        import glob
        import gzip
        import json

        events = []
        for path in glob.glob(os.path.join(
                trace_dir, "**", "*.trace.json.gz"), recursive=True):
            with gzip.open(path, "rt") as f:
                data = json.load(f)
            raw = data.get("traceEvents", [])
            # pid -> process name from metadata events
            pid_names = {}
            for e in raw:
                if e.get("ph") == "M" and e.get("name") == "process_name":
                    pid_names[e.get("pid")] = \
                        e.get("args", {}).get("name", "")
            device_pids = {p for p, n in pid_names.items()
                           if any(k in n for k in
                                  ("TPU", "GPU", "device", "Device"))}
            for e in raw:
                if e.get("ph") != "X" or "dur" not in e:
                    continue
                if device_pids and e.get("pid") not in device_pids:
                    continue
                events.append(e)
        return events

    def rows(self):
        stats = {}
        for e in self._events:
            name = e.get("name", "?")
            dur = float(e.get("dur", 0.0))  # microseconds
            s = stats.setdefault(name, [0, 0.0, 0.0, float("inf")])
            s[0] += 1
            s[1] += dur
            s[2] = max(s[2], dur)
            s[3] = min(s[3], dur)
        total = sum(s[1] for s in stats.values()) or 1.0
        out = []
        for name, (calls, tot, mx, mn) in sorted(
                stats.items(), key=lambda kv: -kv[1][1]):
            out.append({
                "name": name, "calls": calls,
                "total_ms": tot / 1e3, "avg_ms": tot / calls / 1e3,
                "max_ms": mx / 1e3, "min_ms": mn / 1e3,
                "ratio": tot / total,
            })
        return out

    def table(self, limit: int = 30) -> str:
        rows = self.rows()[:limit]
        header = (f"{'Device op':<48}{'Calls':>8}{'Total(ms)':>12}"
                  f"{'Avg(ms)':>12}{'Ratio':>8}")
        lines = [header, "-" * len(header)]
        for r in rows:
            lines.append(
                f"{r['name'][:47]:<48}{r['calls']:>8}"
                f"{r['total_ms']:>12.3f}{r['avg_ms']:>12.3f}"
                f"{r['ratio']:>8.1%}")
        return "\n".join(lines)


class Profiler:
    """Reference-parity profiler driver.

    with Profiler(targets=[ProfilerTarget.CPU], scheduler=(2, 5)) as p:
        for batch in loader:
            train_step(batch)
            p.step()
    print(p.summary().table())
    """

    def __init__(self, *, targets: Optional[Iterable] = None,
                 scheduler=None, on_trace_ready: Optional[Callable] = None,
                 timer_only: bool = False, profile_memory: bool = False,
                 with_flops: bool = False):
        self.targets = list(targets or [ProfilerTarget.CPU])
        if scheduler is None:
            self.scheduler = _default_scheduler
        elif callable(scheduler):
            self.scheduler = scheduler
        else:  # (start, end) tuple like the reference
            start, end = scheduler
            self.scheduler = make_scheduler(
                closed=max(start - 1, 0), ready=1 if start >= 1 else 0,
                record=end - start, repeat=1)
        self.on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self.step_num = 0
        self.current_state = ProfilerState.CLOSED
        self._device_trace_dir = None
        self._device_tracing = False
        self._exported_paths: list = []
        self._events_snapshot = None

    # -- lifecycle --
    def start(self):
        self.current_state = self.scheduler(self.step_num)
        if self.current_state in (ProfilerState.RECORD,
                                  ProfilerState.RECORD_AND_RETURN):
            self._start_record()

    def stop(self):
        if self.current_state in (ProfilerState.RECORD,
                                  ProfilerState.RECORD_AND_RETURN):
            self._stop_record()
            if self.on_trace_ready is not None:
                self.on_trace_ready(self)

    def step(self):
        prev = self.current_state
        self.step_num += 1
        new = self.scheduler(self.step_num)
        recording = (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN)
        if prev in recording and new not in recording:
            self._stop_record()
            if self.on_trace_ready is not None:
                self.on_trace_ready(self)
        elif prev not in recording and new in recording:
            self._start_record()
        self.current_state = new

    def _start_record(self):
        from ..observability import spans as _spans
        _spans.start_recording()
        if not self.timer_only and any(
                t in (ProfilerTarget.TPU, ProfilerTarget.GPU,
                      ProfilerTarget.CUSTOM_DEVICE) for t in self.targets):
            import tempfile
            self._device_trace_dir = tempfile.mkdtemp(prefix="ptpu_xprof_")
            try:
                import jax
                jax.profiler.start_trace(self._device_trace_dir)
                self._device_tracing = True
            except Exception:
                self._device_tracing = False

    def _stop_record(self):
        rt.HostTracer.disable()
        self._events_snapshot = rt.HostTracer.events()
        if self._device_tracing:
            try:
                import jax
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._device_tracing = False

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- results --
    def events(self):
        return self._events_snapshot or rt.HostTracer.events()

    def summary(self) -> SummaryView:
        return SummaryView(self.events())

    def metrics(self) -> dict:
        """Snapshot of the process-wide observability registry
        (serving/train-step/kernel-dispatch instruments) — the
        always-on counters that complement the windowed event trace."""
        from ..observability import metrics as _obs
        return _obs.get_registry().snapshot()

    def export_merged_trace(self, path: str) -> dict:
        """Write the recorded host events, span attrs decoded into
        Perfetto ``args``, as one chrome trace at ``path``.  With a
        device target the spans are also in the profiler's own trace
        under ``device_trace_dir``, on one clock with the device's
        ``XLA Ops``: open that one to lay them over the device."""
        from ..observability.spans import merge_chrome_traces
        return merge_chrome_traces(path, host=self.events())

    def export_chrome_trace(self, path: str):
        rt.HostTracer.export_chrome_trace(path)
        self._exported_paths.append(path)

    @property
    def device_trace_dir(self):
        """Directory with the XLA xplane dump (TensorBoard-viewable)."""
        return self._device_trace_dir

    def device_summary(self) -> "DeviceSummaryView":
        """Per-op device-time table from the capture (reference
        profiler_statistic.py kernel summary).  Requires a device target
        in ``targets`` and a completed record window."""
        if self._device_trace_dir is None:
            raise RuntimeError(
                "device_summary(): no device capture — profile with "
                "targets=[ProfilerTarget.TPU] and complete a record step")
        return DeviceSummaryView(self._device_trace_dir)
