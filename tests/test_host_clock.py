"""The one buffer on the profiler's clock, and the collector's pauses.

``spans.recorded()`` gives its times on the clock the profiler stamps its
host events with: read back from a ``jax.profiler`` trace (its events
through ``benchmarks/harness/xplane.py``, its session's start from the
``Task Environment`` plane), every span agrees with its own copy on the
``/host:CPU`` plane.  ``observability.collector`` observes every pass of
generations 1 and 2, and spans it only inside a record window.
"""

import gc
import glob
import os
import time

import jax
import pytest
from jax.profiler import ProfileData

from benchmarks.harness import xplane
from paddle_tpu import runtime as rt
from paddle_tpu.observability import collector, get_registry, spans

NAMES = ("clock.outer", "clock.inner", "clock.leaf")


def _trace(tmp_path, body, after=lambda: None):
    """``body`` under a profiler session, then ``after`` once the session
    has ended and before a span notices it: (``recorded()`` at the
    session's end, ``recorded()`` after ``after``, the file)."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
        got = spans.recorded()
    finally:
        jax.profiler.stop_trace()
    after()
    later = spans.recorded()
    with spans.span("clock.quiet"):      # notices the end: goes quiet
        pass
    assert not rt.HostTracer.enabled
    path = sorted(glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    return got, later, path


def test_recorded_lies_on_the_profilers_host_clock(tmp_path):
    def body():
        with spans.span("clock.outer", k=1):
            for i in range(3):
                with spans.span("clock.inner", i=i):
                    time.sleep(0.002)
                    with spans.span("clock.leaf"):
                        pass
    got, _, path = _trace(tmp_path, body)
    start = dict(next(p for p in ProfileData.from_file(path).planes
                      if p.name == "Task Environment").stats)
    origin = int(start["profile_start_time"])
    copies = {n: [] for n in NAMES}
    for plane, lines in xplane.read(path, lambda n: n == "/host:CPU"):
        for _, events in lines:
            for n, _, s, e in events:
                if n in copies:
                    copies[n].append((origin + round(s * 1e9),
                                      origin + round(e * 1e9)))
    mine = {n: sorted((t0, t1) for m, t0, t1, _, _ in got if m == n)
            for n in NAMES}
    assert [len(mine[n]) for n in NAMES] == [1, 3, 3]
    for n in NAMES:
        assert len(copies[n]) == len(mine[n]), n
        for (a0, a1), (b0, b1) in zip(mine[n], sorted(copies[n])):
            assert abs(a0 - b0) < 50_000 and abs(a1 - b1) < 50_000, \
                (n, a0 - b0, a1 - b1)


def test_recorded_between_two_anchors_keeps_durations(monkeypatch):
    """Two anchors of a wall clock that runs a part in ten thousand fast:
    a span's times are laid on it by the line between them."""
    spans.start_recording()
    try:
        with spans.span("clock.outer"):
            time.sleep(0.002)
        m0, w0 = spans._window_anchor
        (_, t0, t1, _, _, _), = rt.HostTracer.events()
        m1 = rt.now_ns()
        monkeypatch.setattr(spans, "_anchor",
                            lambda: (m1, w0 + round((m1 - m0) * 1.0001)))
        (name, s, e, _, _), = spans.recorded()
    finally:
        rt.HostTracer.disable()
        rt.HostTracer.clear()
    assert name == "clock.outer"
    assert s == w0 + round((t0 - m0) * 1.0001)
    assert e - s == pytest.approx((t1 - t0) * 1.0001, abs=2)


def test_a_collection_is_observed_always_and_spanned_in_a_window(tmp_path):
    collector.install()
    collector.install()
    assert gc.callbacks.count(collector._on_gc) == 1
    hist = get_registry().get("runtime.gc.pause_seconds")

    def count():
        return hist.summary(generation="1")["count"]

    gc.disable()
    try:
        assert not rt.HostTracer.enabled
        n0 = count()
        gc.collect(1)                     # no window: observed, no span
        gc.collect(0)                     # generation 0: neither
        assert count() == n0 + 1

        def body():
            with spans.span("clock.outer"):
                gc.collect(1)
        # once the session has ended, before a span notices: no span
        got, after, _ = _trace(tmp_path, body, lambda: gc.collect(1))
    finally:
        gc.enable()
    assert count() == n0 + 3
    for rec in (got, after):
        runs = [(s, e, a) for n, s, e, _, a in rec if n == "runtime.gc"]
        assert len(runs) == 1 and runs[0][2] == {"generation": "1"}
    (outer,) = [(s, e) for n, s, e, _, _ in got if n == "clock.outer"]
    assert outer[0] <= runs[0][0] <= runs[0][1] <= outer[1]


def test_an_observation_that_finds_the_lock_held_waits_for_the_next():
    collector.install()
    hist = get_registry().get("runtime.gc.pause_seconds")
    n0 = hist.summary(generation="2")["count"]
    gc.disable()
    try:
        with hist._lock:                  # a reader inside its snapshot
            gc.collect(2)
        assert hist.summary(generation="2")["count"] == n0
        gc.collect(2)
    finally:
        gc.enable()
    assert hist.summary(generation="2")["count"] == n0 + 2
