"""The program's spans, laid on the device's line by the wall clock.

``paddle_tpu.observability.spans.recorded()`` gives the program's spans on
the clock the profiler stamps its host events with, the wall clock.  The
trace file counts from its session's start (the ``profile_start_time`` of
its ``Task Environment`` plane), which the trace reduction does not keep: that
one constant is all that lies between the two.  It is read off the steps:
every ``serving.step`` lies inside the ``bench.eng_step`` that called it, so
the constant lies in ``[S.end - B.end, S.start - B.start]`` for each step,
paired in order from the end; the middle of the intersection is taken.  It
is the session's start on the wall clock, so it has to be a moment of this
run: a program whose spans are on another clock (a monotonic one) reads as
nothing, as does a laying that puts fewer than nine tenths of the steps in
the window inside a ``bench.eng_step``, each with one line on standard
error.

``idle_under`` is chip 0's idle time while the host was inside every span of
a name, by the trace's own ``idle_under``, as a share of the traced span:
unlike ``program_spans.idle_under`` it reads any span, a step's grandchild or
one outside the steps, and does not split the idle time among phases.
"""

from __future__ import annotations

import copy
import sys
import time

from .program_spans import BENCH_STEP, STEP, recorded_spans

INSIDE = 0.9


def _say(why):
    print(f"host_spans: {why}", file=sys.stderr, flush=True)


def origin(bench_steps, program_steps, run_began, now):
    """(constant, low, high): a program time less ``constant`` is the
    trace's; None where no constant lays the steps inside their calls or
    where it is no moment of ``[run_began, now]`` on the wall clock."""
    b = sorted(bench_steps, key=lambda s: s[1])
    p = sorted(program_steps, key=lambda s: s[1])
    n = min(len(b), len(p))
    if not n:
        _say(f"{len(b)} {BENCH_STEP} and {len(p)} {STEP}: nothing to lay")
        return None
    pairs = list(zip(b[len(b) - n:], p[len(p) - n:]))
    low = max(y[2] - x[2] for x, y in pairs)
    high = min(y[1] - x[1] for x, y in pairs)
    if low > high:
        _say(f"no constant puts every {STEP} inside its {BENCH_STEP}: "
             f"[{low:.6f}, {high:.6f}] is empty by {(low - high) * 1e6:.0f} us")
        return None
    c = 0.5 * (low + high)
    if not run_began <= c <= now:
        _say(f"the program's spans are not on the wall clock: the session "
             f"would have started at {c:.3f} s, outside this run "
             f"[{run_began:.3f}, {now:.3f}]")
        return None
    return c, low, high


def laid(obs, ctx):
    """The program's spans ``(name, start_s, end_s)`` on the trace's clock,
    found once a run and kept in ``obs``; None where they cannot be laid."""
    if "host_spans" not in obs:
        obs["host_spans"] = None
        trace, spans = obs.get("trace"), recorded_spans()
        if trace is None or not spans:
            return None
        now = time.time()
        began = now - (time.perf_counter() - ctx.t_start)
        bench = [s for s in trace.spans if s[0] == BENCH_STEP]
        got = origin(bench, [s for s in spans if s[0] == STEP], began, now)
        if got is None:
            return None
        c, low, high = got
        out = [(n, s - c, e - c) for n, s, e in spans]
        steps = [s for s in out if s[0] == STEP
                 and s[2] > trace.t0 and s[1] < trace.t1]
        inside = sum(any(b[1] <= s[1] and s[2] <= b[2] for b in bench)
                     for s in steps)
        if inside < INSIDE * len(steps):
            _say(f"{inside} of {len(steps)} {STEP} lie inside a {BENCH_STEP}")
            return None
        _say(f"session start {c:.6f} s on the wall clock from {len(steps)} "
             f"steps, feasible interval {(high - low) * 1e6:.1f} us wide")
        obs["host_spans"] = out
    return obs["host_spans"]


def idle_under(obs, ctx, span):
    """Chip 0's idle time while the host was inside ``span``, as a share of
    the traced span; percent."""
    trace = obs.get("trace")
    if trace is None or not trace.devices or trace.window_s <= 0:
        return None
    spans = laid(obs, ctx)
    if spans is None:
        return None
    on = copy.copy(trace)
    on.spans = [s for s in spans if s[0] == span]
    return 100.0 * on.idle_under(span) / trace.window_s
