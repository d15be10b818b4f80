"""The program's own spans, read beside the device's line.

The trace reduction keeps only the benchmark's ``bench.*`` annotations and
deletes the ``.xplane.pb``, so the engine's spans are taken from the program's
one buffer (``paddle_tpu.observability.spans.recorded()``: the spans of the
profiler session, on the host tracer's monotonic clock) and laid on the
trace's clock here.

Alignment: every ``bench.eng_step`` of the trace encloses exactly one
``serving.step``.  They are paired in order (from the end where the counts
differ); a pair whose durations differ by more than ``TOLERANCE_S`` says
nothing about the offset and is left out, and nine tenths have to remain.
The offset lies in ``[max(B.start - S.start), min(B.end - S.end)]``; its
midpoint is taken.  An interval empty by more than the tolerance, or fewer
than ten pairs, reads as nothing, with one line on standard error saying why.

A program without ``recorded()`` (the parent of the PR that brought this
file) reads as nothing too.
"""

from __future__ import annotations

import copy
import sys

STEP = "serving.step"
BENCH_STEP = "bench.eng_step"
# the direct children of a step that have an idle share of their own
PHASES = ("serving.admit", "serving.prefill", "serving.plan",
          "serving.decode_block", "serving.harvest")
UNATTRIBUTED = "_unattributed_"
TOLERANCE_S = 200e-6
MIN_PAIRS = 10


def _say(why):
    print(f"program_spans: {why}", file=sys.stderr, flush=True)


def recorded_spans():
    """[(name, start_s, end_s)] on the program's clock, or None where the
    program has no such buffer."""
    try:
        from paddle_tpu.observability.spans import recorded
    except ImportError:
        return None
    return [(n, t0 * 1e-9, t1 * 1e-9) for n, t0, t1, _tid, _attrs in recorded()]


def offset(bench_steps, program_steps):
    """(theta, low, high, pairs): ``theta`` added to a program time gives the
    trace's; None where the pairing proves nothing."""
    b = sorted(bench_steps, key=lambda s: s[1])
    p = sorted(program_steps, key=lambda s: s[1])
    n = min(len(b), len(p))
    pairs = [(x, y) for x, y in zip(b[len(b) - n:], p[len(p) - n:])
             if abs((x[2] - x[1]) - (y[2] - y[1])) <= TOLERANCE_S]
    if len(pairs) < MIN_PAIRS or len(pairs) < 0.9 * max(len(b), len(p)):
        _say(f"{len(pairs)} of {len(b)} {BENCH_STEP} and {len(p)} {STEP} "
             f"pair within {TOLERANCE_S * 1e6:.0f} us: no offset")
        return None
    low = max(x[1] - y[1] for x, y in pairs)
    high = min(x[2] - y[2] for x, y in pairs)
    if low - high > TOLERANCE_S:
        _say(f"no offset puts every {STEP} inside its {BENCH_STEP}: "
             f"[{low:.6f}, {high:.6f}] is empty by {(low - high) * 1e6:.0f} us")
        return None
    return 0.5 * (low + high), low, high, len(pairs)


def aligned(trace, spans):
    """The program's spans on the trace's clock; None where they cannot be
    laid there."""
    got = offset([s for s in trace.spans if s[0] == BENCH_STEP],
                 [s for s in spans if s[0] == STEP])
    if got is None:
        return None
    theta, low, high, pairs = got
    _say(f"offset {theta:.6f} s from {pairs} pairs, feasible interval "
         f"{(high - low) * 1e6:.1f} us wide")
    return [(n, s + theta, e + theta) for n, s, e in spans]


def step_children(spans):
    """The direct children of every ``serving.step``: spans inside a step
    and inside no other span of that step."""
    order = sorted(spans, key=lambda s: (s[1], -s[2]))
    out, step, last = [], None, None
    for sp in order:
        if sp[0] == STEP:
            step, last = sp, None
        elif step is not None and sp[1] >= step[1] and sp[2] <= step[2]:
            if last is None or sp[1] >= last[2]:
                out.append(sp)
                last = sp
    return out


def idle_by_phase(obs):
    """({phase: idle seconds of chip 0 under it}, all of its idle seconds),
    found once a run and kept in ``obs``: each gap between the device's
    operations split by its overlap with the steps' direct children, by the
    trace's own ``idle_under`` over those children in the place of its
    ``bench.*`` spans.  None where there is nothing to split."""
    if "idle_by_phase" not in obs:
        obs["idle_by_phase"] = None
        trace, spans = obs.get("trace"), recorded_spans()
        if trace is not None and trace.devices and trace.window_s > 0 and spans:
            spans = aligned(trace, spans)
            if spans is not None:
                laid = copy.copy(trace)
                laid.spans = step_children(spans)
                under = {name: laid.idle_under(name)
                         for name in {sp[0] for sp in laid.spans}}
                obs["idle_by_phase"] = (
                    under, trace.window_s - trace.busy_by_device()[0])
    return obs["idle_by_phase"]


def idle_under(obs, ctx, span):
    """The chip's idle time while the host was in phase ``span`` of a step,
    as a share of the traced span; percent.  ``_unattributed_`` is all of the
    chip's idle time less what fell to ``PHASES``."""
    split = idle_by_phase(obs)
    if split is None:
        return None
    under, idle = split
    if span == UNATTRIBUTED:
        seconds = idle - sum(under.get(p, 0.0) for p in PHASES)
    else:
        seconds = under.get(span, 0.0)
    return 100.0 * seconds / obs["trace"].window_s


def ms_per(obs, ctx, span, minus=(), per=None):
    """The summed duration of ``span`` less that of the spans in ``minus``,
    over the count of ``per`` (``span`` itself by default); milliseconds."""
    spans = recorded_spans() or []
    units = sum(n == (per or span) for n, _, _ in spans)
    if not units:
        return None
    seconds = sum((e - s) * (1 if n == span else -1) for n, s, e in spans
                  if n == span or n in minus)
    return 1e3 * seconds / units
