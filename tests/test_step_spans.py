"""The engine's step on the profiler's clock: under a bare
``jax.profiler.start_trace`` the spans of ``ServingEngine.step()`` sit
in the ``.xplane.pb`` beside where the device's line would be and in
``observability.spans.recorded()``; the step's accumulators are taken
at the spans' boundaries; tracing observes and reorders nothing.

Tier-1 budget: ONE module-scoped pair of tiny engines (1-layer llama,
float32, ``steps_per_call=2``: budgets end inside blocks, and the last
rider's odd last token takes the one-step program), the same trace
replayed with a profiler session live and without; one engine more,
warmed on the same trace, for the off-CPU account.
"""

import gc
import glob
import os
import time
from collections import defaultdict
from types import SimpleNamespace

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import models
from paddle_tpu import runtime as rt
from paddle_tpu.inference.faultinject import FaultInjector
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.observability import MetricsRegistry, collector, spans
from paddle_tpu.observability.flightrec import FlightRecorder

P, C = 6, 32
SPECS = [(4, 7), (3, 4), (5, 10), (6, 3)]          # (seq_len, max_new)
CHILDREN = ("serving.admit", "serving.prefill", "serving.plan",
            "serving.decode_block", "serving.harvest")
# reasons of a harvest that is the step's own synchronous tail (its wait
# is dispatch time); every other harvest is _harvest_next (overlap time)
SYNC_TAIL = ("budget", "eos", "off", "mask", "penalty", "spec")


def _engine(net, **kw):
    reg = MetricsRegistry()
    return reg, ServingEngine(
        net, num_slots=2, prompt_len=P, max_cache_len=C, steps_per_call=2,
        compute_dtype="float32", registry=reg, **kw)


def _submit(eng, cfg):
    rng = np.random.default_rng(11)
    return [eng.submit(
        rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32),
        max_new_tokens=m) for n, m in SPECS]


def _run(net, cfg, session_dir):
    rec = FlightRecorder()
    reg, eng = _engine(net, flight_recorder=rec)
    if session_dir:
        jax.profiler.start_trace(session_dir)
    try:
        reqs = _submit(eng, cfg)
        eng.run()
        got = spans.recorded() if session_dir else None
    finally:
        if session_dir:
            jax.profiler.stop_trace()
    return SimpleNamespace(eng=eng, reg=reg, rec=rec, reqs=reqs,
                           stats=eng.stats(), recorded=got)


@pytest.fixture(scope="module")
def tiny():
    paddle.seed(2028)
    cfg = models.LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=1, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64)
    net = models.LlamaForCausalLM(cfg)
    net.eval()
    return net, cfg


@pytest.fixture(scope="module")
def runs(tmp_path_factory, tiny):
    net, cfg = tiny
    trace_dir = str(tmp_path_factory.mktemp("xplane"))
    live = _run(net, cfg, trace_dir)
    # the session has ended: a further step records nothing
    n_before = rt.HostTracer.count()
    live.eng.submit(np.arange(3, dtype=np.int32), max_new_tokens=2)
    live.eng.run()
    live.count_after = (n_before, rt.HostTracer.count())
    quiet = _run(net, cfg, None)
    xplane = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    return SimpleNamespace(live=live, quiet=quiet, xplane=xplane)


def _harvests(rec):
    """[(harvest, its .wait spans, its .absorb spans)] of a recorded run."""
    out = []
    for n, t0, t1, _tid, a in rec:
        if n == "serving.harvest":
            inside = [(m, s0, s1) for m, s0, s1, _, _ in rec
                      if m.startswith("serving.harvest.")
                      and t0 <= s0 and s1 <= t1]
            out.append(((t0, t1, a["reason"]),
                        [(s0, s1) for m, s0, s1 in inside
                         if m == "serving.harvest.wait"],
                        [(s0, s1) for m, s0, s1 in inside
                         if m == "serving.harvest.absorb"]))
    return out


def _host_events(xplane):
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(xplane).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)) for e in line.events
                        if e.name.startswith("serving.")]
    return out


def _children_of(events, steps):
    """{index of step: [(name, t0, t1)]} for events strictly inside one
    ``serving.step`` and inside no other child of it."""
    out = defaultdict(list)
    for name, t0, t1 in sorted(events, key=lambda e: (e[1], -e[2])):
        for k, (s0, s1) in enumerate(steps):
            if s0 <= t0 and t1 <= s1:
                if not any(c0 <= t0 and t1 <= c1 for _, c0, c1 in out[k]):
                    out[k].append((name, t0, t1))
                break
    return out


def test_spans_sit_in_the_profilers_trace_and_in_recorded(runs):
    ev = _host_events(runs.xplane)
    names = {e[0] for e in ev}
    for want in ("serving.step", "serving.harvest.wait") + CHILDREN:
        assert want in names, want
    steps = sorted((t0, t1) for n, t0, t1, _ in ev if n == "serving.step")
    inner = [(n, t0, t1) for n, t0, t1, _ in ev
             if n in CHILDREN or n == "serving.harvest.wait"]
    # every child lies inside exactly one step (the run drove nothing
    # between steps that harvests)
    kids = _children_of(inner, steps)
    assert sum(len(v) for v in kids.values()) == \
        sum(n in CHILDREN for n, _, _ in inner)
    seen = {n for v in kids.values() for n, _, _ in v}
    assert seen == set(CHILDREN)
    # attributes arrive as stats, not in the name
    # one serving.prefill a chunk (attr request) and one a landing of the
    # step's first tokens (attr landed), its wait a child of its own
    pre = [st for n, _, _, st in ev if n == "serving.prefill"]
    chunks = [st for st in pre if "request" in st]
    landed = [int(st["landed"]) for st in pre if "landed" in st]
    assert len(chunks) + len(landed) == len(pre)
    assert sorted(int(st["request"]) for st in chunks) == \
        sorted(r.request_id for r in runs.live.reqs)
    assert sum(landed) == len(SPECS)
    assert len(landed) == runs.live.stats["first_token_fetches"] == \
        sum(n == "serving.prefill.wait" for n, *_ in ev)
    blk = [st for n, _, _, st in ev if n == "serving.decode_block"]
    assert blk and {int(st["steps"]) for st in blk} == {1, 2}
    assert all(";" not in n for n in names)
    reasons = {st["reason"] for n, _, _, st in ev if n == "serving.harvest"}
    assert "budget" in reasons and "deferred" in reasons
    # request instants share the identifier with the request's spans
    fin = [st for n, _, _, st in ev if n == "serving.request.finish"]
    assert sorted(int(st["request"]) for st in fin) == \
        sorted(r.request_id for r in runs.live.reqs)
    # the same spans, decoded, in the one buffer
    rec = runs.live.recorded
    by_name = defaultdict(int)
    for n, t0, t1, _tid, attrs in rec:
        assert t1 >= t0
        by_name[n] += 1
    for n in ("serving.step", "serving.harvest.wait") + CHILDREN:
        assert by_name[n] == sum(e[0] == n for e in ev), n
    assert {a["reason"] for n, *_, a in rec if n == "serving.harvest"} \
        == reasons


def test_the_harvest_splits_into_its_fetch_and_its_absorb(runs):
    rec = runs.live.recorded
    harvests = _harvests(rec)
    assert harvests
    for (h0, h1, _), waits, absorbs in harvests:
        # one fetch, then the host's bookkeeping, inside the harvest
        assert len(waits) == len(absorbs) == 1
        (w0, w1), (a0, a1) = waits[0], absorbs[0]
        assert w1 <= a0
        assert (w1 - w0) + (a1 - a0) <= h1 - h0
    # both sites: the pipeline's own overlap point and a synchronous tail
    reasons = {r for (_, _, r), _, _ in harvests}
    assert "deferred" in reasons and reasons & set(SYNC_TAIL)
    assert sum(n == "serving.harvest.absorb" for n, *_ in rec) == \
        len(harvests)
    # the absorb is a grandchild: a step's direct children stay the five
    steps = sorted((t0, t1) for n, t0, t1, _, _ in rec if n == "serving.step")
    kids = _children_of([(n, t0, t1) for n, t0, t1, _, _ in rec
                         if n.startswith("serving.") and n != "serving.step"],
                        steps)
    assert {n for v in kids.values() for n, _, _ in v} == set(CHILDREN)


def test_session_end_makes_spans_quiet(runs):
    before, after = runs.live.count_after
    assert before > 0 and after == before
    assert not rt.HostTracer.enabled


def test_children_cover_the_step_and_boundaries_feed_the_histograms(runs):
    rec = [(n, t0 * 1e-9, t1 * 1e-9, a)
           for n, t0, t1, _tid, a in runs.live.recorded]
    steps = sorted((t0, t1) for n, t0, t1, _ in rec if n == "serving.step")
    kids = _children_of([(n, t0, t1) for n, t0, t1, _ in rec
                         if n in CHILDREN], steps)
    whole = inside = 0.0
    bare = []
    for k, (s0, s1) in enumerate(steps):
        if any(n == "serving.decode_block" for n, _, _ in kids[k]):
            covered = sum(t1 - t0 for _, t0, t1 in kids[k])
            whole += s1 - s0
            inside += covered
            bare.append((s1 - s0) - covered)
    assert len(bare) >= 5
    assert inside > 0.95 * whole, (inside, whole)
    # a warm step of this toy lasts under a millisecond, of which the
    # live spans' own entries and exits are a tenth; what no child covers
    # is a few lines, so by the median (one preempted step proves nothing)
    assert sorted(bare)[len(bare) // 2] < 1e-3, bare
    # dispatch seconds: prefill (the chunks' enqueues and the first
    # tokens' one wait) + decode-block enqueue + the sync tail's wait;
    # overlap seconds: every other harvest's wait.  One boundary,
    # two sinks, so they agree to what lies between two adjacent reads
    # of two clocks.
    dur = defaultdict(float)
    harvests = [(t0, t1, a["reason"]) for n, t0, t1, a in rec
                if n == "serving.harvest"]
    for n, t0, t1, _ in rec:
        if n == "serving.harvest.wait":
            reason = next(r for h0, h1, r in harvests if h0 <= t0 and t1 <= h1)
            n += ".sync" if reason in SYNC_TAIL else ".deferred"
        dur[n] += t1 - t0
    reg = runs.live.reg
    disp = reg.get("serving.step.dispatch_seconds").summary()["sum"]
    over = reg.get("serving.step.overlap_seconds").summary()["sum"]
    want_disp = (dur["serving.prefill.dispatch"]
                 + dur["serving.prefill.wait"]
                 + dur["serving.decode_block"]
                 + dur["serving.harvest.wait.sync"])
    assert disp == pytest.approx(want_disp, rel=0.01, abs=1e-3)
    assert over == pytest.approx(dur["serving.harvest.wait.deferred"],
                                 rel=0.01, abs=1e-3)
    assert dur["serving.harvest.wait.deferred"] > 0
    host = reg.get("serving.step.host_seconds").summary()
    n_disp = reg.get("serving.step.dispatch_seconds").summary()["count"]
    assert host["count"] == n_disp > 0


def test_schedule_identical_with_a_session_live(runs):
    a, b = runs.live, runs.quiet

    def strip(rec):
        return [(e.seq, e.step, e.request, e.kind,
                 tuple(sorted((k, str(v)) for k, v in e.attrs.items())))
                for e in rec.events()]
    # (the live engine served one request more, after its session)
    assert strip(a.rec)[:len(strip(b.rec))] == strip(b.rec)
    for k in ("decode_steps", "block_dispatches", "prefill_chunks",
              "busy_slot_steps", "async_harvests", "async_syncs_by_reason",
              "useful_tokens", "dispatched_tokens"):
        assert a.stats[k] == b.stats[k], k
    for ra, rb in zip(a.reqs, b.reqs):
        np.testing.assert_array_equal(ra.output, rb.output)


def test_nothing_live_formats_nothing(monkeypatch):
    assert not rt.HostTracer.enabled
    did = []

    def boom(*a, **k):
        did.append(a)               # a gc callback's raise is swallowed
        raise AssertionError("a quiet span did work")
    monkeypatch.setattr(spans, "format_span_name", boom)
    monkeypatch.setattr(spans, "_Annotation", SimpleNamespace(
        is_enabled=lambda: False, __call__=boom))
    with spans.span("serving.decode_block", steps=2, active=1) as sp:
        assert sp._ann is None
    spans.instant("serving.request.finish", request=1)
    # the collector's hook, outside a window, spans nothing either
    collector.install()
    gc.collect(1)
    gc.collect(2)
    assert did == []


def test_offcpu_time_counts_a_stop_and_not_a_wait_or_a_stall(tiny):
    """``serving.step.offcpu_seconds``: one observation a dispatching
    step; a sleep in the plan is off the CPU outside any wait, a sleep
    inside a harvest's fetch is a device wait, an injected stall is
    neither host nor wait."""
    net, cfg = tiny
    inj = FaultInjector()
    reg, eng = _engine(net, fault_injector=inj)
    _submit(eng, cfg)
    eng.run()                       # every program compiled: warm
    off = reg.get("serving.step.offcpu_seconds")
    disp = reg.get("serving.step.dispatch_seconds")
    sleeps, armed = [], {}

    def sleepy(fn, where):
        def wrapped(*a, **k):
            if armed.pop(where, False):
                sleeps.append(where)
                time.sleep(0.1)
            return fn(*a, **k)
        return wrapped
    eng._build_samp = sleepy(eng._build_samp, "plan")
    eng._count_experts = sleepy(eng._count_experts, "wait")
    _submit(eng, cfg)
    seen = {}

    def step_until(kind):
        while True:
            n0, s0 = off.summary()["count"], off.summary()["sum"]
            eng.step()
            if kind in sleeps or kind == "stall":
                sleeps.clear()
                return off.summary()["count"] - n0, off.summary()["sum"] - s0
    inj.stall_steps(1, 0.2)
    seen["stall"] = step_until("stall")
    armed["plan"] = True
    seen["plan"] = step_until("plan")
    armed["wait"] = True
    seen["wait"] = step_until("wait")
    eng.run()
    assert seen["stall"][0] == 1 and seen["stall"][1] < 0.1, seen
    assert seen["plan"][0] == 1 and seen["plan"][1] >= 0.09, seen
    assert seen["wait"][0] == 1 and seen["wait"][1] < 0.05, seen
    assert off.summary()["count"] == disp.summary()["count"] > 0
