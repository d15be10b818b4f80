"""The reader of the program's spans, on a synthetic trace and synthetic
spans, and on the CPU rehearsal (where there is no device line: the idle
shares read nothing, the host time per step reads)."""

import os
import time

import pytest

from benchmarks.harness.context import Context
from benchmarks.harness.spec import BENCH_DIR, Cell, load_json
from benchmarks.harness.trace import Trace
from benchmarks.readers import program_spans as ps

THETA = 1234.5678          # the program's clock is this far behind the trace's
STEP_S, N_STEPS = 0.010, 40


def synthetic():
    """A trace of ``N_STEPS`` steps of 10 ms on one chip.  In each step the
    program runs admit 1 ms, prefill 1 ms, plan 2 ms, the enqueue 1 ms and a
    harvest of 4 ms, with 50 us between them and around them; the chip runs
    from the middle of the enqueue to the middle of the harvest.  Returns the
    trace and the program's spans on the program's clock."""
    tr = Trace.__new__(Trace)
    tr.t0, tr.t1 = 100.0, 100.0 + N_STEPS * STEP_S + 0.002
    tr.window_s = tr.t1 - tr.t0
    ops, spans, program = [], [], []
    for k in range(N_STEPS):
        b0 = tr.t0 + 0.001 + k * STEP_S
        spans.append(("bench.eng_step", b0, b0 + STEP_S - 0.0004))
        spans.append(("bench.sample", b0 + STEP_S - 0.0004, b0 + STEP_S))
        s0 = b0 + 20e-6
        program.append(("serving.step", s0, b0 + STEP_S - 0.0004 - 20e-6))
        t = s0 + 50e-6
        for name, dur in (("serving.admit", 1e-3), ("serving.prefill", 1e-3),
                          ("serving.plan", 2e-3),
                          ("serving.decode_block", 1e-3),
                          ("serving.harvest", 4e-3)):
            program.append((name, t, t + dur))
            if name == "serving.prefill":
                program.append(("serving.prefill.dispatch", t + 1e-4, t + 5e-4))
            if name == "serving.decode_block":
                busy0 = t + 0.5e-3
            if name == "serving.harvest":
                program.append(("serving.harvest.wait", t, t + 2e-3))
                ops.append(("%fusion.1 = f32[8]{0} fusion()", busy0, t + 2e-3))
            t += dur + 50e-6
    tr.devices = [{"ops": ops, "modules": [], "module_starts": []}]
    tr.spans = spans
    return tr, [(n, s - THETA, e - THETA) for n, s, e in program]


@pytest.fixture
def synth(monkeypatch):
    tr, program = synthetic()
    monkeypatch.setattr(ps, "recorded_spans", lambda: list(program))
    return tr, program


def test_the_offset_is_recovered(synth):
    tr, program = synth
    theta, low, high, pairs = ps.offset(
        [s for s in tr.spans if s[0] == "bench.eng_step"],
        [s for s in program if s[0] == "serving.step"])
    assert pairs == N_STEPS
    assert abs(theta - THETA) < 1e-9          # the slack is 20 us each side
    assert high - low == pytest.approx(40e-6, abs=1e-9)
    spans = ps.aligned(tr, program)
    assert min(abs(s - 100.00102) for n, s, _ in spans
               if n == "serving.step") < 1e-8


def test_an_infeasible_pairing_reads_as_nothing(synth, monkeypatch, capsys):
    tr, program = synth
    # steps of another length than the calls that enclose them
    short = [(n, s, s + 0.5 * (e - s)) if n == "serving.step" else (n, s, e)
             for n, s, e in program]
    monkeypatch.setattr(ps, "recorded_spans", lambda: short)
    assert ps.idle_under({"trace": tr}, None, "serving.plan") is None
    assert "no offset" in capsys.readouterr().err
    # a program clock that runs one part in a thousand fast
    drift = [(n, s * (1 + 1e-3), e * (1 + 1e-3)) for n, s, e in program]
    monkeypatch.setattr(ps, "recorded_spans", lambda: drift)
    assert ps.idle_under({"trace": tr}, None, "serving.plan") is None
    assert "is empty by" in capsys.readouterr().err
    # too few steps to trust, and a program without the buffer
    monkeypatch.setattr(ps, "recorded_spans", lambda: program[:9 * 8])
    assert ps.idle_under({"trace": tr}, None, "serving.plan") is None
    monkeypatch.setattr(ps, "recorded_spans", lambda: None)
    assert ps.idle_under({"trace": tr}, None, "serving.plan") is None
    assert ps.ms_per({"trace": tr}, None, "serving.step") is None


def test_the_six_shares_add_up_to_the_idle_share(synth):
    tr, _ = synth
    obs = {"trace": tr}
    share = {p: ps.idle_under(obs, None, p) for p in ps.PHASES}
    rest = ps.idle_under(obs, None, ps.UNATTRIBUTED)
    assert sum(share.values()) + rest == pytest.approx(tr.idle_pct(), abs=1e-9)
    per_step = 100.0 / tr.window_s * N_STEPS
    assert share["serving.admit"] == pytest.approx(1e-3 * per_step)
    assert share["serving.prefill"] == pytest.approx(1e-3 * per_step)
    assert share["serving.plan"] == pytest.approx(2e-3 * per_step)
    # the chip starts in the middle of the enqueue and runs through the wait
    assert share["serving.decode_block"] == pytest.approx(0.5e-3 * per_step)
    assert share["serving.harvest"] == pytest.approx(2e-3 * per_step)
    # the rest: between the phases, around the step, the caller's loop and
    # the window's last 2 ms; a step's chip runs 2.55 ms of its 10
    assert rest == pytest.approx(
        100.0 / tr.window_s * (N_STEPS * (STEP_S - 2.55e-3 - 6.5e-3) + 0.002))
    # a child's descendants are the child's
    assert ps.idle_under(obs, None, "serving.prefill.dispatch") == 0.0


def test_host_time_per_step_needs_no_alignment(synth):
    tr, _ = synth
    got = ps.ms_per({}, None, "serving.step", minus=["serving.harvest.wait"])
    assert got == pytest.approx(1e3 * (STEP_S - 0.0004 - 40e-6 - 2e-3))
    assert ps.ms_per({}, None, "serving.harvest.wait",
                     per="serving.step") == pytest.approx(2.0)
    assert ps.ms_per({}, None, "train_step.call") is None


def test_the_traced_rehearsal_reads_the_engines_host_time():
    reh = os.path.join(BENCH_DIR, "rehearsal")
    c = Cell(load_json(os.path.join(reh, "workloads.json")), "tiny_sat",
             traffic_dir=os.path.join(reh, "traffic"))
    ctx = Context(c, 5, 1.5, 1, time.perf_counter(),
                  trace_dir=os.path.join(reh, ".trace_program_spans"))
    ctx.phases.mark("imports")
    obs, rows, ok = c.driver().run(ctx)
    assert ok, rows
    host_ms = ps.ms_per(obs, ctx, "serving.step",
                        minus=["serving.harvest.wait"])
    whole_ms = ps.ms_per(obs, ctx, "serving.step")
    assert 0 < host_ms < whole_ms
    steps = sum(s[0] == "bench.eng_step" for s in obs["trace"].spans)
    assert steps == sum(n == "serving.step" for n, _, _ in ps.recorded_spans())
    # every call encloses its step, so the two clocks can be laid together
    assert ps.aligned(obs["trace"], ps.recorded_spans()) is not None
    # no device line on the CPU: the idle shares read nothing
    assert obs["trace"].devices == []
    assert ps.idle_under(obs, ctx, "serving.plan") is None


def test_the_traced_train_rehearsal_reads_the_dispatch():
    reh = os.path.join(BENCH_DIR, "rehearsal")
    c = Cell(load_json(os.path.join(reh, "workloads.json")), "tiny_train",
             traffic_dir=os.path.join(reh, "traffic"))
    ctx = Context(c, 5, 1.5, 1, time.perf_counter(),
                  trace_dir=os.path.join(reh, ".trace_program_spans"))
    ctx.phases.mark("imports")
    obs, rows, ok = c.driver().run(ctx)
    assert ok, rows
    assert ps.ms_per(obs, ctx, "train_step.call") > 0
