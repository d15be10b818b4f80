"""The reader that lays the program's spans on the trace by the wall clock,
on the synthetic trace of ``test_program_spans`` with the program's spans
moved onto the wall clock, and its three metrics in ``BENCHMARK.json``."""

import copy
import os
import time
from types import SimpleNamespace

import pytest

from benchmarks.harness.spec import BENCH_DIR, load_json
from benchmarks.readers import host_spans as hs
from benchmarks.readers import program_spans as ps
from benchmarks.tests.test_program_spans import N_STEPS, THETA, synthetic

METRICS = {"engine.idle_pct.harvest_wait": "serving.harvest.wait",
           "engine.idle_pct.harvest_absorb": "serving.harvest.absorb",
           "engine.idle_pct.gc": "runtime.gc"}
SERVING = ["yicoder_chat_sat", "lfm2moe_chat_sat", "kimilinear_reason_sat",
           "falconh1_reason_sat"]


def on_the_wall(program, session_start):
    """The synthetic program's spans as ``recorded()`` now gives them: the
    trace's times plus the session's start on the wall clock, with an
    absorb after each fetch and one collection in the tenth step."""
    out = []
    for n, s, e in program:
        s, e = s + THETA + session_start, e + THETA + session_start
        out.append((n, s, e))
        if n == "serving.harvest.wait":
            # the rest of the harvest, 5 us from each end
            out.append(("serving.harvest.absorb", e + 5e-6, e + 2e-3 - 5e-6))
    tenth = sorted(s for n, s, _ in out if n == "serving.plan")[9]
    out.append(("runtime.gc", tenth + 1e-4, tenth + 6e-4))
    return out


@pytest.fixture
def run(monkeypatch):
    tr, program = synthetic()
    began = time.time() - 60.0
    wall = on_the_wall(program, began + 30.0)
    monkeypatch.setattr(hs, "recorded_spans", lambda: list(wall))
    ctx = SimpleNamespace(t_start=time.perf_counter() - 60.0)
    return tr, wall, ctx, began + 30.0


@pytest.mark.parametrize("span", sorted(set(METRICS.values())))
def test_idle_under_the_laid_spans_is_the_traces_own(run, span):
    tr, wall, ctx, start = run
    truth = [(n, s - start, e - start) for n, s, e in wall if n == span]
    on = copy.copy(tr)
    on.spans = truth
    direct = on.idle_under(span)
    got = hs.idle_under({"trace": tr}, ctx, span)
    # to what a float of the wall clock holds (a quarter of a microsecond)
    assert got == pytest.approx(100.0 * direct / tr.window_s, abs=1e-3)
    assert got > 0


def test_the_two_harvest_parts_make_up_the_harvest(run, monkeypatch):
    tr, wall, ctx, _ = run
    obs = {"trace": tr}
    wait = hs.idle_under(obs, ctx, "serving.harvest.wait")
    absorb = hs.idle_under(obs, ctx, "serving.harvest.absorb")
    # the chip runs to the fetch's end, the rest of the harvest is absorb
    per_step = 100.0 / tr.window_s * N_STEPS
    assert wait == pytest.approx(0.0, abs=1e-3)
    assert absorb == pytest.approx(1.99e-3 * per_step, abs=1e-3)
    # the old reader, on its own pairing, reads the whole harvest
    monkeypatch.setattr(ps, "recorded_spans", lambda: list(wall))
    harvest = ps.idle_under({"trace": tr}, ctx, "serving.harvest")
    assert wait + absorb == pytest.approx(harvest, abs=1e-5 * per_step)


def test_spans_on_another_clock_read_nothing(run, monkeypatch, capsys):
    tr, wall, ctx, start = run
    # a monotonic clock: the constant is no moment of this run
    mono = [(n, s - start + 1234.5, e - start + 1234.5) for n, s, e in wall]
    monkeypatch.setattr(hs, "recorded_spans", lambda: mono)
    assert hs.idle_under({"trace": tr}, ctx, "serving.harvest.wait") is None
    assert "not on the wall clock" in capsys.readouterr().err
    # steps that cannot lie inside their calls
    short = [(n, s - 0.004, e) if n == "serving.step" else (n, s, e)
             for n, s, e in wall]
    monkeypatch.setattr(hs, "recorded_spans", lambda: short)
    assert hs.idle_under({"trace": tr}, ctx, "serving.harvest.wait") is None
    assert "is empty by" in capsys.readouterr().err
    # no buffer, no device line
    monkeypatch.setattr(hs, "recorded_spans", lambda: None)
    assert hs.idle_under({"trace": tr}, ctx, "runtime.gc") is None
    monkeypatch.setattr(hs, "recorded_spans", lambda: wall)
    tr.devices = []
    assert hs.idle_under({"trace": tr}, ctx, "runtime.gc") is None


def test_the_three_metrics_are_listed_for_the_four_serving_cells():
    bench = load_json(os.path.join(os.path.dirname(BENCH_DIR),
                                   "BENCHMARK.json"))
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name, span in METRICS.items():
        m = listed[name]
        assert m["workloads"] == SERVING
        assert (m["unit"], m["better"], m["source"], m["moves"], m["layer"]) \
            == ("%", "lower", "device_trace", "out_tok_s",
                "engine: admission and plan")
        data = load_json(os.path.join(BENCH_DIR, "metrics", name + ".json"))
        assert data["reader"] == "benchmarks.readers.host_spans:idle_under"
        assert data["args"] == {"span": span}


def test_the_traced_rehearsal_lays_the_engines_spans():
    """On the CPU rehearsal the program's own spans are laid with no
    tolerance and no count of pairs: every step inside its call, the
    harvest's fetch and absorb inside the harvest."""
    from benchmarks.harness.context import Context
    from benchmarks.harness.spec import Cell
    reh = os.path.join(BENCH_DIR, "rehearsal")
    c = Cell(load_json(os.path.join(reh, "workloads.json")), "tiny_sat",
             traffic_dir=os.path.join(reh, "traffic"))
    ctx = Context(c, 6, 1.5, 1, time.perf_counter(),
                  trace_dir=os.path.join(reh, ".trace_host_spans"))
    ctx.phases.mark("imports")
    obs, rows, ok = c.driver().run(ctx)
    assert ok, rows
    spans = hs.laid(obs, ctx)
    assert spans is not None
    tr = obs["trace"]
    steps = [s for s in spans if s[0] == "serving.step"
             and s[2] > tr.t0 and s[1] < tr.t1]
    bench = [s for s in tr.spans if s[0] == "bench.eng_step"]
    assert steps and all(any(b[1] <= s[1] and s[2] <= b[2] for b in bench)
                         for s in steps)
    names = {n for n, _, _ in spans}
    assert {"serving.harvest.wait", "serving.harvest.absorb"} <= names
    # no device line on the CPU: the shares read nothing
    assert hs.idle_under(obs, ctx, "serving.harvest.wait") is None
