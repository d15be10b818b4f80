"""Observability layer (paddle_tpu/observability/): metrics registry
semantics, exporters, snapshot/diff, span encoding, host+device chrome
trace merging, ServingEngine instrumentation (stats() == registry), the
disabled-mode overhead contract, and the instrument-name lint.

Tier-1 budget discipline: ONE module-scoped engine run covers the
serving acceptance criteria (Prometheus export, merged trace, stats
equality, decode-block timing) — tiny llama shapes, no Pallas compile;
registry-only tests are pure Python."""

import importlib.util
import json
import os
import time
from types import SimpleNamespace

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import models
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.observability import (
    MetricsRegistry, TimeSeriesRecorder, diff_snapshots,
    format_span_name, get_registry, merge_chrome_traces,
    parse_span_name, span,
)
from paddle_tpu.profiler import Profiler, ProfilerTarget


# ---------------------------------------------------------------------------
# registry semantics (pure python)
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("t.requests", "help text")
    c.inc()
    c.inc(4)
    assert c.value() == 5
    with pytest.raises(ValueError, match=">= 0"):
        c.inc(-1)

    g = reg.gauge("t.depth")
    g.set(3)
    g.set(1)
    g.add(2)
    assert g.value() == 3
    assert g.hwm() == 3

    h = reg.histogram("t.lat", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.05, 0.5):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 4
    assert abs(s["sum"] - 0.605) < 1e-9
    assert 0.01 <= s["p50"] <= 0.1          # 2nd/3rd obs in (0.01, 0.1]
    assert 0.1 <= s["p99"] <= 1.0


def test_labels_and_registration_rules():
    reg = MetricsRegistry()
    c = reg.counter("t.route", labels=("decision", "reason"))
    c.inc(decision="pallas", reason="ok")
    c.inc(2, decision="xla", reason="vmem")
    assert c.value(decision="pallas", reason="ok") == 1
    assert c.value(decision="xla", reason="vmem") == 2
    assert c.value(decision="xla", reason="other") == 0
    with pytest.raises(ValueError, match="label"):
        c.inc(decision="pallas")            # missing label
    # re-registration: same type+labels returns the SAME instrument
    assert reg.counter("t.route", labels=("decision", "reason")) is c
    with pytest.raises(ValueError, match="labels"):
        reg.counter("t.route", labels=("decision",))
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("t.route")
    with pytest.raises(ValueError, match="invalid instrument name"):
        reg.counter("Bad-Name")
    with pytest.raises(ValueError, match="invalid instrument name"):
        reg.counter("9starts.with.digit")
    # histogram bucket conflicts must raise, not silently keep old bounds
    h = reg.histogram("t.lat2", buckets=(0.1, 1.0))
    assert reg.histogram("t.lat2", buckets=(1.0, 0.1)) is h  # same sorted
    with pytest.raises(ValueError, match="buckets"):
        reg.histogram("t.lat2", buckets=(0.5, 5.0))


def test_disabled_registry_is_noop():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("t.c")
    g = reg.gauge("t.g")
    h = reg.histogram("t.h")
    c.inc(5)
    g.set(9)
    h.observe(1.0)
    assert c.value() == 0 and g.value() == 0
    assert h.summary()["count"] == 0
    reg.enable()
    c.inc()
    assert c.value() == 1


def test_snapshot_diff_and_json():
    reg = MetricsRegistry()
    c = reg.counter("t.c")
    g = reg.gauge("t.g")
    h = reg.histogram("t.h", buckets=(0.1, 1.0))
    c.inc(3)
    h.observe(0.05)
    before = reg.snapshot()
    c.inc(2)
    g.set(7)
    h.observe(0.5)
    h.observe(0.5)
    after = reg.snapshot()
    json.dumps(after)                        # snapshot is serializable
    d = diff_snapshots(before, after)
    assert d["t.c"]["values"][""] == 2
    assert d["t.g"]["values"][""] == 7
    cell = d["t.h"]["values"][""]
    assert cell["count"] == 2                # the pre-existing obs diffed out
    assert abs(cell["sum"] - 1.0) < 1e-9
    assert 0.1 <= cell["p50"] <= 1.0
    # instruments that did not move during the window drop out —
    # including gauges (a stale level must not be re-attributed)
    assert diff_snapshots(after, after) == {}
    # ...and so do individual zero-delta label cells of a counter
    regl = MetricsRegistry()
    cl = regl.counter("t.route", labels=("reason",))
    cl.inc(reason="a")
    b0 = regl.snapshot()
    cl.inc(reason="b")
    dl = diff_snapshots(b0, regl.snapshot())
    assert dl["t.route"]["values"] == {"reason=b": 1}


def test_prometheus_text_format():
    reg = MetricsRegistry()
    reg.counter("t.tokens", "tokens").inc(12)
    reg.gauge("t.depth").set(4)
    h = reg.histogram("t.lat", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    text = reg.to_prometheus_text()
    assert "# TYPE t_tokens counter" in text
    assert "t_tokens 12" in text
    assert "t_depth 4" in text
    assert 't_lat_bucket{le="0.1"} 1' in text
    assert 't_lat_bucket{le="+Inf"} 2' in text
    assert "t_lat_count 2" in text
    assert '# TYPE t_lat_quantile gauge' in text
    assert 't_lat_quantile{quantile="0.99"}' in text


def test_prometheus_text_quotes_label_values():
    reg = MetricsRegistry()
    c = reg.counter("t.route", labels=("decision", "reason"))
    c.inc(3, decision="xla", reason="vmem_budget")
    h = reg.histogram("t.trial", labels=("kernel",), buckets=(0.1, 1.0))
    h.observe(0.5, kernel="rms_norm")
    text = reg.to_prometheus_text()
    # exposition grammar: label VALUES must be double-quoted
    assert 't_route{decision="xla",reason="vmem_budget"} 3' in text
    assert 't_trial_bucket{kernel="rms_norm",le="1.0"} 1' in text
    assert 't_trial_count{kernel="rms_norm"} 1' in text
    assert 't_trial_quantile{kernel="rms_norm",quantile="0.99"}' in text
    import re as _re
    assert not _re.search(r"\{[^}\"]*=[^\"][^}]*\}", text), \
        "unquoted label value leaked into exposition output"
    # hostile label values cannot fabricate extra labels: ','/'=' are
    # escaped in the snapshot key and restored verbatim on export
    e = reg.counter("t.err", labels=("kind",))
    e.inc(kind="a,b=c")
    assert e.value(kind="a,b=c") == 1
    text2 = reg.to_prometheus_text()
    assert 't_err{kind="a,b=c"} 1' in text2
    assert 'b="c"' not in text2


def test_span_name_roundtrip_hostile_values(tmp_path):
    """Satellite (PR 9): attr values containing the encoding's own
    metacharacters — ``%``, ``;``, ``=`` and their escape sequences —
    survive ``format_span_name``/``parse_span_name`` round trips AND
    the full ``merge_chrome_traces`` path (property-style sweep: the
    ``_esc_attr`` escaping had no end-to-end coverage)."""
    hostile = ["%", ";", "=", "%3B", "%3D", "%25", "a=b;c=d",
               "100%;done=1", ";;==%%", "k=v", "%3D%3B", "trailing;",
               "=lead", "%%25", "a%3Bb;c"]
    for v in hostile:
        enc = format_span_name("t.span", {"v": v, "w": f"x{v}y{v}"})
        name, attrs = parse_span_name(enc)
        assert name == "t.span"
        assert attrs == {"v": v, "w": f"x{v}y{v}"}, v
    # end to end: HostTracer-style tuples with encoded names through
    # the chrome merger — every hostile value must land verbatim in
    # the event's Perfetto args, never as a fabricated extra attr
    events = [(1, 1000 * i, 1000 * i, 1, 0,
               format_span_name("t.ev", {"v": v, "i": i}))
              for i, v in enumerate(hostile)]
    out = str(tmp_path / "hostile.json")
    merge_chrome_traces(out, host=events)
    with open(out) as f:
        evs = [e for e in json.load(f)["traceEvents"]
               if e.get("name") == "t.ev"]
    assert len(evs) == len(hostile)
    for i, v in enumerate(hostile):
        assert evs[i]["args"] == {"v": v, "i": str(i)}, v


def test_histogram_empty_and_single_bucket_edges():
    """Satellite (PR 9): ``Histogram.summary()`` /
    ``_quantile_from_buckets`` on empty and single-bucket histograms —
    the edge cases the fixed-bucket interpolation must not NaN or
    over-range on."""
    from paddle_tpu.observability.metrics import _quantile_from_buckets
    reg = MetricsRegistry()
    # empty: all-zero summary, no snapshot cell, no diff noise
    h = reg.histogram("t.empty", buckets=(0.5,))
    assert h.summary() == {"count": 0, "sum": 0.0, "p50": 0.0,
                           "p95": 0.0, "p99": 0.0}
    assert reg.snapshot()["t.empty"]["values"] == {}
    assert diff_snapshots(reg.snapshot(), reg.snapshot()) == {}
    # single bucket: quantiles interpolate inside [0, bound]
    h1 = reg.histogram("t.single", buckets=(1.0,))
    h1.observe(0.25)
    h1.observe(0.75)
    s1 = h1.summary()
    assert s1["count"] == 2 and abs(s1["sum"] - 1.0) < 1e-9
    assert 0.0 <= s1["p50"] <= 1.0
    assert 0.0 <= s1["p99"] <= 1.0
    # a boundary observation counts in its le bucket, not +Inf
    h1.observe(1.0)
    assert reg.snapshot()["t.single"]["values"][""]["buckets"] == [3, 0]
    # all mass in +Inf clamps to the largest finite bound
    h2 = reg.histogram("t.inf", buckets=(0.1, 1.0))
    h2.observe(5.0)
    h2.observe(7.0)
    s2 = h2.summary()
    assert s2["p50"] == 1.0 and s2["p99"] == 1.0
    # direct edges: zero totals and empty bounds return 0.0, never
    # divide or index out of range
    assert _quantile_from_buckets(0.5, (1.0,), [0, 0]) == 0.0
    assert _quantile_from_buckets(0.5, (), []) == 0.0
    assert _quantile_from_buckets(0.99, (1.0,), [0, 5]) == 1.0


def test_diff_snapshots_fleet_edge_cases():
    """Satellite (PR 17): ``diff_snapshots`` edges the fleet snapshot
    merge leans on — histogram-delta quantiles computed from the
    WINDOW's bucket deltas only, gauge hwm across empty / stale
    windows (process-lifetime caveat), and counter/histogram resets
    (a fresh registry after a crash replaces ``after``)."""
    reg = MetricsRegistry()
    h = reg.histogram("t.lat", buckets=(0.1, 1.0, 10.0))
    # pre-window mass lands entirely in the FIRST bucket...
    for _ in range(100):
        h.observe(0.05)
    before = reg.snapshot()
    # ...window mass entirely in the LAST finite bucket: quantiles of
    # the delta must ignore the 100 earlier observations completely
    for _ in range(4):
        h.observe(5.0)
    cell = diff_snapshots(before, reg.snapshot())["t.lat"]["values"][""]
    assert cell["count"] == 4 and abs(cell["sum"] - 20.0) < 1e-9
    assert 1.0 <= cell["p50"] <= 10.0
    assert 1.0 <= cell["p99"] <= 10.0
    # single-bucket histogram: delta quantiles interpolate in
    # [0, bound] and never NaN on a one-observation window
    regs = MetricsRegistry()
    h1 = regs.histogram("t.one", buckets=(2.0,))
    h1.observe(0.5)
    b1 = regs.snapshot()
    h1.observe(1.5)
    c1 = diff_snapshots(b1, regs.snapshot())["t.one"]["values"][""]
    assert c1["count"] == 1
    assert 0.0 <= c1["p50"] <= 2.0 and 0.0 <= c1["p99"] <= 2.0

    # gauge hwm: an EMPTY window (nothing moved) drops the gauge even
    # though its level is nonzero — stale levels are never re-reported
    regg = MetricsRegistry()
    g = regg.gauge("t.depth")
    g.set(10)
    s0 = regg.snapshot()
    assert diff_snapshots(s0, s0) == {}
    # value returns to its pre-window level but the hwm moved: the
    # window DID see activity and must report it (hwm 10 -> 12)
    g.set(12)
    g.set(10)
    d = diff_snapshots(s0, regg.snapshot())
    assert d["t.depth"] == {"type": "gauge", "values": {"": 10},
                            "hwm": {"": 12}}
    # process-lifetime caveat: a later window whose activity stayed
    # BELOW the earlier peak still reports the old hwm of 12
    s1 = regg.snapshot()
    g.set(3)
    d2 = diff_snapshots(s1, regg.snapshot())
    assert d2["t.depth"]["values"][""] == 3
    assert d2["t.depth"]["hwm"][""] == 12

    # counter reset: ``after`` taken from a FRESH registry (crashed
    # replica rejoining) sits below ``before`` — the delta goes
    # negative rather than silently clamping, so reconciliation
    # arithmetic stays exact and the reset is visible
    rega = MetricsRegistry()
    rega.counter("t.c").inc(9)
    ba = rega.snapshot()
    regb = MetricsRegistry()
    regb.counter("t.c").inc(2)
    assert diff_snapshots(ba, regb.snapshot())["t.c"]["values"][""] == -7
    # histogram reset: the window's count delta is <= 0, and a
    # quantile over negative bucket mass is meaningless — the cell
    # drops entirely (same contract as an unmoved cell)
    regh = MetricsRegistry()
    hh = regh.histogram("t.h", buckets=(1.0,))
    hh.observe(0.5)
    hh.observe(0.5)
    bh = regh.snapshot()
    regh2 = MetricsRegistry()
    regh2.histogram("t.h", buckets=(1.0,)).observe(0.5)
    assert diff_snapshots(bh, regh2.snapshot()) == {}
    # instruments present in ``before`` but absent from the fresh
    # ``after`` drop out (diff iterates ``after``); absent from
    # ``before`` count from zero
    regf = MetricsRegistry()
    regf.counter("t.new").inc(5)
    df = diff_snapshots(ba, regf.snapshot())
    assert df == {"t.new": {"type": "counter", "values": {"": 5}}}


def _drive_timeseries(clock):
    """One synthetic 10-step trace into a capacity-4 recorder —
    deterministic modulo the injected wall clock."""
    reg = MetricsRegistry()
    c = reg.counter("t.tokens")
    g = reg.gauge("t.depth")
    h = reg.histogram("t.lat", buckets=(0.1, 1.0))
    ts = TimeSeriesRecorder(reg, capacity=4, clock=clock)
    g.set(100)                       # pre-window peak, dropped by ring
    for step in range(10):
        c.inc(3)
        g.set(step)
        h.observe(0.05 if step % 2 else 0.5)
        ts.sample(step)
    return reg, ts


def test_timeseries_ring_overflow_determinism():
    """Satellite (PR 17): ``TimeSeriesRecorder`` ring overflow drops
    the OLDEST samples with honest accounting, window aggregates are
    computed over the SURVIVING window only (gauge max = per-window
    hwm, not the registry's process-lifetime hwm), and two identical
    traces serialize byte-for-byte modulo wall."""
    import itertools
    wall = itertools.count(1000)
    reg1, ts1 = _drive_timeseries(lambda: float(next(wall)))
    reg2, ts2 = _drive_timeseries(time.perf_counter)

    # overflow accounting: 10 samples into capacity 4 keeps the last
    # 4 and counts the 6 evicted ones — never silently partial
    assert len(ts1) == 4 and ts1.dropped == 6
    assert ts1.steps() == [6, 7, 8, 9]
    # cumulative storage: a dropped sample loses resolution, not mass
    assert ts1.series("t.tokens") == [(6, 21), (7, 24), (8, 27), (9, 30)]
    assert ts1.rates("t.tokens") == [(7, 3.0), (8, 3.0), (9, 3.0)]
    agg = ts1.aggregates()
    assert agg["first_step"] == 6 and agg["last_step"] == 9
    assert agg["dropped"] == 6 and agg["samples"] == 4
    tok = agg["instruments"]["t.tokens"]
    assert tok["delta"][""] == 9                 # window delta, not 30
    assert abs(tok["rate_per_step"][""] - 3.0) < 1e-9
    # per-window gauge hwm: the pre-window peak of 100 was evicted
    # with its ring slot — max reflects only surviving samples, while
    # the registry hwm still remembers the process-lifetime peak
    dep = agg["instruments"]["t.depth"]
    assert dep["last"][""] == 9 and dep["min"][""] == 6
    assert dep["max"][""] == 9
    assert reg1.gauge("t.depth").hwm() == 100
    # histogram window delta: the oldest surviving sample is the
    # BASE, so the delta covers steps 7..9 (0.05 + 0.5 + 0.05)
    lat = agg["instruments"]["t.lat"]["values"][""]
    assert lat["count"] == 3 and abs(lat["sum"] - 0.6) < 1e-9

    # replay determinism: different wall clocks, identical canonical
    # form once the report-only wall is dropped...
    j1 = json.dumps(ts1.to_dict(drop_wall=True), sort_keys=True)
    j2 = json.dumps(ts2.to_dict(drop_wall=True), sort_keys=True)
    assert j1 == j2
    # ...and the wall-bearing forms differ (the clocks really ran)
    assert (json.dumps(ts1.to_dict(), sort_keys=True)
            != json.dumps(ts2.to_dict(), sort_keys=True))


def test_span_name_roundtrip():
    enc = format_span_name("serving.prefill", {"request": 3, "slot": 1})
    assert enc == "serving.prefill;request=3;slot=1"
    name, attrs = parse_span_name(enc)
    assert name == "serving.prefill"
    assert attrs == {"request": "3", "slot": "1"}
    assert parse_span_name("plain") == ("plain", {})
    # hostile attr values cannot fabricate extra attrs on re-parse
    name2, attrs2 = parse_span_name(
        format_span_name("myapp.handle", {"url": "a=1;b=2"}))
    assert name2 == "myapp.handle" and attrs2 == {"url": "a=1;b=2"}


# ---------------------------------------------------------------------------
# pallas routing counter
# ---------------------------------------------------------------------------

def test_decode_attention_route_counter(monkeypatch):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import decode_attention as da

    monkeypatch.setattr(da, "pallas_enabled", lambda: True)
    c = get_registry().counter("pallas.decode_attention.route",
                               labels=("decision", "reason"))
    base_mix = c.value(decision="xla", reason="dtype_mismatch")
    base_ok = c.value(decision="pallas", reason="ok")
    q4 = jax.ShapeDtypeStruct((2, 2, 2, 64), jnp.float32)
    kc_bf16 = jax.ShapeDtypeStruct((2, 16, 128), jnp.bfloat16)
    assert not da.should_use_pallas(q4, kc_bf16)
    assert c.value(decision="xla",
                   reason="dtype_mismatch") == base_mix + 1
    kc_f32 = jax.ShapeDtypeStruct((2, 16, 128), jnp.float32)
    assert da.should_use_pallas(q4, kc_f32)
    assert c.value(decision="pallas", reason="ok") == base_ok + 1


# train-step compile/step instrument coverage piggybacks on the existing
# TrainStep parity test (tests/test_amp_io_jit.py::
# test_train_step_compiled_matches_eager) — no extra XLA compile here.

# ---------------------------------------------------------------------------
# serving engine instrumentation — ONE module-scoped trace covers the
# acceptance criteria (export, merged trace, stats equality, overhead)
# ---------------------------------------------------------------------------

P, C = 6, 32
SPECS = [(4, 4), (3, 3), (5, 2)]           # (seq_len, max_new)


@pytest.fixture(scope="module")
def served():
    paddle.seed(2024)
    # 1-layer tiny config + steps_per_call=1 (ONE decode-block compile):
    # tier-1 is truncation-scored, so this module keeps XLA work minimal
    cfg = models.LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=1, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64)
    net = models.LlamaForCausalLM(cfg)
    net.eval()
    reg = MetricsRegistry()
    eng = ServingEngine(net, num_slots=2, prompt_len=P, max_cache_len=C,
                        steps_per_call=1, compute_dtype="float32",
                        registry=reg)
    rng = np.random.default_rng(7)
    with Profiler(targets=[ProfilerTarget.CPU]) as prof:
        reqs = [eng.submit(
            rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32),
            max_new_tokens=m) for n, m in SPECS]
        done = eng.run()
    stats = eng.stats()
    host_events = prof.events()

    # disabled-mode decode-block timing: the registry is off, so every
    # instrument touch in step() is the one-bool-check fast path; the
    # tracer is off too (outside the profiler window)
    reg.disable()
    eng.submit(rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32),
               max_new_tokens=16)
    step_times = []
    while eng._queue or any(s is not None for s in eng._slots):
        t0 = time.perf_counter()
        eng.step()
        step_times.append(time.perf_counter() - t0)
    reg.enable()
    return SimpleNamespace(reg=reg, eng=eng, reqs=reqs, done=done,
                           stats=stats, host_events=host_events,
                           step_times=step_times)


def test_serving_prometheus_export(served):
    text = served.reg.to_prometheus_text()
    assert "# TYPE serving_queue_depth gauge" in text
    assert "serving_slot_occupancy" in text
    assert "serving_slots_total 2" in text
    assert f"serving_prefills {len(SPECS)}" in text
    assert "serving_tokens_emitted" in text
    assert "serving_request_latency_seconds_bucket" in text
    assert 'serving_request_latency_seconds_quantile{quantile="0.99"}' \
        in text
    assert 'serving_ttft_seconds_quantile{quantile="0.50"}' in text


def test_serving_stats_equal_registry(served):
    """Acceptance (c): stats() is derived FROM the registry; with a
    fresh per-engine registry the per-engine deltas equal the raw
    instrument values."""
    s, reg = served.stats, served.reg
    assert s["decode_steps"] == reg.get("serving.decode_steps").value()
    assert s["busy_slot_steps"] == \
        reg.get("serving.busy_slot_steps").value()
    assert s["block_dispatches"] == \
        reg.get("serving.block_dispatches").value()
    assert s["prefills"] == reg.get("serving.prefills").value() \
        == len(SPECS)
    assert s["finished"] == \
        reg.get("serving.requests_finished").value() == len(SPECS)
    assert s["peak_queue"] == reg.get("serving.queue_depth").hwm()
    assert s["mean_slot_occupancy"] == pytest.approx(
        s["busy_slot_steps"] / (s["decode_steps"] * s["num_slots"]))
    # lifecycle accounting: every request fully emitted + measured
    assert reg.get("serving.tokens_emitted").value() >= \
        sum(m for _, m in SPECS)
    assert reg.get("serving.request_latency_seconds") \
        .summary()["count"] == len(SPECS)
    assert reg.get("serving.ttft_seconds").summary()["count"] == len(SPECS)
    assert reg.get("serving.queue_depth").value() == 0   # drained
    assert reg.get("serving.slot_occupancy").value() == 0


def test_serving_lifecycle_spans_recorded(served):
    from paddle_tpu.observability.spans import parse_span_name as parse
    names = [parse(e[5])[0] for e in served.host_events]
    for expected in ("serving.request.queued", "serving.prefill",
                     "serving.decode_block", "serving.request.finish"):
        assert expected in names, expected
    # span attrs survive the tracer round trip
    attrs = [parse(e[5])[1] for e in served.host_events
             if parse(e[5])[0] == "serving.decode_block"]
    assert attrs and all("steps" in a and "active" in a for a in attrs)
    # SummaryView strips attr suffixes: one aggregated row per span
    # name, not one per request/dispatch
    from paddle_tpu.profiler import SummaryView
    rows = {r["name"]: r for r in SummaryView(served.host_events).rows()}
    # (a chunk a prompt here, and a landing of first tokens for every
    # step that ran a final chunk)
    assert rows["serving.prefill"]["calls"] == \
        len(SPECS) + served.stats["first_token_fetches"]
    assert not any(";" in n for n in rows)


def test_merged_chrome_trace(served, tmp_path):
    # the device's events are not pasted beside the host lanes on a
    # clock of their own any more: under a profiler session a span sits
    # in the profiler's OWN trace, on the host's line beside where the
    # device's lines are, attrs as stats -- and in the one buffer
    import glob
    import jax
    from jax.profiler import ProfileData
    from paddle_tpu.observability import recorded
    jax.profiler.start_trace(str(tmp_path / "xprof"))
    try:
        with span("serving.decode_block", steps=3, active=2):
            pass
        live = [(n, a) for n, _t0, _t1, _tid, a in recorded()
                if n.startswith("serving.")]
    finally:
        jax.profiler.stop_trace()
    xplane = glob.glob(str(tmp_path / "xprof" / "plugins" / "profile"
                           / "*" / "*.xplane.pb"))[-1]
    in_trace = [dict(e.stats)
                for plane in ProfileData.from_file(xplane).planes
                if plane.name == "/host:CPU"
                for line in plane.lines for e in line.events
                if e.name == "serving.decode_block"]
    assert in_trace == [{"steps": 3, "active": 2}]
    assert live == [("serving.decode_block",
                     {"steps": "3", "active": "2"})]
    out = str(tmp_path / "merged.json")
    info = merge_chrome_traces(out, host=served.host_events)
    assert set(info) == {"host_events", "extra_events", "path"}
    with open(out) as f:
        trace = json.load(f)
    evs = trace["traceEvents"]
    assert {e["pid"] for e in evs} == {0}              # host lanes only
    host_names = {e["name"] for e in evs}
    assert "serving.decode_block" in host_names        # attrs decoded
    blocks = [e for e in evs if e["name"] == "serving.decode_block"]
    assert all("steps" in e["args"] for e in blocks)
    assert info["host_events"] == len(evs) - 1         # + process_name
    # file-path host input decodes span attrs too (same contract as
    # the event-tuple and live-tracer forms)
    hostf = tmp_path / "host.json"
    hostf.write_text(json.dumps({"traceEvents": [
        {"name": "serving.prefill;request=9;slot=1", "ph": "X",
         "pid": 0, "tid": 1, "ts": 0, "dur": 5}]}))
    merge_chrome_traces(str(tmp_path / "m3.json"), host=str(hostf))
    with open(tmp_path / "m3.json") as f:
        t3 = json.load(f)
    ev3 = [e for e in t3["traceEvents"]
           if e["name"] == "serving.prefill"][0]
    assert ev3["args"] == {"request": "9", "slot": "1"}


def test_disabled_overhead_under_2pct(served):
    """Acceptance: disabled-mode instrument overhead on the decode
    block loop < 2%.  ``step_times`` were measured in the fixture with
    the registry disabled; here the exact per-iteration instrument
    touch sequence (a superset of step()'s) is timed on a disabled
    registry and compared against the measured block time."""
    t_block = float(np.median(served.step_times))
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("o.c")
    g = reg.gauge("o.g")
    h = reg.histogram("o.h")

    def touches():                  # >= the per-step() instrument work
        c.inc()
        c.inc(2)
        c.inc(2)
        c.inc()
        c.inc()
        g.set(3)
        g.set(2)
        h.observe(0.01)
        h.observe(0.02)
        with span("serving.decode_block", steps=2, active=1):
            pass

    n = 3000
    t0 = time.perf_counter()
    for _ in range(n):
        touches()
    t_inst = (time.perf_counter() - t0) / n
    # prototype: ~3 us of disabled-path calls vs ~1.4 ms block -> 0.2%
    assert t_inst < 0.02 * t_block, (t_inst, t_block)


# ---------------------------------------------------------------------------
# lint: instrument names across the tree
# ---------------------------------------------------------------------------

def _load_lint():
    path = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "check_metrics_names.py")
    spec = importlib.util.spec_from_file_location("check_metrics_names",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_metrics_name_lint_clean():
    lint = _load_lint()
    errors, regs = lint.check()         # ONE walk (main() would re-walk)
    assert errors == []
    # the lint actually sees the built-in instruments
    names = {r[3] for r in regs}
    assert "serving.queue_depth" in names
    assert "train_step.compiles" in names
    assert "pallas.decode_attention.route" in names
    # the paged serving instruments are covered too
    for n in ("serving.blocks_free", "serving.blocks_in_use",
              "serving.prefix_hits", "serving.prefix_misses",
              "serving.prefill_chunks", "serving.requests_cancelled",
              "serving.prefill_chunk_seconds"):
        assert n in names, n
    # the speculative-decoding, int8-KV, sampling, overload, prefix
    # and goodput/SLO sets are all registered AND enforced by the
    # lint's required-instruments rule (rule 4: deleting a
    # registration site must fail the lint, not flatline a dashboard)
    for n, (kind, labels) in lint.REQUIRED_INSTRUMENTS.items():
        assert n.startswith(
            ("serving.spec.", "serving.kv.", "serving.sample.",
             "serving.preempt.", "serving.swap.", "serving.shed.",
             "serving.timeout.", "serving.prefix.",
             "serving.goodput.", "serving.slo.", "serving.step.",
             "serving.async.", "serving.fault.",
             "serving.lora.", "serving.fairshare.",
             "serving.router.", "serving.migrate.",
             "serving.weights.", "pallas.quantized_matmul.",
             "serving.fleet.", "serving.alerts",
             "serving.shard.", "serving.transport.",
             "serving.handoff.", "serving.role",
             "pallas.decode_attention.route",
             "pallas.moe_experts.route",
             "pallas.kda_decode.route",
             "serving.tpot_seconds")), n
        assert n in names, n
    kinds = {r[3]: r[2] for r in regs}
    assert kinds["serving.spec.accepted_length"] == "histogram"
    assert kinds["serving.spec.verify_steps"] == "counter"
    assert kinds["serving.kv.bytes_swept"] == "counter"
    assert kinds["serving.kv.quant_dtype"] == "gauge"
    assert kinds["serving.sample.sampled_tokens"] == "counter"
    assert kinds["serving.sample.resamples"] == "counter"
    # the overload-resilience set is registered with the right kinds
    # (a gauge silently re-registered as a counter would break the
    # bench's overload arm and any SLO dashboard)
    assert kinds["serving.preempt.requests"] == "counter"
    assert kinds["serving.swap.blocks_out"] == "counter"
    assert kinds["serving.swap.host_blocks"] == "gauge"
    assert kinds["serving.shed.requests"] == "counter"
    assert kinds["serving.timeout.requests"] == "counter"
    # the tiered-prefix-cache set (bench prefix_tiered arm)
    assert kinds["serving.prefix.hit_tokens"] == "counter"
    assert kinds["serving.prefix.partial_hits"] == "counter"
    assert kinds["serving.prefix.host_hits"] == "counter"
    assert kinds["serving.prefix.host_swapin_blocks"] == "counter"
    # the goodput-ledger / latency-attribution / SLO set (PR 9)
    assert kinds["serving.goodput.useful_tokens"] == "counter"
    assert kinds["serving.goodput.wasted_tokens"] == "counter"
    assert kinds["serving.goodput.dispatched_tokens"] == "counter"
    assert kinds["serving.step.host_seconds"] == "histogram"
    assert kinds["serving.step.dispatch_seconds"] == "histogram"
    assert kinds["serving.tpot_seconds"] == "histogram"
    assert kinds["serving.slo.attained"] == "counter"
    assert kinds["serving.slo.missed"] == "counter"
    # labeled overload counters carry their declared label tuples
    by_lbl = {r[3]: r[4] for r in regs}
    assert by_lbl["serving.shed.requests"] == ("reason",)
    assert by_lbl["serving.requests_cancelled"] == ("phase",)
    # PR 11: the goodput/SLO set carries the per-tenant label
    assert by_lbl["serving.goodput.wasted_tokens"] == \
        ("reason", "tenant")
    assert by_lbl["serving.slo.attained"] == ("class", "tenant")
    assert by_lbl["serving.slo.missed"] == ("class", "tenant")
    # the multi-tenant LoRA + fair-share set (PR 11)
    assert kinds["serving.lora.hbm_adapters"] == "gauge"
    assert kinds["serving.lora.swap_ins"] == "counter"
    assert kinds["serving.lora.gathers"] == "counter"
    assert kinds["serving.fairshare.reorders"] == "counter"
    # the front-door router set (PR 12): intake/decision counters
    # carry their label tuples, the queue/replica gauges stay gauges
    assert kinds["serving.router.requests"] == "counter"
    assert kinds["serving.router.routed"] == "counter"
    assert kinds["serving.router.prefix_affinity_tokens"] == "counter"
    assert kinds["serving.router.adapter_affinity_hits"] == "counter"
    assert kinds["serving.router.shed"] == "counter"
    assert kinds["serving.router.timeouts"] == "counter"
    assert kinds["serving.router.queue_depth"] == "gauge"
    assert kinds["serving.router.engines"] == "gauge"
    assert by_lbl["serving.router.requests"] == ("policy",)
    assert by_lbl["serving.router.routed"] == ("reason",)
    assert by_lbl["serving.router.shed"] == ("reason",)
    # the replica-failover set (PR 15): fault/path/outcome labels and
    # the cross-replica migration volume counters
    assert kinds["serving.router.failover.replica_faults"] == "counter"
    assert kinds["serving.router.healthy_engines"] == "gauge"
    assert kinds["serving.migrate.blocks"] == "counter"
    assert kinds["serving.migrate.bytes"] == "counter"
    assert by_lbl["serving.router.failover.replica_faults"] == \
        ("fault",)
    assert by_lbl["serving.router.failover.requests"] == ("path",)
    assert by_lbl["serving.router.failover.probes"] == ("outcome",)
    assert by_lbl["serving.fairshare.served_tokens"] == ("tenant",)
    assert by_lbl["serving.fairshare.deficit"] == ("tenant",)
    # rule 4 fires on a missing required name
    import tempfile
    with tempfile.TemporaryDirectory() as empty_root:
        os.makedirs(os.path.join(empty_root, "paddle_tpu"))
        errs, _ = lint.check(empty_root)
        missing = [e for e in errs if "required instrument" in e]
        assert len(missing) == len(lint.REQUIRED_INSTRUMENTS)
    # the AST walker resolves labels: the route counter's label tuple
    # is visible to the conflict rule
    by_name = {r[3]: r[4] for r in regs}
    assert by_name["pallas.decode_attention.route"] == \
        ("decision", "reason")


def test_metrics_name_lint_catches_violations(tmp_path):
    lint = _load_lint()
    pkg = tmp_path / "paddle_tpu"
    pkg.mkdir()
    (pkg / "bad.py").write_text(
        'r.counter("Bad.Name")\n'
        'r.counter("dup.name")\n'
        'r.gauge("dup.name")\n'
        'r.counter("lbl.name", "help", labels=("a", "b"))\n'
        'r.counter("lbl.name", "help", labels=("a",))\n'
        'r.counter("lbl.bare", "help", labels=("a",))\n'
        'r.counter("lbl.bare")\n'
        'r.counter("lbl.dyn", "help", labels=("a",))\n'
        'r.counter("lbl.dyn", "help", labels=make_labels())\n'
        'HostTracer.counter("Free Form OK", 1)\n')
    all_errors, regs = lint.check(str(tmp_path))
    # the synthetic tree registers none of the required instruments, so
    # rule 4 fires once per required name on top of the 4 violations
    required = [e for e in all_errors if "required instrument" in e]
    assert len(required) == len(lint.REQUIRED_INSTRUMENTS)
    errors = [e for e in all_errors if "required instrument" not in e]
    assert len(errors) == 4
    assert any("Bad.Name" in e for e in errors)
    assert any("dup.name" in e and "conflict" not in e for e in errors)
    # conflicting literal label tuples caught — including a bare
    # (unlabeled) site vs a labeled one; dynamic labels opt out
    assert any("lbl.name" in e for e in errors)
    assert any("lbl.bare" in e for e in errors)
    assert all("lbl.dyn" not in e for e in errors)
    assert all("Free Form OK" not in e for e in errors)


def test_metrics_lint_docs_sync_and_label_rules(tmp_path):
    """Rule 4's label check and rule 5 (docs-sync): a required
    instrument registered with the wrong label tuple fails, and a
    required name missing from README.md fails — while a README that
    names everything is clean."""
    lint = _load_lint()
    pkg = tmp_path / "paddle_tpu"
    pkg.mkdir()
    lines = []
    for name, (kind, labels) in lint.REQUIRED_INSTRUMENTS.items():
        lines.append(
            f'r.{kind}("{name}", "h", labels={tuple(labels or ())!r})')
    (pkg / "m.py").write_text("\n".join(lines) + "\n")
    all_names = sorted(lint.REQUIRED_INSTRUMENTS)
    # README missing exactly one required name -> exactly one error
    (tmp_path / "README.md").write_text("\n".join(all_names[:-1]))
    errs, _ = lint.check(str(tmp_path))
    assert len(errs) == 1
    assert all_names[-1] in errs[0] and "README" in errs[0]
    # README naming every required instrument -> clean
    (tmp_path / "README.md").write_text("\n".join(all_names))
    assert lint.check(str(tmp_path))[0] == []
    # a required instrument re-registered with the WRONG labels fails
    # the label half of rule 4 (relabeling re-keys exported series)
    bad = '\nq = r.counter("serving.goodput.wasted_tokens", "h", ' \
          'labels=("oops",))\n'
    (pkg / "m.py").write_text(
        "\n".join(l for l in lines
                  if "serving.goodput.wasted_tokens" not in l)
        + bad)
    errs3, _ = lint.check(str(tmp_path))
    assert any("serving.goodput.wasted_tokens" in e and "labels" in e
               for e in errs3)
