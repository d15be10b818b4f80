"""Fleet observability plane (PR 17): cross-replica trace stitching,
the step-indexed time-series recorder, the per-tenant SLO burn-rate
monitor and ``Router.fleet_snapshot()`` / ``tools/serving_top.py``.

Tier-1 budget discipline: ONE module-scoped 2-replica kill/failover
trace (the PR-15 recipe — force-swap one request, kill its replica,
migrate at exact bytes) run TWICE with private registries/recorders,
and every acceptance property asserted off those two runs: stitched
replay-determinism (byte-identical modulo wall), the cross-replica
``explain()`` narration with the exact migrated-block count,
``fleet_snapshot()`` reconciling cell-for-cell against the per-replica
registries, and the ``replica_unhealthy`` alert fired exactly once at
the deterministic kill step.  Dispatch-free unit tests (stitcher
corner cases, monitor latching, snapshot merging, the CLIs) ride the
same module."""

import importlib.util
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import models
from paddle_tpu.inference import (FaultInjector, Router, ServingEngine)
from paddle_tpu.inference.serving import TERMINAL_STATES
from paddle_tpu.observability import MetricsRegistry
from paddle_tpu.observability.fleet import (
    ALERT_KINDS, ROUTER_LANE, SLOBurnRateMonitor, StitchedRecord,
    merge_registry_snapshots, orphan_id, stitch_flight_records)
from paddle_tpu.observability.flightrec import (ENGINE_EVENT,
                                                FlightRecorder)
from paddle_tpu.observability.timeseries import TimeSeriesRecorder

P, C, BL = 32, 48, 4


@pytest.fixture(scope="module")
def netm():
    paddle.seed(1234)
    cfg = models.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=1, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=64)
    net = models.LlamaForCausalLM(cfg)
    net.eval()
    return cfg, net


def _mk(net, *, registry, recorder, injector):
    return ServingEngine(
        net, num_slots=2, prompt_len=P, max_cache_len=C,
        steps_per_call=1, block_len=BL, chunk_len=4, num_blocks=16,
        compute_dtype="float32", registry=registry,
        flight_recorder=recorder, fault_injector=injector)


def _run_trace(netm):
    """One full 2-replica kill/failover trace with the whole fleet
    plane attached; returns every artifact the asserts need."""
    cfg, net = netm
    rng = np.random.default_rng(77)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (10, 7, 8)]
    # long enough that the trace outlives the kill by several router steps
    # (the probes, the ring's overflow) now that an engine of
    # ``steps_per_call=1`` prefills both its waiting prompts in one step
    news = [9, 8, 7]

    regs = [MetricsRegistry() for _ in range(2)]
    recs = [FlightRecorder() for _ in range(2)]
    injs = [FaultInjector() for _ in range(2)]
    engs = [_mk(net, registry=regs[i], recorder=recs[i],
                injector=injs[i]) for i in range(2)]
    rrec = FlightRecorder()
    rreg = MetricsRegistry()
    mon = SLOBurnRateMonitor(slo_target=0.9, window_steps=8)
    ts = TimeSeriesRecorder(rreg, capacity=8)
    rt = Router(engs, affinity=True, registry=rreg,
                flight_recorder=rrec, monitor=mon, timeseries=ts)

    hs = [rt.submit(prompts[0], max_new_tokens=news[0],
                    arrival_time=0.0, deadline_s=1e9, tenant="chat"),
          rt.submit(prompts[1], max_new_tokens=news[1],
                    arrival_time=0.0, deadline_s=1e9, tenant="batch"),
          rt.submit(prompts[2], max_new_tokens=news[2],
                    arrival_time=0.0)]
    rt.step(now=0.0)                       # routes everything
    assert all(h.engine is not None for h in hs)
    vi = hs[0].engine
    victim, vinj = engs[vi], injs[vi]
    for _ in range(4):                     # let r0 decode a bit
        rt.step(now=0.0)
    assert hs[0].state == "decode"
    vinj.force_swap(hs[0].request_id)
    vinj.fail_allocs(None)
    rt.step(now=0.0)
    assert hs[0].state == "swapped"
    vblocks = hs[0]._req.swap.n_blocks
    assert vblocks > 0
    vinj.kill_at_step(victim._step_idx + 1)
    rt.step(now=0.0)                       # the kill fires -> failover
    kill_step = rt._step_idx
    assert rt.health[vi] == "unhealthy"
    steps = 0
    while any(h.state not in TERMINAL_STATES for h in hs):
        rt.step(now=0.0)
        for e in engs:
            e._pool.check()
        steps += 1
        assert steps < 120, "trace did not drain"
    assert all(h.state == "finished" for h in hs)
    stats = rt.stats()
    snap = rt.fleet_snapshot()
    return {
        "rt": rt, "engs": engs, "regs": regs, "recs": recs,
        "rrec": rrec, "mon": mon, "ts": ts, "hs": hs, "vi": vi,
        "vblocks": vblocks, "kill_step": kill_step, "stats": stats,
        "snap": snap, "stitched": rt.stitched_record(),
        "outputs": [np.asarray(h.output) for h in hs],
    }


@pytest.fixture(scope="module")
def trace(netm):
    """THE combined trace, twice — the replay pair every determinism
    assert compares."""
    return _run_trace(netm), _run_trace(netm)


# ---------------------------------------------------------------------------
# acceptance: the combined trace
# ---------------------------------------------------------------------------

def test_stitched_record_replay_deterministic(trace):
    """Two runs of one trace stitch byte-identically modulo wall, and
    the stitched record loses no events: its length is exactly the
    sum of the router's and every replica's ring."""
    t1, t2 = trace
    d1 = t1["stitched"].to_dict(drop_wall=True)
    d2 = t2["stitched"].to_dict(drop_wall=True)
    assert json.dumps(d1, sort_keys=True) == \
        json.dumps(d2, sort_keys=True)
    # the scheduling itself replayed exactly (sanity anchor)
    for a, b in zip(t1["outputs"], t2["outputs"]):
        assert np.array_equal(a, b)
    st = t1["stitched"]
    expected = len(t1["rrec"].events()) + sum(
        len(r.events()) for r in t1["recs"])
    assert len(st) == expected == d1["n_events"]
    assert st.replicas == 2
    assert st.dropped_total == 0           # rings were big enough
    # ordering invariant: sorted by (step, lane, seq) — router lane
    # first within a step.  (Per-lane seq is NOT globally monotonic:
    # dispatch-ahead engines stamp a deferred-harvest finish with its
    # DISPATCH step, so a later-seq event can carry an earlier step.)
    def key(e):
        return (e.step,
                -1 if e.replica == ROUTER_LANE else e.replica, e.seq)
    assert [key(e) for e in st.events] == \
        sorted(key(e) for e in st.events)


def test_stitched_ids_and_orphans(trace):
    """Engine events re-keyed to router-global ids; the failover
    probes (direct submissions, no route event) became deterministic
    negative orphan ids, never collided with real traffic."""
    t1, _ = trace
    st = t1["stitched"]
    gids = st.request_ids()
    assert gids == sorted(h.router_id for h in t1["hs"])
    # every engine-lane event resolved: router-global, orphan, or the
    # engine-scoped lane — nothing kept a raw per-replica id
    orphans = {e.request for e in st.events if e.request <= -1000}
    assert orphans                          # the probes are in there
    for e in st.events:
        if e.replica == ROUTER_LANE:
            continue
        assert e.request in gids or e.request == ENGINE_EVENT \
            or e.request in orphans
    # the victim's story crosses lanes: events on both replicas
    lanes = {e.replica for e in st.timeline(t1["hs"][0].router_id)}
    assert {t1["vi"], 1 - t1["vi"], ROUTER_LANE} <= lanes


def test_fleet_explain_narrates_the_hop(trace):
    """The acceptance sentence: killed at the kill step, migrated
    exactly vblocks blocks, finished on the survivor."""
    t1, t2 = trace
    vi, vblocks = t1["vi"], t1["vblocks"]
    text = t1["stitched"].explain(t1["hs"][0].router_id)
    assert f"replica {vi} killed at step {t1['kill_step']}" in text
    assert f"migrated {vblocks} blocks to engine {1 - vi} " \
           f"at exact bytes" in text
    assert f"on engine {1 - vi}" in text
    assert "finished at step" in text
    # deterministic narration across replays
    assert text == t2["stitched"].explain(t2["hs"][0].router_id)
    # unknown ids stay honest
    assert "no events in the stitched record" in \
        t1["stitched"].explain(99999)


def test_alert_fired_exactly_once_at_kill_step(trace):
    """The replica_unhealthy alert: exactly one firing, at the
    deterministic kill step, latched across the whole unhealthy
    stretch, counted in serving.alerts AND present as a
    replay-deterministic flight-recorder event."""
    t1, t2 = trace
    for t in (t1, t2):
        alerts = t["mon"].alerts()
        assert alerts == [{"kind": "replica_unhealthy",
                           "step": t["kill_step"],
                           "engine": t["vi"]}]
        reg = t["rt"]._m.registry
        assert reg.get("serving.alerts").value(
            kind="replica_unhealthy") == 1
        evs = [e for e in t["rrec"].events() if e.kind == "alert"]
        assert len(evs) == 1
        assert evs[0].request == ENGINE_EVENT
        assert evs[0].step == t["kill_step"]
        assert evs[0].attrs == {"kind": "replica_unhealthy",
                                "engine": t["vi"]}
        # and it rides the stitched record on the router lane
        sevs = [e for e in t["stitched"].events if e.kind == "alert"]
        assert len(sevs) == 1 and sevs[0].replica == ROUTER_LANE
    assert t1["kill_step"] == t2["kill_step"]
    # no SLO burn on this trace: every request finished inside its
    # huge deadline, so the windowed burn rate stayed 0 per tenant
    assert t1["mon"].burn_rates() == {"batch": 0.0, "chat": 0.0}
    b = t1["mon"].budgets()
    assert b["chat"]["missed"] == 0 and b["chat"]["consumed"] == 0.0


def test_fleet_snapshot_reconciles_against_replicas(trace):
    """fleet_snapshot(): every per-replica registry cell appears under
    its replica=<i> label with the exact same value, health/load
    mirror the router, and the embedded router stats match stats()."""
    t1, _ = trace
    snap, rt = t1["snap"], t1["rt"]
    assert snap["engines"] == 2
    assert snap["health"] == t1["stats"]["health"]
    merged = snap["registries"]
    for i, reg in enumerate(t1["regs"]):
        for name, inst in reg.snapshot().items():
            assert merged[name]["type"] == inst["type"], name
            assert merged[name]["labels"][0] == "replica"
            for lk, v in inst["values"].items():
                key = f"replica={i}" + ("," + lk if lk else "")
                assert merged[name]["values"][key] == v, (name, key)
    # router stats embedded verbatim (modulo the snapshot counter the
    # call itself bumped — stats() was captured first)
    for k, v in t1["stats"].items():
        if k != "fleet":
            assert snap["router"][k] == v, k
    assert snap["router"]["migrated_blocks"] == t1["vblocks"]
    assert [r["slots_total"] for r in snap["load_reports"]] == [2, 2]
    assert snap["monitor"]["alerts_by_kind"] == \
        {"replica_unhealthy": 1}
    assert rt._m.registry.get("serving.fleet.snapshots").total() >= 1


def test_timeseries_sampled_per_router_step(trace):
    """The router drove the recorder once per step; the ring
    overflowed (capacity 16 < steps) with the loss counted; the
    window aggregates carry the per-window gauge hwm; and two
    replays produce byte-identical series modulo wall."""
    t1, t2 = trace
    ts = t1["ts"]
    assert len(ts) == ts.capacity == 8
    assert ts.dropped == t1["rt"]._step_idx - 8 > 0
    assert ts.steps() == list(range(t1["rt"]._step_idx - 7,
                                    t1["rt"]._step_idx + 1))
    assert json.dumps(ts.to_dict(drop_wall=True), sort_keys=True) == \
        json.dumps(t2["ts"].to_dict(drop_wall=True), sort_keys=True)
    agg = ts.aggregates()
    assert agg["samples"] == 8 and agg["dropped"] == ts.dropped
    g = agg["instruments"]["serving.router.healthy_engines"]
    assert g["type"] == "gauge" and g["last"][""] == 1.0
    assert snap_ts_equal(agg, t1["snap"]["timeseries"])


def snap_ts_equal(a, b):
    """aggregates() embedded in the snapshot was computed later (the
    ring may have identical content — same trace, no steps between) —
    they must agree exactly here because no step ran between."""
    return json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)


def test_perfetto_export_one_lane_per_replica(trace, tmp_path):
    """One chrome file: pid 0/1 = replicas, pid 2 = router lane, tid =
    router-global id, every stitched event present."""
    t1, _ = trace
    st = t1["stitched"]
    out = str(tmp_path / "fleet.json")
    info = st.export_chrome_trace(out)
    assert info["extra_events"] == len(st)
    with open(out) as f:
        doc = json.load(f)
    evs = [e for e in doc["traceEvents"] if e.get("ph") != "M"]
    assert len(evs) == len(st)
    assert {e["pid"] for e in evs} == {0, 1, 2}
    names = {(m["pid"], m["args"]["name"])
             for m in doc["traceEvents"]
             if m.get("ph") == "M" and m["name"] == "process_name"}
    assert {(0, "replica 0"), (1, "replica 1"),
            (2, "router")} <= names
    r0 = t1["hs"][0].router_id
    r0_pids = {e["pid"] for e in evs if e["tid"] == r0}
    assert {0, 1, 2} == r0_pids            # the hop crosses lanes


def test_serving_top_renders_and_checks(trace, tmp_path):
    """The dashboard is a pure function over the snapshot dict, and
    --check validates a dumped snapshot end to end (the tier-1 smoke
    the ISSUE wires in)."""
    t1, _ = trace
    snap = t1["snap"]
    top = _load_tool("serving_top")
    text = top.render(snap)
    assert text == top.render(snap)        # pure: same input, same text
    assert "2 replicas" in text
    assert "replica_unhealthy=1" in text
    assert f"migrated_blocks={t1['vblocks']}" in text
    assert "burn=" in text and "tenant chat" in text
    assert top.check(snap) == []
    path = str(tmp_path / "snap.json")
    with open(path, "w") as f:
        json.dump(snap, f)
    assert top.main([path, "--check"]) == 0
    assert top.main([path]) == 0
    # structural problems are named, not thrown
    bad = dict(snap, health=["healthy"])
    assert any("health" in p for p in top.check(bad))
    assert top.main([str(tmp_path / "missing.json"), "--check"]) == 1


def test_explain_request_cli_stitches(trace, tmp_path, capsys):
    """The multi-record CLI: per-replica exports + --router stitch
    into the fleet story, --timeline renders [on replica k] hops, and
    rc 1 survives for unknown ids."""
    t1, _ = trace
    paths = []
    for i, rec in enumerate(t1["recs"]):
        p = str(tmp_path / f"rep{i}.json")
        rec.export(p)
        paths.append(p)
    rpath = str(tmp_path / "router.json")
    t1["rrec"].export(rpath)
    cli = _load_tool("explain_request")
    r0 = t1["hs"][0].router_id
    # (the trailing-int request id must ride in the records chunk —
    # argparse consumes the positional list in one contiguous run)
    assert cli.main(paths + [str(r0), "--router", rpath]) == 0
    out = capsys.readouterr().out
    assert f"migrated {t1['vblocks']} blocks" in out
    assert cli.main(paths + [str(r0), "--router", rpath,
                             "--timeline"]) == 0
    out = capsys.readouterr().out
    assert "[on replica 0]" in out and "[on replica 1]" in out \
        and "[on router]" in out
    # all ids when none given; rc 1 for an unknown id; single-file
    # mode unchanged
    assert cli.main(paths + ["--router", rpath]) == 0
    assert cli.main(paths + ["424242", "--router", rpath]) == 1
    assert cli.main([paths[0]]) == 0


# ---------------------------------------------------------------------------
# dispatch-free units
# ---------------------------------------------------------------------------

def test_stitcher_units():
    """Corner cases no engine is needed for: generation counting under
    id reuse, orphan determinism, single-record passthrough, drop
    accounting, dict/list/path input forms."""
    router, r0 = FlightRecorder(), FlightRecorder()
    # engine rid 3 is used TWICE (id reuse after crash_reset): two
    # bindings, two submit generations, two distinct global ids
    router.emit("route", 10, 1, engine=0, rid=3, reason="load")
    router.emit("route", 11, 5, engine=0, rid=3, reason="load")
    r0.emit("submit", 3, 1)
    r0.emit("finish", 3, 2, tokens=1)
    r0.emit("submit", 3, 5)
    r0.emit("finish", 3, 6, tokens=2)
    # and one request the router never placed (a probe)
    r0.emit("submit", 8, 7)
    st = stitch_flight_records([r0], router=router)
    assert st.request_ids() == [10, 11]
    assert [e.request for e in st.timeline(10)] == [10, 10, 10]
    assert [e.kind for e in st.timeline(11)] == \
        ["route", "submit", "finish"]
    probe = [e for e in st.events if e.source_request == 8]
    assert probe[0].request == orphan_id(0, 8) == -(1000 + 8)
    assert orphan_id(1, 8) != orphan_id(0, 8)
    # without a router record, ids pass through verbatim
    alone = stitch_flight_records([r0])
    assert alone.request_ids() == [3, 8]
    # drop accounting flows into the stitched header and explain()
    tiny = FlightRecorder(capacity=2)
    tiny.emit("submit", 1, 1)
    tiny.emit("admit", 1, 1, slot=0)
    tiny.emit("finish", 1, 2, tokens=1)
    st2 = stitch_flight_records([tiny])
    assert st2.dropped == {"0": 1} and st2.dropped_total == 1
    assert "dropped 1 event" in st2.explain(1)
    assert "dropped 1 event" in st2.explain(777)   # unknown id too
    # export dict round-trips as a stitch input
    d = {"version": 1, "dropped": 2, "events": [
        {"seq": 0, "step": 1, "request": 4, "kind": "submit",
         "wall": 0.0, "attrs": {}}]}
    st3 = stitch_flight_records([d])
    assert st3.dropped_total == 2 and len(st3) == 1


def test_monitor_units():
    """Latching, burn math, budget exhaustion and re-arming — driven
    directly with synthetic counters, no router."""
    assert ALERT_KINDS == ("burn_rate", "budget_exhausted",
                           "replica_unhealthy", "queue_saturation")
    reg = MetricsRegistry()
    att = reg.counter("serving.slo.attained", "t",
                      labels=("class", "tenant"))
    mis = reg.counter("serving.slo.missed", "t",
                      labels=("class", "tenant"))
    mreg = MetricsRegistry()
    fr = FlightRecorder()
    mon = SLOBurnRateMonitor(slo_target=0.9, window_steps=4,
                             burn_threshold=1.0, registry=mreg,
                             flight_recorder=fr)
    with pytest.raises(ValueError, match="slo_target"):
        SLOBurnRateMonitor(slo_target=1.0)
    with pytest.raises(ValueError, match="window_steps"):
        SLOBurnRateMonitor(window_steps=1)
    # steps 0-2: all attained -> burn 0, no alerts
    for s in range(3):
        att.inc(**{"class": "p0", "tenant": "a"})
        mon.observe(step=s, registries=[reg])
    assert mon.alerts() == [] and mon.burn_rates() == {"a": 0.0}
    # steps 3-5: all missed -> window burn crosses 1.0x; the alert
    # fires ONCE despite the condition holding for three steps
    for s in range(3, 6):
        mis.inc(**{"class": "p0", "tenant": "a"})
        mon.observe(step=s, registries=[reg])
    burns = [a for a in mon.alerts() if a["kind"] == "burn_rate"]
    assert len(burns) == 1 and burns[0]["tenant"] == "a"
    # budget: the very first miss (1 of 4 total) already exceeds the
    # 10% lifetime budget -> exhausted fires once, immediately
    ex = [a for a in mon.alerts() if a["kind"] == "budget_exhausted"]
    assert len(ex) == 1 and ex[0] == {"kind": "budget_exhausted",
                                      "step": 3, "tenant": "a",
                                      "missed": 1, "total": 4}
    assert mon.budgets()["a"]["consumed"] > 1.0
    # recovery re-arms the latch: attained-only window clears it, a
    # fresh burn fires a second alert
    for s in range(6, 10):
        att.inc(**{"class": "p0", "tenant": "a"})
        mon.observe(step=s, registries=[reg])
    assert mon.burn_rates()["a"] == 0.0
    for s in range(10, 12):
        mis.inc(**{"class": "p0", "tenant": "a"})
        mon.observe(step=s, registries=[reg])
    assert len([a for a in mon.alerts()
                if a["kind"] == "burn_rate"]) == 2
    # queue saturation vs explicit depth; health transitions
    mon.observe(step=12, registries=[reg], health=["unhealthy"],
                queue_depth=5, max_queue=4)
    mon.observe(step=13, registries=[reg], health=["unhealthy"],
                queue_depth=5, max_queue=4)       # latched: no repeat
    kinds = [a["kind"] for a in mon.alerts()]
    assert kinds.count("queue_saturation") == 1
    assert kinds.count("replica_unhealthy") == 1
    # shared registries dedupe: passing the same registry twice must
    # not double-count outcomes
    assert mon._tenant_totals([reg, reg]) == \
        mon._tenant_totals([reg])
    # every firing rode the recorder as an 'alert' event
    assert len([e for e in fr.events() if e.kind == "alert"]) == \
        len(mon.alerts())
    # the summary mirrors the counters
    s = mon.summary()
    assert s["alerts_by_kind"]["burn_rate"] == 2
    assert mreg.get("serving.alerts").value(kind="burn_rate") == 2
    assert mreg.get("serving.slo.burn_rate").value(tenant="a") > 0
    assert mreg.get("serving.fleet.monitor_steps").total() == 14


def test_merge_registry_snapshots_units():
    reg0, reg1 = MetricsRegistry(), MetricsRegistry()
    for i, reg in enumerate((reg0, reg1)):
        c = reg.counter("m.ticks", "t", labels=("k",))
        c.inc(10 + i, k="x")
        g = reg.gauge("m.depth", "t")
        g.set(3 + i)
    merged = merge_registry_snapshots([reg0.snapshot(),
                                       reg1.snapshot()])
    assert merged["m.ticks"]["labels"] == ["replica", "k"]
    assert merged["m.ticks"]["values"] == {"replica=0,k=x": 10,
                                           "replica=1,k=x": 11}
    assert merged["m.depth"]["values"] == {"replica=0": 3,
                                           "replica=1": 4}
    assert merged["m.depth"]["hwm"] == {"replica=0": 3, "replica=1": 4}
    # explicit (value, snapshot) pairs: the shared-registry "+" idiom
    m2 = merge_registry_snapshots([("0+1", reg0.snapshot())])
    assert m2["m.ticks"]["values"] == {"replica=0+1,k=x": 10}
    # heterogeneous kinds are a bug, not data
    regX = MetricsRegistry()
    regX.gauge("m.ticks", "t", labels=("k",))
    with pytest.raises(ValueError, match="homogeneous"):
        merge_registry_snapshots([reg0.snapshot(), regX.snapshot()])


def _load_tool(name):
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
