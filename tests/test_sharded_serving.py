"""Mesh-sharded serving dryrun (PR 18): tensor-parallel paged decode
over the 8 virtual host-platform devices (tests/conftest.py forces
``xla_force_host_platform_device_count=8``), MULTICHIP_r*-style.

The determinism claims under test:

- a ``ServingEngine(mesh=...)`` with the arenas kv-head-sharded over
  the mesh's ``model`` axis is TOKEN-EXACT and SCHEDULING-IDENTICAL
  (admissions, dispatch counts, flight-recorder event stories modulo
  wall time) to the single-chip engine on a combined trace — prefix
  hits, chunked prefill, spec-decode verify, int8 KV — because block
  tables and the whole host plan stay replicated;
- the sharded kernel path actually dispatches (route-counter proof:
  ``pallas.decode_attention.route{decision=..., reason="sharded_ok"}``
  advances only for the mesh engine);
- a geometry that cannot split whole kv-heads falls back to the exact
  single-chip engine and says so once (``reason="mesh_geom"``);
- data-parallel replicas (each a shard group) behind the Router carry
  their shard-group identity into route events, ``load_report()`` and
  ``fleet_snapshot()``, and greedy/seed-pinned-sampled outputs are
  exact across the topology change.
"""

import numpy as np
import pytest

import paddle_tpu as paddle

import jax

from paddle_tpu import models
from paddle_tpu.distributed.topology import build_mesh
from paddle_tpu.inference.router import Router
from paddle_tpu.inference.sampling import SamplingParams
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.observability.flightrec import FlightRecorder
from paddle_tpu.observability.metrics import (MetricsRegistry,
                                              get_registry)
from paddle_tpu.ops.pallas import decode_attention as da
from scripted_drafter import ScriptedDrafter


@pytest.fixture(scope="module")
def net2():
    # module-scoped fixtures run BEFORE the autouse _reseed, so seed
    # explicitly
    paddle.seed(2024)
    cfg = models.LlamaConfig(
        vocab_size=96, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=64)
    net = models.LlamaForCausalLM(cfg)
    net.eval()
    return cfg, net


def _mk(net, mesh=None, kv_dtype=None, reg=None, fr=None, drafter=None):
    return ServingEngine(
        net, num_slots=2, prompt_len=8, max_cache_len=32,
        steps_per_call=2, block_len=4, num_blocks=24, chunk_len=4,
        compute_dtype="float32", kv_cache_dtype=kv_dtype,
        registry=reg if reg is not None else MetricsRegistry(),
        flight_recorder=fr, mesh=mesh, drafter=drafter)


def _combined_trace(eng, prompts, spec=2):
    """Prefix hit + chunked prefill + spec verify on one engine: r0
    seeds the radix tree; r2 rides spec-decode (the engine's scripted
    drafter proposes r2's own stream with wrong tokens planted, and
    max_new=8 leaves k_eff room for the verify to dispatch); r3 shares
    r0's first (block-aligned) 4 tokens and is QUEUED behind the 2
    slots, so its admission lands after r0's blocks hit the radix tree
    — a real prefix hit, not a same-step miss."""
    rs = [eng.submit(prompts[0], max_new_tokens=4),
          eng.submit(prompts[1], max_new_tokens=5),
          eng.submit(prompts[2], max_new_tokens=8, spec_decode=spec),
          eng.submit(prompts[3], max_new_tokens=4)]
    eng.run()
    return [r.output.tolist() for r in rs]


def _story(fr):
    """Event sequence modulo wall time (the ONE nondeterministic
    field)."""
    return [(e.kind, e.step, e.request, e.attrs) for e in fr.events()]


def _counts(stats):
    """The deterministic scalars of a stats() dict: recursively keep
    ints/bools (dispatch/admission/token counts), drop wall-clock
    floats and open-ended sub-objects."""
    out = {}
    for k, v in stats.items():
        if isinstance(v, dict):
            out[k] = _counts(v)
        elif isinstance(v, (bool, int)):
            out[k] = v
    return out


@pytest.fixture(scope="module")
def tp_ab(net2):
    """ONE single-chip-vs-tp2 A/B over the int8-KV combined trace,
    shared by every assert below (the module-scoped combined-trace
    pattern — compile once, assert many)."""
    cfg, net = net2
    rng = np.random.default_rng(42)
    base = rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
    tail = rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
    pat = rng.integers(0, cfg.vocab_size, (3,)).astype(np.int32)
    tail2 = rng.integers(0, cfg.vocab_size, (3,)).astype(np.int32)
    prompts = [base,
               np.concatenate([base[:4], tail]),
               np.concatenate([pat, pat, pat[:1]]),
               np.concatenate([base[:4], tail2])]
    # r2's own greedy stream, from the same trace without speculation:
    # what the scripted drafter of both arms proposes from
    plain = _combined_trace(_mk(net, kv_dtype="int8"), prompts, spec=None)

    def drafter():
        return ScriptedDrafter([(prompts[2], plain[2])], cfg.vocab_size)
    route = get_registry().counter("pallas.decode_attention.route",
                                   labels=("decision", "reason"))

    def shard_hits():
        return (route.value(decision="pallas", reason="sharded_ok")
                + route.value(decision="xla", reason="sharded_ok"))

    fr1, fr2 = FlightRecorder(), FlightRecorder()
    r1, r2 = MetricsRegistry(), MetricsRegistry()
    e1 = _mk(net, kv_dtype="int8", reg=r1, fr=fr1, drafter=drafter())
    base_hits = shard_hits()
    out1 = _combined_trace(e1, prompts)
    assert shard_hits() == base_hits        # single-chip: no overlay
    mesh = build_mesh(mp=2, devices=jax.devices()[:2])
    e2 = _mk(net, mesh=mesh, kv_dtype="int8", reg=r2, fr=fr2,
             drafter=drafter())
    out2 = _combined_trace(e2, prompts)
    assert out1 == plain                    # speculation changes no token
    return dict(e1=e1, e2=e2, out1=out1, out2=out2, fr1=fr1, fr2=fr2,
                sharded_hits=shard_hits() - base_hits)


def test_tp2_token_exact(tp_ab):
    assert tp_ab["out1"] == tp_ab["out2"]
    assert all(len(o) > 0 for o in tp_ab["out1"])


def test_tp2_scheduling_identical(tp_ab):
    """Admissions, chunk/dispatch/verify counts, prefix hits — every
    deterministic scalar of stats() matches the single-chip engine
    (each engine has a private registry, so deltas are exact)."""
    c1, c2 = _counts(tp_ab["e1"].stats()), _counts(tp_ab["e2"].stats())
    assert c1 == c2
    assert c1["block_dispatches"] > 0 and c1["prefill_chunks"] >= 3
    assert c1["spec_verify_steps"] > 0      # spec verify really ran
    assert c1["prefix_hit_tokens"] >= 4     # prefix hit really hit


def test_tp2_event_stories_lockstep(tp_ab):
    s1, s2 = _story(tp_ab["fr1"]), _story(tp_ab["fr2"])
    assert s1 == s2 and len(s1) > 0


def test_tp2_route_counter_proof(tp_ab):
    """The tensor-parallel paged path really dispatched: the
    ``sharded_ok`` overlay advanced only while the mesh engine traced
    its paged decode/verify programs (once per compiled program — the
    gate runs at trace time)."""
    assert tp_ab["sharded_hits"] > 0


def test_tp2_arena_sharding_and_identity(tp_ab):
    e1, e2 = tp_ab["e1"], tp_ab["e2"]
    assert e1.shard_group is None and e1._shard is None
    sg = e2.shard_group
    assert sg["sharded"] and sg["n_shards"] == 2
    assert sg["label"] == "tp2@d0" and sg["devices"][:2] == [0, 1]
    assert all(not a.sharding.is_fully_replicated for a in e2._arenas)
    assert e2.load_report()["shard_group"] == sg
    assert e1.load_report()["shard_group"] is None
    # presence/width gauges (private registries -> exact per engine)
    assert e2._m.shard_groups.value() == 1
    assert e2._m.shard_width.value() == 2
    assert e1._m.shard_groups.value() == 0
    assert e1._m.shard_width.value() == 1


def test_mesh_geometry_fallback(net2):
    """hkv=2 over a 3-wide model axis cannot split whole kv-heads:
    the engine must serve single-chip-exact (no shard recipe) and
    count one mesh_geom route decision."""
    _, net = net2
    route = get_registry().counter("pallas.decode_attention.route",
                                   labels=("decision", "reason"))
    before = route.value(decision="xla", reason="mesh_geom")
    mesh = build_mesh(mp=3, devices=jax.devices()[:3])
    eng = _mk(net, mesh=mesh)
    assert eng._shard is None
    assert eng.shard_group["sharded"] is False
    assert eng.shard_group["n_shards"] == 1
    assert eng.shard_group["requested"] == 3
    assert eng.shard_group["label"].startswith("rep@")
    assert route.value(decision="xla", reason="mesh_geom") == before + 1
    # degenerate 1-wide model axis is the same fallback
    eng1 = _mk(net, mesh=build_mesh(mp=1, devices=jax.devices()[:1]))
    assert eng1._shard is None and not eng1.shard_group["sharded"]


def test_one_wide_mesh_places_engine_on_its_device(net2):
    """A one-chip replica built on any device but the first keeps its
    weights and arenas THERE, also after a drain (the donated round
    trip).  (``chip_smoke.multichip_serving`` checks on real chips that
    such replicas serve what the default-device engine serves.)"""
    _, net = net2
    dev = jax.devices()[3]
    eng = _mk(net, mesh=build_mesh(mp=1, devices=[dev]))
    assert eng.shard_group["devices"] == [dev.id]
    req = eng.submit(np.arange(1, 8, dtype=np.int32), max_new_tokens=3)
    eng.run()
    assert req.output.shape == (3,)
    assert all(a.devices() == {dev} for a in eng._arenas)
    assert all(v.devices() == {dev} for v in eng._pb)


def test_mesh_needs_model_axis(net2):
    _, net = net2
    bad = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("data",))
    with pytest.raises(ValueError, match="model"):
        _mk(net, mesh=bad)


def test_sharded_table_guard(net2, monkeypatch):
    """Satellite: a sharded/committed block table reaching
    ``_paged_dispatch`` is a typed error, not silent garbage — tables
    are HOST scheduling state; only arenas shard."""
    import jax.numpy as jnp
    monkeypatch.setattr(da, "pallas_enabled", lambda: True)
    b, hkv, g, d, nb, L = 2, 2, 2, 64, 6, 8
    q = jnp.zeros((b, hkv * g, d), jnp.float32)
    k = jnp.zeros((nb + 1, L, hkv * d), jnp.float32)
    v = jnp.zeros_like(k)
    lens = jnp.array([3, 3], jnp.int32)
    tables = jnp.zeros((b, 4), jnp.int32)
    # replicated table: gate passes, kernel path runs fine
    out = da.decode_attention_paged(q, k, v, tables, lens)
    assert out.shape == (b, hkv * g * d)
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = build_mesh(mp=2, devices=jax.devices()[:2])
    sharded_tbl = jax.device_put(
        tables, NamedSharding(mesh, P("model", None)))
    with pytest.raises(da.ShardedTableError, match="REPLICATED"):
        da.decode_attention_paged(q, k, v, sharded_tbl, lens)
    # the guard is the dispatch's, not the gate's: gate still True
    assert da._guard_replicated_tables([tables]) is None


def test_route_reason_vocab_closed():
    assert "sharded_ok" in da.DECODE_ROUTE_REASONS
    assert "mesh_geom" in da.DECODE_ROUTE_REASONS
    assert len(set(da.DECODE_ROUTE_REASONS)) == len(da.DECODE_ROUTE_REASONS)
    with pytest.raises(ValueError,
                       match="unknown decode-attention route reason"):
        da._count_route("xla", "not_a_reason")
    # the producer's returns stay inside the closed vocabulary
    assert da._shard_route_reason(2, 2) == "sharded_ok"
    assert da._shard_route_reason(2, 3) == "mesh_geom"
    assert da._shard_route_reason(2, 1) == "mesh_geom"


def test_dp_replicas_behind_router(net2):
    """Two tp2 shard groups (disjoint device pairs) as data-parallel
    replicas behind the Router: outputs stay exact vs a single-chip
    engine serving the same prompts (greedy rows trivially; the
    sampled row because an explicit ``SamplingParams(seed=)`` pins
    the stream across topology AND routing), and the shard-group
    identity rides route events + fleet_snapshot."""
    cfg, net = net2
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (6, 8, 5, 7)]
    samp = SamplingParams(temperature=0.7, top_k=8, seed=123)

    def serve(mk_engines, use_router):
        if use_router:
            fr = FlightRecorder()
            rt = Router(mk_engines, flight_recorder=fr,
                        registry=MetricsRegistry())
            hs = [rt.submit(p, max_new_tokens=4,
                            sampling=samp if i == 3 else None)
                  for i, p in enumerate(prompts)]
            rt.run()
            return rt, fr, [h.output.tolist() for h in hs]
        eng = mk_engines[0]
        hs = [eng.submit(p, max_new_tokens=4,
                         sampling=samp if i == 3 else None)
              for i, p in enumerate(prompts)]
        eng.run()
        return eng, None, [h.output.tolist() for h in hs]

    _, _, ref = serve([_mk(net)], use_router=False)
    devs = jax.devices()
    ra = _mk(net, mesh=build_mesh(mp=2, devices=devs[:2]))
    rb = _mk(net, mesh=build_mesh(mp=2, devices=devs[2:4]))
    rt, fr, got = serve([ra, rb], use_router=True)
    assert got == ref
    snap = rt.fleet_snapshot()
    assert snap["shard_groups"] == ["tp2@d0", "tp2@d2"]
    assert [lr["shard_group"]["label"] for lr in snap["load_reports"]] \
        == ["tp2@d0", "tp2@d2"]
    shards = [e.attrs["shard"] for e in fr.events()
              if e.kind == "route"]
    assert len(shards) == len(prompts)
    assert set(shards) <= {"tp2@d0", "tp2@d2"}
    assert len(set(shards)) == 2      # load-primary really spread DP


# ---------------------------------------------------------------------------
# shard-overlay plumbing units (no model build)
# ---------------------------------------------------------------------------

def test_shard_route_reason_geometry():
    # whole kv-heads per shard => sharded_ok; anything else (including
    # the degenerate 1-shard "mesh") is the replicated fallback reason.
    assert da._shard_route_reason(4, 2) == "sharded_ok"
    assert da._shard_route_reason(4, 4) == "sharded_ok"
    assert da._shard_route_reason(8, 2) == "sharded_ok"
    assert da._shard_route_reason(4, 3) == "mesh_geom"
    assert da._shard_route_reason(2, 4) == "mesh_geom"
    assert da._shard_route_reason(4, 1) == "mesh_geom"


def test_shard_dispatch_scope_nests_and_restores():
    assert da._SHARD_N is None
    with da.shard_dispatch_scope(2):
        assert da._SHARD_N == 2
        with da.shard_dispatch_scope(4):
            assert da._SHARD_N == 4
        assert da._SHARD_N == 2
    assert da._SHARD_N is None
    # restored even when the traced body raises
    with pytest.raises(RuntimeError):
        with da.shard_dispatch_scope(2):
            raise RuntimeError("trace failed")
    assert da._SHARD_N is None


def test_count_shard_route_counts_into_process_registry():
    c = get_registry().counter(
        "pallas.decode_attention.route", labels=("decision", "reason"))
    ok0 = c.value(decision="pallas", reason="sharded_ok")
    geom0 = c.value(decision="xla", reason="mesh_geom")
    da.count_shard_route(4, 2, use_pallas=True)
    da.count_shard_route(4, 3, use_pallas=False)
    assert c.value(decision="pallas", reason="sharded_ok") == ok0 + 1
    assert c.value(decision="xla", reason="mesh_geom") == geom0 + 1


def test_single_chip_shard_plumbing_is_inert():
    from paddle_tpu.inference import llm as _llm
    import contextlib as _ctx
    # None shard => no overlay scope, no constraint rewrite, and the
    # guard class is the TypeError subclass _paged_dispatch raises.
    assert isinstance(_llm._shard_scope(None), _ctx.nullcontext().__class__)
    flat = [1, 2, 3]
    out = _llm._constrain_arenas(flat, None)
    assert out == flat and out is not flat
    assert issubclass(da.ShardedTableError, TypeError)
