"""Dispatch-ahead step pipeline (PR 10): sync-vs-async lockstep
parity, forced-sync reason accounting, drain-flush semantics and the
fault-stall attribution satellite.

Tier-1 budget discipline (truncation-scored on the 2-core box): ONE
tiny 1-layer llama model at module scope, steps_per_call=1 (one block
compile shared by both arms), short prompts/budgets.  The parity trace
runs TWICE — ``async_dispatch=True`` vs the ``False`` kill-switch — on
PRIVATE registries and recorders (shared-registry deltas would absorb
the other arm; the memory-bank bench-gate rule), stepping both engines
manually with ``BlockPool.check()`` after every step.

Parity contract (the acceptance anchor): token-for-token equal
outputs (greedy rows also ``generate()``-exact), equal deterministic
scheduling counters, and identical flight-recorder event sequences —
compared stable-sorted by ``step`` with ``wall`` and the
deterministic ``lag`` attr stripped, because a deferred harvest emits
its ``decode_block`` events (stamped with the DISPATCH step) after
the next step's admissions chronologically."""

import importlib.util
import os
from types import SimpleNamespace

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import models
from paddle_tpu import runtime as rt
from paddle_tpu.inference import FaultInjector
from paddle_tpu.inference.sampling import DfaTokenMask, SamplingParams
from paddle_tpu.inference.serving import (ASYNC_SYNC_REASONS,
                                          EngineStalledError,
                                          ServingEngine)
from paddle_tpu.observability import MetricsRegistry, spans
from paddle_tpu.observability.flightrec import FlightRecorder

P, C, BL = 8, 40, 4
TERMINAL = ("finished", "timeout", "shed", "cancelled")


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


def _gen_ref(net, ids, max_new):
    out = net.generate(paddle.to_tensor(ids[None, :]),
                       max_new_tokens=max_new, max_cache_len=C,
                       compute_dtype="float32")
    return np.asarray(out._value)[0]


class _AlwaysDraft:
    def propose(self, context, k):
        return np.repeat(np.asarray(context[-1:], np.int32), k)


def _mask_table(vocab):
    # 2-state DFA cycling tokens 1 -> 2 -> 1 ... (always has a legal
    # continuation, so the masked request runs its full budget)
    table = np.full((2, vocab), -1, np.int32)
    table[0, 1] = 1
    table[1, 2] = 0
    return table


def _drive(net, cfg, async_dispatch):
    """The combined parity trace: greedy + seeded-sampled rows with
    shared-prefix hits and chunked prefill (phase 1, where deferral
    actually engages), then spec decode + a token-masked row + a
    forced preemption/resume (phase 2, the forced-sync modes)."""
    rng = np.random.default_rng(99)
    shared = rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
    fi = FaultInjector()
    reg = MetricsRegistry()
    rec = FlightRecorder()
    eng = ServingEngine(
        net, num_slots=2, prompt_len=P, max_cache_len=C,
        steps_per_call=1, block_len=BL, chunk_len=4, num_blocks=12,
        compute_dtype="float32", registry=reg, flight_recorder=rec,
        fault_injector=fi, drafter=_AlwaysDraft(),
        async_dispatch=async_dispatch)

    def drain(reqs, max_steps=120):
        steps = 0
        while any(r.state not in TERMINAL for r in reqs):
            eng.step(now=0.0)
            eng._pool.check()
            steps += 1
            assert steps < max_steps, "trace did not drain"

    # phase 1: plain greedy (prefix-sharing) + a seeded sampled row —
    # the regime where harvests defer
    ids_a = rng.integers(0, cfg.vocab_size, (7,)).astype(np.int32)
    ids_a[:4] = shared
    ids_b = rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
    ids_c = rng.integers(0, cfg.vocab_size, (7,)).astype(np.int32)
    ids_c[:4] = shared                      # radix hit on A's prefix
    ra = eng.submit(ids_a, max_new_tokens=7, arrival_time=0.0)
    rb = eng.submit(ids_b, max_new_tokens=6, arrival_time=0.0,
                    sampling=SamplingParams(temperature=0.8, top_k=12,
                                            seed=5))
    rc = eng.submit(ids_c, max_new_tokens=5, arrival_time=0.0)
    drain([ra, rb, rc])

    # phase 2: spec decode beside a PLAIN co-rider (the plain row's
    # block dispatches charge syncs{spec}), then a masked row alone
    # (its own block dispatches charge syncs{mask})
    rd = eng.submit(ids_a, max_new_tokens=6, arrival_time=0.0,
                    spec_decode=2)
    rg = eng.submit(ids_b, max_new_tokens=6, arrival_time=0.0)
    drain([rd, rg])
    re_ = eng.submit(ids_b, max_new_tokens=4, arrival_time=0.0,
                     sampling=SamplingParams(
                         temperature=0.0,
                         mask_processor=DfaTokenMask(
                             _mask_table(cfg.vocab_size))))
    drain([re_])

    # phase 3: forced preemption mid-decode, then resume BESIDE a
    # still-deferring co-rider — the swap paths read/write host
    # carries, so the pipeline must sync at both ends (the co-rider
    # is what makes a harvest actually pending at each flush)
    rf = eng.submit(ids_c, max_new_tokens=8, arrival_time=0.0)
    rh = eng.submit(ids_a, max_new_tokens=14, arrival_time=0.0)
    for _ in range(4):                      # both admitted + decoding
        eng.step(now=0.0)
    fi.force_swap(rf.request_id)
    # two injected alloc failures (the direct try AND the after-
    # preemption retry) delay the resume by exactly one step, so it
    # lands while the co-rider's harvest is DEFERRED — the
    # syncs{resume} path (a same-step resume would find the pipeline
    # already flushed by the preempt)
    fi.fail_allocs(2)
    drain([rf, rh])
    return eng, reg, rec, (ra, rb, rc, rd, rg, re_, rf, rh)


@pytest.fixture(scope="module")
def arms(netm):
    cfg, net = netm
    a = _drive(net, cfg, async_dispatch=True)
    s = _drive(net, cfg, async_dispatch=False)
    return a, s


def _norm_events(rec):
    """Stable-sort by step, strip wall and the harvest-lag attr (the
    ONLY deterministic field the pipeline adds)."""
    evs = sorted(rec.events(), key=lambda e: e.step)
    return [(e.step, e.request, e.kind,
             tuple(sorted((k, str(v)) for k, v in e.attrs.items()
                          if k != "lag")))
            for e in evs]


def test_async_lockstep_parity(arms, netm):
    cfg, net = netm
    (ea, rga, reca, qa), (es, rgs, recs, qs) = arms
    # token-exact across the combined trace, arm vs arm
    for a, s in zip(qa, qs):
        np.testing.assert_array_equal(a.output, s.output)
    # greedy rows (incl. the spec row and the resumed row) are also
    # generate()-exact — the engine's standing anchor
    ra, _rb, rc, rd, _rg, _re, rf, _rh = qa
    np.testing.assert_array_equal(
        ra.output, _gen_ref(net, ra.prompt[:ra.seq_len], 7))
    np.testing.assert_array_equal(
        rd.output, _gen_ref(net, rd.prompt[:rd.seq_len], 6))
    np.testing.assert_array_equal(
        rf.output, _gen_ref(net, rf.prompt[:rf.seq_len], 8))
    # deterministic scheduling counters identical
    sa, ss = ea.stats(), es.stats()
    for k in ("decode_steps", "busy_slot_steps", "block_dispatches",
              "prefills", "prefill_chunks", "prefix_hits",
              "prefix_hit_tokens", "preemptions", "preempt_resumes",
              "swap_blocks_out", "swap_blocks_in", "kv_bytes_swept",
              "useful_tokens", "wasted_tokens", "dispatched_tokens",
              "wasted_by_reason", "spec_verify_steps",
              "spec_accepted_tokens", "sampled_tokens",
              "masked_tokens", "finished"):
        assert sa[k] == ss[k], k
    # flight-recorder event sequences identical modulo wall + lag
    assert _norm_events(reca) == _norm_events(recs)
    eng_checks = (ea, es)
    for e in eng_checks:
        e._pool.check()
        assert e._pending is None          # run ended flushed


def _drive_block_arm(net, cfg, async_dispatch):
    """More requests than slots at ``steps_per_call=8``, budgets ending at
    mixed offsets of a block: nearly every block holds a budget finish."""
    reg, rec = MetricsRegistry(), FlightRecorder()
    eng = ServingEngine(
        net, num_slots=3, prompt_len=P, max_cache_len=C, steps_per_call=8,
        block_len=BL, chunk_len=4, compute_dtype="float32", registry=reg,
        flight_recorder=rec, async_dispatch=async_dispatch)
    rng = np.random.default_rng(36)
    reqs = [eng.submit(rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32),
                       max_new_tokens=m, arrival_time=0.0)
            for n, m in [(7, 12), (3, 30), (8, 17), (5, 9), (6, 21), (2, 14),
                         (8, 26), (4, 11), (7, 19)]]
    while any(r.state != "finished" for r in reqs):
        eng.step(now=0.0)
        eng._pool.check()
    return eng, rec, reqs


def test_block_through_budget_finishes_async_lockstep_parity(netm):
    """The plan is shared by both arms: with the block running through
    budget finishes they still agree byte for byte (tokens, counters, the
    flight recorder's sequence modulo the harvest lag), the async arm
    defers the blocks in which no rider ends, and a block in which one
    ends harvests synchronously under ``budget``."""
    cfg, net = netm
    (ea, reca, qa), (es, recs, qs) = (_drive_block_arm(net, cfg, on)
                                      for on in (True, False))
    for a, s in zip(qa, qs):
        assert len(a.output) == a.max_new_tokens
        np.testing.assert_array_equal(a.output, s.output)
    np.testing.assert_array_equal(
        qa[3].output, _gen_ref(net, qa[3].prompt[:qa[3].seq_len], 9))
    sa, ss = ea.stats(), es.stats()
    for k in ("decode_steps", "busy_slot_steps", "block_dispatches",
              "prefills", "prefill_chunks", "kv_bytes_swept",
              "useful_tokens", "wasted_tokens", "dispatched_tokens",
              "wasted_by_reason", "finished"):
        assert sa[k] == ss[k], k
    assert sa["decode_steps"] / sa["block_dispatches"] >= 6
    assert sa["busy_slot_steps"] == sum(r.max_new_tokens - 1 for r in qa)
    assert _norm_events(reca) == _norm_events(recs)
    assert sa["async_syncs_by_reason"]["budget"] > 0
    assert sa["async_harvests"] > 0 and ss["async_harvests"] == 0
    for e in (ea, es):
        assert e._pending is None


def test_async_overlap_and_sync_reasons(arms):
    (ea, rga, reca, _qa), (es, rgs, recs, _qs) = arms
    sa, ss = ea.stats(), es.stats()
    # the async arm really pipelined: deferred harvests completed
    # after the next dispatch was enqueued, and the overlap histogram
    # observed the waits; the kill-switch arm observed nothing
    assert sa["async_dispatch"] is True and ss["async_dispatch"] is False
    assert sa["async_harvests"] > 0
    assert ss["async_harvests"] == 0 and ss["async_syncs"] == 0
    assert rga.get("serving.step.overlap_seconds").summary()["count"] > 0
    assert rgs.get("serving.step.overlap_seconds").summary()["count"] == 0
    # forced syncs happened ONLY for documented reasons — and the
    # trace exercised the big ones
    by_reason = sa["async_syncs_by_reason"]
    assert set(by_reason) == set(ASYNC_SYNC_REASONS)
    fired = {k for k, v in by_reason.items() if v > 0}
    assert fired <= set(ASYNC_SYNC_REASONS)
    for expected in ("budget", "chunk_final", "spec", "mask",
                     "preempt", "resume"):
        assert by_reason[expected] > 0, expected
    assert sum(by_reason.values()) == sa["async_syncs"]
    # the deferred harvests are visible per-request: some async
    # decode_block event carries the deterministic lag attr, no sync
    # event does, and explain() renders it
    lags = [e for e in reca.events()
            if e.kind == "decode_block" and e.attrs.get("lag")]
    assert lags
    assert not [e for e in recs.events()
                if e.kind == "decode_block" and e.attrs.get("lag")]
    assert "harvested dispatch-ahead" in ea.explain(lags[0].request)
    # step-split attribution stayed coherent in both arms
    for rg in (rga, rgs):
        d = rg.get("serving.step.dispatch_seconds").summary()
        h = rg.get("serving.step.host_seconds").summary()
        assert d["count"] == h["count"] > 0
        assert d["sum"] > 0.0 and h["sum"] >= 0.0


def test_timeline_cli_renders_harvest_lag(arms, tmp_path, capsys):
    """tools/explain_request.py --timeline marks deferred harvests."""
    (ea, _rga, reca, qa), _s = arms
    lag_ev = next(e for e in reca.events()
                  if e.kind == "decode_block" and e.attrs.get("lag"))
    path = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "explain_request.py")
    spec = importlib.util.spec_from_file_location("explain_request",
                                                  path)
    cli = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cli)
    record = str(tmp_path / "async_record.json")
    reca.export(record)
    assert cli.main([record, str(lag_ev.request), "--timeline"]) == 0
    out = capsys.readouterr().out
    assert "[harvested +" in out
    # the rendered explanation (non-timeline mode) names the lag too
    assert cli.main([record, str(lag_ev.request)]) == 0
    assert "harvested dispatch-ahead" in capsys.readouterr().out


def test_drain_flushes_inflight_harvest_before_stall_raise(netm):
    """run(wall_timeout_s=) flushes the pending harvest (reason
    'drain') before raising EngineStalledError: every token the
    device already produced reaches its request, and clearing the
    fault drains the SAME engine token-exactly.  Also the stall-
    attribution satellite: injected stalls land in
    serving.fault.stall_seconds, never in step.host_seconds."""
    cfg, net = netm
    rng = np.random.default_rng(3)
    ids_a = rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
    ids_b = rng.integers(0, cfg.vocab_size, (5,)).astype(np.int32)
    fi = FaultInjector()
    reg = MetricsRegistry()
    eng = ServingEngine(
        net, num_slots=1, prompt_len=P, max_cache_len=C,
        steps_per_call=1, block_len=BL, chunk_len=P,
        compute_dtype="float32", registry=reg, fault_injector=fi)
    a = eng.submit(ids_a, max_new_tokens=24)
    b = eng.submit(ids_b, max_new_tokens=3)   # queued behind a (1 slot)
    for _ in range(3):                        # admit + prefill + decode
        eng.step()
    assert eng._pending is not None           # a harvest is in flight
    n_before = len(a.tokens)
    fi.stall_steps(2, 0.05)
    with pytest.raises(EngineStalledError):
        eng.run(wall_timeout_s=0.04)
    # flushed: pending gone, the already-produced tokens landed, the
    # sync was charged to the documented 'drain' reason
    assert eng._pending is None
    assert len(a.tokens) > n_before
    assert reg.get("serving.async.syncs").value(reason="drain") >= 1
    eng._pool.check()
    # stall attribution: the injected sleeps observed their own
    # histogram and were carved OUT of host_seconds
    st = reg.get("serving.fault.stall_seconds").summary()
    assert st["count"] >= 1 and st["sum"] >= 0.05
    host = reg.get("serving.step.host_seconds").summary()
    assert host["sum"] < st["sum"]
    # clearing the fault lets the SAME engine drain token-exactly
    done = {r.request_id: r for r in eng.run()}
    np.testing.assert_array_equal(
        done[a.request_id].output, _gen_ref(net, ids_a, 24))
    np.testing.assert_array_equal(
        done[b.request_id].output, _gen_ref(net, ids_b, 3))
    assert eng.stats()["async_harvests"] > 0
    eng._pool.check()


# -- a step's first tokens land together (PR 39) ------------------------------

LAND_MIX = [(7, 5), (3, 9), (8, 4), (5, 12), (6, 3), (2, 7), (8, 6), (4, 10),
            (7, 2)]                          # (prompt rows, max_new_tokens)
LAND_KEYS = ("decode_steps", "busy_slot_steps", "block_dispatches",
             "prefills", "prefill_chunks", "prefix_hits", "kv_bytes_swept",
             "useful_tokens", "wasted_tokens", "dispatched_tokens",
             "wasted_by_reason", "spec_verify_steps", "spec_accepted_tokens",
             "masked_tokens", "finished")


def _dead_end_table(vocab):
    # one legal first token, then no legal continuation: the grammar is
    # complete at the request's first token
    table = np.full((2, vocab), -1, np.int32)
    table[0, 5] = 1
    return table


def _land_arm(net, vocab, variant, async_dispatch, eos=None):
    """Six slots whose prompts are one chunk each at ``steps_per_call`` 2:
    the first step enqueues three final chunks, later steps two or three.
    Returns what the two arms have to agree on, and the per-step deltas."""
    reg, rec = MetricsRegistry(), FlightRecorder()
    geo = dict(num_slots=6, prompt_len=P, max_cache_len=C, steps_per_call=2,
               block_len=BL, chunk_len=P, compute_dtype="float32")
    mix = list(LAND_MIX)
    if variant == "slot_state":
        # the geometry of tests/test_lfm2_moe.py, whose programs are cached
        geo = dict(num_slots=3, prompt_len=48, max_cache_len=80, block_len=8,
                   num_blocks=40, chunk_len=16, steps_per_call=4,
                   compute_dtype="float32", host_cache_blocks=0)
        mix = [(9, 6), (16, 9), (4, 5), (12, 1), (7, 8), (15, 4)]
    if variant == "budget_one":
        mix[1], mix[4] = (3, 1), (6, 1)
    eng = ServingEngine(
        net, registry=reg, flight_recorder=rec, eos_token_id=eos,
        async_dispatch=async_dispatch,
        drafter=_AlwaysDraft() if variant == "spec" else None,
        role="prefill" if variant == "prefill_role" else "both", **geo)
    rng = np.random.default_rng(39)
    reqs = []
    for k, (n, m) in enumerate(mix):
        kw = {}
        if variant == "mask_dead_end" and k in (1, 2):
            kw["sampling"] = SamplingParams(
                temperature=0.0,
                mask_processor=DfaTokenMask(_dead_end_table(vocab)))
        if variant == "spec" and k == 2:
            kw["spec_decode"] = 2
        reqs.append(eng.submit(
            rng.integers(0, vocab, (n,)).astype(np.int32),
            max_new_tokens=m, arrival_time=0.0, **kw))
    order, handoffs, deltas = [], [], []
    before = eng.stats()
    while any(r.state not in TERMINAL + ("swapped",) for r in reqs):
        order += [r.request_id for r in eng.step(now=0.0)]
        for r in eng.take_handoffs():
            # (the router would carry the parcel to a decode replica)
            handoffs.append(r.request_id)
            eng._host_tier.drop(r.swap.host_key)
        eng._pool.check()
        after = eng.stats()
        deltas.append((
            after["prefills"] - before["prefills"],
            after["first_token_fetches"] - before["first_token_fetches"],
            after["async_syncs_by_reason"]["chunk_final"]
            - before["async_syncs_by_reason"]["chunk_final"]))
        before = after
        assert len(deltas) < 200, "trace did not drain"
    assert not eng._first_owed and eng._pending is None
    by_request = {r.request_id: [] for r in reqs}
    for e in rec.events():
        if e.request in by_request:
            by_request[e.request].append(
                (e.step, e.kind, tuple(sorted(
                    (k, str(v)) for k, v in e.attrs.items() if k != "lag"))))
    return SimpleNamespace(eng=eng, reqs=reqs, order=order, handoffs=handoffs,
                           deltas=deltas, stats=eng.stats(),
                           events=by_request)


@pytest.fixture(scope="module")
def lfm2m():
    model = models.Lfm2MoeForCausalLM(models.tiny_lfm2_config())
    model.eval()
    return model


@pytest.mark.parametrize("variant", [
    "plain", "budget_one", "eos_first", "mask_dead_end", "spec",
    "prefill_role", "slot_state"])
def test_a_steps_first_tokens_land_together_and_as_lockstep_lands_them(
        netm, lfm2m, variant):
    """The dispatch-ahead arm enqueues a step's chunks back to back and
    fetches the first tokens of the prompts that finished among them once;
    the lockstep arm lands each behind its own chunk, through the same
    function.  Tokens, the finished order, the handoffs' order, the counters
    and every request's own events are the same."""
    cfg, net = netm
    vocab = cfg.vocab_size
    if variant == "slot_state":
        net, vocab = lfm2m, 256
    eos = None
    if variant == "eos_first":
        # the first token the fourth prompt is served becomes the EOS
        rng = np.random.default_rng(39)
        fourth = [rng.integers(0, vocab, (n,)).astype(np.int32)
                  for n, _ in LAND_MIX[:4]][3]
        eos = int(_gen_ref(net, fourth, 1)[0])
    a, s = (_land_arm(net, vocab, variant, on, eos=eos) for on in (True, False))
    for ra, rs in zip(a.reqs, s.reqs):
        assert ra.state == rs.state
        np.testing.assert_array_equal(ra.tokens, rs.tokens)
    assert a.order == s.order and a.handoffs == s.handoffs
    for k in LAND_KEYS:
        assert a.stats[k] == s.stats[k], k
    assert a.events == s.events
    # one fetch a step that finished n >= 1 prompts, and prefills is n; the
    # pipeline is flushed for them at most once a step
    assert [f for _, f, _ in a.deltas] == [int(n > 0) for n, _, _ in a.deltas]
    assert max(n for n, _, _ in a.deltas) >= 2
    assert all(c <= 1 for _, _, c in a.deltas)
    assert a.stats["first_token_fetches"] < a.stats["prefills"] == len(a.reqs)
    assert s.stats["first_token_fetches"] == s.stats["prefills"]
    if variant == "budget_one":
        assert [len(r.output) for r in a.reqs][1] == 1
    if variant == "eos_first":
        assert a.reqs[3].tokens[0] == eos and a.reqs[3].remaining == \
            a.reqs[3].max_new_tokens - 1
    if variant == "mask_dead_end":
        assert [a.reqs[k].tokens[0] for k in (1, 2)] == [5, 5]
        assert a.reqs[1].state == "finished" and a.reqs[1].n_emitted == 1
    if variant == "spec":
        assert a.stats["spec_verify_steps"] > 0
    if variant == "prefill_role":
        # handed over in the order the final chunks were enqueued: FIFO
        assert a.handoffs == [r.request_id for r in a.reqs
                              if r.state == "swapped"]
        assert len(a.handoffs) == len(a.reqs)
    if variant == "slot_state":
        assert a.stats["slot_state_bytes"] > 0


def test_a_chunk_that_raises_leaves_the_prompts_before_it_landed(
        netm, monkeypatch):
    """The first token owed when a later chunk's enqueue raises still lands
    in that step, as it did when each landed behind its own chunk: no
    request is left between the line and the decode mix, and the engine
    serves on, token-exact, once the fault is gone."""
    cfg, net = netm
    reg = MetricsRegistry()
    eng = ServingEngine(net, num_slots=6, prompt_len=P, max_cache_len=C,
                        steps_per_call=2, block_len=BL, chunk_len=P,
                        compute_dtype="float32", registry=reg)
    rng = np.random.default_rng(7)
    ids = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
           for n in (5, 8, 3, 6)]
    reqs = [eng.submit(i, max_new_tokens=4, arrival_time=0.0) for i in ids]
    chunk_fn, calls = eng._chunk_fn, []

    def second_raises(flags, lora_on=False):
        calls.append(flags)
        if len(calls) == 2:
            raise RuntimeError("no second chunk")
        return chunk_fn(flags, lora_on)
    monkeypatch.setattr(eng, "_chunk_fn", second_raises)
    with pytest.raises(RuntimeError, match="no second chunk"):
        eng.step(now=0.0)                   # two chunks this step
    assert [r.state for r in reqs] == ["decode"] + 3 * ["prefill"]
    assert len(reqs[0].tokens) == 1 and not eng._first_owed
    assert list(eng._prefilling) == reqs[1:]
    monkeypatch.setattr(eng, "_chunk_fn", chunk_fn)
    while any(r.state != "finished" for r in reqs):
        eng.step(now=0.0)
        eng._pool.check()
    for r, i in zip(reqs, ids):
        np.testing.assert_array_equal(r.output, _gen_ref(net, i, 4))
    # an engine's stats() count its own fetches on a registry it shares
    fetched = eng.stats()["first_token_fetches"]
    assert 2 <= fetched < eng.stats()["prefills"] == 4
    twin = ServingEngine(net, num_slots=6, prompt_len=P, max_cache_len=C,
                         steps_per_call=2, block_len=BL, chunk_len=P,
                         compute_dtype="float32", registry=reg)
    assert twin.stats()["first_token_fetches"] == 0
    assert reg.get("serving.first_token_fetches").value() == fetched


# -- a budget finish admits into its slot before the block is fetched ---------

AHEAD_GEO = {
    "dense": dict(num_slots=3, prompt_len=P, max_cache_len=C, steps_per_call=8,
                  block_len=BL, chunk_len=4, compute_dtype="float32"),
    # the geometry of tests/test_lfm2_moe.py, whose programs are cached
    "slot_state": dict(num_slots=3, prompt_len=48, max_cache_len=80,
                       block_len=8, num_blocks=40, chunk_len=16,
                       steps_per_call=4, compute_dtype="float32",
                       host_cache_blocks=0),
}
AHEAD_MIX = {
    "dense": [(7, 12), (3, 30), (8, 17), (5, 9), (6, 21), (2, 14), (8, 26),
              (4, 11), (7, 19)],
    "slot_state": [(9, 6), (16, 9), (4, 5), (12, 3), (7, 8), (15, 4), (5, 6)],
}
AHEAD_KEYS = ("decode_steps", "busy_slot_steps", "block_dispatches",
              "prefills", "prefill_chunks", "kv_bytes_swept", "useful_tokens",
              "wasted_tokens", "dispatched_tokens", "finished")


def _ahead_arm(net, vocab, variant, mode, eos=None, mix=None):
    """Requests over three slots whose budgets end inside blocks.  ``mode``:
    ``ahead`` the dispatch-ahead engine, ``lockstep`` the kill-switch arm,
    ``after`` the dispatch-ahead engine admitting only after each harvest
    (``_leaving`` answers nothing: the order before admissions went ahead).
    Returns the requests, each block's riders, the counters, the
    ``_admit`` calls made ahead of a harvest, and, for each slot a harvest
    found handed over to a new request, what the slot held before and after
    the harvest."""
    reg, rec = MetricsRegistry(), FlightRecorder()
    eng = ServingEngine(net, registry=reg, flight_recorder=rec,
                        eos_token_id=eos, async_dispatch=mode != "lockstep",
                        **AHEAD_GEO[variant])
    if mode == "after":
        eng._leaving = lambda n, active: {}
    admit, calls = eng._admit, []

    def spy(now, out, leaving=None):
        calls.append(bool(leaving))
        return admit(now, out, leaving)
    eng._admit = spy
    absorb, handed = eng._absorb_block, []

    def held(i):
        r = eng._slots[i]
        return (r, r.state, list(r.blocks), eng._tables[i].tolist(),
                int(eng._tok[i]), int(eng._lens[i]), bool(eng._done[i]))

    def absorb_spy(p, *args):
        new = [(i, r) for i, r in zip(p.active, p.reqs)
               if eng._slots[i] is not r]
        before = [held(i) for i, _ in new]
        absorb(p, *args)
        handed.extend(dict(old=r, before=b, after=held(i))
                      for (i, r), b in zip(new, before))
    eng._absorb_block = absorb_spy
    rng = np.random.default_rng(44)
    reqs = [eng.submit(rng.integers(0, vocab, (n,)).astype(np.int32),
                       max_new_tokens=m, arrival_time=0.0)
            for n, m in (mix or AHEAD_MIX[variant])]
    while any(r.state != "finished" for r in reqs):
        eng.step(now=0.0)
        eng._pool.check()
        assert eng._step_idx < 400, "did not drain"
    assert not eng._first_owed and eng._pending is None
    riders = {}
    for e in rec.events():
        if e.kind == "decode_block":
            riders.setdefault(e.step, set()).add(e.request)
    return SimpleNamespace(eng=eng, reg=reg, reqs=reqs, stats=eng.stats(),
                           riders=riders, ahead_calls=sum(calls),
                           handed=handed)


@pytest.fixture(scope="module")
def ahead_arms(netm, lfm2m):
    cfg, net = netm
    nets = {"dense": (net, cfg.vocab_size), "slot_state": (lfm2m, 256)}
    return {(v, mode): _ahead_arm(*nets[v], v, mode)
            for v in AHEAD_GEO for mode in ("ahead", "lockstep", "after")}


@pytest.mark.parametrize("variant", ["dense", "slot_state"])
def test_admission_ahead_keeps_every_token_and_every_blocks_riders(
        ahead_arms, netm, variant):
    """Admitting into the slots a block leaves by budget before its fetch
    changes no token and no block's riding set: the dispatch-ahead engine,
    the lockstep arm and the dispatch-ahead engine admitting after each
    harvest agree request for request and block for block.  The slot-state
    family reuses a state row whose old rider is still in flight."""
    a, s, f = (ahead_arms[variant, m] for m in ("ahead", "lockstep", "after"))
    for ra, rs, rf in zip(a.reqs, s.reqs, f.reqs):
        np.testing.assert_array_equal(ra.output, rs.output)
        np.testing.assert_array_equal(ra.output, rf.output)
    if variant == "dense":
        cfg, net = netm
        r = a.reqs[3]
        np.testing.assert_array_equal(
            r.output, _gen_ref(net, r.prompt[:r.seq_len], r.max_new_tokens))
    assert a.riders == s.riders == f.riders
    for k in AHEAD_KEYS:
        assert a.stats[k] == s.stats[k] == f.stats[k], k
    assert a.stats["admits_ahead"] >= 2 and a.ahead_calls >= 2
    assert f.ahead_calls == f.stats["admits_ahead"] == 0
    if variant == "slot_state":
        assert a.stats["slot_state_bytes"] > 0


@pytest.mark.parametrize("variant", ["dense", "slot_state"])
def test_the_retiring_riders_release_leaves_the_new_riders_slot_alone(
        ahead_arms, variant):
    """A rider retired by the harvest after its slot was handed over leaves
    the new rider's slot, table row and carries as its admission wrote them:
    the row held the new rider's blocks, done, at length 0, with the token
    carry untouched; the retired request holds no slot and no block."""
    for mode in ("ahead", "lockstep"):
        arm = ahead_arms[variant, mode]
        assert len(arm.handed) == arm.stats["admits_ahead"] or \
            mode == "lockstep"
        assert len(arm.handed) >= 2, mode
        for h in arm.handed:
            assert h["after"] == h["before"]
            _new, state, blocks, row, _tok, lens, done = h["after"]
            assert state == "prefill" and (lens, done) == (0, True)
            assert blocks and row[:len(blocks)] == blocks
            assert set(row[len(blocks):]) <= {arm.eng._pool.trash}
            old = h["old"]
            assert old.state == "finished"
            assert old.slot is None and old.blocks == []
    # the order before: a slot a block leaves is admitted into next step
    assert not ahead_arms[variant, "after"].handed


def test_a_budget_finish_enqueues_the_new_prompt_before_the_fetch(netm):
    """In a step whose block ends a rider by its budget with a request
    queued, the queued request is admitted and its first chunk enqueued
    between the block's dispatch and the fetch of its outputs, and
    ``serving.admits_ahead`` counts each such admission."""
    cfg, net = netm
    spans.start_recording()
    try:
        arm = _ahead_arm(net, cfg.vocab_size, "dense", "ahead")
        got = spans.recorded()
    finally:
        rt.HostTracer.disable()
        rt.HostTracer.clear()
    ahead = [(t0, t1) for n, t0, t1, _, a in got
             if n == "serving.admit" and "leaving" in a]
    assert len(ahead) == arm.ahead_calls >= 2
    for a0, a1 in ahead:
        blk = max(t1 for n, _, t1, _, _ in got
                  if n == "serving.decode_block" and t1 <= a0)
        fetch = min(t0 for n, t0, _, _, _ in got
                    if n == "serving.harvest.wait" and t0 >= a1)
        assert [1 for n, t0, t1, _, _ in got
                if n == "serving.prefill.dispatch" and a1 <= t0
                and t1 <= fetch]
        assert not [1 for n, t0, _, _, _ in got
                    if n == "serving.harvest.wait" and blk <= t0 < a1]
    assert arm.stats["admits_ahead"] == len(arm.handed) == \
        arm.reg.get("serving.admits_ahead").value()


@pytest.mark.parametrize("case", ["eos", "nothing_queued", "lockstep"])
def test_no_admission_ahead_where_the_block_cannot_know_its_finishes(
        netm, case):
    """Blocks that sync for EOS admit after their harvest, an engine with
    nothing queued has nothing to admit, and the lockstep arm, whose block is
    not in flight, counts nothing in ``serving.admits_ahead``."""
    cfg, net = netm
    mix = AHEAD_MIX["dense"][:3] if case == "nothing_queued" else None
    arm = _ahead_arm(net, cfg.vocab_size, "dense",
                     "lockstep" if case == "lockstep" else "ahead",
                     eos=cfg.vocab_size - 1 if case == "eos" else None,
                     mix=mix)
    assert arm.stats["admits_ahead"] == 0
    assert arm.reg.get("serving.admits_ahead").value() == 0
    if case == "lockstep":
        assert arm.ahead_calls >= 2       # the same order, uncounted
    else:
        assert arm.ahead_calls == 0
    if case == "eos":
        assert arm.stats["async_syncs_by_reason"]["eos"] > 0
