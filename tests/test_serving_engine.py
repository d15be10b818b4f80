"""Continuous-batching ServingEngine (inference/serving.py): greedy
parity with per-request static generation on a mixed-length trace,
slot-reuse hygiene (no stale-KV leak), admission under a full pool, the
static-batching (gang) baseline mode, and a fast CPU smoke of the
scheduler loop driving the Pallas decode kernel in interpret mode.

Tier-1 budget discipline: the suite is truncation-scored (870s wall),
so the unmarked tests keep XLA compile counts minimal — ONE engine
config and TWO distinct oracle ``max_new_tokens`` values (the
``generate()`` executable cache is keyed on them) cover parity, slot
reuse and full-pool admission in a single trace; the wider scenario
matrix (per-scenario engines, EOS configs, gang mode, the bench path)
is ``slow``-marked and runs on demand / on chip."""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import models
from paddle_tpu.inference.serving import ServingEngine


@pytest.fixture(scope="module")
def netm():
    paddle.seed(2024)
    cfg = models.tiny_llama_config()
    net = models.LlamaForCausalLM(cfg)
    net.eval()
    return cfg, net


P, C = 6, 32      # one (prompt_len, max_cache_len) so oracles share


def _oracle(net, padded_prompt, seq_len, max_new):
    """Per-request static-batch greedy generation — the parity oracle.
    Compiled once per distinct max_new (cache key) on the shared net."""
    ids = paddle.to_tensor(padded_prompt[None, :].astype(np.int32))
    return np.asarray(net.generate(
        ids, seq_lens=np.array([seq_len]), max_new_tokens=max_new,
        max_cache_len=C, compute_dtype="float32")._value)[0]


def _pad(ids):
    padded = np.zeros((P,), np.int32)
    padded[:ids.size] = ids
    return padded


def test_mixed_trace_parity_slot_reuse_admission(netm):
    """The acceptance contract in one trace: 5 mixed-length requests
    through 2 slots — every slot is reused 2-3x (a freed slot's stale
    KV must not leak into its next occupant), the pool is full with a
    backlog (admission-under-full-pool), budgets force both the full
    decode block and the single-step fallback — and every request's
    output is token-for-token identical to per-request static-batch
    greedy generation."""
    cfg, net = netm
    rng = np.random.default_rng(0)
    eng = ServingEngine(net, num_slots=2, prompt_len=P, max_cache_len=C,
                        steps_per_call=3, compute_dtype="float32")
    specs = [(4, 7), (6, 2), (3, 7), (5, 2), (2, 7)]
    reqs = []
    for seq_len, max_new in specs:
        ids = rng.integers(0, cfg.vocab_size, (seq_len,)).astype(np.int32)
        reqs.append((ids, seq_len, max_new,
                     eng.submit(ids, max_new_tokens=max_new)))
    assert eng.stats()["peak_queue"] == len(specs)  # backlog > pool
    done = eng.run()
    assert [r.request_id for r in done] == [r.request_id
                                            for *_, r in reqs]
    stats = eng.stats()
    assert stats["finished"] == len(specs)
    assert stats["prefills"] == len(specs)
    assert 0.0 < stats["mean_slot_occupancy"] <= 1.0
    for ids, seq_len, max_new, req in reqs:
        want = _oracle(net, _pad(ids), seq_len, max_new)
        np.testing.assert_array_equal(req.output, want)
        assert req.finish_time is not None and req.latency >= 0


def test_engine_loop_smoke_pallas_interpret(monkeypatch):
    """Fast tier-1 smoke: the scheduler loop drives the REAL flash-
    decode Pallas kernel (interpret mode on CPU) end to end — geometry
    chosen so the paged gate routes (packed arena, g <= 8,
    block_len % 8 == 0) — admissions, chunked prefill, mixed-fill
    decode blocks over the BLOCK-TABLE kernel, evictions and block
    reuse all run over the kernel path on every PR."""
    from paddle_tpu.observability.metrics import get_registry
    from paddle_tpu.ops.pallas import decode_attention as da
    monkeypatch.setattr(da, "pallas_enabled", lambda: True)
    cfg = models.LlamaConfig(
        vocab_size=128, hidden_size=256, intermediate_size=256,
        num_hidden_layers=1, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=64)
    net = models.LlamaForCausalLM(cfg)
    net.eval()
    assert cfg.head_dim == 64 and da.packed_ok(2, 64)
    q4 = np.zeros((2, 2, 2, 64), np.float32)
    kc = np.zeros((2, 16, 128), np.float32)
    assert da.should_use_pallas(q4, kc)     # the dense gate still routes
    route = get_registry().counter("pallas.decode_attention.route",
                                   labels=("decision", "reason"))
    base_paged = route.value(decision="pallas", reason="paged_ok")
    rng = np.random.default_rng(5)
    eng = ServingEngine(net, num_slots=2, prompt_len=4, max_cache_len=16,
                        steps_per_call=2, block_len=8,
                        compute_dtype="float32")
    reqs = [eng.submit(rng.integers(0, cfg.vocab_size, (n,))
                       .astype(np.int32), max_new_tokens=m)
            for n, m in ((4, 5), (3, 3), (4, 4))]
    done = eng.run()
    assert len(done) == 3
    for r in reqs:
        assert r.output.shape == (r.max_new_tokens,)
        assert (r.output >= 0).all() and (r.output < cfg.vocab_size).all()
    assert 0.0 < eng.stats()["mean_slot_occupancy"] <= 1.0
    # the decode blocks really dispatched the paged kernel variant
    assert route.value(decision="pallas",
                       reason="paged_ok") > base_paged


def test_submit_guards(netm):
    cfg, net = netm
    eng = ServingEngine(net, num_slots=1, prompt_len=4, max_cache_len=8,
                        compute_dtype="float32")
    with pytest.raises(ValueError, match="prompt"):
        eng.submit(np.zeros((5,), np.int32))
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(np.zeros((4,), np.int32), max_new_tokens=0)
    with pytest.raises(ValueError, match="max_cache_len"):
        eng.submit(np.zeros((4,), np.int32), max_new_tokens=100)
    # the capacity error is block-aware: tokens AND blocks reported
    with pytest.raises(ValueError, match=r"blocks"):
        eng.submit(np.zeros((4,), np.int32), max_new_tokens=100)
    with pytest.raises(ValueError, match="seq_len"):
        eng.submit(np.zeros((4,), np.int32), seq_len=9)
    with pytest.raises(ValueError, match="num_slots"):
        ServingEngine(net, num_slots=0, prompt_len=4, max_cache_len=8)
    with pytest.raises(ValueError, match="block_len"):
        ServingEngine(net, num_slots=1, prompt_len=4, max_cache_len=8,
                      block_len=0)
    # kv_cache_dtype: floats and "int8" only — an int4/uint8 arena
    # would silently cast K/V with no scale planes
    with pytest.raises(ValueError, match="kv_cache_dtype.*int4"):
        ServingEngine(net, num_slots=1, prompt_len=4, max_cache_len=8,
                      kv_cache_dtype="int4")
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        ServingEngine(net, num_slots=1, prompt_len=4, max_cache_len=8,
                      kv_cache_dtype="not_a_dtype")
    # a request that fits max_cache_len but not the (shrunk) pool
    small = ServingEngine(net, num_slots=1, prompt_len=4,
                          max_cache_len=8, block_len=2, num_blocks=2,
                          compute_dtype="float32")
    with pytest.raises(ValueError, match="num_blocks"):
        small.submit(np.zeros((4,), np.int32), max_new_tokens=4)
    with pytest.raises(ValueError, match="beam|slot-granular"):
        from paddle_tpu.models.generation import GenerationConfig
        from paddle_tpu.inference.llm import build_slot_prefill
        build_slot_prefill(net, 8, GenerationConfig(num_beams=2))
    with pytest.raises(ValueError, match="beam|chunked"):
        from paddle_tpu.models.generation import GenerationConfig
        from paddle_tpu.inference.llm import build_chunk_prefill
        build_chunk_prefill(net, GenerationConfig(num_beams=2))


def test_cancel_queued_request(netm):
    """cancel() drops a still-queued request (no device work involved:
    nothing here compiles) and refuses in-flight/unknown ids."""
    cfg, net = netm
    eng = ServingEngine(net, num_slots=1, prompt_len=4, max_cache_len=8,
                        compute_dtype="float32")
    a = eng.submit(np.zeros((4,), np.int32), max_new_tokens=2)
    b = eng.submit(np.ones((4,), np.int32), max_new_tokens=2)
    assert eng.cancel(a.request_id) is True
    assert a.state == "cancelled"
    assert eng.cancel(a.request_id) is False        # already gone
    assert eng.cancel(10_000) is False              # unknown
    s = eng.stats()
    assert s["cancelled"] == 1
    assert len(eng._queue) == 1 and eng._queue[0] is b
    # the counter is phase-labeled now (cancel reaches in-flight and
    # swapped requests too); a queued-phase cancel lands there
    assert eng.metrics_registry.get("serving.requests_cancelled") \
        .value(phase="queued") >= 1


def test_block_pool_unit():
    """Host-side BlockPool semantics: alloc/refcount/publish/LRU
    reclaim — no device work."""
    from paddle_tpu.inference.serving import BlockPool
    pool = BlockPool(4, block_len=2)
    assert pool.available() == 4 and pool.trash == 4
    blocks = pool.alloc(3)
    assert sorted(blocks) == [0, 1, 2] and pool.in_use() == 3
    assert pool.alloc(2) is None                  # only 1 left
    pool.register(blocks[0], b"dg0")
    pool.register(blocks[1], b"dg1")
    pool.register(blocks[2], b"dg1")      # duplicate content: first wins
    assert pool.lookup(b"dg1") == blocks[1]
    for blk in blocks:
        pool.unpin(blk)
    # published blocks park in the LRU (still mapped), others free
    assert pool.available() == 4 and pool.cached() == 2
    assert pool.lookup(b"dg0") == blocks[0]
    hit = pool.lookup(b"dg1")
    pool.pin(hit)                                 # prefix hit re-pins
    assert pool.cached() == 1 and pool.in_use() == 1
    # exhausting the free list reclaims the LRU (dg0 unmaps)
    got = pool.alloc(3)
    assert len(got) == 3 and pool.lookup(b"dg0") is None
    assert pool.alloc(1) is None                  # truly empty now
    pool.unpin(hit)
    assert pool.lookup(b"dg1") == hit             # still cached
    with pytest.raises(RuntimeError, match="double free"):
        pool.unpin(hit)


def test_paged_prefix_parity_chunked_prefill(netm):
    """The paged acceptance contract in one trace: 5 requests / 2 slots
    over a 12-block pool (block_len 2 — every request spans multiple
    blocks and the pool is smaller than the trace's total footprint, so
    freed blocks are reused), three requests sharing a 4-token (2 full
    block) prefix, chunk_len 4 (the 6-token prompts prefill in 2
    chunks) — and every output token-for-token identical to per-request
    static greedy generation across block reuse, prefix hits and
    chunked prefill.  Oracle max_new values reuse the module's
    generate() executable cache (tier-1 compile budget)."""
    cfg, net = netm
    rng = np.random.default_rng(6)
    shared = rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
    eng = ServingEngine(net, num_slots=2, prompt_len=P, max_cache_len=C,
                        steps_per_call=3, block_len=2, chunk_len=4,
                        num_blocks=12, compute_dtype="float32")
    specs = [(6, 7, True), (5, 2, False), (6, 7, True), (4, 2, False),
             (5, 7, True)]
    reqs = []
    for seq_len, max_new, share in specs:
        ids = rng.integers(0, cfg.vocab_size, (seq_len,)).astype(np.int32)
        if share:
            ids[:4] = shared
        reqs.append((ids, seq_len, max_new,
                     eng.submit(ids, max_new_tokens=max_new)))
    done = eng.run(max_iters=500)
    assert len(done) == len(specs)
    for ids, seq_len, max_new, req in reqs:
        want = _oracle(net, _pad(ids), seq_len, max_new)
        np.testing.assert_array_equal(req.output, want)
    s = eng.stats()
    # requests 2 and 4 admit after request 0's prefill published the
    # shared blocks: 2 block hits each (the submit-time probe missed —
    # nothing was published yet — so the admission-time re-probe did it)
    assert s["prefix_hits"] == 4
    assert 0.0 < s["prefix_hit_rate"] < 1.0
    # 2 chunks per 6/5-token miss, 1 chunk per 4-token miss, 1 chunk
    # for each sharer's unmatched tail: the hits really skipped compute
    assert s["prefill_chunks"] == 7
    assert s["prefills"] == len(specs)
    assert s["blocks_in_use"] == 0                 # pool fully drained
    assert 0 < s["peak_blocks_in_use"] <= 12
    # post-run: a queued sharer pins cached prefix blocks; cancel()
    # releases the pins (the cancel-of-prefix-pinned contract)
    in_use0 = eng.stats()["blocks_in_use"]
    ids2 = np.concatenate([shared,
                           rng.integers(0, cfg.vocab_size, (2,))
                           .astype(np.int32)])
    late = eng.submit(ids2, max_new_tokens=7)
    assert len(late.matched) == 2                  # submit-time hit
    assert eng.stats()["blocks_in_use"] == in_use0 + 2
    assert eng.cancel(late.request_id) is True
    assert eng.stats()["blocks_in_use"] == in_use0
    assert eng.stats()["cancelled"] == 1


@pytest.mark.parametrize("slots,spc,want", [(6, 2, [3, 3, 2, 1, 1, 1, 1]),
                                             (6, 8, [1] * 12),
                                             (3, 1, [3, 2, 1])])
def test_backlog_of_prompts_is_worked_off_by_its_share_a_step(
        netm, slots, spc, want):
    """A step runs one chunk while no more than ``steps_per_call`` slots
    wait for their prompt, and a ``steps_per_call``-th of the waiting
    slots' chunks beyond that (rounded up, FIFO): with one chunk a step a
    batch of hundreds of slots never fills.  Prompts of two chunks each;
    the tokens are the oracle's whatever the share."""
    cfg, net = netm
    rng = np.random.default_rng(5)
    eng = ServingEngine(net, num_slots=slots, prompt_len=P, max_cache_len=C,
                        steps_per_call=spc, block_len=4, chunk_len=3,
                        compute_dtype="float32", prefix_cache_mode="none")
    prompts = [rng.integers(0, cfg.vocab_size, (5,)).astype(np.int32)
               for _ in range(slots)]
    reqs = [eng.submit(p, max_new_tokens=3) for p in prompts]
    got, before = [], 0
    while len(got) < len(want):
        eng.step()
        now = eng.stats()["prefill_chunks"]
        got.append(now - before)
        before = now
    assert got == want
    eng.run()
    for p, r in zip(prompts, reqs):
        assert list(r.output) == list(_oracle(net, _pad(p), 5, 3))


def test_stats_before_any_finish_returns_nones(netm):
    """stats() on a virgin engine (and mid-flight before any request
    finishes) must not divide by zero: mean latency/TTFT over the empty
    finished set are None, rates are 0.0."""
    cfg, net = netm
    eng = ServingEngine(net, num_slots=1, prompt_len=4, max_cache_len=8,
                        compute_dtype="float32")
    s = eng.stats()
    assert s["mean_latency_s"] is None
    assert s["mean_ttft_s"] is None
    assert s["mean_slot_occupancy"] == 0.0
    assert s["prefix_hit_rate"] == 0.0
    assert s["spec_acceptance_rate"] == 0.0
    assert s["spec_mean_accepted_len"] == 0.0
    assert s["finished"] == 0
    # still None with work queued but nothing finished
    eng.submit(np.zeros((4,), np.int32), max_new_tokens=2,
               arrival_time=1e18)
    s2 = eng.stats()
    assert s2["mean_latency_s"] is None and s2["mean_ttft_s"] is None


def test_submit_failure_after_prefix_probe_unpins(netm, monkeypatch):
    """Regression for the probe-pin leak: a submit() that fails AFTER
    its prefix probe pinned cached blocks must unpin them and drop the
    request — otherwise every failed submit leaks refcounts until the
    pool is exhausted.  Fail repeatedly (more times than the pool has
    blocks), then verify the pool recovered and a real submit+run still
    works."""
    cfg, net = netm
    eng = ServingEngine(net, num_slots=1, prompt_len=4, max_cache_len=8,
                        block_len=2, num_blocks=4,
                        compute_dtype="float32")
    rng = np.random.default_rng(21)
    shared = rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
    first = eng.submit(shared, max_new_tokens=1)   # publishes 2 blocks
    eng.run(max_iters=100)
    assert eng.stats()["prefix_cached_blocks"] == 2
    avail0 = eng._pool.available()

    from paddle_tpu.inference import serving as srv
    real_instant = srv._span_instant

    def exploding_instant(name, **attrs):
        if name == "serving.request.queued":
            raise RuntimeError("injected submit failure")
        return real_instant(name, **attrs)

    monkeypatch.setattr(srv, "_span_instant", exploding_instant)
    submitted0 = eng.metrics_registry.get(
        "serving.requests_submitted").value()
    for _ in range(eng.num_blocks + 2):     # would exhaust if leaking
        with pytest.raises(RuntimeError, match="injected"):
            eng.submit(shared, max_new_tokens=1)
        assert eng._pool.available() == avail0
        assert len(eng._queue) == 0
    # a dropped submit must not advance the submitted counter either
    assert eng.metrics_registry.get(
        "serving.requests_submitted").value() == submitted0
    monkeypatch.setattr(srv, "_span_instant", real_instant)
    req = eng.submit(shared, max_new_tokens=1)
    assert len(req.matched) == 1                   # probe still hits
    done = eng.run(max_iters=100)
    assert [r.request_id for r in done] == [req.request_id]
    assert eng._pool.available() == avail0


@pytest.mark.slow
def test_int8_kv_parity_trace_and_scheduling(netm):
    """The int8-KV acceptance contract on one compact mixed trace: an
    engine with ``kv_cache_dtype="int8"`` must make IDENTICAL
    scheduling decisions to the full-precision engine — admissions,
    prefix hits, block tables, dispatch counts are token-independent
    with eos=None — while its greedy tokens agree above threshold
    (exact equality is not promised: int8 KV noise may flip a near-tie
    argmax, after which streams diverge freely) and its modeled KV
    sweep is a fraction of the float engine's."""
    cfg, net = netm
    rng = np.random.default_rng(7)
    shared = rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
    specs = [(6, 7), (5, 2), (5, 7), (4, 4)]
    prompts = []
    for i, (n, _m) in enumerate(specs):
        ids = rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
        if i in (0, 2):
            ids[:4] = shared     # one full (block_len=4) shared block
        prompts.append(ids)

    from paddle_tpu.observability.metrics import MetricsRegistry

    def build(kvdt):
        # private registries: the two engines run INTERLEAVED, and
        # shared-registry per-engine deltas are only exact for
        # sequential engines (the _ServingInstruments caveat)
        eng = ServingEngine(net, num_slots=2, prompt_len=P,
                            max_cache_len=C, steps_per_call=3,
                            block_len=4, chunk_len=4,
                            compute_dtype="float32",
                            kv_cache_dtype=kvdt,
                            registry=MetricsRegistry())
        reqs = [eng.submit(p, max_new_tokens=m, arrival_time=0.0)
                for p, (_n, m) in zip(prompts, specs)]
        return eng, reqs

    e_f, r_f = build(None)
    e_q, r_q = build("int8")
    assert e_q.kv_cache_dtype == "int8"
    # lockstep: every scheduler iteration must finish the same
    # requests and hold identical block tables in both engines
    for _ in range(200):
        fin_f = [r.request_id for r in e_f.step(now=0.0)]
        fin_q = [r.request_id for r in e_q.step(now=0.0)]
        assert fin_f == fin_q
        np.testing.assert_array_equal(e_f._tables, e_q._tables)
        if all(r.state == "finished" for r in r_f):
            break
    assert all(r.state == "finished" for r in r_q)
    s_f, s_q = e_f.stats(), e_q.stats()
    for key in ("prefills", "prefill_chunks", "decode_steps",
                "block_dispatches", "prefix_hits", "prefix_misses",
                "peak_blocks_in_use", "finished"):
        assert s_f[key] == s_q[key], key
    assert s_f["prefix_hits"] >= 1          # the shared block really hit
    agree = np.concatenate([a.output == b.output
                            for a, b in zip(r_f, r_q)])
    assert agree.mean() >= 0.9
    # the whole point: the quantized arena sweeps a fraction of the
    # bytes (f32 baseline here -> ~3.8x; vs a bf16 cache it is ~1.9x)
    assert s_q["kv_cache_dtype"] == "int8"
    assert s_q["kv_bytes_swept"] * 2 < s_f["kv_bytes_swept"]


def test_int8_blockpool_digest_dtype_separation(netm):
    """Prefix digests are salted with the KV cache dtype: the same
    prompt yields DISJOINT digest chains for bf16 vs int8 engines, so
    a block published under one dtype can never be mapped into a cache
    of the other (their arena bytes differ)."""
    from paddle_tpu.inference.serving import BlockPool, _block_digests
    cfg, net = netm
    ids = np.arange(12, dtype=np.int32)
    d_f = _block_digests(ids, 12, 4, salt=b"ptpu-paged-kv/float32")
    d_q = _block_digests(ids, 12, 4, salt=b"ptpu-paged-kv/int8")
    assert len(d_f) == len(d_q) == 3
    assert not set(d_f) & set(d_q)
    # a pool holding the float engine's published block misses every
    # int8 probe of the same prefix
    pool = BlockPool(4, 4)
    (blk,) = pool.alloc(1)
    pool.register(blk, d_f[0])
    assert pool.lookup(d_f[0]) == blk
    assert all(pool.lookup(dg) is None for dg in d_q)
    # engines derive the salt from their arena dtype
    e_f = ServingEngine(net, num_slots=1, prompt_len=P, max_cache_len=C,
                        compute_dtype="float32")
    e_q = ServingEngine(net, num_slots=1, prompt_len=P, max_cache_len=C,
                        compute_dtype="float32", kv_cache_dtype="int8")
    assert e_f._digest_salt != e_q._digest_salt
    assert b"int8" in e_q._digest_salt


def test_int8_engine_smoke_forced_gate(monkeypatch):
    """The int8 engine end to end with the Pallas gate forced open: the
    v5e cannot DMA scale planes of ``H_kv < 128`` lanes (PR 22), so the
    gate sends the engine's decode dispatches to the dequantizing XLA
    view under the named reason ``int8_scale_lanes`` — never into the
    kernel Mosaic refuses, never under ``pallas_unavailable``.  (The
    dequant-in-kernel variants keep their direct-call parity tests in
    ``test_pallas_kernels.py``.)"""
    from paddle_tpu.observability.metrics import get_registry
    from paddle_tpu.ops.pallas import decode_attention as da
    monkeypatch.setattr(da, "pallas_enabled", lambda: True)
    cfg = models.LlamaConfig(
        vocab_size=128, hidden_size=256, intermediate_size=256,
        num_hidden_layers=1, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=64)
    net = models.LlamaForCausalLM(cfg)
    net.eval()
    route = get_registry().counter("pallas.decode_attention.route",
                                   labels=("decision", "reason"))
    base = route.value(decision="xla", reason="int8_scale_lanes")
    rng = np.random.default_rng(9)
    eng = ServingEngine(net, num_slots=2, prompt_len=4, max_cache_len=16,
                        steps_per_call=2, block_len=8,
                        compute_dtype="float32", kv_cache_dtype="int8")
    reqs = [eng.submit(rng.integers(0, cfg.vocab_size, (n,))
                       .astype(np.int32), max_new_tokens=m)
            for n, m in ((4, 5), (3, 3))]
    done = eng.run()
    assert len(done) == 2
    for r in reqs:
        assert r.output.shape == (r.max_new_tokens,)
        assert (r.output >= 0).all() and (r.output < cfg.vocab_size).all()
    assert route.value(decision="xla",
                       reason="int8_scale_lanes") > base


# ---------------------------------------------------------------------------
# slow: the wider scheduler scenario matrix (per-scenario engine configs
# recompile the serving programs; excluded from the truncation-scored
# tier-1 budget, run on demand and on chip)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_wide_trace_three_slots(netm):
    """7 requests / 3 slots / block 3 — a second occupancy mix over the
    same parity oracle."""
    cfg, net = netm
    rng = np.random.default_rng(1)
    eng = ServingEngine(net, num_slots=3, prompt_len=P, max_cache_len=C,
                        steps_per_call=3, compute_dtype="float32")
    specs = [(4, 7), (6, 2), (3, 9), (5, 5), (6, 8), (2, 3), (4, 1)]
    reqs = []
    for seq_len, max_new in specs:
        ids = rng.integers(0, cfg.vocab_size, (seq_len,)).astype(np.int32)
        reqs.append((ids, seq_len, max_new,
                     eng.submit(ids, max_new_tokens=max_new)))
    assert len(eng.run()) == len(specs)
    for ids, seq_len, max_new, req in reqs:
        np.testing.assert_array_equal(
            req.output, _oracle(net, _pad(ids), seq_len, max_new))


@pytest.mark.slow
def test_slot_reuse_matches_fresh_engine(netm):
    """Adversarial slot-reuse check: with ONE slot the second request
    decodes in the first one's cache row and must equal a fresh-engine
    run of itself alone (no stale-KV leak through the scrub + lens
    masking)."""
    cfg, net = netm
    rng = np.random.default_rng(2)
    ids_a = rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
    ids_b = rng.integers(0, cfg.vocab_size, (3,)).astype(np.int32)
    eng = ServingEngine(net, num_slots=1, prompt_len=P, max_cache_len=C,
                        steps_per_call=2, compute_dtype="float32")
    req_a = eng.submit(ids_a, max_new_tokens=7)
    req_b = eng.submit(ids_b, max_new_tokens=2)  # reuses A's slot
    eng.run()
    fresh = ServingEngine(net, num_slots=1, prompt_len=P,
                          max_cache_len=C, steps_per_call=2,
                          compute_dtype="float32")
    req_b2 = fresh.submit(ids_b, max_new_tokens=2)
    fresh.run()
    np.testing.assert_array_equal(req_b.output, req_b2.output)
    np.testing.assert_array_equal(
        req_a.output, _oracle(net, _pad(ids_a), ids_a.size, 7))
    np.testing.assert_array_equal(
        req_b.output, _oracle(net, _pad(ids_b), ids_b.size, 2))


@pytest.mark.slow
def test_eos_frees_slot_early(netm):
    """A request whose stream hits EOS finishes before its budget, pads
    the remainder (the generate() convention) and frees its slot."""
    cfg, net = netm
    rng = np.random.default_rng(3)
    ids = rng.integers(0, cfg.vocab_size, (P,)).astype(np.int32)
    # pick the 3rd greedily generated token as the EOS id so the engine
    # must cut the request short at step 3
    eos = int(_oracle(net, ids, P, 7)[2])
    eng = ServingEngine(net, num_slots=2, prompt_len=P, max_cache_len=C,
                        steps_per_call=3, eos_token_id=eos,
                        pad_token_id=0, compute_dtype="float32")
    req = eng.submit(ids, max_new_tokens=7)
    eng.run()
    want = np.asarray(net.generate(
        paddle.to_tensor(ids[None, :]), max_new_tokens=7,
        max_cache_len=C, eos_token_id=eos, pad_token_id=0,
        compute_dtype="float32")._value)[0]
    np.testing.assert_array_equal(req.output, want)
    assert req.output.shape == (7,)
    assert (req.output[3:] == 0).all()      # padded past EOS
    assert eng.stats()["finished"] == 1


@pytest.mark.slow
def test_static_batching_mode_gang_schedules(netm):
    """The baseline arm: static_batching only admits into an EMPTY
    pool, so a short request finishing early cannot be backfilled —
    but outputs still match the oracle (scheduling never changes
    per-request math)."""
    cfg, net = netm
    rng = np.random.default_rng(4)
    eng = ServingEngine(net, num_slots=2, prompt_len=P, max_cache_len=C,
                        steps_per_call=1, compute_dtype="float32",
                        static_batching=True)
    reqs = []
    for max_new in (7, 2, 5):
        ids = rng.integers(0, cfg.vocab_size, (P,)).astype(np.int32)
        reqs.append((ids, eng.submit(ids, max_new_tokens=max_new)))
    assert len(eng.run()) == 3
    # gang 1 = requests 0+1 decoding together for max(7,2) steps; the
    # 3rd request only starts after BOTH finish -> occupancy below the
    # continuous engine's on the same trace
    assert eng.stats()["mean_slot_occupancy"] < 1.0
    for ids, req in reqs:
        np.testing.assert_array_equal(
            req.output, _oracle(net, ids, P, req.max_new_tokens))


@pytest.mark.slow
def test_paged_fragmentation_stress(netm):
    """Fragmentation + cancel-mid-run over a tight pool: 8 mixed
    requests (some sharing a prefix) through 3 slots and only 14
    blocks, one queued request cancelled between scheduler iterations.
    Every surviving output must still match the oracle and the pool
    must drain to zero pinned blocks with clean refcounts."""
    cfg, net = netm
    rng = np.random.default_rng(8)
    shared = rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
    eng = ServingEngine(net, num_slots=3, prompt_len=P, max_cache_len=C,
                        steps_per_call=3, block_len=2, chunk_len=4,
                        num_blocks=14, compute_dtype="float32")
    specs = [(6, 7, True), (4, 2, False), (5, 7, True), (6, 2, False),
             (3, 7, False), (6, 7, True), (5, 2, True), (4, 7, False)]
    reqs = []
    for seq_len, max_new, share in specs:
        ids = rng.integers(0, cfg.vocab_size, (seq_len,)).astype(np.int32)
        if share:
            ids[:4] = shared
        reqs.append((ids, seq_len, max_new,
                     eng.submit(ids, max_new_tokens=max_new)))
    victim = reqs[5][3]                      # deep enough to stay queued
    for _ in range(2):
        eng.step()
    assert eng.cancel(victim.request_id) is True
    done = eng.run(max_iters=2000)
    finished_ids = {r.request_id for r in eng._finished}
    assert victim.request_id not in finished_ids
    for ids, seq_len, max_new, req in reqs:
        if req is victim:
            continue
        np.testing.assert_array_equal(
            req.output, _oracle(net, _pad(ids), seq_len, max_new))
    s = eng.stats()
    assert s["finished"] == len(specs) - 1 and s["cancelled"] == 1
    assert s["blocks_in_use"] == 0
    assert all(r == 0 for r in eng._pool._ref)


@pytest.mark.slow
def test_prefix_reclaim_and_admission_valve(netm):
    """Refcount-exhaustion corners on a 4-block pool: (a) a retired
    request's published blocks stay mapped (LRU) and serve a later
    submit-time pin; (b) a queue head that cannot allocate while a
    LATER request's submit-time pin holds a block and NOTHING is
    active triggers the release valve — without it the scheduler would
    spin forever and run() would blow max_iters; (c) the head's
    allocation then reclaims the whole LRU, so the shared prefix
    re-misses at the sharer's admission — and outputs still match the
    oracle throughout.  Pinned to the DIGEST cache mode: part (c)'s
    reclaim-forgets semantics is exactly what the tiered radix mode
    (the default) replaces — its demote-to-host behavior is covered
    by tests/test_prefixcache.py."""
    cfg, net = netm
    rng = np.random.default_rng(9)
    shared = rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
    eng = ServingEngine(net, num_slots=2, prompt_len=P, max_cache_len=8,
                        steps_per_call=2, block_len=2, chunk_len=4,
                        num_blocks=4, compute_dtype="float32",
                        prefix_cache_mode="digest")
    req_a = eng.submit(shared, max_new_tokens=1)     # 2 blocks, publishes 2
    eng.run(max_iters=100)
    assert eng.stats()["prefix_cached_blocks"] == 2  # parked, mapped
    # head X needs all 4 blocks; Y (submitted after) pins a cached one
    req_x = eng.submit(
        rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32),
        max_new_tokens=3)                            # 4 blocks, no match
    req_y = eng.submit(shared, max_new_tokens=1)
    assert len(req_y.matched) == 1                   # (a) submit-time hit
    done = eng.run(max_iters=300)                    # (b) valve or hang
    assert {r.request_id for r in done} == {req_x.request_id,
                                            req_y.request_id}
    s = eng.stats()
    # (c) the valve released Y's pin and X's alloc unmapped the LRU:
    # nobody scored an admission-time hit in this engine's lifetime
    assert s["prefix_hits"] == 0 and s["prefix_misses"] == 4
    assert s["blocks_in_use"] == 0
    for req, n, m in ((req_a, 4, 1), (req_x, 6, 3), (req_y, 4, 1)):
        np.testing.assert_array_equal(
            req.output, _oracle(net, _pad(req.prompt[:n]), n, m))


@pytest.mark.slow
def test_gpt_paged_serving_parity():
    """The GPT chunk/paged path (learned positions, MHA): engine output
    equals per-request greedy generate() with chunked prefill and
    multi-block prompts."""
    paddle.seed(11)
    cfg = models.tiny_gpt_config()
    net = models.GPTForCausalLM(cfg)
    net.eval()
    rng = np.random.default_rng(12)
    eng = ServingEngine(net, num_slots=2, prompt_len=P, max_cache_len=C,
                        steps_per_call=2, block_len=4, chunk_len=4,
                        compute_dtype="float32")
    reqs = []
    for seq_len, max_new in ((6, 5), (4, 3), (5, 5)):
        ids = rng.integers(0, cfg.vocab_size, (seq_len,)).astype(np.int32)
        reqs.append((ids, seq_len, max_new,
                     eng.submit(ids, max_new_tokens=max_new)))
    assert len(eng.run(max_iters=500)) == 3
    for ids, seq_len, max_new, req in reqs:
        want = np.asarray(net.generate(
            paddle.to_tensor(_pad(ids)[None, :]),
            seq_lens=np.array([seq_len]), max_new_tokens=max_new,
            max_cache_len=C, compute_dtype="float32")._value)[0]
        np.testing.assert_array_equal(req.output, want)


@pytest.mark.slow
def test_bench_llm_serving_section():
    """The bench.py llm_serving section end to end on CPU (slow: full
    trace through both arms): emits tokens/s, p50/p99 latency and
    occupancy for continuous AND static arms, plus the shared-prefix
    A/B (prefix cache on/off)."""
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "bench_mod", os.path.join(os.path.dirname(__file__), "..",
                                  "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    out = bench._bench_serving(False)
    for k in ("tokens_per_s", "static_tokens_per_s", "p50_latency_ms",
              "p99_latency_ms", "static_p50_latency_ms",
              "static_p99_latency_ms", "mean_slot_occupancy",
              "vs_static", "prefix"):
        assert k in out, k
    assert out["tokens_per_s"] > 0
    assert 0.0 < out["mean_slot_occupancy"] <= 1.0
    assert out["mean_slot_occupancy"] >= out["static_slot_occupancy"]
    pfx = out["prefix"]
    for k in ("tokens_per_s", "no_cache_tokens_per_s", "vs_no_cache",
              "mean_ttft_ms", "no_cache_mean_ttft_ms",
              "prefix_hit_rate", "peak_blocks_in_use", "prefill_chunks",
              "no_cache_prefill_chunks"):
        assert k in pfx, k
    assert 0.0 < pfx["prefix_hit_rate"] <= 1.0
    # hits skip chunks; the cached arm must compute strictly fewer
    assert pfx["prefill_chunks"] < pfx["no_cache_prefill_chunks"]
    tiered = out["prefix_tiered"]
    for k in ("block_len", "hbm_blocks", "system_len", "turns",
              "conversations", "tiered", "digest", "no_cache",
              "hit_tokens_vs_digest", "ttft_vs_digest"):
        assert k in tiered, k
    for arm in ("tiered", "digest", "no_cache"):
        for k in ("tokens_per_s", "mean_ttft_ms", "hit_tokens",
                  "host_hits", "host_swapin_blocks", "swapin_bytes",
                  "prefill_chunks"):
            assert k in tiered[arm], (arm, k)
    # the acceptance gate: the tiered radix cache beats the PR-3
    # digest cache on the multi-turn trace — strictly more cache
    # tokens served (host-tier retention), strictly fewer recomputed
    # chunks, and real host->HBM swap-in traffic
    assert tiered["tiered"]["hit_tokens"] > tiered["digest"]["hit_tokens"]
    assert tiered["tiered"]["prefill_chunks"] < \
        tiered["digest"]["prefill_chunks"]
    assert tiered["tiered"]["host_swapin_blocks"] > 0
    assert tiered["tiered"]["swapin_bytes"] > 0
    assert tiered["digest"]["host_swapin_blocks"] == 0
    assert tiered["no_cache"]["hit_tokens"] == 0
    # fewer chunks shows up as lower mean TTFT on a quiet box (~0.93x
    # measured solo; the deterministic gates above are the primary
    # result).  The bound is deliberately a STRUCTURAL-regression
    # gate, not a perf gate: swap-program compiles landing inside the
    # timed window measured ~2.4x, while 2-core box contention alone
    # has measured up to ~1.3x on a correct build
    assert tiered["ttft_vs_digest"] < 2.0
    kvq = out["kv_int8"]
    for k in ("baseline_dtype", "tokens_per_s", "baseline_tokens_per_s",
              "vs_baseline", "achieved_GBps", "baseline_achieved_GBps",
              "kv_bytes_swept", "baseline_kv_bytes_swept",
              "token_agreement", "engine_token_agreement",
              "delta_nll_pct", "gate"):
        assert k in kvq, k
    # the whole point: the int8 arm models a fraction of the bytes, and
    # the teacher-forced quality gate holds
    assert kvq["kv_bytes_swept"] * 2 < kvq["baseline_kv_bytes_swept"]
    assert kvq["gate"]["token_agreement_ok"]
    assert kvq["gate"]["nll_ok"]
    wq = out["weight_quant"]
    for k in ("baseline_dtype", "baseline_tokens_per_s",
              "baseline_achieved_GBps", "baseline_weight_bytes_swept",
              "forced_tokens", "int8", "int4", "gate"):
        assert k in wq, k
    for arm in ("int8", "int4"):
        for k in ("tokens_per_s", "achieved_GBps",
                  "weight_bytes_swept", "token_agreement",
                  "decisive_token_agreement", "engine_token_agreement",
                  "delta_nll_pct", "token_agreement_ok", "nll_ok"):
            assert k in wq[arm], (arm, k)
    # deterministic gates: quality per quantized dtype, strictly
    # shrinking modeled weight sweep, scheduling identity, and the
    # forced-enable route proof that both bit widths dispatch Pallas
    assert wq["gate"]["token_agreement_ok"]
    assert wq["gate"]["nll_ok"]
    assert wq["gate"]["bytes_order_ok"]
    # the decisive-margin filter must not hollow out the token gate
    assert wq["decisive_frac"] > 0.5
    assert wq["gate"]["dispatch_parity_ok"]
    assert wq["gate"]["route_ok"]
    assert wq["baseline_weight_bytes_swept"] \
        > wq["int8"]["weight_bytes_swept"] \
        > wq["int4"]["weight_bytes_swept"] > 0
    spec = out["spec"]
    for k in ("k", "tokens_per_s", "no_spec_tokens_per_s", "vs_no_spec",
              "mean_accepted_len", "acceptance_rate", "drafts_per_token",
              "draft_hit_rate", "accepted_length_le",
              "accepted_length_counts"):
        assert k in spec, k
    # the repetitive trace really speculates: drafts verify at a mean
    # accepted length > 1 and the arm beats the non-speculative engine
    assert spec["mean_accepted_len"] > 1.0
    assert spec["vs_no_spec"] > 1.0
    assert 0.0 < spec["acceptance_rate"] <= 1.0
    # the distribution and the verify counter cover the same window
    assert sum(spec["accepted_length_counts"]) == spec["verify_steps"]
    samp = out["sampling"]
    for k in ("temperature", "top_k", "greedy_tokens_per_s",
              "sampled_tokens_per_s", "spec_sampled_tokens_per_s",
              "sampled_vs_greedy", "spec_sampled_vs_sampled",
              "sampled_tokens", "resamples", "mean_accepted_len",
              "greedy_spec_mean_accepted_len", "accepted_len_delta",
              "acceptance_rate"):
        assert k in samp, k
    # the sampled arms really sampled (and spec-sampling really hit
    # the residual-resample branch at least once on this trace)
    assert samp["sampled_tokens"] > 0
    assert samp["resamples"] > 0
    assert samp["sampled_tokens_per_s"] > 0
    assert samp["spec_sampled_tokens_per_s"] > 0
    ov = out["overload"]
    for k in ("p99_ttft_ms", "no_preempt_p99_ttft_ms",
              "ttft_vs_no_preempt", "preemptions", "swap_blocks_out",
              "short_delay_slo_ms", "completion_rate",
              "no_preempt_completion_rate", "slo_timeouts",
              "no_preempt_slo_timeouts", "shed_demo"):
        assert k in ov, k
    # the preempt arm really preempted, and preemption improves BOTH
    # p99 TTFT and completion rate on the bursty trace
    assert ov["preemptions"] >= 1 and ov["swap_blocks_out"] > 0
    assert ov["p99_ttft_ms"] < ov["no_preempt_p99_ttft_ms"]
    assert ov["completion_rate"] > ov["no_preempt_completion_rate"]
    assert ov["no_preempt_slo_timeouts"] > ov["slo_timeouts"]
    assert ov["shed_demo"] == {"rejected": 1, "evicted": 1}
    # PR 9: goodput sub-objects on the spec + overload arms — gated
    # ONLY on deterministic token counts (conservation is exact
    # integer equality; TPOT/SLO wall numbers ride along ungated)
    for arm_g in (spec["goodput"], ov["goodput"]):
        for k in ("useful_tokens", "wasted_tokens",
                  "dispatched_tokens", "wasted_by_reason", "goodput",
                  "gate"):
            assert k in arm_g, k
        assert arm_g["gate"]["conservation_ok"]
        assert arm_g["useful_tokens"] + arm_g["wasted_tokens"] \
            == arm_g["dispatched_tokens"] > 0
        # exact-bytes swap preemption never recomputes (the ledger's
        # structural-zero claim, bench-checked too)
        assert arm_g["wasted_by_reason"]["recompute_preempt"] == 0
    # PR 10: the dispatch-ahead A/B — gated ONLY on deterministic
    # counters (token-exact outputs, equal dispatch/token counts,
    # real pipelining, syncs confined to the documented reasons);
    # tokens/s and the host/overlap second sums ride along ungated
    aa = out["async"]
    for k in ("tokens_per_s", "sync_tokens_per_s", "vs_sync",
              "async_syncs", "async_harvests", "syncs_by_reason",
              "host_ms", "dispatch_ms", "overlap_ms", "sync_host_ms",
              "sync_dispatch_ms", "gate"):
        assert k in aa, k
    assert aa["gate"]["token_exact"]
    assert aa["gate"]["dispatch_counts_equal"]
    assert aa["gate"]["pipelined"]
    assert aa["gate"]["sync_reasons_documented"]
    # PR 14: the depth-S finish-bitmap/fused-window A/B — gated ONLY
    # on deterministic counters (token-exact across all three arms,
    # admission order identical, event stories byte-identical modulo
    # step/lag, eos syncs and dispatches strictly lower at depth S,
    # depth gauge hwm == S); walls ride along ungated
    ad = out["async_depth"]
    for k in ("depth", "eos_token_id", "tokens_per_s",
              "depth1_tokens_per_s", "lockstep_tokens_per_s",
              "eos_syncs", "block_dispatches", "async_harvests",
              "depth_hwm", "host_ms", "dispatch_ms", "overlap_ms",
              "gate"):
        assert k in ad, k
    for g in ("token_exact", "eos_syncs_strictly_lower",
              "dispatches_strictly_lower",
              "admission_order_identical", "event_stories_identical",
              "depth_gauge_reaches_s"):
        assert ad["gate"][g], g
    assert ad["eos_syncs"]["depthS"] < ad["eos_syncs"]["depth1"]
    # the spec arm's waste is dominated by rejected draft positions
    assert spec["goodput"]["wasted_by_reason"]["spec_reject"] > 0
    assert "no_spec_goodput" in spec
    assert "mean_tpot_ms" in spec and "no_spec_mean_tpot_ms" in spec
    # overload SLO attainment (wall-shaped, reported not gated) and
    # the no-preempt arm's goodput comparison key exist
    for k in ("slo_attained", "slo_missed", "no_preempt_slo_attained",
              "no_preempt_slo_missed", "no_preempt_goodput",
              "mean_tpot_ms"):
        assert k in ov, k
    # PR 11: the multi-tenant LoRA arm — deterministic gates only
    # (K=1 merged-weights parity, gather==dispatch route counts, the
    # steady tenant strictly improving under fair-share); tokens/s
    # and p99 TTFT ride along ungated
    lo = out["lora"]
    for k in (1, 4, 8):
        assert lo["adapters"][k]["gate_gather_count"], k
        assert lo["adapters"][k]["tokens_per_s"] > 0
    assert lo["adapters"][1]["gate_k1_token_exact"]
    assert lo["starvation"]["gate_steady_improves"]
    assert lo["starvation"]["gate_reordered"]
    assert "k8_vs_k1" in lo
    # PR 12: the front-door router arm — deterministic gates only
    # (token-exact outputs across arms, prefix hit tokens strictly
    # higher and adapter swap-ins strictly lower under affinity);
    # tokens/s rides along ungated
    ro = out["router"]
    for k in ("replicas", "turns", "conversations", "affinity",
              "round_robin", "hit_tokens_vs_round_robin"):
        assert k in ro, k
    for arm in ("affinity", "round_robin"):
        for k in ("tokens_per_s", "prefix_hit_tokens",
                  "adapter_swap_ins", "routed_by_reason",
                  "prefix_affinity_tokens", "adapter_affinity_hits"):
            assert k in ro[arm], (arm, k)
    assert ro["gate_token_exact"]
    assert ro["gate_prefix_hits_higher"]
    assert ro["gate_swap_ins_lower"]
    # round-robin never consulted affinity; affinity never cycled
    assert ro["round_robin"]["prefix_affinity_tokens"] == 0
    assert ro["affinity"]["routed_by_reason"]["round_robin"] == 0
    # PR 15: the replica-failover arm — deterministic gates only
    # (token-exact recovery, completion 1.0 vs < 1.0, exact migrated-
    # block and retry counts); walls report-only
    fo = out["failover"]
    for k in ("replicas", "n_requests", "reference", "on", "off",
              "affected_requests", "victim_parcel_blocks"):
        assert k in fo, k
    for arm in ("reference", "on", "off"):
        for k in ("completion_rate", "failed", "replica_faults",
                  "failover_requests", "migrated_blocks", "wall_ms"):
            assert k in fo[arm], (arm, k)
    assert fo["gate_on_token_exact"]
    assert fo["gate_on_completes_all"]
    assert fo["gate_off_loses_requests"]
    assert fo["gate_migrated_blocks_exact"]
    assert fo["gate_retries_exact"]
    assert fo["reference"]["replica_faults"] == 0
    # PR 18: the multichip arm — 8-virtual-device child process,
    # deterministic counter gates only (tp token-exact + dispatch
    # parity + sharded-route proof, dp token-exact across the
    # topology change, exact shard-group labels); scaling/occupancy
    # walls report-only
    mcp = out["multichip"]
    assert "error" not in mcp, mcp.get("error")
    assert mcp["devices"] == 8
    assert mcp["gate_tp_token_exact"]
    assert mcp["gate_tp_dispatch_parity"]
    assert mcp["gate_sharded_route"]
    assert mcp["gate_dp_token_exact"]
    assert mcp["gate_shard_groups"]
    assert mcp["dp"]["shard_groups"] == ["tp2@d0", "tp2@d2"]
    for k in ("scaling", "tokens_per_s", "per_replica_occupancy"):
        assert k in mcp["dp"], k
    # PR 20: the disaggregated prefill/decode arm — deterministic
    # counter gates only (token-exact vs the monolithic fleet, exact
    # chunk-final handoff count, parcel-block conservation through
    # the router stage, zero prefill work on the decode replica,
    # rerun-identical counters); TTFT/TPOT walls report-only
    dg = out["disagg"]
    assert "error" not in dg, dg.get("error")
    for k in ("replicas", "n_requests", "max_new", "monolithic",
              "disagg"):
        assert k in dg, k
    for arm in ("monolithic", "disagg"):
        for k in ("roles", "counters", "mean_ttft_steps",
                  "mean_tpot_steps", "wall_ms"):
            assert k in dg[arm], (arm, k)
    assert dg["disagg"]["roles"] == ["prefill", "decode"]
    assert dg["gate_token_exact"]
    assert dg["gate_handoffs_exact"]
    assert dg["gate_parcel_blocks_exact"]
    assert dg["gate_no_prefill_on_decode"]
    assert dg["gate_deterministic"]
    # the monolithic fleet never hands off — roles are pure policy
    assert sum(dg["monolithic"]["counters"]["handoffs"]) == 0
