"""Tiered radix-tree prefix cache (inference/prefixcache.py +
serving.py wiring): token-level longest-prefix match, HBM -> host-RAM
demotion with exact-bytes promotion on hit, cache-aware admission
ordering, fault-injected degradation (swap-in failure / forced tier
eviction) and the extended BlockPool.check() invariants.

Tier-1 budget discipline (truncation-scored 870s wall on a 2-core
box): the radix-tree and host-tier units are model-free with zero XLA
dispatches; the compile-bearing unmarked tests are ONE multi-turn
radix-vs-none trace (tiny model, 1 slot, <= 4-chunk prompts, 2-token
budgets), one small admission-order engine and one fault-degradation
engine.  The int8 twin and the fragmentation stress are
``slow``-marked."""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import models
from paddle_tpu.inference.faultinject import FaultInjector
from paddle_tpu.inference.prefixcache import HostTier, RadixPrefixCache
from paddle_tpu.inference.serving import BlockPool, ServingEngine
from paddle_tpu.observability.metrics import MetricsRegistry


@pytest.fixture(scope="module")
def netm():
    paddle.seed(2024)
    cfg = models.tiny_llama_config()
    net = models.LlamaForCausalLM(cfg)
    net.eval()
    return cfg, net


P, C = 16, 24     # one (prompt_len, max_cache_len) so oracles share


def _oracle(net, ids, max_new):
    padded = np.zeros((P,), np.int32)
    padded[:ids.size] = ids
    out = paddle.to_tensor(padded[None, :].astype(np.int32))
    return np.asarray(net.generate(
        out, seq_lens=np.array([ids.size]), max_new_tokens=max_new,
        max_cache_len=C, compute_dtype="float32")._value)[0]


# -- model-free units ------------------------------------------------

def _fake_rows(block):
    """Stand-in for the engine's arena gather: one tiny stack per
    'arena', content keyed by the block id so promotions are
    distinguishable."""
    return [np.full((1, 2, 2), block, np.float32)]


def test_host_tier_unit():
    """HostTier semantics: reason accounting, cache capacity with
    LRU eviction + evict_cb, pinned entries survive eviction, preempt
    parcels ignore the capacity bound, tolerant unpin."""
    evicted = []
    tier = HostTier(cache_capacity_blocks=2, evict_cb=evicted.append)
    k1 = tier.put(_fake_rows(1), 1, "cache")
    k2 = tier.put(_fake_rows(2), 1, "cache")
    assert tier.blocks("cache") == 2 and tier.blocks("preempt") == 0
    # preempt puts always fit, and never count against the cache cap
    kp = tier.put([np.zeros((3, 2, 2), np.float32)], 3, "preempt")
    assert tier.blocks("preempt") == 3 and tier.blocks() == 5
    # a third cache put evicts the LRU cache entry (k1), not preempt
    k3 = tier.put(_fake_rows(3), 1, "cache")
    assert evicted == [k1] and tier.entry(k1) is None
    assert tier.blocks("cache") == 2
    # pinned entries are not evictable: k2 pinned, k3 is the victim
    tier.pin(k2)
    k4 = tier.put(_fake_rows(4), 1, "cache")
    assert evicted == [k1, k3]
    # pinned-full refuses instead of evicting a pin
    tier.pin(k4)
    assert tier.put(_fake_rows(5), 1, "cache") is None
    assert not tier.would_accept(1)
    tier.unpin(k4)
    assert tier.would_accept(1)
    # touch moves k2 ahead of k4 in LRU age
    tier.unpin(k2)
    tier.touch(k2)
    tier.put(_fake_rows(6), 1, "cache")
    assert tier.entry(k4) is None and tier.entry(k2) is not None
    # unpin of a consumed key is a tolerated no-op
    tier.drop(k2)
    tier.unpin(k2)
    assert tier.audit() == []
    with pytest.raises(ValueError, match="reason"):
        tier.put(_fake_rows(7), 1, "wat")
    # a parcel wider than the whole budget is refused outright
    assert tier.put([np.zeros((9, 2, 2))], 9, "cache") is None


def test_radix_tree_unit():
    """The tree itself: insert/split/longest-prefix match at token
    granularity, block spans with holes, demote -> host location,
    promote -> back to HBM, prune, and the audit invariants (clean
    tree passes, corrupted tree raises through BlockPool.check)."""
    L = 2
    pool = BlockPool(num_blocks=8, block_len=L)
    tier = HostTier(cache_capacity_blocks=8)
    tree = RadixPrefixCache(L, pool, tier)
    tier.evict_cb = tree.drop_host
    pool.audit_hooks.append(lambda: tree.audit(pool))

    ids_a = np.array([5, 6, 7, 8, 9, 10], np.int32)   # 3 blocks
    blocks_a = pool.alloc(3)
    tree.insert(ids_a, blocks_a, 3)
    assert pool.check()
    # exact match, token-granular
    m, span = tree.match(ids_a)
    assert m == 6 and [b for _, b in span] == blocks_a
    assert all(kind == "hbm" for kind, _ in span)
    # partial match ends mid-block: 3 tokens matched, 1 block mapped
    m, span = tree.match(np.array([5, 6, 7, 99], np.int32))
    assert m == 3 and len(span) == 1 and span[0] == ("hbm", blocks_a[0])
    # divergent branch splits the node: shares 2 tokens (1 block)
    ids_b = np.array([5, 6, 42, 43], np.int32)
    blocks_b = pool.alloc(2)
    tree.insert(ids_b, blocks_b, 2)
    assert pool.check()
    m, span = tree.match(ids_b)
    # position 0 was registered first by A: first writer wins
    assert m == 4 and span == [("hbm", blocks_a[0]), ("hbm", blocks_b[1])]
    m, span = tree.match(ids_a)
    assert m == 6 and [b for _, b in span] == blocks_a

    # release A's pins -> its blocks park in the tree LRU; reclaim via
    # alloc demotes them to the host tier in LRU order.  The promote
    # destination is allocated FIRST, while the free list still has
    # room, so the promotion below does not itself trigger reclaim.
    (fresh,) = pool.alloc(1)
    for b in blocks_a:
        pool.unpin(b)
    assert pool.cached() == 3 and pool.available() == 2 + 3
    def _demote_all(blks):        # reclaim_cb receives the batch
        for b in blks:
            tree.demote(b, _fake_rows(b))
    pool.reclaim_cb = _demote_all
    grabbed = pool.alloc(3)               # 2 free + 1 reclaimed
    assert pool.check()
    m, span = tree.match(ids_a)
    assert m == 6 and len(span) == 3
    kinds = [kind for kind, _ in span]
    assert kinds.count("host") == 1
    # the LRU demoted the OLDEST unpinned block: position 0
    assert span[0][0] == "host"
    # promotion swaps the host location back to a fresh HBM block
    key = span[0][1]
    tree.promote(key, fresh)
    assert pool.check()
    m, span = tree.match(ids_a)
    assert all(kind == "hbm" for kind, _ in span)
    assert tier.blocks("cache") == 0

    # a dropped host parcel leaves a HOLE: the span stops there but
    # deeper blocks stay registered and the token match is unchanged
    for b in [fresh] + grabbed:
        pool.unpin(b)
    pool.alloc(5)                          # 3 freed + 2 more demotions
    m, span = tree.match(ids_a)
    n_host = sum(kind == "host" for kind, _ in span)
    assert n_host >= 1
    first_host = next(ref for kind, ref in span if kind == "host")
    tree.drop_host(first_host)
    tier.drop(first_host)
    m2, span2 = tree.match(ids_a)
    assert m2 == 6 and len(span2) < len(span)
    assert pool.check()

    # corruption is caught: a tree-held block forced onto the free
    # list trips the pool-side invariant
    if tree._hbm:
        bid = next(iter(tree._hbm))
        pool._free.append(bid)
        with pytest.raises(RuntimeError, match="tree-referenced"):
            pool.check()
        pool._free.pop()
        assert pool.check()
    # and a dangling host location trips the tree-side audit
    tree._host[9999] = (tree.root, 0)
    with pytest.raises(RuntimeError, match="radix"):
        pool.check()
    del tree._host[9999]
    assert pool.check()


# -- engine traces ---------------------------------------------------

def _multiturn_trace(net, cfg, mode, kvdt=None, num_blocks=8):
    """Two conversations x three turns over a 1-slot engine with a
    deliberately small HBM pool: every turn's prompt extends the
    conversation history over a 4-token shared system prompt, and the
    pool is small enough that turn N's blocks are reclaimed while the
    other conversation runs — the tiered radix cache demotes them to
    host RAM and swaps them back.
    Returns (engine, [(prompt_ids, request), ...])."""
    rng = np.random.default_rng(3)
    sys_ids = rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
    # private registry per engine: the arms are COMPARED, and stats()
    # deltas on the shared process registry would absorb the other
    # arm's increments once both have run (the _ServingInstruments
    # sharing caveat)
    eng = ServingEngine(net, num_slots=1, prompt_len=P, max_cache_len=C,
                        steps_per_call=1, block_len=2, chunk_len=4,
                        num_blocks=num_blocks, prefix_cache_mode=mode,
                        compute_dtype="float32", kv_cache_dtype=kvdt,
                        registry=MetricsRegistry())
    hist = [list(sys_ids), list(sys_ids)]
    served = []
    for _turn in range(3):
        reqs = []
        for ci in range(2):
            user = rng.integers(0, cfg.vocab_size, (2,)).astype(np.int32)
            hist[ci].extend(int(x) for x in user)
            ids = np.asarray(hist[ci], np.int32)
            reqs.append((ci, ids, eng.submit(ids, max_new_tokens=2)))
        while (eng._queue or eng._swapped
               or any(s is not None for s in eng._slots)):
            eng.step()
            eng._pool.check()
        for ci, ids, r in reqs:
            assert r.state == "finished"
            hist[ci].extend(int(x) for x in r.output)
            served.append((ids, r))
    return eng, served


def test_tiered_multiturn_parity_and_hit_tokens(netm):
    """The acceptance trace: the SAME multi-turn conversation trace
    through a tiered-radix engine and an engine without the prefix
    cache.  Every output is token-for-token generate()-exact in BOTH
    arms (so the histories, and therefore the traces, are identical),
    the pool audits clean after every step, the radix arm serves hits
    from the host tier by exact-bytes swap-in, and it serves cache
    tokens where the other arm serves none."""
    cfg, net = netm
    eng_r, served_r = _multiturn_trace(net, cfg, "radix")
    eng_d, served_d = _multiturn_trace(net, cfg, "none")
    for (ids_r, rr), (ids_d, rd) in zip(served_r, served_d):
        np.testing.assert_array_equal(ids_r, ids_d)   # same trace
        np.testing.assert_array_equal(rr.output, rd.output)
        np.testing.assert_array_equal(rr.output,
                                      _oracle(net, ids_r, 2))
    s_r, s_d = eng_r.stats(), eng_d.stats()
    # the host tier really served hits
    assert s_r["prefix_host_hits"] >= 1
    assert s_r["host_swapin_blocks"] >= 1
    assert s_r["swap_blocks_in"] >= s_r["host_swapin_blocks"]
    assert s_r["prefix_hit_tokens"] > s_d["prefix_hit_tokens"] == 0
    # fewer recomputed chunks is the TTFT mechanism, trace-identical
    # so directly comparable
    assert s_r["prefill_chunks"] < s_d["prefill_chunks"]
    # both engines drained clean
    assert eng_r._pool.in_use() == 0 and eng_d._pool.in_use() == 0
    assert s_r["swap_host_blocks"] == 0        # no preemptions here
    eng_r._pool.check()
    eng_d._pool.check()


def test_cache_aware_admission_order(netm):
    """Within a scheduling class, admission prefers queued requests
    whose matched prefix is resident (HBM first), FIFO among equal
    residency — and priority still dominates residency.  Default
    all-cold traces stay byte-identical FIFO."""
    cfg, net = netm
    rng = np.random.default_rng(7)
    shared = rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
    eng = ServingEngine(net, num_slots=4, prompt_len=P, max_cache_len=C,
                        steps_per_call=1, block_len=2, chunk_len=4,
                        compute_dtype="float32")
    # seed the tree: publish the shared prefix's 2 blocks
    eng.submit(shared, max_new_tokens=1)
    eng.run(max_iters=100)
    cold = [rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
            for _ in range(3)]
    sharer_ids = np.concatenate(
        [shared, rng.integers(0, cfg.vocab_size, (2,)).astype(np.int32)])
    c0 = eng.submit(cold[0], max_new_tokens=1)
    c1 = eng.submit(cold[1], max_new_tokens=1)
    sh = eng.submit(sharer_ids, max_new_tokens=1)
    eng._admit(eng._clock(), [])        # host-only: map queue -> slots
    got = [r.request_id for r in eng._prefilling]
    # resident sharer admits ahead of earlier-submitted cold requests;
    # colds keep FIFO between themselves
    assert got == [sh.request_id, c0.request_id, c1.request_id], got
    for r in (c0, c1, sh):
        eng.cancel(r.request_id)
    eng._prefilling.clear()
    for i in range(eng.num_slots):
        eng._slots[i] = None
        eng._done[i] = True
    eng._pool.check()

    # priority dominates residency: a cold priority-1 arrival beats
    # the resident priority-0 sharer
    hi = eng.submit(cold[2], max_new_tokens=1, priority=1)
    sh2 = eng.submit(sharer_ids, max_new_tokens=1, priority=0)
    eng._admit(eng._clock(), [])
    got2 = [r.request_id for r in eng._prefilling]
    assert got2 == [hi.request_id, sh2.request_id], got2
    for r in (hi, sh2):
        eng.cancel(r.request_id)
    eng._prefilling.clear()
    for i in range(eng.num_slots):
        eng._slots[i] = None
        eng._done[i] = True
    eng._pool.check()

    # all-cold default trace: byte-identical FIFO (the strict
    # tie-break leaves order alone when nothing is resident)
    eng2 = ServingEngine(net, num_slots=3, prompt_len=P,
                         max_cache_len=C, block_len=2,
                         compute_dtype="float32")
    rs = [eng2.submit(ids, max_new_tokens=1) for ids in cold]
    eng2._admit(eng2._clock(), [])
    assert [r.request_id for r in eng2._prefilling] == \
        [r.request_id for r in rs]


def test_swapin_fault_and_tier_evict_degrade(netm):
    """Injected host-tier failures degrade to recompute, never wedge:
    (1) fail_swapins drops the host parcels and the sharer recomputes
    its tail token-exactly (no host hit scored, no leak); (2) clearing
    the fault and re-demoting restores host hits; (3) force_tier_evicts
    punches holes that recompute refills — pool audits clean after
    every phase."""
    cfg, net = netm
    rng = np.random.default_rng(11)
    shared = rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
    big = rng.integers(0, cfg.vocab_size, (10,)).astype(np.int32)
    fi = FaultInjector()
    eng = ServingEngine(net, num_slots=1, prompt_len=P, max_cache_len=C,
                        steps_per_call=1, block_len=2, chunk_len=4,
                        num_blocks=7, compute_dtype="float32",
                        fault_injector=fi)

    def drain():
        while (eng._queue or eng._swapped
               or any(s is not None for s in eng._slots)):
            eng.step()
            eng._pool.check()

    eng.submit(shared, max_new_tokens=2)
    drain()
    eng.submit(big, max_new_tokens=2)     # evicts the shared blocks
    drain()
    assert eng.stats()["host_cache_blocks"] > 0
    # (1) swap-in failure: degrade to recompute, token-exact
    fi.fail_swapins(None)
    r1 = eng.submit(shared, max_new_tokens=2)
    drain()
    np.testing.assert_array_equal(r1.output, _oracle(net, shared, 2))
    s = eng.stats()
    assert s["prefix_host_hits"] == 0 and s["host_swapin_blocks"] == 0
    assert ("swapin_fail", None) in fi.events
    # the failed parcels were dropped, not leaked
    assert eng.stats()["host_cache_blocks"] < 7
    # (2) clear + re-demote: the tier serves again
    fi.clear_swapin_failures()
    eng.submit(big, max_new_tokens=2)
    drain()
    r2 = eng.submit(shared, max_new_tokens=2)
    drain()
    np.testing.assert_array_equal(r2.output, _oracle(net, shared, 2))
    assert eng.stats()["prefix_host_hits"] >= 1
    # (3) forced tier evictions: holes open, recompute refills
    eng.submit(big, max_new_tokens=2)
    drain()
    assert eng.stats()["host_cache_blocks"] > 0
    fi.force_tier_evicts(16)
    eng.step()
    eng._pool.check()
    assert eng.stats()["host_cache_blocks"] == 0
    assert ("tier_evict", None) in fi.events
    r3 = eng.submit(shared, max_new_tokens=2)
    drain()
    np.testing.assert_array_equal(r3.output, _oracle(net, shared, 2))
    assert eng._pool.in_use() == 0
    eng._pool.check()


def test_promotion_scatter_raise_releases_pins(netm, monkeypatch):
    """PR-15 satellite (HostTier pin accounting on a failed swap-in):
    a scatter that raises MID-PROMOTION must not leak the entry pin
    or strand the parcel unreachable — the hardened
    ``_map_radix_span`` rollback releases the request's probe pins
    (pool blocks AND tier parcels) symmetrically, so a caller that
    never retries leaves nothing pinned and tier eviction never
    wedges.  Asserted via ``audit()``/``check()`` after the raise and
    again after an injected ``fail_swapins`` storm over two sharers
    of the same host span."""
    cfg, net = netm
    rng = np.random.default_rng(17)
    shared = rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
    big = rng.integers(0, cfg.vocab_size, (10,)).astype(np.int32)
    fi = FaultInjector()
    eng = ServingEngine(net, num_slots=1, prompt_len=P, max_cache_len=C,
                        steps_per_call=1, block_len=2, chunk_len=4,
                        num_blocks=7, compute_dtype="float32",
                        fault_injector=fi)

    def drain():
        while (eng._queue or eng._swapped
               or any(s is not None for s in eng._slots)):
            eng.step()
            eng._pool.check()

    eng.submit(shared, max_new_tokens=2)
    drain()
    eng.submit(big, max_new_tokens=2)     # demotes the shared span
    drain()
    assert eng.stats()["host_cache_blocks"] > 0

    # two queued sharers pin the host span (pins > 1 per parcel)
    a = eng.submit(shared, max_new_tokens=2)
    b = eng.submit(shared, max_new_tokens=2)
    assert a.host_pins and b.host_pins
    tier = eng._host_tier
    assert all(tier.entry(k).pins == 2 for k in a.host_pins)

    # inject a raising scatter at the promotion site
    from paddle_tpu.inference import serving as srv
    real_span = srv._span

    def exploding(name, **attrs):
        if name == "serving.cache_swap_in":
            raise RuntimeError("injected scatter failure")
        return real_span(name, **attrs)

    monkeypatch.setattr(srv, "_span", exploding)
    with pytest.raises(RuntimeError, match="injected scatter"):
        eng.step()
    # the hardened rollback: the admitting request holds NOTHING —
    # its probe pins released (parcels back to the sibling's single
    # pin, un-evictability cannot leak), span metadata cleared, and
    # the parcels stay reachable in the tree (no strand)
    assert a.host_pins == [] and a.matched == [] and a.rspan == []
    assert all(tier.entry(k).pins == 1 for k in b.host_pins)
    assert set(tier.keys("cache")) == set(eng._radix._host)
    eng._pool.check()
    monkeypatch.setattr(srv, "_span", real_span)

    # the retry re-probes from scratch and admits cleanly
    drain()
    np.testing.assert_array_equal(a.output, _oracle(net, shared, 2))
    np.testing.assert_array_equal(b.output, _oracle(net, shared, 2))
    assert all(tier.entry(k) is None or tier.entry(k).pins == 0
               for k in set(a.host_pins) | set(b.host_pins))
    eng._pool.check()

    # the fail_swapins storm over fresh sharers: every admission
    # degrades (parcels drop), audits stay clean at every step, no
    # pin survives the drain
    eng.submit(big, max_new_tokens=2)     # re-demote the shared span
    drain()
    fi.fail_swapins(None)
    c = eng.submit(shared, max_new_tokens=2)
    d = eng.submit(shared, max_new_tokens=2)
    drain()
    fi.clear_swapin_failures()
    np.testing.assert_array_equal(c.output, _oracle(net, shared, 2))
    np.testing.assert_array_equal(d.output, _oracle(net, shared, 2))
    assert all(e.pins == 0 for e in eng._host_tier._entries.values())
    assert eng._pool.in_use() == 0
    eng._pool.check()


def test_engine_guards_and_mode_validation(netm):
    """Constructor guards: bad prefix_cache_mode (the deleted
    ``"digest"`` like any other) / negative host_cache_blocks raise;
    ``"none"`` builds no index; host_cache_blocks=0 disables demotion
    (reclaim forgets) without disabling the radix index."""
    cfg, net = netm
    for bad in ("lru", "digest"):
        with pytest.raises(ValueError, match="prefix_cache_mode"):
            ServingEngine(net, num_slots=1, prompt_len=4, max_cache_len=8,
                          prefix_cache_mode=bad)
    with pytest.raises(TypeError, match="enable_prefix_cache"):
        ServingEngine(net, num_slots=1, prompt_len=4, max_cache_len=8,
                      enable_prefix_cache=False)
    with pytest.raises(ValueError, match="host_cache_blocks"):
        ServingEngine(net, num_slots=1, prompt_len=4, max_cache_len=8,
                      host_cache_blocks=-1)
    e_none = ServingEngine(net, num_slots=1, prompt_len=4,
                           max_cache_len=8, prefix_cache_mode="none")
    assert e_none.prefix_cache_mode == "none" and e_none._radix is None
    e0 = ServingEngine(net, num_slots=1, prompt_len=4, max_cache_len=8,
                       host_cache_blocks=0)
    assert e0.prefix_cache_mode == "radix"
    assert not e0._host_tier.would_accept(1)


# -- slow twins ------------------------------------------------------

@pytest.mark.slow
def test_tiered_multiturn_parity_int8(netm):
    """The multi-turn tiered trace over the int8 arenas: demotion and
    promotion move codes AND scale planes at exact bytes, so host-tier
    hits stay bit-identical to the uninterrupted int8 engine."""
    cfg, net = netm
    eng_r, served_r = _multiturn_trace(net, cfg, "radix", kvdt="int8")
    eng_p, served_p = _multiturn_trace(net, cfg, "none", kvdt="int8")
    for (ids_r, rr), (ids_p, rp) in zip(served_r, served_p):
        np.testing.assert_array_equal(ids_r, ids_p)
        np.testing.assert_array_equal(rr.output, rp.output)
    assert eng_r.stats()["prefix_host_hits"] >= 1
    eng_r._pool.check()


@pytest.mark.slow
def test_tiered_fragmentation_stress(netm):
    """Adversarial mix over a scarce pool WITH the tiered cache:
    shared-prefix and cold requests interleaved through 2 slots and
    10 blocks, random forced swaps and a mid-run cancel — every
    surviving output oracle-exact, the pool audits clean after every
    step, and the tier drains its preempt half to zero."""
    cfg, net = netm
    rng = np.random.default_rng(13)
    shared = rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
    fi = FaultInjector()
    eng = ServingEngine(net, num_slots=2, prompt_len=P, max_cache_len=C,
                        steps_per_call=2, block_len=2, chunk_len=4,
                        num_blocks=10, compute_dtype="float32",
                        fault_injector=fi)
    reqs = []
    for i in range(10):
        n = int(rng.integers(4, 9))
        ids = rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
        if rng.random() < 0.5:
            ids[:4] = shared
        m = int(rng.integers(2, 6))
        reqs.append((ids, m, eng.submit(ids, max_new_tokens=m)))
    victim = reqs[7][2]
    steps = 0
    cancelled = False
    while (eng._queue or eng._swapped
           or any(s is not None for s in eng._slots)):
        if steps == 3:
            cancelled = eng.cancel(victim.request_id)
        if steps % 4 == 2:
            live = [r for _, _, r in reqs
                    if r.state in ("prefill", "decode")]
            if live:
                fi.force_swap(live[0].request_id)
        eng.step()
        eng._pool.check()
        steps += 1
        assert steps < 1000
    for ids, m, r in reqs:
        if r is victim and cancelled:
            continue
        np.testing.assert_array_equal(r.output, _oracle(net, ids, m))
    s = eng.stats()
    assert s["swap_host_blocks"] == 0 and eng._pool.in_use() == 0
    eng._pool.check()
