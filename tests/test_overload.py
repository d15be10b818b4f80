"""Overload-resilient serving (inference/serving.py + faultinject.py):
preemption + host-RAM KV swap with token-exact resume, priority/EDF
admission, bounded-queue shedding, queue-delay timeouts, the
fault-injection harness (alloc exhaustion / forced swap / stalled
step), BlockPool.check() invariants and the EngineStalledError guard.

Tier-1 budget discipline (truncation-scored 870s wall on a 2-core
box): the only compile-bearing unmarked tests are ONE combined
preempt/swap/resume parity trace (greedy + spec-decode + seeded
sampling co-resident, forced and pressure preemptions, cancel-in-
flight piggybacked on its warm programs) and one tiny
pressure-preemption trace; the scheduling-order, shed, timeout and
pool-audit units poke host-side state with zero XLA dispatches.  The
int8-arena parity twin and the wide adversarial trace are
``slow``-marked."""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import models
from paddle_tpu.inference.faultinject import FaultInjector
from paddle_tpu.inference.sampling import SamplingParams
from paddle_tpu.inference.serving import (AdmissionError, BlockPool,
                                          EngineStalledError,
                                          ServingEngine)


@pytest.fixture(scope="module")
def netm():
    paddle.seed(2024)
    cfg = models.tiny_llama_config()
    net = models.LlamaForCausalLM(cfg)
    net.eval()
    return cfg, net


P, C = 6, 32      # one (prompt_len, max_cache_len) so oracles share


def _oracle(net, ids, max_new):
    padded = np.zeros((P,), np.int32)
    padded[:ids.size] = ids
    out = paddle.to_tensor(padded[None, :].astype(np.int32))
    return np.asarray(net.generate(
        out, seq_lens=np.array([ids.size]), max_new_tokens=max_new,
        max_cache_len=C, compute_dtype="float32")._value)[0]


def _drain_checked(eng, fi=None, force_at=(), reqs=()):
    """Drive step() manually, force-swapping every in-flight request at
    the given step indices, auditing the pool after every iteration."""
    steps = 0
    while (eng._queue or eng._swapped
           or any(s is not None for s in eng._slots)):
        if fi is not None and steps in force_at:
            for r in reqs:
                if r.state in ("prefill", "decode"):
                    fi.force_swap(r.request_id)
        eng.step()
        eng._pool.check()
        steps += 1
        assert steps < 500, "trace did not drain"
    return steps


def _combined_trace(net, cfg, kvdt, fi=None, force_at=()):
    """The acceptance trace: a greedy, a spec-decode and a seeded-
    sampled request co-resident on one engine; with ``fi`` armed,
    every in-flight request is forced to swap at three different
    iterations (prefill AND decode phases get hit)."""
    rng = np.random.default_rng(11)
    eng = ServingEngine(net, num_slots=3, prompt_len=P, max_cache_len=C,
                        steps_per_call=1, block_len=4,
                        compute_dtype="float32", kv_cache_dtype=kvdt,
                        fault_injector=fi)
    ids = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
           for n in (4, 5, 4)]
    r1 = eng.submit(ids[0], max_new_tokens=10)
    r2 = eng.submit(ids[1], max_new_tokens=10, spec_decode=3)
    r3 = eng.submit(ids[2], max_new_tokens=10,
                    sampling=SamplingParams(temperature=0.9, top_k=8,
                                            seed=7))
    _drain_checked(eng, fi, force_at, (r1, r2, r3))
    return eng, ids, (r1, r2, r3)


def _assert_combined_parity(net, cfg, kvdt):
    ref_eng, ids, ref = _combined_trace(net, cfg, kvdt)
    fi = FaultInjector()
    eng, _, got = _combined_trace(net, cfg, kvdt, fi, force_at=(2, 4, 6))
    s = eng.stats()
    assert s["preemptions"] >= 3 and \
        s["preempt_resumes"] == s["preemptions"]
    assert s["swap_blocks_out"] == s["swap_blocks_in"] > 0
    assert s["swap_host_blocks"] == 0 and s["swapped_waiting"] == 0
    # the whole point: a request that was swapped out and re-admitted
    # (several times, in prefill and decode phases, spec and sampled
    # modes included) emits token-for-token what the uninterrupted
    # engine emits — and the greedy row token-for-token generate()
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a.output, b.output)
    np.testing.assert_array_equal(got[0].output,
                                  _oracle(net, ids[0], 10))
    assert all(("forced_swap", r.request_id) in fi.events for r in got)
    assert eng._pool.in_use() == 0
    return eng


@pytest.mark.slow
def test_preempt_swap_resume_parity_float(netm):
    """Forced preempt -> host-RAM swap -> resume is token-exact on the
    float arena with spec-decode and seeded sampling active in the
    same trace; cancel-in-flight rides the warm engine afterwards."""
    cfg, net = netm
    eng = _assert_combined_parity(net, cfg, None)

    # -- satellite piggyback: cancel() now reaches IN-FLIGHT requests
    # (warm programs, no new compiles).  The cancelled decode-phase
    # request frees its blocks immediately; the co-resident request
    # is unharmed and stays generate()-exact.
    rng = np.random.default_rng(21)
    ca = rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
    cb = rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
    base_cancel = eng.stats()["cancelled"]
    ra = eng.submit(ca, max_new_tokens=10)
    rb = eng.submit(cb, max_new_tokens=10)
    eng.step()
    eng.step()
    assert ra.state == "decode"
    in_use_before = eng._pool.in_use()
    assert eng.cancel(ra.request_id)
    assert ra.state == "cancelled" and ra.slot is None
    assert eng._pool.in_use() < in_use_before      # blocks freed NOW
    eng._pool.check()
    eng.run(wall_timeout_s=120)
    assert rb.state == "finished"
    np.testing.assert_array_equal(rb.output, _oracle(net, cb, 10))
    assert eng.stats()["cancelled"] == base_cancel + 1
    assert not eng.cancel(ra.request_id)           # terminal: False
    # swapped-phase cancel drops the host copy (preempt directly: a
    # forced swap would round-trip back in within the same step
    # because the pool has room)
    rc = eng.submit(ca, max_new_tokens=10)
    eng.step()
    eng._preempt(rc, reason="test")
    assert rc.state == "swapped"
    assert eng.cancel(rc.request_id)
    assert rc.state == "cancelled" and eng.stats()["swap_host_blocks"] == 0
    eng._pool.check()


@pytest.mark.slow
def test_preempt_swap_resume_parity_int8(netm):
    """The same combined trace over the int8 arenas: codes AND scale
    planes swap at exact bytes, so resume parity holds bit-for-bit
    against the uninterrupted int8 engine."""
    cfg, net = netm
    _assert_combined_parity(net, cfg, "int8")


def test_pressure_preemption_strictly_worse_victim(netm):
    """A high-priority arrival that cannot allocate preempts the
    lowest-class running victim (blocks swap to host RAM, slot frees),
    runs, and the victim resumes to a token-exact finish.  Equal-class
    arrivals never preempt (no thrash)."""
    cfg, net = netm
    rng = np.random.default_rng(5)
    long_ids = rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
    short_ids = rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)

    def build():
        # long: 4 + 10 - 1 = 13 tokens -> 4 blocks of 4; pool of 5
        # leaves 1 free, short needs 2 -> only preemption can admit it
        return ServingEngine(net, num_slots=2, prompt_len=P,
                             max_cache_len=C, steps_per_call=1,
                             block_len=4, num_blocks=5,
                             compute_dtype="float32")

    eng = build()
    rl = eng.submit(long_ids, max_new_tokens=10, priority=0)
    eng.step()
    eng.step()
    rs = eng.submit(short_ids, max_new_tokens=5, priority=1)
    eng.step()
    assert rl.state == "swapped" and rs.state in ("prefill", "decode")
    assert eng.stats()["preemptions"] == 1
    eng._pool.check()
    eng.run(wall_timeout_s=120)
    np.testing.assert_array_equal(rl.output, _oracle(net, long_ids, 10))
    np.testing.assert_array_equal(rs.output, _oracle(net, short_ids, 5))
    assert eng.stats()["preempt_resumes"] == 1
    eng._pool.check()

    # equal class: the arrival waits instead of thrashing the victim
    # (same engine, warm programs — the drained pool replays the
    # scenario without the priority gap)
    r1 = eng.submit(long_ids, max_new_tokens=10)
    eng.step()
    r2 = eng.submit(short_ids, max_new_tokens=5)
    eng.step()
    assert r1.state == "decode" and r2.state == "queued"
    eng.run(wall_timeout_s=120)
    assert eng.stats()["preemptions"] == 1      # unchanged from above
    np.testing.assert_array_equal(r2.output, _oracle(net, short_ids, 5))


def test_priority_edf_admission_order(netm):
    """Admission is priority-then-EDF, FIFO within a class — asserted
    at the host scheduling layer (``_admit`` + the prefill queue), no
    dispatch needed."""
    cfg, net = netm
    rng = np.random.default_rng(7)
    ids = rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
    eng = ServingEngine(net, num_slots=6, prompt_len=P, max_cache_len=C,
                        compute_dtype="float32")
    t0 = eng._clock()
    lo = eng.submit(ids, max_new_tokens=2, priority=0)
    hi_late = eng.submit(ids, max_new_tokens=2, priority=2,
                         deadline_s=50.0, arrival_time=t0)
    hi_soon = eng.submit(ids, max_new_tokens=2, priority=2,
                         deadline_s=5.0, arrival_time=t0)
    mid_a = eng.submit(ids, max_new_tokens=2, priority=1)
    mid_b = eng.submit(ids, max_new_tokens=2, priority=1)
    hi_nodl = eng.submit(ids, max_new_tokens=2, priority=2,
                         arrival_time=t0)
    eng._admit(eng._clock(), [])       # host-only: map queue -> slots
    got = [r.request_id for r in eng._prefilling]
    # priority 2 first (EDF within: 5s, 50s, then no deadline), then
    # priority 1 FIFO, then priority 0
    want = [hi_soon.request_id, hi_late.request_id, hi_nodl.request_id,
            mid_a.request_id, mid_b.request_id, lo.request_id]
    assert got == want, (got, want)
    # slot indices were assigned in that same order
    assert [eng._slots[i].request_id for i in range(6)] == want

    # default traces (no SLO kwargs) stay FIFO over submission order
    eng2 = ServingEngine(net, num_slots=3, prompt_len=P, max_cache_len=C,
                         compute_dtype="float32")
    rs = [eng2.submit(ids, max_new_tokens=2) for _ in range(3)]
    eng2._admit(eng2._clock(), [])
    assert [r.request_id for r in eng2._prefilling] == \
        [r.request_id for r in rs]


def test_bounded_queue_shed_and_admission_error(netm):
    """A full bounded queue sheds: a strictly-higher-class arrival
    displaces the worst queued request (state "shed"); an equal-class
    arrival is refused with a typed AdmissionError and nothing is
    enqueued or leaked.  Host-only (future arrivals, no dispatch)."""
    cfg, net = netm
    rng = np.random.default_rng(9)
    ids = rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
    eng = ServingEngine(net, num_slots=1, prompt_len=P, max_cache_len=C,
                        compute_dtype="float32", max_queue=2)
    far = 1e18                          # never "arrives"
    a = eng.submit(ids, max_new_tokens=3, arrival_time=far, priority=1)
    b = eng.submit(ids, max_new_tokens=3, arrival_time=far, priority=0)
    with pytest.raises(AdmissionError) as ei:
        eng.submit(ids, max_new_tokens=3, arrival_time=far, priority=0)
    assert ei.value.queue_depth == 2 and ei.value.max_queue == 2
    assert len(eng._queue) == 2
    # higher-class arrival displaces the worst queued request (b:
    # lowest priority); a keeps its place
    hi = eng.submit(ids, max_new_tokens=3, arrival_time=far, priority=5)
    assert b.state == "shed" and b.finish_time is not None
    assert b.output.size == b.max_new_tokens      # padded terminal output
    assert a.state == "queued" and hi.state == "queued"
    assert len(eng._queue) == 2
    # lowest PRIORITY is always shed first: a (p1) goes before either
    # p5 request whatever the deadlines say
    c = eng.submit(ids, max_new_tokens=3, arrival_time=far, priority=5,
                   deadline_s=1.0)
    assert a.state == "shed" and hi.state == "queued" \
        and c.state == "queued"
    # within one class, deadlines break the tie: the no-deadline
    # request (hi) is worse than both deadlined ones
    d = eng.submit(ids, max_new_tokens=3, arrival_time=far, priority=5,
                   deadline_s=0.5)
    assert hi.state == "shed" and c.state == "queued" \
        and d.state == "queued"
    s = eng.stats()
    assert s["shed"] == 4               # 1 rejected + 3 evicted (b, a, hi)
    eng._pool.check()

    # an INVALID submission must never shed a victim: the bounded-
    # queue decision runs only after every validation passes
    from paddle_tpu.inference.sampling import (SamplingParams,
                                               TokenMaskProcessor)

    class _BadMask(TokenMaskProcessor):
        def begin(self, prompt_ids):
            pass

        def allowed(self):
            return np.ones(7, bool)     # wrong width vs the vocab

    before = [(r.request_id, r.state) for r in eng._queue]
    with pytest.raises(ValueError, match="wide"):
        eng.submit(ids, max_new_tokens=3, arrival_time=far,
                   priority=99,
                   sampling=SamplingParams(mask_processor=_BadMask()))
    assert [(r.request_id, r.state) for r in eng._queue] == before
    assert eng.stats()["shed"] == 4     # nobody paid for the bad submit
    eng._pool.check()

    # a bounded-queue-REJECTED spec submit must not widen the
    # engine-lifetime verify width or install the default drafter
    assert eng._spec_k_max == 0 and eng._drafter is None
    with pytest.raises(AdmissionError):
        eng.submit(ids, max_new_tokens=3, arrival_time=far,
                   spec_decode=7)       # same class as queue: rejected
    assert eng._spec_k_max == 0 and eng._drafter is None

    # expired queued entries are dead weight, not shed fodder nor a
    # reason to reject: a full queue of past-SLO requests times out at
    # submit and the fresh EQUAL-class arrival is accepted
    import time as _time
    eng5 = ServingEngine(net, num_slots=1, prompt_len=P,
                         max_cache_len=C, compute_dtype="float32",
                         max_queue=1)
    old = eng5.submit(ids, max_new_tokens=3, max_queue_delay_s=0.0)
    _time.sleep(0.005)
    fresh = eng5.submit(ids, max_new_tokens=3)
    assert old.state == "timeout" and fresh.state == "queued"
    s5 = eng5.stats()
    assert s5["timeouts"] == 1 and s5["shed"] == 0
    eng5._pool.check()


def test_queue_delay_timeout_and_deadline_is_not_a_kill(netm):
    """A queued request whose wait exceeds max_queue_delay_s finishes
    with state "timeout" (padded output, pins released, returned from
    step()); deadline_s alone never kills — it only orders.  Driven
    with an alloc-failure fault so nothing ever dispatches."""
    cfg, net = netm
    rng = np.random.default_rng(13)
    ids = rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
    fi = FaultInjector()
    fi.fail_allocs(None)               # admission can never allocate
    eng = ServingEngine(net, num_slots=1, prompt_len=P, max_cache_len=C,
                        compute_dtype="float32", fault_injector=fi)
    t = eng.submit(ids, max_new_tokens=3, max_queue_delay_s=0.0)
    dl = eng.submit(ids, max_new_tokens=3, deadline_s=0.001)
    import time as _time
    _time.sleep(0.005)
    out = eng.step()
    assert t.state == "timeout" and t in out
    assert t.finish_time is not None and t.output.size == 3
    assert dl.state == "queued"        # deadline passed, NOT killed
    assert eng.stats()["timeouts"] == 1
    eng._pool.check()
    # clearing the fault serves the survivor (its prefix pins were
    # never leaked by the sweep)
    fi.clear_alloc_failures()
    eng.cancel(dl.request_id)          # keep the test dispatch-free
    assert not (eng._queue or eng._swapped)
    eng._pool.check()


def test_blockpool_check_audit_and_idempotent_release():
    """BlockPool.check() catches refcount drift / double-free /
    tree-index corruption; park, re-pin and reclaim move a tree-held
    block as they should; _release_blocks is idempotent (model-free
    unit)."""
    pool = BlockPool(num_blocks=6, block_len=4)
    assert pool.check()
    blocks = pool.alloc(3)
    pool.tree_hold(blocks[0])
    assert pool.check()
    pool.unpin(blocks[0])              # tree-held -> parks in the LRU
    pool.unpin(blocks[1])              # not held -> free list
    assert pool.check() and list(pool._tree_lru) == [blocks[0]]
    pool.pin(blocks[0])                # a hit re-pins it out of the LRU
    assert pool.check() and not pool._tree_lru
    pool.unpin(blocks[0])
    with pytest.raises(RuntimeError, match="double free"):
        pool.unpin(blocks[1])
    # direct corruption is caught by the audit
    pool._ref[blocks[2]] = 0           # leaked: ref 0, nowhere
    with pytest.raises(RuntimeError, match="leaked"):
        pool.check()
    pool._ref[blocks[2]] = 1
    assert pool.check()
    pool._free.append(blocks[2])       # free while pinned
    with pytest.raises(RuntimeError, match="free list"):
        pool.check()
    pool._free.pop()
    tr_pool = BlockPool(num_blocks=2, block_len=4)
    got = []
    tr_pool.reclaim_cb = got.append
    (b0,) = tr_pool.alloc(1)
    tr_pool.tree_hold(b0)
    tr_pool.unpin(b0)
    del tr_pool._tree_lru[b0]          # held at ref 0, out of the LRU
    with pytest.raises(RuntimeError, match="unreclaimable"):
        tr_pool.check()
    tr_pool._tree_lru[b0] = True
    tr_pool._tree_ref.discard(b0)      # in the LRU, not held
    with pytest.raises(RuntimeError, match="not tree-referenced"):
        tr_pool.check()
    tr_pool._tree_ref.add(b0)
    assert tr_pool.check()
    # reclaim: the free list first, then the parked block, one callback
    assert tr_pool.alloc(2) == [1 - b0, b0] and got == [[b0]]
    assert tr_pool.check() and not tr_pool._tree_ref

    # _release_blocks idempotence at the engine layer needs no engine:
    # the contract is "blocks cleared before return", so a double call
    # must not double-unpin — emulate with a minimal stand-in
    class _Req:
        matched = []
        slot = None
        adapter_slot = None   # no LoRA adapter pinned (PR 11)
    pool2 = BlockPool(num_blocks=6, block_len=4)
    req = _Req()
    req.blocks = pool2.alloc(2)

    class _Eng:
        _pool = pool2
        _tables = np.zeros((1, 2), np.int32)

        def _update_block_gauges(self):
            pass
    eng = _Eng()
    ServingEngine._release_blocks(eng, req)
    assert pool2.in_use() == 0 and req.blocks == []
    ServingEngine._release_blocks(eng, req)     # second call: no-op
    assert pool2.check()


def test_fault_injection_no_wedge_and_stall_guard(netm):
    """The >= 3 fault modes of the harness: (1) allocation exhaustion
    wedges admission -> run(wall_timeout_s) raises a diagnosable
    EngineStalledError, the pool audits clean, and clearing the fault
    drains the SAME engine to a token-exact finish; (2) stalled steps
    trip the same guard and also recover; (3) forced swap-outs are
    covered by the parity trace (test_preempt_swap_resume_parity_*).
    max_new_tokens=1 keeps this chunk-program-only (no decode
    compiles)."""
    cfg, net = netm
    rng = np.random.default_rng(17)
    ids = rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)

    fi = FaultInjector()
    fi.fail_allocs(None)
    eng = ServingEngine(net, num_slots=1, prompt_len=P, max_cache_len=C,
                        compute_dtype="float32", fault_injector=fi)
    w = eng.submit(ids, max_new_tokens=1)
    with pytest.raises(EngineStalledError) as ei:
        eng.run(wall_timeout_s=0.15)
    msg = str(ei.value)
    assert "queued=1" in msg and "blocks free" in msg
    eng._pool.check()
    assert ("alloc_fail", None) in fi.events
    fi.clear_alloc_failures()
    eng.run(wall_timeout_s=120)
    assert w.state == "finished"
    np.testing.assert_array_equal(w.output, _oracle(net, ids, 1))
    eng._pool.check()

    # stalled steps that also make no progress, SAME engine (warm
    # programs): the wall guard trips, then recovery drains
    fi.stall_steps(100, 0.05)
    fi.fail_allocs(None)
    w2 = eng.submit(ids, max_new_tokens=1)
    with pytest.raises(EngineStalledError):
        eng.run(wall_timeout_s=0.1)
    eng._pool.check()
    assert ("stall", None) in fi.events
    fi._stalls.clear()
    fi.clear_alloc_failures()
    eng.run(wall_timeout_s=120)
    assert w2.state == "finished"
    eng._pool.check()

    # a finite alloc-failure burst delays admission but never wedges
    n_fail0 = fi.events.count(("alloc_fail", None))
    fi.fail_allocs(3)
    w3 = eng.submit(ids, max_new_tokens=1)
    eng.run(wall_timeout_s=120)
    assert w3.state == "finished"
    assert fi.events.count(("alloc_fail", None)) == n_fail0 + 3
    eng._pool.check()

    # a SWAP-wedged engine (only live request parked on the swap list,
    # resume allocation failing) must nap between retries, not
    # hot-spin: the alloc-failure event count bounds the loop rate
    # max_new=3: step 1 emits the prefill token + one decode token,
    # leaving the request IN FLIGHT with one token of budget
    w4 = eng.submit(ids, max_new_tokens=3)
    eng.step()
    assert w4.state == "decode"
    eng._preempt(w4, reason="test")
    assert w4.state == "swapped"
    fi.fail_allocs(None)
    n_fail1 = fi.events.count(("alloc_fail", None))
    with pytest.raises(EngineStalledError):
        eng.run(wall_timeout_s=0.15)
    spins = fi.events.count(("alloc_fail", None)) - n_fail1
    assert spins < 2000, f"swap-wedged run hot-spun: {spins} allocs"
    fi.clear_alloc_failures()
    eng.run(wall_timeout_s=120)
    assert w4.state == "finished"
    np.testing.assert_array_equal(w4.output, _oracle(net, ids, 3))
    eng._pool.check()


@pytest.mark.slow
def test_wide_overload_trace_invariants(netm):
    """Adversarial wide trace: mixed priorities/deadlines over a
    scarce pool with a bounded queue, queue-delay SLOs, random forced
    swaps and finite alloc-failure bursts — every request reaches a
    terminal state, the pool audits clean after every step, nothing
    leaks, and every FINISHED greedy request is generate()-exact."""
    cfg, net = netm
    rng = np.random.default_rng(31)
    fi = FaultInjector()
    eng = ServingEngine(net, num_slots=3, prompt_len=P, max_cache_len=C,
                        steps_per_call=2, block_len=4, num_blocks=14,
                        compute_dtype="float32", max_queue=6,
                        fault_injector=fi)
    reqs, oracle_args = [], {}
    for i in range(14):
        n = int(rng.integers(3, 5))
        m = int(rng.integers(4, 11))
        ids = rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
        kw = {"priority": int(rng.integers(0, 3))}
        if rng.random() < 0.4:
            kw["deadline_s"] = float(rng.uniform(0.5, 5.0))
        if rng.random() < 0.3:
            kw["max_queue_delay_s"] = float(rng.uniform(0.05, 0.4))
        try:
            r = eng.submit(ids, max_new_tokens=m, **kw)
        except AdmissionError:
            continue
        reqs.append(r)
        oracle_args[r.request_id] = (ids, m)
    steps = 0
    while (eng._queue or eng._swapped
           or any(s is not None for s in eng._slots)):
        if steps % 5 == 2:
            live = [r for r in reqs if r.state in ("prefill", "decode")]
            if live:
                fi.force_swap(live[int(rng.integers(len(live)))].request_id)
        if steps % 7 == 3:
            fi.fail_allocs(2)
        eng.step()
        eng._pool.check()
        steps += 1
        assert steps < 2000
    terminal = {"finished", "timeout", "shed", "cancelled"}
    assert all(r.state in terminal for r in reqs)
    assert eng._pool.in_use() == 0
    assert eng.stats()["swap_host_blocks"] == 0
    for r in reqs:
        if r.state == "finished":
            ids, m = oracle_args[r.request_id]
            np.testing.assert_array_equal(r.output,
                                          _oracle(net, ids, m))
    # no cancels in this trace, so every swap-out resumed exactly once
    s = eng.stats()
    assert s["preemptions"] == s["preempt_resumes"]
