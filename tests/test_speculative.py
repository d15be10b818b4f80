"""Speculative decoding (inference/speculative.py + the ServingEngine
spec_decode mode): drafter semantics, the acceptance rule, and the
greedy-equivalence contract — spec-decode output token-for-token
identical to per-request ``generate()`` and to the non-speculative
engine across acceptance, rejection, rollback and EOS cases.

Tier-1 budget discipline (truncation-scored suite): the drafter and
acceptance-rule tests are pure host numpy; the parity trace uses ONE
engine config, the module-shared tiny net, and two oracle max_new
values; the wider matrix (ModelDrafter through an engine, interpret-
mode kernel smoke) is ``slow``-marked."""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import models
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.inference.speculative import (ModelDrafter, NGramDrafter,
                                              accept_drafts,
                                              build_spec_verify)
from scripted_drafter import ScriptedDrafter


@pytest.fixture(scope="module")
def netm():
    paddle.seed(2024)
    cfg = models.tiny_llama_config()
    net = models.LlamaForCausalLM(cfg)
    net.eval()
    return cfg, net


P, C = 12, 48     # one (prompt_len, max_cache_len) so oracles share


def _oracle(net, ids, n, max_new, eos=None):
    padded = np.zeros((P,), np.int32)
    padded[:n] = ids[:n]
    return np.asarray(net.generate(
        paddle.to_tensor(padded[None, :]), seq_lens=np.array([n]),
        max_new_tokens=max_new, max_cache_len=C, eos_token_id=eos,
        compute_dtype="float32")._value)[0]


# ---------------------------------------------------------------------------
# host-side units: drafter + acceptance rule (no device work)
# ---------------------------------------------------------------------------

def test_ngram_drafter_basic_matching():
    dr = NGramDrafter(max_ngram=3, min_ngram=1)
    # trailing [7, 8] recurs earlier; continuation after it is 9, 10
    ctx = np.array([1, 7, 8, 9, 10, 11, 7, 8], np.int32)
    np.testing.assert_array_equal(dr.propose(ctx, 2), [9, 10])
    # longest n wins: trailing [8, 9] only matches at n=2; n=3 has none
    ctx2 = np.array([5, 8, 9, 2, 4, 8, 9], np.int32)
    np.testing.assert_array_equal(dr.propose(ctx2, 2), [2, 4])
    # no prior occurrence of the last token at any n -> empty
    assert dr.propose(np.array([1, 2, 3, 4], np.int32), 4).size == 0
    # k <= 0 and too-short contexts -> empty
    assert dr.propose(ctx, 0).size == 0
    assert dr.propose(np.array([3], np.int32), 4).size == 0


def test_ngram_drafter_constant_run_proposes_full_k():
    """The continuation-length rule: on a constant run the most recent
    match sits flush against the end and could only propose its
    truncated tail — the drafter must back off to a match with a full
    k-token continuation (self-drafting's bread-and-butter case)."""
    dr = NGramDrafter()
    ctx = np.full((12,), 42, np.int32)
    np.testing.assert_array_equal(dr.propose(ctx, 4), [42] * 4)
    # periodic run: proposes the cycle continuation, full k
    cyc = np.array([1, 2, 3] * 4, np.int32)
    np.testing.assert_array_equal(dr.propose(cyc, 4), [1, 2, 3, 1])


def test_ngram_drafter_guards():
    with pytest.raises(ValueError, match="min_ngram"):
        NGramDrafter(max_ngram=2, min_ngram=3)
    with pytest.raises(ValueError, match="min_ngram"):
        NGramDrafter(min_ngram=0)


def test_accept_drafts_rule():
    # full acceptance: every draft matches, bonus token appended
    emitted, a = accept_drafts([5, 6, 7, 8], np.array([5, 6, 7]))
    assert emitted == [5, 6, 7, 8] and a == 3
    # first mismatch: accepted prefix + the target's correction token
    emitted, a = accept_drafts([5, 9, 7, 8], np.array([5, 6, 7]))
    assert emitted == [5, 9] and a == 1
    # total rejection: just the correction (a plain decode step)
    emitted, a = accept_drafts([4, 9, 7, 8], np.array([5, 6, 7]))
    assert emitted == [4] and a == 0
    # empty drafts: the single greedy token
    emitted, a = accept_drafts([4], np.zeros((0,), np.int32))
    assert emitted == [4] and a == 0
    # accepted EOS stops acceptance (no token conditioned on post-EOS
    # context may be emitted — the sequential loop pads there)
    emitted, a = accept_drafts([5, 2, 7, 8], np.array([5, 2, 7]),
                               eos_token_id=2)
    assert emitted == [5, 2] and a == 2
    # correction token may itself be EOS (emitted like the plain path)
    emitted, a = accept_drafts([2, 6, 7], np.array([5, 6]),
                               eos_token_id=2)
    assert emitted == [2] and a == 0


def test_build_spec_verify_guards(netm):
    cfg, net = netm
    from paddle_tpu.inference.sampling import DfaTokenMask, SamplingParams
    from paddle_tpu.models.generation import GenerationConfig
    with pytest.raises(ValueError, match="beam"):
        build_spec_verify(net, GenerationConfig(num_beams=2), 4)
    with pytest.raises(ValueError, match="steps"):
        build_spec_verify(net, GenerationConfig(), 0)
    # token-mask rows structurally never reach a verify program
    with pytest.raises(ValueError, match="mask"):
        build_spec_verify(net, GenerationConfig(), 4,
                          samp_flags=(True, False, False, True))
    # sampling + spec_decode now composes (stochastic speculative
    # sampling); the ONE unsupported combo is a mask processor + spec
    eng = ServingEngine(net, num_slots=1, prompt_len=4, max_cache_len=8,
                        do_sample=True, compute_dtype="float32")
    eng.submit(np.zeros((4,), np.int32), max_new_tokens=4, spec_decode=2)
    mask = DfaTokenMask(np.zeros((1, cfg.vocab_size), np.int32))
    with pytest.raises(ValueError, match="mask"):
        eng.submit(np.zeros((4,), np.int32), max_new_tokens=4,
                   spec_decode=2,
                   sampling=SamplingParams(temperature=0.7,
                                           mask_processor=mask))
    eng2 = ServingEngine(net, num_slots=1, prompt_len=4, max_cache_len=8,
                         compute_dtype="float32")
    with pytest.raises(ValueError, match="spec_decode"):
        eng2.submit(np.zeros((4,), np.int32), spec_decode=0)
    # a REJECTED spec submit must not widen the engine-lifetime verify
    # width or install the default drafter
    with pytest.raises(ValueError, match="max_cache_len"):
        eng2.submit(np.zeros((4,), np.int32), max_new_tokens=100,
                    spec_decode=32)
    assert eng2._spec_k_max == 0 and eng2._drafter is None


# ---------------------------------------------------------------------------
# the tier-1 greedy-equivalence trace
# ---------------------------------------------------------------------------

def test_spec_parity_acceptance_rejection_rollback_eos(netm):
    """The acceptance contract in one trace: two speculative requests
    whose drafts are their own greedy continuation with a wrong token
    planted at fixed positions (``ScriptedDrafter``: real acceptances,
    and rejections + KV rollback, whatever the weights), a plain
    request coexisting in the same iterations, and an EOS cut
    mid-stream — every output token-for-token identical to per-request
    greedy ``generate()`` AND to the non-speculative engine on the same
    requests."""
    cfg, net = netm
    rng = np.random.default_rng(0)
    pat = rng.integers(0, cfg.vocab_size, (3,)).astype(np.int32)
    rep = np.tile(pat, 4)                             # 12 tokens
    rnd = rng.integers(0, cfg.vocab_size, (10,)).astype(np.int32)
    plain = rng.integers(0, cfg.vocab_size, (7,)).astype(np.int32)
    # an EOS that cuts rep's stream short (from the no-EOS oracle:
    # tokens before EOS are unaffected by the eos config)
    eos = int(_oracle(net, rep, 12, 14)[3])

    specs = [(rep, 12, 14, 3), (rnd, 10, 14, 3), (plain, 7, 6, None)]
    drafter = ScriptedDrafter(
        [(ids, _oracle(net, ids, n, mn, eos=eos))
         for ids, n, mn, k in specs if k is not None], cfg.vocab_size)
    eng = ServingEngine(net, num_slots=2, prompt_len=P, max_cache_len=C,
                        steps_per_call=2, block_len=4, chunk_len=8,
                        eos_token_id=eos, compute_dtype="float32",
                        drafter=drafter)
    reqs = [eng.submit(ids, max_new_tokens=mn, spec_decode=k)
            for ids, n, mn, k in specs]
    done = eng.run(max_iters=500)
    assert len(done) == len(specs)
    for req, (ids, n, mn, _k) in zip(reqs, specs):
        np.testing.assert_array_equal(
            req.output, _oracle(net, ids, n, mn, eos=eos))
    s = eng.stats()
    assert s["spec_verify_steps"] > 0
    assert s["spec_accepted_tokens"] > 0          # real acceptances
    # real rejections too (rollback exercised): some drafted tokens
    # did NOT survive verification
    assert s["spec_draft_tokens"] > s["spec_accepted_tokens"]
    assert 0.0 < s["spec_acceptance_rate"] < 1.0
    assert s["spec_draft_hits"] > 0
    assert s["mean_latency_s"] is not None and s["mean_latency_s"] > 0
    assert s["blocks_in_use"] == 0                # pool fully drained
    assert all(r == 0 for r in eng._pool._ref)    # clean refcounts

    # the non-speculative engine on the same requests — same tokens
    eng2 = ServingEngine(net, num_slots=2, prompt_len=P, max_cache_len=C,
                         steps_per_call=2, block_len=4, chunk_len=8,
                         eos_token_id=eos, compute_dtype="float32")
    reqs2 = [eng2.submit(ids, max_new_tokens=mn)
             for ids, n, mn, _k in specs]
    eng2.run(max_iters=500)
    for r_spec, r_plain in zip(reqs, reqs2):
        np.testing.assert_array_equal(r_spec.output, r_plain.output)
    assert eng2.stats()["spec_verify_steps"] == 0


def test_spec_decode_over_int8_kv_smoke(netm):
    """Speculative decoding over the QUANTIZED cache: the verify
    forward reads and quantize-writes the SAME int8 arenas the decode
    path maintains, so spec output must stay token-for-token identical
    to the non-speculative int8 engine — greedy equivalence is an
    argmax-agreement argument over one engine's own logits and holds
    whatever the at-rest cache dtype.  Acceptance/rollback bookkeeping
    must really engage (verify forwards dispatched, drafts scored)."""
    cfg, net = netm
    rng = np.random.default_rng(11)
    pat = rng.integers(0, cfg.vocab_size, (3,)).astype(np.int32)
    rep = np.tile(pat, 4)                             # 12 tokens

    def run(spec_k):
        eng = ServingEngine(net, num_slots=1, prompt_len=P,
                            max_cache_len=C, steps_per_call=1,
                            block_len=4, chunk_len=12,
                            compute_dtype="float32",
                            kv_cache_dtype="int8")
        req = eng.submit(rep, max_new_tokens=8, spec_decode=spec_k)
        eng.run(max_iters=200)
        return eng, req

    e_s, r_s = run(3)
    e_p, r_p = run(None)
    np.testing.assert_array_equal(r_s.output, r_p.output)
    s = e_s.stats()
    assert s["kv_cache_dtype"] == "int8"
    assert s["spec_verify_steps"] >= 1
    assert s["spec_draft_tokens"] >= 1
    assert e_p.stats()["spec_verify_steps"] == 0


def test_model_drafter_proposes_target_continuation(netm):
    """ModelDrafter through the compiled generate path: with the
    TARGET as its own draft model the proposal must be exactly the
    target's greedy continuation (the 100%-acceptance bound), padded
    contexts and the fixed-capacity grid included."""
    cfg, net = netm
    rng = np.random.default_rng(1)
    ids = rng.integers(0, cfg.vocab_size, (9,)).astype(np.int32)
    dr = ModelDrafter(net, max_context=P, max_draft=4,
                      compute_dtype="float32")
    d = dr.propose(ids, 3)
    want = np.asarray(net.generate(
        paddle.to_tensor(np.pad(ids, (0, P - ids.size))[None, :]),
        seq_lens=np.array([ids.size]), max_new_tokens=4,
        max_cache_len=P + 4, compute_dtype="float32")._value)[0]
    np.testing.assert_array_equal(d, want[:3])
    assert dr.propose(ids, 0).size == 0
    with pytest.raises(ValueError, match="max_context"):
        ModelDrafter(net, max_context=0)


# ---------------------------------------------------------------------------
# slow: wider matrix
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_model_drafter_engine_full_acceptance(netm):
    """A spec engine whose ModelDrafter IS the target model: every
    draft verifies (acceptance rate 1.0 up to budget clamps) and
    output still equals the oracle."""
    cfg, net = netm
    rng = np.random.default_rng(2)
    ids = rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
    dr = ModelDrafter(net, max_context=P + 16, max_draft=4,
                      compute_dtype="float32")
    eng = ServingEngine(net, num_slots=1, prompt_len=P, max_cache_len=C,
                        steps_per_call=1, block_len=4, chunk_len=8,
                        drafter=dr, compute_dtype="float32")
    req = eng.submit(ids, max_new_tokens=12, spec_decode=4)
    eng.run(max_iters=200)
    np.testing.assert_array_equal(req.output,
                                  _oracle(net, ids, 8, 12))
    s = eng.stats()
    assert s["spec_acceptance_rate"] == 1.0
    assert s["spec_mean_accepted_len"] > 1.0


@pytest.mark.slow
def test_spec_engine_pallas_interpret_smoke(monkeypatch):
    """The spec scheduler drives the K-wide paged Pallas kernel
    (interpret mode) end to end: geometry chosen so the multi gate
    routes, and the route counter must record paged_multi_ok."""
    from paddle_tpu.observability.metrics import get_registry
    from paddle_tpu.ops.pallas import decode_attention as da
    monkeypatch.setattr(da, "pallas_enabled", lambda: True)
    cfg = models.LlamaConfig(
        vocab_size=128, hidden_size=256, intermediate_size=256,
        num_hidden_layers=1, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=64)
    net = models.LlamaForCausalLM(cfg)
    net.eval()
    rng = np.random.default_rng(7)
    route = get_registry().counter("pallas.decode_attention.route",
                                   labels=("decision", "reason"))
    base = route.value(decision="pallas", reason="paged_multi_ok")
    eng = ServingEngine(net, num_slots=2, prompt_len=8, max_cache_len=16,
                        steps_per_call=1, block_len=8,
                        compute_dtype="float32")
    pat = rng.integers(0, cfg.vocab_size, (2,)).astype(np.int32)
    reqs = [eng.submit(np.tile(pat, 4), max_new_tokens=6, spec_decode=3),
            eng.submit(rng.integers(0, cfg.vocab_size, (6,))
                       .astype(np.int32), max_new_tokens=4,
                       spec_decode=3)]
    done = eng.run(max_iters=200)
    assert len(done) == 2
    for r in reqs:
        assert r.output.shape == (r.max_new_tokens,)
        assert (r.output >= 0).all() and (r.output < cfg.vocab_size).all()
    assert route.value(decision="pallas",
                       reason="paged_multi_ok") > base


@pytest.mark.slow
def test_gpt_spec_parity():
    """The GPT verify path (learned positions, MHA): spec-decode engine
    output equals per-request greedy generate()."""
    paddle.seed(11)
    cfg = models.tiny_gpt_config()
    net = models.GPTForCausalLM(cfg)
    net.eval()
    rng = np.random.default_rng(12)
    pat = rng.integers(0, cfg.vocab_size, (2,)).astype(np.int32)
    rep = np.tile(pat, 4)
    eng = ServingEngine(net, num_slots=2, prompt_len=8, max_cache_len=32,
                        steps_per_call=2, block_len=4, chunk_len=4,
                        compute_dtype="float32")
    reqs = [(rep, 8, 8, 3),
            (rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32),
             6, 5, 2)]
    subs = [eng.submit(ids, max_new_tokens=mn, spec_decode=k)
            for ids, n, mn, k in reqs]
    assert len(eng.run(max_iters=500)) == 2
    for req, (ids, n, mn, _k) in zip(subs, reqs):
        padded = np.zeros((8,), np.int32)
        padded[:n] = ids
        want = np.asarray(net.generate(
            paddle.to_tensor(padded[None, :]), seq_lens=np.array([n]),
            max_new_tokens=mn, max_cache_len=32,
            compute_dtype="float32")._value)[0]
        np.testing.assert_array_equal(req.output, want)
