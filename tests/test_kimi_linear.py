"""The Kimi Linear hybrid decoder (``models/kimi_linear.py``) against its plain
reference (``benchmarks/reference/kimi_linear.py``) at a small size on the
CPU: the three forms of the delta rule, latent attention absorbed and
expanded, the expert layer's shares with the shared expert counted once, the
engine with slots reused, a preemption and poisoned state, and that a dense
model's arenas and programs are what they were.

Tolerances, with their reasons.  Everything here is float32 on both sides, so
a logit differs from the reference's only by the order of float32 sums and by
the chunkwise form's factored decays: the largest difference seen is 3e-6 on
logits of size 3; ``LOGIT_TOL`` 1e-4 leaves room for another backend's order
and is a thousand times under what the float8 reference moves a logit by
(tested to fail).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import kimi_linear as family
from benchmarks.reference import kimi_linear as ref
from paddle_tpu import models
from paddle_tpu.inference import ServingEngine
from paddle_tpu.models.generation import (LatentCacheSpec, SlotStateError,
                                          init_paged_latent_arena,
                                          init_slot_state)
from paddle_tpu.ops.pallas import kda

LOGIT_TOL = 1e-4
# the published keys at a toy size, as a configuration's file would hand them
# to the family; weights at 0.125 (a gain of one a matmul)
CFG = dict(
    model_type="kimi_linear", vocab_size=256, hidden_size=64,
    intermediate_size=128, moe_intermediate_size=32, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=4, head_dim=16,
    kv_lora_rank=32, q_lora_rank=None, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, mla_use_nope=True, rope_theta=10000,
    rope_scaling=None,
    linear_attn_config={"kda_layers": [1, 2, 4], "full_attn_layers": [3],
                        "head_dim": 16, "num_heads": 8,
                        "short_conv_kernel_size": 4},
    first_k_dense_replace=1, moe_layer_freq=1, num_experts=8,
    num_experts_per_token=2, num_shared_experts=1, moe_renormalize=True,
    moe_router_activation_func="sigmoid", num_expert_group=1, topk_group=1,
    use_grouped_topk=True, routed_scaling_factor=2.446, rms_norm_eps=1e-5,
    hidden_act="silu", tie_word_embeddings=False, num_nextn_predict_layers=0,
    model_max_length=1048576, kda_gate_rank=8, l2_norm_eps=1e-6,
    dtype="float32", initializer_range=0.125)


def seeded(cfg, seed=0):
    """(the program's model, the reference's weights) of ``cfg`` on the
    family's seeded leaves."""
    model = family.build(cfg)
    key = jax.random.PRNGKey(seed)
    leaves = [family.leaf(key, i, shape, kind, jnp.float32)
              for i, (_, shape, kind) in enumerate(family.leaf_specs(cfg))]
    for (_, p), v in zip(model.named_parameters(), leaves):
        p.set_value(v)
    return model, family.as_reference(cfg, leaves)


@pytest.fixture(scope="module")
def pair():
    return seeded(CFG)


@pytest.fixture(scope="module")
def model(pair):
    return pair[0]


@pytest.fixture(scope="module")
def weights(pair):
    return pair[1]


def reference_logits(weights, ids, mode=None, cfg=CFG):
    """Reference logits of every position of ``ids`` (padded to the bucket)."""
    n = len(ids)
    total = -(-n // 16) * 16
    padded = np.zeros((total,), np.int32)
    padded[:n] = ids
    return np.concatenate([
        np.asarray(ref.logits_and_margins(weights, cfg, padded, r, 16,
                                          mode)[0])
        for r in range(0, total, 16)])[:n]


def served_gap(weights, prompt, output):
    seq = np.concatenate([prompt, output]).astype(np.int32)
    rows = reference_logits(weights, seq)[len(prompt) - 1:-1]
    return float((rows.max(-1) - rows[np.arange(len(output)), output]).max())


# -- (a) the delta rule's three forms --------------------------------------------

def kda_inputs(t, b=2, h=4, d=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    l2 = lambda x: x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)  # noqa: E731
    return dict(
        q=l2(jax.random.normal(ks[0], (b, t, h, d))) * d ** -0.5,
        k=l2(jax.random.normal(ks[1], (b, t, h, d))),
        v=jax.random.normal(ks[2], (b, t, h, d)),
        g=-jnp.exp(jax.random.normal(ks[3], (b, t, h, d)) - 3.0),
        beta=jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h))),
        s0=0.1 * jax.random.normal(ks[5], (b, h, d, d)))


@pytest.mark.parametrize("t,n_valid", [(150, None), (64, None), (1, None),
                                       (150, (150, 97)), (70, (0, 1))],
                         ids=["two_and_a_bit_subchunks", "one_subchunk",
                              "one_row", "short_of_the_chunk",
                              "none_and_one_valid"])
def test_kda_chunk_is_the_recurrence(t, n_valid):
    x = kda_inputs(t)
    nv = None if n_valid is None else jnp.asarray(n_valid)
    o_r, s_r = kda.kda_recurrent(**x, n_valid=nv)
    o_c, s_c = kda.kda_chunk(**x, n_valid=nv)
    for b in range(2):
        n = t if n_valid is None else n_valid[b]
        np.testing.assert_allclose(o_c[b, :n], o_r[b, :n], atol=2e-6)
    np.testing.assert_allclose(s_c, s_r, atol=2e-6)


def test_kda_state_carries_over_chunks_that_are_no_multiple_of_the_subchunk():
    x = kda_inputs(150, seed=1)
    o_whole, s_whole = kda.kda_chunk(**x)
    cut = lambda a, lo, hi: {k: v[:, lo:hi] for k, v in a.items()  # noqa: E731
                             if k != "s0"}
    o1, s1 = kda.kda_chunk(**cut(x, 0, 70), s0=x["s0"])
    o2, s2 = kda.kda_chunk(**cut(x, 70, 150), s0=s1)
    np.testing.assert_allclose(jnp.concatenate([o1, o2], 1), o_whole,
                               atol=2e-6)
    np.testing.assert_allclose(s2, s_whole, atol=2e-6)


def test_kda_decode_steps_are_the_recurrence():
    """Eight tokens through ``kda_decode_step``'s ``jnp`` body, the state in
    an arena of three layers, against the recurrence; the chunk before them
    left the state."""
    x = kda_inputs(40, seed=2)
    head = {k: v[:, :32] for k, v in x.items() if k != "s0"}
    _, s = kda.kda_chunk(**head, s0=x["s0"])
    arena = jnp.zeros((3, 3) + s.shape[1:]).at[:2, 1].set(s)
    rows, live = jnp.arange(2), jnp.ones((2,), bool)
    outs = []
    for t in range(32, 40):
        o, arena = kda.kda_decode_step(
            arena, 1, rows, live, *(x[n][:, t] for n in "qkv"),
            x["g"][:, t], x["beta"][:, t])
        outs.append(o)
    o_r, s_r = kda.kda_recurrent(**x)
    np.testing.assert_allclose(jnp.stack(outs, 1), o_r[:, 32:], atol=2e-6)
    np.testing.assert_allclose(arena[:2, 1], s_r, atol=2e-6)


# -- (c), (f) the model against the reference ---------------------------------------

def test_forward_matches_the_reference(model, weights):
    ids = np.random.default_rng(0).integers(0, 256, (90,)).astype(np.int32)
    out = np.asarray(model(jnp.asarray(ids[None]))._value)[0]
    want = reference_logits(weights, ids)
    assert np.abs(out - want).max() < LOGIT_TOL
    # a lower precision is far outside the tolerance
    low = reference_logits(weights, ids, mode="fp8")
    assert np.abs(low - want).max() > 100 * LOGIT_TOL


def test_the_configuration_refuses_what_it_has_no_answer_for():
    for key, value in [("q_lora_rank", 64), ("rope_scaling", {"factor": 2}),
                       ("num_nextn_predict_layers", 1),
                       ("mla_use_nope", False)]:
        with pytest.raises(ValueError, match=key):
            models.tiny_kimi_linear_config(**{key: value})
    with pytest.raises(ValueError, match="once each"):
        models.tiny_kimi_linear_config(linear_attn_config={
            "kda_layers": [1, 2], "full_attn_layers": [2, 3], "head_dim": 16,
            "num_heads": 8, "short_conv_kernel_size": 4})
    with pytest.raises(ValueError, match="no answer"):
        family.build(dict(CFG, sliding_window=4096))


class Paged:
    """The arenas and tables an engine would hold, driven by hand so that the
    test sees logits and not tokens."""

    def __init__(self, model, num_slots=3, block_len=8, max_blocks=8):
        spec = model.kv_cache_spec()
        assert isinstance(spec, LatentCacheSpec) and spec == (1, 40)
        self.model, self.num_slots = model, num_slots
        nb = num_slots * max_blocks
        self.trash = nb
        self.arenas = init_paged_latent_arena(spec.layers, nb, block_len,
                                              spec.row, jnp.float32)
        assert self.arenas[0][0].shape == (nb + 1, block_len, 128)
        self.state = init_slot_state(model.slot_state_spec(), num_slots,
                                     jnp.bfloat16)
        assert [a.dtype for a in self.state] == [jnp.bfloat16, jnp.float32]
        # poison the state: a slot must never read what was there before
        self.state = [a.astype(jnp.float32) + 7.0 for a in self.state]
        self.tables = np.full((num_slots, max_blocks), self.trash, np.int32)
        for s in range(num_slots):      # scattered, slot-major
            self.tables[s] = np.arange(nb)[s::num_slots][:max_blocks]
        self._chunk = jax.jit(model.prefill_chunk)
        self._step = jax.jit(model.decode_step)

    def kvs(self, tables, extra):
        tables = jnp.asarray(tables)
        return [(a, tables) for a, in self.arenas] + \
            [dict(state=self.state, **extra)]

    def adopt(self, kvs):
        *kv, slot_state = kvs
        self.arenas = [(a,) for a, _ in kv]
        self.state = slot_state["state"]

    def prefill(self, slot, ids, chunk):
        n = len(ids)
        for start in range(0, n, chunk):
            part = np.zeros((1, chunk), np.int32)
            part[0, :min(chunk, n - start)] = ids[start:start + chunk]
            logits, kvs = self._chunk(
                jnp.asarray(part), jnp.asarray(start, jnp.int32),
                jnp.asarray(n, jnp.int32),
                self.kvs(self.tables[slot][None],
                         {"slot": jnp.asarray(slot, jnp.int32)}))
            self.adopt(kvs)
        return np.asarray(logits)[0]

    def decode(self, tokens, lens, live):
        tables = np.where(np.asarray(live)[:, None], self.tables, self.trash)
        logits, kvs = self._step(
            jnp.asarray(tokens, jnp.int32), jnp.asarray(lens, jnp.int32),
            self.kvs(tables, {"counters": self.model.init_block_counters()}))
        self.adopt(kvs)
        return np.asarray(logits), np.asarray(kvs[-1]["counters"])


@pytest.mark.parametrize("prompt_len", [40, 1],
                         ids=["two_and_a_half_chunks", "a_single_row"])
def test_chunked_prefill_then_decode_matches_the_reference(model, weights,
                                                           prompt_len):
    """Expanded prefill in chunks of 16 through the latent arena and the
    state arenas, then eight absorbed decode steps in slot 1 of 3 beside a
    frozen slot: every logit row against the reference's plain forward over
    prompt and continuation."""
    rng = np.random.default_rng(prompt_len)
    paged = Paged(model)
    seq = list(rng.integers(0, 256, prompt_len))
    rows = [paged.prefill(1, np.asarray(seq), 16)]
    # slot 2 is mid-prefill: frozen in the decode step, its state must stay
    paged.prefill(2, rng.integers(0, 256, 16), 16)
    frozen = [np.asarray(a[2]) for a in paged.state]
    for _ in range(8):
        seq.append(int(np.argmax(rows[-1])))
        logits, counters = paged.decode([0, seq[-1], 5],
                                        [0, len(seq) - 1, 16],
                                        [False, True, False])
        rows.append(logits[1])
    want = reference_logits(weights, np.asarray(seq))[prompt_len - 1:]
    assert np.abs(np.stack(rows) - want).max() < LOGIT_TOL
    for a, was in zip(paged.state, frozen):
        np.testing.assert_array_equal(np.asarray(a[2]), was)
    # one live row, three expert layers, two experts a token
    assert counters[:-2].sum() == 6 and counters[-2] == 3 \
        and counters[-1] == 6


# -- (d) the shares add up -----------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """Two chips' shares of a layer, ``held=(0, n/2)`` and ``(n/2, n/2)``,
    with the shared expert counted once, are the uncut reference layer."""
    whole, w_whole = seeded(CFG, seed=3)
    lp = w_whole["layers"][1]
    u = jax.random.normal(jax.random.PRNGKey(5), (24, 64))
    want, _ = ref.expert_ffn(lp, u, ref._routing(CFG), None)
    shared = ref.swiglu_ffn(u, lp["sg"], lp["su"], lp["sd"], None)
    total = -shared         # both shares compute it: counted once
    for first in (0, 4):
        share, _ = seeded(dict(CFG, num_experts=4, router_experts=8,
                               first_expert_held=first), seed=3)
        moe = share.model.layers[1].block_sparse_moe
        assert moe.experts.held == (first, 4)
        # a share's planes are its own draw; give it the whole layer's
        full = whole.model.layers[1].block_sparse_moe
        for name in ("w1", "w3", "w2"):
            getattr(moe.experts, name).set_value(
                getattr(full.experts, name)._value[first:first + 4])
        for name in ("router", "expert_bias"):
            getattr(moe.experts, name).set_value(
                getattr(full.experts, name)._value)
        for name in ("gate_proj", "up_proj", "down_proj"):
            getattr(moe.shared_experts, name).weight.set_value(
                getattr(full.shared_experts, name).weight._value)
        from paddle_tpu.core.tensor import Tensor
        y, _ = moe.apply(Tensor(u[None]), None)
        total = total + y._value[0]
    np.testing.assert_allclose(total, want, atol=1e-5)


# -- (e) the engine ---------------------------------------------------------------------

def engine(model, **kw):
    geo = dict(num_slots=3, prompt_len=48, max_cache_len=80, block_len=8,
               num_blocks=40, chunk_len=16, steps_per_call=4,
               compute_dtype="float32", host_cache_blocks=0)
    geo.update(kw)
    return ServingEngine(model, **geo)


def test_engine_serves_generates_tokens_over_reused_slots(model):
    eng = engine(model)
    rng = np.random.default_rng(4)
    shapes = [(40, 9), (1, 5), (17, 12), (33, 7), (5, 10)]
    sent = [(ids, eng.submit(ids, max_new_tokens=m)) for ids, m in
            ((rng.integers(0, 256, (n,)).astype(np.int32), m)
             for n, m in shapes)]
    eng.run()
    for ids, r in sent:
        want = np.asarray(model.generate(jnp.asarray(ids[None]),
                                         max_new_tokens=r.max_new_tokens)
                          ._value)[0]
        np.testing.assert_array_equal(np.asarray(r.output), want)
    described = eng.engine_spec()
    assert described["kv_layout"] == "latent"
    assert described["kv_row_bytes"] == 128 * 4      # 40 values rest as 128
    # tails [4, 3 kda, 3 rows, 3 x 128] and states [4, 3, 8, 16, 16], float32
    assert described["slot_state_bytes"] == \
        4 * 3 * 3 * 384 * 4 + 4 * 3 * 8 * 16 * 16 * 4
    assert eng.stats()["kv_arena_bytes"] == 41 * 8 * 128 * 4


def test_engine_carries_both_states_through_a_preemption(model, weights):
    eng = engine(model)
    rng = np.random.default_rng(3)
    shapes = [(40, 9), (1, 5), (17, 12), (33, 7), (5, 20), (16, 3), (48, 14)]
    sent = [(ids, eng.submit(ids, max_new_tokens=m)) for ids, m in
            ((rng.integers(0, 256, (n,)).astype(np.int32), m)
             for n, m in shapes)]
    victim = None
    for _ in range(400):
        eng.step()
        if victim is None:
            decoding = [r for r in eng._slots if r is not None
                        and r.state == "decode" and len(r.tokens) >= 2
                        and r.remaining > 4]
            if decoding:
                victim = decoding[0]
                before = [np.asarray(a[victim.slot]) for a in eng._slot_state]
                assert eng._preempt(victim)
                assert len(victim.swap.slot_state) == 2
                for got, want in zip(victim.swap.slot_state, before):
                    np.testing.assert_array_equal(got, want)
        if all(r.state == "finished" for _, r in sent):
            break
    assert victim is not None
    stats = eng.stats()
    assert stats["preemptions"] == 1 and stats["preempt_resumes"] == 1
    for ids, r in sent:
        assert r.state == "finished" and len(r.output) == r.max_new_tokens
        assert served_gap(weights, ids, r.output) < LOGIT_TOL


@pytest.mark.parametrize("reset", [True, False], ids=["sound", "not_reset"])
def test_a_finished_slots_state_is_poisoned_and_never_read(
        model, weights, monkeypatch, reset):
    """A slot whose request finished in a decode program leaves NaN in both
    of its state rows (the matrix state's in one key row a head); a sound
    engine never reads them, so every arena stays finite but for them and
    the tokens are the reference's.  An engine that loses the reset serves
    garbage."""
    if not reset:
        monkeypatch.setattr(
            models.KimiLinearForCausalLM, "_chunk_state",
            staticmethod(lambda arena, slot, start: arena[slot][None]))
    eng = engine(model, num_slots=2)
    rng = np.random.default_rng(11)
    sent = [(ids, eng.submit(ids, max_new_tokens=m)) for ids, m in
            ((rng.integers(0, 256, (n,)).astype(np.int32), m)
             for n, m in [(20, 6), (9, 11), (33, 5), (4, 9), (18, 7)])]
    eng.run()
    tails, states = eng._slot_state
    assert np.isnan(np.asarray(tails[:2])).all()
    assert np.isnan(np.asarray(states[:2, :, :, 0])).all()
    gaps = [served_gap(weights, ids, r.output) for ids, r in sent]
    if reset:
        assert all(np.isfinite(np.asarray(a[2])).all()      # the trash row
                   for a in eng._slot_state)
        assert np.isfinite(np.asarray(states[:2, :, :, 1:])).all()
        assert all(np.isfinite(np.asarray(a)).all() for a in eng._arenas)
        assert max(gaps) < LOGIT_TOL
    else:
        assert gaps[0] < LOGIT_TOL and min(gaps[2:]) > 1.0, gaps


@pytest.mark.parametrize("feature,kw", [
    ("HostTier", dict(host_cache_blocks=8)),
    ("handoff", dict(role="prefill")),
    ("speculative", dict(drafter=object())),
])
def test_what_moves_blocks_without_the_state_refuses_the_model(model, feature,
                                                               kw):
    geo = dict(num_slots=2, prompt_len=16, max_cache_len=32, block_len=8,
               compute_dtype="float32", host_cache_blocks=0)
    geo.update(kw)
    with pytest.raises(SlotStateError, match="kda_conv_tail, kda_state"):
        ServingEngine(model, **geo)
    with pytest.raises(SlotStateError):
        model.verify_step(None, None, None, None)


def test_a_latent_cache_has_no_int8_form(model):
    with pytest.raises(ValueError, match="latent cache"):
        engine(model, kv_cache_dtype="int8")


# -- (g) a dense model is served as it was ---------------------------------------------

def test_a_dense_models_arenas_and_programs_are_unchanged():
    """The arenas and the donation lists of a dense model do not know the
    latent cache or the state arenas: pairs of ``Hkv x D`` arenas a layer and
    nothing behind them.  (That its lowered chunk and block programs are the
    parent's, text for text, is recorded by hand:
    ``benchmarks/records/pr37/dense_hlo_identity.txt``.)"""
    import paddle_tpu as paddle
    paddle.seed(0)
    dense = models.LlamaForCausalLM(models.tiny_llama_config())
    eng = ServingEngine(dense, num_slots=2, prompt_len=16, max_cache_len=32,
                        block_len=8, chunk_len=16, steps_per_call=4,
                        compute_dtype="float32", host_cache_blocks=0)
    n_layers, hkv, d = dense.kv_cache_spec()
    assert len(eng._arenas) == 2 * n_layers and eng._slot_state == []
    from paddle_tpu.ops.pallas.decode_attention import paged_arena_shape
    assert {a.shape for a in eng._arenas} == \
        {paged_arena_shape(2 * 4 + 1, hkv, 8, d)}
    assert eng._donate == tuple(range(6, 6 + 2 * n_layers))
    assert eng._donate_blk == tuple(range(7, 7 + 2 * n_layers))
    described = eng.engine_spec()
    assert described["kv_layout"] == "kv" and \
        described["kv_row_bytes"] == 2 * hkv * d * 4 * n_layers
    eng.submit(np.arange(1, 12, dtype=np.int32), max_new_tokens=6)
    eng.run()
    assert eng.stats()["slot_state_bytes"] == 0
