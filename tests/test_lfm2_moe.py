"""The sparse hybrid decoder (``models/lfm2.py``) against its plain reference
(``benchmarks/reference/lfm2_moe.py``) at a small size on the CPU: the whole
forward, chunked prefill and decoding through the paged cache and the per-slot
state arena, the engine with slots reused and a preemption, the expert layer's
shares, and every refusal of what cannot carry the state.

Tolerances, with their reasons.  Everything here is float32 on both sides, so
a logit differs from the reference's only by the order of float32 sums: the
largest difference seen is 5e-6 on logits of size 3; ``LOGIT_TOL`` 1e-4 leaves
room for another backend's order and is five hundred times under the least
that a lower precision moves a logit (the router computed in bfloat16 moves
one by 0.05 and more, the float8 reference by 0.1: both are tested to fail).
"""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import lfm2_moe as ref
from paddle_tpu import models, nn
from paddle_tpu.inference import ServingEngine
from paddle_tpu.inference.llm import build_weight_quant_plan
from paddle_tpu.models.generation import (SlotStateError, init_paged_kv_arena,
                                          init_slot_state)

LOGIT_TOL = 1e-4
REF_CFG = dict(num_attention_heads=4, num_key_value_heads=2, hidden_size=64,
               norm_eps=1e-5, rope_parameters={"rope_theta": 1e6},
               num_experts_per_tok=2, norm_topk_prob=True,
               routed_scaling_factor=1.0)


def seeded_model(seed=0, **kw):
    """Hidden 64, six layers (conv, conv, attention, conv, conv, attention),
    one dense, 8 experts top-2; weights at std 0.125 (a gain of one a matmul,
    so the layers and not the tied embedding make the logits), taps at 0.5,
    a non-zero expert bias."""
    model = models.Lfm2MoeForCausalLM(models.tiny_lfm2_config(**kw))
    model.eval()
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        shape = tuple(p.shape)
        if "norm" in name:
            v = np.ones(shape)
        elif name.endswith("conv_weight"):
            v = rng.normal(0, 0.5, shape)
        elif name.endswith("expert_bias"):
            v = rng.normal(0, 0.1, shape)
        else:
            v = rng.normal(0, 0.125, shape)
        p.set_value(jnp.asarray(v, jnp.float32))
    return model


def reference_weights(model, held=None):
    f = lambda p: jnp.asarray(p._value, jnp.float32)  # noqa: E731
    layers = []
    for l in model.lfm2.layers:
        d = {"op_norm": f(l.operator_norm.weight),
             "ffn_norm": f(l.ffn_norm.weight)}
        if l.kind == "conv":
            d.update(in_proj=f(l.conv.in_proj.weight),
                     taps=f(l.conv.conv_weight),
                     out_proj=f(l.conv.out_proj.weight))
        else:
            a = l.self_attn
            d.update(wq=f(a.q_proj.weight), wk=f(a.k_proj.weight),
                     wv=f(a.v_proj.weight), wo=f(a.out_proj.weight),
                     q_norm=f(a.q_layernorm.weight),
                     k_norm=f(a.k_layernorm.weight))
        ff = l.feed_forward
        if l.sparse:
            first, count = held or ff.held
            cut = slice(first - ff.held[0], first - ff.held[0] + count)
            d.update(router=f(ff.router), expert_bias=f(ff.expert_bias),
                     held=(first, count), eg=ff.w1._value[cut],
                     eu=ff.w3._value[cut], ed=ff.w2._value[cut])
        else:
            d.update(wg=f(ff.w1.weight), wu=f(ff.w3.weight),
                     wd=f(ff.w2.weight))
        layers.append(d)
    return {"embed": f(model.lfm2.embed_tokens.weight), "layers": layers,
            "norm": f(model.lfm2.embedding_norm.weight)}


def reference_logits(weights, ids, mode=None):
    """Reference logits of every position of ``ids`` (padded to the bucket)."""
    n = len(ids)
    total = -(-n // 16) * 16
    padded = np.zeros((total,), np.int32)
    padded[:n] = ids
    return np.concatenate([
        np.asarray(ref.logits_and_margins(weights, REF_CFG, padded, r, 16,
                                          mode)[0])
        for r in range(0, total, 16)])[:n]


@pytest.fixture(scope="module")
def model():
    return seeded_model()


@pytest.fixture(scope="module")
def weights(model):
    return reference_weights(model)


def test_forward_matches_the_reference(model, weights):
    ids = np.random.default_rng(1).integers(0, 256, (2, 40)).astype(np.int32)
    out = np.asarray(model(jnp.asarray(ids))._value)
    for row in range(2):
        want = reference_logits(weights, ids[row])
        assert np.abs(out[row] - want).max() < LOGIT_TOL
        # one precision down is another model: the comparison sees it
        assert np.abs(reference_logits(weights, ids[row], "fp8")
                      - want).max() > 100 * LOGIT_TOL


def test_near_ties_of_the_routing_are_left_out_and_counted(model, weights,
                                                          monkeypatch,
                                                          capsys):
    """``sequence_logits`` (what the benchmark's check calls) returns zeros
    where some layer's routing margin is under ``ROUTING_EPS`` and the rows
    as they are elsewhere, counts over the rows given (those whose next token
    ``ids`` holds; the harness pads with zeros past them), prints what it
    kept, and never leaves out more than ``MAX_LEFT_OUT`` of the rows given
    so far: the smallest margins go first."""
    monkeypatch.setattr(ref, "ROUTING_EPS", 0.02)
    monkeypatch.setattr(ref, "TALLY", {"kept": 0, "given": 0})
    ids = np.random.default_rng(2).integers(1, 256, (32,)).astype(np.int32)
    ids[21:] = 0                    # 12 served tokens behind position 8
    assert ref.rows_given(ids, 8, 16) == 12
    raw, margin = ref.logits_and_margins(weights, REF_CFG, ids, 8, 16)
    raw, margin = np.asarray(raw), np.asarray(margin)
    out = margin < 0.02
    assert 2 < out[:12].sum() < 10  # this sequence has both kinds of row
    got = np.asarray(ref.sequence_logits(weights, REF_CFG, ids, 8, 16))
    np.testing.assert_array_equal(got[~out], raw[~out])
    assert not got[out].any()
    kept = 12 - out[:12].sum()
    assert f"kept={kept} of 12 rows given" in capsys.readouterr().out
    assert ref.TALLY == {"kept": kept, "given": 12}
    # a control's rows are as they are: only its pick is read
    low = ref.sequence_logits(weights, REF_CFG, ids, 8, 16, mode="int8")
    assert np.asarray(low).all()
    # the cap is over the calls so far: 24 rows given, 4 may be left out, of
    # which an earlier call took 2
    monkeypatch.setattr(ref, "MAX_LEFT_OUT", 4 / 24)
    ref.TALLY.update(kept=10, given=12)
    got = np.asarray(ref.sequence_logits(weights, REF_CFG, ids, 8, 16))
    gone = ~got[:12].any(axis=-1)
    assert gone.sum() == 2 and set(np.flatnonzero(gone)) == \
        set(np.argsort(margin[:12], kind="stable")[:2])
    assert ref.TALLY == {"kept": 20, "given": 24}


def test_router_in_bfloat16_fails_the_tolerance(model, weights, monkeypatch):
    """The variant that computes the router's scores in bfloat16: a near-tie
    flips an expert, and a logit moves by far more than ``LOGIT_TOL``."""
    monkeypatch.setattr(nn.RoutedExperts, "score_dtype", jnp.bfloat16)
    ids = np.random.default_rng(1).integers(0, 256, (40,)).astype(np.int32)
    out = np.asarray(model(jnp.asarray(ids[None]))._value)[0]
    assert np.abs(out - reference_logits(weights, ids)).max() > 10 * LOGIT_TOL


# -- chunked prefill, then decoding, through the paged cache and the state arena ----

class Paged:
    """The arenas and tables an engine would hold, driven by hand so that the
    test sees logits and not tokens."""

    def __init__(self, model, num_slots=3, block_len=8, max_blocks=8):
        n_kv, hkv, d = model.kv_cache_spec()
        self.model, self.block_len = model, block_len
        self.num_slots, self.max_blocks = num_slots, max_blocks
        nb = num_slots * max_blocks
        self.trash = nb
        self.arenas = init_paged_kv_arena(n_kv, nb, block_len, hkv, d,
                                          jnp.float32)
        self.state = init_slot_state(model.slot_state_spec(), num_slots,
                                     jnp.float32)
        # poison the state: a slot must never read what was there before
        self.state = [a + 7.0 for a in self.state]
        self.tables = np.full((num_slots, max_blocks), self.trash, np.int32)
        for s in range(num_slots):      # scattered, slot-major
            self.tables[s] = np.arange(nb)[s::num_slots][:max_blocks]

    def kvs(self, tables, extra):
        tables = jnp.asarray(tables)
        return [(k, v, tables) for k, v in self.arenas] + \
            [dict(state=self.state, **extra)]

    def adopt(self, kvs):
        *kv, slot_state = kvs
        self.arenas = [(k, v) for k, v, _ in kv]
        self.state = slot_state["state"]

    def prefill(self, slot, ids, chunk):
        n = len(ids)
        for start in range(0, n, chunk):
            part = np.zeros((1, chunk), np.int32)
            part[0, :min(chunk, n - start)] = ids[start:start + chunk]
            logits, kvs = self.model.prefill_chunk(
                jnp.asarray(part), jnp.asarray(start, jnp.int32),
                jnp.asarray(n, jnp.int32),
                self.kvs(self.tables[slot][None],
                         {"slot": jnp.asarray(slot, jnp.int32)}))
            self.adopt(kvs)
        return np.asarray(logits)[0]

    def decode(self, tokens, lens, live):
        tables = np.where(np.asarray(live)[:, None], self.tables, self.trash)
        logits, kvs = self.model.decode_step(
            jnp.asarray(tokens, jnp.int32), jnp.asarray(lens, jnp.int32),
            self.kvs(tables, {"counters": self.model.init_block_counters()}))
        self.adopt(kvs)
        return np.asarray(logits), np.asarray(kvs[-1]["counters"])


@pytest.mark.parametrize("prompt_len", [40, 1],
                         ids=["two_and_a_half_chunks", "a_single_row"])
def test_chunked_prefill_then_decode_matches_the_reference(model, weights,
                                                           prompt_len):
    """A prompt of 2.5 chunks of 16, and one of a single row (its state
    after the chunk still holds a row of the zeros it started from); then
    eight decode steps in slot 1 of 3 beside a frozen slot.  Every logit row
    against the reference's full forward over prompt and continuation."""
    rng = np.random.default_rng(prompt_len)
    paged = Paged(model)
    seq = list(rng.integers(0, 256, prompt_len))
    rows = [paged.prefill(1, np.asarray(seq), 16)]
    # slot 2 is mid-prefill: frozen in the decode step, its state must stay
    paged.prefill(2, rng.integers(0, 256, 16), 16)
    frozen = np.asarray(paged.state[0][2])
    for _ in range(8):
        seq.append(int(np.argmax(rows[-1])))
        lens = [0, len(seq) - 1, 16]
        logits, counters = paged.decode([0, seq[-1], 5], lens,
                                        [False, True, False])
        rows.append(logits[1])
    want = reference_logits(weights, np.asarray(seq))[prompt_len - 1:]
    assert np.abs(np.stack(rows) - want).max() < LOGIT_TOL
    np.testing.assert_array_equal(np.asarray(paged.state[0][2]), frozen)
    # one live row, five expert layers, two experts a token
    assert counters[:-2].sum() == 10 and counters[-2] == 5 \
        and counters[-1] == 10


# -- the engine -------------------------------------------------------------------

def served_gap(weights, prompt, output):
    """The widest gap by which a served token's logit lies under the
    reference's best, over the request's tokens."""
    seq = np.concatenate([prompt, output]).astype(np.int32)
    rows = reference_logits(weights, seq)[len(prompt) - 1:-1]
    return float((rows.max(-1) - rows[np.arange(len(output)), output]).max())


def engine(model, **kw):
    geo = dict(num_slots=3, prompt_len=48, max_cache_len=80, block_len=8,
               num_blocks=40, chunk_len=16, steps_per_call=4,
               compute_dtype="float32", host_cache_blocks=0)
    geo.update(kw)
    return ServingEngine(model, **geo)


def test_engine_reuses_slots_and_carries_state_through_a_preemption(
        model, weights):
    """More requests than slots (every slot is reused, by prompts of 2.5
    chunks and of one row, at mixed fill), and one request preempted in
    mid-decode and resumed: its state rows travel with its swap record."""
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
                state_before = np.asarray(eng._slot_state[0][victim.slot])
                assert eng._preempt(victim)
                assert victim.swap.slot_state is not None
                np.testing.assert_array_equal(victim.swap.slot_state[0],
                                              state_before)
        if all(r.state == "finished" for _, r in sent):
            break
    assert victim is not None
    stats = eng.stats()
    assert stats["preemptions"] == 1 and stats["preempt_resumes"] == 1
    assert stats["slot_state_bytes"] == 4 * 4 * 2 * 64 * 4
    for ids, r in sent:
        assert r.state == "finished" and len(r.output) == r.max_new_tokens
        assert served_gap(weights, ids, r.output) < LOGIT_TOL


@pytest.mark.parametrize("reset,depth", [(True, 1), (True, 3), (False, 1)],
                         ids=["sound", "sound_depth3", "not_reset"])
def test_a_finished_slots_state_is_poisoned_and_never_read(
        model, weights, monkeypatch, reset, depth):
    """A slot whose request finished in a decode program leaves NaN in its
    state rows; a sound engine never reads them (the next prompt starts
    from zeros, the decode programs skip rows that enter done), so keys,
    values and tokens stay what they were.  An engine that loses the reset
    serves garbage from them, far outside any limit: a stale tail alone
    would move a logit by less than bfloat16's noise does."""
    if not reset:
        monkeypatch.setattr(
            models.Lfm2MoeForCausalLM, "_chunk_tails",
            staticmethod(lambda arena, slot, start: arena[slot][None]))
    # at depth 3 a row that finished on the device rides further blocks,
    # already poisoned, before the host has seen its finish
    eng = engine(model, num_slots=2, async_depth=depth)
    rng = np.random.default_rng(11)
    sent = [(ids, eng.submit(ids, max_new_tokens=m)) for ids, m in
            ((rng.integers(0, 256, (n,)).astype(np.int32), m)
             for n, m in [(20, 6), (9, 11), (33, 5), (4, 9), (18, 7)])]
    eng.run()
    state, = eng._slot_state
    # both slots' last occupants finished in a decode program
    assert np.isnan(np.asarray(state[:2])).all()
    gaps = [served_gap(weights, ids, r.output) for ids, r in sent]
    if reset:
        assert np.isfinite(np.asarray(state[2])).all()      # the trash row
        assert all(np.isfinite(np.asarray(a)).all() for a in eng._arenas)
        assert max(gaps) < LOGIT_TOL
    else:
        # the first occupant started from the arena's zeros; every slot's
        # later occupant started from NaN, which also spreads through the
        # blocks it wrote
        assert gaps[0] < LOGIT_TOL and min(gaps[2:]) > 1.0, gaps


def test_a_rider_that_ends_inside_a_block_leaves_poison_and_a_clean_slot(
        model, weights):
    """A rider whose budget ends at the second step of a four-step block
    (its neighbour is owed all four, so the block runs whole) takes two
    tokens, and its state rows are poisoned by the block it froze in; the
    prompt that takes the slot next serves the reference's tokens."""
    from paddle_tpu.observability.flightrec import FlightRecorder
    rec = FlightRecorder()
    eng = engine(model, num_slots=2, flight_recorder=rec)
    rng = np.random.default_rng(36)
    sent = [(ids, eng.submit(ids, max_new_tokens=m)) for ids, m in
            ((rng.integers(0, 256, (n,)).astype(np.int32), m)
             for n, m in [(20, 15), (9, 3), (12, 8)])]
    (_, long_), (_, short), (_, nxt) = sent
    while short.state != "finished":
        eng.step()
    slot = next(e.attrs["slot"] for e in rec.events()
                if e.kind == "admit" and e.request == short.request_id)
    last = {e.request: e.attrs["steps"] for e in rec.events()
            if e.kind == "decode_block"}
    assert (last[short.request_id], last[long_.request_id]) == (2, 4)
    assert len(short.output) == 3 and long_.state == "decode"
    state, = eng._slot_state
    assert np.isnan(np.asarray(state[slot])).all()
    assert np.isfinite(np.asarray(state[1 - slot])).all()
    eng.run()
    assert nxt.state == "finished" and [e.attrs["slot"] for e in rec.events()
                                        if e.kind == "admit" and
                                        e.request == nxt.request_id] == [slot]
    assert all(np.isfinite(np.asarray(a)).all() for a in eng._arenas)
    for ids, r in sent:
        assert len(r.output) == r.max_new_tokens
        assert served_gap(weights, ids, r.output) < LOGIT_TOL


def test_expert_load_counters_reach_the_registry(model):
    from paddle_tpu.observability.metrics import MetricsRegistry
    reg = MetricsRegistry()
    eng = engine(model, registry=reg)
    eng.submit(np.arange(5, dtype=np.int32), max_new_tokens=9)
    eng.run()
    snap = reg.snapshot()
    rows = sum(snap["moe.expert_tokens"]["values"].values())
    pairs = snap["moe.layer_steps"]["values"][""]
    # eight decode steps of one live row through five expert layers
    assert pairs == 5 * eng.stats()["decode_steps"]
    assert rows == 2 * 5 * 8
    assert snap["moe.experts_touched"]["values"][""] == rows


# -- the expert layer ----------------------------------------------------------------

def expert_layer(held=None, seed=0, **kw):
    layer = nn.RoutedExperts(64, 32, 8, kw.pop("top_k", 2), held=held, **kw)
    rng = np.random.default_rng(seed)
    full = {"router": rng.normal(0, 0.125, (64, 8)),
            "expert_bias": rng.normal(0, 0.1, (8,)),
            "w1": rng.normal(0, 0.125, (8, 64, 32)),
            "w3": rng.normal(0, 0.125, (8, 64, 32)),
            "w2": rng.normal(0, 0.125, (8, 32, 64))}
    first, count = layer.held
    for name, v in full.items():
        if name in ("w1", "w3", "w2"):
            v = v[first:first + count]
        getattr(layer, name).set_value(jnp.asarray(v, jnp.float32))
    return layer


def reference_experts(layer, u, held, top_k=2):
    first, count = held
    lo = first - layer.held[0]
    lp = {"router": layer.router._value, "held": held,
          "expert_bias": layer.expert_bias._value,
          "eg": layer.w1._value[lo:lo + count],
          "eu": layer.w3._value[lo:lo + count],
          "ed": layer.w2._value[lo:lo + count]}
    return np.asarray(ref.expert_ffn(lp, u, (top_k, True, 1.0), None)[0])


def test_the_shares_add_up_to_the_uncut_layer():
    """``held = (0,2), (2,2), (4,2), (6,2)``: each share equals the
    reference given the same share, and the four add up to the uncut
    reference's layer output (there is no shared expert to count once)."""
    u = jnp.asarray(np.random.default_rng(4).normal(0, 1, (48, 64)),
                    jnp.float32)
    whole = expert_layer()
    uncut = reference_experts(whole, u, (0, 8))
    assert np.abs(np.asarray(whole.apply(u)[0]) - uncut).max() < LOGIT_TOL
    total = np.zeros_like(uncut)
    for first in (0, 2, 4, 6):
        share, load = expert_layer(held=(first, 2)).apply(u)
        want = reference_experts(whole, u, (first, 2))
        assert np.abs(np.asarray(share) - want).max() < LOGIT_TOL
        assert np.abs(want).max() > 0.01
        # every share routes over all eight experts
        assert int(load[:-1].sum()) == 48 * 2
        total += np.asarray(share)
    assert np.abs(total - uncut).max() < LOGIT_TOL


def test_no_token_is_dropped_when_every_token_picks_one_expert():
    """A bias that sends every row to experts 3 and 5: two groups as long as
    the batch and six empty ones; the output is still the reference's."""
    layer = expert_layer()
    bias = np.full((8,), -10.0, np.float32)
    bias[[3, 5]] = 10.0
    layer.expert_bias.set_value(jnp.asarray(bias))
    u = jnp.asarray(np.random.default_rng(5).normal(0, 1, (200, 64)),
                    jnp.float32)
    out, load = layer.apply(u)
    assert load.tolist() == [0, 0, 0, 200, 0, 200, 0, 0, 2]
    assert np.abs(np.asarray(out)
                  - reference_experts(layer, u, (0, 8))).max() < LOGIT_TOL


def test_held_has_to_be_a_range_of_the_experts():
    with pytest.raises(ValueError, match="held"):
        nn.RoutedExperts(64, 32, 8, 2, held=(6, 4))


# -- what cannot carry the state refuses it by name ------------------------------------

def test_the_prefix_cache_is_off_and_says_so(model, caplog):
    with caplog.at_level(logging.INFO, logger="paddle_tpu.inference.serving"):
        eng = engine(model)
    assert "conv_tail" in caplog.text and "prefix_cache_mode='none'" in caplog.text
    assert eng.prefix_cache_mode == "none" and eng._radix is None
    stats = eng.stats()
    assert stats["prefix_cache_disabled"] is True
    ids = np.arange(24, dtype=np.int32)
    for _ in range(2):          # the same prompt twice: no hit to be had
        eng.submit(ids, max_new_tokens=2)
        eng.run()
    assert eng.stats()["prefix_hit_rate"] == 0.0
    # a dense engine is asked nothing and says nothing
    dense = ServingEngine(models.LlamaForCausalLM(models.tiny_llama_config()),
                          num_slots=2, prompt_len=16)
    assert dense.stats()["prefix_cache_disabled"] is False
    assert dense.stats()["slot_state_bytes"] == 0


@pytest.mark.parametrize("feature", [
    "submit_spec_decode", "drafter", "verify_step", "host_tier",
    "role_prefill", "role_decode", "migration_parcel", "mesh_mp2"])
def test_what_cannot_carry_the_state_refuses_by_name(model, feature):
    from paddle_tpu.inference.speculative import NGramDrafter
    with pytest.raises(SlotStateError) as err:
        if feature == "submit_spec_decode":
            engine(model).submit(np.arange(4, dtype=np.int32), spec_decode=2)
        elif feature == "drafter":
            engine(model, drafter=NGramDrafter())
        elif feature == "verify_step":
            model.verify_step(None, None, None, None)
        elif feature == "host_tier":
            engine(model, host_cache_blocks=8)
        elif feature == "role_prefill":
            engine(model, role="prefill")
        elif feature == "role_decode":
            engine(model, role="decode")
        elif feature == "migration_parcel":
            engine(model).migrate_in(
                np.arange(4, dtype=np.int32), parcel={
                    "key": 0, "n_blocks": 1, "tok": 1, "lens": 4,
                    "phase": "decode"})
        elif feature == "mesh_mp2":
            from paddle_tpu.distributed.topology import build_mesh
            engine(model, mesh=build_mesh(mp=2, devices=jax.devices()[:2]))
    assert "conv_tail" in str(err.value) and "Lfm2MoeForCausalLM" in str(err.value)


@pytest.mark.parametrize("dtype", ["int8", "int4"])
def test_weight_quantisation_refuses_expert_planes(model, dtype):
    with pytest.raises(ValueError, match="expert planes"):
        build_weight_quant_plan(model, dtype)
    with pytest.raises(ValueError, match="expert planes"):
        engine(model, weight_dtype=dtype)


def test_a_migration_without_a_parcel_recomputes(model, weights):
    """The recompute-from-prompt path moves no bytes and so is served."""
    eng = engine(model)
    ids = np.arange(7, 30, dtype=np.int32)
    r = eng.migrate_in(ids, max_new_tokens=6)
    eng.run()
    assert served_gap(weights, ids, r.output) < LOGIT_TOL


# -- the dense families see no change -----------------------------------------------------

# tokens and donated arguments of this trace on PR 33's parent commit
# (7141b4d), where the arenas' bytes were also identical.  Since PR 36 the
# plan runs the whole block through budget finishes: the same blocks go to
# the same slots a step sooner (the tables after five steps are that
# commit's after six), and rows frozen behind their budget write their own
# dead block or the trash block, so the arenas' absolute sums are this
# schedule's; floats to 1e-6 so that another host's sum order does not fail
# a byte-identical program
PARENT_DENSE_TRACE = {
    "tokens": [[7, 208, 145, 85, 129, 251, 44, 223, 78],
               [226, 166, 166, 166, 166],
               [14, 154, 246, 100, 211, 26, 146, 192, 52, 57, 153, 226],
               [213, 223, 252, 208, 18, 118, 187],
               [249, 200, 216, 74, 246, 218, 126, 184, 151, 89],
               [188, 75, 69], [12, 188, 183, 88, 199, 185]],
    "tables_mid": [[4, 3, 2, 12, 12], [9, 10, 12, 12, 12], [5, 6, 7, 8, 12]],
    "arena_abs": [2983.379067473463, 2937.021637606551, 2973.299260141095,
                  3118.6366175461735],
    "donate": [[6, 7, 8, 9], [7, 8, 9, 10]]}


def test_a_dense_engine_is_what_it_was_on_the_parent():
    import paddle_tpu as paddle
    paddle.seed(7)
    m = models.LlamaForCausalLM(models.tiny_llama_config())
    eng = ServingEngine(m, num_slots=3, prompt_len=24, max_cache_len=40,
                        block_len=8, num_blocks=12, chunk_len=8,
                        steps_per_call=4, compute_dtype="float32",
                        host_cache_blocks=0)
    rng = np.random.default_rng(5)
    reqs = [eng.submit(rng.integers(0, 256, (n,)).astype(np.int32),
                       max_new_tokens=k)
            for n, k in [(20, 9), (1, 5), (17, 12), (11, 7), (5, 10), (16, 3),
                         (9, 6)]]
    for _ in range(5):
        eng.step()
    want = PARENT_DENSE_TRACE
    assert eng._tables.tolist() == want["tables_mid"]
    eng.run()
    assert [r.output.tolist() for r in reqs] == want["tokens"]
    assert [list(eng._donate), list(eng._donate_blk)] == want["donate"]
    assert eng._slot_state == [] and len(eng._arenas) == 4
    sums = [float(np.abs(np.asarray(a, np.float64)).sum())
            for a in eng._arenas]
    np.testing.assert_allclose(sums, want["arena_abs"], rtol=1e-6)


def test_generate_is_the_engines_answer(model):
    """``generate`` (the whole-sequence forward, a token a step) and the
    engine (chunks, paged cache, state arena) serve the same greedy tokens,
    prompts of unequal length in one padded batch."""
    rng = np.random.default_rng(9)
    ids = rng.integers(0, 256, (2, 20)).astype(np.int32)
    lens = np.asarray([20, 7], np.int32)
    want = np.asarray(model.generate(ids, seq_lens=lens,
                                     max_new_tokens=6)._value)
    eng = engine(model)
    got = [eng.submit(ids[i, :lens[i]], max_new_tokens=6) for i in range(2)]
    eng.run()
    assert [r.output.tolist() for r in got] == want.tolist()


def test_a_model_can_be_built_without_a_byte_of_initial_values():
    """``nn.lazy.placeholders()``, a loader's context: every parameter is a
    placeholder of its shape, type and placement until ``set_value`` gives
    it a value (the benchmark builds the 5.27 B configuration so: its own
    initial values would not fit the chip beside the seed's)."""
    from paddle_tpu.nn.lazy import Unmaterialized, placeholders
    with placeholders():
        m = models.Lfm2MoeForCausalLM(models.tiny_lfm2_config())
    m.to(dtype="bfloat16")
    params = list(m.parameters())
    assert all(isinstance(p._value, Unmaterialized) for p in params)
    w = m.lfm2.layers[2].feed_forward.w1
    assert w.shape == [8, 64, 32] and w.dtype == jnp.bfloat16
    assert w._value.sharding.device_set == {jax.devices()[0]}
    w.set_value(jnp.ones((8, 64, 32), jnp.float32))
    assert isinstance(w._value, jax.Array) and w.dtype == jnp.bfloat16
    # outside the context parameters are materialized as ever
    assert isinstance(nn.Linear(2, 2).weight._value, jax.Array)
