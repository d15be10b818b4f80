"""The Falcon-H1 hybrid decoder (``models/falcon_h1.py``) against its plain
reference (``benchmarks/reference/falcon_h1.py``) at a small size on the CPU,
and the reference against the public ``transformers`` implementation: the
three forms of the state-space recurrence, the whole-sequence forward, the
engine's chunked prefill and decode with slots reused, and poisoned state.

Tolerances, with their reasons.  Everything here is float32 on both sides, so
a logit differs from the reference's only by the order of float32 sums and by
the chunked form's factored decays: ``REL_TOL`` 1e-4 of the largest logit is
some thirty times the largest difference seen, and a hundred times under what
the float8 reference moves a logit by (tested to fail).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import falcon_h1 as family
from benchmarks.reference import falcon_h1 as ref
from paddle_tpu import models
from paddle_tpu.inference import ServingEngine
from paddle_tpu.models.generation import SlotStateError, init_slot_state
from paddle_tpu.ops.pallas import ssd

REL_TOL = 1e-4
# the published keys at a toy size (hidden 256, two groups of state 32, four
# layers, vocabulary 512, a KV head for five query heads as published), with
# the published multipliers, as a configuration's file would hand them over
CFG = dict(
    model_type="falcon_h1", vocab_size=512, hidden_size=256,
    intermediate_size=384, num_hidden_layers=4, num_attention_heads=10,
    num_key_value_heads=2, head_dim=16, hidden_act="silu", rms_norm_eps=1e-5,
    rope_theta=1e11, rope_scaling=None, max_position_embeddings=262144,
    tie_word_embeddings=False, attention_bias=False, mlp_bias=False,
    projectors_bias=False, attn_layer_indices=None, mamba_use_mlp=True,
    mlp_expansion_factor=8, num_logits_to_keep=1, mamba_d_ssm=128,
    mamba_n_heads=8, mamba_d_head=16, mamba_n_groups=2, mamba_d_state=32,
    mamba_d_conv=4, mamba_expand=2, mamba_chunk_size=128,
    mamba_conv_bias=True, mamba_proj_bias=False, mamba_norm_before_gate=False,
    mamba_rms_norm=True, embedding_multiplier=5.656854249492381,
    lm_head_multiplier=0.0078125,
    mlp_multipliers=[0.1767766952966369, 0.011160714285714284],
    key_multiplier=0.011048543456039804, attention_in_multiplier=1,
    attention_out_multiplier=0.0375,
    ssm_multipliers=[0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738],
    ssm_in_multiplier=0.25, ssm_out_multiplier=0.08838834764831845,
    dtype="float32")


def seeded(cfg=CFG, seed=0):
    """(the program's model, the flat leaves, the reference's weights) of
    ``cfg`` on the family's seeded leaves."""
    model = family.build(cfg)
    key = jax.random.PRNGKey(seed)
    leaves = [family.leaf(key, i, shape, kind, jnp.float32)
              for i, (_, shape, kind) in enumerate(family.leaf_specs(cfg))]
    for (_, p), v in zip(model.named_parameters(), leaves):
        p.set_value(v)
    return model, leaves, family.as_reference(cfg, leaves)


@pytest.fixture(scope="module")
def seeded_pair():
    return seeded()


@pytest.fixture(scope="module")
def model(seeded_pair):
    return seeded_pair[0]


@pytest.fixture(scope="module")
def weights(seeded_pair):
    return seeded_pair[2]


def reference_logits(weights, ids, mode=None):
    """Reference logits of every position of ``ids`` (padded to the bucket)."""
    n = len(ids)
    total = -(-n // 32) * 32
    padded = np.zeros((total,), np.int32)
    padded[:n] = ids
    return np.concatenate([
        np.asarray(ref.sequence_logits(weights, CFG, padded, r, 32, mode))
        for r in range(0, total, 32)])[:n]


def close(got, want):
    return np.abs(np.asarray(got) - want).max() < REL_TOL * np.abs(want).max()


def served_gap(weights, prompt, output):
    seq = np.concatenate([prompt, output]).astype(np.int32)
    rows = reference_logits(weights, seq)[len(prompt) - 1:-1]
    return float((rows.max(-1) - rows[np.arange(len(output)), output]).max()
                 / np.abs(rows).max())


# -- the recurrence's three forms --------------------------------------------------

def ssd_inputs(t, b=2, h=16, p=8, g=2, n=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        x=jax.random.normal(ks[0], (b, t, h, p)),
        B=jax.random.normal(ks[1], (b, t, g, n)),
        C=jax.random.normal(ks[2], (b, t, g, n)),
        dt=jax.nn.softplus(jax.random.normal(ks[3], (b, t, h)) - 2.0),
        A=-jnp.arange(1, h + 1, dtype=jnp.float32) / 4,
        D=jax.random.normal(ks[4], (h,)),
        s0=jax.random.normal(ks[5], (b, h, p, n)))


@pytest.mark.parametrize("t,n_valid", [(45, None), (16, None), (1, None),
                                       (45, (45, 20)), (40, (0, 1))],
                         ids=["two_and_a_bit_chunks", "one_chunk", "one_row",
                              "short_of_the_chunk", "none_and_one_valid"])
def test_ssd_chunk_is_the_recurrence(t, n_valid):
    x = ssd_inputs(t)
    nv = None if n_valid is None else jnp.asarray(n_valid)
    y_r, s_r = ssd.ssd_recurrent(**x, n_valid=nv)
    y_c, s_c = ssd.ssd_chunk(**x, n_valid=nv, chunk=16)
    for b in range(2):
        n = t if n_valid is None else n_valid[b]
        np.testing.assert_allclose(y_c[b, :n], y_r[b, :n], atol=2e-5)
    np.testing.assert_allclose(s_c, s_r, atol=2e-5)


def test_ssd_state_carries_over_chunks_that_are_no_multiple_of_the_chunk():
    x = ssd_inputs(45, seed=1)
    y_whole, s_whole = ssd.ssd_chunk(**x, chunk=16)
    cut = lambda lo, hi: {k: v[:, lo:hi] for k, v in x.items()  # noqa: E731
                          if k in ("x", "B", "C", "dt")}
    y1, s1 = ssd.ssd_chunk(**cut(0, 21), A=x["A"], D=x["D"], s0=x["s0"],
                           chunk=16)
    y2, s2 = ssd.ssd_chunk(**cut(21, 45), A=x["A"], D=x["D"], s0=s1, chunk=16)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y_whole,
                               atol=2e-5)
    np.testing.assert_allclose(s2, s_whole, atol=2e-5)


@pytest.mark.parametrize("body", ["jnp", "pallas_interpret"])
def test_ssd_decode_steps_are_the_recurrence(body, monkeypatch):
    """Six tokens through ``ssd_decode_step``, the state in an arena of three
    layers beside a row that is not live, against the recurrence; the chunk
    before them left the state.  ``pallas_interpret`` forces the gate to the
    kernel, which runs in interpret mode off the chip."""
    if body == "pallas_interpret":
        monkeypatch.setattr(ssd, "_ssd_route_reason",
                            lambda arena, heads, groups: "state_ok")
    x = ssd_inputs(30, seed=2)
    head = {k: (v[:, :24] if k in ("x", "B", "C", "dt") else v)
            for k, v in x.items()}
    _, s = ssd.ssd_chunk(**head, chunk=16)
    arena = jnp.full((4, 3) + s.shape[1:], 7.0).at[:2, 1].set(s)
    rows, live = jnp.asarray([0, 1, 3]), jnp.asarray([True, True, False])
    pad = lambda a: jnp.concatenate([a, a[:1]], 0)  # noqa: E731
    ys = []
    for t in range(24, 30):
        y, arena = ssd.ssd_decode_step(
            arena, 1, rows, live, *(pad(x[k][:, t]) for k in ("x", "B", "C",
                                                                 "dt")),
            x["A"], x["D"])
        ys.append(y[:2])
    y_r, s_r = ssd.ssd_recurrent(**x)
    np.testing.assert_allclose(jnp.stack(ys, 1), y_r[:, 24:], atol=2e-5)
    np.testing.assert_allclose(arena[:2, 1], s_r, atol=2e-5)
    np.testing.assert_array_equal(arena[2], 7.0)     # no row of its own


def test_route_reasons_are_a_closed_set():
    assert len(set(ssd.SSD_ROUTE_REASONS)) == len(ssd.SSD_ROUTE_REASONS)
    arena = jnp.zeros((2, 1, 32, 128, 256))
    assert ssd._ssd_route_reason(arena, 32, 2) in ssd.SSD_ROUTE_REASONS


# -- the model against the reference, the reference against the public code --------

def test_forward_matches_the_reference(model, weights):
    """Two SSD chunks of the published 128 and a part of a third."""
    ids = np.random.default_rng(0).integers(0, 512, (300,)).astype(np.int32)
    out = np.asarray(model(jnp.asarray(ids[None]))._value)[0]
    want = reference_logits(weights, ids)
    assert close(out, want)
    # a lower precision is far outside the tolerance
    low = reference_logits(weights, ids, mode="fp8")
    assert not close(low, want)
    assert np.abs(low - want).max() > 100 * REL_TOL * np.abs(want).max()


def test_the_reference_is_the_public_implementation(seeded_pair):
    """``transformers``' ``FalconH1ForCausalLM`` (its torch path on the CPU)
    with the seeded leaves copied across gives the reference's logits: this
    pins the equations, the muP vector's sections and the order of the
    groups."""
    torch = pytest.importorskip("torch")
    hf = pytest.importorskip("transformers.models.falcon_h1."
                             "modeling_falcon_h1")
    from transformers import FalconH1Config
    _, leaves, weights = seeded_pair
    keys = {k: v for k, v in CFG.items() if k not in ("model_type", "dtype")}
    hcfg = FalconH1Config(**keys, attn_implementation="eager")
    torch.manual_seed(0)
    net = hf.FalconH1ForCausalLM(hcfg).eval()
    state = {}
    for (name, _, _), v in zip(family.leaf_specs(CFG), leaves):
        v = torch.from_numpy(np.asarray(v, np.float32))
        if name.endswith("conv_weight"):
            name, v = name.replace("conv_weight", "conv1d.weight"), v[:, None]
        elif name.endswith("conv_bias"):
            name = name.replace("conv_bias", "conv1d.bias")
        elif name.endswith("norm_weight"):
            name = name.replace("norm_weight", "norm.weight")
        elif v.ndim == 2 and name != "model.embed_tokens.weight":
            v = v.T                  # torch keeps a linear weight [out, in]
        state[name] = v.contiguous()
    missing, unexpected = net.load_state_dict(state, strict=False)
    assert not unexpected and all("mup_vector" in m for m in missing), \
        (missing, unexpected)
    ids = np.random.default_rng(1).integers(0, 512, (150,)).astype(np.int32)
    with torch.no_grad():
        got = net(torch.from_numpy(ids[None].astype(np.int64)),
                  use_cache=False, logits_to_keep=0).logits[0].numpy()
    assert close(got, reference_logits(weights, ids))


def test_the_configuration_refuses_what_it_has_no_answer_for():
    for key, value in [("attn_layer_indices", [0]), ("mamba_use_mlp", False),
                       ("num_logits_to_keep", 2), ("projectors_bias", True),
                       ("mamba_norm_before_gate", True),
                       ("tie_word_embeddings", True)]:
        with pytest.raises(ValueError, match=key):
            models.tiny_falcon_h1_config(**{key: value})
    for key, value in [("mlp_expansion_factor", 4),
                       ("mamba_chunk_size", 256)]:
        with pytest.raises(ValueError, match=key):
            family.build(dict(CFG, **{key: value}))
    with pytest.raises(ValueError, match="no answer"):
        family.build(dict(CFG, sliding_window=4096))


class Paged:
    """The arenas and tables an engine would hold, driven by hand so that the
    test sees logits and not tokens."""

    def __init__(self, model, num_slots=3, block_len=8, max_blocks=8):
        from paddle_tpu.models.generation import init_paged_kv_arena
        n_layers, hkv, d = model.kv_cache_spec()
        self.model, self.num_slots = model, num_slots
        nb = num_slots * max_blocks
        self.trash = nb
        self.arenas = init_paged_kv_arena(n_layers, nb, block_len, hkv, d,
                                          jnp.float32)
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
            self.kvs(tables, {}))
        self.adopt(kvs)
        return np.asarray(logits)


@pytest.mark.parametrize("prompt_len", [40, 1],
                         ids=["two_and_a_half_chunks", "a_single_row"])
def test_chunked_prefill_then_decode_matches_the_reference(model, weights,
                                                           prompt_len):
    """Prefill in chunks of 16 through the KV and state arenas, then eight
    decode steps in slot 1 of 3 beside a frozen slot: every logit row
    against the reference's plain forward over prompt and continuation."""
    rng = np.random.default_rng(prompt_len)
    paged = Paged(model)
    seq = list(rng.integers(0, 512, prompt_len))
    rows = [paged.prefill(1, np.asarray(seq), 16)]
    # slot 2 is mid-prefill: frozen in the decode step, its state must stay
    paged.prefill(2, rng.integers(0, 512, 16), 16)
    frozen = [np.asarray(a[2]) for a in paged.state]
    for _ in range(8):
        seq.append(int(np.argmax(rows[-1])))
        logits = paged.decode([0, seq[-1], 5], [0, len(seq) - 1, 16],
                              [False, True, False])
        rows.append(logits[1])
    want = reference_logits(weights, np.asarray(seq))[prompt_len - 1:]
    assert close(np.stack(rows), want)
    for a, was in zip(paged.state, frozen):
        np.testing.assert_array_equal(np.asarray(a[2]), was)


# -- the engine -----------------------------------------------------------------------

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
            ((rng.integers(0, 512, (n,)).astype(np.int32), m)
             for n, m in shapes)]
    eng.run()
    for ids, r in sent:
        want = np.asarray(model.generate(jnp.asarray(ids[None]),
                                         max_new_tokens=r.max_new_tokens)
                          ._value)[0]
        np.testing.assert_array_equal(np.asarray(r.output), want)
    # tails [4 layers, 3 rows, 128 + 2 x 2 x 32] and states [4, 8, 16, 32],
    # float32, for each slot and the trash row
    assert eng.engine_spec()["slot_state_bytes"] == \
        4 * (4 * 3 * 256 * 4 + 4 * 8 * 16 * 32 * 4)
    # no expert counters ride the decode block of a model that has none
    assert eng.stats().get("moe_expert_tokens", 0) == 0


@pytest.mark.parametrize("reset", [True, False], ids=["sound", "not_reset"])
def test_a_finished_slots_state_is_poisoned_and_never_read(
        model, weights, monkeypatch, reset):
    """A slot whose request finished in a decode program leaves NaN in both
    of its state rows (the state's in its first head channel a head); a
    sound engine never reads them, so every arena stays finite but for them
    and the tokens are the reference's.  An engine that loses the reset
    serves garbage."""
    if not reset:
        monkeypatch.setattr(
            models.FalconH1ForCausalLM, "_chunk_state",
            staticmethod(lambda arena, slot, start: arena[slot][None]))
    eng = engine(model, num_slots=2)
    rng = np.random.default_rng(11)
    sent = [(ids, eng.submit(ids, max_new_tokens=m)) for ids, m in
            ((rng.integers(0, 512, (n,)).astype(np.int32), m)
             for n, m in [(20, 6), (9, 11), (33, 5), (4, 9), (18, 7)])]
    eng.run()
    tails, states = eng._slot_state
    assert np.isnan(np.asarray(tails[:2])).all()
    assert np.isnan(np.asarray(states[:2, :, :, 0])).all()
    if reset:
        assert all(np.isfinite(np.asarray(a[2])).all()      # the trash row
                   for a in eng._slot_state)
        assert np.isfinite(np.asarray(states[:2, :, :, 1:])).all()
        assert all(np.isfinite(np.asarray(a)).all() for a in eng._arenas)
        assert max(served_gap(weights, ids, r.output) for ids, r in sent) \
            < REL_TOL
    else:
        # NaN logits: the argmax serves id 0, not the reference's tokens
        assert all((np.asarray(r.output) == 0).all() for _, r in sent[2:])


def test_what_moves_blocks_without_the_state_refuses_the_model(model):
    with pytest.raises(SlotStateError, match="ssm_conv_tail, ssm_state"):
        engine(model, host_cache_blocks=8)
    with pytest.raises(SlotStateError):
        model.verify_step(None, None, None, None)
