"""The LongCat-Flash decoder (``models/longcat_flash.py``) against its plain
reference (``benchmarks/reference/longcat_flash.py``) at a small size on the
CPU: the whole forward, prefill then decode through the latent arenas, the
chunk and the absorbed decode writing and reading the same rows, the shares
of the expert layer adding up to the uncut layer, zero-compute experts, the
shortcut branch's input, and the engine with reused slots and a shared
prefix.

Tolerances, with their reasons.  Everything here is float32 on both sides, so
a logit differs from the reference's only by the order of float32 sums: the
largest difference seen is 3e-6 on logits of size 4; ``LOGIT_TOL`` 1e-4 leaves
room for another backend's order and is a thousand times under what the
float8 reference moves a logit by (tested to fail).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import longcat_flash as family
from benchmarks.reference import longcat_flash as ref
from paddle_tpu import models
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.inference import ServingEngine
from paddle_tpu.models.generation import LatentCacheSpec, init_paged_latent_arena
from paddle_tpu.nn.layer import experts

LOGIT_TOL = 1e-4
# the published keys at a toy size, as a configuration's file would hand them
# to the family; weights at 0.125 (a gain of one a matmul)
CFG = dict(
    vocab_size=256, hidden_size=64, ffn_hidden_size=128,
    expert_ffn_hidden_size=32, num_layers=2, num_attention_heads=4,
    kv_lora_rank=32, q_lora_rank=48, qk_rope_head_dim=8, v_head_dim=16,
    qk_nope_head_dim=16, mla_scale_q_lora=True, mla_scale_kv_lora=True,
    routed_scaling_factor=6.0, n_routed_experts=8, zero_expert_num=4,
    zero_expert_type="identity", moe_topk=3, max_position_embeddings=4096,
    rms_norm_eps=1e-5, rope_theta=10000.0, attention_method="MLA",
    attention_bias=False, dtype="float32", initializer_range=0.125)


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


def test_forward_matches_the_reference(model, weights):
    ids = np.random.default_rng(0).integers(0, 256, (40,)).astype(np.int32)
    out = np.asarray(model(jnp.asarray(ids[None]))._value)[0]
    want = reference_logits(weights, ids)
    assert np.abs(out - want).max() < LOGIT_TOL
    # a lower precision is far outside the tolerance
    low = reference_logits(weights, ids, mode="fp8")
    assert np.abs(low - want).max() > 100 * LOGIT_TOL


def test_the_configuration_refuses_what_it_has_no_answer_for():
    for key, value in [("attention_bias", True),
                       ("zero_expert_type", "constant"),
                       ("attention_method", "GQA")]:
        with pytest.raises(ValueError, match=key):
            models.tiny_longcat_flash_config(**{key: value})
    with pytest.raises(ValueError, match="mla_scale_q_lora"):
        models.tiny_longcat_flash_config(q_lora_rank=None)
    with pytest.raises(ValueError, match="no answer"):
        family.build(dict(CFG, sliding_window=4096))


class Paged:
    """The latent arenas and tables an engine would hold, driven by hand so
    that the test sees logits and not tokens."""

    def __init__(self, model, num_slots=3, block_len=8, max_blocks=8):
        spec = model.kv_cache_spec()
        assert isinstance(spec, LatentCacheSpec) and spec == (4, 40)
        self.model = model
        nb = num_slots * max_blocks
        self.trash = nb
        self.arenas = init_paged_latent_arena(spec.layers, nb, block_len,
                                              spec.row, jnp.float32)
        self.tables = np.full((num_slots, max_blocks), self.trash, np.int32)
        for s in range(num_slots):      # scattered, slot-major
            self.tables[s] = np.arange(nb)[s::num_slots][:max_blocks]
        self._chunk = jax.jit(model.prefill_chunk)
        self._step = jax.jit(model.decode_step)

    def kvs(self, tables):
        tables = jnp.asarray(tables)
        return [(a, tables) for a, in self.arenas]

    def prefill(self, slot, ids, chunk):
        n = len(ids)
        for start in range(0, n, chunk):
            part = np.zeros((1, chunk), np.int32)
            part[0, :min(chunk, n - start)] = ids[start:start + chunk]
            logits, kvs = self._chunk(
                jnp.asarray(part), jnp.asarray(start, jnp.int32),
                jnp.asarray(n, jnp.int32), self.kvs(self.tables[slot][None]))
            self.arenas = [(a,) for a, _ in kvs]
        return np.asarray(logits)[0]

    def decode(self, tokens, lens, live):
        tables = np.where(np.asarray(live)[:, None], self.tables, self.trash)
        block = {"state": [], "stale": None,
                 "counters": self.model.init_block_counters()}
        logits, kvs = self._step(
            jnp.asarray(tokens, jnp.int32), jnp.asarray(lens, jnp.int32),
            self.kvs(tables) + [block])
        self.arenas = [(a,) for a, _ in kvs[:-1]]
        return np.asarray(logits), np.asarray(kvs[-1]["counters"])

    def rows(self, slot, n):
        """The latent rows of positions ``0 .. n-1`` of a slot, arena by
        arena."""
        return [np.asarray(a)[self.tables[slot]].reshape(-1, a.shape[-1])[:n]
                for a, in self.arenas]


@pytest.mark.parametrize("prompt_len", [40, 1],
                         ids=["two_and_a_half_chunks", "a_single_row"])
def test_chunked_prefill_then_decode_matches_the_reference(model, weights,
                                                           prompt_len):
    """Expanded prefill in chunks of 16 through the latent arenas, then eight
    absorbed decode steps in slot 1 of 3 beside two frozen slots: every logit
    row against the reference's plain forward over prompt and
    continuation."""
    rng = np.random.default_rng(prompt_len)
    paged = Paged(model)
    seq = list(rng.integers(0, 256, prompt_len))
    rows = [paged.prefill(1, np.asarray(seq), 16)]
    for _ in range(8):
        seq.append(int(np.argmax(rows[-1])))
        logits, counters = paged.decode([0, seq[-1], 5],
                                        [0, len(seq) - 1, 16],
                                        [False, True, False])
        rows.append(logits[1])
    want = reference_logits(weights, np.asarray(seq))[prompt_len - 1:]
    assert np.abs(np.stack(rows) - want).max() < LOGIT_TOL
    # one live row, two layers, three picks a layer: the real experts' rows
    # and the zero-compute picks make up the picks
    real, zero, picks, pairs, touched = (counters[:-4].sum(), *counters[-4:])
    assert picks == 6 and real + zero == 6 and pairs == 2
    # a row's picks are distinct experts: each real one touches an expert
    assert touched == real


def test_the_chunk_and_the_absorbed_decode_agree(model):
    """A sequence prefilled in one slot and decoded token by token, and the
    same sequence prefilled whole in chunks in another: the latent rows
    written (the rotated positional key among them) and the last logits are
    the same."""
    rng = np.random.default_rng(7)
    paged = Paged(model)
    seq = rng.integers(0, 256, 29).astype(np.int32)
    last = paged.prefill(0, seq[:21], 16)
    for t in range(21, 29):
        logits, _ = paged.decode([seq[t], 0, 0], [t, 0, 0],
                                 [True, False, False])
        last = logits[0]
    whole = paged.prefill(2, seq, 16)
    for a, b in zip(paged.rows(0, 29), paged.rows(2, 29)):
        np.testing.assert_allclose(a, b, atol=1e-5)
    np.testing.assert_allclose(last, whole, atol=LOGIT_TOL)


def test_the_shares_add_up_to_the_uncut_layer():
    """32 chips' shares of a layer, each holding one of 32 real experts:
    their outputs summed, with what every share computes alike (attention,
    the dense feed-forwards and the zero-compute experts' part) counted once,
    are the uncut reference layer."""
    cfg = dict(CFG, n_routed_experts=32, zero_expert_num=16, moe_topk=6,
               num_layers=1)
    whole, w_whole = seeded(cfg, seed=3)
    layer = whole.model.layers[0]
    planes = {n: getattr(layer.mlp, n)._value for n in ("w1", "w3", "w2")}
    x = jax.random.normal(jax.random.PRNGKey(5), (24, 64))
    want, _ = ref.layer_forward(w_whole["layers"][0], x, ref._dims(cfg),
                                ref._routing(cfg), 8)
    share, _ = seeded(dict(cfg, n_routed_experts=1, router_experts=32), 3)
    part = share.model.layers[0]
    for name in ("router", "expert_bias"):
        getattr(part.mlp, name).set_value(getattr(layer.mlp, name)._value)
    pos0, n_valid = jnp.zeros((1,), jnp.int32), jnp.full((1,), 24, jnp.int32)

    def out(first, scale=1.0):
        part.mlp.held = (first, 1)
        for name, v in planes.items():
            getattr(part.mlp, name).set_value(v[first:first + 1] * scale)
        return part.step(Tensor(x[None]), pos0, n_valid, None)[0]._value[0]

    alike = out(0, scale=0.0)       # no real expert's part: what all compute
    total = alike + sum(out(first) - alike for first in range(32))
    np.testing.assert_allclose(total, want, atol=1e-4)


def test_a_row_routed_only_to_zero_compute_experts(monkeypatch):
    """Biases that put every pick on a zero-compute expert: the row gets
    ``scale * sum(s) * u`` and puts no row into the grouped matmul."""
    layer = experts.RoutedExperts(16, 8, 6, 3, zero_experts=4,
                                  norm_topk_prob=False,
                                  routed_scaling_factor=6.0,
                                  scoring="softmax")
    layer.expert_bias.set_value(jnp.concatenate(
        [jnp.zeros((6,)), jnp.full((4,), 10.0)]))
    sizes = []
    honest = experts.grouped_matmul
    monkeypatch.setattr(experts, "grouped_matmul",
                        lambda xs, w, s: sizes.append(s) or honest(xs, w, s))
    u = jax.random.normal(jax.random.PRNGKey(0), (5, 16))
    out, load = layer.apply(u)
    s = jax.nn.softmax(u @ layer.router._value, axis=-1)
    top3 = jnp.sort(s[:, 6:], axis=-1)[:, 1:]
    np.testing.assert_allclose(out, 6.0 * top3.sum(-1, keepdims=True) * u,
                               rtol=1e-5)
    assert sizes and all(int(jnp.sum(s)) == 0 for s in sizes)
    # no real expert got a row; 15 picks, every one zero-compute
    assert load.tolist() == [0] * 6 + [15, 15, 0]


def test_the_moe_reads_the_first_sublayers_norm(model, weights, monkeypatch):
    """The shortcut branch's input is ``rms_post0(h1)``, h1 after the first
    attention sublayer, not the second sublayer's norm."""
    seen = []
    honest = experts.RoutedExperts.apply
    monkeypatch.setattr(experts.RoutedExperts, "apply",
                        lambda self, u, live=None: seen.append(u)
                        or honest(self, u, live))
    ids = np.random.default_rng(2).integers(0, 256, (16,)).astype(np.int32)
    model(jnp.asarray(ids[None]))
    lp, dims = weights["layers"][0], ref._dims(CFG)
    x = weights["embed"][ids].astype(jnp.float32)
    h1 = ref._attention(lp["attn0"], lp["in0"], x, dims, None)
    np.testing.assert_allclose(seen[0], ref.rmsnorm(h1, lp["post0"], 1e-5),
                               atol=1e-5)


def test_the_latent_gate_takes_sixty_four_heads(monkeypatch):
    """At the cell's widths (64 heads over rows of 576 values resting as 640,
    blocks of 16, 128 slots of 2048 positions) the latent decode kernel is
    taken on the chip: its gate answers ``latent_ok``."""
    from paddle_tpu.ops.pallas import decode_attention as da
    monkeypatch.setattr(da, "pallas_enabled", lambda: True)
    shape = jax.ShapeDtypeStruct
    q = shape((128, 64, 640), jnp.bfloat16)
    arena = shape((16385, 16, 640), jnp.bfloat16)
    tables = shape((128, 128), jnp.int32)
    assert da._route_decision_latent(q, arena, tables, 512) == \
        (True, "latent_ok")


def engine(model, **kw):
    geo = dict(num_slots=3, prompt_len=48, max_cache_len=80, block_len=8,
               num_blocks=40, chunk_len=16, steps_per_call=4,
               compute_dtype="float32", host_cache_blocks=0)
    geo.update(kw)
    return ServingEngine(model, **geo)


def test_engine_serves_reused_slots_and_a_shared_prefix(model, weights):
    """Five requests over three slots, two of them sharing a prompt prefix
    of three blocks (the prefix cache hands the second the first's latent
    rows): each served answer is the reference's, and the block counters
    add up."""
    from paddle_tpu.observability.metrics import get_registry
    eng = engine(model)
    rng = np.random.default_rng(4)
    prefix = rng.integers(0, 256, (24,)).astype(np.int32)
    prompts = [np.concatenate([prefix, rng.integers(0, 256, (9,))]),
               rng.integers(0, 256, (1,)), rng.integers(0, 256, (17,)),
               np.concatenate([prefix, rng.integers(0, 256, (5,))]),
               rng.integers(0, 256, (5,))]
    snap0 = get_registry().snapshot()
    sent = []
    for ids, m in zip(prompts, [9, 5, 12, 7, 10]):
        sent.append((ids.astype(np.int32),
                     eng.submit(ids.astype(np.int32), max_new_tokens=m)))
        if len(sent) == 1:
            eng.run()       # the prefix is cached before the second comes
    eng.run()
    assert eng.stats()["prefix_hit_tokens"] >= 24
    for ids, r in sent:
        assert r.state == "finished" and len(r.output) == r.max_new_tokens
        assert served_gap(weights, ids, r.output) < LOGIT_TOL
    snap = get_registry().snapshot()

    def grew(name):
        total = lambda s: sum(s.get(name, {}).get("values", {}).values())  # noqa: E731
        return total(snap) - total(snap0)

    assert grew("moe.expert_picks") > 0
    assert grew("moe.expert_picks") == \
        grew("moe.zero_expert_picks") + grew("moe.expert_tokens")
    assert 0 < grew("moe.zero_expert_picks") < grew("moe.expert_picks")


def test_the_chunk_kernel_serves_the_xla_routes_tokens(model, monkeypatch):
    """The engine with the latent chunk kernel forced (interpret mode, query
    tiles of 4 positions) serves the tokens the gathered and expanded XLA
    route serves: prompts of one to three chunks, and two that share a
    prefix of three blocks, the second's chunk starting at the hit."""
    from paddle_tpu.observability.metrics import get_registry
    from paddle_tpu.ops.pallas import decode_attention as da
    rng = np.random.default_rng(46)
    prefix = rng.integers(0, 256, (24,))
    prompts = [np.concatenate([prefix, rng.integers(0, 256, (9,))]),
               rng.integers(0, 256, (40,)), rng.integers(0, 256, (5,)),
               np.concatenate([prefix, rng.integers(0, 256, (13,))])]

    def served():
        eng = engine(model)
        sent = []
        for ids in prompts:
            sent.append(eng.submit(ids.astype(np.int32), max_new_tokens=6))
            if len(sent) == 1:
                eng.run()       # the prefix is cached before the last comes
        eng.run()
        assert eng.stats()["prefix_hit_tokens"] >= 24
        return [list(r.output) for r in sent]

    xla = served()
    route = get_registry().counter("pallas.latent_chunk.route",
                                   labels=("decision", "reason"))
    before = route.value(decision="pallas", reason="latent_chunk_ok")
    monkeypatch.setattr(da, "_latent_chunk_decision",
                        lambda *shape: (4, "latent_chunk_ok"))
    assert served() == xla
    assert route.value(decision="pallas", reason="latent_chunk_ok") > before
