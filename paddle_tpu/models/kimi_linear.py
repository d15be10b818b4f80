"""Kimi Linear sparse hybrid decoder (``model_type`` ``kimi_linear``): gated
delta-rule linear attention (KDA) whose matrix state is per-slot state,
latent attention (MLA, no rotary) whose paged cache is one row a token, and
routed experts of which this chip may hold a share, beside a shared one.

Layer ``i`` (1-based, as the published lists count) is ``h = h +
Op_i(rms(h))``, ``h = h + FFN_i(rms(h))``.  ``Op_i`` is KDA where ``i`` is in
``linear_attn_config["kda_layers"]`` and MLA where it is in
``["full_attn_layers"]``; ``FFN_i`` is a dense SwiGLU in the first
``first_k_dense_replace`` layers and, after them, ``nn.RoutedExperts`` (sigmoid
scores over all ``num_experts``, the correction bias picks and does not
weigh, the picked scores renormalised and scaled) plus a shared expert.  The
head is not tied.

Three kinds of state on the serving path:

* an MLA layer keeps ``[rms(c), k_pe]`` (``kv_lora_rank + qk_rope_head_dim``
  values) a token: ``kv_cache_spec()`` answers a ``LatentCacheSpec`` and the
  engine makes one arena a layer.  Decode runs absorbed (the up-projections
  folded into the query and the output, every head over the one shared row:
  ``ops/pallas/decode_attention.decode_attention_latent``); a chunk runs
  expanded over the slot's gathered rows;
* a KDA layer keeps the last ``short_conv_kernel_size - 1`` rows of its
  q, k, v projections (compute dtype) and
* its heads' states ``S`` [dk, dv] in float32 (``ops/pallas/kda.py``):
  ``slot_state_spec()`` names both, the engine keeps an arena of each and
  hands them to the step entry points as the last entry of ``kvs``.  A decode
  step updates ``S`` in place in its arena.

There is one step function a level (``step``, ``_run``): rows of ``C``
positions starting at ``pos0`` of which ``n_valid`` count.  The
whole-sequence ``forward`` (no cache, state from zeros), the chunk of a
prefill (one row, ``C = chunk_len``) and the decode step (a row a slot, ``C =
1``) are that function at three shapes.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..incubate.nn.functional import swiglu
from ..nn.initializer import Constant, Normal
from ..ops.pallas.kda import kda_chunk, kda_decode_step
from .generation import (LatentCacheSpec, SlotStateError,
                         generate_by_forward, paged_verify_scatter)


@dataclass
class KimiLinearConfig:
    """The published keys under their published names.  Not published:
    ``experts_held`` (``(first, count)`` of the ``num_experts`` whose planes
    this model has; all by default), ``kda_gate_rank`` (the width of the two
    low-rank gates) and ``l2_norm_eps``.  ``vocab_size`` is what the
    embedding and the head hold, a slice where the vocabulary is sliced;
    ``head_dim`` (hidden over heads) shapes nothing here."""
    vocab_size: int = 163840
    hidden_size: int = 2304
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 27
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    head_dim: Optional[int] = None
    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_use_nope: bool = True
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = None
    linear_attn_config: dict = field(default_factory=lambda: {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27],
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "head_dim": 128, "num_heads": 32, "short_conv_kernel_size": 4})
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    num_experts: int = 256
    num_experts_per_token: int = 8
    num_shared_experts: int = 1
    moe_renormalize: bool = True
    moe_router_activation_func: str = "sigmoid"
    num_expert_group: int = 1
    topk_group: int = 1
    use_grouped_topk: bool = True
    routed_scaling_factor: float = 2.446
    rms_norm_eps: float = 1e-5
    hidden_act: str = "silu"
    tie_word_embeddings: bool = False
    num_nextn_predict_layers: int = 0
    model_max_length: int = 1048576
    experts_held: Optional[Tuple[int, int]] = None
    kda_gate_rank: int = 128
    l2_norm_eps: float = 1e-6

    def __post_init__(self):
        refused = {
            "q_lora_rank": self.q_lora_rank is not None,
            "rope_scaling": self.rope_scaling is not None,
            "num_nextn_predict_layers": self.num_nextn_predict_layers > 0,
            "mla_use_nope": not self.mla_use_nope,
            "moe_router_activation_func":
                self.moe_router_activation_func != "sigmoid",
            "hidden_act": self.hidden_act != "silu",
            "tie_word_embeddings": self.tie_word_embeddings,
            "moe_layer_freq": self.moe_layer_freq != 1,
            # one group of which one is kept is the plain top-k
            "num_expert_group": self.num_expert_group != 1,
            "topk_group": self.topk_group != 1,
            "num_key_value_heads":
                self.num_key_value_heads != self.num_attention_heads,
        }
        for key, bad in refused.items():
            if bad:
                raise ValueError(f"{key}={getattr(self, key)!r}: this model "
                                 "has no answer for it")
        lin = self.linear_attn_config
        kda, full = list(lin["kda_layers"]), list(lin["full_attn_layers"])
        if sorted(kda + full) != list(range(1, self.num_hidden_layers + 1)) \
                or not full:
            raise ValueError(
                f"kda_layers {kda} and full_attn_layers {full} must name "
                f"layers 1..{self.num_hidden_layers} once each, at least one "
                "of them full (the engine's block tables come with its cache)")
        self.layer_kinds = ["kda" if i + 1 in kda else "mla"
                            for i in range(self.num_hidden_layers)]
        if self.experts_held is None:
            self.experts_held = (0, self.num_experts)
        self.experts_held = tuple(int(x) for x in self.experts_held)

    @property
    def latent_row(self):
        return self.kv_lora_rank + self.qk_rope_head_dim


def tiny_kimi_linear_config(**kw):
    """The smallest shape with every mechanism: both operators, the leading
    dense layer and expert layers with a shared expert, more experts than a
    token picks."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                moe_intermediate_size=32, num_hidden_layers=4,
                num_attention_heads=4, num_key_value_heads=4,
                kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                v_head_dim=16,
                linear_attn_config={
                    "kda_layers": [1, 2, 4], "full_attn_layers": [3],
                    "head_dim": 16, "num_heads": 8,
                    "short_conv_kernel_size": 4},
                num_experts=8, num_experts_per_token=2, kda_gate_rank=8)
    base.update(kw)
    return KimiLinearConfig(**base)


def _linear(n_in, n_out):
    return nn.Linear(n_in, n_out, bias_attr=False,
                     weight_attr=nn.ParamAttr(initializer=Normal(0.0, 0.02)))


class StateRows(NamedTuple):
    """Where a decode step finds its rows' matrix states: row ``i`` is
    ``arena[rows[i], layer]``, from zeros where not ``live``."""
    arena: jax.Array        # [slots + 1, kda layers, H, dk, dv] float32
    layer: int
    rows: jax.Array         # [B] int32
    live: jax.Array         # [B] bool


class KimiDeltaAttention(nn.Layer):
    """KDA.  ``q~, k~, v~`` each through a depthwise causal convolution of
    ``short_conv_kernel_size`` taps and SiLU; per head ``q`` and ``k``
    divided by their L2 norm, ``q`` scaled by ``d^-1/2``; a decay a key
    channel ``g = -exp(A_log) softplus(f_b(f_a(x)) + dt_bias)`` and a write
    strength a head ``beta = sigmoid(b(x))`` drive the delta rule
    (``ops/pallas/kda.py``); the output is RMS-normalised per head, gated by
    ``sigmoid(g_b(g_a(x)))`` and projected."""

    def __init__(self, config: KimiLinearConfig):
        super().__init__()
        lin = config.linear_attn_config
        h = config.hidden_size
        self.num_heads, self.head_dim = int(lin["num_heads"]), \
            int(lin["head_dim"])
        self.taps = int(lin["short_conv_kernel_size"])
        self.l2_eps, self.eps = config.l2_norm_eps, config.rms_norm_eps
        w, r = self.num_heads * self.head_dim, config.kda_gate_rank
        normal = Normal(0.0, 0.02)
        self.q_proj, self.k_proj, self.v_proj = \
            _linear(h, w), _linear(h, w), _linear(h, w)
        # q's, k's and v's taps, stacked as the projections are concatenated
        self.conv_weight = self.create_parameter(
            (3 * w, self.taps), default_initializer=normal)
        self.A_log = self.create_parameter(
            (self.num_heads,), default_initializer=Constant(0.0))
        self.f_a_proj, self.f_b_proj = _linear(h, r), _linear(r, w)
        self.dt_bias = self.create_parameter(
            (w,), default_initializer=Constant(0.0))
        self.b_proj = _linear(h, self.num_heads)
        self.g_a_proj, self.g_b_proj = _linear(h, r), _linear(r, w)
        self.o_norm = nn.RMSNorm(self.head_dim, config.rms_norm_eps)
        self.o_proj = _linear(w, h)

    def step(self, u, n_valid, cache):
        """u: Tensor [B, C, H]; ``cache = (tail, state)``: ``tail`` [B,
        taps-1, 3W] the rows of ``[q~, k~, v~]`` before the first of ``u``;
        ``state`` the heads' states before it, [B, heads, dk, dv] (the rows
        run through ``kda_chunk``), or a ``StateRows`` (``C = 1``: the step
        runs in place in the arena).  Returns (out Tensor, (the tail and the
        state after the last valid row))."""
        tail, state = cache
        with jax.named_scope("kda"):
            b, c, _ = u.shape
            nh, d = self.num_heads, self.head_dim
            with jax.named_scope("kda_conv"):
                z = jnp.concatenate(
                    [self.q_proj(u)._value, self.k_proj(u)._value,
                     self.v_proj(u)._value], axis=-1)
                zp = jnp.concatenate([tail.astype(z.dtype), z], axis=1)
                taps = self.conv_weight._value.astype(jnp.float32)
                zf = zp.astype(jnp.float32)
                conv = jax.nn.silu(sum(taps[:, j] * zf[:, j:j + c]
                                       for j in range(self.taps)))
                new_tail = jax.vmap(
                    lambda rows, n: jax.lax.dynamic_slice_in_dim(
                        rows, n, self.taps - 1, axis=0))(zp, n_valid)
            q, k, v = (a.reshape(b, c, nh, d)
                       for a in jnp.split(conv, 3, axis=-1))
            l2 = lambda a: a * jax.lax.rsqrt(  # noqa: E731
                jnp.sum(a * a, axis=-1, keepdims=True) + self.l2_eps)
            q, k = l2(q) * d ** -0.5, l2(k)
            f32 = lambda t: t._value.astype(jnp.float32)  # noqa: E731
            rate = f32(self.f_b_proj(self.f_a_proj(u))) \
                + self.dt_bias._value.astype(jnp.float32)
            g = -jnp.exp(self.A_log._value.astype(jnp.float32))[:, None] \
                * jax.nn.softplus(rate).reshape(b, c, nh, d)
            beta = jax.nn.sigmoid(f32(self.b_proj(u)))
            if isinstance(state, StateRows):
                o, arena = kda_decode_step(
                    state.arena, state.layer, state.rows, state.live,
                    q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
                o, state = o[:, None], state._replace(arena=arena)
            else:
                o, state = kda_chunk(q, k, v, g, beta, state, n_valid)
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                                  + self.eps) \
                * self.o_norm.weight._value.astype(jnp.float32)
            gate = jax.nn.sigmoid(f32(self.g_b_proj(self.g_a_proj(u))))
            y = (o.reshape(b, c, nh * d) * gate).astype(u._value.dtype)
            return self.o_proj(Tensor(y)), (new_tail, state)


class KimiMLAttention(nn.Layer):
    """MLA without rotary: ``q = W_q x`` as heads of ``[q_nope, q_pe]``;
    ``[c~, k_pe] = W_kva x``, ``c = rms(c~)``; ``[k_nope, v]_h = W_kvb,h c``;
    ``k_h = [k_nope_h, k_pe]`` with ``k_pe`` shared by the heads; causal
    softmax of ``q_h . k_h / sqrt(nope + rope)``.  What a token caches is
    ``[c, k_pe]``."""

    def __init__(self, config: KimiLinearConfig):
        super().__init__()
        h = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.nope, self.rope = config.qk_nope_head_dim, \
            config.qk_rope_head_dim
        self.v_dim, self.rank = config.v_head_dim, config.kv_lora_rank
        self.scale = (self.nope + self.rope) ** -0.5
        self.q_proj = _linear(h, self.num_heads * (self.nope + self.rope))
        self.kv_a_proj_with_mqa = _linear(h, self.rank + self.rope)
        self.kv_a_layernorm = nn.RMSNorm(self.rank, config.rms_norm_eps)
        self.kv_b_proj = _linear(self.rank,
                                 self.num_heads * (self.nope + self.v_dim))
        self.o_proj = _linear(self.num_heads * self.v_dim, h)

    def _kv_b(self):
        """W_kvb as ([rank, heads, nope], [rank, heads, v])."""
        w = self.kv_b_proj.weight._value.reshape(
            self.rank, self.num_heads, self.nope + self.v_dim)
        return w[..., :self.nope], w[..., self.nope:]

    def _expanded(self, q_nope, q_pe, rows, q_pos):
        """Plain attention of the queries at positions ``q_pos`` [B, C] over
        the latent rows ``rows`` [B, S, >= rank + rope], row ``s`` at
        position ``s``: keys and values expanded through W_kvb."""
        with jax.named_scope("mla_expand"):
            w_k, w_v = self._kv_b()
            c, k_pe = rows[..., :self.rank], \
                rows[..., self.rank:self.rank + self.rope]
            k_nope = jnp.einsum("bsr,rhn->bshn", c, w_k)
            v = jnp.einsum("bsr,rhv->bshv", c, w_v)
            logits = (jnp.einsum("bchn,bshn->bhcs", q_nope, k_nope,
                                 preferred_element_type=jnp.float32)
                      + jnp.einsum("bchr,bsr->bhcs", q_pe, k_pe,
                                   preferred_element_type=jnp.float32)) \
                * self.scale
            keep = jnp.arange(rows.shape[1])[None, None, :] \
                <= q_pos[:, :, None]
            logits = jnp.where(keep[:, None], logits, -jnp.inf)
            probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
            return jnp.einsum("bhcs,bshv->bchv", probs, v)

    def _absorbed(self, q_nope, q_pe, arena, tables, lens):
        """One token a row over the latent arena, the up-projections folded
        into the query (``q_lat = W_kvb^K^T q_nope``) and the output (``o =
        W_kvb^V o_lat``): every head reads the one shared row a token."""
        from ..ops.pallas.decode_attention import decode_attention_latent
        with jax.named_scope("mla_absorb"):
            w_k, w_v = self._kv_b()
            q_lat = jnp.einsum("bhn,rhn->bhr", q_nope, w_k)
            q = jnp.concatenate([q_lat, q_pe], axis=-1)
            q = jnp.pad(q, ((0, 0), (0, 0),
                            (0, arena.shape[-1] - q.shape[-1])))
            o_lat = decode_attention_latent(
                q.astype(arena.dtype), arena, tables, lens, self.rank,
                self.scale)
            return jnp.einsum("bhr,rhv->bhv", o_lat.astype(w_v.dtype), w_v)

    def step(self, u, pos0, n_valid, kv):
        """u: Tensor [B, C, H] at positions ``pos0[b] + c``.  ``kv`` None:
        causal attention inside the rows.  ``kv = (arena, tables)``: the
        valid rows' latent rows are written through the tables and the
        queries attend over everything written."""
        from ..ops.pallas.decode_attention import paged_gather_view
        with jax.named_scope("mla_attention"):
            b, c, _ = u.shape
            q = self.q_proj(u)._value.reshape(
                b, c, self.num_heads, self.nope + self.rope)
            q_nope, q_pe = q[..., :self.nope], q[..., self.nope:]
            ckv = self.kv_a_proj_with_mqa(u)
            latent = self.kv_a_layernorm(Tensor(ckv._value[..., :self.rank]))
            rows = jnp.concatenate(
                [latent._value, ckv._value[..., self.rank:]], axis=-1)
            pos = pos0[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
            if kv is None:
                out = self._expanded(q_nope, q_pe, rows, pos - pos0[:, None])
            else:
                arena, tables = kv
                rows = jnp.pad(rows, ((0, 0), (0, 0),
                                      (0, arena.shape[-1] - rows.shape[-1])))
                arena = paged_verify_scatter(arena, tables, pos0, n_valid,
                                             rows[:, :, None, :])
                if c == 1:
                    out = self._absorbed(q_nope[:, 0], q_pe[:, 0], arena,
                                         tables, pos0)[:, None]
                else:
                    out = self._expanded(
                        q_nope, q_pe, paged_gather_view(arena, tables), pos)
                kv = (arena, tables)
            out = out.reshape(b, c, -1).astype(u._value.dtype)
            return self.o_proj(Tensor(out)), kv


class KimiMLP(nn.Layer):
    def __init__(self, hidden_size, width):
        super().__init__()
        self.gate_proj, self.up_proj, self.down_proj = \
            _linear(hidden_size, width), _linear(hidden_size, width), \
            _linear(width, hidden_size)

    def forward(self, x):
        return self.down_proj(swiglu(self.gate_proj(x), self.up_proj(x)))


class KimiSparseMoe(nn.Layer):
    """The held share of the routed experts' output plus the shared expert's,
    which every chip of the deployment computes alike."""

    def __init__(self, config: KimiLinearConfig):
        super().__init__()
        self.experts = nn.RoutedExperts(
            config.hidden_size, config.moe_intermediate_size,
            config.num_experts, config.num_experts_per_token,
            held=config.experts_held, norm_topk_prob=config.moe_renormalize,
            use_expert_bias=True,
            routed_scaling_factor=config.routed_scaling_factor)
        self.shared_experts = KimiMLP(
            config.hidden_size,
            config.moe_intermediate_size * config.num_shared_experts)

    def routed(self, u, live):
        """u: Tensor [B, C, H].  Returns (the held experts' part as a Tensor,
        the experts' load)."""
        b, c, h = u.shape
        rows = None if live is None else jnp.repeat(live, c)
        y, load = self.experts.apply(u._value.reshape(b * c, h), rows)
        return Tensor(y.reshape(b, c, h)), load

    def apply(self, u, live):
        y, load = self.routed(u, live)
        with jax.named_scope("moe_shared_expert"):
            return y + self.shared_experts(u), load


class KimiDecoderLayer(nn.Layer):
    def __init__(self, config: KimiLinearConfig, layer_idx: int):
        super().__init__()
        self.kind = config.layer_kinds[layer_idx]
        self.input_layernorm = nn.RMSNorm(config.hidden_size,
                                          config.rms_norm_eps)
        self.self_attn = KimiDeltaAttention(config) if self.kind == "kda" \
            else KimiMLAttention(config)
        self.post_attention_layernorm = nn.RMSNorm(config.hidden_size,
                                                   config.rms_norm_eps)
        self.sparse = layer_idx >= config.first_k_dense_replace
        if self.sparse:
            self.block_sparse_moe = KimiSparseMoe(config)
        else:
            self.mlp = KimiMLP(config.hidden_size, config.intermediate_size)

    def step(self, x, pos0, n_valid, cache, live=None):
        """The layer on rows ``x`` (Tensor [B, C, H]) at positions ``pos0[b]
        + c``, ``n_valid[b]`` of them valid.  ``cache`` is what this layer's
        operator keeps: KDA's ``(tail, state)``, or MLA's ``(arena, tables)``
        (None: no cache, the rows are the whole sequence).  Returns (x,
        cache, load): ``load`` is the expert layer's [num_experts + 1] count
        over the ``live`` rows, None for a dense feed-forward."""
        u = self.input_layernorm(x)
        if self.kind == "kda":
            y, cache = self.self_attn.step(u, n_valid, cache)
        else:
            y, cache = self.self_attn.step(u, pos0, n_valid, cache)
        x = x + y
        u = self.post_attention_layernorm(x)
        if not self.sparse:
            return x + self.mlp(u), cache, None
        y, load = self.block_sparse_moe.apply(u, live)
        return x + y, cache, load


class KimiLinearModel(nn.Layer):
    def __init__(self, config: KimiLinearConfig):
        super().__init__()
        self.embed_tokens = nn.Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=nn.ParamAttr(initializer=Normal(0.0, 0.02)))
        self.layers = nn.LayerList(
            [KimiDecoderLayer(config, i)
             for i in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)


class KimiLinearForCausalLM(nn.Layer):
    def __init__(self, config: KimiLinearConfig):
        super().__init__()
        self.config = config
        self.model = KimiLinearModel(config)
        self.lm_head = _linear(config.hidden_size, config.vocab_size)
        self.n_kda = config.layer_kinds.count("kda")
        self.n_mla = config.num_hidden_layers - self.n_kda

    # -- what the engine's cache manager asks ------------------------------------
    def kv_cache_spec(self):
        """One latent row a token for the MLA layers only; ``_run`` maps the
        model's MLA layers onto the arenas in order."""
        return LatentCacheSpec(self.n_mla, self.config.latent_row)

    def slot_state_spec(self):
        """What a slot keeps beside its blocks: the KDA layers' convolution
        tails in the compute dtype and their matrix states in float32."""
        lin = self.config.linear_attn_config
        nh, d = int(lin["num_heads"]), int(lin["head_dim"])
        return [("kda_conv_tail",
                 (self.n_kda, int(lin["short_conv_kernel_size"]) - 1,
                  3 * nh * d)),
                ("kda_state", (self.n_kda, nh, d, d), jnp.float32)]

    def init_block_counters(self):
        """Zeros of what a decode block counts: rows routed to each expert
        summed over steps and expert layers, the (layer, step) pairs, and the
        experts that got a row summed over those pairs."""
        return jnp.zeros((self.config.num_experts + 2,), jnp.int32)

    @staticmethod
    def poison_slot_state(arenas, finished):
        """The state arenas with the slots that ``finished`` poisoned: the
        tails whole, the matrix states in their first key row a head, from
        which a step's ``S'^T k`` carries NaN into every value channel.  A
        whole row is 8 MB a slot; this is 2 KB a head."""
        from ..inference.llm import _poison_rows
        tails, states = arenas
        b = finished.shape[0]
        first = states[:b, :, :, 0]
        return [_poison_rows(tails, finished),
                states.at[:b, :, :, 0].set(jnp.where(
                    finished[:, None, None, None], jnp.nan, first))]

    # -- the one step ----------------------------------------------------------------
    def _run(self, ids, pos0, n_valid, kvs, tails, states, live=None):
        """ids [B, C] at positions ``pos0[b] + c``, ``n_valid[b]`` valid.
        ``kvs``: one ``(arena, tables)`` an MLA layer, or None (no cache);
        ``tails`` [B, n_kda, taps-1, 3W]; ``states`` [B, n_kda, heads, dk,
        dv], or a ``StateRows`` whose arena the KDA layers update in turn.
        Returns (hidden Tensor [B, C, H] after the final norm, kvs, tails,
        states, counters of ``init_block_counters``'s layout)."""
        x = self.model.embed_tokens(Tensor(ids))
        in_place = isinstance(states, StateRows)
        new_kvs, new_tails, new_states = [], [], []
        counters = self.init_block_counters()
        for layer in self.model.layers:
            if layer.kind == "kda":
                j = len(new_tails)
                cache = (tails[:, j], states._replace(layer=j) if in_place
                         else states[:, j])
            else:
                cache = None if kvs is None else kvs[len(new_kvs)]
            x, cache, load = layer.step(x, pos0, n_valid, cache, live)
            if layer.kind == "kda":
                new_tails.append(cache[0])
                if in_place:
                    states = cache[1]
                else:
                    new_states.append(cache[1])
            else:
                new_kvs.append(cache)
            if load is not None:
                counters = counters + jnp.concatenate(
                    [load[:-1], jnp.ones((1,), jnp.int32), load[-1:]])
        if not in_place:
            states = jnp.stack(new_states, axis=1)
        return (self.model.norm(x), new_kvs, jnp.stack(new_tails, axis=1),
                states, counters)

    def _logits(self, hidden):
        return jnp.dot(hidden, self.lm_head.weight._value.astype(hidden.dtype))

    def _zero_state(self, b, dtype):
        (_, tail), (_, state, state_dtype) = self.slot_state_spec()
        return jnp.zeros((b,) + tail, dtype), \
            jnp.zeros((b,) + state, state_dtype)

    def forward(self, input_ids):
        """Logits [B, S, V] of whole sequences: no cache, state from zeros."""
        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        b, s = ids.shape
        dtype = self.model.embed_tokens.weight._value.dtype
        hidden = self._run(
            ids.astype(jnp.int32), jnp.zeros((b,), jnp.int32),
            jnp.full((b,), s, jnp.int32), None, *self._zero_state(b, dtype))[0]
        return Tensor(self._logits(hidden._value))

    def generate(self, input_ids, seq_lens=None, max_new_tokens=32):
        """Greedy tokens [B, max_new_tokens] after the (right-padded)
        prompts, by the whole-sequence ``forward`` (``generate_by_forward``):
        the plain answer the engine's tokens are compared with."""
        return generate_by_forward(lambda buf: self.forward(buf)._value,
                                   input_ids, seq_lens, max_new_tokens)

    # -- the engine's entry points (inference/llm.py) ---------------------------------
    def decode_step(self, tokens, lens, kvs):
        """One decode step of every slot: tokens [B], lens [B]; ``kvs`` is
        the MLA layers' paged entries and last the slot state ``{"state":
        [tail arena [B + 1, n_kda, taps-1, 3W], matrix-state arena [B + 1,
        n_kda, heads, dk, dv]], "counters", "stale"}``.  A row whose table is
        all trash (vacant, prefilling) or that was ``stale`` when the block
        began (done: its state rows may be poisoned) starts from zeros,
        never from its rows, and writes the arenas' last row, as its latent
        row goes to the trash block."""
        *kvs, slot_state = kvs
        tail_arena, state_arena = slot_state["state"]
        b = tokens.shape[0]
        live = kvs[0][1][:, 0] != kvs[0][0].shape[0] - 1
        if slot_state.get("stale") is not None:
            live = live & ~slot_state["stale"]
        rows = jnp.where(live, jnp.arange(b), tail_arena.shape[0] - 1)
        hidden, kvs, tails, states, counters = self._run(
            tokens[:, None], lens, jnp.ones((b,), jnp.int32), kvs,
            jnp.where(live[:, None, None, None], tail_arena[:b], 0),
            StateRows(state_arena, 0, rows, live), live)
        tail_arena = tail_arena.at[rows].set(tails.astype(tail_arena.dtype))
        slot_state = dict(slot_state, state=[tail_arena, states.arena],
                          counters=slot_state["counters"] + counters)
        return self._logits(hidden._value[:, 0]), kvs + [slot_state]

    def prefill_chunk(self, ids, start, n_valid, kvs):
        """One chunk of one prompt: ids [1, C] at ``start ..``, the prompt
        ``n_valid`` long; the slot state entry carries ``slot``.  Both kinds
        of state start from zeros where ``start == 0`` and from the slot's
        rows otherwise, and the slot's rows are written at the end.  Returns
        the logits at position ``n_valid - 1`` (meaningful on the chunk that
        covers it) and the updated kvs."""
        *kvs, slot_state = kvs
        slot, c = slot_state["slot"], ids.shape[1]
        tails, states = (self._chunk_state(a, slot, start)
                         for a in slot_state["state"])
        count = jnp.clip(n_valid - start, 0, c).astype(jnp.int32)
        hidden, kvs, tails, states, _ = self._run(
            ids, start.reshape(1), count.reshape(1), kvs, tails, states)
        arenas = [a.at[slot].set(new[0].astype(a.dtype)) for a, new in
                  zip(slot_state["state"], (tails, states))]
        last = hidden._value[0, jnp.clip(n_valid - 1 - start, 0, c - 1)]
        return (self._logits(last[None, :]),
                kvs + [dict(slot_state, state=arenas)])

    @staticmethod
    def _chunk_state(arena, slot, start):
        """The state a chunk starts from: zeros at the head of a prompt
        (whoever had the slot before), the slot's row otherwise."""
        return jnp.where(start == 0, 0, arena[slot])[None]

    def verify_step(self, tokens, lens, n_valid, kvs):
        raise SlotStateError(self, "speculative decoding (verify_step: a "
                             "rejected draft would have to roll the state "
                             "back)")
