"""LFM2 sparse hybrid decoder (``model_type`` ``lfm2_moe``): layers of two
kinds in one model and two kinds of state on the serving path.

Layer ``i`` is ``h = h + Op_i(rms(h))``, ``h = h + FFN_i(rms(h))``.  ``Op_i``
is a gated short convolution where ``layer_types[i] == "conv"`` and grouped-
query attention (queries and keys RMS-normalised per head before RoPE) where
``"full_attention"``; ``FFN_i`` is a dense SwiGLU in the first
``num_dense_layers`` layers and ``nn.RoutedExperts`` after them.  The head is
tied to the embedding.

Only the attention layers keep keys and values, so ``kv_cache_spec()`` counts
those and the engine makes arenas for them alone.  A convolution layer keeps
the last ``conv_L_cache - 1`` rows of its gated input: ``slot_state_spec()``
names that per-slot state, the engine keeps one arena of it beside the paged
KV (``models/generation.py`` ``init_slot_state``) and hands it to the step
entry points as the last entry of ``kvs``.

There is one step function a level (``Lfm2DecoderLayer.step``,
``Lfm2MoeForCausalLM._run``): rows of ``C`` positions starting at ``pos0``
of which ``n_valid`` count.  The whole-sequence ``forward`` (no cache, state
from zeros), the chunk of a prefill (one row, ``C = chunk_len``) and the
decode step (a row a slot, ``C = 1``) are that function at three shapes.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..incubate.nn.functional import llama_rope, swiglu
from ..nn import functional as F
from ..nn.initializer import Normal
from .generation import (SlotStateError, generate_by_forward,
                         paged_verify_scatter)

LAYER_KINDS = ("conv", "full_attention")


@dataclass
class Lfm2MoeConfig:
    """The published keys under their published names, and ``head_dim``
    (not published: hidden over heads)."""
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 40
    layer_types: Optional[list] = None
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: Optional[int] = None
    max_position_embeddings: int = 128000
    norm_eps: float = 1e-5
    conv_L_cache: int = 3
    conv_bias: bool = False
    num_experts: int = 64
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    rope_parameters: dict = field(default_factory=lambda: {
        "rope_theta": 1000000.0, "rope_type": "default"})
    tie_word_embeddings: bool = True

    def __post_init__(self):
        if self.layer_types is None:
            # the published pattern: two leading convolutions, then an
            # attention layer at the head of every period of four
            self.layer_types = [
                "full_attention" if i >= 2 and (i - 2) % 4 == 0 else "conv"
                for i in range(self.num_hidden_layers)]
        self.layer_types = list(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers or \
                any(t not in LAYER_KINDS for t in self.layer_types):
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers, "
                f"each one of {LAYER_KINDS}; got {self.layer_types}")
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_attention_heads
        if self.conv_bias:
            raise ValueError("conv_bias=True: the short convolution here "
                             "has no bias")
        if self.rope_parameters.get("rope_type", "default") != "default":
            raise ValueError(f"rope_parameters {self.rope_parameters}: only "
                             "the default rotary embedding is implemented")
        if not self.tie_word_embeddings:
            raise ValueError("tie_word_embeddings=False: the head here is "
                             "the embedding")

    @property
    def rope_theta(self):
        return float(self.rope_parameters["rope_theta"])


def tiny_lfm2_config(**kw):
    """The smallest shape with every mechanism: both operators, a dense and
    an expert feed-forward, more experts than a token picks."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                moe_intermediate_size=32, num_hidden_layers=6,
                layer_types=["conv", "conv", "full_attention", "conv",
                             "conv", "full_attention"],
                num_dense_layers=1, num_attention_heads=4,
                num_key_value_heads=2, max_position_embeddings=256,
                num_experts=8, num_experts_per_tok=2)
    base.update(kw)
    return Lfm2MoeConfig(**base)


def _linear(n_in, n_out):
    return nn.Linear(n_in, n_out, bias_attr=False,
                     weight_attr=nn.ParamAttr(initializer=Normal(0.0, 0.02)))


class Lfm2ShortConv(nn.Layer):
    """``B, C, x = split3(in_proj(u))``; ``z = B * x``; a depthwise causal
    convolution of ``conv_L_cache`` taps over ``z``; ``out_proj(C * conv)``.
    What a sequence carries from one call to the next is its last
    ``conv_L_cache - 1`` rows of ``z``."""

    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        h = config.hidden_size
        self.in_proj = _linear(h, 3 * h)
        self.conv_weight = self.create_parameter(
            (h, config.conv_L_cache), default_initializer=Normal(0.0, 0.02))
        self.out_proj = _linear(h, h)

    def step(self, u, n_valid, tail):
        """u: Tensor [B, C, H]; tail [B, L-1, H]: the rows of ``z`` before
        the first of ``u``; ``n_valid`` [B] of the C rows count.  Returns
        (out Tensor, the tail after the last valid row)."""
        with jax.named_scope("short_conv"):
            c = u.shape[1]
            gate_b, gate_c, x = jnp.split(self.in_proj(u)._value, 3, axis=-1)
            zp = jnp.concatenate([tail.astype(x.dtype), gate_b * x], axis=1)
            taps = self.conv_weight._value.astype(x.dtype)
            n_taps = taps.shape[1]
            conv = sum(taps[:, j] * zp[:, j:j + c] for j in range(n_taps))
            # rows n_valid .. n_valid+L-2 of [tail, z] are the last L-1
            # valid rows; with fewer valid rows than that they reach back
            # into the old tail
            new_tail = jax.vmap(lambda rows, n: jax.lax.dynamic_slice_in_dim(
                rows, n, n_taps - 1, axis=0))(zp, n_valid)
            return self.out_proj(Tensor(gate_c * conv)), new_tail


class Lfm2Attention(nn.Layer):
    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        h, d = config.hidden_size, config.head_dim
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim, self.rope_theta = d, config.rope_theta
        self.q_proj = _linear(h, self.num_heads * d)
        self.k_proj = _linear(h, self.num_kv_heads * d)
        self.v_proj = _linear(h, self.num_kv_heads * d)
        self.out_proj = _linear(self.num_heads * d, h)
        self.q_layernorm = nn.RMSNorm(d, config.norm_eps)
        self.k_layernorm = nn.RMSNorm(d, config.norm_eps)

    def step(self, u, pos0, n_valid, kv):
        """u: Tensor [B, C, H] at positions ``pos0[b] + c``.  ``kv`` None:
        causal attention inside the rows.  ``kv = (k_arena, v_arena,
        tables)``: the valid rows' keys and values are written through the
        tables and the queries attend over everything written."""
        from ..ops.pallas.decode_attention import (decode_attention_paged,
                                                   paged_prefix_attention)
        b, c, _ = u.shape
        d = self.head_dim
        q = self.q_layernorm(self.q_proj(u).reshape([b, c, self.num_heads, d]))
        k = self.k_layernorm(self.k_proj(u).reshape(
            [b, c, self.num_kv_heads, d]))
        v = self.v_proj(u).reshape([b, c, self.num_kv_heads, d])
        pos = pos0[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
        q, k = llama_rope(q, k, rotary_emb_base=self.rope_theta,
                          position_ids=pos)
        if kv is None:
            out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
            return self.out_proj(out.reshape([b, c, -1])), None
        k_arena, v_arena, tables = kv
        k_arena = paged_verify_scatter(k_arena, tables, pos0, n_valid,
                                       k._value)
        v_arena = paged_verify_scatter(v_arena, tables, pos0, n_valid,
                                       v._value)
        if c == 1:
            out = decode_attention_paged(q._value[:, 0], k_arena, v_arena,
                                         tables, pos0)
        else:
            out = paged_prefix_attention(q._value, k_arena, v_arena, tables,
                                         pos0)
        return (self.out_proj(Tensor(out.reshape(b, c, -1))),
                (k_arena, v_arena, tables))


class Lfm2MLP(nn.Layer):
    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        h, m = config.hidden_size, config.intermediate_size
        self.w1, self.w3, self.w2 = _linear(h, m), _linear(h, m), _linear(m, h)

    def forward(self, x):
        return self.w2(swiglu(self.w1(x), self.w3(x)))


class Lfm2DecoderLayer(nn.Layer):
    def __init__(self, config: Lfm2MoeConfig, layer_idx: int):
        super().__init__()
        self.kind = config.layer_types[layer_idx]
        self.operator_norm = nn.RMSNorm(config.hidden_size, config.norm_eps)
        if self.kind == "conv":
            self.conv = Lfm2ShortConv(config)
        else:
            self.self_attn = Lfm2Attention(config)
        self.ffn_norm = nn.RMSNorm(config.hidden_size, config.norm_eps)
        self.sparse = layer_idx >= config.num_dense_layers
        if self.sparse:
            self.feed_forward = nn.RoutedExperts(
                config.hidden_size, config.moe_intermediate_size,
                config.num_experts, config.num_experts_per_tok,
                norm_topk_prob=config.norm_topk_prob,
                use_expert_bias=config.use_expert_bias,
                routed_scaling_factor=config.routed_scaling_factor)
        else:
            self.feed_forward = Lfm2MLP(config)

    def step(self, x, pos0, n_valid, cache, live=None):
        """The layer on rows ``x`` (Tensor [B, C, H]) at positions ``pos0[b]
        + c``, ``n_valid[b]`` of them valid.  ``cache`` is what this layer's
        operator keeps: a convolution's tail [B, L-1, H], or an attention's
        ``(k_arena, v_arena, tables)`` (None: no cache, the rows are the
        whole sequence).  Returns (x, cache, load): ``load`` is the expert
        layer's [num_experts + 1] count over the ``live`` rows, None for a
        dense feed-forward."""
        u = self.operator_norm(x)
        if self.kind == "conv":
            y, cache = self.conv.step(u, n_valid, cache)
        else:
            y, cache = self.self_attn.step(u, pos0, n_valid, cache)
        x = x + y
        u = self.ffn_norm(x)
        if not self.sparse:
            return x + self.feed_forward(u), cache, None
        b, c, h = u.shape
        rows = None if live is None else jnp.repeat(live, c)
        y, load = self.feed_forward.apply(u._value.reshape(b * c, h), rows)
        return x + Tensor(y.reshape(b, c, h)), cache, load


class Lfm2MoeModel(nn.Layer):
    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        self.embed_tokens = nn.Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=nn.ParamAttr(initializer=Normal(0.0, 0.02)))
        self.layers = nn.LayerList(
            [Lfm2DecoderLayer(config, i)
             for i in range(config.num_hidden_layers)])
        self.embedding_norm = nn.RMSNorm(config.hidden_size, config.norm_eps)


class Lfm2MoeForCausalLM(nn.Layer):
    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        self.config = config
        self.lfm2 = Lfm2MoeModel(config)
        self.n_conv = sum(t == "conv" for t in config.layer_types)
        self.n_attn = config.num_hidden_layers - self.n_conv

    # -- what the engine's cache manager asks ------------------------------------
    def kv_cache_spec(self):
        """Arenas for the attention layers only; ``_run`` maps the model's
        attention layers onto them in order."""
        return (self.n_attn, self.config.num_key_value_heads,
                self.config.head_dim)

    def slot_state_spec(self):
        """What a slot keeps beside its blocks: ``[(name, shape a slot)]``."""
        return [("conv_tail", (self.n_conv, self.config.conv_L_cache - 1,
                               self.config.hidden_size))]

    def init_block_counters(self):
        """Zeros of what a decode block counts: rows routed to each expert
        summed over steps and expert layers, the (layer, step) pairs, and the
        experts that got a row summed over those pairs."""
        return jnp.zeros((self.config.num_experts + 2,), jnp.int32)

    # -- the one step ----------------------------------------------------------------
    def _run(self, ids, pos0, n_valid, kvs, tails, live=None):
        """ids [B, C] at positions ``pos0[b] + c``, ``n_valid[b]`` valid.
        ``kvs``: one ``(k_arena, v_arena, tables)`` an attention layer, or
        None (no cache); ``tails`` [B, n_conv, L-1, H].  Returns (hidden
        Tensor [B, C, H] after the final norm, kvs, tails, counters of
        ``init_block_counters``'s layout)."""
        x = self.lfm2.embed_tokens(Tensor(ids))
        new_kvs, new_tails = [], []
        counters = self.init_block_counters()
        for layer in self.lfm2.layers:
            if layer.kind == "conv":
                cache = tails[:, len(new_tails)]
            else:
                cache = None if kvs is None else kvs[len(new_kvs)]
            x, cache, load = layer.step(x, pos0, n_valid, cache, live)
            (new_tails if layer.kind == "conv" else new_kvs).append(cache)
            if load is not None:
                counters = counters + jnp.concatenate(
                    [load[:-1], jnp.ones((1,), jnp.int32), load[-1:]])
        return (self.lfm2.embedding_norm(x), new_kvs,
                jnp.stack(new_tails, axis=1), counters)

    def _logits(self, hidden):
        """The head, tied to the embedding."""
        return jnp.dot(hidden, self.lfm2.embed_tokens.weight._value.T
                       .astype(hidden.dtype))

    def _zero_tails(self, b, dtype):
        (_, shape), = self.slot_state_spec()
        return jnp.zeros((b,) + shape, dtype)

    def forward(self, input_ids):
        """Logits [B, S, V] of whole sequences: no cache, state from zeros."""
        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        b, s = ids.shape
        dtype = self.lfm2.embed_tokens.weight._value.dtype
        hidden, _, _, _ = self._run(
            ids.astype(jnp.int32), jnp.zeros((b,), jnp.int32),
            jnp.full((b,), s, jnp.int32), None, self._zero_tails(b, dtype))
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
        the attention layers' paged entries and last the slot state
        ``{"state": [tail arena [B + 1, n_conv, L-1, H]], "counters",
        "stale"}``.  A row whose table is all trash (vacant, prefilling)
        or that was ``stale`` when the block began (done: its state row may
        be poisoned, ``inference/llm.py`` ``_poison_rows``) starts from
        zeros, never from its row, and writes the arena's last row, as
        its keys go to the trash block."""
        *kvs, slot_state = kvs
        arena, = slot_state["state"]
        b = tokens.shape[0]
        live = kvs[0][2][:, 0] != kvs[0][0].shape[0] - 1
        if slot_state.get("stale") is not None:
            live = live & ~slot_state["stale"]
        hidden, kvs, tails, counters = self._run(
            tokens[:, None], lens, jnp.ones((b,), jnp.int32), kvs,
            jnp.where(live[:, None, None, None], arena[:b], 0), live)
        rows = jnp.where(live, jnp.arange(b), arena.shape[0] - 1)
        arena = arena.at[rows].set(tails.astype(arena.dtype))
        slot_state = dict(slot_state, state=[arena],
                          counters=slot_state["counters"] + counters)
        return self._logits(hidden._value[:, 0]), kvs + [slot_state]

    def prefill_chunk(self, ids, start, n_valid, kvs):
        """One chunk of one prompt: ids [1, C] at ``start ..``, the prompt
        ``n_valid`` long; the slot state entry carries ``slot``.  The state
        starts from zeros where ``start == 0`` and from the slot's row
        otherwise, and the slot's row is written at the end.  Returns the
        logits at position ``n_valid - 1`` (meaningful on the chunk that
        covers it) and the updated kvs."""
        *kvs, slot_state = kvs
        arena, = slot_state["state"]
        slot, c = slot_state["slot"], ids.shape[1]
        tails = self._chunk_tails(arena, slot, start)
        count = jnp.clip(n_valid - start, 0, c).astype(jnp.int32)
        hidden, kvs, tails, _ = self._run(
            ids, start.reshape(1), count.reshape(1), kvs, tails)
        arena = arena.at[slot].set(tails[0].astype(arena.dtype))
        last = hidden._value[0, jnp.clip(n_valid - 1 - start, 0, c - 1)]
        return (self._logits(last[None, :]),
                kvs + [dict(slot_state, state=[arena])])

    @staticmethod
    def _chunk_tails(arena, slot, start):
        """The state a chunk starts from: zeros at the head of a prompt
        (whoever had the slot before), the slot's row otherwise."""
        return jnp.where(start == 0, 0, arena[slot])[None]

    def verify_step(self, tokens, lens, n_valid, kvs):
        raise SlotStateError(self, "speculative decoding (verify_step: a "
                             "rejected draft would have to roll the state "
                             "back)")
