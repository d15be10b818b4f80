"""Falcon-H1 hybrid decoder (``model_type`` ``falcon_h1``): a Mamba-2 (SSD)
mixer and grouped-query attention side by side in every layer, their outputs
summed, with muP multipliers inside the projections.

Layer ``i`` is ``u = rms(h)``, ``h = h + m_ssm_out SSM(u) + m_attn_out
Attn(m_attn_in u)``, ``h = h + MLP(rms(h))`` with ``MLP(v) = m_down W_d (W_u v
* silu(m_gate W_g v))``; the model is ``h_0 = m_emb E[ids]`` and ``logits =
m_head W_head rms(h_L)``, the head untied.

* ``Attn``: ``q = W_q u``, ``k = m_key W_k u``, ``v = W_v u`` in heads of
  ``head_dim``, RoPE over the whole head on q and k, causal softmax at
  ``head_dim^-1/2`` with each KV head shared by its group of query heads, and
  ``W_o``.  Its keys and values are what ``kv_cache_spec()`` counts: every
  layer keeps them.
* ``SSM`` (``FalconH1Mixer``): ``p = W_in (m_ssm_in u) * mu`` with ``mu`` the
  five ``ssm_multipliers`` laid over the sections ``z``, ``x``, ``B``, ``C``
  and ``dt``; ``xBC = silu(conv(xBC) + b)``, a depthwise causal convolution of
  ``mamba_d_conv`` taps; ``x`` in heads of ``mamba_d_head``, ``B`` and ``C``
  in ``mamba_n_groups`` groups of ``mamba_d_state``; ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)``; the recurrence of ``ops/pallas/ssd.py``;
  ``y = W_out gnorm(y * silu(z))`` with ``gnorm`` an RMSNorm over each
  group's channels.  A slot keeps the last ``mamba_d_conv - 1`` rows of
  ``xBC`` (compute dtype) and every head's state [head, state] in float32:
  ``slot_state_spec()`` names both, and a decode step updates the state in
  place in its arena.

So every layer holds both a KV arena pair and a state row.  There is one step
function a level (``step``, ``_run``): rows of ``C`` positions starting at
``pos0`` of which ``n_valid`` count.  The whole-sequence ``forward`` (no
cache, state from zeros), the chunk of a prefill (one row, ``C =
chunk_len``) and the decode step (a row a slot, ``C = 1``) are that function
at three shapes.
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
from ..nn.initializer import Constant, Normal
from ..ops.pallas.ssd import ssd_chunk, ssd_decode_step
from .generation import (SlotStateError, StateRows, generate_by_forward,
                         paged_verify_scatter, poison_first_rows)


@dataclass
class FalconH1Config:
    """The published keys under their published names.  ``mamba_chunk_size``
    is the chunk of the SSD form (``ssd_chunk``); ``mlp_expansion_factor``
    shapes nothing where ``intermediate_size`` is given, as in the public
    code."""
    vocab_size: int = 261120
    hidden_size: int = 5120
    intermediate_size: int = 21504
    num_hidden_layers: int = 72
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e11
    rope_scaling: Optional[dict] = None
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    mlp_bias: bool = False
    projectors_bias: bool = False
    attn_layer_indices: Optional[list] = None
    mamba_use_mlp: bool = True
    mlp_expansion_factor: int = 8
    num_logits_to_keep: int = 1
    mamba_d_ssm: int = 4096
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_n_groups: int = 2
    mamba_d_state: int = 256
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 128
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    mamba_norm_before_gate: bool = False
    mamba_rms_norm: bool = True
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    mlp_multipliers: list = field(default_factory=lambda: [1.0, 1.0])
    key_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    ssm_multipliers: list = field(
        default_factory=lambda: [1.0, 1.0, 1.0, 1.0, 1.0])
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0

    def __post_init__(self):
        refused = {
            "attn_layer_indices": self.attn_layer_indices is not None,
            "mamba_use_mlp": not self.mamba_use_mlp,
            "hidden_act": self.hidden_act != "silu",
            "rope_scaling": self.rope_scaling is not None,
            "tie_word_embeddings": self.tie_word_embeddings,
            "attention_bias": self.attention_bias,
            "mlp_bias": self.mlp_bias,
            "projectors_bias": self.projectors_bias,
            "mamba_proj_bias": self.mamba_proj_bias,
            "mamba_conv_bias": not self.mamba_conv_bias,
            "mamba_norm_before_gate": self.mamba_norm_before_gate,
            "mamba_rms_norm": not self.mamba_rms_norm,
            "num_logits_to_keep": self.num_logits_to_keep != 1,
            "mamba_d_head": self.mamba_d_head * self.mamba_n_heads
            != self.mamba_d_ssm,
            "mamba_n_groups": self.mamba_n_heads % self.mamba_n_groups != 0,
            "num_key_value_heads":
                self.num_attention_heads % self.num_key_value_heads != 0,
        }
        for key, bad in refused.items():
            if bad:
                raise ValueError(f"{key}={getattr(self, key)!r}: this model "
                                 "has no answer for it")
        # published as the integer 100000000000, past what int32 holds
        self.rope_theta = float(self.rope_theta)

    @property
    def conv_dim(self):
        """The convolved channels: ``x``, then ``B`` and ``C``."""
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    def mup_vector(self):
        """``ssm_multipliers`` laid over ``in_proj``'s sections (z, x, B, C,
        dt), float32."""
        gn = self.mamba_n_groups * self.mamba_d_state
        sizes = (self.mamba_d_ssm, self.mamba_d_ssm, gn, gn,
                 self.mamba_n_heads)
        return jnp.concatenate([jnp.full((n,), m, jnp.float32) for n, m in
                                zip(sizes, self.ssm_multipliers)])


def tiny_falcon_h1_config(**kw):
    """The smallest shape with every mechanism: two groups of heads, more
    query heads than KV heads, multipliers that are not 1."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, head_dim=16, mamba_d_ssm=64,
                mamba_n_heads=8, mamba_d_head=8, mamba_n_groups=2,
                mamba_d_state=16, rope_theta=1e4, mamba_chunk_size=16,
                embedding_multiplier=2.0, lm_head_multiplier=0.5,
                mlp_multipliers=[0.5, 0.75], key_multiplier=0.5,
                attention_in_multiplier=1.0, attention_out_multiplier=0.5,
                ssm_multipliers=[0.5, 0.25, 0.5, 1.0, 0.5],
                ssm_in_multiplier=0.5, ssm_out_multiplier=0.75)
    base.update(kw)
    return FalconH1Config(**base)


def _linear(n_in, n_out):
    return nn.Linear(n_in, n_out, bias_attr=False,
                     weight_attr=nn.ParamAttr(initializer=Normal(0.0, 0.02)))


class FalconH1MLP(nn.Layer):
    def __init__(self, config: FalconH1Config):
        super().__init__()
        h, m = config.hidden_size, config.intermediate_size
        self.gate_proj, self.up_proj, self.down_proj = \
            _linear(h, m), _linear(h, m), _linear(m, h)
        self.gate_multiplier, self.down_multiplier = config.mlp_multipliers

    def forward(self, x):
        return self.down_proj(swiglu(self.gate_proj(x) * self.gate_multiplier,
                                     self.up_proj(x))) * self.down_multiplier


class FalconH1Mixer(nn.Layer):
    """The Mamba-2 mixer (module docstring, ``SSM``)."""

    def __init__(self, config: FalconH1Config):
        super().__init__()
        h = config.hidden_size
        self.heads, self.head_dim = config.mamba_n_heads, config.mamba_d_head
        self.groups, self.state = config.mamba_n_groups, config.mamba_d_state
        self.d_ssm, self.taps = config.mamba_d_ssm, config.mamba_d_conv
        self.chunk, self.eps = config.mamba_chunk_size, config.rms_norm_eps
        self.in_multiplier = config.ssm_in_multiplier
        self.mup = config.mup_vector()
        conv = config.conv_dim
        self.conv_weight = self.create_parameter(
            (conv, self.taps), default_initializer=Normal(0.0, 0.02))
        self.conv_bias = self.create_parameter(
            (conv,), default_initializer=Constant(0.0))
        self.dt_bias = self.create_parameter(
            (self.heads,), default_initializer=Constant(1.0))
        self.A_log = self.create_parameter(
            (self.heads,), default_initializer=Constant(0.0))
        self.D = self.create_parameter(
            (self.heads,), default_initializer=Constant(1.0))
        self.norm_weight = self.create_parameter(
            (self.d_ssm,), default_initializer=Constant(1.0))
        self.in_proj = _linear(h, self.d_ssm + conv + self.heads)
        self.out_proj = _linear(self.d_ssm, h)

    def step(self, u, n_valid, cache):
        """u: Tensor [B, C, H]; ``cache = (tail, state)``: ``tail`` [B,
        taps-1, conv] the rows of ``xBC`` before the first of ``u``; ``state``
        the heads' states before it, [B, heads, head_dim, state] (the rows
        run through ``ssd_chunk``), or a ``StateRows`` (``C = 1``: the step
        runs in place in the arena).  Returns (out Tensor, (the tail and the
        state after the last valid row))."""
        tail, state = cache
        with jax.named_scope("ssm"):
            b, c, _ = u.shape
            f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
            p = f32(self.in_proj(u * self.in_multiplier)._value) * self.mup
            z, xbc, dt = jnp.split(p, [self.d_ssm, p.shape[-1] - self.heads],
                                   axis=-1)
            with jax.named_scope("ssd_conv"):
                # the rows as a slot caches them, in the compute dtype
                xbc = xbc.astype(u._value.dtype)
                zp = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
                taps, zf = f32(self.conv_weight._value), f32(zp)
                conv = jax.nn.silu(sum(taps[:, j] * zf[:, j:j + c]
                                       for j in range(self.taps))
                                   + f32(self.conv_bias._value))
                new_tail = jax.vmap(
                    lambda rows, n: jax.lax.dynamic_slice_in_dim(
                        rows, n, self.taps - 1, axis=0))(zp, n_valid)
            gn = self.groups * self.state
            x = conv[..., :self.d_ssm].reshape(b, c, self.heads,
                                               self.head_dim)
            B = conv[..., self.d_ssm:self.d_ssm + gn].reshape(
                b, c, self.groups, self.state)
            C = conv[..., self.d_ssm + gn:].reshape(b, c, self.groups,
                                                    self.state)
            dt = jax.nn.softplus(dt + f32(self.dt_bias._value))
            A = -jnp.exp(f32(self.A_log._value))
            D = f32(self.D._value)
            if isinstance(state, StateRows):
                y, arena = ssd_decode_step(
                    state.arena, state.layer, state.rows, state.live,
                    x[:, 0], B[:, 0], C[:, 0], dt[:, 0], A, D)
                y, state = y[:, None], state._replace(arena=arena)
            else:
                y, state = ssd_chunk(x, B, C, dt, A, D, state, n_valid,
                                     chunk=self.chunk)
            y = y.reshape(b, c, self.groups, -1) * jax.nn.silu(
                z.reshape(b, c, self.groups, -1))
            y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                                  + self.eps)
            y = y.reshape(b, c, self.d_ssm) * f32(self.norm_weight._value)
            return (self.out_proj(Tensor(y.astype(u._value.dtype))),
                    (new_tail, state))


class FalconH1Attention(nn.Layer):
    """Grouped-query attention with RoPE; keys scaled by ``key_multiplier``
    before RoPE and the cache."""

    def __init__(self, config: FalconH1Config):
        super().__init__()
        h, d = config.hidden_size, config.head_dim
        self.num_heads, self.num_kv_heads = config.num_attention_heads, \
            config.num_key_value_heads
        self.head_dim, self.rope_theta = d, config.rope_theta
        self.key_multiplier = config.key_multiplier
        self.q_proj = _linear(h, self.num_heads * d)
        self.k_proj = _linear(h, self.num_kv_heads * d)
        self.v_proj = _linear(h, self.num_kv_heads * d)
        self.o_proj = _linear(self.num_heads * d, h)

    def step(self, u, pos0, n_valid, kv):
        """u: Tensor [B, C, H] at positions ``pos0[b] + c``.  ``kv`` None:
        causal attention inside the rows.  ``kv = (k_arena, v_arena,
        tables)``: the valid rows' keys and values are written through the
        tables and the queries attend over everything written."""
        from ..ops.pallas.decode_attention import (decode_attention_paged,
                                                   paged_prefix_attention)
        with jax.named_scope("attention"):
            b, c, _ = u.shape
            d = self.head_dim
            q = self.q_proj(u).reshape([b, c, self.num_heads, d])
            k = (self.k_proj(u) * self.key_multiplier).reshape(
                [b, c, self.num_kv_heads, d])
            v = self.v_proj(u).reshape([b, c, self.num_kv_heads, d])
            pos = pos0[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
            q, k = llama_rope(q, k, rotary_emb_base=self.rope_theta,
                              position_ids=pos)
            if kv is None:
                out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
                return self.o_proj(out.reshape([b, c, -1])), None
            k_arena, v_arena, tables = kv
            k_arena = paged_verify_scatter(k_arena, tables, pos0, n_valid,
                                           k._value)
            v_arena = paged_verify_scatter(v_arena, tables, pos0, n_valid,
                                           v._value)
            if c == 1:
                out = decode_attention_paged(q._value[:, 0], k_arena, v_arena,
                                             tables, pos0)
            else:
                out = paged_prefix_attention(q._value, k_arena, v_arena,
                                             tables, pos0)
            return (self.o_proj(Tensor(out.reshape(b, c, -1))),
                    (k_arena, v_arena, tables))


class FalconH1DecoderLayer(nn.Layer):
    def __init__(self, config: FalconH1Config):
        super().__init__()
        self.feed_forward = FalconH1MLP(config)
        self.mamba = FalconH1Mixer(config)
        self.self_attn = FalconH1Attention(config)
        self.input_layernorm = nn.RMSNorm(config.hidden_size,
                                          config.rms_norm_eps)
        self.pre_ff_layernorm = nn.RMSNorm(config.hidden_size,
                                           config.rms_norm_eps)
        self.attn_in = config.attention_in_multiplier
        self.attn_out = config.attention_out_multiplier
        self.ssm_out = config.ssm_out_multiplier

    def step(self, x, pos0, n_valid, kv, ssm):
        """The layer on rows ``x`` (Tensor [B, C, H]) at positions ``pos0[b]
        + c``, ``n_valid[b]`` of them valid.  ``kv`` is the attention's
        ``(k_arena, v_arena, tables)`` (None: no cache, the rows are the whole
        sequence), ``ssm`` the mixer's ``(tail, state)``.  Returns (x, kv,
        ssm)."""
        u = self.input_layernorm(x)
        y_ssm, ssm = self.mamba.step(u, n_valid, ssm)
        y_attn, kv = self.self_attn.step(u * self.attn_in, pos0, n_valid, kv)
        x = x + y_ssm * self.ssm_out + y_attn * self.attn_out
        return x + self.feed_forward(self.pre_ff_layernorm(x)), kv, ssm


class FalconH1Model(nn.Layer):
    def __init__(self, config: FalconH1Config):
        super().__init__()
        self.embed_tokens = nn.Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=nn.ParamAttr(initializer=Normal(0.0, 0.02)))
        self.layers = nn.LayerList(
            [FalconH1DecoderLayer(config)
             for _ in range(config.num_hidden_layers)])
        self.final_layernorm = nn.RMSNorm(config.hidden_size,
                                          config.rms_norm_eps)


class FalconH1ForCausalLM(nn.Layer):
    def __init__(self, config: FalconH1Config):
        super().__init__()
        self.config = config
        self.model = FalconH1Model(config)
        self.lm_head = _linear(config.hidden_size, config.vocab_size)

    # -- what the engine's cache manager asks ------------------------------------
    def kv_cache_spec(self):
        """Every layer's attention keeps keys and values."""
        c = self.config
        return (c.num_hidden_layers, c.num_key_value_heads, c.head_dim)

    def slot_state_spec(self):
        """What a slot keeps beside its blocks: every layer's convolution
        tail in the compute dtype and its heads' states in float32."""
        c = self.config
        return [("ssm_conv_tail",
                 (c.num_hidden_layers, c.mamba_d_conv - 1, c.conv_dim)),
                ("ssm_state", (c.num_hidden_layers, c.mamba_n_heads,
                               c.mamba_d_head, c.mamba_d_state), jnp.float32)]

    # the slots that finished are poisoned in their tails and in the first
    # head channel a head of their states (a row of ``mamba_d_state`` lanes),
    # from which a step's ``y = S C`` carries NaN into that channel of every
    # head and the gated norm over each group into all of them: 1 KB a head
    # where a state is 16.8 MB a slot
    poison_slot_state = staticmethod(poison_first_rows)

    # -- the one step ----------------------------------------------------------------
    def _run(self, ids, pos0, n_valid, kvs, tails, states):
        """ids [B, C] at positions ``pos0[b] + c``, ``n_valid[b]`` valid.
        ``kvs``: one ``(k_arena, v_arena, tables)`` a layer, or None (no
        cache); ``tails`` [B, layers, taps-1, conv]; ``states`` [B, layers,
        heads, head_dim, state], or a ``StateRows`` whose arena the layers
        update in turn.  Returns (hidden Tensor [B, C, H] after the final
        norm, kvs, tails, states)."""
        x = self.model.embed_tokens(Tensor(ids)) \
            * self.config.embedding_multiplier
        in_place = isinstance(states, StateRows)
        new_kvs, new_tails, new_states = [], [], []
        for i, layer in enumerate(self.model.layers):
            kv = None if kvs is None else kvs[i]
            ssm = (tails[:, i], states._replace(layer=i) if in_place
                   else states[:, i])
            x, kv, (tail, state) = layer.step(x, pos0, n_valid, kv, ssm)
            new_kvs.append(kv)
            new_tails.append(tail)
            if in_place:
                states = state
            else:
                new_states.append(state)
        if not in_place:
            states = jnp.stack(new_states, axis=1)
        return (self.model.final_layernorm(x), new_kvs,
                jnp.stack(new_tails, axis=1), states)

    def _logits(self, hidden):
        head = self.lm_head.weight._value.astype(hidden.dtype)
        return jnp.dot(hidden, head) * self.config.lm_head_multiplier

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
        the layers' paged entries and last the slot state ``{"state": [tail
        arena [B + 1, layers, taps-1, conv], state arena [B + 1, layers,
        heads, head_dim, state]], "stale"}``.  A row whose table is all trash
        (vacant, prefilling) or that was ``stale`` when the block began
        (done: its state rows may be poisoned) starts from zeros, never from
        its rows, and writes the arenas' last row, as its keys go to the
        trash block."""
        *kvs, slot_state = kvs
        tail_arena, state_arena = slot_state["state"]
        b = tokens.shape[0]
        live = kvs[0][2][:, 0] != kvs[0][0].shape[0] - 1
        if slot_state.get("stale") is not None:
            live = live & ~slot_state["stale"]
        rows = jnp.where(live, jnp.arange(b), tail_arena.shape[0] - 1)
        hidden, kvs, tails, states = self._run(
            tokens[:, None], lens, jnp.ones((b,), jnp.int32), kvs,
            jnp.where(live[:, None, None, None], tail_arena[:b], 0),
            StateRows(state_arena, 0, rows, live))
        tail_arena = tail_arena.at[rows].set(tails.astype(tail_arena.dtype))
        return (self._logits(hidden._value[:, 0]),
                kvs + [dict(slot_state, state=[tail_arena, states.arena])])

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
        hidden, kvs, tails, states = self._run(
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
