"""LongCat-Flash decoder (``LongCat-Flash-Chat``): every layer a "double
layer" of two latent-attention sublayers with a low-rank query and a rotary
part, two dense SwiGLU feed-forwards, and one shortcut-connected MoE (ScMoE)
whose router picks among real and zero-compute experts.

Layer, with ``rms_*`` RMSNorms::

    h1  = x  + MLA_0(rms_in0(x))
    u   = rms_post0(h1)
    m   = MoE(u)                          # the shortcut branch
    h2  = h1 + FFN_0(u)
    h3  = h2 + MLA_1(rms_in1(h2))
    out = h3 + FFN_1(rms_post1(h3)) + m

``MoE`` is ``nn.RoutedExperts`` with a softmax over all ``n_routed_experts +
zero_expert_num`` router outputs (float32), the ``moe_topk`` picked by score
plus the correction bias (which picks and does not weigh), no
renormalisation, the picked scores times ``routed_scaling_factor``; ids from
``n_routed_experts`` on are zero-compute (identity) experts, a pick of which
adds its weight times ``u``.  ``MLA`` is ``models/mla.py``'s layer with the
low-rank query, the LoRA scales ``sqrt(hidden / q_lora_rank)`` on ``q`` and
``sqrt(hidden / kv_lora_rank)`` on the normed latent (``mla_scale_q_lora``,
``mla_scale_kv_lora``) and the rotary part at ``rope_theta``.  A final RMSNorm
and an untied head.

The only state is the latent rows: ``kv_cache_spec()`` answers a
``LatentCacheSpec`` of two arenas a layer (sublayer 0, then 1), ``[c, k_pe]``
a token each.  There is one step function a level (``step``, ``_run``): rows
of ``C`` positions starting at ``pos0`` of which ``n_valid`` count; the
whole-sequence ``forward``, a prefill chunk and the decode step are that
function at three shapes.  A decode block counts its experts' load
(``init_block_counters``), zero-compute picks apart.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..nn.initializer import Normal
from .generation import LatentCacheSpec, generate_by_forward
from .kimi_linear import KimiMLP
from .mla import LatentAttention


@dataclass
class LongCatFlashConfig:
    """The published keys under their published names.  Not published:
    ``experts_held`` (``(first, count)`` of the ``n_routed_experts`` whose
    planes this model has; all by default).  ``vocab_size`` is what the
    embedding and the head hold, a slice where the vocabulary is sliced."""
    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28
    num_attention_heads: int = 64
    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = 1536
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    qk_nope_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    routed_scaling_factor: float = 6.0
    n_routed_experts: int = 512
    zero_expert_num: int = 256
    zero_expert_type: str = "identity"
    moe_topk: int = 12
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000000.0
    attention_method: str = "MLA"
    attention_bias: bool = False
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        refused = {
            "attention_method": self.attention_method != "MLA",
            "attention_bias": self.attention_bias,
            "zero_expert_type": self.zero_expert_type != "identity",
            # the scale is of the low-rank query's width
            "mla_scale_q_lora": self.mla_scale_q_lora
            and self.q_lora_rank is None,
        }
        for key, bad in refused.items():
            if bad:
                raise ValueError(f"{key}={getattr(self, key)!r}: this model "
                                 "has no answer for it")
        if self.experts_held is None:
            self.experts_held = (0, self.n_routed_experts)
        self.experts_held = tuple(int(x) for x in self.experts_held)

    @property
    def latent_row(self):
        return self.kv_lora_rank + self.qk_rope_head_dim


def tiny_longcat_flash_config(**kw):
    """The smallest shape with every mechanism: two double layers, a
    low-rank query, more real and zero-compute experts than a token picks."""
    base = dict(vocab_size=256, hidden_size=64, ffn_hidden_size=128,
                expert_ffn_hidden_size=32, num_layers=2,
                num_attention_heads=4, kv_lora_rank=32, q_lora_rank=48,
                qk_rope_head_dim=8, v_head_dim=16, qk_nope_head_dim=16,
                n_routed_experts=8, zero_expert_num=4, moe_topk=3,
                rope_theta=10000.0)
    base.update(kw)
    return LongCatFlashConfig(**base)


class LongCatFlashDecoderLayer(nn.Layer):
    """One double layer (the module's docstring)."""

    def __init__(self, config: LongCatFlashConfig):
        super().__init__()
        h, eps = config.hidden_size, config.rms_norm_eps
        q_rank = config.q_lora_rank
        self.input_layernorm = nn.LayerList(
            [nn.RMSNorm(h, eps) for _ in range(2)])
        self.self_attn = nn.LayerList([LatentAttention(
            h, config.num_attention_heads, config.qk_nope_head_dim,
            config.qk_rope_head_dim, config.v_head_dim, config.kv_lora_rank,
            eps, q_lora_rank=q_rank, rope_theta=config.rope_theta,
            q_scale=(h / q_rank) ** 0.5 if config.mla_scale_q_lora else 1.0,
            kv_scale=(h / config.kv_lora_rank) ** 0.5
            if config.mla_scale_kv_lora else 1.0) for _ in range(2)])
        self.post_attention_layernorm = nn.LayerList(
            [nn.RMSNorm(h, eps) for _ in range(2)])
        self.mlps = nn.LayerList(
            [KimiMLP(h, config.ffn_hidden_size) for _ in range(2)])
        self.mlp = nn.RoutedExperts(
            h, config.expert_ffn_hidden_size, config.n_routed_experts,
            config.moe_topk, held=config.experts_held, norm_topk_prob=False,
            use_expert_bias=True,
            routed_scaling_factor=config.routed_scaling_factor,
            zero_experts=config.zero_expert_num, scoring="softmax")

    def moe(self, u, live):
        """The shortcut branch on ``u`` (Tensor [B, C, H]): (the held and
        zero-compute experts' part as a Tensor, the load of
        ``RoutedExperts.apply``)."""
        b, c, h = u.shape
        rows = None if live is None else jnp.repeat(live, c)
        y, load = self.mlp.apply(u._value.reshape(b * c, h), rows)
        return Tensor(y.reshape(b, c, h)), load

    def step(self, x, pos0, n_valid, cache, live=None):
        """The layer on rows ``x`` (Tensor [B, C, H]) at positions ``pos0[b]
        + c``, ``n_valid[b]`` of them valid.  ``cache``: the two sublayers'
        ``(arena, tables)``, or None (the rows are the whole sequence).
        Returns (x, cache, the MoE's load over the ``live`` rows)."""
        kv0, kv1 = (None, None) if cache is None else cache
        y, kv0 = self.self_attn[0].step(self.input_layernorm[0](x), pos0,
                                        n_valid, kv0)
        h = x + y
        u = self.post_attention_layernorm[0](h)
        m, load = self.moe(u, live)
        h = h + self.mlps[0](u)
        y, kv1 = self.self_attn[1].step(self.input_layernorm[1](h), pos0,
                                        n_valid, kv1)
        h = h + y
        h = h + self.mlps[1](self.post_attention_layernorm[1](h)) + m
        return h, (kv0, kv1), load


class LongCatFlashModel(nn.Layer):
    def __init__(self, config: LongCatFlashConfig):
        super().__init__()
        self.embed_tokens = nn.Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=nn.ParamAttr(initializer=Normal(0.0, 0.02)))
        self.layers = nn.LayerList(
            [LongCatFlashDecoderLayer(config)
             for _ in range(config.num_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)


class LongCatFlashForCausalLM(nn.Layer):
    # the block counters carry the zero-compute picks and all picks
    # (inference/serving.py ``_count_experts``)
    counts_picks = True

    def __init__(self, config: LongCatFlashConfig):
        super().__init__()
        self.config = config
        self.model = LongCatFlashModel(config)
        self.lm_head = nn.Linear(
            config.hidden_size, config.vocab_size, bias_attr=False,
            weight_attr=nn.ParamAttr(initializer=Normal(0.0, 0.02)))

    # -- what the engine's cache manager asks ------------------------------------
    def kv_cache_spec(self):
        """One latent row a token for each of a layer's two sublayers:
        arena ``2 i + j`` is layer ``i``'s sublayer ``j``."""
        return LatentCacheSpec(2 * self.config.num_layers,
                               self.config.latent_row)

    def init_block_counters(self):
        """Zeros of what a decode block counts: rows routed to each real
        expert, the picks that fell on zero-compute experts and all picks,
        summed over steps and layers; the (layer, step) pairs; and the real
        experts that got a row summed over those pairs."""
        return jnp.zeros((self.config.n_routed_experts + 4,), jnp.int32)

    # -- the one step ----------------------------------------------------------------
    def _run(self, ids, pos0, n_valid, kvs, live=None):
        """ids [B, C] at positions ``pos0[b] + c``, ``n_valid[b]`` valid.
        ``kvs``: the ``2 x num_layers`` ``(arena, tables)``, or None (no
        cache).  Returns (hidden Tensor [B, C, H] after the final norm, kvs,
        counters of ``init_block_counters``'s layout)."""
        x = self.model.embed_tokens(Tensor(ids))
        new_kvs = []
        counters = self.init_block_counters()
        for i, layer in enumerate(self.model.layers):
            cache = None if kvs is None else (kvs[2 * i], kvs[2 * i + 1])
            x, cache, load = layer.step(x, pos0, n_valid, cache, live)
            new_kvs += list(cache)
            counters = counters + jnp.concatenate(
                [load[:-1], jnp.ones((1,), jnp.int32), load[-1:]])
        return self.model.norm(x), new_kvs, counters

    def _logits(self, hidden):
        return jnp.dot(hidden, self.lm_head.weight._value.astype(hidden.dtype))

    def forward(self, input_ids):
        """Logits [B, S, V] of whole sequences, no cache."""
        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        b, s = ids.shape
        hidden = self._run(ids.astype(jnp.int32), jnp.zeros((b,), jnp.int32),
                           jnp.full((b,), s, jnp.int32), None)[0]
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
        the latent arenas' paged entries and last the block's own entry
        ``{"state": [], "stale", "counters"}``.  A row whose table is all
        trash (vacant, prefilling) or that was ``stale`` when the block
        began (done) is left out of the counters; its latent rows go to the
        trash block."""
        *kvs, block = kvs
        b = tokens.shape[0]
        live = kvs[0][1][:, 0] != kvs[0][0].shape[0] - 1
        if block.get("stale") is not None:
            live = live & ~block["stale"]
        hidden, kvs, counters = self._run(
            tokens[:, None], lens, jnp.ones((b,), jnp.int32), kvs, live)
        return self._logits(hidden._value[:, 0]), \
            kvs + [dict(block, counters=block["counters"] + counters)]

    def prefill_chunk(self, ids, start, n_valid, kvs):
        """One chunk of one prompt: ids [1, C] at ``start ..``, the prompt
        ``n_valid`` long.  Returns the logits at position ``n_valid - 1``
        (meaningful on the chunk that covers it) and the updated kvs."""
        c = ids.shape[1]
        count = jnp.clip(n_valid - start, 0, c).astype(jnp.int32)
        hidden, kvs, _ = self._run(ids, start.reshape(1), count.reshape(1),
                                   kvs)
        last = hidden._value[0, jnp.clip(n_valid - 1 - start, 0, c - 1)]
        return self._logits(last[None, :]), kvs

    def verify_step(self, tokens, lens, n_valid, kvs):
        raise NotImplementedError(
            "LongCatFlashForCausalLM has no speculative decoding "
            "(verify_step)")
