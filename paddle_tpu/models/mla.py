"""Multi-head latent attention (MLA), one layer for every model that keeps a
latent paged cache.

``q = s_q W_q x``, or with a low-rank query ``q = s_q W_qb rms(W_qa x)``, as
heads of ``[q_nope, q_pe]``; ``[c~, k_pe] = W_kva x``, ``c = s_kv rms(c~)``;
``[k_nope, v]_h = W_kvb,h c``; ``k_h = [k_nope_h, k_pe]`` with ``k_pe``
shared by the heads; causal softmax of ``q_h . k_h / sqrt(nope + rope)``;
``W_o`` over the heads' values.  With a rotary part, ``q_pe`` and ``k_pe``
are rotated at their positions before anything else sees them (pairs
``(x[2i], x[2i+1])`` at angle ``pos theta^(-2i/rope)``); without one (NoPE)
they are dotted as they are.  ``s_q`` and ``s_kv`` are the LoRA scales (1
where a model has none).

What a token caches is ``[c, k_pe]``, ``kv_lora_rank + qk_rope_head_dim``
values: the scaled latent and the rotated positional key.  Decode runs
absorbed (the up-projections folded into the query and the output, every head
over the one shared row: ``ops/pallas/decode_attention.
decode_attention_latent``), and so does a prompt chunk where the kernel is
taken, its query tiles each reading the slot's rows only up to their causal
frontier (``latent_chunk_attention``, route counter
``pallas.latent_chunk.route``); elsewhere a chunk runs expanded over the
slot's gathered rows.  The query's scale is applied before ``q`` is split,
so the absorbed query ``q_lat = W_kvb^K^T q_nope`` carries it, and the
latent's scale is in the cached row: the kernel sees neither.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.tensor import Tensor
from ..nn.initializer import Normal
from .generation import paged_verify_scatter


def _linear(n_in, n_out):
    return nn.Linear(n_in, n_out, bias_attr=False,
                     weight_attr=nn.ParamAttr(initializer=Normal(0.0, 0.02)))


def rotate_pairs(x, pos, inv_freq):
    """``x`` [B, C, ..., R] rotated at positions ``pos`` [B, C]: the pair
    ``(x[2i], x[2i+1])`` by ``pos * inv_freq[i]``, in float32, returned in
    ``x``'s dtype."""
    ang = pos.astype(jnp.float32)[..., None] * inv_freq
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[2:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    x0, x1 = xf[..., 0::2], xf[..., 1::2]
    out = jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


class LatentAttention(nn.Layer):
    """MLA (the module's docstring).  ``q_lora_rank`` None: a full-rank
    query; ``rope_theta`` None: no rotary part; ``q_scale``, ``kv_scale``:
    the LoRA scales."""

    def __init__(self, hidden_size, num_heads, qk_nope_head_dim,
                 qk_rope_head_dim, v_head_dim, kv_lora_rank, eps, *,
                 q_lora_rank: Optional[int] = None,
                 rope_theta: Optional[float] = None, q_scale=1.0,
                 kv_scale=1.0):
        super().__init__()
        h = hidden_size
        self.num_heads = num_heads
        self.nope, self.rope = qk_nope_head_dim, qk_rope_head_dim
        self.v_dim, self.rank = v_head_dim, kv_lora_rank
        self.scale = (self.nope + self.rope) ** -0.5
        self.q_scale, self.kv_scale = float(q_scale), float(kv_scale)
        self.inv_freq = None if rope_theta is None else (
            1.0 / float(rope_theta) ** (np.arange(0, self.rope, 2)
                                        / self.rope)).astype(np.float32)
        width = num_heads * (self.nope + self.rope)
        self.q_lora_rank = q_lora_rank
        if q_lora_rank is None:
            self.q_proj = _linear(h, width)
        else:
            self.q_a_proj = _linear(h, q_lora_rank)
            self.q_a_layernorm = nn.RMSNorm(q_lora_rank, eps)
            self.q_b_proj = _linear(q_lora_rank, width)
        self.kv_a_proj_with_mqa = _linear(h, self.rank + self.rope)
        self.kv_a_layernorm = nn.RMSNorm(self.rank, eps)
        self.kv_b_proj = _linear(self.rank,
                                 num_heads * (self.nope + self.v_dim))
        self.o_proj = _linear(num_heads * self.v_dim, h)

    def _query(self, u):
        if self.q_lora_rank is None:
            q = self.q_proj(u)._value
        else:
            q = self.q_b_proj(self.q_a_layernorm(self.q_a_proj(u)))._value
        if self.q_scale != 1.0:
            q = q * self.q_scale
        return q

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

    def _absorb_query(self, q_nope, q_pe, arena):
        """``[q_lat, q_pe]`` with ``q_lat = W_kvb^K^T q_nope``, zero-padded
        to the arena's row and in its dtype, and ``W_kvb^V`` for the
        output: the up-projections folded into the query and the output."""
        w_k, w_v = self._kv_b()
        q_lat = jnp.einsum("...hn,rhn->...hr", q_nope, w_k)
        q = jnp.concatenate([q_lat, q_pe], axis=-1)
        pad = [(0, 0)] * (q.ndim - 1) + [(0, arena.shape[-1] - q.shape[-1])]
        return jnp.pad(q, pad).astype(arena.dtype), w_v

    def _absorbed(self, q_nope, q_pe, arena, tables, lens):
        """One token a row over the latent arena, absorbed
        (``_absorb_query``): every head reads the one shared row a
        token."""
        from ..ops.pallas.decode_attention import decode_attention_latent
        with jax.named_scope("mla_absorb"):
            q, w_v = self._absorb_query(q_nope, q_pe, arena)
            o_lat = decode_attention_latent(q, arena, tables, lens,
                                            self.rank, self.scale)
            return jnp.einsum("bhr,rhv->bhv", o_lat.astype(w_v.dtype), w_v)

    def _absorbed_chunk(self, q_nope, q_pe, arena, tables, pos0, n_valid,
                        tq):
        """A chunk's queries over the latent arena, absorbed: each query
        tile of ``tq`` positions reads the slot's rows through its table
        only up to its causal frontier
        (``ops/pallas/decode_attention.latent_chunk_attention``)."""
        from ..ops.pallas.decode_attention import latent_chunk_attention
        with jax.named_scope("mla_chunk"):
            q, w_v = self._absorb_query(q_nope, q_pe, arena)
            o_lat = latent_chunk_attention(q, arena, tables, pos0, n_valid,
                                           self.rank, self.scale, tq)
            return jnp.einsum("bchr,rhv->bchv", o_lat.astype(w_v.dtype),
                              w_v)

    def step(self, u, pos0, n_valid, kv):
        """u: Tensor [B, C, H] at positions ``pos0[b] + c``.  ``kv`` None:
        causal attention inside the rows.  ``kv = (arena, tables)``: the
        valid rows' latent rows are written through the tables and the
        queries attend over everything written."""
        from ..ops.pallas.decode_attention import (latent_chunk_tile,
                                                   paged_gather_view)
        with jax.named_scope("mla_attention"):
            b, c, _ = u.shape
            q = self._query(u).reshape(
                b, c, self.num_heads, self.nope + self.rope)
            q_nope, q_pe = q[..., :self.nope], q[..., self.nope:]
            ckv = self.kv_a_proj_with_mqa(u)
            latent = self.kv_a_layernorm(
                Tensor(ckv._value[..., :self.rank]))._value
            if self.kv_scale != 1.0:
                latent = latent * self.kv_scale
            rows = jnp.concatenate(
                [latent, ckv._value[..., self.rank:]], axis=-1)
            pos = pos0[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
            if self.inv_freq is not None:
                with jax.named_scope("mla_rope"):
                    q_pe = rotate_pairs(q_pe, pos, self.inv_freq)
                    rows = jnp.concatenate(
                        [latent, rotate_pairs(rows[..., self.rank:], pos,
                                              self.inv_freq)], axis=-1)
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
                elif tq := latent_chunk_tile(c, self.num_heads, arena,
                                             tables, self.rank):
                    out = self._absorbed_chunk(q_nope, q_pe, arena, tables,
                                               pos0, n_valid, tq)
                else:
                    out = self._expanded(
                        q_nope, q_pe, paged_gather_view(arena, tables), pos)
                kv = (arena, tables)
            out = out.reshape(b, c, -1).astype(u._value.dtype)
            return self.o_proj(Tensor(out)), kv
