"""Routed experts on the serving path: a layer that is told which experts it
holds, routes every token over all ``num_experts`` at the published router
width, and returns the part of the result that its own experts give.

What an absent expert would add is left out; on one chip there is no exchange
and nothing stands in for the chips that hold the others.  Dropless by
construction: the (token, expert) assignments are sorted by expert and run
through one grouped matmul whose groups are as long as the routing made them
(``ops/pallas/grouped_matmul.py``: on the chip a kernel that reads each
touched expert's plane once, elsewhere ``jax.lax.ragged_dot``), so no
capacity is chosen and no token can exceed it.
``incubate/distributed/models/moe`` dispatches into ``[experts, capacity,
width]`` buffers instead, which at a dropless capacity is ``num_experts /
top_k`` times the useful work.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.tensor import Tensor
from ...ops.pallas.grouped_matmul import grouped_matmul
from ..initializer import Constant, Normal
from .layers import Layer

__all__ = ["RoutedExperts", "route_tokens", "grouped_experts"]


def route_tokens(u, router, expert_bias, top_k, *, norm_topk_prob=True,
                 scaling=1.0, score_dtype=jnp.float32):
    """Sigmoid scores of rows ``u`` [N, H] over every expert, the ``top_k``
    picked by score plus bias (the bias picks, it does not weigh), the picked
    scores normalised.  Returns (chosen [N, k] int32, weights [N, k] in
    ``score_dtype``).  Scores and top-k are float32 whatever ``u`` is: a
    near-tie between the k-th and the next score decides an expert."""
    with jax.named_scope("moe_router"):
        s = jax.nn.sigmoid(jnp.dot(u.astype(score_dtype),
                                   router.astype(score_dtype),
                                   preferred_element_type=score_dtype))
        pick = s if expert_bias is None else \
            s + expert_bias.astype(score_dtype)[None, :]
        _, chosen = jax.lax.top_k(pick, top_k)
        w = jnp.take_along_axis(s, chosen, axis=-1)
        if norm_topk_prob:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
        return chosen.astype(jnp.int32), w * scaling


def grouped_experts(u, chosen, weights, w1, w3, w2, held):
    """The held experts' part of ``sum_e w_e down_e(silu(gate_e(u)) *
    up_e(u))`` for rows ``u`` [N, H]: assignments sorted by expert, one
    grouped matmul a projection, combined by the weights.  ``w1``, ``w3``
    [count, H, M], ``w2`` [count, M, H]; ``held = (first, count)``."""
    first, count = held
    n, k = chosen.shape
    with jax.named_scope("moe_experts"):
        local = chosen.reshape(-1) - first
        mine = (local >= 0) & (local < count)
        # an assignment to an absent expert sorts behind every group and
        # falls outside all of them
        key = jnp.where(mine, local, count)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.zeros((count + 1,), jnp.int32).at[key].add(1)[:count]
        xs = jnp.take(u, order // k, axis=0)
        gate = grouped_matmul(xs, w1, sizes)
        up = grouped_matmul(xs, w3, sizes)
        y = grouped_matmul(jax.nn.silu(gate) * up, w2, sizes)
        y = jnp.take(y, jnp.argsort(order), axis=0).reshape(n, k, -1)
        # an absent expert's rows are past every group: the kernel stores
        # zeros there, but on the chip ``ragged_dot`` leaves what the
        # memory held (4.6 and NaN in two tries, PR 34), and 0 x NaN is NaN
        y = jnp.where(mine.reshape(n, k, 1), y.astype(jnp.float32), 0.0)
        out = jnp.einsum("nk,nkh->nh", weights, y)
        return out.astype(u.dtype)


class RoutedExperts(Layer):
    """``num_experts`` SwiGLU experts of width ``expert_width`` behind a
    sigmoid router with ``top_k`` experts a token; this layer has the planes
    of ``held = (first, count)`` of them (all, by default)."""

    score_dtype = jnp.float32

    def __init__(self, hidden_size, expert_width, num_experts, top_k, *,
                 held=None, norm_topk_prob=True, use_expert_bias=True,
                 routed_scaling_factor=1.0):
        super().__init__()
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        first, count = held if held is not None else (0, self.num_experts)
        if not (0 <= first and count >= 1
                and first + count <= self.num_experts):
            raise ValueError(f"held={held!r} is not a range of the "
                             f"{self.num_experts} experts")
        self.held = (int(first), int(count))
        self.norm_topk_prob = bool(norm_topk_prob)
        self.routed_scaling_factor = float(routed_scaling_factor)
        h, m = int(hidden_size), int(expert_width)
        normal = Normal(0.0, 0.02)
        self.router = self.create_parameter(
            (h, self.num_experts), default_initializer=normal)
        self.expert_bias = self.create_parameter(
            (self.num_experts,), default_initializer=Constant(0.0)) \
            if use_expert_bias else None
        self.w1 = self.create_parameter((count, h, m),
                                        default_initializer=normal)
        self.w3 = self.create_parameter((count, h, m),
                                        default_initializer=normal)
        self.w2 = self.create_parameter((count, m, h),
                                        default_initializer=normal)

    def route(self, u):
        bias = None if self.expert_bias is None else self.expert_bias._value
        return route_tokens(
            u, self.router._value, bias, self.top_k,
            norm_topk_prob=self.norm_topk_prob,
            scaling=self.routed_scaling_factor, score_dtype=self.score_dtype)

    def apply(self, u, live=None):
        """Rows ``u`` [N, H] (a raw array) to (the held experts' output
        [N, H], load [num_experts + 1] int32: the rows routed to each expert
        and how many experts got at least one).  ``live`` [N] bool leaves
        rows out of the load count, not out of the computation."""
        chosen, weights = self.route(u)
        out = grouped_experts(u, chosen, weights, self.w1._value,
                              self.w3._value, self.w2._value, self.held)
        one = jnp.ones(chosen.shape, jnp.int32) if live is None else \
            jnp.broadcast_to(live[:, None], chosen.shape).astype(jnp.int32)
        per = jnp.zeros((self.num_experts,), jnp.int32).at[
            chosen.reshape(-1)].add(one.reshape(-1))
        return out, jnp.concatenate([per, jnp.sum(per > 0)[None]])

    def forward(self, x):
        v = x._value
        out, _ = self.apply(v.reshape(-1, v.shape[-1]))
        return Tensor(out.reshape(v.shape))
