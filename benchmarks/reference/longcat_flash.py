"""Plain reference of the LongCat-Flash decoder (``LongCat-Flash-Chat``).

Straightforward ``jax.numpy`` in float32 under ``jax.default_matmul_precision
("highest")``, every matmul at ``HIGHEST`` besides: no kernel, no cache, no
batching, nothing imported from the program.  Every layer is a double layer
(``rms_*`` RMSNorms of eps ``rms_norm_eps``)::

    h1  = x  + MLA_0(rms_in0(x))
    u   = rms_post0(h1)
    m   = MoE(u)                                   # shortcut branch
    h2  = h1 + FFN_0(u)                            # SwiGLU
    h3  = h2 + MLA_1(rms_in1(h2))
    out = h3 + FFN_1(rms_post1(h3)) + m

and a final RMSNorm and an untied head.

* MLA: ``q = sqrt(H / q_rank) W_qb rms(W_qa x)`` as heads of ``[q_nope,
  q_pe]``; ``[c~, k_r] = W_kva x``, ``c = sqrt(H / kv_rank) rms(c~)``;
  ``[k_nope, v]_h = W_kvb,h c``; ``k_pe = RoPE(k_r)`` shared by the heads and
  ``q_pe = RoPE(q_pe)`` at the token's position, theta ``rope_theta``, in
  DeepSeek-V3's form: the rotary lanes de-interleaved (even lanes, then odd)
  and turned by ``rotate_half`` against ``cos, sin`` of ``cat(f, f)``, ``f_i
  = pos theta^(-2i/rope)``; causal softmax of ``(q_nope . k_nope + q_pe .
  k_pe) / sqrt(nope + rope)``; ``W_o`` over the heads' values.  Expanded: no
  absorption, no cache.
* MoE: ``s = softmax(W_r u)`` over the router's ``router_experts +
  zero_expert_num`` outputs; the ``moe_topk`` with the largest ``s + b``
  picked (``b`` picks and does not weigh); ``w = routed_scaling_factor s``,
  not renormalised; ``y = sum_e w_e E_e(u)`` over the real experts *held*
  (``held = (first, count)``; what an absent expert would add is left out)
  ``+ (sum of w_e over the zero-compute experts picked) u``.

Departures, each on purpose:

* Every matrix is handed over in the stored type and widened to float32 where
  it is used (a layer's own, one expert at a time, the rows looked up, the
  head once a block of rows): float32 copies of 5.2 B parameters are 21 GB
  and do not fit the chip.  Widening changes no value.
* The feed-forwards, the MoE and the head walk the sequence in blocks of
  ``bucket`` rows, and attention its heads in groups of ``HEAD_GROUP``, so
  that activations, scores and logits fit; each acts row by row or head by
  head, so the blocks change no value.
* ``mode="int8"`` and ``mode="fp8"`` are the control, not the reference:
  every matmul input, the router's too, is fake-quantised (weights per output
  channel, activations per token, symmetric; int8, or float8 e4m3), the
  nearest precisions below bfloat16.  The softmaxes stay float32.
* ``sequence_logits`` leaves out of the comparison the positions at which the
  reference's own routing is a near-tie: where, in some layer, the last
  expert picked leads the first one left out (on ``s + b``) by less than
  ``ROUTING_EPS``.  There the bfloat16 program may rightly pick the other
  expert.  The scores are those of a 768-way softmax: a picked one is some
  0.011 (about 9 times the mean, 1/768), the 12th and the 13th lie some
  0.0004 apart at the median, and a relative error of 1% in ``u`` moves a
  picked score by some 0.0002 (a CPU draw of the seeded router at its
  published widths); ``ROUTING_EPS`` is half again as much.  A row
  left out is returned as zeros, so that any token's gap there is 0.  The
  rows that count are the rows *given*: those whose next token ``ids`` holds.
  Never more than ``MAX_LEFT_OUT`` of the rows given to this process's calls
  so far are left out: where more are, those of the call with the smallest
  margins are.  Each call prints how many of the rows given it kept and the
  tally so far.  ``logits_and_margins`` gives the rows and the margins as
  they are.

Weight layout: ``{"embed": [V, H], "layers": [...], "norm": [H], "head":
[H, V]}``; a linear weight is ``[in, out]``.  A layer is a dict with ``in0``,
``in1``, ``post0``, ``post1`` [H]; ``attn0``, ``attn1`` each ``{q_a_proj,
q_a_layernorm, q_b_proj, kv_a_proj_with_mqa, kv_a_layernorm, kv_b_proj,
o_proj}``; ``ffn0``, ``ffn1`` each ``{gate_proj, up_proj, down_proj}``; and
``moe`` ``{router [H, E + Z], expert_bias [E + Z], held, w1, w3 [count, H,
M], w2 [count, M, H]}``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# the fake quantisation of the control, the norm, a SwiGLU and the rows given
# are those of the Kimi Linear reference
from .kimi_linear import _mm, rmsnorm, rows_given, swiglu_ffn

HI = jax.lax.Precision.HIGHEST
ROUTING_EPS = 0.0003    # 1.5 x what a 1% error in u moves a picked score by
MAX_LEFT_OUT = 0.9      # of the rows given so far
HEAD_GROUP = 16
TALLY = {"kept": 0, "given": 0}     # over this process's calls


def _f32(a):
    return a.astype(jnp.float32)


def rope(x, pos, theta):
    """DeepSeek-V3's rotary embedding of ``x`` [T, ..., R] at positions
    ``pos`` [T]: the lanes de-interleaved, then ``x cos + rotate_half(x)
    sin``."""
    r = x.shape[-1]
    freq = pos.astype(jnp.float32)[:, None] \
        * (1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    emb = jnp.concatenate([freq, freq], axis=-1)
    emb = emb.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (r,))
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    half = jnp.concatenate([-x[..., r // 2:], x[..., :r // 2]], axis=-1)
    return x * jnp.cos(emb) + half * jnp.sin(emb)


def mla(ap, u, dims, mode):
    heads, nope, rope_d, vd, rank, q_rank, eps, theta = dims["mla"]
    t, h = u.shape
    q_lat = rmsnorm(_mm(u, _f32(ap["q_a_proj"]), mode), ap["q_a_layernorm"],
                    eps)
    q = np.sqrt(h / q_rank) * _mm(q_lat, _f32(ap["q_b_proj"]), mode)
    q = q.reshape(t, heads, nope + rope_d)
    ckv = _mm(u, _f32(ap["kv_a_proj_with_mqa"]), mode)
    c = np.sqrt(h / rank) * rmsnorm(ckv[:, :rank], ap["kv_a_layernorm"], eps)
    pos = jnp.arange(t)
    k_pe = rope(ckv[:, rank:], pos, theta)                    # [T, R]
    q_pe = rope(q[..., nope:], pos, theta)                    # [T, heads, R]
    kv = _mm(c, _f32(ap["kv_b_proj"]), mode).reshape(t, heads, nope + vd)
    causal = pos[:, None] >= pos[None, :]
    size = math.gcd(heads, HEAD_GROUP)

    def group(g):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            a, g * size, size, axis=1)
        s = (jnp.einsum("thd,shd->hts", sl(q[..., :nope]),
                        sl(kv[..., :nope]), precision=HI)
             + jnp.einsum("thr,sr->hts", sl(q_pe), k_pe, precision=HI)) \
            / np.sqrt(nope + rope_d)
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shv->thv", p, sl(kv[..., nope:]),
                          precision=HI)

    o = jax.lax.map(group, jnp.arange(heads // size))   # [G, T, g, v]
    o = jnp.moveaxis(o, 0, 1).reshape(t, heads * vd)
    return _mm(o, _f32(ap["o_proj"]), mode)


def ffn(fp, u, mode):
    return swiglu_ffn(u, _f32(fp["gate_proj"]), _f32(fp["up_proj"]),
                      _f32(fp["down_proj"]), mode)


def route(mp, u, top_k, scale, n_real, mode):
    """([R, E + Z] combine weights: zero for an expert a row did not pick;
    [R] the zero-compute experts' summed weight; [R] routing margin: by how
    much the last expert picked leads the first one left out)."""
    s = jax.nn.softmax(_mm(u, mp["router"], mode), axis=-1)
    lead, chosen = jax.lax.top_k(s + mp["expert_bias"][None, :], top_k + 1)
    margin, chosen = lead[:, top_k - 1] - lead[:, top_k], chosen[:, :top_k]
    w = jnp.take_along_axis(s, chosen, axis=-1) * scale
    rows = jnp.arange(u.shape[0])[:, None]
    w_all = jnp.zeros_like(s).at[rows, chosen].set(w)
    return w_all, jnp.sum(w_all[:, n_real:], axis=-1), margin


def moe(mp, u, routing, mode):
    """The held experts' part and the zero-compute experts' part of the
    block's output, one expert at a time; and the rows' routing margins."""
    top_k, scale, n_real = routing
    first, count = mp["held"]
    w_all, w_zero, margin = route(mp, u, top_k, scale, n_real, mode)

    def one(e, acc):
        wide = lambda name: _f32(mp[name][e])  # noqa: E731
        y = swiglu_ffn(u, wide("w1"), wide("w3"), wide("w2"), mode)
        w = jax.lax.dynamic_slice_in_dim(w_all, first + e, 1, axis=1)
        return acc + w * y

    routed = jax.lax.fori_loop(0, count, one, jnp.zeros_like(u))
    return routed + w_zero[:, None] * u, margin


# -- one layer ------------------------------------------------------------------------

class _Dims(dict):
    """Static sizes, hashable by content for ``jit``."""
    def __hash__(self):
        return hash(tuple(sorted(self.items())))


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _attention(ap, norm_w, x, dims, mode):
    with jax.default_matmul_precision("highest"):
        return x + mla(ap, rmsnorm(x, norm_w, dims["eps"]), dims, mode)


@functools.partial(jax.jit, static_argnames=("eps", "held", "routing", "mode"))
def _first_half(lp, h1, eps, held, routing, mode):
    """A block of rows after the first attention: (h2, the MoE's part,
    margins)."""
    with jax.default_matmul_precision("highest"):
        u = rmsnorm(h1, lp["post0"], eps)
        m, margin = moe(dict(lp["moe"], held=held), u, routing, mode)
        return h1 + ffn(lp["ffn0"], u, mode), m, margin


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _second_half(lp, h3, m, eps, mode):
    with jax.default_matmul_precision("highest"):
        return h3 + ffn(lp["ffn1"], rmsnorm(h3, lp["post1"], eps), mode) + m


def _blocks(fn, bucket, *rows):
    t = rows[0].shape[0]
    outs = [fn(*(r[i:i + bucket] for r in rows)) for i in range(0, t, bucket)]
    if isinstance(outs[0], tuple):
        return tuple(jnp.concatenate(o, axis=0) for o in zip(*outs))
    return jnp.concatenate(outs, axis=0)


def layer_forward(lp, x, dims, routing, bucket, mode=None):
    """One double layer on the sequence ``x`` [T, H] at positions 0..T-1,
    the feed-forwards and the MoE in blocks of ``bucket`` rows; with the
    rows' routing margins."""
    held = tuple(lp["moe"]["held"])
    arrays = dict(lp, moe={k: v for k, v in lp["moe"].items()
                           if k != "held"})
    eps = dims["eps"]
    h1 = _attention(arrays["attn0"], arrays["in0"], x, dims, mode)
    h2, m, margin = _blocks(
        lambda r: _first_half(arrays, r, eps, held, routing, mode), bucket, h1)
    h3 = _attention(arrays["attn1"], arrays["in1"], h2, dims, mode)
    out = _blocks(lambda r, mr: _second_half(arrays, r, mr, eps, mode),
                  bucket, h3, m)
    return out, margin


def _dims(cfg):
    eps = float(cfg["rms_norm_eps"])
    return _Dims(
        eps=eps,
        mla=(int(cfg["num_attention_heads"]), int(cfg["qk_nope_head_dim"]),
             int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"]),
             int(cfg["kv_lora_rank"]), int(cfg["q_lora_rank"]), eps,
             float(cfg["rope_theta"])))


def _routing(cfg):
    n_real = int(cfg.get("router_experts", cfg["n_routed_experts"]))
    return (int(cfg["moe_topk"]), float(cfg["routed_scaling_factor"]),
            n_real)


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head_logits(norm_w, head, x, eps, mode):
    with jax.default_matmul_precision("highest"):
        return _mm(rmsnorm(x, norm_w, eps), head.astype(jnp.float32), mode)


def sequence_logits(weights, cfg, ids, first, bucket, mode=None):
    """Float32 logits of the token sequence ``ids`` [T] at positions
    ``first .. first+bucket-1``: row i is the distribution of token
    ``first+i+1``.  ``ids`` may carry padding past the positions asked for;
    the layers are causal, so it cannot reach them.  ``T`` is a multiple of
    ``bucket``.  Rows at which the routing is a near-tie are left out (zeros;
    the module's docstring)."""
    logits, least = logits_and_margins(weights, cfg, ids, first, bucket, mode)
    if mode is not None:
        return logits       # a control's rows only say which token it picks
    given = rows_given(ids, first, bucket)
    out = near_ties(np.asarray(least), given)
    kept = given - int(out[:given].sum())
    TALLY["kept"] += kept
    TALLY["given"] += given
    print(f"reference kept={kept} of {given} rows given (left out: routing "
          f"margin under {ROUTING_EPS}); so far kept={TALLY['kept']} of "
          f"{TALLY['given']}, at least {1 - MAX_LEFT_OUT:.2f} of them",
          flush=True)
    return jnp.where(jnp.asarray(out)[:, None], 0.0, logits)


def near_ties(least, given):
    """[rows] bool: the rows left out.  Every row whose least routing margin
    is under ``ROUTING_EPS``; but of the ``given`` rows at the head only as
    many as keep the process's tally within ``MAX_LEFT_OUT``, the smallest
    margins first."""
    out = least < ROUTING_EPS
    most = max(int(MAX_LEFT_OUT * (TALLY["given"] + given))
               - (TALLY["given"] - TALLY["kept"]), 0)
    if out[:given].sum() > most:
        out[:given] = False
        out[np.argsort(least[:given], kind="stable")[:most]] = True
    return out


def logits_and_margins(weights, cfg, ids, first, bucket, mode=None):
    """``sequence_logits``'s rows as they are, and for each the least routing
    margin over the layers at that position."""
    dims, routing = _dims(cfg), _routing(cfg)
    x = weights["embed"][jnp.asarray(ids)].astype(jnp.float32)
    least = jnp.full((x.shape[0],), jnp.inf)
    for lp in weights["layers"]:
        x, margin = layer_forward(lp, x, dims, routing, bucket, mode)
        least = jnp.minimum(least, margin)
    rows = jax.lax.dynamic_slice_in_dim(x, first, bucket, axis=0)
    return (_head_logits(weights["norm"], weights["head"], rows, dims["eps"],
                         mode),
            jax.lax.dynamic_slice_in_dim(least, first, bucket, axis=0))
