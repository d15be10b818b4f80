"""Plain reference of the Kimi Linear sparse hybrid decoder (``model_type``
``kimi_linear``).

Straightforward ``jax.numpy`` in float32 with ``highest`` matmul precision: no
kernel, no cache, no batching, nothing imported from the program.  Pre-norm
residual layers, ``h = h + Op_i(rms(h))``, ``h = h + FFN_i(rms(h))``, a final
RMSNorm and an untied head.

* KDA (``Op_i`` for ``i`` in ``kda_layers``, 1-based), ``H`` heads of ``d``:
  ``q~, k~, v~ = W_q x, W_k x, W_v x``, each through its own depthwise causal
  convolution of 4 taps (rows before the sequence are zero) and SiLU; per head
  ``q`` and ``k`` divided by their L2 norm, ``q`` scaled by ``d^-1/2``;
  ``g_t = -exp(A_log_h) softplus(W_f2 (W_f1 x_t) + dt_bias)`` a key channel,
  ``alpha_t = exp(g_t)``; ``beta_t = sigmoid(W_b x_t)`` a head; a head's state
  ``S`` [d, d] (key by value) goes token by token ``S' = diag(alpha_t) S``,
  ``S = S' + beta_t k_t (v_t - S'^T k_t)^T``, ``o_t = S^T q_t``; ``y_t = W_o
  [rmsnorm_head(o_t) * sigmoid(W_g2 (W_g1 x_t))]``.
* MLA without rotary (``Op_i`` for ``i`` in ``full_attn_layers``): ``q = W_q
  x`` as heads of ``[q_nope, q_pe]``; ``[c~, k_pe] = W_kva x``, ``c = rms(c~)``;
  ``[k_nope, v]_h = W_kvb,h c``; ``k_h = [k_nope_h, k_pe]``, no rotation
  anywhere; causal softmax of ``q_h . k_h / sqrt(nope + rope)``; ``W_o`` over
  the heads' values.  Expanded: no absorption, no cache.
* FFN: a dense SwiGLU in the first ``first_k_dense_replace`` layers; after
  them ``s = sigmoid(W_r u)`` over all the router's experts, the
  ``num_experts_per_token`` with the largest ``s + b`` picked (``b`` picks and
  does not weigh), ``w = routed_scaling_factor s / (sum s + 1e-6)``, ``y =
  sum_e w_e E_e(u) + E_shared(u)`` over the experts *held*: a layer's ``held =
  (first, count)`` names the experts whose planes it was given, and what an
  absent expert would add is left out.

Departures, each on purpose:

* ``1e-6`` beside the sum of the picked scores, as the program's router has
  it; the scores are in (0, 1) and eight of the largest sum to 6 or more.
* The experts' planes, the embedding and the head are handed over in the
  stored type and widened to float32 where they are used (one expert, the rows
  looked up, the head once a block of rows): float32 copies of 4.3 B
  parameters are 17 GB and do not fit the chip.  Widening changes no value.
* The feed-forward and the head walk the sequence in blocks of ``bucket`` rows
  so that their activations and the logits fit; they act row by row, so the
  blocks change no value.
* ``mode="int8"`` and ``mode="fp8"`` are the control, not the reference: every
  matmul input, the router's and the low-rank gates' too, is fake-quantised
  (weights per output channel, activations per token, symmetric; int8, or
  float8 e4m3), the nearest precisions below bfloat16.  The recurrence and
  the softmax stay float32.
* ``sequence_logits`` leaves out of the comparison the positions at which the
  reference's own routing is a near-tie: where, in some expert layer, the last
  expert picked leads the first one left out by less than ``ROUTING_EPS``.
  There the bfloat16 program may rightly pick the other expert, and the two
  then compute different functions.  With 256 experts and 8 picked the 8th
  and the 9th score lie some 0.007 apart at the median, and bfloat16
  activations move a score by about 0.001 (one percent of the stream on
  router logits of deviation 0.96, through a sigmoid's slope of 0.12 at a
  picked score).  A row left out is returned as zeros, so that any token's gap
  there is 0.  The rows that count are the rows *given*: those whose next
  token ``ids`` holds (the harness pads ``ids`` with zeros past the served
  tokens and compares none of the rows there).  Never more than
  ``MAX_LEFT_OUT`` of the rows given to this process's calls so far are left
  out: where more are, those of the call with the smallest margins are.  Each
  call prints how many of the rows given it kept and the tally so far.
  ``logits_and_margins`` gives the rows and the margins as they are.

Weight layout: ``{"embed": [V, H], "layers": [...], "norm": [H], "head":
[H, V]}``; a linear weight is ``[in, out]``.  A layer is a dict with
``op_norm``, ``ffn_norm`` and either ``taps`` [3W, 4] (q's, k's, v's), ``a_log``
[heads], ``dt_bias`` [W], ``wq``, ``wk``, ``wv`` [H, W], ``f_a``, ``f_b``,
``wb`` [H, heads], ``g_a``, ``g_b``, ``o_norm`` [d], ``wo`` (KDA) or ``wq``,
``wkva``, ``kva_norm``, ``wkvb``, ``wo`` (MLA), and either ``wg``, ``wu``,
``wd`` (dense) or ``router`` [H, E], ``expert_bias`` [E], ``held``, ``eg``,
``eu`` [count, H, M], ``ed`` [count, M, H], ``sg``, ``su``, ``sd`` (experts).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
ROUTING_EPS = 0.003     # some three times what bfloat16 activations move a score by
MAX_LEFT_OUT = 0.9      # of the rows given so far
TALLY = {"kept": 0, "given": 0}     # over this process's calls


def _fq(x, axis):
    """Symmetric int8 fake quantisation along ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _fq8(x, axis):
    """Fake quantisation to float8 (e4m3) along ``axis``, scaled so that the
    largest magnitude sits at the format's largest."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, mode):
    if mode == "int8":
        x, w = _fq(x, -1), _fq(w, 0)
    elif mode == "fp8":
        x, w = _fq8(x, -1), _fq8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


# -- the two operators, on one whole sequence [T, H] ------------------------------

def _causal_conv(z, taps):
    """z [T, W], taps [W, n]: ``sum_j taps[:, j] z[t - (n-1) + j]``, zeros
    before the sequence."""
    t, n = z.shape[0], taps.shape[1]
    zp = jnp.concatenate([jnp.zeros((n - 1, z.shape[1]), z.dtype), z], 0)
    return sum(taps[:, j][None, :] * zp[j:j + t] for j in range(n))


def kda(lp, u, dims, mode):
    heads, d, eps, l2_eps = dims["kda"]
    t = u.shape[0]
    w = heads * d
    taps = lp["taps"]

    def branch(weight, i):
        conv = _causal_conv(_mm(u, weight, mode), taps[i * w:(i + 1) * w])
        return jax.nn.silu(conv).reshape(t, heads, d)

    q, k, v = branch(lp["wq"], 0), branch(lp["wk"], 1), branch(lp["wv"], 2)
    l2 = lambda a: a / jnp.sqrt(jnp.sum(a * a, -1, keepdims=True) + l2_eps)  # noqa: E731
    q, k = l2(q) * d ** -0.5, l2(k)
    rate = _mm(_mm(u, lp["f_a"], mode), lp["f_b"], mode) + lp["dt_bias"]
    alpha = jnp.exp(-jnp.exp(lp["a_log"])[None, :, None]
                    * jax.nn.softplus(rate).reshape(t, heads, d))
    beta = jax.nn.sigmoid(_mm(u, lp["wb"], mode))            # [T, heads]

    def token(S, xs):
        qt, kt, vt, at, bt = xs
        S = at[:, :, None] * S
        pred = jnp.einsum("hk,hkv->hv", kt, S, precision=HI)
        S = S + kt[:, :, None] * (bt[:, None] * (vt - pred))[:, None, :]
        return S, jnp.einsum("hk,hkv->hv", qt, S, precision=HI)

    _, o = jax.lax.scan(token, jnp.zeros((heads, d, d), jnp.float32),
                        (q, k, v, alpha, beta))
    gate = jax.nn.sigmoid(_mm(_mm(u, lp["g_a"], mode), lp["g_b"], mode))
    y = rmsnorm(o, lp["o_norm"], eps).reshape(t, w) * gate
    return _mm(y, lp["wo"], mode)


def mla(lp, u, dims, mode):
    heads, nope, rope, vd, rank, eps = dims["mla"]
    t = u.shape[0]
    q = _mm(u, lp["wq"], mode).reshape(t, heads, nope + rope)
    ckv = _mm(u, lp["wkva"], mode)
    c, k_pe = rmsnorm(ckv[:, :rank], lp["kva_norm"], eps), ckv[:, rank:]
    kv = _mm(c, lp["wkvb"], mode).reshape(t, heads, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe[:, None, :], (t, heads, rope))],
        axis=-1)
    s = jnp.einsum("thd,shd->hts", q, k, precision=HI) / np.sqrt(nope + rope)
    pos = jnp.arange(t)
    s = jnp.where((pos[:, None] >= pos[None, :])[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hts,shv->thv", p, kv[..., nope:], precision=HI)
    return _mm(o.reshape(t, heads * vd), lp["wo"], mode)


# -- the feed-forwards, on a block of rows [R, H] -----------------------------------

def swiglu_ffn(u, wg, wu, wd, mode):
    a = jax.nn.silu(_mm(u, wg, mode)) * _mm(u, wu, mode)
    return _mm(a, wd, mode)


def route(lp, u, top_k, renormalize, scale, mode):
    """([R, E] combine weights: zero for an expert a row did not pick; [R]
    routing margin: by how much the last expert picked leads the first one
    left out)."""
    s = jax.nn.sigmoid(_mm(u, lp["router"], mode))
    lead, chosen = jax.lax.top_k(s + lp["expert_bias"][None, :], top_k + 1)
    margin, chosen = lead[:, top_k - 1] - lead[:, top_k], chosen[:, :top_k]
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if renormalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    w = w * scale
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(w), margin


def expert_ffn(lp, u, routing, mode):
    """The held experts' part of the block's output, one expert at a time,
    plus the shared expert's; and the rows' routing margins."""
    first, count = lp["held"]
    w_all, margin = route(lp, u, *routing, mode)

    def one(e, acc):
        wide = lambda name: lp[name][e].astype(jnp.float32)  # noqa: E731
        y = swiglu_ffn(u, wide("eg"), wide("eu"), wide("ed"), mode)
        w = jax.lax.dynamic_slice_in_dim(w_all, first + e, 1, axis=1)
        return acc + w * y

    routed = jax.lax.fori_loop(0, count, one, jnp.zeros_like(u))
    return routed + swiglu_ffn(u, lp["sg"], lp["su"], lp["sd"], mode), margin


# -- one layer ------------------------------------------------------------------------

class _Dims(dict):
    """Static sizes, hashable by content for ``jit``."""
    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def _held_static(lp):
    """``held`` is shape, not data: out of the traced dict."""
    return {k: v for k, v in lp.items() if k != "held"}, lp.get("held")


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _operator(lp, x, dims, mode):
    u = rmsnorm(x, lp["op_norm"], dims["eps"])
    if "taps" in lp:
        return x + kda(lp, u, dims, mode)
    return x + mla(lp, u, dims, mode)


@functools.partial(jax.jit, static_argnames=("eps", "held", "routing", "mode"))
def _feed_forward(lp, x, eps, held, routing, mode):
    u = rmsnorm(x, lp["ffn_norm"], eps)
    if "router" in lp:
        y, margin = expert_ffn(dict(lp, held=held), u, routing, mode)
        return x + y, margin
    return x + swiglu_ffn(u, lp["wg"], lp["wu"], lp["wd"], mode), \
        jnp.full((x.shape[0],), jnp.inf)


def layer_forward(lp, x, dims, routing, bucket, mode=None):
    """One layer on the sequence ``x`` [T, H] at positions 0..T-1, the
    feed-forward in blocks of ``bucket`` rows; with the rows' routing margins
    (infinite where the feed-forward is dense)."""
    arrays, held = _held_static(lp)
    x = _operator(arrays, x, dims, mode)
    blocks = [_feed_forward(arrays, x[r:r + bucket], dims["eps"], held,
                            routing, mode)
              for r in range(0, x.shape[0], bucket)]
    return (jnp.concatenate([b[0] for b in blocks], axis=0),
            jnp.concatenate([b[1] for b in blocks], axis=0))


def _dims(cfg):
    lin = cfg["linear_attn_config"]
    eps = float(cfg["rms_norm_eps"])
    return _Dims(
        eps=eps,
        kda=(int(lin["num_heads"]), int(lin["head_dim"]), eps,
             float(cfg.get("l2_norm_eps", 1e-6))),
        mla=(int(cfg["num_attention_heads"]), int(cfg["qk_nope_head_dim"]),
             int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"]),
             int(cfg["kv_lora_rank"]), eps))


def _routing(cfg):
    return (int(cfg["num_experts_per_token"]), bool(cfg["moe_renormalize"]),
            float(cfg["routed_scaling_factor"]))


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head_logits(norm_w, head, x, eps, mode):
    return _mm(rmsnorm(x, norm_w, eps), head.astype(jnp.float32), mode)


def sequence_logits(weights, cfg, ids, first, bucket, mode=None):
    """Float32 logits of the token sequence ``ids`` [T] at positions
    ``first .. first+bucket-1``: row i is the distribution of token
    ``first+i+1``.  ``ids`` may carry padding past the positions asked for; the
    operators are causal, so it cannot reach them.  ``T`` is a multiple of
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


def rows_given(ids, first, bucket):
    """How many of the rows ``first .. first+bucket-1`` have their next
    token in ``ids``: up to the last token that is not padding (zero)."""
    held = np.flatnonzero(np.asarray(ids))
    last = int(held[-1]) if held.size else 0
    return int(min(max(last - first, 0), bucket))


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
    margin over the expert layers at that position."""
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
