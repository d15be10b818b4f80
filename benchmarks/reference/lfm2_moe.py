"""Plain reference of the LFM2 sparse hybrid decoder (``model_type`` ``lfm2_moe``).

Straightforward ``jax.numpy`` in float32 with ``highest`` matmul precision: no
kernel, no cache, no batching, nothing imported from the program.  It follows
the published architecture: pre-norm residual layers whose operator is either
a gated short convolution or grouped-query attention with per-head RMS
normalisation of queries and keys before rotate-half RoPE, and whose
feed-forward is a dense SwiGLU in the leading ``num_dense_layers`` layers and a
block of routed experts after them (sigmoid scores, the bias picks and does
not weigh, the chosen scores normalised), a final RMSNorm and a head tied to
the embedding.

Layer ``i``: ``h = h + Op_i(rms(h))``, ``h = h + FFN_i(rms(h))``.

* short convolution: ``B, C, x = split3(in_proj(u))``, ``z = B * x``,
  ``c_t = k_0 z_{t-2} + k_1 z_{t-1} + k_2 z_t`` per channel (``z`` zero before
  the sequence), ``out_proj(C * c)``;
* experts: ``s = sigmoid(router(u))``, chosen ``= top_k(s + expert_bias)``,
  ``w = s[chosen] / (sum s[chosen] + 1e-6) * routed_scaling_factor``, output
  ``sum_e w_e down_e(silu(gate_e(u)) * up_e(u))`` over the experts *held*: a
  layer's ``held = (first, count)`` names the experts whose planes it was
  given, routing is over all ``num_experts`` and what an absent expert would
  add is left out.

Departures, each on purpose:

* The experts' planes are handed over in the stored type and widened to
  float32 one expert at a time: float32 copies of 5.27 B parameters are 21 GB
  and do not fit the chip.  Widening changes no value.
* The feed-forward and the head walk the sequence in blocks of ``bucket`` rows
  so that their activations and the logits fit; they act row by row, so the
  blocks change no value.
* ``mode="int8"`` and ``mode="fp8"`` are the control, not the reference: every
  matmul input, the router's too, is fake-quantised (weights per output
  channel, activations per token, symmetric; int8, or float8 e4m3), the
  nearest precisions below bfloat16.

* ``sequence_logits`` leaves out of the comparison the positions at which
  the reference's own routing is a near-tie: where, in some expert layer, the
  last expert chosen leads the first one left out by less than
  ``ROUTING_EPS``.  There the bfloat16 program may rightly pick the other
  expert (its activations move a score by about 0.002: one percent of the
  stream, on router logits of deviation 0.9, through a sigmoid's slope of a
  quarter), and the two then compute different functions, a quarter of an
  expert layer's output apart.  On the chip (``PERF.md`` section 6, PR 33,
  and ``benchmarks/records/pr33/``): with every position compared, sound
  runs read 0.33-0.63 and the int8 control 0.62-0.93, they do not separate;
  at the positions kept, sound runs read 0.06-0.30 in forty runs and 0.39 in
  one, the int8 control 0.51-0.75 and fp8 0.94-1.29.  What is left of the
  sound runs' gap comes from near-ties elsewhere in the context (all
  eighteen kept tokens over 0.2 had one a position or two back, which the
  convolutions carry forward; leaving those out too still read 0.29 in one
  chip run of two, on 34 tokens, and was dropped), and no choice of
  positions separates a maximum further: of the kept tokens 0.04% of a sound
  run's lie over 0.25 and none of 4,600 over 0.3, 4.4% of int8's picks lie
  over 0.36 and 1.5% over 0.45.  A row left out is returned as zeros, so
  that any token's gap there is 0.  The rows that count are the rows
  *given*: those whose next token ``ids`` holds (the harness pads ``ids``
  with zeros past the served tokens and compares none of the rows there).
  Never more than ``MAX_LEFT_OUT`` of the rows given to this process's
  calls so far are left out (a check's calls together, since a short answer
  alone may be all near-ties): where more are, those of the call with the
  smallest margins are.  Each call prints how many of the rows given it kept
  and the tally so far: the harness's ``check`` line counts every served
  token, kept or not.  ``logits_and_margins`` gives the rows and the margins
  as they are.

Weight layout: ``{"embed": [V, H], "layers": [...], "norm": [H]}``; a linear
weight is ``[in, out]``.  A layer is a dict with ``op_norm``, ``ffn_norm`` and
either ``in_proj`` [H, 3H], ``taps`` [H, L], ``out_proj`` (convolution) or
``wq``, ``wk``, ``wv``, ``wo``, ``q_norm``, ``k_norm`` (attention), and either
``wg``, ``wu``, ``wd`` (dense) or ``router`` [H, E], ``expert_bias`` [E],
``held``, ``eg``, ``eu`` [count, H, M], ``ed`` [count, M, H] (experts).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
ROUTING_EPS = 0.005     # some twice what bfloat16 activations move a score by
MAX_LEFT_OUT = 0.9      # of the rows given so far; sound runs' checks read 0.78-0.86
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


def rope(x, pos, theta):
    """x: [T, heads, D]; pos: [T].  Rotate-half form."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


# -- the two operators, on one whole sequence [T, H] ------------------------------

def short_conv(lp, u, mode):
    t = u.shape[0]
    b, c, x = jnp.split(_mm(u, lp["in_proj"], mode), 3, axis=-1)
    z = b * x
    taps = lp["taps"]
    n = taps.shape[1]
    zp = jnp.concatenate([jnp.zeros((n - 1, z.shape[1]), z.dtype), z], 0)
    conv = sum(taps[:, j][None, :] * zp[j:j + t] for j in range(n))
    return _mm(c * conv, lp["out_proj"], mode)


def attention(lp, u, dims, mode):
    hq, hkv, hd, eps, theta = dims
    t = u.shape[0]
    pos = jnp.arange(t)
    q = _mm(u, lp["wq"], mode).reshape(t, hq, hd)
    k = _mm(u, lp["wk"], mode).reshape(t, hkv, hd)
    v = _mm(u, lp["wv"], mode).reshape(t, hkv, hd)
    q = rope(rmsnorm(q, lp["q_norm"], eps), pos, theta)
    k = rope(rmsnorm(k, lp["k_norm"], eps), pos, theta)
    q = q.reshape(t, hkv, hq // hkv, hd)
    s = jnp.einsum("tkgd,skd->kgts", q, k, precision=HI) / np.sqrt(hd)
    s = jnp.where((pos[:, None] >= pos[None, :])[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgts,skd->tkgd", p, v, precision=HI).reshape(t, hq * hd)
    return _mm(o, lp["wo"], mode)


# -- the two feed-forwards, on a block of rows [R, H] -------------------------------

def dense_ffn(lp, u, mode):
    a = jax.nn.silu(_mm(u, lp["wg"], mode)) * _mm(u, lp["wu"], mode)
    return _mm(a, lp["wd"], mode)


def route(lp, u, top_k, norm_topk, scale, mode):
    """([R, E] combine weights: zero for an expert a row did not choose;
    [R] routing margin: by how much the last expert chosen leads the first
    one left out)."""
    s = jax.nn.sigmoid(_mm(u, lp["router"], mode))
    lead, chosen = jax.lax.top_k(s + lp["expert_bias"][None, :], top_k + 1)
    margin, chosen = lead[:, top_k - 1] - lead[:, top_k], chosen[:, :top_k]
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    w = w * scale
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(w), margin


def expert_ffn(lp, u, routing, mode):
    """The held experts' part of the block's output, one expert at a time,
    and the rows' routing margins."""
    first, count = lp["held"]
    w_all, margin = route(lp, u, *routing, mode)

    def one(e, acc):
        g = _mm(u, lp["eg"][e].astype(jnp.float32), mode)
        up = _mm(u, lp["eu"][e].astype(jnp.float32), mode)
        y = _mm(jax.nn.silu(g) * up, lp["ed"][e].astype(jnp.float32), mode)
        w = jax.lax.dynamic_slice_in_dim(w_all, first + e, 1, axis=1)
        return acc + w * y

    return jax.lax.fori_loop(0, count, one, jnp.zeros_like(u)), margin


# -- one layer ------------------------------------------------------------------------

def _held_static(lp):
    """``held`` is shape, not data: out of the traced dict."""
    return {k: v for k, v in lp.items() if k != "held"}, lp.get("held")


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _operator(lp, x, dims, mode):
    u = rmsnorm(x, lp["op_norm"], dims[3])
    if "taps" in lp:
        return x + short_conv(lp, u, mode)
    return x + attention(lp, u, dims, mode)


@functools.partial(jax.jit, static_argnames=("eps", "held", "routing", "mode"))
def _feed_forward(lp, x, eps, held, routing, mode):
    u = rmsnorm(x, lp["ffn_norm"], eps)
    if "router" in lp:
        y, margin = expert_ffn(dict(lp, held=held), u, routing, mode)
        return x + y, margin
    return x + dense_ffn(lp, u, mode), jnp.full((x.shape[0],), jnp.inf)


def layer_forward(lp, x, dims, routing, bucket, mode=None):
    """One layer on the sequence ``x`` [T, H] at positions 0..T-1, the
    feed-forward in blocks of ``bucket`` rows; with the rows' routing margins
    (infinite where the feed-forward is dense)."""
    arrays, held = _held_static(lp)
    x = _operator(arrays, x, dims, mode)
    blocks = [_feed_forward(arrays, x[r:r + bucket], dims[3], held, routing,
                            mode) for r in range(0, x.shape[0], bucket)]
    return (jnp.concatenate([b[0] for b in blocks], axis=0),
            jnp.concatenate([b[1] for b in blocks], axis=0))


def _dims(cfg):
    hq = int(cfg["num_attention_heads"])
    return (hq, int(cfg["num_key_value_heads"]),
            int(cfg.get("head_dim") or cfg["hidden_size"] // hq),
            float(cfg["norm_eps"]),
            float(cfg["rope_parameters"]["rope_theta"]))


def _routing(cfg):
    return (int(cfg["num_experts_per_tok"]), bool(cfg["norm_topk_prob"]),
            float(cfg["routed_scaling_factor"]))


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head_logits(norm_w, embed, x, eps, mode):
    return _mm(rmsnorm(x, norm_w, eps), embed.T, mode)


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
    x = weights["embed"][jnp.asarray(ids)]
    least = jnp.full((x.shape[0],), jnp.inf)
    for lp in weights["layers"]:
        x, margin = layer_forward(lp, x, dims, routing, bucket, mode)
        least = jnp.minimum(least, margin)
    rows = jax.lax.dynamic_slice_in_dim(x, first, bucket, axis=0)
    return (_head_logits(weights["norm"], weights["embed"], rows, dims[3],
                         mode),
            jax.lax.dynamic_slice_in_dim(least, first, bucket, axis=0))
