"""Plain reference of the Falcon-H1 hybrid decoder (``model_type``
``falcon_h1``).

Straightforward ``jax.numpy`` in float32 with ``highest`` matmul precision: no
kernel, no cache, no batching, nothing imported from the program.  Every layer
is alike (``attn_layer_indices`` null, ``mamba_use_mlp`` true), RMSNorm eps
``rms_norm_eps``:

* the model: ``h_0 = embedding_multiplier E[ids]``, ``logits =
  lm_head_multiplier (W_head rms(h_L))``, the head untied;
* a layer: ``u = rms(h)``, ``h = h + ssm_out_multiplier SSM(u) +
  attention_out_multiplier Attn(attention_in_multiplier u)``, ``h = h +
  MLP(rms(h))`` with ``MLP(v) = down W_d (W_u v * silu(gate W_g v))``
  (``mlp_multipliers = [gate, down]``);
* ``Attn``: ``q = W_q u``, ``k = key_multiplier W_k u``, ``v = W_v u`` in heads
  of ``head_dim``, RoPE (rotate-half, ``rope_theta``) over the whole head of
  q and k, causal softmax at ``head_dim^-1/2``, each KV head shared by
  ``heads / kv_heads`` query heads, ``W_o``; no biases;
* ``SSM``: ``p = (W_in (ssm_in_multiplier u)) * mu``, ``mu`` the five
  ``ssm_multipliers`` over the sections ``z`` (d_ssm), ``x`` (d_ssm), ``B``,
  ``C`` (groups x state each) and ``dt`` (heads); ``xBC = silu(conv(xBC) +
  b)``, depthwise and causal over ``mamba_d_conv`` taps, zeros before the
  sequence; ``x`` in heads of ``mamba_d_head``, ``B`` and ``C`` in groups of
  ``mamba_d_state``, head ``h`` reading group ``h // (heads / groups)``;
  ``dt_h = softplus(dt_h + dt_bias_h)``, ``A_h = -exp(A_log_h)``, no clamp; a
  head's state ``S_h`` [head_dim, state] goes token by token ``S_h =
  exp(dt_h A_h) S_h + dt_h x_h B^T``, ``y_h = S_h C + D_h x_h``; ``y = W_out
  gnorm(y * silu(z))``, ``gnorm`` an RMSNorm over each group's ``d_ssm /
  groups`` channels with a learned weight.

Departures, each on purpose:

* The projections, the embedding and the head are handed over in the stored
  type and widened to float32 where they are used (a layer's weights in its
  call, the rows looked up, the head once a block of rows): float32 copies of
  4.4 B parameters are 17.6 GB and do not fit the chip.  Widening changes no
  value.
* The feed-forward and the head walk the sequence in blocks of ``bucket`` rows
  so that their activations and the logits fit; they act row by row, so the
  blocks change no value.
* ``mode="int8"`` and ``mode="fp8"`` are the control, not the reference: every
  matmul input is fake-quantised (weights per output channel, activations per
  token, symmetric; int8, or float8 e4m3), the nearest precisions below
  bfloat16.  The convolution, the recurrence, its state and the softmax stay
  float32.

Weight layout: ``{"embed": [V, H], "layers": [...], "norm": [H], "head": [H,
V]}``; a linear weight is ``[in, out]``.  A layer is a dict of ``ln_in``,
``ln_ff`` [H]; ``wg``, ``wu`` [H, M], ``wd`` [M, H]; ``w_in`` [H, 2 d_ssm + 2
groups state + heads], ``conv`` [conv, taps], ``conv_b`` [conv], ``dt_bias``,
``a_log``, ``d`` [heads], ``gnorm`` [d_ssm], ``w_out`` [d_ssm, H]; ``wq``,
``wk``, ``wv``, ``wo``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


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
    w = w.astype(jnp.float32)
    if mode == "int8":
        x, w = _fq(x, -1), _fq(w, 0)
    elif mode == "fp8":
        x, w = _fq8(x, -1), _fq8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: [T, heads, D] at positions 0..T-1.  Rotate-half form."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


# -- the two sequence mixers, on one whole sequence [T, H] --------------------------

def attention(lp, u, dims, mode):
    hq, hkv, hd, theta, key_mult = dims["attn"]
    t = u.shape[0]
    q = _mm(u, lp["wq"], mode).reshape(t, hq, hd)
    k = (_mm(u, lp["wk"], mode) * key_mult).reshape(t, hkv, hd)
    v = _mm(u, lp["wv"], mode).reshape(t, hkv, hd)
    q, k = rope(q, theta), rope(k, theta)
    q = q.reshape(t, hkv, hq // hkv, hd)
    s = jnp.einsum("tkgd,skd->kgts", q, k, precision=HI) / np.sqrt(hd)
    pos = jnp.arange(t)
    s = jnp.where((pos[:, None] >= pos[None, :])[None, None], s, -jnp.inf)
    o = jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(s, axis=-1), v,
                   precision=HI)
    return _mm(o.reshape(t, hq * hd), lp["wo"], mode)


def _causal_conv(z, taps):
    """z [T, W], taps [W, n]: ``sum_j taps[:, j] z[t - (n-1) + j]``, zeros
    before the sequence."""
    t, n = z.shape[0], taps.shape[1]
    zp = jnp.concatenate([jnp.zeros((n - 1, z.shape[1]), z.dtype), z], 0)
    return sum(taps[:, j][None, :] * zp[j:j + t] for j in range(n))


def ssm(lp, u, dims, mode):
    heads, hd, groups, state, in_mult, mu, eps = dims["ssm"]
    t = u.shape[0]
    d_ssm, gn = heads * hd, groups * state
    mu = jnp.concatenate([jnp.full((n,), m, jnp.float32) for m, n in zip(
        mu, (d_ssm, d_ssm, gn, gn, heads))])
    p = _mm(u * in_mult, lp["w_in"], mode) * mu
    z, xbc, dt = p[:, :d_ssm], p[:, d_ssm:-heads], p[:, -heads:]
    xbc = jax.nn.silu(_causal_conv(xbc, lp["conv"]) + lp["conv_b"])
    x = xbc[:, :d_ssm].reshape(t, heads, hd)
    B = jnp.repeat(xbc[:, d_ssm:d_ssm + gn].reshape(t, groups, state),
                   heads // groups, axis=1)
    C = jnp.repeat(xbc[:, d_ssm + gn:].reshape(t, groups, state),
                   heads // groups, axis=1)
    dt = jax.nn.softplus(dt + lp["dt_bias"])
    A = -jnp.exp(lp["a_log"])

    def token(S, xs):
        xt, bt, ct, dtt = xs
        S = jnp.exp(dtt * A)[:, None, None] * S \
            + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
        return S, jnp.einsum("hpn,hn->hp", S, ct, precision=HI) \
            + lp["d"][:, None] * xt

    _, y = jax.lax.scan(token, jnp.zeros((heads, hd, state), jnp.float32),
                        (x, B, C, dt))
    y = (y.reshape(t, d_ssm) * jax.nn.silu(z)).reshape(t, groups, -1)
    y = rmsnorm(y, lp["gnorm"].reshape(groups, -1), eps).reshape(t, d_ssm)
    return _mm(y, lp["w_out"], mode)


# -- one layer ------------------------------------------------------------------------

class _Dims(dict):
    """Static sizes, hashable by content for ``jit``."""
    def __hash__(self):
        return hash(tuple(sorted(self.items())))


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _mixers(lp, x, dims, mode):
    u = rmsnorm(x, lp["ln_in"], dims["eps"])
    attn_in, attn_out, ssm_out = dims["mult"]
    return x + ssm_out * ssm(lp, u, dims, mode) \
        + attn_out * attention(lp, attn_in * u, dims, mode)


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _feed_forward(lp, x, dims, mode):
    gate, down = dims["mlp"]
    u = rmsnorm(x, lp["ln_ff"], dims["eps"])
    a = _mm(u, lp["wu"], mode) * jax.nn.silu(gate * _mm(u, lp["wg"], mode))
    return x + down * _mm(a, lp["wd"], mode)


def layer_forward(lp, x, dims, bucket, mode=None):
    """One layer on the sequence ``x`` [T, H] at positions 0..T-1, the
    feed-forward in blocks of ``bucket`` rows."""
    x = _mixers(lp, x, dims, mode)
    return jnp.concatenate([_feed_forward(lp, x[r:r + bucket], dims, mode)
                            for r in range(0, x.shape[0], bucket)], axis=0)


def _dims(cfg):
    heads, groups = int(cfg["mamba_n_heads"]), int(cfg["mamba_n_groups"])
    mu = tuple(float(m) for m in cfg["ssm_multipliers"])
    eps = float(cfg["rms_norm_eps"])
    return _Dims(
        eps=eps,
        attn=(int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]),
              int(cfg["head_dim"]), float(cfg["rope_theta"]),
              float(cfg["key_multiplier"])),
        ssm=(heads, int(cfg["mamba_d_head"]), groups,
             int(cfg["mamba_d_state"]),
             float(cfg["ssm_in_multiplier"]), mu, eps),
        mult=(float(cfg["attention_in_multiplier"]),
              float(cfg["attention_out_multiplier"]),
              float(cfg["ssm_out_multiplier"])),
        mlp=tuple(float(m) for m in cfg["mlp_multipliers"]))


@functools.partial(jax.jit, static_argnames=("eps", "mult", "mode"))
def _head_logits(norm_w, head, x, eps, mult, mode):
    return mult * _mm(rmsnorm(x, norm_w, eps), head, mode)


def sequence_logits(weights, cfg, ids, first, bucket, mode=None):
    """Float32 logits of the token sequence ``ids`` [T] at positions
    ``first .. first+bucket-1``: row i is the distribution of token
    ``first+i+1``.  ``ids`` may carry padding past the positions asked for;
    both mixers are causal, so it cannot reach them.  ``T`` is a multiple of
    ``bucket``."""
    dims = _dims(cfg)
    x = weights["embed"][jnp.asarray(ids)].astype(jnp.float32) \
        * float(cfg["embedding_multiplier"])
    for lp in weights["layers"]:
        x = layer_forward(lp, x, dims, bucket, mode)
    rows = jax.lax.dynamic_slice_in_dim(x, first, bucket, axis=0)
    return _head_logits(weights["norm"], weights["head"], rows, dims["eps"],
                        float(cfg["lm_head_multiplier"]), mode)
