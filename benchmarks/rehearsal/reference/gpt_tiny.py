"""Plain reference of the GPT-2 topology: ``jax.numpy`` in float32 at
``highest`` matmul precision, no kernel, no cache, nothing imported from the
program.  Pre-norm blocks, LayerNorm with bias, learned positions, one fused
QKV projection whose columns are [q | k | v] and within each [head][head_dim],
causal multi-head attention, GELU (the exact, erf form), untied head.

``mode="fp8"`` is the control, not the reference: every matmul input
fake-quantised to float8 (e4m3), weights per output channel and activations
per token.

Weight layout: ``rehearsal/families/gpt_tiny.py`` ``as_reference``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _fq8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _linear(x, w, b, mode):
    if mode == "fp8":
        x, w = _fq8(x, -1), _fq8(w, 0)
    y = jnp.matmul(x, w, precision=HI)
    return y if b is None else y + b


def _layernorm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _layer(lp, x, heads, eps, mode):
    t, h = x.shape
    hd = h // heads
    a = _layernorm(x, lp["ln_1.weight"], lp["ln_1.bias"], eps)
    qkv = _linear(a, lp["attn.qkv_proj.weight"], lp["attn.qkv_proj.bias"], mode)
    q, k, v = (qkv[:, i * h:(i + 1) * h].reshape(t, heads, hd) for i in range(3))
    s = jnp.einsum("thd,shd->hts", q, k, precision=HI) / np.sqrt(hd)
    pos = jnp.arange(t)
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v,
                   precision=HI).reshape(t, h)
    x = x + _linear(o, lp["attn.out_proj.weight"], lp["attn.out_proj.bias"], mode)
    a = _layernorm(x, lp["ln_2.weight"], lp["ln_2.bias"], eps)
    a = jax.nn.gelu(_linear(a, lp["mlp.fc_in.weight"], lp["mlp.fc_in.bias"], mode),
                    approximate=False)
    return x + _linear(a, lp["mlp.fc_out.weight"], lp["mlp.fc_out.bias"], mode)


def sequence_logits(weights, cfg, ids, first, count, mode="f32"):
    """Float32 logits of the token sequence ``ids`` [T] at positions
    ``first .. first+count-1``: row i is the distribution of token
    ``first+i+1``.  ``ids`` may carry padding past the positions asked for;
    attention is causal, so it cannot reach them."""
    ids = jnp.asarray(ids)
    eps = float(cfg["layer_norm_epsilon"])
    x = weights["wte"][ids] + weights["wpe"][:ids.shape[0]]
    for lp in weights["layers"]:
        x = _layer(lp, x, int(cfg["n_head"]), eps, mode)
    rows = jax.lax.dynamic_slice_in_dim(x, first, count, axis=0)
    return _linear(_layernorm(rows, weights["ln_f_w"], weights["ln_f_b"], eps),
                   weights["head"], None, mode)
