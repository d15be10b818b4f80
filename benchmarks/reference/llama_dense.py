"""Plain reference of the dense Llama-architecture decoder (Yi-Coder, Mistral).

Straightforward ``jax.numpy`` in float32 with ``highest`` matmul precision: no
kernel, no cache, no batching, nothing imported from the program.  It follows
the published architecture (pre-norm residual blocks, RMSNorm, rotary
embeddings in the rotate-half form, grouped-query causal attention, SwiGLU,
untied output head, mean next-token cross entropy, AdamW with decoupled decay).

Departures, each on purpose:

* ``store`` names the type the configuration keeps its parameters in.  After
  every AdamW update the parameters are rounded to it (to nearest), because
  that is what the configuration states: weights *stored* in bfloat16 with no
  float32 master copy.  All arithmetic stays float32.
* ``mode="int8"`` and ``mode="fp8"`` are the control, not the reference: every
  matmul input is fake-quantised (weights per output channel, activations per
  token, symmetric; int8, or float8 e4m3), the nearest precisions below
  bfloat16.
* Training runs layer by layer and in blocks of rows so that float32 state of
  a 1.5 to 2 B model fits beside its activations; Adam's moments may live in
  host memory (``moments_on_host``).  The mathematics is that of one step on
  the whole batch.

Weight layout: ``{"embed": [V, H], "layers": [{"wq", "wk", "wv", "wo", "wg",
"wu", "wd", "ln1", "ln2"}, ...], "norm": [H], "head": [H, V]}``; a linear
weight is ``[in, out]``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
LAYER_KEYS = ("wq", "wk", "wv", "wo", "wg", "wu", "wd", "ln1", "ln2")


def _fq(x, axis):
    """Symmetric int8 fake quantisation along ``axis`` with a straight-through
    gradient."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + jax.lax.stop_gradient(q - x)


def _fq8(x, axis):
    """Fake quantisation to float8 (e4m3) along ``axis``, scaled so that the
    largest magnitude sits at the format's largest, straight-through."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(x, w, mode):
    if mode == "int8":
        x, w = _fq(x, -1), _fq(w, 0)
    elif mode == "fp8":
        x, w = _fq8(x, -1), _fq8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def rmsnorm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope(x, pos, theta):
    """x: [..., T, heads, D]; pos: [T].  Rotate-half form."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def layer_forward(lp, x, dims, mode="f32"):
    """One decoder layer on rows ``x`` [R, T, H] at positions 0..T-1."""
    hq, hkv, hd, eps, theta = dims
    r, t, _ = x.shape
    pos = jnp.arange(t)
    h = rmsnorm(x, lp["ln1"], eps)
    q = _mm(h, lp["wq"], mode).reshape(r, t, hq, hd)
    k = _mm(h, lp["wk"], mode).reshape(r, t, hkv, hd)
    v = _mm(h, lp["wv"], mode).reshape(r, t, hkv, hd)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    g = hq // hkv
    q = q.reshape(r, t, hkv, g, hd)
    s = jnp.einsum("rtkgd,rskd->rkgts", q, k, precision=HI) / np.sqrt(hd)
    mask = pos[:, None] >= pos[None, :]
    s = jnp.where(mask[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("rkgts,rskd->rtkgd", p, v, precision=HI).reshape(r, t, hq * hd)
    x = x + _mm(o, lp["wo"], mode)
    h = rmsnorm(x, lp["ln2"], eps)
    a = jax.nn.silu(_mm(h, lp["wg"], mode)) * _mm(h, lp["wu"], mode)
    return x + _mm(a, lp["wd"], mode)


def _dims(cfg):
    hq = int(cfg["num_attention_heads"])
    return (hq, int(cfg["num_key_value_heads"]),
            int(cfg.get("head_dim") or cfg["hidden_size"] // hq),
            float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]))


# ---------------------------------------------------------------------------
# serving: logits of one sequence at chosen positions
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _layer_fwd_jit(lp, x, dims, mode):
    return layer_forward(lp, x, dims, mode)


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head_logits(norm_w, head_w, x, eps, mode):
    return _mm(rmsnorm(x, norm_w, eps), head_w, mode)


def sequence_logits(weights, cfg, ids, first, count, mode="f32"):
    """Float32 logits of the token sequence ``ids`` [T] at positions
    ``first .. first+count-1``: row i is the distribution of token
    ``first+i+1``.  ``ids`` may carry padding past the positions asked for;
    attention is causal, so it cannot reach them."""
    dims = _dims(cfg)
    x = weights["embed"][jnp.asarray(ids)][None]
    for lp in weights["layers"]:
        x = _layer_fwd_jit(lp, x, dims, mode)
    rows = jax.lax.dynamic_slice_in_dim(x[0], first, count, axis=0)
    return _head_logits(weights["norm"], weights["head"], rows,
                        dims[3], mode)


# ---------------------------------------------------------------------------
# training: n AdamW steps, layer by layer
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _layer_bwd_jit(lp, x, dy, dims, mode):
    _, vjp = jax.vjp(lambda p, a: layer_forward(p, a, dims, mode), lp, x)
    return vjp(dy)


@functools.partial(jax.jit, static_argnames=("eps", "mode", "n_total"))
def _head_loss_grad(norm_w, head_w, x, labels, eps, mode, n_total):
    def f(nw, hw, a):
        logits = _mm(rmsnorm(a, nw, eps), hw, mode)
        lse = jax.nn.logsumexp(logits, axis=-1)
        pick = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
        return jnp.sum(lse - pick) / n_total
    return jax.value_and_grad(f, argnums=(0, 1, 2))(norm_w, head_w, x)


@functools.partial(jax.jit, static_argnames=("vocab",))
def _embed_grad(tokens, dx, vocab):
    flat = dx.reshape(-1, dx.shape[-1])
    return jnp.zeros((vocab, dx.shape[-1]), jnp.float32).at[
        tokens.reshape(-1)].add(flat)


@functools.partial(jax.jit, static_argnames=("store",), donate_argnums=(0, 2, 3))
def _adamw(p, g, m, v, t, lr, b1, b2, eps, wd, store):
    p = p * (1.0 - lr * wd)
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    mh = m / (1.0 - b1 ** t)
    vh = v / (1.0 - b2 ** t)
    p = p - lr * mh / (jnp.sqrt(vh) + eps)
    if store != "float32":
        p = p.astype(store).astype(jnp.float32)
    return p, m, v


@jax.jit
def _norm(a):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))


def _tree_add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


class _Moments:
    """Adam's two moments of every leaf, on the device or in host memory."""

    def __init__(self, on_host):
        self.on_host = on_host
        self.store = {}

    def get(self, key, like):
        if key not in self.store:
            z = jnp.zeros(like.shape, jnp.float32)
            return z, jnp.zeros(like.shape, jnp.float32)
        m, v = self.store[key]
        if self.on_host:
            return (jax.device_put(m, like.sharding),
                    jax.device_put(v, like.sharding))
        return m, v

    def put(self, key, m, v):
        self.store[key] = ((np.asarray(m), np.asarray(v)) if self.on_host
                           else (m, v))


def train_steps(weights, cfg, batches, hyper, *, mode="f32", store="bfloat16",
                row_block=1, moments_on_host=False, put_rows=None,
                keep_moments=False):
    """Follow ``len(batches)`` AdamW steps from ``weights`` (float32 leaves,
    updated in place in the dict).  ``batches`` is a list of ``(tokens,
    labels)`` int arrays [B, T].  ``hyper``: lr, beta1, beta2, epsilon,
    weight_decay.  ``put_rows`` places a [B, ...] array over devices (identity
    on one device).  Returns the loss of every step and, per leaf in the
    order embed, layers (LAYER_KEYS each), norm, head, the norm of the first
    step's gradient."""
    dims = _dims(cfg)
    eps = dims[3]
    vocab = int(cfg["vocab_size"])
    put_rows = put_rows or (lambda a: a)
    lr, b1, b2 = hyper["lr"], hyper["beta1"], hyper["beta2"]
    aeps, wd = hyper["epsilon"], hyper["weight_decay"]
    moments = _Moments(moments_on_host)
    n_layers = len(weights["layers"])
    losses, grad_norms = [], None

    def update(key, p, g, t, last):
        m, v = moments.get(key, p)
        p, m, v = _adamw(p, g, m, v, float(t), lr, b1, b2, aeps, wd, store)
        if not last or keep_moments:
            moments.put(key, m, v)
        return p

    for step, (tokens, labels) in enumerate(batches, start=1):
        last = step == len(batches)
        b, t = tokens.shape
        tokens_d = put_rows(jnp.asarray(tokens))
        labels_d = put_rows(jnp.asarray(labels))
        acts = [weights["embed"][tokens_d]]
        for lp in weights["layers"]:
            x = acts[-1]
            acts.append(jnp.concatenate(
                [_layer_fwd_jit(lp, x[r:r + row_block], dims, mode)
                 for r in range(0, b, row_block)], axis=0))
        loss, d_norm, d_head, dxs = 0.0, None, None, []
        for r in range(0, b, row_block):
            part, (gn, gh, gx) = _head_loss_grad(
                weights["norm"], weights["head"], acts[-1][r:r + row_block],
                labels_d[r:r + row_block], eps, mode, b * t)
            loss = loss + part
            d_norm = gn if d_norm is None else d_norm + gn
            d_head = gh if d_head is None else d_head + gh
            dxs.append(gx)
        dx = jnp.concatenate(dxs, axis=0)
        losses.append(float(loss))
        norms = {}
        if step == 1:
            norms["norm"], norms["head"] = _norm(d_norm), _norm(d_head)
        weights["norm"] = update("norm", weights["norm"], d_norm, step, last)
        weights["head"] = update("head", weights["head"], d_head, step, last)
        del d_norm, d_head
        acts.pop()
        for li in range(n_layers - 1, -1, -1):
            lp, x = weights["layers"][li], acts.pop()
            g_lp, dxs = None, []
            for r in range(0, b, row_block):
                g_part, gx = _layer_bwd_jit(lp, x[r:r + row_block],
                                            dx[r:r + row_block], dims, mode)
                g_lp = g_part if g_lp is None else _tree_add(g_lp, g_part)
                dxs.append(gx)
            dx = jnp.concatenate(dxs, axis=0)
            for k in LAYER_KEYS:
                if step == 1:
                    norms[(li, k)] = _norm(g_lp[k])
                lp[k] = update((li, k), lp[k], g_lp[k], step, last)
            del g_lp, x
        g_embed = _embed_grad(tokens_d, dx, vocab)
        if step == 1:
            norms["embed"] = _norm(g_embed)
        weights["embed"] = update("embed", weights["embed"], g_embed, step, last)
        del g_embed, dx
        if step == 1:
            grad_norms = [float(norms["embed"])]
            for li in range(n_layers):
                grad_norms += [float(norms[(li, k)]) for k in LAYER_KEYS]
            grad_norms += [float(norms["norm"]), float(norms["head"])]
    return {"losses": losses, "grad_norms": grad_norms}


def flat_leaves(weights):
    """The leaves in the order ``train_steps`` reports them."""
    out = [weights["embed"]]
    for lp in weights["layers"]:
        out += [lp[k] for k in LAYER_KEYS]
    return out + [weights["norm"], weights["head"]]
