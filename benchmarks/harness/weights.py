"""Seeded weights, made on the device in one jitted call in the type they are
served or trained in.  The program's model gets these values put into its
parameters; the reference makes the same values by itself from the same seed
(threefry is partitionable, so a leaf's values do not depend on how it is
sharded)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

INIT_STD = 0.02
LAYER_LEAVES = ("q_proj", "k_proj", "v_proj", "o_proj", "gate_proj",
                "up_proj", "down_proj", "input_layernorm",
                "post_attention_layernorm")


def leaf_specs(cfg):
    """(name, shape, kind) of every leaf in the order of the program's
    ``named_parameters()`` for the dense Llama family."""
    h, m = int(cfg["hidden_size"]), int(cfg["intermediate_size"])
    v, hq = int(cfg["vocab_size"]), int(cfg["num_attention_heads"])
    hd = int(cfg.get("head_dim") or h // hq)
    kv = int(cfg["num_key_value_heads"]) * hd
    specs = [("llama.embed_tokens.weight", (v, h), "normal")]
    shapes = {"q_proj": (h, hq * hd), "k_proj": (h, kv), "v_proj": (h, kv),
              "o_proj": (hq * hd, h), "gate_proj": (h, m), "up_proj": (h, m),
              "down_proj": (m, h)}
    for i in range(int(cfg["num_hidden_layers"])):
        for leaf in LAYER_LEAVES:
            if leaf in shapes:
                part = "self_attn" if leaf in ("q_proj", "k_proj", "v_proj",
                                               "o_proj") else "mlp"
                specs.append((f"llama.layers.{i}.{part}.{leaf}.weight",
                              shapes[leaf], "normal"))
            else:
                specs.append((f"llama.layers.{i}.{leaf}.weight", (h,), "ones"))
    specs.append(("llama.norm.weight", (h,), "ones"))
    specs.append(("lm_head.weight", (h, v), "normal"))
    return specs


def _key(seed):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2147483629),
                              seed // 2147483629)


def _leaf(key, index, shape, kind, dtype):
    if kind == "ones":
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, index)
    return (jax.random.normal(k, shape, jnp.float32) * INIT_STD).astype(dtype)


def make(cfg, seed, dtype, shardings=None, indices=None):
    """All leaves (or those at ``indices``) in one jitted call."""
    specs = leaf_specs(cfg)
    indices = list(range(len(specs))) if indices is None else list(indices)

    def gen(key):
        return [_leaf(key, i, specs[i][1], specs[i][2], dtype) for i in indices]

    out_sh = None if shardings is None else [shardings[i] for i in indices]
    return jax.jit(gen, out_shardings=out_sh)(_key(seed))


def as_reference(cfg, leaves):
    """Flat leaves (``leaf_specs`` order) to the reference's layout, float32."""
    f = [jnp.asarray(a, jnp.float32) for a in leaves]
    n = int(cfg["num_hidden_layers"])
    keys = ("wq", "wk", "wv", "wo", "wg", "wu", "wd", "ln1", "ln2")
    layers = [dict(zip(keys, f[1 + 9 * i:1 + 9 * (i + 1)])) for i in range(n)]
    return {"embed": f[0], "layers": layers, "norm": f[-2], "head": f[-1]}
