"""A second family, wired purely as files: the GPT-2 topology of the program's
``models/gpt.py`` (learned positions, LayerNorm with biases, one fused QKV
projection, biased linears, GELU), served only.  It shows what a family that
is not the dense Llama one brings (``benchmarks/README.md``, "A family"):
published keys under other names, one of them a list, leaves that are neither
normal nor ones, and its own work counts.  No training cell runs it, so it has
no ``train_loss`` and no training counts.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

INIT_STD = 0.02
# published key -> the program's configuration class's
PASSED = {"vocab_size": "vocab_size", "n_embd": "hidden_size",
          "n_layer": "num_hidden_layers", "n_head": "num_attention_heads",
          "n_inner": "intermediate_size",
          "n_positions": "max_position_embeddings",
          "layer_norm_epsilon": "layer_norm_eps",
          "resid_pdrop": "hidden_dropout_prob",
          "attn_pdrop": "attention_probs_dropout_prob"}
STATED = {"architectures": ["GPT2LMHeadModel"], "model_type": "gpt2",
          "activation_function": "gelu", "tie_word_embeddings": False}
LAYER_LEAVES = (("ln_1.weight", "h", "ones"), ("ln_1.bias", "h", "zeros"),
                ("attn.qkv_proj.weight", "h,3h", "normal"),
                ("attn.qkv_proj.bias", "3h", "normal"),
                ("attn.out_proj.weight", "h,h", "normal"),
                ("attn.out_proj.bias", "h", "normal"),
                ("ln_2.weight", "h", "ones"), ("ln_2.bias", "h", "zeros"),
                ("mlp.fc_in.weight", "h,m", "normal"),
                ("mlp.fc_in.bias", "m", "normal"),
                ("mlp.fc_out.weight", "m,h", "normal"),
                ("mlp.fc_out.bias", "h", "normal"))


def build(cfg):
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    for key, want in STATED.items():
        if cfg[key] != want:
            raise ValueError(f"{key}={cfg[key]!r}: this family has {want!r}")
    model = GPTForCausalLM(GPTConfig(**{ours: cfg[theirs]
                                        for theirs, ours in PASSED.items()}))
    model.eval()
    return model


def vocab_size(cfg):
    return int(cfg["vocab_size"])


def leaf_specs(cfg):
    """(name, shape, kind) in the order of the program's
    ``named_parameters()``."""
    sizes = {"h": int(cfg["n_embd"]), "3h": 3 * int(cfg["n_embd"]),
             "m": int(cfg["n_inner"])}
    v, h = int(cfg["vocab_size"]), sizes["h"]
    specs = [("gpt.wte.weight", (v, h), "normal"),
             ("gpt.wpe.weight", (int(cfg["n_positions"]), h), "normal")]
    for i in range(int(cfg["n_layer"])):
        specs += [(f"gpt.h.{i}.{leaf}", tuple(sizes[d] for d in dims.split(",")),
                   kind) for leaf, dims, kind in LAYER_LEAVES]
    return specs + [("gpt.ln_f.weight", (h,), "ones"),
                    ("gpt.ln_f.bias", (h,), "zeros"),
                    ("lm_head.weight", (h, v), "normal")]


def leaf(key, index, shape, kind, dtype):
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind == "zeros":
        return jnp.zeros(shape, dtype)
    k = jax.random.fold_in(key, index)
    return (jax.random.normal(k, shape, jnp.float32) * INIT_STD).astype(dtype)


def as_reference(cfg, leaves):
    """Flat leaves to ``{"wte", "wpe", "layers": [{...}], "ln_f_w", "ln_f_b",
    "head"}``, float32; a layer's keys are ``LAYER_LEAVES``' names."""
    f = [jnp.asarray(a, jnp.float32) for a in leaves]
    k = len(LAYER_LEAVES)
    layers = [dict(zip((name for name, _, _ in LAYER_LEAVES),
                       f[2 + k * i:2 + k * (i + 1)]))
              for i in range(int(cfg["n_layer"]))]
    return {"wte": f[0], "wpe": f[1], "layers": layers, "ln_f_w": f[-3],
            "ln_f_b": f[-2], "head": f[-1]}


# -- work ---------------------------------------------------------------------------

def _matmul_params(cfg):
    h, m = int(cfg["n_embd"]), int(cfg["n_inner"])
    return int(cfg["n_layer"]) * (4 * h * h + 2 * h * m) + h * int(cfg["vocab_size"])


def _kv_bytes_per_token(cfg, itemsize=4):
    return 2 * int(cfg["n_layer"]) * int(cfg["n_embd"]) * itemsize


def serve_flops(cfg, tokens, context_sum):
    return (2.0 * _matmul_params(cfg) * tokens
            + 4.0 * int(cfg["n_layer"]) * int(cfg["n_embd"]) * context_sum)


def decode_step_work(cfg, steps, tokens, context_sum):
    return (serve_flops(cfg, tokens, context_sum),
            steps * _matmul_params(cfg) * 4 + context_sum * _kv_bytes_per_token(cfg))


def decode_attention_work(cfg, context_sum):
    return serve_flops(cfg, 0, context_sum), context_sum * _kv_bytes_per_token(cfg)
