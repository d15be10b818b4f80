"""The dense Llama-architecture decoder (Yi-Coder, Mistral) as the benchmark
sees it: which class of the program it is built from, its leaves in the
program's order with the value of each, the same leaves in the reference's
layout, and the operations and bytes its algorithm needs whatever implements
them.  The harness reaches all of this through ``Cell.family`` and knows none
of it (``benchmarks/README.md``, "A family").
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

INIT_STD = 0.02
LAYER_LEAVES = ("q_proj", "k_proj", "v_proj", "o_proj", "gate_proj",
                "up_proj", "down_proj", "input_layernorm",
                "post_attention_layernorm")
# the published keys the program's configuration class takes as they are
PASSED = ("vocab_size", "hidden_size", "intermediate_size",
          "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
          "max_position_embeddings", "rms_norm_eps", "rope_theta",
          "tie_word_embeddings")
# the published keys this family has one answer for; another value is
# another family
STATED = {"architectures": ["LlamaForCausalLM"], "model_type": "llama",
          "hidden_act": "silu", "attention_bias": False, "mlp_bias": False}


# -- the program's model ----------------------------------------------------------

def build(cfg, train=False, tensor_parallel=False, **options):
    """The program's model from the published keys, its weights not yet the
    seed's.  ``tensor_parallel``: the layout divides the matmuls; ``options``
    are the mix's ``model_options`` for the program's configuration class."""
    from paddle_tpu import models
    for key, want in STATED.items():
        if cfg.get(key, want) != want:
            raise ValueError(f"{key}={cfg[key]!r}: the dense Llama family "
                             f"has {want!r}")
    if cfg.get("torch_dtype", cfg["dtype"]) != cfg["dtype"]:
        raise ValueError(f"dtype {cfg['dtype']!r} is not the published "
                         f"torch_dtype {cfg['torch_dtype']!r}")
    if train:
        options = dict(options, tensor_parallel=bool(tensor_parallel),
                       fused_linear_loss=not tensor_parallel)
    lcfg = models.LlamaConfig(**{k: cfg[k] for k in PASSED}, **options)
    model = models.LlamaForCausalLM(lcfg)
    model.train() if train else model.eval()
    return model


def train_loss(model):
    """``loss_fn(net, tokens, labels)`` as ``TrainStep`` wants it."""
    from paddle_tpu.models import LlamaPretrainingCriterion
    criterion = LlamaPretrainingCriterion(model.config)

    def loss_fn(net, tokens, labels):
        if model.config.fused_linear_loss:
            return net(tokens, labels=labels)[0]
        return criterion(net(tokens), labels)

    return loss_fn


def vocab_size(cfg):
    """How many token ids the model as built takes and gives."""
    return int(cfg["vocab_size"])


# -- the leaves ----------------------------------------------------------------------

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


def leaf(key, index, shape, kind, dtype):
    """The value of leaf ``index`` of ``leaf_specs`` under the seed's key."""
    if kind == "ones":
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, index)
    return (jax.random.normal(k, shape, jnp.float32) * INIT_STD).astype(dtype)


def as_reference(cfg, leaves):
    """Flat leaves (``leaf_specs`` order) to the reference's layout, float32."""
    f = [jnp.asarray(a, jnp.float32) for a in leaves]
    n = int(cfg["num_hidden_layers"])
    keys = ("wq", "wk", "wv", "wo", "wg", "wu", "wd", "ln1", "ln2")
    layers = [dict(zip(keys, f[1 + 9 * i:1 + 9 * (i + 1)])) for i in range(n)]
    return {"embed": f[0], "layers": layers, "norm": f[-2], "head": f[-1]}


# -- operations and bytes the algorithm needs, from the configuration and the
# rows' lengths, whatever implements them ---------------------------------------------

def param_counts(cfg):
    h, m = int(cfg["hidden_size"]), int(cfg["intermediate_size"])
    v, hq = int(cfg["vocab_size"]), int(cfg["num_attention_heads"])
    hd = int(cfg.get("head_dim") or h // hq)
    kv = int(cfg["num_key_value_heads"]) * hd
    layers = int(cfg["num_hidden_layers"])
    per_layer = h * hq * hd * 2 + 2 * h * kv + 3 * h * m + 2 * h
    embed = v * h
    head = h * v
    return {"total": layers * per_layer + embed + head + h,
            "matmul": layers * (per_layer - 2 * h) + head,
            "embed": embed}


def train_flops_per_token(cfg, seq):
    """6 x N_matmul + 6 x L x seq x hidden (``bench.py``'s count: forward and
    backward of every matmul and of causal attention; gather excluded;
    recomputation not counted)."""
    n = param_counts(cfg)["matmul"]
    return 6.0 * n + 6.0 * int(cfg["num_hidden_layers"]) * seq * int(cfg["hidden_size"])


def attention_train_flops(cfg, batch, seq):
    """Causal attention alone, forward and backward, of one step: 6 x L x
    seq x hidden per token."""
    return 6.0 * int(cfg["num_hidden_layers"]) * seq * int(cfg["hidden_size"]) * batch * seq


def serve_flops(cfg, tokens, context_sum):
    """Forward FLOPs of ``tokens`` processed positions whose attention spans
    sum to ``context_sum`` keys: 2 x N_matmul per token plus 4 x hidden per
    key per layer (QK and PV)."""
    n = param_counts(cfg)["matmul"]
    hq = int(cfg["num_attention_heads"])
    hd = int(cfg.get("head_dim") or int(cfg["hidden_size"]) // hq)
    return 2.0 * n * tokens + 4.0 * int(cfg["num_hidden_layers"]) * hq * hd * context_sum


def kv_bytes_per_token(cfg, itemsize=2):
    hq = int(cfg["num_attention_heads"])
    hd = int(cfg.get("head_dim") or int(cfg["hidden_size"]) // hq)
    return 2 * int(cfg["num_hidden_layers"]) * int(cfg["num_key_value_heads"]) * hd * itemsize


def weight_bytes(cfg, itemsize=2):
    """Bytes one decode step must read of the weights: every matmul weight
    once (the embedding is gathered, a row per token)."""
    return param_counts(cfg)["matmul"] * itemsize


def decode_step_work(cfg, steps, tokens, context_sum):
    """(FLOPs, bytes) of ``steps`` decode steps that emitted ``tokens`` tokens
    over ``context_sum`` valid keys: the matmul weights once a step, and the
    keys and values of every live row."""
    return (serve_flops(cfg, tokens, context_sum),
            steps * weight_bytes(cfg) + context_sum * kv_bytes_per_token(cfg))


def decode_attention_work(cfg, context_sum):
    """(FLOPs, bytes) of decode attention alone over ``context_sum`` valid
    keys: its dot products, and the keys and values read."""
    return (serve_flops(cfg, 0, context_sum),
            context_sum * kv_bytes_per_token(cfg))
