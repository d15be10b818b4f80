"""The Falcon-H1 hybrid decoder (``model_type`` ``falcon_h1``) as the benchmark
sees it: which class of the program it is built from, its leaves in the
program's order with the value of each, the same leaves in the reference's
layout, and the operations and bytes its algorithm needs whatever implements
them.  The harness reaches all of this through ``Cell.family`` and knows none
of it (``benchmarks/README.md``, "A family").  Served only: no training
function.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import lfm2_moe

# leaf kinds; a drawn kind reads ``<kind>@<standard deviation>``.  The
# published multipliers are muP's, set for the trained weights' scale: with
# every matrix drawn at 0.02 (a simulation of one layer at the published
# widths, 256 rows) the state's part of the mixer's ``y`` is 0.2% of ``D x``
# (``B`` and ``C`` come out of the convolution at 0.03 and 0.09, so ``C . B``
# is nothing), the attention's softmax is flat (logits of deviation 0.02: all
# 256 keys alike, key_multiplier 0.011 sees to that) and its output and the
# MLP's are 0.009 and 0.007 beside a stream of 0.11: a lost state, a wrong
# rotary or a wrong cached key would read as sound.  Drawn as below the same
# simulation reads the state's part 0.4 to 2.9 times ``D x`` (by head), the
# attention's logits at deviation 2.0 over some 18 keys, and the mixers'
# outputs 0.113 (SSM) and 0.053 (attention) on a stream of 0.11; the MLP's,
# by the same arithmetic, about 0.1.
# ``ssm_in``: in_proj; ``taps``: the convolution (PyTorch's own for a
# depthwise kernel of 4 would be uniform in +-0.5); ``attn_qk``: q_proj and
# k_proj; ``attn_o``: o_proj; ``mlp``: the three MLP planes.  ``dt_bias`` is
# the public initialisation's: the inverse softplus of a log-uniform draw in
# [0.001, 0.1]; ``a_log`` is ``log(1 .. heads)``, ``D`` ones, the
# convolution's bias zeros.
DRAWN = {"ssm_in": "0.1", "taps": "1.0", "attn_qk": "0.19", "attn_o": "0.05",
         "mlp": "0.05"}
DT_RANGE = (1e-3, 1e-1)
# the published keys the program's configuration class takes as they are
PASSED = ("vocab_size", "hidden_size", "intermediate_size",
          "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
          "head_dim", "hidden_act", "rms_norm_eps", "rope_theta", "rope_scaling",
          "max_position_embeddings", "tie_word_embeddings", "attention_bias",
          "mlp_bias", "projectors_bias", "attn_layer_indices", "mamba_use_mlp",
          "num_logits_to_keep", "mamba_d_ssm", "mamba_n_heads",
          "mamba_d_head", "mamba_n_groups", "mamba_d_state", "mamba_d_conv",
          "mamba_expand", "mamba_chunk_size", "mamba_conv_bias",
          "mamba_proj_bias", "mamba_norm_before_gate", "mamba_rms_norm",
          "embedding_multiplier", "lm_head_multiplier", "mlp_multipliers",
          "key_multiplier", "attention_in_multiplier",
          "attention_out_multiplier", "ssm_multipliers", "ssm_in_multiplier",
          "ssm_out_multiplier")
# read by the family itself, not the program's
OWN = ("dtype", "initializer_range")
# the published keys this family has one answer for (``mlp_expansion_factor``
# shapes nothing where ``intermediate_size`` is given; the SSD chunk is the
# published one)
STATED = {"model_type": "falcon_h1", "mlp_expansion_factor": 8,
          "mamba_chunk_size": 128}


# -- the program's model ----------------------------------------------------------

def build(cfg, train=False, tensor_parallel=False, **options):
    """The program's model from the published keys, its parameters not yet
    materialized: the seed's values are put in next, and the model's own
    initial values would not fit the chip beside them."""
    from paddle_tpu import models
    from paddle_tpu.nn.lazy import placeholders
    if train or tensor_parallel:
        raise ValueError("the falcon_h1 family is served on one chip; it has "
                         "no training function and no tensor-parallel layout")
    for key, want in STATED.items():
        if cfg.get(key, want) != want:
            raise ValueError(f"{key}={cfg[key]!r}: this family has {want!r}")
    unknown = sorted(set(cfg) - set(PASSED) - set(STATED) - set(OWN))
    if unknown:
        raise ValueError(f"published keys {unknown}: the falcon_h1 family has "
                         f"no answer for them")
    keys = {k: cfg[k] for k in PASSED if k in cfg}
    with placeholders():
        model = models.FalconH1ForCausalLM(models.FalconH1Config(**keys,
                                                                 **options))
    model.eval()
    return model


def vocab_size(cfg):
    return int(cfg["vocab_size"])


# -- the leaves ----------------------------------------------------------------------

def _dims(cfg):
    heads, groups = int(cfg["mamba_n_heads"]), int(cfg["mamba_n_groups"])
    d_ssm, state = int(cfg["mamba_d_ssm"]), int(cfg["mamba_d_state"])
    hq, hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    return {"h": int(cfg["hidden_size"]), "m": int(cfg["intermediate_size"]),
            "v": int(cfg["vocab_size"]), "n": int(cfg["num_hidden_layers"]),
            "hq": hq, "hkv": hkv, "hd": int(cfg["head_dim"]),
            "heads": heads, "p": int(cfg["mamba_d_head"]), "groups": groups,
            "state": state, "d_ssm": d_ssm, "taps": int(cfg["mamba_d_conv"]),
            "conv": d_ssm + 2 * groups * state,
            "proj": 2 * d_ssm + 2 * groups * state + heads}


def layer_leaves(cfg):
    """(name inside a layer, shape, kind) of a layer's leaves: every layer is
    alike."""
    d = _dims(cfg)
    h, m, hd = d["h"], d["m"], d["hd"]
    return [("feed_forward.gate_proj.weight", (h, m), "mlp"),
            ("feed_forward.up_proj.weight", (h, m), "mlp"),
            ("feed_forward.down_proj.weight", (m, h), "mlp"),
            ("mamba.conv_weight", (d["conv"], d["taps"]), "taps"),
            ("mamba.conv_bias", (d["conv"],), "zeros"),
            ("mamba.dt_bias", (d["heads"],), "dt_bias"),
            ("mamba.A_log", (d["heads"],), "a_log"),
            ("mamba.D", (d["heads"],), "ones"),
            ("mamba.norm_weight", (d["d_ssm"],), "ones"),
            ("mamba.in_proj.weight", (h, d["proj"]), "ssm_in"),
            ("mamba.out_proj.weight", (d["d_ssm"], h), "normal"),
            ("self_attn.q_proj.weight", (h, d["hq"] * hd), "attn_qk"),
            ("self_attn.k_proj.weight", (h, d["hkv"] * hd), "attn_qk"),
            ("self_attn.v_proj.weight", (h, d["hkv"] * hd), "normal"),
            ("self_attn.o_proj.weight", (d["hq"] * hd, h), "attn_o"),
            ("input_layernorm.weight", (h,), "ones"),
            ("pre_ff_layernorm.weight", (h,), "ones")]


CONSTANT = ("ones", "zeros", "a_log", "dt_bias")


def leaf_specs(cfg):
    """(name, shape, kind) of every leaf in the order of the program's
    ``named_parameters()``: a layer's own parameters before its sublayers'."""
    d = _dims(cfg)
    std = dict(DRAWN, normal=str(float(cfg.get("initializer_range", 0.02))))
    drawn = lambda kind: kind if kind in CONSTANT else f"{kind}@{std[kind]}"  # noqa: E731
    specs = [("model.embed_tokens.weight", (d["v"], d["h"]), drawn("normal"))]
    for i in range(d["n"]):
        specs += [(f"model.layers.{i}.{name}", shape, drawn(kind))
                  for name, shape, kind in layer_leaves(cfg)]
    specs += [("model.final_layernorm.weight", (d["h"],), "ones"),
              ("lm_head.weight", (d["h"], d["v"]), drawn("normal"))]
    return specs


def leaf(key, index, shape, kind, dtype):
    """The value of leaf ``index`` of ``leaf_specs`` under the seed's key."""
    if kind == "zeros":
        return jnp.zeros(shape, dtype)
    if kind == "a_log":
        return jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32)) \
            .astype(dtype)
    if kind == "dt_bias":
        lo, hi = map(jnp.log, DT_RANGE)
        dt = jnp.exp(jax.random.uniform(jax.random.fold_in(key, index), shape,
                                        jnp.float32, lo, hi))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return lfm2_moe.leaf(key, index, shape, kind, dtype)


REFERENCE_NAME = {
    "feed_forward.gate_proj.weight": "wg", "feed_forward.up_proj.weight": "wu",
    "feed_forward.down_proj.weight": "wd", "mamba.conv_weight": "conv",
    "mamba.conv_bias": "conv_b", "mamba.dt_bias": "dt_bias",
    "mamba.A_log": "a_log", "mamba.D": "d", "mamba.norm_weight": "gnorm",
    "mamba.in_proj.weight": "w_in", "mamba.out_proj.weight": "w_out",
    "self_attn.q_proj.weight": "wq", "self_attn.k_proj.weight": "wk",
    "self_attn.v_proj.weight": "wv", "self_attn.o_proj.weight": "wo",
    "input_layernorm.weight": "ln_in", "pre_ff_layernorm.weight": "ln_ff"}
# the projections stay in the stored type: the reference widens a layer's in
# its call
STORED = ("wg", "wu", "wd", "w_in", "w_out", "wq", "wk", "wv", "wo")


def as_reference(cfg, leaves):
    """Flat leaves (``leaf_specs`` order) to the reference's layout: float32,
    but the projections, the embedding and the head in the stored type (the
    reference widens them where it uses them)."""
    leaves = list(leaves)
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    at, layers = 1, []
    for _ in range(_dims(cfg)["n"]):
        lp = {}
        for name, _, _ in layer_leaves(cfg):
            ref = REFERENCE_NAME[name]
            lp[ref] = leaves[at] if ref in STORED else f32(leaves[at])
            at += 1
        layers.append(lp)
    return {"embed": leaves[0], "layers": layers, "norm": f32(leaves[at]),
            "head": leaves[at + 1]}


# -- operations and bytes the algorithm needs, from the configuration and the
# rows' lengths, whatever implements them ---------------------------------------------

def param_counts(cfg):
    """Parameters by what reads them: ``total`` as held here; ``layer`` one
    layer's; ``matmul`` every matmul weight a token passes (the layers'
    projections and the head: the embedding is looked up); ``head``."""
    d = _dims(cfg)
    h, hd = d["h"], d["hd"]
    mlp = 3 * h * d["m"]
    ssm_mm = h * d["proj"] + d["d_ssm"] * h
    ssm_small = d["conv"] * (d["taps"] + 1) + 3 * d["heads"] + d["d_ssm"]
    attn = 2 * h * (d["hq"] + d["hkv"]) * hd
    layer = mlp + ssm_mm + ssm_small + attn + 2 * h
    head = h * d["v"]
    return {"total": d["n"] * layer + 2 * head + h, "layer": layer,
            "matmul": d["n"] * (mlp + ssm_mm + attn) + head, "head": head}


def ssd_state_bytes_per_slot(cfg):
    """The states a slot keeps, float32: heads x head_dim x state a layer."""
    d = _dims(cfg)
    return d["n"] * d["heads"] * d["p"] * d["state"] * 4


def tail_bytes_per_slot(cfg, itemsize=2):
    d = _dims(cfg)
    return d["n"] * (d["taps"] - 1) * d["conv"] * itemsize


def kv_bytes_per_token(cfg, itemsize=2):
    d = _dims(cfg)
    return 2 * d["n"] * d["hkv"] * d["hd"] * itemsize


def _ssd_token_flops(cfg):
    """The decay, the write ``dt x B^T`` (a product and a sum) and ``S C`` (a
    product and a sum) of one token: 5 operations an entry of the state, every
    layer."""
    d = _dims(cfg)
    return 5.0 * d["n"] * d["heads"] * d["p"] * d["state"]


def serve_flops(cfg, tokens, context_sum):
    """Forward FLOPs of ``tokens`` processed positions whose attention spans
    sum to ``context_sum`` keys: 2 x the matmul weights a token passes, the
    state-space recurrence of each layer, and 4 x query heads x head_dim per
    key in each layer (QK and PV)."""
    d = _dims(cfg)
    return (2.0 * param_counts(cfg)["matmul"] * tokens
            + _ssd_token_flops(cfg) * tokens
            + 4.0 * d["n"] * d["hq"] * d["hd"] * context_sum)


def ssd_decode_work(cfg, rows):
    """(FLOPs, bytes) of the state update of ``rows`` decoded tokens alone: the
    state of each live row of each layer once in and once out."""
    return (_ssd_token_flops(cfg) * rows,
            2.0 * ssd_state_bytes_per_slot(cfg) * rows)


def decode_step_work(cfg, steps, tokens, context_sum):
    """(FLOPs, bytes) of ``steps`` decode steps that emitted ``tokens`` tokens
    over ``context_sum`` cached keys: the layers' weights and the head once a
    step, the state and the convolution tails of every live row read and
    written, and the keys and values of the context."""
    pc = param_counts(cfg)
    return (serve_flops(cfg, tokens, context_sum),
            steps * (_dims(cfg)["n"] * pc["layer"] + pc["head"]) * 2
            + ssd_decode_work(cfg, tokens)[1]
            + 2 * tokens * tail_bytes_per_slot(cfg)
            + context_sum * kv_bytes_per_token(cfg))


def decode_attention_work(cfg, context_sum):
    """(FLOPs, bytes) of decode attention alone over ``context_sum`` cached
    keys: its dot products, and the keys and values read."""
    d = _dims(cfg)
    return (4.0 * d["n"] * d["hq"] * d["hd"] * context_sum,
            context_sum * kv_bytes_per_token(cfg))
