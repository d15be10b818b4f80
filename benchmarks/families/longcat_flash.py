"""The LongCat-Flash decoder (``LongCat-Flash-Chat``) as the benchmark sees it:
which class of the program it is built from, its leaves in the program's order
with the value of each, the same leaves in the reference's layout, and the
operations and bytes its algorithm needs whatever implements them.  The harness
reaches all of this through ``Cell.family`` and knows none of it
(``benchmarks/README.md``, "A family").  Served only: no training function.

A configuration of this family may be one chip's share of a deployment:
``n_routed_experts`` counts the real experts held here, ``router_experts``
(assumed, not published under that name: it is the published
``n_routed_experts``) the real experts the router scores, ahead of the
``zero_expert_num`` zero-compute ones, and ``first_expert_held`` where the
share starts; ``vocab_size`` is the slice of the vocabulary held.

A zero-compute pick costs no operation and no byte: the work functions count
the real experts a token picks on average, ``moe_topk`` times the real
experts' share of the router's outputs (8 of 12 as published; the seeded
correction bias spreads the picks evenly, and ``moe.zero_pick_pct`` says how
near the run came).
"""

from __future__ import annotations

import jax.numpy as jnp

# the value of a leaf under the seed's key: ``<kind>@<deviation>@<mean>``,
# ``expert_bias`` as stratified quantiles dealt by the seed; one rule for the
# sparse families
from .lfm2_moe import leaf  # noqa: F401

# leaf kinds; a drawn kind reads ``<kind>@<standard deviation>`` or
# ``<kind>@<standard deviation>@<mean>``.
# ``expert_bias`` only picks: the stratified quantiles ``(i + 0.5) / n`` of a
# normal around minus a picked score, dealt to the router's 768 outputs by the
# seed (as ``families/lfm2_moe.py`` argues), real and zero-compute experts
# alike, so that a third of the picks fall on zero-compute experts as the
# published average has it.  A picked softmax score is some 0.011 and the
# 12th and 13th lie some 0.0004 apart at the median, so a deviation of 0.001
# moves many picks (a program that leaves the bias out picks otherwise) and
# shuts no expert out: over 4096 rows the least fed of 512 gets half the mean
# (a CPU draw of the seeded router at its published widths).
# ``mla_o`` and ``ffn_down``, the attention sublayers' output projections and
# the dense feed-forwards' down projections: at 0.02 each attention adds 1.6
# and each feed-forward 3.5 to a stream that grows to 12 in four layers, while
# the MoE adds 0.6 (its zero-compute part) and the held experts 0.1, so that a
# lost pick (``top_k_less_one``) moves a logit by less than bfloat16 does
# (0.028 against 0.064 by the root mean square).  At 0.006 and 0.003 they add
# 0.45 and 0.5 beside the MoE's 0.6 and the lost pick moves a logit 1.7 times
# what bfloat16 does (0.074 against 0.044); every other planted fault moves it
# by 1.1-2.0 either way (a CPU model at an eighth of the widths with each draw
# scaled by the root of 8, logits at 27-34 positions of 256 whose routing is
# no near-tie).
DRAWN = {"expert_bias": "0.001@-0.01", "mla_o": "0.006", "ffn_down": "0.003"}
# the published keys the program's configuration class takes as they are
PASSED = ("vocab_size", "hidden_size", "ffn_hidden_size",
          "expert_ffn_hidden_size", "num_layers", "num_attention_heads",
          "kv_lora_rank", "q_lora_rank", "qk_rope_head_dim", "v_head_dim",
          "qk_nope_head_dim", "mla_scale_q_lora", "mla_scale_kv_lora",
          "routed_scaling_factor", "zero_expert_num", "zero_expert_type",
          "moe_topk", "max_position_embeddings", "rms_norm_eps",
          "rope_theta", "attention_method", "attention_bias")
# read by the family itself, not the program's
OWN = ("dtype", "initializer_range", "n_routed_experts", "router_experts",
       "first_expert_held")


# -- the program's model ----------------------------------------------------------

def build(cfg, train=False, tensor_parallel=False, **options):
    """The program's model from the published keys, its parameters not yet
    materialized: the seed's values are put in next, and the model's own
    initial values would not fit the chip beside them."""
    from paddle_tpu import models
    from paddle_tpu.nn.lazy import placeholders
    if train or tensor_parallel:
        raise ValueError("the longcat_flash family is served on one chip; it "
                         "has no training function and no tensor-parallel "
                         "layout")
    unknown = sorted(set(cfg) - set(PASSED) - set(OWN))
    if unknown:
        raise ValueError(f"published keys {unknown}: the longcat_flash "
                         f"family has no answer for them")
    keys = {k: cfg[k] for k in PASSED if k in cfg}
    d = _dims(cfg)
    with placeholders():
        model = models.LongCatFlashForCausalLM(models.LongCatFlashConfig(
            **keys, n_routed_experts=d["router"],
            experts_held=(d["first"], d["e"]), **options))
    model.eval()
    return model


def vocab_size(cfg):
    return int(cfg["vocab_size"])


# -- the leaves ----------------------------------------------------------------------

def _dims(cfg):
    e = int(cfg["n_routed_experts"])
    return {"h": int(cfg["hidden_size"]), "m": int(cfg["ffn_hidden_size"]),
            "me": int(cfg["expert_ffn_hidden_size"]), "e": e,
            "router": int(cfg.get("router_experts", e)),
            "zero": int(cfg["zero_expert_num"]),
            "first": int(cfg.get("first_expert_held", 0)),
            "k": int(cfg["moe_topk"]), "v": int(cfg["vocab_size"]),
            "n": int(cfg["num_layers"]),
            "hq": int(cfg["num_attention_heads"]),
            "qr": int(cfg["q_lora_rank"]),
            "nope": int(cfg["qk_nope_head_dim"]),
            "rope": int(cfg["qk_rope_head_dim"]),
            "vd": int(cfg["v_head_dim"]), "rank": int(cfg["kv_lora_rank"])}


def _mla_leaves(d, j):
    hq, h = d["hq"], d["h"]
    at = f"self_attn.{j}."
    return [(at + "q_a_proj.weight", (h, d["qr"]), "normal"),
            (at + "q_a_layernorm.weight", (d["qr"],), "ones"),
            (at + "q_b_proj.weight", (d["qr"], hq * (d["nope"] + d["rope"])),
             "normal"),
            (at + "kv_a_proj_with_mqa.weight", (h, d["rank"] + d["rope"]),
             "normal"),
            (at + "kv_a_layernorm.weight", (d["rank"],), "ones"),
            (at + "kv_b_proj.weight", (d["rank"], hq * (d["nope"] + d["vd"])),
             "normal"),
            (at + "o_proj.weight", (hq * d["vd"], h), "mla_o")]


def layer_leaves(cfg):
    """(name inside a layer, shape, kind) of a layer's leaves, in the order of
    the program's ``named_parameters()``: every layer is alike."""
    d = _dims(cfg)
    h, m, me, e = d["h"], d["m"], d["me"], d["e"]
    out = [(f"input_layernorm.{j}.weight", (h,), "ones") for j in (0, 1)]
    out += _mla_leaves(d, 0) + _mla_leaves(d, 1)
    out += [(f"post_attention_layernorm.{j}.weight", (h,), "ones")
            for j in (0, 1)]
    for j in (0, 1):
        out += [(f"mlps.{j}.gate_proj.weight", (h, m), "normal"),
                (f"mlps.{j}.up_proj.weight", (h, m), "normal"),
                (f"mlps.{j}.down_proj.weight", (m, h), "ffn_down")]
    width = d["router"] + d["zero"]
    out += [("mlp.router", (h, width), "normal"),
            ("mlp.expert_bias", (width,), "expert_bias"),
            ("mlp.w1", (e, h, me), "normal"), ("mlp.w3", (e, h, me), "normal"),
            ("mlp.w2", (e, me, h), "normal")]
    return out


def leaf_specs(cfg):
    """(name, shape, kind) of every leaf in the order of the program's
    ``named_parameters()``: a layer's own parameters before its sublayers'."""
    d = _dims(cfg)
    std = dict(DRAWN, normal=str(float(cfg.get("initializer_range", 0.02))))
    drawn = lambda kind: kind if kind == "ones" else f"{kind}@{std[kind]}"  # noqa: E731
    specs = [("model.embed_tokens.weight", (d["v"], d["h"]), drawn("normal"))]
    for i in range(d["n"]):
        specs += [(f"model.layers.{i}.{name}", shape, drawn(kind))
                  for name, shape, kind in layer_leaves(cfg)]
    specs += [("model.norm.weight", (d["h"],), "ones"),
              ("lm_head.weight", (d["h"], d["v"]), drawn("normal"))]
    return specs


def _reference_name(name):
    """A layer leaf's name to the reference's ``(group, key)``: ``group`` a
    sublayer's dict (``attn0``, ``ffn1``, ...) or the layer's own."""
    parts = name.split(".")
    if parts[0] in ("input_layernorm", "post_attention_layernorm"):
        return None, {"input_layernorm": "in", "post_attention_layernorm":
                      "post"}[parts[0]] + parts[1]
    if parts[0] == "self_attn":
        return "attn" + parts[1], parts[2]
    if parts[0] == "mlps":
        return "ffn" + parts[1], parts[2]
    return "moe", parts[1]


def as_reference(cfg, leaves):
    """Flat leaves (``leaf_specs`` order) to the reference's layout: the
    norms, the router and its bias float32, every matrix in the stored type
    (the reference widens a layer's own where it uses them); ``held`` is the
    share of the router's real experts whose planes are there."""
    leaves = list(leaves)
    d = _dims(cfg)
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    at, layers = 1, []
    for _ in range(d["n"]):
        lp = {"moe": {"held": (d["first"], d["e"])}}
        for name, shape, _ in layer_leaves(cfg):
            group, key = _reference_name(name)
            value = f32(leaves[at]) if len(shape) == 1 or key == "router" \
                else leaves[at]
            (lp if group is None else lp.setdefault(group, {}))[key] = value
            at += 1
        layers.append(lp)
    return {"embed": leaves[0], "layers": layers, "norm": f32(leaves[at]),
            "head": leaves[at + 1]}


# -- operations and bytes the algorithm needs, from the configuration and the
# rows' lengths, whatever implements them ---------------------------------------------

def real_picks(cfg):
    """The real experts a token picks on average: ``moe_topk`` times the real
    experts' share of the router's outputs."""
    d = _dims(cfg)
    return d["k"] * d["router"] / (d["router"] + d["zero"])


def param_counts(cfg):
    """Parameters by what reads them: ``total`` as held here; ``one_expert``;
    ``held`` the routed planes of one layer held here; ``dense`` every matrix
    outside the routed experts that a token passes (the two latent attentions,
    the two dense feed-forwards, the router, the head's slice); ``touched``
    what one token multiplies with on the whole deployment (``dense`` plus its
    real experts, ``real_picks`` a layer); ``touched_here`` with only the
    share of those experts that is held here."""
    d = _dims(cfg)
    h, hq = d["h"], d["hq"]
    one_expert = 3 * h * d["me"]
    mla = (h * d["qr"] + d["qr"] * hq * (d["nope"] + d["rope"])
           + h * (d["rank"] + d["rope"])
           + d["rank"] * hq * (d["nope"] + d["vd"]) + hq * d["vd"] * h)
    ffn = 3 * h * d["m"]
    router = h * (d["router"] + d["zero"])
    layer_dense = 2 * mla + 2 * ffn + router
    layer_small = 4 * h + 2 * (d["qr"] + d["rank"]) + d["router"] + d["zero"]
    n = d["n"]
    total = (2 * d["v"] * h + h
             + n * (layer_dense + layer_small + d["e"] * one_expert))
    dense = n * layer_dense + h * d["v"]
    touched = dense + n * real_picks(cfg) * one_expert
    return {"total": total, "one_expert": one_expert,
            "held": d["e"] * one_expert, "dense": dense, "layers": n,
            "mla": mla, "ffn": ffn, "touched": touched,
            "touched_here": dense + n * real_picks(cfg) * one_expert
            * d["e"] / d["router"]}


def latent_bytes_per_token(cfg, itemsize=2):
    """The latent rows a cached token keeps, as the algorithm needs them
    (``kv_lora_rank + qk_rope_head_dim`` values in each of a layer's two
    attention sublayers)."""
    d = _dims(cfg)
    return 2 * d["n"] * (d["rank"] + d["rope"]) * itemsize


def cache_bytes_per_token(cfg, itemsize=2):
    """The same rows as the program rests them, padded to whole lanes of
    128."""
    d = _dims(cfg)
    return 2 * d["n"] * (-(-(d["rank"] + d["rope"]) // 128) * 128) * itemsize


def serve_flops(cfg, tokens, context_sum):
    """Forward FLOPs of ``tokens`` processed positions whose attention spans
    sum to ``context_sum`` keys, as this chip's share: 2 x the parameters a
    token touches here (of its real experts the held share; a zero-compute
    pick costs nothing), and 4 x heads x (the key's and the value's width,
    expanded) per key in each attention sublayer."""
    d = _dims(cfg)
    return (2.0 * param_counts(cfg)["touched_here"] * tokens
            + 2.0 * 2 * d["n"] * d["hq"]
            * (d["nope"] + d["rope"] + d["vd"]) * context_sum)


def experts_counted(cfg):
    """How many experts the load counters of an expert layer count over: the
    router's real experts, held here or not (zero-compute picks are counted
    apart)."""
    return _dims(cfg)["router"]


def expert_work(cfg, steps, tokens, itemsize=2):
    """(FLOPs, bytes) of the routed experts' matmuls of ``steps`` decode
    steps that emitted ``tokens`` tokens: the held share of each token's real
    experts, and every held expert's planes read once a layer a step (at
    this batch some 87% of them get rows: ``moe.experts_touched_pct`` says how
    nearly)."""
    pc, d = param_counts(cfg), _dims(cfg)
    return (2.0 * pc["layers"] * real_picks(cfg) * d["e"] / d["router"]
            * pc["one_expert"] * tokens,
            steps * pc["layers"] * pc["held"] * itemsize)


def decode_step_work(cfg, steps, tokens, context_sum):
    """(FLOPs, bytes) of ``steps`` decode steps that emitted ``tokens`` tokens
    over ``context_sum`` cached rows: every matrix outside the routed experts
    and the held planes once a step, and the latent rows of the context."""
    pc = param_counts(cfg)
    return (serve_flops(cfg, tokens, context_sum),
            steps * pc["dense"] * 2 + expert_work(cfg, steps, tokens)[1]
            + context_sum * latent_bytes_per_token(cfg))


def decode_attention_work(cfg, context_sum):
    """(FLOPs, bytes) of latent decode attention alone over ``context_sum``
    cached rows of each attention sublayer: the absorbed dot products (every
    head over a row's ``rank + rope`` values, and its weights over the
    ``rank``), and the rows read."""
    d = _dims(cfg)
    return (2.0 * 2 * d["n"] * d["hq"] * (2 * d["rank"] + d["rope"])
            * context_sum, context_sum * latent_bytes_per_token(cfg))
