"""The Kimi Linear sparse hybrid decoder (``model_type`` ``kimi_linear``) as
the benchmark sees it: which class of the program it is built from, its leaves
in the program's order with the value of each, the same leaves in the
reference's layout, and the operations and bytes its algorithm needs whatever
implements them.  The harness reaches all of this through ``Cell.family`` and
knows none of it (``benchmarks/README.md``, "A family").  Served only: no
training function.

A configuration of this family may be one chip's share of a deployment:
``num_experts`` counts the experts held here, ``router_experts`` (assumed, not
published under that name: it is the published ``num_experts``) the router's
width and ``first_expert_held`` where the share starts; ``vocab_size`` is the
slice of the vocabulary held.
"""

from __future__ import annotations

import jax.numpy as jnp

# the value of a leaf under the seed's key: ``<kind>@<deviation>@<mean>``,
# ``expert_bias`` as stratified quantiles dealt by the seed; one rule for both
# sparse families
from .lfm2_moe import leaf  # noqa: F401

# leaf kinds; a drawn kind reads ``<kind>@<standard deviation>`` or
# ``<kind>@<standard deviation>@<mean>``.
# ``taps``: at 0.02 a KDA layer's q, k, v would be 4% of what the projections
# give and the SiLU behind them a straight line.
# ``a_log`` and ``dt_bias``: the decay a key channel is ``alpha = exp(-exp(A_log)
# softplus(rate + dt_bias))`` with ``rate`` of deviation 0.22 at these widths;
# around -4.5 at 1.0, times ``exp(A_log)`` at 0.3, ``alpha`` lies in about
# 0.9-0.999: a decay that is 1 to rounding would hide a lost decay, one under
# 0.9 forgets a prompt in a few dozen rows.
# ``expert_bias`` only picks: the stratified quantiles ``(i + 0.5) / n`` of a
# normal around minus a picked score, dealt to the experts by the seed (as
# ``families/lfm2_moe.py`` argues), at a deviation of 0.01: the 8th and 9th of
# 256 scores lie some 0.007 apart, so at 0.1 a low bias would shut an expert
# out for good, a decode step would read fewer planes than the work counts
# take and the seed would decide how many.
# ``mla_q`` and ``mla_o``: at 0.02 an MLA layer's softmax is flat (logits of
# deviation 0.64: a row attends to 400 of 600 keys alike) and its output 0.04
# beside a stream of 1.7, so that nothing of the latent path could show in a
# logit: ``k_pe`` left out of the score read 0.19, inside the sound runs' range
# (my chip run, PR 37).  With the queries at 0.06 the logits have deviation 1.9
# (some 45 keys), and with the output projection at 0.08 the layer's output is
# 0.5, what a KDA layer's is.
DRAWN = {"taps": "0.5", "a_log": "0.3", "dt_bias": "1.0@-4.5",
         "expert_bias": "0.01@-0.85", "mla_q": "0.06", "mla_o": "0.08"}
# the published keys the program's configuration class takes as they are
PASSED = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
          "head_dim", "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
          "qk_rope_head_dim", "v_head_dim", "mla_use_nope", "rope_theta",
          "rope_scaling", "linear_attn_config", "first_k_dense_replace",
          "moe_layer_freq", "num_experts_per_token", "num_shared_experts",
          "moe_renormalize", "moe_router_activation_func", "num_expert_group",
          "topk_group", "use_grouped_topk", "routed_scaling_factor",
          "rms_norm_eps", "hidden_act", "tie_word_embeddings",
          "num_nextn_predict_layers", "model_max_length", "vocab_size")
# the assumed keys (not in the published config) it takes too
ASSUMED = ("kda_gate_rank", "l2_norm_eps")
# read by the family itself, not the program's
OWN = ("dtype", "initializer_range", "num_experts", "router_experts",
       "first_expert_held")
# the published keys this family has one answer for
STATED = {"model_type": "kimi_linear"}


# -- the program's model ----------------------------------------------------------

def build(cfg, train=False, tensor_parallel=False, **options):
    """The program's model from the published keys, its parameters not yet
    materialized: the seed's values are put in next, and the model's own
    initial values would not fit the chip beside them."""
    from paddle_tpu import models
    from paddle_tpu.nn.lazy import placeholders
    if train or tensor_parallel:
        raise ValueError("the kimi_linear family is served on one chip; it "
                         "has no training function and no tensor-parallel "
                         "layout")
    for key, want in STATED.items():
        if cfg.get(key, want) != want:
            raise ValueError(f"{key}={cfg[key]!r}: this family has {want!r}")
    unknown = sorted(set(cfg) - set(PASSED) - set(ASSUMED) - set(STATED)
                     - set(OWN))
    if unknown:
        raise ValueError(f"published keys {unknown}: the kimi_linear family "
                         f"has no answer for them")
    keys = {k: cfg[k] for k in PASSED + ASSUMED if k in cfg}
    d = _dims(cfg)
    with placeholders():
        model = models.KimiLinearForCausalLM(models.KimiLinearConfig(
            **keys, num_experts=d["router"],
            experts_held=(d["first"], d["e"]), **options))
    model.eval()
    return model


def vocab_size(cfg):
    return int(cfg["vocab_size"])


# -- the leaves ----------------------------------------------------------------------

def _dims(cfg):
    lin = cfg["linear_attn_config"]
    e = int(cfg["num_experts"])
    return {"h": int(cfg["hidden_size"]), "m": int(cfg["intermediate_size"]),
            "me": int(cfg["moe_intermediate_size"]), "e": e,
            "router": int(cfg.get("router_experts", e)),
            "first": int(cfg.get("first_expert_held", 0)),
            "k": int(cfg["num_experts_per_token"]),
            "shared": int(cfg["num_shared_experts"]),
            "v": int(cfg["vocab_size"]), "n": int(cfg["num_hidden_layers"]),
            "dense": int(cfg["first_k_dense_replace"]),
            "kh": int(lin["num_heads"]), "kd": int(lin["head_dim"]),
            "kw": int(lin["num_heads"]) * int(lin["head_dim"]),
            "taps": int(lin["short_conv_kernel_size"]),
            "rank_g": int(cfg.get("kda_gate_rank", 128)),
            "hq": int(cfg["num_attention_heads"]),
            "nope": int(cfg["qk_nope_head_dim"]),
            "rope": int(cfg["qk_rope_head_dim"]),
            "vd": int(cfg["v_head_dim"]), "rank": int(cfg["kv_lora_rank"])}


def layer_kind(cfg, i):
    """``kda`` or ``mla`` for layer ``i`` (0-based; the published lists count
    from 1)."""
    return "kda" if i + 1 in cfg["linear_attn_config"]["kda_layers"] else "mla"


def layer_leaves(cfg, i):
    """(name inside the layer, shape, kind) of layer ``i``'s leaves."""
    d = _dims(cfg)
    h, w, r = d["h"], d["kw"], d["rank_g"]
    out = [("input_layernorm.weight", (h,), "ones")]
    if layer_kind(cfg, i) == "kda":
        out += [("self_attn.conv_weight", (3 * w, d["taps"]), "taps"),
                ("self_attn.A_log", (d["kh"],), "a_log"),
                ("self_attn.dt_bias", (w,), "dt_bias"),
                ("self_attn.q_proj.weight", (h, w), "normal"),
                ("self_attn.k_proj.weight", (h, w), "normal"),
                ("self_attn.v_proj.weight", (h, w), "normal"),
                ("self_attn.f_a_proj.weight", (h, r), "normal"),
                ("self_attn.f_b_proj.weight", (r, w), "normal"),
                ("self_attn.b_proj.weight", (h, d["kh"]), "normal"),
                ("self_attn.g_a_proj.weight", (h, r), "normal"),
                ("self_attn.g_b_proj.weight", (r, w), "normal"),
                ("self_attn.o_norm.weight", (d["kd"],), "ones"),
                ("self_attn.o_proj.weight", (w, h), "normal")]
    else:
        hq = d["hq"]
        out += [("self_attn.q_proj.weight",
                 (h, hq * (d["nope"] + d["rope"])), "mla_q"),
                ("self_attn.kv_a_proj_with_mqa.weight",
                 (h, d["rank"] + d["rope"]), "normal"),
                ("self_attn.kv_a_layernorm.weight", (d["rank"],), "ones"),
                ("self_attn.kv_b_proj.weight",
                 (d["rank"], hq * (d["nope"] + d["vd"])), "normal"),
                ("self_attn.o_proj.weight", (hq * d["vd"], h), "mla_o")]
    out.append(("post_attention_layernorm.weight", (h,), "ones"))
    if i < d["dense"]:
        out += [("mlp.gate_proj.weight", (h, d["m"]), "normal"),
                ("mlp.up_proj.weight", (h, d["m"]), "normal"),
                ("mlp.down_proj.weight", (d["m"], h), "normal")]
    else:
        e, me, ms = d["e"], d["me"], d["me"] * d["shared"]
        moe = "block_sparse_moe."
        out += [(moe + "experts.router", (h, d["router"]), "normal"),
                (moe + "experts.expert_bias", (d["router"],), "expert_bias"),
                (moe + "experts.w1", (e, h, me), "normal"),
                (moe + "experts.w3", (e, h, me), "normal"),
                (moe + "experts.w2", (e, me, h), "normal"),
                (moe + "shared_experts.gate_proj.weight", (h, ms), "normal"),
                (moe + "shared_experts.up_proj.weight", (h, ms), "normal"),
                (moe + "shared_experts.down_proj.weight", (ms, h), "normal")]
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
                  for name, shape, kind in layer_leaves(cfg, i)]
    specs += [("model.norm.weight", (d["h"],), "ones"),
              ("lm_head.weight", (d["h"], d["v"]), drawn("normal"))]
    return specs


REFERENCE_NAME = {
    "input_layernorm.weight": "op_norm",
    "post_attention_layernorm.weight": "ffn_norm",
    "self_attn.conv_weight": "taps", "self_attn.A_log": "a_log",
    "self_attn.dt_bias": "dt_bias", "self_attn.q_proj.weight": "wq",
    "self_attn.k_proj.weight": "wk", "self_attn.v_proj.weight": "wv",
    "self_attn.f_a_proj.weight": "f_a", "self_attn.f_b_proj.weight": "f_b",
    "self_attn.b_proj.weight": "wb", "self_attn.g_a_proj.weight": "g_a",
    "self_attn.g_b_proj.weight": "g_b", "self_attn.o_norm.weight": "o_norm",
    "self_attn.o_proj.weight": "wo",
    "self_attn.kv_a_proj_with_mqa.weight": "wkva",
    "self_attn.kv_a_layernorm.weight": "kva_norm",
    "self_attn.kv_b_proj.weight": "wkvb",
    "mlp.gate_proj.weight": "wg", "mlp.up_proj.weight": "wu",
    "mlp.down_proj.weight": "wd",
    "block_sparse_moe.experts.router": "router",
    "block_sparse_moe.experts.expert_bias": "expert_bias",
    "block_sparse_moe.experts.w1": "eg", "block_sparse_moe.experts.w3": "eu",
    "block_sparse_moe.experts.w2": "ed",
    "block_sparse_moe.shared_experts.gate_proj.weight": "sg",
    "block_sparse_moe.shared_experts.up_proj.weight": "su",
    "block_sparse_moe.shared_experts.down_proj.weight": "sd"}
STORED = ("eg", "eu", "ed")     # the experts' planes stay in the stored type


def as_reference(cfg, leaves):
    """Flat leaves (``leaf_specs`` order) to the reference's layout: float32,
    but the experts' planes, the embedding and the head in the stored type
    (the reference widens one expert, the rows looked up and one block of
    logits at a time); ``held`` is the share of the ``router``'s experts
    whose planes are there."""
    leaves = list(leaves)
    d = _dims(cfg)
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    at, layers = 1, []
    for i in range(d["n"]):
        lp = {}
        for name, _, _ in layer_leaves(cfg, i):
            ref = REFERENCE_NAME[name]
            lp[ref] = leaves[at] if ref in STORED else f32(leaves[at])
            at += 1
        if "router" in lp:
            lp["held"] = (d["first"], d["e"])
        layers.append(lp)
    return {"embed": leaves[0], "layers": layers, "norm": f32(leaves[at]),
            "head": leaves[at + 1]}


# -- operations and bytes the algorithm needs, from the configuration and the
# rows' lengths, whatever implements them ---------------------------------------------

def _kinds(cfg):
    return [layer_kind(cfg, i) for i in range(int(cfg["num_hidden_layers"]))]


def param_counts(cfg):
    """Parameters by what reads them: ``total`` as held here; ``one_expert``;
    ``held`` the routed planes of one expert layer held here; ``dense`` every
    matmul weight outside the routed experts that a token passes (operators,
    the dense feed-forward, routers, shared experts, the head's slice);
    ``touched`` what one token multiplies with on the whole deployment
    (``dense`` plus its ``num_experts_per_token`` experts an expert layer);
    ``touched_here`` with only the share of those experts that is held
    here."""
    d = _dims(cfg)
    h, w, r = d["h"], d["kw"], d["rank_g"]
    one_expert = 3 * h * d["me"]
    kda = 4 * h * w + 2 * (h * r + r * w) + h * d["kh"]
    kda_small = 3 * w * d["taps"] + d["kh"] + w + d["kd"]
    mla = (h * d["hq"] * (d["nope"] + d["rope"]) + h * (d["rank"] + d["rope"])
           + d["rank"] * d["hq"] * (d["nope"] + d["vd"])
           + d["hq"] * d["vd"] * h)
    total = 2 * d["v"] * h + h
    dense = h * d["v"]
    n_sparse = 0
    for i, kind in enumerate(_kinds(cfg)):
        total += 2 * h
        op = kda if kind == "kda" else mla
        total += op + (kda_small if kind == "kda" else d["rank"])
        if i < d["dense"]:
            ffn = 3 * h * d["m"]
        else:
            ffn = h * d["router"] + d["shared"] * one_expert
            total += d["router"] + d["e"] * one_expert
            n_sparse += 1
        total += ffn
        dense += op + ffn
    return {"total": total, "one_expert": one_expert,
            "held": d["e"] * one_expert, "dense": dense,
            "sparse_layers": n_sparse,
            "touched": dense + n_sparse * d["k"] * one_expert,
            "touched_here": dense + n_sparse * d["k"] * one_expert
            * d["e"] / d["router"]}


def kda_state_bytes_per_slot(cfg):
    """The matrix states a slot keeps, float32: heads x dk x dv a KDA
    layer."""
    d = _dims(cfg)
    return _kinds(cfg).count("kda") * d["kh"] * d["kd"] * d["kd"] * 4


def tail_bytes_per_slot(cfg, itemsize=2):
    d = _dims(cfg)
    return _kinds(cfg).count("kda") * (d["taps"] - 1) * 3 * d["kw"] * itemsize


def latent_bytes_per_token(cfg, itemsize=2):
    """The latent row a cached token keeps, as the algorithm needs it
    (``kv_lora_rank + qk_rope_head_dim`` values an MLA layer; the program
    rests it padded to whole lanes)."""
    d = _dims(cfg)
    return _kinds(cfg).count("mla") * (d["rank"] + d["rope"]) * itemsize


def _kda_token_flops(cfg):
    """Decay, ``S'^T k``, the outer product and ``S^T q`` of one token: 7
    operations an entry of the state."""
    d = _dims(cfg)
    return 7.0 * _kinds(cfg).count("kda") * d["kh"] * d["kd"] * d["kd"]


def serve_flops(cfg, tokens, context_sum):
    """Forward FLOPs of ``tokens`` processed positions whose attention spans
    sum to ``context_sum`` keys, as this chip's share: 2 x the parameters a
    token touches here (of its ``num_experts_per_token`` experts the held
    share), the delta rule of each KDA layer, and 4 x heads x (the key's and
    the value's width, expanded) per key in each MLA layer."""
    d = _dims(cfg)
    return (2.0 * param_counts(cfg)["touched_here"] * tokens
            + _kda_token_flops(cfg) * tokens
            + 2.0 * _kinds(cfg).count("mla") * d["hq"]
            * (d["nope"] + d["rope"] + d["vd"]) * context_sum)


def experts_counted(cfg):
    """How many experts the load counters of an expert layer count over: the
    router's width, held here or not."""
    return _dims(cfg)["router"]


def expert_work(cfg, steps, tokens, itemsize=2):
    """(FLOPs, bytes) of the routed experts' matmuls of ``steps`` decode
    steps that emitted ``tokens`` tokens: the held share of each token's
    ``k`` experts, and every held expert's planes read once a layer a step
    (at this family's batch every expert gets rows:
    ``moe.experts_touched_pct`` says how nearly)."""
    pc, d = param_counts(cfg), _dims(cfg)
    return (2.0 * pc["sparse_layers"] * d["k"] * d["e"] / d["router"]
            * pc["one_expert"] * tokens,
            steps * pc["sparse_layers"] * pc["held"] * itemsize)


def kda_decode_work(cfg, tokens):
    """(FLOPs, bytes) of the delta rule of ``tokens`` decoded tokens: the
    state of each live row of each KDA layer once in and once out."""
    return (_kda_token_flops(cfg) * tokens,
            2.0 * kda_state_bytes_per_slot(cfg) * tokens)


def decode_step_work(cfg, steps, tokens, context_sum):
    """(FLOPs, bytes) of ``steps`` decode steps that emitted ``tokens`` tokens
    over ``context_sum`` cached rows: every weight outside the routed experts
    and the held planes once a step, the KDA state and the convolution tails
    of every live row read and written, and the latent rows of the
    context."""
    pc = param_counts(cfg)
    return (serve_flops(cfg, tokens, context_sum),
            steps * pc["dense"] * 2 + expert_work(cfg, steps, tokens)[1]
            + kda_decode_work(cfg, tokens)[1]
            + 2 * tokens * tail_bytes_per_slot(cfg)
            + context_sum * latent_bytes_per_token(cfg))


def decode_attention_work(cfg, context_sum):
    """(FLOPs, bytes) of latent decode attention alone over ``context_sum``
    cached rows of the MLA layers: the absorbed dot products (every head
    over a row's ``rank + rope`` values, and its weights over the ``rank``),
    and the rows read."""
    d = _dims(cfg)
    return (2.0 * _kinds(cfg).count("mla") * d["hq"]
            * (2 * d["rank"] + d["rope"]) * context_sum,
            context_sum * latent_bytes_per_token(cfg))
