"""The LFM2 sparse hybrid decoder (``model_type`` ``lfm2_moe``) as the
benchmark sees it: which class of the program it is built from, its leaves in
the program's order with the value of each, the same leaves in the reference's
layout, and the operations and bytes its algorithm needs whatever implements
them.  The harness reaches all of this through ``Cell.family`` and knows none
of it (``benchmarks/README.md``, "A family").  Served only: no training
function.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# leaf kinds; a drawn kind reads ``<kind>@<standard deviation>`` or
# ``<kind>@<standard deviation>@<mean>``.  The taps at 0.02 would make a
# convolution layer's output 3% of the stream.  ``expert_bias`` only picks: a
# zero bias would make it a no-op, and one drawn around zero at 0.1 moves the
# weights of a program that wrongly weighs by the biased score by a tenth,
# which reads inside bfloat16's own noise (0.19 against sound runs' 0.06-0.25
# on the chip: PERF.md section 6, PR 33).  Top-k ignores a common offset, so
# the bias is drawn around -0.9, about minus a chosen expert's score: the
# routing is what it was, and weights taken from a biased score near zero are
# garbage.  Its values are not drawn one by one: they are the stratified
# quantiles ``(i + 0.5) / n`` of that normal, dealt to the experts by the seed
# (as the traffic's lengths are), so that every seed has the same biases in
# every layer and decides only which expert gets which.  Drawn one by one, the
# seed also decided how many experts a low bias shuts out of the top four
# (15 to 20 of 64), a chunk and a decode step take as long as the experts
# they touch, and six seeds read `out_tok_s` 4.7% apart (PERF.md section 6,
# PR 33).  ``normal`` is drawn at the configuration's ``initializer_range``
# (0.02 where it has none): at a toy width 0.02 leaves the stream to the
# embedding, and a model whose head is its embedding then answers every token
# with itself.
DRAWN = {"taps": "0.5", "expert_bias": "0.1@-0.9"}
# the published keys the program's configuration class takes as they are
PASSED = ("vocab_size", "hidden_size", "intermediate_size",
          "moe_intermediate_size", "num_hidden_layers", "layer_types",
          "num_dense_layers", "num_attention_heads", "num_key_value_heads",
          "max_position_embeddings", "norm_eps", "conv_L_cache", "conv_bias",
          "num_experts", "num_experts_per_tok", "norm_topk_prob",
          "use_expert_bias", "routed_scaling_factor", "rope_parameters")
# the assumed keys (not in the published config) it takes too
ASSUMED = ("head_dim", "tie_word_embeddings")
# read by the family itself, not the program's
OWN = ("dtype", "initializer_range")
# the published keys this family has one answer for
STATED = {"model_type": "lfm2_moe"}


# -- the program's model ----------------------------------------------------------

def build(cfg, train=False, tensor_parallel=False, **options):
    """The program's model from the published keys, its parameters not yet
    materialized: the seed's values are put in next, and the model's own
    initial values would not fit the chip beside them."""
    from paddle_tpu import models
    from paddle_tpu.nn.lazy import placeholders
    if train or tensor_parallel:
        raise ValueError("the lfm2_moe family is served on one chip; it has "
                         "no training function and no tensor-parallel layout")
    for key, want in STATED.items():
        if cfg.get(key, want) != want:
            raise ValueError(f"{key}={cfg[key]!r}: this family has {want!r}")
    unknown = sorted(set(cfg) - set(PASSED) - set(ASSUMED) - set(STATED)
                     - set(OWN))
    if unknown:
        raise ValueError(f"published keys {unknown}: the lfm2_moe family has "
                         f"no answer for them")
    if not cfg["use_expert_bias"]:
        raise ValueError("use_expert_bias=False: leaf_specs counts the bias")
    keys = {k: cfg[k] for k in PASSED + ASSUMED if k in cfg}
    with placeholders():
        model = models.Lfm2MoeForCausalLM(
            models.Lfm2MoeConfig(**keys, **options))
    model.eval()
    return model


def vocab_size(cfg):
    return int(cfg["vocab_size"])


# -- the leaves ----------------------------------------------------------------------

def _dims(cfg):
    h, hq = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    hd = int(cfg.get("head_dim") or h // hq)
    return {"h": h, "hq": hq, "hd": hd, "q": hq * hd,
            "kv": int(cfg["num_key_value_heads"]) * hd,
            "m": int(cfg["intermediate_size"]),
            "me": int(cfg["moe_intermediate_size"]),
            "e": int(cfg["num_experts"]), "k": int(cfg["num_experts_per_tok"]),
            "v": int(cfg["vocab_size"]), "taps": int(cfg["conv_L_cache"]),
            "dense": int(cfg["num_dense_layers"])}


def layer_leaves(cfg, i):
    """(name inside the layer, shape, kind) of layer ``i``'s leaves."""
    d = _dims(cfg)
    h = d["h"]
    out = [("operator_norm.weight", (h,), "ones")]
    if cfg["layer_types"][i] == "conv":
        out += [("conv.conv_weight", (h, d["taps"]), "taps"),
                ("conv.in_proj.weight", (h, 3 * h), "normal"),
                ("conv.out_proj.weight", (h, h), "normal")]
    else:
        out += [("self_attn.q_proj.weight", (h, d["q"]), "normal"),
                ("self_attn.k_proj.weight", (h, d["kv"]), "normal"),
                ("self_attn.v_proj.weight", (h, d["kv"]), "normal"),
                ("self_attn.out_proj.weight", (d["q"], h), "normal"),
                ("self_attn.q_layernorm.weight", (d["hd"],), "ones"),
                ("self_attn.k_layernorm.weight", (d["hd"],), "ones")]
    out.append(("ffn_norm.weight", (h,), "ones"))
    if i < d["dense"]:
        out += [("feed_forward.w1.weight", (h, d["m"]), "normal"),
                ("feed_forward.w3.weight", (h, d["m"]), "normal"),
                ("feed_forward.w2.weight", (d["m"], h), "normal")]
    else:
        e, me = d["e"], d["me"]
        out += [("feed_forward.router", (h, e), "normal"),
                ("feed_forward.expert_bias", (e,), "expert_bias"),
                ("feed_forward.w1", (e, h, me), "normal"),
                ("feed_forward.w3", (e, h, me), "normal"),
                ("feed_forward.w2", (e, me, h), "normal")]
    return out


def leaf_specs(cfg):
    """(name, shape, kind) of every leaf in the order of the program's
    ``named_parameters()``, walking ``layer_types``.  The head is the
    embedding: no leaf of its own."""
    d = _dims(cfg)
    std = dict(DRAWN, normal=str(float(cfg.get("initializer_range", 0.02))))
    drawn = lambda kind: kind if kind == "ones" else f"{kind}@{std[kind]}"  # noqa: E731
    specs = [("lfm2.embed_tokens.weight", (d["v"], d["h"]), drawn("normal"))]
    for i in range(int(cfg["num_hidden_layers"])):
        specs += [(f"lfm2.layers.{i}.{name}", shape, drawn(kind))
                  for name, shape, kind in layer_leaves(cfg, i)]
    specs.append(("lfm2.embedding_norm.weight", (d["h"],), "ones"))
    return specs


def leaf(key, index, shape, kind, dtype):
    """The value of leaf ``index`` of ``leaf_specs`` under the seed's key."""
    if kind == "ones":
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, index)
    std, mean = (list(map(float, kind.split("@")[1:])) + [0.0])[:2]
    if kind.startswith("expert_bias@"):
        n, = shape
        unit = jax.scipy.special.ndtri((jnp.arange(n) + 0.5) / n)
        unit = jax.random.permutation(k, unit.astype(jnp.float32))
    else:
        unit = jax.random.normal(k, shape, jnp.float32)
    return (unit * std + mean).astype(dtype)


REFERENCE_NAME = {
    "operator_norm.weight": "op_norm", "ffn_norm.weight": "ffn_norm",
    "conv.conv_weight": "taps", "conv.in_proj.weight": "in_proj",
    "conv.out_proj.weight": "out_proj", "self_attn.q_proj.weight": "wq",
    "self_attn.k_proj.weight": "wk", "self_attn.v_proj.weight": "wv",
    "self_attn.out_proj.weight": "wo",
    "self_attn.q_layernorm.weight": "q_norm",
    "self_attn.k_layernorm.weight": "k_norm",
    "feed_forward.w1.weight": "wg", "feed_forward.w3.weight": "wu",
    "feed_forward.w2.weight": "wd", "feed_forward.router": "router",
    "feed_forward.expert_bias": "expert_bias", "feed_forward.w1": "eg",
    "feed_forward.w3": "eu", "feed_forward.w2": "ed"}
STORED = ("eg", "eu", "ed")     # the experts' planes stay in the stored type


def as_reference(cfg, leaves):
    """Flat leaves (``leaf_specs`` order) to the reference's layout: float32,
    but the experts' planes in the stored type (the reference widens one
    expert at a time), every expert held."""
    leaves = list(leaves)
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    at, layers = 1, []
    for i in range(int(cfg["num_hidden_layers"])):
        lp = {}
        for name, _, _ in layer_leaves(cfg, i):
            ref = REFERENCE_NAME[name]
            lp[ref] = leaves[at] if ref in STORED else f32(leaves[at])
            at += 1
        if "router" in lp:
            lp["held"] = (0, int(cfg["num_experts"]))
        layers.append(lp)
    return {"embed": f32(leaves[0]), "layers": layers, "norm": f32(leaves[at])}


# -- operations and bytes the algorithm needs, from the configuration and the
# rows' lengths, whatever implements them ---------------------------------------------

def param_counts(cfg):
    """Parameters by what reads them: ``total``; ``expert`` one expert
    layer's experts; ``dense`` every matmul weight outside the experts that a
    token passes (operators, dense feed-forwards, routers, the tied head);
    ``touched`` what one token multiplies with (``dense`` plus its
    ``num_experts_per_tok`` experts an expert layer)."""
    d = _dims(cfg)
    h = d["h"]
    one_expert = 3 * h * d["me"]
    total = d["v"] * h + h
    dense = h * d["v"]                   # the head, tied to the embedding
    n_sparse = 0
    for i, kind in enumerate(cfg["layer_types"][:int(cfg["num_hidden_layers"])]):
        total += 2 * h
        if kind == "conv":
            op = 4 * h * h
            total += op + h * d["taps"]
        else:
            op = 2 * h * d["q"] + 2 * h * d["kv"]
            total += op + 2 * d["hd"]
        if i < d["dense"]:
            ffn = 3 * h * d["m"]
        else:
            ffn = h * d["e"]
            total += d["e"] + d["e"] * one_expert
            n_sparse += 1
        total += ffn
        dense += op + ffn
    return {"total": total, "expert": d["e"] * one_expert, "dense": dense,
            "one_expert": one_expert, "sparse_layers": n_sparse,
            "touched": dense + n_sparse * d["k"] * one_expert}


def _attention_layers(cfg):
    return sum(t == "full_attention"
               for t in cfg["layer_types"][:int(cfg["num_hidden_layers"])])


def serve_flops(cfg, tokens, context_sum):
    """Forward FLOPs of ``tokens`` processed positions whose attention spans
    sum to ``context_sum`` keys: 2 x the parameters a token touches (its
    ``num_experts_per_tok`` experts, not all), plus 4 x heads x head size per
    key in each attention layer (QK and PV)."""
    d = _dims(cfg)
    return (2.0 * param_counts(cfg)["touched"] * tokens
            + 4.0 * _attention_layers(cfg) * d["q"] * context_sum)


def kv_bytes_per_token(cfg, itemsize=2):
    d = _dims(cfg)
    return 2 * _attention_layers(cfg) * d["kv"] * itemsize


def state_bytes_per_slot(cfg, itemsize=2):
    """The convolution tails a slot keeps: ``conv_L_cache - 1`` rows a layer."""
    d = _dims(cfg)
    n_conv = int(cfg["num_hidden_layers"]) - _attention_layers(cfg)
    return n_conv * (d["taps"] - 1) * d["h"] * itemsize


def experts_counted(cfg):
    """How many experts the load counters of an expert layer count over."""
    return int(cfg["num_experts"])


def experts_read(cfg, steps, tokens):
    """Experts an expert layer must read in one decode step: every expert
    that can get a token, ``min(num_experts, k x live rows)``, from the
    configuration and the driver's own counts alone.  Where the router is
    uneven some of them get none, the count is then too high and the roofline
    reads too high by as much: ``moe.experts_touched_pct`` (the program's own
    counters, reported beside it and never part of this count) says by how
    much."""
    d = _dims(cfg)
    return min(float(d["e"]), d["k"] * tokens / max(steps, 1))


def expert_work(cfg, steps, tokens, itemsize=2):
    """(FLOPs, bytes) of the expert matmuls of ``steps`` decode steps that
    emitted ``tokens`` tokens: each token's ``k`` experts, and every expert
    that can get a token read once a layer a step."""
    pc, d = param_counts(cfg), _dims(cfg)
    return (2.0 * pc["sparse_layers"] * d["k"] * pc["one_expert"] * tokens,
            steps * pc["sparse_layers"] * experts_read(cfg, steps, tokens)
            * pc["one_expert"] * itemsize)


def decode_step_work(cfg, steps, tokens, context_sum):
    """(FLOPs, bytes) of ``steps`` decode steps that emitted ``tokens`` tokens
    over ``context_sum`` valid keys: every weight outside the experts and the
    head once a step, the experts of ``expert_work``, the valid keys and
    values of the attention layers, and the convolution state of every live
    row read and written."""
    pc = param_counts(cfg)
    return (serve_flops(cfg, tokens, context_sum),
            steps * pc["dense"] * 2 + expert_work(cfg, steps, tokens)[1]
            + context_sum * kv_bytes_per_token(cfg)
            + 2 * tokens * state_bytes_per_slot(cfg))


def decode_attention_work(cfg, context_sum):
    """(FLOPs, bytes) of decode attention alone over ``context_sum`` valid
    keys of the attention layers: its dot products, and the keys and values
    read."""
    d = _dims(cfg)
    return (4.0 * _attention_layers(cfg) * d["q"] * context_sum,
            context_sum * kv_bytes_per_token(cfg))
