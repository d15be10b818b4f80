"""The work the algorithm needs, for the rooflines: (FLOPs, bytes) over the
traced span, from the configuration and what the driver counted."""

from ..harness import flops


def decode_step(obs, ctx):
    """Decode steps of the traced span: the matmul weights once a step, and
    the valid keys and values of every live row."""
    cfg = ctx.cell.config["model"]
    t = obs.get("traced")
    if not t or not t["decode_steps"]:
        return None
    work = t["work"]
    nbytes = (t["decode_steps"] * flops.weight_bytes(cfg)
              + work["decode_context"] * flops.kv_bytes_per_token(cfg))
    ops = flops.serve_flops(cfg, work["decode_tokens"], work["decode_context"])
    return ops, nbytes


def decode_attention(obs, ctx):
    """Decode attention alone: the valid keys and values read, and its dot
    products."""
    cfg = ctx.cell.config["model"]
    t = obs.get("traced")
    if not t or not t["work"]["decode_context"]:
        return None
    ctxsum = t["work"]["decode_context"]
    hq = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // hq
    return (4.0 * cfg["num_hidden_layers"] * hq * hd * ctxsum,
            ctxsum * flops.kv_bytes_per_token(cfg))


def flash_attention_train(obs, ctx):
    """Causal attention forward and backward of the traced steps, on one
    chip; recomputation not counted."""
    t = obs.get("traced")
    if not t or not t["steps"]:
        return None
    return t["steps"] * obs["attention_flops_per_step"], 0.0
