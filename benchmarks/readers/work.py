"""The work the algorithm needs, for the rooflines: (FLOPs, bytes) over the
traced span.  What was done is what the driver counted; what that costs is
the cell's family's to say."""


def decode_step(obs, ctx):
    """Decode steps of the traced span: the matmul weights once a step, and
    the valid keys and values of every live row."""
    t = obs.get("traced")
    if not t or not t["decode_steps"]:
        return None
    work = t["work"]
    return ctx.cell.family.decode_step_work(
        ctx.cell.config["model"], t["decode_steps"], work["decode_tokens"],
        work["decode_context"])


def decode_attention(obs, ctx):
    """Decode attention alone: the valid keys and values read, and its dot
    products."""
    t = obs.get("traced")
    if not t or not t["work"]["decode_context"]:
        return None
    return ctx.cell.family.decode_attention_work(
        ctx.cell.config["model"], t["work"]["decode_context"])


def flash_attention_train(obs, ctx):
    """Causal attention forward and backward of the traced steps, on one
    chip; recomputation not counted."""
    t = obs.get("traced")
    if not t or not t["steps"]:
        return None
    return t["steps"] * obs["attention_flops_per_step"], 0.0
