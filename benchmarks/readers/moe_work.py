"""The work the expert matmuls of the traced decode steps need, for
``kernel.moe_experts_roofline``: what was done is what the driver counted, what
that costs is the cell's family's to say (``expert_work``)."""


def decode_experts(obs, ctx):
    """(FLOPs, bytes): each decoded token's experts, and every expert that
    can get a token read once an expert layer a decode step."""
    t = obs.get("traced")
    work = getattr(ctx.cell.family, "expert_work", None)
    if not t or not t["decode_steps"] or work is None:
        return None
    return work(ctx.cell.config["model"], t["decode_steps"],
                t["work"]["decode_tokens"])
