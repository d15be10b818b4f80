"""The work the delta rule of the traced decode steps needs, for
``kernel.kda_decode_roofline``: what was done is what the driver counted, what
that costs is the cell's family's to say (``kda_decode_work``).  A family
without a matrix state gives nothing to read."""


def decode_state(obs, ctx):
    """(FLOPs, bytes): the state of each live row of each KDA layer once in
    and once out a decoded token, and the rule's operations."""
    t = obs.get("traced")
    work = getattr(ctx.cell.family, "kda_decode_work", None)
    if not t or not t["work"]["decode_tokens"] or work is None:
        return None
    return work(ctx.cell.config["model"], t["work"]["decode_tokens"])
