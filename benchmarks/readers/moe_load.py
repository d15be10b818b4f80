"""Expert load from the program's registry counters ``moe.expert_tokens`` (by
expert), ``moe.layer_steps`` and ``moe.experts_touched``, which the engine
feeds at every harvest from the decode block's own count; over the whole run,
warm-up included.  A program without the counters gives nothing to read."""


def _values(name):
    from paddle_tpu.observability.metrics import get_registry
    counter = get_registry().snapshot().get(name)
    return list(counter.get("values", {}).values()) if counter else []


def touched_pct(obs, ctx):
    """Experts that got at least one live row, over the experts an expert
    layer has, over every (expert layer, decode step) pair; percent."""
    pairs, touched = sum(_values("moe.layer_steps")), \
        sum(_values("moe.experts_touched"))
    experts = getattr(ctx.cell.family, "experts_counted", None)
    if not pairs or experts is None:
        return None
    return 100.0 * touched / (pairs * experts(ctx.cell.config["model"]))


def max_over_mean(obs, ctx):
    """The busiest expert's rows over the mean expert's."""
    rows = _values("moe.expert_tokens")
    experts = getattr(ctx.cell.family, "experts_counted", None)
    if not rows or not sum(rows) or experts is None:
        return None
    return max(rows) * experts(ctx.cell.config["model"]) / sum(rows)
