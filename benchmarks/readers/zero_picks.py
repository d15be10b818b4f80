"""The share of the experts' picks that fell on zero-compute experts, from the
program's registry counters ``moe.zero_expert_picks`` and ``moe.expert_picks``
(fed at every harvest from the decode block's own count; over the whole run,
warm-up included).  A program without the counters gives nothing to read."""

from .moe_load import _values


def pct(obs, ctx):
    """Zero-compute picks over all picks of live decode rows; percent."""
    picks = sum(_values("moe.expert_picks"))
    if not picks:
        return None
    return 100.0 * sum(_values("moe.zero_expert_picks")) / picks
