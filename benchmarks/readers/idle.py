"""The device's idle share of the traced span (1 - union of device events over
the span), mean of the chips or the worst chip; percent."""


def read(obs, ctx, worst=False):
    trace = obs.get("trace")
    if trace is None:
        return None
    return trace.idle_pct(worst=worst)
