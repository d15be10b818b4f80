"""Device busy milliseconds per counted unit over the traced span (the busiest
chip)."""

from .common import dig


def read(obs, ctx, per):
    trace = obs.get("trace")
    units = dig(obs, per)
    if trace is None or not units:
        return None
    return 1e3 * max(trace.busy_by_device()) / float(units)
