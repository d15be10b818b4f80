"""Device time of the programs (or of the operations inside them) whose names
match, per unit of work counted over the traced span; milliseconds."""

from .common import dig


def read(obs, ctx, module, per, op=None, kernel=None, per_scale=1.0):
    trace = obs.get("trace")
    if trace is None:
        return None
    seconds, n = trace.matching_seconds(module, op, kernel)
    units = dig(obs, per)
    if not n or not units:
        return None
    return 1e3 * seconds / (float(units) * per_scale)
