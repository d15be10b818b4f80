"""A number the driver observed (a count, a clocked time), optionally scaled
or averaged."""

from .common import dig


def read(obs, ctx, key, scale=1.0, mean=False):
    v = dig(obs, key)
    if v is None:
        return None
    if mean:
        if not v:
            return None
        v = sum(v) / len(v)
    return float(v) * scale
