"""One observed number over another (or over a product of others)."""

from .common import dig


def read(obs, ctx, num, den, scale=1.0):
    n = dig(obs, num)
    d = 1.0
    for key in ([den] if isinstance(den, str) else den):
        part = dig(obs, key)
        if part is None:
            return None
        d *= part
    if n is None or not d:
        return None
    return scale * float(n) / float(d)
