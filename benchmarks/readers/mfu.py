"""The whole step's share of the chips' peak: FLOPs the algorithm needs per
second of the window over chips x peak; percent."""

from .common import dig


def read(obs, ctx, flops=None, seconds="window_s", flops_per_unit=None, rate=None):
    if flops is not None:
        f, s = dig(obs, flops), dig(obs, seconds)
        if f is None or not s:
            return None
        per_s = f / s
    else:
        a, b = dig(obs, flops_per_unit), dig(obs, rate)
        if a is None or b is None:
            return None
        per_s = a * b
    return 100.0 * per_s / (ctx.cell.chips * ctx.peaks["bf16_flops"])
