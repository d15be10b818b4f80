"""A kernel's or a step's share of its roofline: the least time the chip could
take for the work the algorithm needs (the larger of operations over peak
FLOP/s and bytes over peak bytes/s) over the device time of the matching
events; percent.  The work comes from a function of the benchmark's own, named
in the metric's file.  A share over 100 means the count is too high or the
time leaves work out, and the run says so."""

import sys

from ..harness.spec import resolve


def read(obs, ctx, work, module, op=None, kernel=None):
    trace = obs.get("trace")
    if trace is None:
        return None
    seconds, n = trace.matching_seconds(module, op, kernel)
    need = resolve(work)(obs, ctx)
    if not n or seconds <= 0 or need is None:
        return None
    flops, nbytes = need
    least = max(flops / ctx.peaks["bf16_flops"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    share = 100.0 * least / seconds
    if share > 100.0:
        print(f"roofline share {share:.1f}% over 100: the work is counted too "
              f"high or the time leaves part of it out ({work})",
              file=sys.stderr, flush=True)
    return share
