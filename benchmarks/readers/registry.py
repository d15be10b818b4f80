"""One of the program's registry counters over another, each summed over its
labels; over the whole run, warm-up included (``obs["stats"]`` holds the
window's difference of five ``stats()`` keys and no others).  A program
without the second counter gives nothing to read."""

from .moe_load import _values


def ratio(obs, ctx, num, den):
    d = sum(_values(den))
    return sum(_values(num)) / d if d else None
