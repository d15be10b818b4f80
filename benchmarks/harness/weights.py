"""Seeded weights, made on the device in one jitted call in the type they are
served or trained in.  The program's model gets these values put into its
parameters; the reference makes the same values by itself from the same seed
(threefry is partitionable, so a leaf's values do not depend on how it is
sharded).  Which leaves there are and what one holds is the family's
(``leaf_specs``, ``leaf``): leaf ``i`` draws from ``fold_in(key, i)``."""

from __future__ import annotations

import jax


def _key(seed):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2147483629),
                              seed // 2147483629)


def make(family, cfg, seed, dtype, shardings=None, indices=None):
    """All leaves (or those at ``indices``) in one jitted call."""
    specs = family.leaf_specs(cfg)
    indices = list(range(len(specs))) if indices is None else list(indices)

    def gen(key):
        return [family.leaf(key, i, specs[i][1], specs[i][2], dtype)
                for i in indices]

    out_sh = None if shardings is None else [shardings[i] for i in indices]
    return jax.jit(gen, out_shardings=out_sh)(_key(seed))
