"""Placement of JAX's persistent compilation cache.

A 1.1B train step plus the serving programs is minutes of compiling,
and the directory is part of what makes a cached executable findable
again, so the cache lives where the operator says
(``JAX_COMPILATION_CACHE_DIR``, which JAX reads by itself) or, when
that is unset, at one fixed place inside the checkout.  This module is
the only non-test code that touches ``jax_compilation_cache_dir``.
"""

from __future__ import annotations

import os
from typing import Optional

import jax

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

# resolved from the package, not the cwd: the same checkout finds the
# same cache whatever directory the entry point was started from
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache(path: Optional[str] = None) -> str:
    """Turn the persistent compilation cache on and return its
    directory.  With ``JAX_COMPILATION_CACHE_DIR`` set this is a no-op
    (JAX already honours the variable; ``path`` is ignored so nothing in
    the program can move a cache the environment placed).  Otherwise the
    cache goes to ``path`` — a deployment's own setting, e.g.
    ``inference.Config.set_compilation_cache_dir`` — or to
    ``DEFAULT_CACHE_DIR``.  Call it from an entry point, before the
    first compilation."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = path or DEFAULT_CACHE_DIR
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    # JAX's default skips programs that compile in under a second; the
    # serving path has dozens of those per engine
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
