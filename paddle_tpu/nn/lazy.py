"""Lazy parameter initialization.

Capability analogue of ``paddle.LazyGuard``
(reference: python/paddle/nn/initializer/lazy_init.py — defer parameter
materialization so huge models can be constructed before sharding).  The
TPU design: parameters created under the guard are placed in **host (CPU)
memory** instead of accelerator HBM; they move to the device (or to their
sharded placement) the first time compute touches them or when an
explicit ``shard_tensor``/``device_put`` assigns their layout.  This is
the deferral that matters on TPU — a 70B model's fp32 init fits in host
RAM while the mesh placement decides where each shard lives.
"""

from __future__ import annotations

import contextlib

import jax

__all__ = ["LazyGuard", "in_lazy_mode"]

_LAZY = False
_PLACEHOLDERS = False


def in_lazy_mode() -> bool:
    return _LAZY


class Unmaterialized:
    """What a parameter holds between its construction under
    ``placeholders()`` and its first ``set_value``: the shape, type and
    placement its value will have, and no memory anywhere."""

    __slots__ = ("shape", "dtype", "sharding")

    def __init__(self, shape, dtype, sharding):
        self.shape, self.sharding = tuple(shape), sharding
        self.dtype = jax.numpy.dtype(dtype)

    ndim = property(lambda self: len(self.shape))

    def astype(self, dtype):
        return Unmaterialized(self.shape, dtype, self.sharding)


@contextlib.contextmanager
def placeholders():
    """For a loader, not a mode a user trains under: layers constructed
    inside get ``Unmaterialized`` parameters, which the loader then fills
    one by one with ``set_value``.  A model whose weights are about to be
    read from a checkpoint or generated on the device needs no initial
    values, and one that fills the device cannot afford them beside the
    real ones (10.5 GB twice over on a 16 GB chip)."""
    global _PLACEHOLDERS
    prev, _PLACEHOLDERS = _PLACEHOLDERS, True
    try:
        yield
    finally:
        _PLACEHOLDERS = prev


def placeholder(shape, dtype):
    """The placeholder of a parameter created now, or None outside
    ``placeholders()``."""
    if not _PLACEHOLDERS:
        return None
    return Unmaterialized(
        shape, dtype, jax.sharding.SingleDeviceSharding(jax.devices()[0]))


class LazyGuard:
    """with LazyGuard(): model = BigModel()  -> params live on host."""

    def __enter__(self):
        global _LAZY
        self._prev = _LAZY
        _LAZY = True
        return self

    def __exit__(self, *exc):
        global _LAZY
        _LAZY = self._prev
        return False


def lazy_init_scope():
    """Context under which parameter initializers run: in lazy mode the
    whole init computation executes with the CPU as JAX's default device,
    so the values are *born* in host RAM (never touching HBM — the point
    of lazy init for models larger than a chip); otherwise a no-op."""
    import contextlib
    if not _LAZY:
        return contextlib.nullcontext()
    cpus = jax.devices("cpu")
    if not cpus:
        return contextlib.nullcontext()
    return jax.default_device(cpus[0])
