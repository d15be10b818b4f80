"""Shared predicates for Pallas kernel selection."""

from __future__ import annotations

import contextlib
import functools

import jax

from ...core.flags import flag


@functools.lru_cache(maxsize=None)
def on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


# depth of the active ``partitioned_scope``s (set at TRACE time by the
# builders of multi-device programs; tracing is synchronous under them)
_partitioned_depth = 0


@contextlib.contextmanager
def partitioned_scope(active: bool = True):
    """Mark the enclosed trace as a program GSPMD will partition over
    more than one device.  ``active=False`` is a no-op, so a builder can
    enter it unconditionally with its own mesh test."""
    global _partitioned_depth
    _partitioned_depth += bool(active)
    try:
        yield
    finally:
        _partitioned_depth -= bool(active)


def gspmd_partitioned() -> bool:
    """Is the program being traced one that GSPMD partitions?  Either a
    builder said so (``partitioned_scope``: ``ServingEngine(mesh=)``'s
    tensor-parallel programs, ``TrainStep`` over a mesh) or a global
    mesh of more than one device is set (``fleet.init``), which every
    jit traced under it inherits."""
    if _partitioned_depth:
        return True
    from ...distributed.topology import get_global_mesh
    mesh = get_global_mesh()
    return mesh is not None and mesh.size > 1


def _wanted_on_chip() -> bool:
    return bool(flag("prefer_pallas_kernels")) and on_tpu()


def pallas_enabled() -> bool:
    """The flag, the platform and the partitioning of the program being
    traced — nothing else: a kernel that Mosaic refuses raises at
    compile time instead of rerouting to XLA, and a geometry the chip
    cannot take is rejected by its gate under a named reason (see
    ``decode_attention._gate_shared``).

    A partitioned program takes no kernel at all: ``pallas_call`` has no
    partitioning rule and its TPU lowering raises "Mosaic kernels
    cannot be automatically partitioned. Please wrap the call in a
    shard_map" (jax 0.9.0, four v5e chips) as soon as one sits in a
    multi-device jit.  Until the kernels are wrapped, tensor-parallel
    serving and mesh training run on the XLA paths; the route counters
    say so under ``gspmd_partitioned``."""
    return _wanted_on_chip() and not gspmd_partitioned()


def refused_for_partitioning() -> bool:
    """Of a gate that found ``pallas_enabled()`` false: was partitioning
    the reason (as against the flag or the platform)?"""
    return _wanted_on_chip() and gspmd_partitioned()
