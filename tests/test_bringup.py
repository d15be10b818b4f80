"""PR 22 bring-up rules that the CPU can check: the one compile-cache
function, the platform predicates, and the partitioning rule of the
Pallas gates."""

import os

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.core import device
from paddle_tpu.ops.pallas import _common
from paddle_tpu.ops.pallas import decode_attention as da
from paddle_tpu.ops.pallas import quantized_matmul as qmm
from paddle_tpu.utils import compile_cache


def test_compile_cache_env_wins(monkeypatch, tmp_path):
    """Variable set: our code leaves ``jax.config`` alone, whatever
    path a caller passes."""
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    got = compile_cache.enable_compile_cache(str(tmp_path / "mine"))
    assert got == str(tmp_path / "env")
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / "mine").exists()


def test_compile_cache_default_is_in_checkout(monkeypatch):
    """Variable unset: the fixed git-ignored directory beside the
    package, whatever the cwd."""
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir("/")
    try:
        got = compile_cache.enable_compile_cache()
        assert got == os.path.join(root, ".jax_cache") \
            == compile_cache.DEFAULT_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == got
        with open(os.path.join(root, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          before[1])


def test_platform_predicates_know_only_tpu(monkeypatch):
    class Dev:
        def __init__(self, platform):
            self.platform = platform

    for plat, want in (("tpu", True), ("cpu", False), ("gpu", False)):
        monkeypatch.setattr(jax, "devices", lambda p=plat: [Dev(p)])
        _common.on_tpu.cache_clear()
        assert _common.on_tpu() is want
    monkeypatch.undo()
    _common.on_tpu.cache_clear()
    assert device.Place("tpu").is_tpu_place()
    assert not device.Place("cpu").is_tpu_place()
    assert not device.Place("gpu").is_tpu_place()
    # a TPU place on this box names no device; it is not a CPU device
    with pytest.raises(RuntimeError, match="names no device"):
        device.Place("tpu", 0).jax_device()
    assert device.Place("cpu", 3).jax_device() == jax.devices()[3]


def test_partitioned_program_takes_no_kernel(monkeypatch):
    """On the chip a GSPMD-partitioned program cannot hold a Mosaic
    kernel, so inside a builder's scope or under a global mesh every
    gate answers XLA — the counted ones under ``gspmd_partitioned``."""
    from paddle_tpu.distributed.topology import (build_mesh,
                                                 get_global_mesh,
                                                 set_global_mesh)
    monkeypatch.setattr(_common, "on_tpu", lambda: True)
    q4 = jnp.zeros((2, 2, 2, 64), jnp.bfloat16)
    arena = jnp.zeros((9, 8, 128), jnp.bfloat16)
    tables = jnp.zeros((2, 4), jnp.int32)
    x = jnp.zeros((8, 128), jnp.bfloat16)
    w8 = jnp.zeros((128, 128), jnp.int8)

    def reasons():
        return (da._route_decision_paged(q4, arena, tables)[1],
                qmm._qmm_route_reason(x, w8, require_flag=False))

    assert _common.pallas_enabled()
    assert reasons() == ("paged_ok", "int8_ok")
    with da.shard_dispatch_scope(2):
        assert not _common.pallas_enabled()
        assert reasons() == ("gspmd_partitioned", "gspmd_partitioned")
    with da.shard_dispatch_scope(1):        # one shard: not partitioned
        assert _common.pallas_enabled()
    saved = get_global_mesh()
    set_global_mesh(build_mesh(dp=2, mp=2, devices=jax.devices()[:4]))
    try:
        assert reasons() == ("gspmd_partitioned", "gspmd_partitioned")
    finally:
        set_global_mesh(saved)
    assert reasons() == ("paged_ok", "int8_ok")
    # off the chip the reason stays the platform's
    monkeypatch.setattr(_common, "on_tpu", lambda: False)
    with _common.partitioned_scope():
        assert reasons() == ("pallas_unavailable", "pallas_unavailable")
