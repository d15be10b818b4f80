"""Test config: force an 8-device virtual CPU mesh (SURVEY §4 implication:
CPU-XLA fake-device parity, the analogue of fake_cpu_device.h) so distributed
sharding tests run without TPUs.  Tests are hermetic and CPU-only: the chip
is reached only through ``chip_smoke.py`` and the chip tool.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# Persistent XLA compilation cache: the tier-1 suite is COMPILE-bound
# on a 1-core box (most modules trace the same tiny models over and
# over), so warm-cache reruns cut wall time by several minutes.  The
# cache keys on serialized HLO + compile options + backend, so a code
# change that alters any traced program recompiles exactly that
# program — correctness is unaffected.  Opt out by exporting
# JAX_COMPILATION_CACHE_DIR= (empty).
if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    jax.config.update("jax_compilation_cache_dir",
                      "/tmp/paddle_tpu_xla_cache")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

assert jax.devices()[0].platform == "cpu", "tests must run on CPU XLA"
assert jax.device_count() == 8, "expected 8 virtual CPU devices"

import pytest  # noqa: E402


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'`: slow marks long paths (bench-driving
    # tests, full serving traces) that only run on demand / on chip
    config.addinivalue_line(
        "markers", "slow: long-running paths excluded from tier-1")


@pytest.fixture(autouse=True)
def _reseed():
    import paddle_tpu as paddle
    paddle.seed(2024)
    yield
