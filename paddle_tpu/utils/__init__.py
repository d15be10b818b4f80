"""paddle_tpu.utils (analogue of ``python/paddle/utils``: dlpack interop,
cpp_extension custom-op build/load, run_check environment check) plus
the entry points' compile-cache placement."""

from . import dlpack  # noqa: F401
from . import cpp_extension  # noqa: F401
from .compile_cache import enable_compile_cache  # noqa: F401
from .install_check import run_check  # noqa: F401

__all__ = ["dlpack", "cpp_extension", "enable_compile_cache", "run_check"]
