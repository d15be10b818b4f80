"""Predictor implementation (≙ AnalysisPredictor, SURVEY §3.5).

Serve path: Config names a saved model (paddle_tpu.jit.save artifact:
StableHLO program + weights); create_predictor loads it, places weights on
device once, and compiles the program AOT. ``run`` is the hot loop —
one fused XLA executable call, no Python op dispatch.
"""

from __future__ import annotations

import pickle
import threading
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np


class Config:
    """≙ paddle_infer.Config (analysis_config.cc).

    Knobs with real effect on this backend:

    - ``set_compilation_cache_dir`` — persistent XLA executable cache
      (≙ serialized TRT engines); ignored when the environment sets
      ``JAX_COMPILATION_CACHE_DIR``.
    - ``enable_memory_optim`` — donate input device buffers to the
      executable so XLA reuses them for outputs (≙ memory-reuse passes).
    - ``set_tpu_device_id`` / ``set_device_id`` — place weights and run
      on a specific local device.
    - precision is an EXPORT-TIME property on TPU: pass
      ``precision="bfloat16"`` to ``paddle.jit.save`` — the knob readers
      (``precision_mode``) report what the artifact was exported with.
    - graph passes: XLA's fixed pipeline subsumes the reference's IR pass
      registry; ``pass_builder()`` lists and deletes the REAL
      predictor-level passes (input_donation, persistent_compile_cache)
      and ``switch_ir_optim(False)`` gates them without erasing settings.
    """

    def __init__(self, prog_file: Optional[str] = None,
                 params_file: Optional[str] = None):
        # accept either the jit.save prefix or explicit file paths
        if prog_file is not None and prog_file.endswith(".ptpu_model"):
            self._prefix = prog_file[: -len(".ptpu_model")]
        else:
            self._prefix = prog_file
        self._params_file = params_file
        self._cache_dir: Optional[str] = None
        self._memory_optim = False
        self._glog_info = False
        self._device = None
        self._device_id = 0
        self._ir_optim = True
        self._math_threads = None

    def set_model(self, prefix: str, params_file: Optional[str] = None):
        self._prefix = prefix
        self._params_file = params_file

    def model_dir(self):
        return self._prefix

    def enable_memory_optim(self, flag: bool = True):
        """Donate input buffers to the executable (XLA reuses them)."""
        self._memory_optim = flag

    def memory_optim_enabled(self) -> bool:
        return self._effective_memory_optim()

    # switch_ir_optim(False) gates these without erasing the settings
    def _effective_memory_optim(self) -> bool:
        return bool(self._ir_optim and self._memory_optim)

    def _effective_cache_dir(self):
        return self._cache_dir if self._ir_optim else None

    def disable_glog_info(self):
        self._glog_info = False

    def set_compilation_cache_dir(self, path: str):
        """Persistent XLA executable cache (≙ TRT engine serialization)."""
        self._cache_dir = path

    def enable_tpu(self, device_id: int = 0):
        self._device = "tpu"
        self._device_id = device_id

    def set_tpu_device_id(self, device_id: int):
        self._device_id = device_id

    set_device_id = set_tpu_device_id

    def tpu_device_id(self) -> int:
        return self._device_id

    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0,
                       *a, **k):  # accepted for API parity
        self._device = "tpu"
        self._device_id = device_id

    def disable_gpu(self):
        self._device = "cpu"

    def set_cpu_math_library_num_threads(self, n: int):
        self._math_threads = int(n)

    def cpu_math_library_num_threads(self) -> int:
        return self._math_threads or 1

    def switch_ir_optim(self, flag: bool = True):
        """False GATES the predictor-level program passes (donation +
        persistent compile cache) without destroying their settings —
        toggling back on restores them; XLA's own fixed pipeline always
        runs (it is the compiler, not a pass registry)."""
        self._ir_optim = flag

    def ir_optim(self) -> bool:
        return self._ir_optim

    def delete_pass(self, name: str):
        self.pass_builder().delete_pass(name)

    def pass_builder(self):
        """The passes that actually exist in this serving stack, as a
        controllable registry (the reference's 100-entry IR pass list is
        subsumed by XLA's fixed pipeline; these are the knobs ABOVE it)."""
        cfg = self

        class _PassBuilder:
            def all_passes(self):
                passes = ["xla:fixed-pipeline(fusion,layout,"
                          "rematerialization)"]
                if cfg._effective_memory_optim():
                    passes.append("input_donation")
                if cfg._effective_cache_dir():
                    passes.append("persistent_compile_cache")
                return passes

            def delete_pass(self, name):
                if name == "input_donation":
                    cfg._memory_optim = False
                elif name == "persistent_compile_cache":
                    cfg._cache_dir = None
                # the XLA fixed pipeline is not deletable (it IS the
                # compiler); unknown names are ignored like the reference

        return _PassBuilder()

    def summary(self) -> str:
        return (f"Config(model={self._prefix!r}, device={self._device}"
                f":{self._device_id}, cache_dir={self._cache_dir!r}, "
                f"memory_optim={self._memory_optim})")


class _IOHandle:
    """Zero-copy style tensor handle (≙ ZeroCopyTensor)."""

    def __init__(self, name: str, shape, dtype):
        self.name = name
        self._shape = tuple(shape)
        self._dtype = dtype
        self._array = None

    def shape(self):
        return list(self._shape)

    def copy_from_cpu(self, data: np.ndarray):
        self._array = jnp.asarray(data)

    def share_external_data(self, array):
        """True zero-copy: accept a device array without host staging."""
        self._array = getattr(array, "_value", array)

    def copy_to_cpu(self) -> np.ndarray:
        if self._array is None:
            raise RuntimeError(f"output {self.name!r} not produced yet; "
                               "call predictor.run() first")
        return np.asarray(self._array)

    def to_device_array(self):
        return self._array


class Predictor:
    def __init__(self, config: Config, _shared=None):
        self.config = config
        if _shared is not None:
            (self._exported, self._param_values, self._in_spec,
             self._compiled, self._precision, self._donating) = _shared
        else:
            prefix = config.model_dir()
            if prefix is None:
                raise ValueError("Config has no model path")
            if config._effective_cache_dir():
                # the environment's JAX_COMPILATION_CACHE_DIR wins when
                # set (utils/compile_cache.py)
                from ..utils.compile_cache import enable_compile_cache
                enable_compile_cache(config._effective_cache_dir())
            from jax import export as jax_export
            with open(prefix + ".ptpu_model", "rb") as f:
                self._exported = jax_export.deserialize(f.read())
            with open(prefix + ".ptpu_params", "rb") as f:
                meta = pickle.load(f)
            device = None
            try:
                devices = jax.devices()
                if 0 <= config._device_id < len(devices):
                    device = devices[config._device_id]
            except Exception:
                pass
            self._param_values = [
                jax.device_put(jnp.asarray(v), device) if device is not None
                else jnp.asarray(v) for v in meta["values"]]
            self._in_spec = meta["in_spec"]
            self._precision = meta.get("precision")
            exported = self._exported
            jit_kwargs = {}
            # SNAPSHOT the donation decision: it is baked into the
            # compiled executable, so run() must not re-read the mutable
            # config (a post-create switch_ir_optim(False) would skip
            # the defensive input copies while XLA still donates)
            self._donating = bool(config._effective_memory_optim()
                                  and self._in_spec)
            if self._donating:
                # donate input buffers: XLA may write outputs in place
                jit_kwargs["donate_argnums"] = tuple(
                    range(1, 1 + len(self._in_spec)))
            self._compiled = jax.jit(
                lambda pv, *ins: exported.call(pv, *ins), **jit_kwargs)
        self._precision = getattr(self, "_precision", None)
        self._inputs: Dict[str, _IOHandle] = {}
        self._outputs: Dict[str, _IOHandle] = {}
        self._out_values: Optional[tuple] = None
        self._lock = threading.Lock()
        for i, (shape, dtype) in enumerate(self._in_spec):
            name = f"input_{i}"
            self._inputs[name] = _IOHandle(name, shape, dtype)

    # -- reference API surface --
    def get_input_names(self) -> List[str]:
        return list(self._inputs)

    def get_input_handle(self, name: str) -> _IOHandle:
        return self._inputs[name]

    def get_output_names(self) -> List[str]:
        self._ensure_ran()
        return list(self._outputs)

    def get_output_handle(self, name: str) -> _IOHandle:
        self._ensure_ran()
        return self._outputs[name]

    def run(self, inputs: Optional[List] = None):
        """Execute the compiled program. Either feed via input handles
        (reference style) or pass arrays directly and get arrays back."""
        donating = self._donating
        if inputs is not None:
            arrays = [getattr(a, "_value", None) if hasattr(a, "_value")
                      else jnp.asarray(a) for a in inputs]
            arrays = [a if a is not None else jnp.asarray(b)
                      for a, b in zip(arrays, inputs)]
            if donating:
                # donation invalidates the fed buffers; callers own these
                # arrays (paddle Tensors), so feed defensive copies
                arrays = [jnp.array(a, copy=True) for a in arrays]
        else:
            arrays = []
            for name, h in self._inputs.items():
                if h._array is None:
                    raise RuntimeError(f"input {name!r} not set; call "
                                       "copy_from_cpu first")
                arrays.append(h._array)
            if donating:
                # staged device buffers are predictor-owned (copy_from_cpu
                # staged them); mark them consumed so a second run()
                # cannot feed donated (deleted) buffers
                for h in self._inputs.values():
                    h._array = None
        with self._lock:
            out = self._compiled(self._param_values, *arrays)
        outs = out if isinstance(out, (tuple, list)) else (out,)
        self._outputs = {}
        for i, o in enumerate(outs):
            h = _IOHandle(f"output_{i}", o.shape, o.dtype)
            h._array = o
            self._outputs[h.name] = h
        self._out_values = tuple(outs)
        if inputs is not None:
            return [np.asarray(o) for o in outs]
        return True

    def _ensure_ran(self):
        if not self._outputs:
            # run lazily if inputs are staged (reference returns names after
            # graph load; we materialize them on first demand)
            raise RuntimeError("no outputs yet; call run() first")

    def precision_mode(self) -> Optional[str]:
        """Export-time compute precision of the loaded artifact (set via
        paddle.jit.save(precision=...)); None = full precision."""
        return self._precision

    def clone(self) -> "Predictor":
        """Share weights + executable with a new handle (per-thread serving,
        ≙ AnalysisPredictor::Clone)."""
        return Predictor(self.config,
                         _shared=(self._exported, self._param_values,
                                  self._in_spec, self._compiled,
                                  self._precision, self._donating))


def create_predictor(config: Config) -> Predictor:
    return Predictor(config)
