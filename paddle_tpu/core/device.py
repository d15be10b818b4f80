"""Device management.

TPU-native analogue of ``paddle.device`` (reference:
``python/paddle/device/__init__.py:244 set_device``) and the backend/device
registry (``paddle/phi/backends/device_manager.h:134``).  On JAX, devices are
enumerated by the runtime (PJRT); "places" become thin descriptors wrapping a
``jax.Device``.  The PJRT plugin mechanism is the analogue of the reference's
custom-device C API (``paddle/phi/backends/device_ext.h:94``): third-party
hardware integrates below us, so no extra plugin layer is re-implemented here.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax


class Place:
    """A device descriptor (analogue of ``phi::Place``)."""

    def __init__(self, device_type: str, device_id: int = 0):
        self.device_type = device_type
        self.device_id = device_id

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def is_cpu_place(self):
        return self.device_type == "cpu"

    def is_tpu_place(self):
        return self.device_type == "tpu"

    def jax_device(self) -> jax.Device:
        """The ``jax.Device`` this place names.  A place with no such
        device is an error — handing back some other device would let a
        TPU place run on the CPU unnoticed."""
        devs = [d for d in jax.devices() if d.platform == self.device_type]
        if not 0 <= self.device_id < len(devs):
            raise RuntimeError(
                f"{self!r} names no device: this process has "
                f"{len(devs)} {self.device_type!r} device(s) "
                f"(platforms present: {get_all_device_type()})")
        return devs[self.device_id]


def CPUPlace():
    return Place("cpu", 0)


def TPUPlace(device_id: int = 0):
    return Place("tpu", device_id)


_current_place: Optional[Place] = None


@functools.lru_cache(maxsize=None)
def _default_backend() -> str:
    return jax.devices()[0].platform


def get_all_device_type():
    return sorted({d.platform for d in jax.devices()})


def device_count(device_type: Optional[str] = None) -> int:
    if device_type is None:
        return jax.device_count()
    return len([d for d in jax.devices() if d.platform == device_type])


def set_device(device: str) -> Place:
    """Mirror ``paddle.set_device``; accepts 'cpu', 'tpu', 'tpu:0'."""
    global _current_place
    if ":" in device:
        dtype_, idx = device.split(":", 1)
        place = Place(dtype_, int(idx))
    else:
        place = Place(device, 0)
    _current_place = place
    return place


def get_device() -> str:
    place = current_place()
    return f"{place.device_type}:{place.device_id}"


def current_place() -> Place:
    global _current_place
    if _current_place is None:
        _current_place = Place(_default_backend(), 0)
    return _current_place


def CUDAPlace(dev_id: int = 0) -> Place:
    """API-parity constructor: in this TPU build "cuda" names the
    accelerator, so CUDAPlace maps to the TPU place (the cuda shim in
    paddle_tpu.device does the same for device strings)."""
    return Place("tpu", dev_id)


def is_compiled_with_cuda() -> bool:  # API parity: this build has no CUDA
    return False


def is_compiled_with_tpu() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())


def is_tpu_backend() -> bool:
    return _default_backend() == "tpu"


def synchronize():
    """Block until all dispatched device work completes."""
    (jax.device_put(0.0) + 0).block_until_ready()


class Stream:
    """Stream facade (≙ paddle.device.Stream / cuda streams).

    XLA owns stream scheduling on TPU — compiled programs already overlap
    compute, HBM traffic and collectives — so a Stream here is an ordering
    scope: ``synchronize`` drains the device; ``record_event``/``wait_event``
    give the reference's event-ordering API over block_until_ready.
    """

    def __init__(self, device=None, priority=2):
        self.device = device
        self.priority = priority

    def synchronize(self):
        synchronize()

    def record_event(self, event: "Event" = None) -> "Event":
        event = event or Event()
        event.record(self)
        return event

    def wait_event(self, event: "Event"):
        event.synchronize()

    def wait_stream(self, stream: "Stream"):
        stream.synchronize()


class Event:
    """Event facade (≙ paddle.device.Event): records a point in the
    dispatched work; query/synchronize/elapsed_time over host clocks after a
    device drain."""

    def __init__(self, enable_timing: bool = True, blocking: bool = False):
        self.enable_timing = enable_timing
        self._time_ns = None

    def record(self, stream: Optional[Stream] = None):
        from ..runtime import now_ns
        synchronize()  # device-complete timestamp
        self._time_ns = now_ns()

    def query(self) -> bool:
        return self._time_ns is not None

    def synchronize(self):
        synchronize()

    def elapsed_time(self, end_event: "Event") -> float:
        """Milliseconds between two recorded events."""
        if self._time_ns is None or end_event._time_ns is None:
            raise RuntimeError("both events must be recorded")
        return (end_event._time_ns - self._time_ns) / 1e6


_default_stream = Stream()


def current_stream(device=None) -> Stream:
    return _default_stream


def stream_guard(stream: Stream):
    import contextlib

    @contextlib.contextmanager
    def guard():
        yield stream

    return guard()


def memory_stats(device: Optional[str] = None) -> dict:
    """Device memory statistics: HBM numbers from PJRT plus host-runtime
    counters (≙ paddle/fluid/memory/stats.h surfaced via paddle.device)."""
    from .. import runtime as rt
    if device is None:
        place = current_place()
    elif ":" in device:  # a query must not mutate the current device
        dtype_, idx = device.split(":", 1)
        place = Place(dtype_, int(idx))
    else:
        place = Place(device, 0)
    # the CPU backend reports no allocator statistics (None)
    stats = dict(place.jax_device().memory_stats() or {})
    for name in rt.stat_names():
        stats[f"host.{name}"] = rt.stat_current(name)
    return stats


def max_memory_allocated(device=None) -> int:
    s = memory_stats(device)
    return int(s.get("peak_bytes_in_use", s.get("bytes_in_use", 0)))


def memory_allocated(device=None) -> int:
    return int(memory_stats(device).get("bytes_in_use", 0))


def empty_cache():
    """No-op on XLA (allocator is runtime-managed); kept for API parity."""
