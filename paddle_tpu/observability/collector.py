"""The cyclic garbage collector's pauses, measured where they happen.

``install()`` adds one ``gc.callbacks`` hook to the process (a second
call adds nothing).  For a collection of generation 1 or 2 the hook
observes the pause into the default registry's histogram
``runtime.gc.pause_seconds{generation}``, always; while a record window
is live it also records a ``runtime.gc`` span (attr ``generation``), on
the profiler's clock like every other span.  Generation 0 returns after
one comparison: its pauses are tens of microseconds, it is the most
frequent generation, and a span each would disturb the traced run it
measures.

The hook may run inside any allocation, on any thread, including
inside a reader of the histogram or the window's own critical section
on the same thread, so it takes no lock it could wait on: it asks
``spans.recording()`` rather than going live, and an observation that
finds the histogram's lock held waits in a list for the next pause.
"""

from __future__ import annotations

import gc
import time

from .metrics import get_registry
from .spans import recording, span

# pauses from a young generation's 0.1 ms to a full collection's second
PAUSE_BUCKETS = (1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3, 5e-3, 0.01, 0.025,
                 0.05, 0.1, 0.25, 0.5, 1.0, 2.5)

_hist = None
_pending: list = []          # (seconds, generation) not yet observed
_open = [None, None]         # [start ns, the open span or None]


def _on_gc(phase, info):
    gen = info["generation"]
    if gen == 0:
        return
    if phase == "start":
        sp = None
        if recording():
            sp = span("runtime.gc", generation=gen)
            sp.open()
        _open[0], _open[1] = time.perf_counter_ns(), sp
        return
    t0, sp = _open
    if t0 is None:
        return                   # installed between a start and its stop
    _open[0], _open[1] = None, None
    if sp is not None:
        sp.__exit__(None, None, None)
    _pending.append(((time.perf_counter_ns() - t0) * 1e-9, str(gen)))
    while _pending:
        seconds, g = _pending[0]
        if not _hist.try_observe(seconds, generation=g):
            break
        _pending.pop(0)


def install():
    """Hook the collector once per process; idempotent."""
    global _hist
    if _hist is None:
        _hist = get_registry().histogram(
            "runtime.gc.pause_seconds",
            "wall time of one cyclic-collector pass of generation 1 or 2 "
            "(generation 0 is not observed), by generation",
            labels=("generation",), buckets=PAUSE_BUCKETS)
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
