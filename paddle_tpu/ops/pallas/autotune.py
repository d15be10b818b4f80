"""Pallas kernel autotuning harness.

TPU analogue of the reference's runtime autotune
(``paddle/phi/kernels/autotune/{auto_tune_base.h,cache.h}``: time each
candidate algorithm once, cache the winner per input signature) and of
CINN's auto_schedule role for kernel configs.

Usage:

    tuned = autotune(
        lambda bq, bk: functools.partial(flash_attention,
                                         block_q=bq, block_k=bk),
        candidates=[(128, 128), (256, 128), (128, 256)],
    )
    out = tuned(q, k, v)      # first call times candidates; later calls
                              # reuse the cached winner for that signature
"""

from __future__ import annotations

import itertools
import time
from typing import Callable, Dict, Sequence, Tuple

import jax

__all__ = ["autotune", "clear_cache", "cache_info", "applied_schedules"]

_CACHE: Dict[Tuple, Tuple] = {}
_ANON = itertools.count()


def _abstract(a):
    if hasattr(a, "shape") and hasattr(a, "dtype"):
        return ("arr", tuple(a.shape), str(a.dtype))
    return ("val", a)


def _signature(args, kwargs):
    sig = [_abstract(a) for a in args]
    sig.extend((k, _abstract(v)) for k, v in sorted(kwargs.items()))
    return tuple(sig)


def _sync(out):
    """Wait for the device to finish ``out``."""
    return jax.block_until_ready(out)


def _time_once(fn, args, kwargs, warmup=1, iters=3) -> float:
    for _ in range(warmup):
        out = fn(*args, **kwargs)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args, **kwargs)
    _sync(out)
    return (time.perf_counter() - t0) / iters


def autotune(make_fn: Callable, candidates: Sequence, name: str = None):
    """make_fn(*candidate) -> callable kernel variant.  Returns a wrapper
    that, per input signature, times every candidate once and caches the
    fastest."""
    label = name
    if label is None:
        base = getattr(make_fn, "__name__", "pallas_op")
        if base == "<lambda>":
            # anonymous factories must not share cache entries: two
            # different lambdas with same-shaped inputs would collide
            base = f"lambda_{next(_ANON)}"
        label = base

    def tuned(*args, **kwargs):
        from ...core.flags import flag
        if not flag("use_autotune"):
            # kill switch (FLAGS_use_autotune): first candidate, no timing
            first = candidates[0]
            first = first if isinstance(first, tuple) else (first,)
            return make_fn(*first)(*args, **kwargs)
        key = (label, _signature(args, kwargs))
        if key in _CACHE:
            best = _CACHE[key][0]
            return make_fn(*best)(*args, **kwargs)
        best, best_t = None, float("inf")
        for cand in candidates:
            cand = cand if isinstance(cand, tuple) else (cand,)
            try:
                t = _time_once(make_fn(*cand), args, kwargs)
            except Exception:
                continue  # invalid config for this shape
            if t < best_t:
                best, best_t = cand, t
        if best is None:
            raise ValueError(
                f"autotune({label}): no candidate config succeeded for "
                f"signature {key[1]}")
        _CACHE[key] = (best, best_t)
        return make_fn(*best)(*args, **kwargs)

    tuned.__name__ = f"autotuned_{label}"
    return tuned


def clear_cache():
    _CACHE.clear()


def cache_info():
    """{(name, signature): (winning_config, seconds)} snapshot."""
    return dict(_CACHE)


# ---------------------------------------------------------------------------
# persistent schedule store (the CINN auto_schedule analogue: searched
# kernel configs survive the process, since every TPU compile is seconds).
# Kernels read it at TRACE time, so it decides what gets compiled: the
# default is the tracked ``schedules.json`` beside the kernels — what a
# checkout compiles depends on nothing outside the checkout — and
# $PTPU_AUTOTUNE_CACHE names another file for a deployment's own tuning.
# ---------------------------------------------------------------------------

def _persist_path():
    import os
    return os.environ.get(
        "PTPU_AUTOTUNE_CACHE",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "schedules.json"))


_PERSIST_MEMO: Dict[Tuple[str, str], object] = {}


def persistent_get(key: str):
    import json
    path = _persist_path()
    memo_key = (path, key)
    if memo_key in _PERSIST_MEMO:
        return _PERSIST_MEMO[memo_key]
    try:
        with open(path) as f:
            value = json.load(f).get(key)
    except (OSError, ValueError):
        value = None
    # memoize (including misses): best_blocks consults this on every
    # eager attention call — disk I/O must not be on the hot path
    _PERSIST_MEMO[memo_key] = value
    return value


def applied_schedules() -> Dict[str, object]:
    """The tuned schedules this process has looked up AND found — i.e.
    what the store changed about the programs compiled so far."""
    return {key: value for (_path, key), value in _PERSIST_MEMO.items()
            if value is not None}


def persistent_put(key: str, value):
    import json
    import os
    import tempfile
    path = _persist_path()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    # re-read immediately before replace + unique temp name: concurrent
    # tuners (multi-host, parallel tests) each merge the freshest snapshot
    # and never share a torn temp file; last writer wins per whole file
    data = {}
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        pass
    data[key] = value
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path),
                               prefix=".autotune-", suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(data, f)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    _PERSIST_MEMO[(path, key)] = value
