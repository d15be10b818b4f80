"""Offline Pallas schedule search for every kernel in the pack.

Generalization of the flash-attention block search (the CINN
``auto_schedule`` role, ``paddle/cinn/auto_schedule/search_space/
search_space.h:41``): each kernel exposes its block-size space here, the
harness times every feasible candidate EAGERLY on the real device and
persists the winner keyed by ``kernel/shape/dtype/chip`` — kernels then
consult the store at trace time (timing is impossible inside jit), and
fall back to their measured-default heuristics on a miss.

Run ``python tools/tune_pallas_schedules.py`` on the chip to (re)search
the 1.1B Llama shapes; winners land in the same schedule store the flash
search uses (the tracked ``schedules.json`` beside this file, or
$PTPU_AUTOTUNE_CACHE).
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np

from .autotune import _sync, _time_once, persistent_get, persistent_put

__all__ = ["chip_kind", "get_schedule", "put_schedule", "tune_kernel",
           "tune_rms_norm", "tune_rope", "tune_quantized_matmul",
           "tune_fused_adamw", "tune_fused_adamw2d",
           "tune_decode_attention", "tune_bench_shapes"]


def chip_kind() -> str:
    import jax
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        return str(dev.device_kind).replace(" ", "_")
    return "interpret"


def _key(kernel: str, sig: str) -> str:
    return f"sched/{kernel}/{sig}/{chip_kind()}"


def get_schedule(kernel: str, sig: str):
    """Winner config for (kernel, shape-sig) on THIS chip, or None."""
    return persistent_get(_key(kernel, sig))


def put_schedule(kernel: str, sig: str, config):
    persistent_put(_key(kernel, sig), config)


def tune_kernel(kernel: str, sig: str, make_fn: Callable,
                candidates: Sequence, args: Tuple,
                iters: int = 3, default=None, min_gain: float = 0.05):
    """Time ``make_fn(*candidate)(*args)`` for every candidate; persist
    the winner ONLY when it beats the kernel's default config by more
    than ``min_gain`` (a winner within the noise of the default is not
    a real win, and persisting it can hurt in-model where the standalone
    timing context differs).
    Returns ``(best_config, table)``; table entries are
    ``(config, seconds | None)`` (None = failed to compile/run)."""
    import time as _time

    from ...observability import metrics as _obs
    from ...observability.spans import span as _span
    reg = _obs.get_registry()
    trial_count = reg.counter(
        "tuner.trials", "schedule-search candidate trials",
        labels=("kernel", "outcome"))
    trial_seconds = reg.histogram(
        "tuner.trial_seconds",
        "wall time per candidate trial (compile + timed iters)",
        labels=("kernel",))
    table: List = []
    errors: List = []
    best, best_t = None, float("inf")
    default_t = None
    for cand in candidates:
        cand_t = cand if isinstance(cand, tuple) else (cand,)
        w0 = _time.perf_counter()
        try:
            with _span("tuner.trial", kernel=kernel, sig=sig,
                       candidate=cand):
                t = _time_candidate(make_fn(*cand_t), args, iters=iters)
        except Exception as e:
            trial_count.inc(kernel=kernel, outcome="error")
            trial_seconds.observe(_time.perf_counter() - w0, kernel=kernel)
            table.append((cand, None))
            errors.append((cand, str(e)[:200]))
            continue
        trial_count.inc(kernel=kernel, outcome="ok")
        trial_seconds.observe(_time.perf_counter() - w0, kernel=kernel)
        table.append((cand, t))
        if cand == default:
            default_t = t
        if t < best_t:
            best, best_t = cand, t
    keep = best is not None and (
        default is None or default_t is None or
        best_t < default_t * (1.0 - min_gain))
    if keep:
        put_schedule(kernel, sig, best)
    elif best is not None:
        # below the noise floor vs the default: make sure no stale winner
        # overrides the heuristic
        put_schedule(kernel, sig, None)
        best = default if default_t is not None else best
    if best is None and errors:
        print(f"tune_kernel({kernel}/{sig}): all candidates failed; "
              f"first error: {errors[0]}")
    return best, table


def _time_candidate(fn, args, iters: int = 3):
    """Per-candidate timing: jit once, then measure DEVICE time from the
    xplane profiler trace (sum of leaf device ops / iters).  Host wall
    clock includes per-dispatch overhead of the same order as a sub-ms
    kernel; device totals do not.  Falls back to wall clock where no
    profiler trace is available (CPU interpret mode)."""
    import jax

    jfn = jax.jit(fn)
    iters = max(iters, 5)
    # compile + warm, and keep the wall measurement as the fallback
    wall = _time_once(jfn, args, {}, warmup=2, iters=iters)
    try:
        dev = jax.devices()[0]
        if dev.platform != "tpu":
            return wall
        import re
        import shutil
        import tempfile

        from ...profiler.profiler import DeviceSummaryView
        tdir = tempfile.mkdtemp(prefix="ptpu_sched_")
        try:
            jax.profiler.start_trace(tdir)
            try:
                out = None
                for _ in range(iters):
                    out = jfn(*args)
                _sync(out)
            finally:
                # a leaked global trace would poison every later candidate
                # (start_trace fails -> wall-clock mixes with device time)
                jax.profiler.stop_trace()
            total = 0.0
            for row in DeviceSummaryView(tdir).rows():
                name = row["name"]
                if name.startswith("jit_") or re.fullmatch(r"\d+", name):
                    continue  # container lanes double-count children
                total += row["total_ms"]
            if total > 0:
                return total / 1e3 / iters
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
    except Exception:
        pass
    return wall


# ---------------------------------------------------------------------------
# per-kernel spaces
# ---------------------------------------------------------------------------

def _divisors_of(n: int, step: int, lo: int, hi: int) -> List[int]:
    return [r for r in range(lo, min(hi, n) + 1, step) if n % r == 0]


def tune_rms_norm(n: int, d: int, dtype="bfloat16", iters: int = 3):
    """Search the row-block size of the fused RMSNorm kernel for a
    [n, d] input."""
    import jax.numpy as jnp

    from .rms_norm import _pick_rows, _rms_fwd_impl, rms_sig
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((n, d)), dtype)
    w = jnp.asarray(rng.standard_normal((d,)), dtype)
    cands = _divisors_of(n, 8, 8, 2048) or [n]
    default = _pick_rows(n) or n
    if default not in cands:
        cands.append(default)
    return tune_kernel(
        "rms_norm", rms_sig(n, d, x.dtype),
        lambda rows: functools.partial(_rms_fwd_impl, epsilon=1e-6,
                                       rows=rows),
        cands, (x, w), iters=iters, default=default)


def tune_rope(b: int, s: int, h: int, d: int, dtype="bfloat16",
              iters: int = 3):
    """Search the sequence-block size of the fused RoPE kernel."""
    import jax.numpy as jnp

    from .rope import _pick_block_s, _rope_call, rope_sig
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((b, s, h, d)), dtype)
    cos = jnp.asarray(rng.standard_normal((1, s, 1, d // 2)), jnp.float32)
    sin = jnp.asarray(rng.standard_normal((1, s, 1, d // 2)), jnp.float32)
    cands = [bs for bs in _divisors_of(s, 1, 1, s)
             if bs == s or bs % 8 == 0]
    default = _pick_block_s(s, h, d) or s
    if default not in cands:
        cands.append(default)
    return tune_kernel(
        "rope", rope_sig(b, s, h, d, x.dtype),
        lambda bs: functools.partial(_rope_call, block_s=bs),
        cands, (x, cos, sin), iters=iters, default=default)


def tune_quantized_matmul(m: int, k: int, n: int, dtype="bfloat16",
                          iters: int = 3):
    """Search (block_m, block_n) of the int8 weight matmul."""
    import jax.numpy as jnp

    from .quantized_matmul import _qmm_impl, qmm_sig
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((m, k)), dtype)
    qw = jnp.asarray(rng.integers(-127, 127, (k, n)), jnp.int8)
    scales = jnp.asarray(rng.uniform(0.01, 0.02, (1, n)), jnp.float32)
    from .quantized_matmul import BLOCK_M, BLOCK_N
    bm_c = [bm for bm in (8, 64, 128, 256, 512) if bm <= m]
    bn_c = [bn for bn in (128, 256, 512) if n % bn == 0]
    cands = [(bm, bn) for bm in bm_c for bn in bn_c]
    default = (min(BLOCK_M, max(8, m)),
               BLOCK_N if n % BLOCK_N == 0 else 128)
    if default not in cands:
        cands.append(default)
    return tune_kernel(
        "quantized_matmul", qmm_sig(m, k, n, x.dtype),
        lambda bm, bn: functools.partial(_qmm_impl, out_dtype=x.dtype,
                                         block_m=bm, block_n=bn),
        cands, (x, qw, scales), iters=iters, default=default)


def tune_fused_adamw(numel: int, dtype="bfloat16", iters: int = 3):
    """Search the flat chunk size of the fused AdamW update."""
    import jax.numpy as jnp

    from .fused_optimizer import _adamw_call, adamw_sig
    rng = np.random.default_rng(0)
    p = jnp.asarray(rng.standard_normal(numel), dtype)
    g = jnp.asarray(rng.standard_normal(numel), dtype)
    m = jnp.zeros((numel,), jnp.float32)
    v = jnp.zeros((numel,), jnp.float32)
    lr = jnp.asarray([[1e-3]], jnp.float32)
    t = jnp.asarray([[1.0]], jnp.float32)
    cands = [c for c in (1 << 15, 1 << 17, 1 << 19, 1 << 21, 0)
             if c == 0 or c < numel]  # 0 = whole-array (no grid)
    default = 0 if numel <= (1 << 19) else (1 << 19)
    if default not in cands:
        cands.append(default)
    return tune_kernel(
        "fused_adamw", adamw_sig(numel, p.dtype),
        lambda chunk: functools.partial(_adamw_call, chunk=chunk),
        cands, (p, g, m, v, lr, t), iters=iters, default=default)


def tune_fused_adamw2d(shape=(7296, 8192), p_dtype="bfloat16",
                       m_dtype="bfloat16", iters: int = 3):
    """Search the (bm, bn) grid blocks of the native-shape fused AdamW
    update at a large-param shape."""
    import jax.numpy as jnp

    from .fused_optimizer import (_adamw_call_2d, _pick_blocks,
                                  adamw2d_sig)
    rng = np.random.default_rng(0)
    p = jnp.asarray(rng.standard_normal(shape), p_dtype)
    g = jnp.asarray(rng.standard_normal(shape), p_dtype)
    m = jnp.zeros(shape, m_dtype)
    v = jnp.zeros(shape, m_dtype)
    lr = jnp.asarray([[1e-3]], jnp.float32)
    t = jnp.asarray([[1.0]], jnp.float32)
    seed = jnp.asarray([[7]], jnp.int32)
    m_dim, n = shape
    bm_c = [bm for bm in (64, 128, 256, 512) if m_dim % bm == 0]
    bn_c = [bn for bn in (128, 256, 512) if n % bn == 0]
    cands = [(bm, bn) for bm in bm_c for bn in bn_c]
    default = _pick_blocks(m_dim, n, jnp.dtype(p_dtype),
                           jnp.dtype(m_dtype))
    if default not in cands:
        cands.append(default)
    kw = dict(beta1=0.9, beta2=0.999, epsilon=1e-8, wd=0.01, sr=True)
    return tune_kernel(
        "fused_adamw2d", adamw2d_sig(shape, p.dtype, m.dtype),
        lambda bm, bn: functools.partial(_adamw_call_2d,
                                         blocks=(bm, bn), **kw),
        cands, (p, g, m, v, lr, t, seed), iters=iters, default=default)


def tune_decode_attention(b=32, hkv=8, g=4, s=2048, d=64,
                          dtype="bfloat16", iters: int = 3):
    """Search the DMA chunk size (cache slots) of the flash-decode
    attention kernel.  The candidate must win at SERVING-representative
    fill levels, not only the full-prefix worst case: a big chunk looks
    best when every slot is valid but over-streams short prefixes (a
    1024-slot chunk reads 4x the bytes of a 130-slot prefix), so the
    per-candidate metric sums a short-, mid-, full-prefix AND a ragged
    mixed-fill run (the continuous-batching slot-pool shape)."""
    import jax.numpy as jnp

    from .decode_attention import (_decode_attention_pallas,
                                   decode_attn_sig, DEFAULT_CHUNK)
    rng = np.random.default_rng(0)
    w = hkv * d
    q4 = jnp.asarray(rng.standard_normal((b, hkv, g, d)), dtype)
    kc = jnp.asarray(rng.standard_normal((b, s, w)), dtype)
    vc = jnp.asarray(rng.standard_normal((b, s, w)), dtype)
    fills = [jnp.full((b,), max(8, s // 8), jnp.int32),
             jnp.full((b,), s // 2, jnp.int32),
             jnp.full((b,), s - 8, jnp.int32),
             # continuous-batching serving (inference/serving.py) holds
             # a MIX of fill levels in one batch — per-row n_chunks
             # raggedness, where a too-big chunk over-streams the short
             # rows even when the batch also has full rows
             jnp.asarray([max(8, ((i % 4) + 1) * (s // 4) - 8)
                          for i in range(b)], jnp.int32)]
    cands = [c for c in (128, 256, 512, 1024) if s % c == 0]
    default = DEFAULT_CHUNK if s % DEFAULT_CHUNK == 0 else cands[0]

    def make(chunk):
        def run(q4a, kca, vca):
            outs = [_decode_attention_pallas(q4a, kca, vca, lens,
                                             chunk=chunk)
                    for lens in fills]
            return sum(o.astype(jnp.float32).sum() for o in outs)
        return run

    return tune_kernel(
        "decode_attention", decode_attn_sig(b, hkv, g, s, d, q4.dtype),
        make, cands, (q4, kc, vc), iters=iters, default=default)


def tune_bench_shapes(iters: int = 3) -> Dict[str, Tuple]:
    """Search every kernel at the 1.1B Llama shapes (no benchmark cell
    runs them; ROADMAP D12).  Returns {kernel/sig: (best, table)} for
    reporting."""
    out = {}
    # Llama 1.1B: hidden 2048, b8 s2048 -> rms rows over 16384 rows
    out["rms_norm/16384x2048"] = tune_rms_norm(16384, 2048, iters=iters)
    out["rope/8x2048x32x64"] = tune_rope(8, 2048, 32, 64, iters=iters)
    out["quantized_matmul/2048x2048x8192"] = tune_quantized_matmul(
        2048, 2048, 8192, iters=iters)
    out["fused_adamw/4194304"] = tune_fused_adamw(1 << 22, iters=iters)
    out["fused_adamw2d/7296x8192"] = tune_fused_adamw2d(iters=iters)
    out["decode_attention/32x8x4x2048x64"] = tune_decode_attention(
        iters=iters)
    return out
