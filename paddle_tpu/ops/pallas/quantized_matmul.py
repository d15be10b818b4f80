"""Int8/int4-weight matmul Pallas kernel.

TPU analogue of the reference's int8 cutlass epilogues
(``paddle/phi/kernels/fusion/cutlass``): ``y = x @ (W_q * scale)``
with the weight dequantized int8->bf16 in VMEM and the per-output-channel
scale applied as an epilogue on the fp32 accumulator.  The int4 variant
streams two codes per int8 byte and unpacks the nibbles in-kernel, so
HBM weight traffic halves again over int8.

Measured on the real chip (2026-07-30): parity with XLA's fused
dequant+matmul at both prefill (M=256, K=N=4096) and decode (M=16,
K=N=8192) shapes — XLA also streams int8 from HBM and fuses the upcast.
The kernel therefore ships as an **opt-in** (FLAGS_use_int8_matmul_kernel
for the QuantizedLinearInfer layer path; ``weight_dtype=`` on the serving
engine opts in explicitly) building block / autotune target rather than
the default path.  Interpret mode keeps CPU CI on the same code path.

Routing mirrors ``decode_attention``: every gate decision lands on the
``pallas.quantized_matmul.route`` counter with a closed reason
vocabulary, and the XLA fallback (``dequant_matmul_xla``) reproduces the
kernel's math — codes upcast to the activation dtype, fp32 accumulator,
scale epilogue — so routing never changes semantics, only bandwidth.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._common import on_tpu, pallas_enabled, refused_for_partitioning

BLOCK_M = 256
BLOCK_N = 256

# Closed vocabulary for the `reason` label of
# `pallas.quantized_matmul.route`.  Every string `_qmm_route_reason`
# can return must appear here (graftlint vocab pass).
QMM_ROUTE_REASONS = (
    "int8_ok",
    "int4_ok",
    "flag_disabled",
    "pallas_unavailable",
    "gspmd_partitioned",
    "bad_rank",
    "k_mismatch",
    "geometry",
    "rows_below_min",
    "rows_above_cap",
)

_route_counter_inst = None


def _route_counter():
    global _route_counter_inst
    if _route_counter_inst is None:
        from ...observability import metrics as _obs
        _route_counter_inst = _obs.get_registry().counter(
            "pallas.quantized_matmul.route",
            "quantized-matmul routing decisions by outcome",
            labels=("decision", "reason"),
        )
    return _route_counter_inst


def _rows(x):
    m = 1
    for s in x.shape[:-1]:
        m *= s
    return m


def _qmm_route_reason(x, qweight, bits=8, max_m=None, require_flag=True):
    """Why the quantized-matmul gate routed the way it did.

    Returns one of QMM_ROUTE_REASONS; the "*_ok" entries mean the Pallas
    kernel is taken, everything else names the disqualifier (first match
    wins, checked cheapest-first)."""
    from ...core.flags import flag
    if require_flag and not flag("use_int8_matmul_kernel"):
        return "flag_disabled"
    if not pallas_enabled():
        if refused_for_partitioning():
            return "gspmd_partitioned"
        return "pallas_unavailable"
    if x.ndim < 2 or qweight.ndim != 2:
        return "bad_rank"
    k = qweight.shape[0] * 2 if bits == 4 else qweight.shape[0]
    n = qweight.shape[1]
    if x.shape[-1] != k:
        return "k_mismatch"
    if k % 128 or n % 128:
        return "geometry"
    m = _rows(x)
    if m < 8:
        return "rows_below_min"
    if max_m is not None and m > max_m:
        return "rows_above_cap"
    return "int4_ok" if bits == 4 else "int8_ok"


def _route_decision(x, qweight, bits=8, max_m=None, require_flag=True):
    reason = _qmm_route_reason(x, qweight, bits=bits, max_m=max_m,
                               require_flag=require_flag)
    return reason in ("int8_ok", "int4_ok"), reason


def should_use_pallas(x, qweight, max_m=None, bits=8,
                      require_flag=True) -> bool:
    """max_m: callers serving matmuls (QuantizedLinearInfer) cap M at
    decode-sized rows — the kernel streams the whole [K, bn] weight
    block per M-block, so at prefill-sized M the weight re-read
    multiplies (measured 13x slower than XLA's fused int8 upcast at
    M=4096, K=8192 on v5e); at decode M (one weight sweep) it is at the
    weight-streaming roofline.

    Counts the decision on pallas.quantized_matmul.route (trace/gate
    time, like decode_attention's gate)."""
    use, reason = _route_decision(x, qweight, bits=bits, max_m=max_m,
                                  require_flag=require_flag)
    _route_counter().inc(decision="pallas" if use else "xla",
                         reason=reason)
    return use


def pack_int4(codes):
    """[K, N] int8 codes in [-8, 7] -> [K//2, N] packed int8.

    Split-K-halves layout: packed row i carries codes[i] in the low
    nibble and codes[K//2 + i] in the high nibble.  The in-kernel unpack
    is then two cheap vector ops + a sublane concat — no lane
    interleave, which Mosaic cannot tile.  K must be even (the serving
    loader guarantees it; hot projections have K % 128 == 0)."""
    codes = jnp.asarray(codes)
    k = codes.shape[0]
    if k % 2:
        raise ValueError(
            f"pack_int4: K ({k}) must be even to pack two codes per byte")
    half = k // 2
    lo = codes[:half].astype(jnp.int32) & 0xF
    hi = (codes[half:].astype(jnp.int32) & 0xF) << 4
    return (lo | hi).astype(jnp.int8)


def _unpack_nibbles(packed_i32):
    # sign-extend each nibble: (v ^ 8) - 8 maps 0..15 -> -8..7
    lo = ((packed_i32 & 0xF) ^ 8) - 8
    hi = (((packed_i32 >> 4) & 0xF) ^ 8) - 8
    return lo, hi


def unpack_int4(packed):
    """Inverse of pack_int4: [K//2, N] packed int8 -> [K, N] int8 codes."""
    p = jnp.asarray(packed).astype(jnp.int32)
    lo, hi = _unpack_nibbles(p)
    return jnp.concatenate([lo, hi], axis=0).astype(jnp.int8)


def _apply_act(acc, act):
    if act is None or act == "none":
        return acc
    if act == "relu":
        return jnp.maximum(acc, 0.0)
    if act == "gelu":
        # tanh approximation (Mosaic has no erf lowering); deviates from
        # exact-erf GELU by <= ~3e-3 absolute — well under the int8
        # quantization error this kernel already carries
        inner = 0.7978845608028654 * (acc + 0.044715 * acc * acc * acc)
        return acc * 0.5 * (1.0 + jnp.tanh(inner))
    if act == "silu":
        return acc * (1.0 / (1.0 + jnp.exp(-acc)))
    raise ValueError(f"quantized_matmul: unsupported epilogue act {act!r}")


def _kernel(x_ref, qw_ref, scale_ref, y_ref, *, act=None):
    x = x_ref[:]
    # int8 -> the activation dtype in VMEM: bf16 activations keep the MXU
    # at full bf16 rate, fp32 activations keep full precision; the
    # accumulator is fp32 either way
    w = qw_ref[:].astype(x.dtype)
    acc = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    # scales arrive as a [1, bn] row (2-D keeps Mosaic's 128-lane tiling)
    y_ref[:] = _apply_act(acc * scale_ref[:], act).astype(y_ref.dtype)


def _kernel_bias(x_ref, qw_ref, scale_ref, bias_ref, y_ref, *, act=None):
    x = x_ref[:]
    w = qw_ref[:].astype(x.dtype)
    acc = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    acc = acc * scale_ref[:] + bias_ref[:]
    y_ref[:] = _apply_act(acc, act).astype(y_ref.dtype)


def _kernel_i4(x_ref, qw_ref, scale_ref, y_ref, *, act=None):
    x = x_ref[:]
    # qw_ref block is [K//2, bn] packed; unpack in VMEM.  Split-K-halves
    # packing means the two nibble planes concat along sublanes (axis 0),
    # which Mosaic tiles natively (K % 128 == 0 -> K//2 % 64 == 0)
    lo, hi = _unpack_nibbles(qw_ref[:].astype(jnp.int32))
    w = jnp.concatenate([lo, hi], axis=0).astype(x.dtype)
    acc = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    y_ref[:] = _apply_act(acc * scale_ref[:], act).astype(y_ref.dtype)


def _kernel_i4_bias(x_ref, qw_ref, scale_ref, bias_ref, y_ref, *, act=None):
    x = x_ref[:]
    lo, hi = _unpack_nibbles(qw_ref[:].astype(jnp.int32))
    w = jnp.concatenate([lo, hi], axis=0).astype(x.dtype)
    acc = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    acc = acc * scale_ref[:] + bias_ref[:]
    y_ref[:] = _apply_act(acc, act).astype(y_ref.dtype)


def qmm_sig(m, k, n, dtype, bits=8):
    import numpy as np
    tag = "/int4" if bits == 4 else ""
    return f"{m}x{k}x{n}/{np.dtype(dtype)}{tag}"


def _qmm_impl(x2, qweight, scales2, out_dtype, block_m=None, block_n=None,
              bias2=None, act=None, bits=8):
    m, k = x2.shape
    n = qweight.shape[1]
    wrows = qweight.shape[0]   # k for int8, k//2 for packed int4
    if block_m is None and block_n is None:
        from .schedule_search import get_schedule
        hit = get_schedule("quantized_matmul",
                           qmm_sig(m, k, n, x2.dtype, bits=bits))
        if hit:
            block_m, block_n = int(hit[0]), int(hit[1])
    # N blocks must tile N exactly (gate guarantees n % 128 == 0)
    bn = block_n if block_n and n % block_n == 0 else \
        (BLOCK_N if n % BLOCK_N == 0 else 128)
    # M is padded up to a whole number of blocks (bounded VMEM per block)
    if block_m:
        bm = block_m
    else:
        # power-of-two bm (sublane-aligned for every dtype) nearest m
        bm = 8
        while bm * 2 <= min(BLOCK_M, m):
            bm *= 2
        # VMEM fit for the untuned default: the kernel holds x[bm,K]
        # (act dtype) + the streamed weight block (int8: [K,bn] bytes,
        # int4: [K//2,bn] bytes + the unpacked [K,bn] temp in int32 and
        # the act dtype) + fp32 acc/out [bm,bn], and Pallas
        # double-buffers the streamed inputs — large K (e.g. the 8192
        # MLP width) overflows the 16 MB scoped limit at bm=256
        # (measured on v5e; the OOM named this site)
        act_bytes = jnp.dtype(x2.dtype).itemsize

        def vmem(bmx, bnx):
            base = 2 * (bmx * k * act_bytes + wrows * bnx) + 8 * bmx * bnx
            if bits == 4:
                base += k * bnx * (4 + act_bytes)
            return base
        budget = 12 << 20
        while bm > 8 and vmem(bm, bn) > budget:
            bm //= 2
        while bn > 128 and vmem(bm, bn) > budget:
            bn //= 2
    pad_m = (-m) % bm
    if pad_m:
        x2 = jnp.pad(x2, ((0, pad_m), (0, 0)))
    mp = m + pad_m
    in_specs = [
        pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
        pl.BlockSpec((wrows, bn), lambda i, j: (0, j)),
        pl.BlockSpec((1, bn), lambda i, j: (0, j)),
    ]
    args = [x2, qweight, scales2]
    kern = _kernel_i4 if bits == 4 else _kernel
    kern_bias = _kernel_i4_bias if bits == 4 else _kernel_bias
    if bias2 is not None:
        kernel = functools.partial(kern_bias, act=act)
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j: (0, j)))
        args.append(bias2)
    else:
        kernel = functools.partial(kern, act=act)
    y = pl.pallas_call(
        kernel,
        grid=(mp // bm, n // bn),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, n), out_dtype),
        interpret=not on_tpu(),
    )(*args)
    return y[:m]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _qmm(x2, qweight, scales2, out_dtype):
    return _qmm_impl(x2, qweight, scales2, out_dtype)


def _qmm_fwd(x2, qweight, scales2, out_dtype):
    # zero-size array carries the primal dtype through the residual pytree
    # (a raw np.dtype is not a valid JAX pytree leaf)
    return _qmm_impl(x2, qweight, scales2, out_dtype), \
        (qweight, scales2, jnp.zeros((0,), x2.dtype))


def _qmm_bwd(out_dtype, res, g):
    # dx = g @ (W_int8 * scale)^T — plain XLA; weights/scales nondiff.
    # Cast back to the primal dtype: custom_vjp cotangents must match the
    # primal aval (bf16 activations would otherwise get fp32 cotangents).
    qweight, scales2, x_proto = res
    w = qweight.astype(jnp.float32) * scales2
    dx = g.astype(jnp.float32) @ w.T
    return dx.astype(x_proto.dtype), None, None


_qmm.defvjp(_qmm_fwd, _qmm_bwd)


def _true_k(qweight, bits):
    return qweight.shape[0] * 2 if bits == 4 else qweight.shape[0]


def quantized_matmul(x, qweight, scales, out_dtype=None, bias=None,
                     act=None, bits=8):
    """x: [..., K] float; qweight: [K, N] int8 (or [K//2, N] packed int4
    when bits=4); scales: [N] fp32.  Returns [..., N] in out_dtype
    (defaults to x dtype).

    ``bias``/``act`` fuse the dequant epilogue INTO the kernel (bias add
    + gelu/relu/silu on the fp32 accumulator before the store) — the
    serving win: a custom call is an XLA fusion barrier, so an unfused
    epilogue materializes the activation between kernels (reference
    analogue: the TRT int8 engine's fused epilogues,
    ``fused_multi_transformer_int8_op.cu``).  The plain int8 form is
    differentiable w.r.t. x (custom vjp; weights frozen int8); the
    fused-epilogue and int4 forms are inference-only.
    """
    shape = x.shape
    k = _true_k(qweight, bits)
    n = qweight.shape[1]
    if n % 128:
        raise ValueError(
            f"quantized_matmul: N ({n}) must be a multiple of 128")
    if shape[-1] != k:
        raise ValueError(
            f"quantized_matmul: x last dim ({shape[-1]}) != weight K ({k})")
    x2 = x.reshape(-1, k)
    out_dtype = out_dtype or x.dtype
    scales2 = jnp.asarray(scales, jnp.float32).reshape(1, n)
    if bits == 4:
        bias2 = None if bias is None else \
            jnp.asarray(bias, jnp.float32).reshape(1, n)
        y = _qmm_impl(x2, qweight, scales2, jnp.dtype(out_dtype),
                      bias2=bias2, act=act, bits=4)
    elif bias is None and act is None:
        y = _qmm(x2, qweight, scales2, jnp.dtype(out_dtype))
    else:
        bias2 = None if bias is None else \
            jnp.asarray(bias, jnp.float32).reshape(1, n)
        y = _qmm_impl(x2, qweight, scales2, jnp.dtype(out_dtype),
                      bias2=bias2, act=act)
    return y.reshape(shape[:-1] + (n,))


def dequant_view(qweight, scales, bits=8, dtype=jnp.float32):
    """Materialize the dequantized weight [K, N] in ``dtype`` — the
    XLA-side view of codes x scales (unpacks int4 first)."""
    codes = unpack_int4(qweight) if bits == 4 else qweight
    w = codes.astype(jnp.float32) * jnp.asarray(scales, jnp.float32)[None, :]
    return w.astype(dtype)


def dequant_matmul_xla(x, qweight, scales, bits=8, out_dtype=None,
                       bias=None):
    """XLA fallback with the kernel's exact math: codes upcast to the
    activation dtype, fp32 accumulator, per-channel scale (+ bias) as an
    fp32 epilogue.  XLA fuses the upcast into the matmul, so this still
    streams int8/int4 from HBM — routing here costs precision nothing
    and bandwidth only the fusion quality."""
    shape = x.shape
    k = _true_k(qweight, bits)
    n = qweight.shape[1]
    if shape[-1] != k:
        raise ValueError(
            f"dequant_matmul_xla: x last dim ({shape[-1]}) != weight K ({k})")
    codes = unpack_int4(qweight) if bits == 4 else qweight
    x2 = x.reshape(-1, k)
    acc = jax.lax.dot_general(x2, codes.astype(x2.dtype),
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    acc = acc * jnp.asarray(scales, jnp.float32)[None, :]
    if bias is not None:
        acc = acc + jnp.asarray(bias, jnp.float32)[None, :]
    out_dtype = out_dtype or x.dtype
    return acc.astype(out_dtype).reshape(shape[:-1] + (n,))


def routed_quantized_matmul(x, qweight, scales, bits=8, out_dtype=None,
                            bias=None, max_m=None, require_flag=False):
    """Gate + dispatch: the serving-engine entry point.  ``weight_dtype=``
    on the engine is the explicit opt-in, so the kernel flag is not
    consulted by default (require_flag=False); the decision still lands
    on pallas.quantized_matmul.route either way."""
    use, reason = _route_decision(x, qweight, bits=bits, max_m=max_m,
                                  require_flag=require_flag)
    _route_counter().inc(decision="pallas" if use else "xla",
                         reason=reason)
    if use:
        return quantized_matmul(x, qweight, scales, out_dtype=out_dtype,
                                bias=bias, bits=bits)
    return dequant_matmul_xla(x, qweight, scales, bits=bits,
                              out_dtype=out_dtype, bias=bias)
