"""Fused cached-decode attention (flash-decode) Pallas kernel.

TPU analogue of the reference's fused decode attention —
``paddle/fluid/operators/fused/fused_multi_transformer_op.cu`` layered
over ``masked_multihead_attention`` (one-token attention over a growing
KV cache, reading ``sequence_lengths``).

Round-5 motivation (VERDICT r4 weak #3): the XLA einsum decode
attention measured ~373 GB/s in-model (58% of the b32 decode step) and
swept the FULL static cache every step even when only a short valid
prefix holds data.  The design was shaped by four measured dead ends:

1. ``[B, S, H, D]`` / ``[B, H, S, D]`` caches are lane-PADDED at rest
   (D=64 < the 128-lane tile) — 2x HBM and half-rate streaming.
2. A (B, H, S-chunk) grid costs ~1 us per grid step — 2048 tiny
   programs burn ~2 ms regardless of compute, and clamped index maps
   do not skip the tail DMA.
3. Lane-slicing a 0.5 MB VMEM value at a non-tile offset (per-fold
   ``buf[:, 64:128]``) relayouts the whole value per slice.
4. Advanced-indexing scatters into a per-head-packed layout lower to
   ~1.5 ms/layer XLA scatters.

The layout that satisfies every constraint at once: the cache at rest
is ``[B, S, W]`` with ``W = H_kv * D`` — all heads of one slot
CONTIGUOUS in lanes (head h at lane offset h*D).  Then:

- the decode scatter is a plain row scatter ``cache.at[b, lens]``
  (exactly the form XLA lowers to an O(B*W) write);
- a prefix chunk is ONE contiguous, tile-aligned DMA;
- the kernel processes 128-lane GROUPS (128/D heads per group) with a
  block-diagonal ``q_cat`` — one [hp*8, 128] x [rows, 128] dot yields
  every grouped head's logits with full-lane contraction, and all big
  slices sit on 128-lane tile boundaries;
- traffic is O(valid prefix): the chunk loop stops at ``lens[b]``
  (the reference mmha ``sequence_lengths`` contract), with one program
  per batch row (grid overhead O(B), not O(B*H*chunks)).

Two bodies live here.  The STAGED one lands a row's whole valid prefix
in VMEM and then computes on all of it: the dense ``_kernel``
(``LLMPredictor``'s contiguous cache); its landing buffers grow with
the cache (``_VMEM_BUDGET``, reason ``vmem_budget``).  The STREAMING
one (``_paged_stream_kernel``: the float paged cache, decode and the
K-wide verify alike) walks the prefix in double-buffered groups of
blocks with an online softmax and prefetches across slots; what it
stages does not grow with the table, and the budget does not bind it
(PR 29: 80% of the HBM roofline at the serving cell's geometry where
a staged body read 44%, kernel alone on the v5e).  The int8 paged
cache has no kernel: it reads through ``paged_dequant_view`` on every
platform (reason ``int8_scale_lanes``).
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import (on_tpu, pallas_enabled, partitioned_scope,
                      refused_for_partitioning)

# The closed label vocabulary of the ``pallas.decode_attention.route``
# counter's ``reason`` axis (graftlint DECODE_ROUTE_REASONS; the
# runtime guard is ``_count_route``).  The ``*_ok`` entries mean the
# Pallas kernel dispatched; everything else names the disqualifier
# that sent the call to the XLA fallback.  ``sharded_ok``/``mesh_geom``
# are the mesh-sharded serving overlay (``shard_dispatch_scope``):
# recorded IN ADDITION to the kernel decision, they prove a paged
# program traced with its kv-head shard geometry accepted
# (``sharded_ok``) or fell back to replicated arenas (``mesh_geom``).
DECODE_ROUTE_REASONS = (
    "ok", "paged_ok", "paged_multi_ok", "latent_ok", "sharded_ok",
    "mesh_geom",
    "flag_disabled", "pallas_unavailable", "gspmd_partitioned",
    "unpacked_cache", "dtype_mismatch", "scales_mismatch", "geometry",
    "int8_scale_lanes", "group_too_wide", "seq_align",
    "paged_block_len", "query_rows", "vmem_budget",
)


class ShardedTableError(TypeError):
    """A paged dispatch received a block table committed with a
    non-replicated device sharding.  Block tables are HOST scheduling
    state: the byte-deterministic plan drives every kv-head shard with
    ONE replicated table, and the Pallas kernels scalar-prefetch it
    whole — a partitioned table would silently index a different
    arena row per shard.  Shard the ARENAS (``ServingEngine(mesh=)``),
    never the tables."""


# mesh-sharded serving overlay (module-scoped, set at TRACE time by the
# serving builders): the kv-head shard count the paged arenas are
# partitioned over, or None outside a sharded serving program.  Not
# thread-local — tracing is synchronous under the builder call.
_SHARD_N = None


@contextlib.contextmanager
def shard_dispatch_scope(n_shards: int):
    """Mark the enclosed trace as a mesh-sharded serving program: every
    paged route decision additionally records the shard-overlay reason
    (``sharded_ok``/``mesh_geom``) for its kv-head geometry — the
    deterministic route-counter proof that the sharded path actually
    dispatched (one count per compiled paged program, the same
    trace-time discipline as the kernel decision itself)."""
    global _SHARD_N
    prev = _SHARD_N
    _SHARD_N = int(n_shards)
    try:
        # a sharded serving program is GSPMD-partitioned: no kernel of
        # any kind may be traced into it (``_common.pallas_enabled``)
        with partitioned_scope(_SHARD_N > 1):
            yield
    finally:
        _SHARD_N = prev


def _shard_route_reason(hkv: int, n_shards: int) -> str:
    """Producer of the shard-overlay route reasons: ``sharded_ok`` when
    the kv heads divide evenly over the shard axis (each shard owns
    whole heads — the partitioned math is per-head-identical to the
    replicated program), ``mesh_geom`` when they do not (the engine
    keeps the arenas replicated over the mesh instead)."""
    if n_shards > 1 and hkv % n_shards == 0:
        return "sharded_ok"
    return "mesh_geom"


def count_shard_route(hkv: int, n_shards: int, use_pallas: bool):
    """Record one shard-overlay route decision (see
    ``shard_dispatch_scope``; also called once at engine init when the
    mesh geometry forces the replicated fallback)."""
    _count_route("pallas" if use_pallas else "xla",
                 _shard_route_reason(hkv, n_shards))


_LANES = 128
DEFAULT_CHUNK = 256            # cache slots per DMA chunk
_NEG_INF = -1e30
_GPAD = 8                      # q rows per head block (sublane unit)
# What the gate admits is what the compiler is told: ``_VMEM_BUDGET``
# bounds the buffers a kernel STAGES (K/V landing buffers or stages,
# the logits scratch), and every decode ``pallas_call`` sets Mosaic's
# scoped-VMEM limit to ``_VMEM_LIMIT`` so that the softmax's
# temporaries — a handful of logits-sized values the estimate does not
# itemise — have room on top of a full budget whatever the compiler's
# default is.  The budget BINDS the one kernel that stages a whole
# context, the dense ``_kernel`` (on the v5e the bf16 ``_kernel``
# compiles and agrees at the budget's edge, 5376 staged rows of 512
# lanes, with this limit; 5120 rows also fit the default limit: chip
# run, PR 22).  The streaming paged kernel (``_paged_stream_kernel``)
# stages two stages of ``_STAGE_BYTES`` an operand whatever the
# table's width, so it passes the same estimate at any context.
_VMEM_BUDGET = 12 << 20
_VMEM_LIMIT = 32 << 20
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT)
# the streaming kernel carries its stage parity and its prefetch from
# one program to the next: the grid must run in order
_STREAM_COMPILER_PARAMS = pltpu.CompilerParams(
    vmem_limit_bytes=_VMEM_LIMIT, dimension_semantics=("arbitrary",))

# One stage of the streaming kernel: the bytes of K (and as many of V)
# that are computed on while the next stage's DMAs land.  A fixed byte
# size; the rows of a stage follow from what the code sees
# (``_stage_blocks``): 8 blocks of 16 x 4096 B at 16 bf16 KV heads of
# 128, 128 rows.
_STAGE_BYTES = 512 << 10


def _stage_blocks(arena, tables):
    """Blocks in one stage of ``_paged_stream_kernel``: as many whole
    blocks as ``_STAGE_BYTES`` holds at this arena's width, item size
    and block length, at least one, and no more than the table has."""
    blk_bytes = (arena.shape[1] * arena.shape[2]
                 * jnp.dtype(arena.dtype).itemsize)
    return max(1, min(_STAGE_BYTES // blk_bytes, tables.shape[1]))


def _paged_table_rule(arena):
    """(ok, reason) for what the paged kernel needs of a block: the
    staged unit is a whole block, so ``block_len`` must sit on the
    8-row sublane tile (``paged_block_len``; bf16 and f32 arenas
    compile at 8, 16 and 32 on the v5e)."""
    if arena.shape[1] % 8:
        return False, "paged_block_len"
    return True, None


def _stream_rows(hkv, cq, g):
    """Rows of the streaming kernel's q block and accumulator: every
    query row of a slot (``cq`` positions of ``g`` query heads on each
    of ``hkv`` KV heads), rounded up to the sublane unit."""
    return -(-(hkv * cq * g) // _GPAD) * _GPAD


def _paged_staging(hkv, cq, g, arena, tables):
    """(s, acc_rows) of ``_gate_shared``'s estimate for the streaming
    paged kernel: the rows of K (and of V) it holds in VMEM, both
    stages, and the rows of its full-width accumulator
    (``_stream_rows``)."""
    return (2 * _stage_blocks(arena, tables) * arena.shape[1],
            _stream_rows(hkv, cq, g))


def packed_ok(num_kv_heads: int, head_dim: int) -> bool:
    """Can this head geometry use the packed [B, S, H*D] cache?"""
    w = num_kv_heads * head_dim
    return w % _LANES == 0 and (_LANES % head_dim == 0
                                or head_dim % _LANES == 0)


def cache_shape(batch, num_kv_heads, max_cache_len, head_dim):
    """At-rest KV cache shape: packed [B, S, H*D] when the geometry
    allows, else the plain [B, S, H, D] fallback."""
    if packed_ok(num_kv_heads, head_dim):
        return (batch, max_cache_len, num_kv_heads * head_dim)
    return (batch, max_cache_len, num_kv_heads, head_dim)


def paged_arena_shape(num_blocks, num_kv_heads, block_len, head_dim):
    """At-rest PAGED KV arena shape: one pool of ``num_blocks`` blocks
    of ``block_len`` slots shared by every sequence (vLLM's
    PagedAttention layout), packed [NB, L, H*D] when the head geometry
    allows (each block row keeps the heads-in-lanes tiling of
    ``cache_shape``), else [NB, L, H, D]."""
    if packed_ok(num_kv_heads, head_dim):
        return (num_blocks, block_len, num_kv_heads * head_dim)
    return (num_blocks, block_len, num_kv_heads, head_dim)


def paged_scale_shape(num_blocks, num_kv_heads, block_len):
    """At-rest shape of an int8 arena's parallel absmax-scale plane:
    one f32 scale per block slot per kv head
    (``models.generation.quantize_kv_heads``).  4/D of the code arena's
    bytes — the price of exact, pure-scatter quantize-on-append."""
    return (num_blocks, block_len, num_kv_heads)


def paged_latent_shape(num_blocks, block_len, row):
    """At-rest shape of a LATENT paged arena: one row of ``row`` values a
    token (a compressed KV row and the shared positional key behind it),
    not keys and values of ``H_kv x D``.  The row is padded with zeros
    to whole 128-lane tiles (576 values rest as 640): a block is staged
    by DMA, and Mosaic slices an HBM plane only in whole lane tiles."""
    return (num_blocks, block_len, -(-row // _LANES) * _LANES)


def paged_gather_view(arena, tables):
    """Dense per-sequence view of a paged arena: gather each row's
    blocks through its table and fold the block axis into a
    [B, max_blocks * L, ...] cache the existing attention math reads.
    Table entries past a sequence's allocation point at the trash block
    (last arena row); its contents are finite garbage hidden by the
    same ``lens`` masking that hides unwritten slots of a dense
    cache."""
    g = arena[tables]                  # [B, max_blocks, L, ...]
    b, nb, blk_len = g.shape[:3]
    return g.reshape((b, nb * blk_len) + g.shape[3:])


def paged_dequant_view(arena, scales, tables, out_dtype):
    """Dense DEQUANTIZED per-sequence view of an int8 paged arena: the
    gather of ``paged_gather_view`` with each entry's per-kv-head
    absmax scale multiplied back in, cast to the compute dtype.  This
    is the quantized cache's ONE read path, on the chip and on the
    CPU alike — one definition of the dequant math shared by
    ``decode_attention_paged``, ``decode_attention_paged_multi`` and
    ``paged_prefix_attention``."""
    if jnp.dtype(arena.dtype) != jnp.dtype(jnp.int8):
        raise TypeError(
            "paged_dequant_view: kv_scales supplied for a "
            f"{jnp.dtype(arena.dtype).name} arena — scale planes only "
            "ride an int8 code arena (a float cache must pass "
            "kv_scales=None)")
    g = arena[tables].astype(jnp.float32)   # [B, max_blocks, L, ...]
    s = scales[tables]                      # [B, max_blocks, L, H_kv]
    if arena.ndim == 3:
        d = arena.shape[2] // scales.shape[2]
        s = jnp.repeat(s, d, axis=-1)       # heads-in-lanes expansion
    else:
        s = s[..., None]
    deq = (g * s).astype(out_dtype)
    b, nb, blk_len = deq.shape[:3]
    return deq.reshape((b, nb * blk_len) + deq.shape[3:])


def decode_attn_sig(b, hkv, g, s, d, dtype):
    import numpy as np
    return f"{b}x{hkv}x{g}x{s}x{d}/{np.dtype(dtype)}"


def _gate_shared(q4, cache, s, align_ok, align_reason, q_rows=_GPAD,
                 has_scales=False, acc_rows=0):
    """The gate checks common to the dense and paged dispatchers —
    ONE implementation so the two routes cannot silently diverge.
    ``s`` is the count of rows staged in VMEM (the whole cache for the
    dense kernel, ``_paged_staging`` for the streaming one);
    ``align_ok``/``align_reason`` inject the path-specific
    sublane-tiling rule at its position in the check order; ``q_rows``
    is the per-head q-row block the caller stages (``_GPAD`` for the
    single-token kernels, a multiple of it for the K-wide verify) and
    scales the logits-scratch VMEM estimate; ``acc_rows`` is the rows
    of the streaming kernel's full-width q block and float32
    accumulator (0 for the dense kernel, which has neither);
    ``has_scales`` says the caller carries the int8 cache's scale
    arenas: an int8 cache with them rejects as ``int8_scale_lanes``,
    without them, like every other q/cache dtype mix, as
    ``dtype_mismatch``.  Returns (use_pallas, reason-or-None); the
    caller maps None to its accept reason."""
    from ...core.flags import flag
    if not flag("use_decode_attention_kernel"):
        return False, "flag_disabled"
    if not pallas_enabled():
        if refused_for_partitioning():
            return False, "gspmd_partitioned"
        return False, "pallas_unavailable"
    if cache.ndim != 3:
        return False, "unpacked_cache"
    if jnp.dtype(q4.dtype) != jnp.dtype(cache.dtype):
        if has_scales and jnp.dtype(cache.dtype) == jnp.dtype(jnp.int8):
            # a kernel would stage the [NB+1, L, H_kv] f32 scale planes
            # by DMA, one [L, H_kv] slab per block, and Mosaic slices
            # an HBM plane only in whole 128-lane tiles ("Slice shape
            # along dimension 2 must be aligned to tiling (128), but is
            # 8", v5e / jax 0.9.0, at block lengths 16 and 32 alike).
            # No served model has 128 KV heads, so there is no int8
            # kernel: the cache reads through ``paged_dequant_view``
            # until the scale planes change shape.
            return False, "int8_scale_lanes"
        return False, "dtype_mismatch"
    if has_scales:
        # scale arenas beside a cache of the compute dtype: the caller
        # broke the operand contract (``paged_dequant_view`` raises)
        return False, "scales_mismatch"
    b, hkv, g, d = q4.shape
    w = cache.shape[2]
    if not packed_ok(hkv, d) or w != hkv * d:
        return False, "geometry"
    if g > _GPAD:        # q_cat blocks hold at most 8 query heads/KV head
        return False, "group_too_wide"
    if not align_ok:
        return False, align_reason
    itemsize = jnp.dtype(cache.dtype).itemsize
    gw = max(_LANES, d)
    lg_bytes = (w // gw) * (gw // d) * q_rows * s * 4
    vmem = 2 * s * w * itemsize + lg_bytes
    vmem += acc_rows * w * (4 + 2 * jnp.dtype(q4.dtype).itemsize)
    if vmem > _VMEM_BUDGET:
        return False, "vmem_budget"
    return True, None


def _route_decision(q4, cache):
    """(use_pallas, reason) for the decode-attention dispatch gate —
    the reason string feeds the ``pallas.decode_attention.route``
    fallback-rate counter."""
    s = cache.shape[1]
    use, reason = _gate_shared(q4, cache, s, s % 8 == 0, "seq_align")
    return use, reason or "ok"


_route_counter_inst = None


def _route_counter():
    # resolved once: the gate runs per trace AND per eager/interpret
    # decode step, so the registry lookup must not be on that path.
    # Always the PROCESS-DEFAULT registry: the gate is a free function
    # with no engine context, so route decisions are process-global —
    # engines holding a private registry= still contribute here, and a
    # private registry's export carries no route series
    global _route_counter_inst
    if _route_counter_inst is None:
        from ...observability import metrics as _obs
        _route_counter_inst = _obs.get_registry().counter(
            "pallas.decode_attention.route",
            "decode-attention dispatch decisions (pallas kernel vs XLA "
            "fallback, with the gating reason)",
            labels=("decision", "reason"))
    return _route_counter_inst


def _count_route(decision: str, reason: str):
    """ONE emit site for the route counter, guarding the closed reason
    vocabulary at runtime (the graftlint vocab pass cannot resolve the
    tuple-returning gate functions, so the closure is enforced here)."""
    if reason not in DECODE_ROUTE_REASONS:
        raise ValueError(
            f"unknown decode-attention route reason {reason!r} — "
            f"known: {DECODE_ROUTE_REASONS}")
    _route_counter().inc(decision=decision, reason=reason)


def should_use_pallas(q4, cache) -> bool:
    use, reason = _route_decision(q4, cache)
    # counted at trace/gate time (once per compiled program or direct
    # query, not per device step): the always-on Pallas-fallback-rate
    # signal the benchmark's route check and a Prometheus scrape read
    _count_route("pallas" if use else "xla", reason)
    return use


def _route_decision_paged(q4, arena, tables, kv_scales=None):
    """(use_pallas, reason) for the PAGED decode-attention gate: the
    shared gate (``_gate_shared``) evaluated on the arena geometry,
    with the paged-only table rule (``_paged_table_rule``) in place of
    ``seq_align``.  Accepts route as ``paged_ok`` so the route counter
    separates paged-kernel traffic from dense ``ok``; the quantized
    cache (``kv_scales`` given) never accepts."""
    s, acc_rows = _paged_staging(q4.shape[1], 1, q4.shape[2], arena,
                                 tables)
    use, reason = _gate_shared(
        q4, arena, s, *_paged_table_rule(arena),
        has_scales=kv_scales is not None, acc_rows=acc_rows)
    return use, reason or "paged_ok"


def should_use_pallas_paged(q4, arena, tables, kv_scales=None) -> bool:
    use, reason = _route_decision_paged(q4, arena, tables, kv_scales)
    _count_route("pallas" if use else "xla", reason)
    if _SHARD_N is not None:
        count_shard_route(q4.shape[1], _SHARD_N, use)
    return use


_QROWS_MAX = 4 * _GPAD      # per-head q-row cap of the K-wide kernel


def _route_decision_paged_multi(q5, arena, tables, kv_scales=None):
    """(use_pallas, reason) for the K-WIDE paged verify gate
    (``decode_attention_paged_multi``): the shared gate evaluated on
    the arena geometry with the paged table rule, plus the verify
    kernel's own row budget — the block-diagonal q staging packs
    ``g * C`` query rows per head (C speculative positions x G grouped
    query heads), rounded up to the sublane unit; wider than
    ``_QROWS_MAX`` rows would blow the logits scratch for no win
    (reason ``query_rows``).  Accepts route as ``paged_multi_ok`` so
    the route counter separates verify traffic from single-token
    ``paged_ok``."""
    b, cq, hkv, g, d = q5.shape
    qr = -(-(g * cq) // _GPAD) * _GPAD
    if qr > _QROWS_MAX:
        return False, "query_rows"
    s, acc_rows = _paged_staging(hkv, cq, g, arena, tables)
    use, reason = _gate_shared(
        q5[:, 0], arena, s, *_paged_table_rule(arena),
        q_rows=qr, has_scales=kv_scales is not None, acc_rows=acc_rows)
    return use, reason or "paged_multi_ok"


def should_use_pallas_paged_multi(q5, arena, tables,
                                  kv_scales=None) -> bool:
    use, reason = _route_decision_paged_multi(q5, arena, tables,
                                              kv_scales)
    _count_route("pallas" if use else "xla", reason)
    if _SHARD_N is not None:
        count_shard_route(q5.shape[2], _SHARD_N, use)
    return use


def _kernel(lens_ref, qcat_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, lg_ref, ksem, vsem,
            *, chunk, n_chunks_max, scale, out_dtype, hkv, g, d, gw, hp,
            ng):
    """One program per batch row, two-phase (no per-chunk softmax
    chains).  Phase 0: guarded chunk DMAs for the valid prefix only.
    Phase 1: one block-diagonal dot per 128-lane head group.  Phase 2:
    one masked softmax over the whole logits scratch.  Phase 3: one PV
    dot per group, outputs sliced from the small [hp*8, gw] result.

    Scratch-reuse invariant: VMEM scratch is SHARED across the grid and
    the prefix-aware DMAs refresh only rows ``<= length`` — ``vbuf`` is
    zeroed at program 0 ONLY, ``kbuf`` is NEVER zeroed, so past this
    row's prefix both buffers hold the PREVIOUS program's chunks (or,
    at program 0, zeros/undefined).  Correctness rests on exactly two
    properties: (a) every logit at row > length is masked to -1e30
    before exp, so stale K contributes weight exp(-inf) = 0; (b) vbuf
    was zeroed once at program 0, so a zero weight can never meet an
    undefined NaN bit pattern in V (0 * NaN = NaN — stale-but-real V
    from earlier programs is finite and safe under (a)).  Both depend
    on the grid executing SEQUENTIALLY (Pallas-TPU 'arbitrary' grid
    order); declaring the batch dimension 'parallel' would race
    programs on the shared scratch and break the invariant."""
    bi = pl.program_id(0)
    length = lens_ref[bi]                     # last valid slot index
    n_chunks = length // chunk + 1
    rows = n_chunks_max * chunk

    # program 0 owns undefined scratch: zero V so stale NaN bit
    # patterns can never poison a PV dot (p is exactly 0 beyond the
    # prefix, but 0 * NaN = NaN).  K needs no memset: garbage logits
    # are masked to -inf before exp.
    @pl.when(bi == 0)
    def _():
        vbuf[...] = jnp.zeros_like(vbuf)

    for c in range(n_chunks_max):             # static unroll, guarded
        @pl.when(c < n_chunks)
        def _(c=c):
            pltpu.make_async_copy(
                k_hbm.at[bi, pl.ds(c * chunk, chunk), :],
                kbuf.at[pl.ds(c * chunk, chunk), :], ksem.at[c]).start()
            pltpu.make_async_copy(
                v_hbm.at[bi, pl.ds(c * chunk, chunk), :],
                vbuf.at[pl.ds(c * chunk, chunk), :], vsem.at[c]).start()

    for c in range(n_chunks_max):
        @pl.when(c < n_chunks)
        def _(c=c):
            pltpu.make_async_copy(
                k_hbm.at[bi, pl.ds(c * chunk, chunk), :],
                kbuf.at[pl.ds(c * chunk, chunk), :], ksem.at[c]).wait()

    # phase 1: per group, [hp*8, gw] @ [rows, gw]^T — the block-
    # diagonal q_cat contracts all gw lanes; rival heads' lanes hold
    # zeros, so each output row is exactly one head's logits
    for p in range(ng):
        lg_ref[p] = jax.lax.dot_general(
            qcat_ref[0, p], kbuf[:, p * gw:(p + 1) * gw],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [hp*8, rows]

    # phase 2: masked softmax (mask by row validity and q-row padding)
    sub = jax.lax.broadcasted_iota(jnp.int32, (ng, hp * _GPAD, rows), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (ng, hp * _GPAD, rows), 2)
    keep = (row <= length) & (jax.lax.rem(sub, _GPAD) < g)
    lg = jnp.where(keep, lg_ref[...], _NEG_INF)
    m = jnp.max(lg, axis=-1, keepdims=True)
    p_ = jnp.exp(lg - m)
    l = jnp.sum(p_, axis=-1, keepdims=True)    # [ng, hp*8, 1]
    lg_ref[...] = p_

    for c in range(n_chunks_max):
        @pl.when(c < n_chunks)
        def _(c=c):
            pltpu.make_async_copy(
                v_hbm.at[bi, pl.ds(c * chunk, chunk), :],
                vbuf.at[pl.ds(c * chunk, chunk), :], vsem.at[c]).wait()

    # phase 3: PV per group; the head's D lanes and G rows come from
    # the small [hp*8, gw] result (cheap slices)
    for p in range(ng):
        pv_w = jax.lax.dot_general(
            lg_ref[p].astype(vbuf.dtype), vbuf[:, p * gw:(p + 1) * gw],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [hp*8, gw]
        for j in range(hp):
            h = p * hp + j
            o_ref[0, h] = (pv_w[j * _GPAD:j * _GPAD + g,
                                j * d:(j + 1) * d]
                           / l[p, j * _GPAD:j * _GPAD + g]
                           ).astype(out_dtype)


def _paged_stream_kernel(lens_ref, tbl_ref, q_ref, k_hbm, v_hbm, o_ref,
                         kbuf, vbuf, m_ref, l_ref, acc_ref, stage_ref,
                         ksem, vsem,
                         *, block_len, bpg, n_blocks_max, cq, g, hkv, d,
                         scale, out_dtype):
    """Streaming paged decode attention over a FLOAT arena: one program
    per slot, the slot's context walked in GROUPS of ``bpg`` blocks
    (``rows = bpg * block_len`` staged rows, sized by ``_stage_blocks``)
    through two stages of K and two of V used in turn, with an online
    softmax across groups — running max ``m_ref``, running sum ``l_ref``
    and the rescaled float32 accumulator ``acc_ref``, one division at
    the end.  QK^T, the mask, ``exp`` and PV run on one group while the
    next group's block DMAs (``tbl_ref[b, c]`` resolved at issue time
    from the scalar-prefetched table) are in flight, and only over the
    ``ceil(valid rows / rows)`` groups of the valid prefix: neither the
    staged bytes nor the compute grow with the table's width.

    The prefetch crosses the program boundary: the grid is sequential,
    the table and ``lens`` are whole in SMEM, so while program ``bi``
    computes its last group it issues group 0 of program ``bi + 1`` into
    the other stage.  ``stage_ref[0]`` carries which stage holds the
    next program's first group; program 0 primes the pipeline, the last
    program prefetches nothing, and every copy started is waited for
    inside the program that computes on it.  One DMA semaphore per
    stage and operand: a group's copies all signal it, and the wait
    walks the same blocks.

    Layout: every query row of the slot sits on the sublane axis of ONE
    block-diagonal q, ``q_ref[0]`` [P, W] (``_build_qall``): row
    ``h * cq * g + c * g + gi`` holds position c of grouped query head
    gi of KV head h in lanes [h*D, (h+1)*D) and zeros elsewhere, so one
    full-width dot per group gives every head's logits [P, rows] with
    no padding rows between heads, the softmax statistics are [P, 1],
    and one dot gives PV [P, W], of which row r's own head's D lanes
    are the answer (the rest, other heads' V under this head's weights,
    is dropped at the end).  ``cq`` is 1 for decode and K+1 for the
    speculative verifier; query c sees cache rows ``<= lens[b] + c``,
    the causal frontier the sequential decode loop would have given it.

    Stale-buffer invariant: a stage is refreshed only as far as the
    group's valid blocks reach, so rows past the frontier of the last
    group hold an EARLIER group's or an earlier SLOT's data (or, for K
    before anything was staged, undefined bits).  Correctness rests on
    (a) the masked-logit flush: every logit past its query's frontier
    is set to -1e30 before ``exp``, so stale K weighs exp(-inf) = 0 (the
    first group always holds row 0, so the running max is real from
    then on); (b) both V stages are zeroed once at program 0, so a zero
    weight never meets an undefined NaN bit pattern (0 * NaN = NaN;
    stale-but-real V is finite and safe under (a)).  Both, and the
    cross-program prefetch, depend on the grid executing SEQUENTIALLY
    (``dimension_semantics=("arbitrary",)``): a 'parallel' batch
    dimension would race programs on the shared stages.

    A LATENT arena (``_latent_stream_kernel``) is this body with
    ``v_hbm``, ``vbuf`` and ``vsem`` None: one row a token, shared by
    every query head (``hkv`` 1), whose first ``d`` lanes are also its
    value.  Only K is staged, PV reads those lanes of the K stage, and
    the K stages are what is zeroed once."""
    bi = pl.program_id(0)
    last_prog = pl.num_programs(0) - 1
    rows = bpg * block_len
    n_rows = q_ref.shape[1]                   # P
    length = lens_ref[bi]                     # first query's global slot

    def group_blocks(b, gi):
        # how many of group gi's blocks hold rows slot b's queries see
        n_blk = jnp.minimum((lens_ref[b] + cq - 1) // block_len + 1,
                            n_blocks_max)
        return jnp.clip(n_blk - gi * bpg, 0, bpg)

    def for_group(b, gi, stage, count, act):
        for c in range(bpg):                  # short unroll, guarded
            @pl.when(c < count)
            def _(c=c):
                blk = tbl_ref[b, gi * bpg + c]
                dst = pl.ds(c * block_len, block_len)
                act(pltpu.make_async_copy(
                    k_hbm.at[blk], kbuf.at[stage, dst, :], ksem.at[stage]))
                if v_hbm is not None:
                    act(pltpu.make_async_copy(
                        v_hbm.at[blk], vbuf.at[stage, dst, :],
                        vsem.at[stage]))

    values = kbuf if v_hbm is None else vbuf

    @pl.when(bi == 0)
    def _():
        values[...] = jnp.zeros_like(values)
        stage_ref[0] = 0
        for_group(0, 0, 0, group_blocks(0, 0), lambda cp: cp.start())

    first = stage_ref[0]
    n_groups = jnp.minimum((length + cq - 1) // rows + 1,
                           -(-n_blocks_max // bpg))
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    row = jax.lax.broadcasted_iota(jnp.int32, (n_rows, rows), 1)
    frontier = length
    if cq > 1:      # row r of a head's cq*g is position r // g
        sub = jax.lax.broadcasted_iota(jnp.int32, (n_rows, rows), 0)
        frontier = length + jax.lax.rem(sub, cq * g) // g

    def group(gi, carry):
        stage = jax.lax.rem(first + gi, 2)
        # the next group in flight before this one is waited for: this
        # slot's, or past its last group the next slot's first
        tail = gi == n_groups - 1
        nxt_b = jnp.where(tail, jnp.minimum(bi + 1, last_prog), bi)
        nxt_g = jnp.where(tail, 0, gi + 1)
        nxt_n = jnp.where(tail & (bi == last_prog), 0,
                          group_blocks(nxt_b, nxt_g))
        for_group(nxt_b, nxt_g, 1 - stage, nxt_n, lambda cp: cp.start())
        for_group(bi, gi, stage, group_blocks(bi, gi),
                  lambda cp: cp.wait())

        lg = jax.lax.dot_general(
            q_ref[0], kbuf[stage], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [P, rows]
        lg = jnp.where(row + gi * rows <= frontier, lg, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(lg, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p_ = jnp.exp(lg - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p_, axis=-1, keepdims=True)
        scaled, weights, vals = alpha * acc_ref[...], \
            p_.astype(values.dtype), values[stage]
        if v_hbm is None:       # a latent row's values: its own first lanes
            vals = vals[:, :acc_ref.shape[1]]
        acc_ref[...] = scaled + jax.lax.dot_general(
            weights, vals, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [P, W]
        m_ref[...] = m_new
        return carry

    jax.lax.fori_loop(0, n_groups, group, 0)
    stage_ref[0] = jax.lax.rem(first + n_groups, 2)

    # row r keeps its own head's D lanes of the accumulator
    head = jax.lax.broadcasted_iota(jnp.int32, (n_rows, d), 0) // (cq * g)
    out = jnp.zeros((n_rows, d), jnp.float32)
    for h in range(hkv):
        out = jnp.where(head == h, acc_ref[:, h * d:(h + 1) * d], out)
    o_ref[0] = (out / l_ref[...]).astype(out_dtype)


def _build_qcat(q4, hp, ng, gw):
    """Block-diagonal q: [B, H_kv, G, D] -> [B, ng, hp*8, gw] where
    group p, block j holds head p*hp+j's q in lane range [j*D, (j+1)*D)
    and zeros elsewhere."""
    b, hkv, g, d = q4.shape
    q8 = jnp.pad(q4, ((0, 0), (0, 0), (0, _GPAD - g), (0, 0)))
    qg = q8.reshape(b, ng, hp, _GPAD, d)
    eye = jnp.eye(hp, dtype=q4.dtype)
    qcat = jnp.einsum("bnjgd,jk->bnjgkd", qg, eye)
    return qcat.reshape(b, ng, hp * _GPAD, gw)


def _decode_attention_pallas(q4, k_cache, v_cache, lens, chunk=None):
    """q4: [B, H_kv, G, D]; caches packed [B, S, H_kv*D]."""
    b, hkv, g, d = q4.shape
    s = k_cache.shape[1]
    w = k_cache.shape[2]
    gw = max(_LANES, d)            # lanes per head group
    hp = gw // d                   # heads per group
    ng = w // gw                   # head groups
    if chunk is None:
        from .schedule_search import get_schedule
        hit = get_schedule("decode_attention",
                           decode_attn_sig(b, hkv, g, s, d, q4.dtype))
        chunk = int(hit) if hit else DEFAULT_CHUNK
    while s % chunk:
        chunk //= 2
    n_chunks_max = s // chunk
    kernel = functools.partial(
        _kernel, chunk=chunk, n_chunks_max=n_chunks_max,
        scale=1.0 / (d ** 0.5), out_dtype=q4.dtype, hkv=hkv, g=g, d=d,
        gw=gw, hp=hp, ng=ng)
    qcat = _build_qcat(q4, hp, ng, gw)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, ng, hp * _GPAD, gw),
                         lambda bi, lens_p: (bi, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, hkv, g, d),
                               lambda bi, lens_p: (bi, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((s, w), k_cache.dtype),
            pltpu.VMEM((s, w), v_cache.dtype),
            pltpu.VMEM((ng, hp * _GPAD, s), jnp.float32),
            pltpu.SemaphoreType.DMA((n_chunks_max,)),
            pltpu.SemaphoreType.DMA((n_chunks_max,)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q4.dtype),
        compiler_params=_COMPILER_PARAMS,
        interpret=not on_tpu(),
    )(lens.astype(jnp.int32), qcat, k_cache, v_cache)


def _guard_replicated_tables(tables):
    """The paged dispatch path assumes block tables are replicated host
    plan state (the scalar-prefetched table must be WHOLE on every
    shard).  A concrete committed array carrying a partitioned sharding
    is the one way that assumption can silently break — reject it with
    a typed error.  Tracers are skipped: under a serving trace the
    table is a fresh per-dispatch host push whose (replicated) layout
    the builders control."""
    if isinstance(tables, jax.core.Tracer) \
            or not isinstance(tables, jax.Array):
        return
    sharding = getattr(tables, "sharding", None)
    if sharding is not None and not sharding.is_fully_replicated:
        raise ShardedTableError(
            f"paged decode dispatch requires a REPLICATED block table; "
            f"got one committed with {sharding} — block tables are "
            f"host scheduling state driven identically on every "
            f"kv-head shard (shard the arenas via ServingEngine(mesh=), "
            f"never the tables)")


def _build_qall(q5):
    """Block-diagonal q over the whole cache width: [B, C, H_kv, G, D]
    -> [B, P, H_kv*D], row ``h*C*G + c*G + gi`` holding position c of
    query head gi of KV head h in lanes [h*D, (h+1)*D) and zeros
    elsewhere; P is H_kv*C*G rounded up to the sublane unit (the
    padding rows are zero queries: finite, dropped by the caller)."""
    b, cq, hkv, g, d = q5.shape
    qh = jnp.transpose(q5, (0, 2, 1, 3, 4)).reshape(b, hkv, cq * g, d)
    eye = jnp.eye(hkv, dtype=q5.dtype)
    qall = jnp.einsum("bhrd,hk->bhrkd", qh, eye).reshape(
        b, hkv * cq * g, hkv * d)
    pad = _stream_rows(hkv, cq, g) - hkv * cq * g
    return jnp.pad(qall, ((0, 0), (0, pad), (0, 0)))


def _paged_stream(q5, k_arena, v_arena, tables, lens):
    """Dispatch ``_paged_stream_kernel``.  q5: [B, C, H_kv, G, D]; float
    arenas packed [NB+1, L, H_kv*D] (last row = trash block); tables:
    [B, max_blocks] int32 arena row indices; lens: [B] global slot of
    the FIRST query.  Returns [B, H_kv, C*G, D], rows ``c*G + gi``."""
    _guard_replicated_tables(tables)
    b, cq, hkv, g, d = q5.shape
    blk_len, w = k_arena.shape[1:]
    bpg = _stage_blocks(k_arena, tables)
    rows = bpg * blk_len
    qall = _build_qall(q5)
    n_rows = qall.shape[1]
    kernel = functools.partial(
        _paged_stream_kernel, block_len=blk_len, bpg=bpg,
        n_blocks_max=tables.shape[1], cq=cq, g=g, hkv=hkv, d=d,
        scale=1.0 / (d ** 0.5), out_dtype=q5.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, n_rows, w),
                               lambda bi, lens_p, tbl_p: (bi, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, n_rows, d),
                               lambda bi, lens_p, tbl_p: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, rows, w), k_arena.dtype),    # K stages
            pltpu.VMEM((2, rows, w), v_arena.dtype),    # V stages
            pltpu.VMEM((n_rows, 1), jnp.float32),       # running max
            pltpu.VMEM((n_rows, 1), jnp.float32),       # running sum
            pltpu.VMEM((n_rows, w), jnp.float32),       # accumulator
            pltpu.SMEM((1,), jnp.int32),                # live stage
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_rows, d), q5.dtype),
        compiler_params=_STREAM_COMPILER_PARAMS,
        interpret=not on_tpu(),
    )(lens.astype(jnp.int32), tables.astype(jnp.int32), qall, k_arena,
      v_arena)
    return out[:, :hkv * cq * g].reshape(b, hkv, cq * g, d)


def _decode_attention_pallas_paged(q4, k_arena, v_arena, tables, lens):
    """q4: [B, H_kv, G, D]; arenas packed [NB+1, L, H_kv*D] (last row =
    trash block); tables: [B, max_blocks] int32 arena row indices."""
    return _paged_stream(q4[:, None], k_arena, v_arena, tables, lens)


def _decode_attention_pallas_paged_multi(q5, k_arena, v_arena, tables,
                                         lens):
    """q5: [B, C, H_kv, G, D]; arenas packed [NB+1, L, H_kv*D] (last
    row = trash block); tables: [B, max_blocks] int32; lens: [B] global
    position of the FIRST query.  Returns [B, C, H_kv, G, D]."""
    b, cq, hkv, g, d = q5.shape
    out = _paged_stream(q5, k_arena, v_arena, tables, lens)
    # head-major rows c*g+gi back to [B, C, H_kv, G, D]
    return jnp.transpose(out.reshape(b, hkv, cq, g, d), (0, 2, 1, 3, 4))


def _latent_stream_kernel(lens_ref, tbl_ref, q_ref, c_hbm, o_ref, cbuf,
                          m_ref, l_ref, acc_ref, stage_ref, csem, **static):
    """``_paged_stream_kernel`` over one latent arena: no V operand."""
    _paged_stream_kernel(lens_ref, tbl_ref, q_ref, c_hbm, None, o_ref, cbuf,
                         None, m_ref, l_ref, acc_ref, stage_ref, csem, None,
                         **static)


def _route_decision_latent(q, arena, tables, dv):
    """(use_pallas, reason) for the latent decode gate.  ``q`` [B, G, W]:
    every query head of a slot against the slot's one row a token, so the
    kernel's q block is the ``G`` rows as they are and ``group_too_wide``
    (the block-diagonal q of the KV kernels) does not arise; what is
    staged is two stages of rows ``W`` wide and an accumulator ``dv``
    wide."""
    from ...core.flags import flag
    if not flag("use_decode_attention_kernel"):
        return False, "flag_disabled"
    if not pallas_enabled():
        if refused_for_partitioning():
            return False, "gspmd_partitioned"
        return False, "pallas_unavailable"
    if jnp.dtype(q.dtype) != jnp.dtype(arena.dtype):
        return False, "dtype_mismatch"
    w = arena.shape[2]
    if arena.ndim != 3 or q.shape[2] != w or w % _LANES or dv % _LANES \
            or dv > w:
        return False, "geometry"
    ok, reason = _paged_table_rule(arena)
    if not ok:
        return False, reason
    rows = _stage_blocks(arena, tables) * arena.shape[1]
    p = _stream_rows(1, 1, q.shape[1])
    vmem = (2 * rows * w * jnp.dtype(arena.dtype).itemsize + p * rows * 4
            + p * (dv * 4 + 2 * w * jnp.dtype(q.dtype).itemsize))
    if vmem > _VMEM_BUDGET:
        return False, "vmem_budget"
    return True, "latent_ok"


def _latent_stream(q, arena, tables, lens, dv, scale):
    """Dispatch ``_latent_stream_kernel``.  q [B, G, W]; arena
    [NB+1, L, W] (last row = trash block); returns [B, G, dv]."""
    _guard_replicated_tables(tables)
    b, g, w = q.shape
    blk_len = arena.shape[1]
    bpg = _stage_blocks(arena, tables)
    rows = bpg * blk_len
    n_rows = _stream_rows(1, 1, g)
    qall = jnp.pad(q, ((0, 0), (0, n_rows - g), (0, 0)))
    kernel = functools.partial(
        _latent_stream_kernel, block_len=blk_len, bpg=bpg,
        n_blocks_max=tables.shape[1], cq=1, g=g, hkv=1, d=dv, scale=scale,
        out_dtype=q.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, n_rows, w),
                               lambda bi, lens_p, tbl_p: (bi, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, n_rows, dv),
                               lambda bi, lens_p, tbl_p: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, rows, w), arena.dtype),      # row stages
            pltpu.VMEM((n_rows, 1), jnp.float32),       # running max
            pltpu.VMEM((n_rows, 1), jnp.float32),       # running sum
            pltpu.VMEM((n_rows, dv), jnp.float32),      # accumulator
            pltpu.SMEM((1,), jnp.int32),                # live stage
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_rows, dv), q.dtype),
        compiler_params=_STREAM_COMPILER_PARAMS,
        name="decode_attention_latent",
        interpret=not on_tpu(),
    )(lens.astype(jnp.int32), tables.astype(jnp.int32), qall, arena)
    return out[:, :g]


def decode_attention_latent(q, arena, tables, lens, dv, scale):
    """One-token attention of ``G`` query heads over a LATENT paged
    cache: every head scores the same row a token (``paged_latent_shape``)
    and sums the rows' first ``dv`` lanes under its weights, which is
    latent attention with the up-projections absorbed into the query and
    the output.

    q: [B, G, W], the absorbed query beside its positional part, zero in
    the row's pad lanes; arena [NB+1, L, W]; tables [B, max_blocks]; lens
    [B] = index of the last valid slot; ``scale`` multiplies the logits.
    On the chip the streaming kernel walks the table (no dense copy of
    it); elsewhere the rows are gathered.  Returns [B, G, dv] in q.dtype."""
    use, reason = _route_decision_latent(q, arena, tables, dv)
    _count_route("pallas" if use else "xla", reason)
    if use:
        return _latent_stream(q, arena, tables, lens, dv, scale)
    rows = paged_gather_view(arena, tables)                  # [B, S, W]
    logits = jnp.einsum("bgw,bsw->bgs", q, rows,
                        preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(rows.shape[1])[None, :] <= lens[:, None]
    logits = jnp.where(valid[:, None, :], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bgs,bsv->bgv", probs, rows[..., :dv])


def _decode_attention_xla(q4, k_cache, v_cache, lens):
    """Reference math on the logical [B, S, H_kv, D] view (fp32
    softmax): the non-TPU / odd-shape fallback.  Accepts packed
    [B, S, W] or unpacked [B, S, H, D] caches."""
    b, hkv, g, d = q4.shape
    if k_cache.ndim == 3:
        s = k_cache.shape[1]
        k_cache = k_cache.reshape(b, s, hkv, d)
        v_cache = v_cache.reshape(b, s, hkv, d)
    s_max = k_cache.shape[1]
    logits = jnp.einsum("bkgd,bskd->bkgs", q4, k_cache,
                        preferred_element_type=jnp.float32)
    logits = logits / jnp.sqrt(jnp.float32(d))
    valid = jnp.arange(s_max)[None, :] <= lens[:, None]       # [B, S]
    logits = jnp.where(valid[:, None, None, :], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1).astype(q4.dtype)
    return jnp.einsum("bkgs,bskd->bkgd", probs, v_cache.astype(q4.dtype))


def decode_attention(q, k_cache, v_cache, lens):
    """One-token GQA attention over the valid cache prefix.

    q: [B, H_q, D]; k_cache/v_cache: packed [B, S, H_kv*D] (heads
    contiguous in lanes) or unpacked [B, S, H_kv, D]; lens: [B] =
    index of the LAST valid slot (the just-written token) — slots
    ``<= lens`` participate.  Returns [B, H_q * D] in q.dtype.
    """
    b, hq, d = q.shape
    hkv = (k_cache.shape[2] // d if k_cache.ndim == 3
           else k_cache.shape[2])
    g = hq // hkv
    q4 = q.reshape(b, hkv, g, d)
    if should_use_pallas(q4, k_cache):
        out = _decode_attention_pallas(q4, k_cache, v_cache, lens)
    else:
        out = _decode_attention_xla(q4, k_cache, v_cache, lens)
    return out.reshape(b, hq * d)


def decode_attention_paged(q, k_arena, v_arena, tables, lens,
                           kv_scales=None):
    """One-token GQA attention over a PAGED cache prefix.

    q: [B, H_q, D]; arenas: ``paged_arena_shape`` pools (packed
    [NB+1, L, H_kv*D] or unpacked [NB+1, L, H_kv, D], last row = trash
    block); tables: [B, max_blocks] int32 arena row per logical block;
    lens: [B] = index of the LAST valid slot; kv_scales: None for a
    float cache, or the int8 cache's ``(k_scales, v_scales)`` pair of
    [NB+1, L, H_kv] f32 absmax planes.  On TPU (and when the block
    geometry passes ``_route_decision_paged``) a float cache runs the
    block-table Pallas kernel — DMA indirection through the
    scalar-prefetched table, no dense copy of the pool.  Otherwise,
    and always for the int8 cache, the gather-based XLA path
    materializes each row's dense view (``paged_gather_view``, or the
    dequantized ``paged_dequant_view`` for int8) and reuses the
    reference math.  Returns [B, H_q * D] in q.dtype.
    """
    b, hq, d = q.shape
    hkv = (k_arena.shape[2] // d if k_arena.ndim == 3
           else k_arena.shape[2])
    g = hq // hkv
    q4 = q.reshape(b, hkv, g, d)
    if should_use_pallas_paged(q4, k_arena, tables, kv_scales):
        out = _decode_attention_pallas_paged(q4, k_arena, v_arena,
                                             tables, lens)
    elif kv_scales is not None:
        out = _decode_attention_xla(
            q4, paged_dequant_view(k_arena, kv_scales[0], tables, q.dtype),
            paged_dequant_view(v_arena, kv_scales[1], tables, q.dtype),
            lens)
    else:
        out = _decode_attention_xla(q4, paged_gather_view(k_arena, tables),
                                    paged_gather_view(v_arena, tables),
                                    lens)
    return out.reshape(b, hq * d)


def paged_prefix_attention(q, k_arena, v_arena, tables, start,
                           kv_scales=None):
    """Chunked-prefill attention over the paged cache: C chunk queries
    at global positions ``start + row`` attend causally over everything
    already written through the block table (prefix-cached blocks,
    earlier chunks, and this chunk's own K/V — the scatter happens
    before this read).

    q: [B, C, H_q, D]; arenas/tables as ``decode_attention_paged``;
    start: [B] first global position of the chunk.  Always the
    gather-based XLA path with fp32 softmax — prefill is
    compute-bound over the chunk, not cache-sweep-bound, so the paged
    kernel's DMA indirection buys nothing here (the verifier's
    cache-sweep-bound twin, ``decode_attention_paged_multi``, is the
    one that gates into the K-wide Pallas kernel).  Returns
    [B, C, H_q, D] in q.dtype; rows past the prompt's true length
    compute garbage that the caller masks (their K/V writes were
    trash-routed, so the garbage never enters any other row's
    prefix).  ``kv_scales`` selects the int8 cache's dequantizing
    gather view, same contract as ``decode_attention_paged``."""
    return _paged_multi_xla(q, k_arena, v_arena, tables, start,
                            kv_scales)


def decode_attention_paged_multi(q, k_arena, v_arena, tables, lens,
                                 kv_scales=None):
    """K-wide GQA attention over a PAGED cache prefix — the speculative
    -decoding verify forward's attention (one target forward scores the
    just-written token plus K draft candidates).

    q: [B, C, H_q, D] — C = K+1 query positions per row, position c at
    global slot ``lens[b] + c`` (their K/V were scattered through the
    table before this read, exactly the chunk-prefill discipline);
    arenas/tables as ``decode_attention_paged``; lens: [B] global slot
    of the FIRST query.  Query c attends causally over slots
    ``<= lens[b] + c`` — token-for-token the prefix the sequential
    decode loop would have offered it, which is what makes longest-
    prefix acceptance exactly greedy-equivalent.  Unlike chunk prefill
    this path IS cache-sweep-bound (C is small, the prefix is long), so
    it gates into the K-wide paged Pallas kernel
    (``_route_decision_paged_multi``; accept reason ``paged_multi_ok``)
    with the gather-based XLA path as the universal fallback and the
    int8 cache's (``kv_scales``) only reader.  Returns
    [B, C, H_q, D] in q.dtype."""
    b, cc, hq, d = q.shape
    hkv = (k_arena.shape[2] // d if k_arena.ndim == 3
           else k_arena.shape[2])
    g = hq // hkv
    q5 = q.reshape(b, cc, hkv, g, d)
    if should_use_pallas_paged_multi(q5, k_arena, tables, kv_scales):
        out = _decode_attention_pallas_paged_multi(
            q5, k_arena, v_arena, tables, lens)
        return out.reshape(b, cc, hq, d)
    return _paged_multi_xla(q, k_arena, v_arena, tables, lens, kv_scales)


def _paged_multi_xla(q, k_arena, v_arena, tables, start, kv_scales=None):
    """Gather-based multi-position paged attention (fp32 softmax): the
    shared XLA body of ``paged_prefix_attention`` and
    ``decode_attention_paged_multi`` — each row's dense view is
    materialized through its table (dequantized through
    ``paged_dequant_view`` when ``kv_scales`` marks an int8 cache) and
    query c is masked to rows ``<= start[b] + c``."""
    b, cc, hq, d = q.shape
    if kv_scales is not None:
        kd = paged_dequant_view(k_arena, kv_scales[0], tables, q.dtype)
        vd = paged_dequant_view(v_arena, kv_scales[1], tables, q.dtype)
    else:
        kd = paged_gather_view(k_arena, tables)
        vd = paged_gather_view(v_arena, tables)
    if kd.ndim == 3:
        s = kd.shape[1]
        hkv = kd.shape[2] // d
        kd = kd.reshape(b, s, hkv, d)
        vd = vd.reshape(b, s, hkv, d)
    else:
        s, hkv = kd.shape[1], kd.shape[2]
    g = hq // hkv
    q5 = q.reshape(b, cc, hkv, g, d)
    logits = jnp.einsum("bckgd,bskd->bckgs", q5, kd,
                        preferred_element_type=jnp.float32)
    logits = logits / jnp.sqrt(jnp.float32(d))
    pos = start.reshape(b, 1) + jnp.arange(cc)[None, :]        # [B, C]
    keep = jnp.arange(s)[None, None, :] <= pos[:, :, None]     # [B, C, S]
    logits = jnp.where(keep[:, :, None, None, :], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bckgs,bskd->bckgd", probs, vd.astype(q.dtype))
    return out.reshape(b, cc, hq, d)
