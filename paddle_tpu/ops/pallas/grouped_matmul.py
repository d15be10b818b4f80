"""Grouped matmul that streams its groups' planes (Pallas).

``grouped_matmul(xs [m, K], w [G, K, N], sizes [G])`` is
``jax.lax.ragged_dot(xs, w, sizes)`` as the routed-expert layer uses it
(``nn/layer/experts.py``): the rows are sorted by group, group ``g`` owns
rows ``offset_g .. offset_g + sizes[g]``, and rows past ``sum(sizes)``
come back zero.  Inputs in the planes' dtype, float32 accumulation over
the whole of K, results in the inputs' dtype.

The call is bound by the planes' bytes (64 planes of 2048 x 1536 bf16 are
403 MB, 0.49 ms at 819 GB/s, against 0.07 ms of FLOPs at 2048 rows), so
the kernel is built around reading a touched group's plane once and an
untouched one never:

- the work units ("visits") are (row tile, group) pairs, listed by a
  small table that is built from ``sizes`` in ``jnp`` before the call
  (``visit_table``) and scalar-prefetched.  An expert layer's three calls
  over the same ``sizes`` build it once: it is one jitted callee, and XLA
  merges its identical calls (compiled for the v5e, three calls and one
  have the same operations).  Visits are ordered by group, then by row tile, which
  is also ascending in the row tile because the groups are contiguous;
- the grid is (N tiles, visits) with K whole.  The streamed operand is the
  ``[K, tn]`` block of the visit's group, double-buffered by the
  pipeline; a group that straddles row tiles has consecutive visits with
  the same block index, for which the pipeline fetches nothing, so a
  plane is read once an N tile however its rows lie.  The row tile
  ``[tm, K]`` and the output tile ``[tm, tn]`` are small beside it;
- a row tile is shared by the groups that meet in it: each visit stores
  the rows of its own group and keeps what earlier visits of the tile
  stored (zero on the tile's first visit), so the output tile goes back
  to HBM once, when the visits move on to the next tile;
- row tiles past ``sum(sizes)`` get one visit each that stores zeros and
  names the last group read, so nothing is fetched for it; the table is
  padded to its static length with copies of the last visit, for which
  nothing is fetched or computed.

The megablox ``gmm`` that ships with jax (``jax/experimental/pallas/ops/
tpu/megablox``) has the same visit structure with K tiled at 128; this
kernel keeps K whole, because a K tile of 128 turns one 6.3 MB plane into
16 x 12 small DMAs and an accumulator pass each.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import on_tpu, pallas_enabled, refused_for_partitioning

__all__ = ["grouped_matmul", "MOE_ROUTE_REASONS"]

# The closed vocabulary of the ``pallas.moe_experts.route`` counter's
# ``reason`` axis: every string ``_moe_route_reason`` can return
# (graftlint vocab pass).  ``grouped_ok`` means the kernel dispatched;
# every other entry names what sent the call to ``jax.lax.ragged_dot``.
MOE_ROUTE_REASONS = (
    "grouped_ok", "pallas_unavailable", "gspmd_partitioned",
    "no_rows", "dtype_not_bf16", "k_align", "n_align", "vmem_budget",
)

_LANES = 128
# Rows of a row tile.  At 128 rows a visit of a whole [2048, 1536] plane
# is 0.8 GFLOP (4.1 us at the v5e's peak) against 6.3 MB (7.7 us at 819
# GB/s): bound by the plane, as the call is.  At 512 rows it would turn
# over (16 us of MXU).  On the v5e 64, 128 and 256 rows read within 1% of
# each other with N whole, and 256 up to 13% slower at a narrow ``tn``
# (chip run, PR 34).
_ROW_TILE = 128
# The streamed operand's share of VMEM: two buffers of one [K, tn] block.
# ``tn`` is the widest divisor of N in whole lane tiles that fits
# (``_col_tile``): the wider the block, the longer the DMA's contiguous
# runs and the fewer sweeps of the row tiles; at K 2048, N 1536 the whole
# plane fits (12.6 MB) and reads 437 us a call where ``tn`` 512 reads 450
# and 256 reads 471 (chip run, PR 34).  What the gate admits is what the
# compiler is told (``_VMEM_LIMIT`` leaves room for the row and output
# tiles' buffers and the float32 product on top of a full budget).
_STREAM_BUDGET = 16 << 20
_VMEM_LIMIT = 48 << 20


def _row_tile(m):
    return min(m, _ROW_TILE)


def _col_tile(k, n, itemsize):
    """The widest ``tn`` that divides N in whole 128-lane tiles with two
    ``[K, tn]`` buffers inside ``_STREAM_BUDGET``; 0 if not even one lane
    tile fits."""
    lanes = n // _LANES
    for parts in range(1, lanes + 1):
        if lanes % parts == 0 and \
                2 * k * (n // parts) * itemsize <= _STREAM_BUDGET:
            return n // parts
    return 0


def _moe_route_reason(xs, w):
    """Why the gate routed as it did: one of ``MOE_ROUTE_REASONS``, from
    what the code can observe (platform, partitioning, shapes, dtype);
    the first two as ``decode_attention``'s gate names them."""
    if not pallas_enabled():
        if refused_for_partitioning():
            return "gspmd_partitioned"
        return "pallas_unavailable"
    return _geometry_reason(xs, w)


def _geometry_reason(xs, w):
    if xs.shape[0] < 1:
        return "no_rows"
    if jnp.dtype(xs.dtype) != jnp.bfloat16 \
            or jnp.dtype(w.dtype) != jnp.bfloat16:
        return "dtype_not_bf16"
    k, n = w.shape[1:]
    if k % _LANES:
        return "k_align"
    if n % _LANES:
        return "n_align"
    if not _col_tile(k, n, jnp.dtype(w.dtype).itemsize):
        return "vmem_budget"
    return "grouped_ok"


_route_counter_inst = None


def _route_counter():
    # the process-default registry, resolved once (as
    # ``decode_attention._route_counter``)
    global _route_counter_inst
    if _route_counter_inst is None:
        from ...observability import metrics as _obs
        _route_counter_inst = _obs.get_registry().counter(
            "pallas.moe_experts.route",
            "grouped-matmul dispatch decisions of the routed-expert layer "
            "(pallas kernel vs jax.lax.ragged_dot, with the gating reason)",
            labels=("decision", "reason"))
    return _route_counter_inst


def should_use_pallas(xs, w) -> bool:
    """The gate, counted once a call at trace time."""
    reason = _moe_route_reason(xs, w)
    use = reason == "grouped_ok"
    _route_counter().inc(decision="pallas" if use else "xla", reason=reason)
    return use


# one jitted callee: an expert layer's table is some thirty small ``jnp``
# operations, and a program of eight such layers lowers them once
@functools.partial(jax.jit, static_argnames=("m", "tm"))
def _visit_table(sizes, *, m, tm):
    """The visits of ``m`` rows grouped by ``sizes`` [G] at ``tm`` rows a
    tile: (offsets [G + 1], group of each visit [V], row tile of each
    visit [V], number of visits [1]), all int32, ``V = row tiles + G -
    1``.  Entries past the number of visits repeat the last visit."""
    g = sizes.shape[0]
    tiles = -(-m // tm)
    n_slots = tiles + g - 1
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    total = ends[-1]
    first_tile = starts // tm
    spans = jnp.where(sizes > 0, (ends - 1) // tm - first_tile + 1, 0)
    # the row tiles past the last group's, one visit each
    covered = (total + tm - 1) // tm
    counts = jnp.concatenate([spans, (tiles - covered)[None]])
    upto = jnp.cumsum(counts)
    n_visits = upto[-1]
    v = jnp.minimum(jnp.arange(n_slots, dtype=jnp.int32), n_visits - 1)
    owner = jnp.sum(v[:, None] >= upto[None, :], axis=1).astype(jnp.int32)
    nth = v - jnp.take(upto - counts, owner)
    tail = owner == g
    # a tail visit names the last group that was read: no plane is
    # fetched for it, and none of its rows are that group's
    last_read = jnp.max(jnp.where(sizes > 0, jnp.arange(g), 0))
    group = jnp.where(tail, last_read, jnp.minimum(owner, g - 1))
    tile = jnp.where(tail, covered,
                     jnp.take(first_tile, jnp.minimum(owner, g - 1))) + nth
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return (offsets.astype(jnp.int32), group.astype(jnp.int32),
            tile.astype(jnp.int32), n_visits[None].astype(jnp.int32))


def _kernel(offs_ref, group_ref, tile_ref, nvis_ref, x_ref, w_ref, o_ref,
            *, tm):
    v = pl.program_id(1)

    @pl.when(v < nvis_ref[0])
    def _visit():
        g, t = group_ref[v], tile_ref[v]
        lo, hi = offs_ref[g] - t * tm, offs_ref[g + 1] - t * tm
        fresh = jnp.logical_or(
            v == 0, tile_ref[jnp.maximum(v - 1, 0)] != t)

        has_rows = jnp.logical_and(lo < tm, hi > 0)

        @pl.when(has_rows)
        def _rows():
            acc = jnp.dot(x_ref[...], w_ref[...],
                          preferred_element_type=jnp.float32)
            row = jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
            mine = jnp.logical_and(row >= lo, row < hi)
            # what other groups' visits of this tile stored; on the
            # tile's first visit the buffer holds nothing yet
            kept = jnp.where(fresh, jnp.zeros_like(o_ref), o_ref[...])
            o_ref[...] = jnp.where(mine, acc.astype(o_ref.dtype), kept)

        @pl.when(jnp.logical_not(has_rows))
        def _tail():
            o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("tm", "tn", "interpret"))
def _grouped_matmul_pallas(xs, w, offsets, group, tile, n_visits, *, tm, tn,
                           interpret):
    """One jitted callee a shape: the calls of a program that share shapes
    share its lowering (jax lowers an inner ``jit`` of one jaxpr once and
    calls it)."""
    m, k = xs.shape
    n = w.shape[2]
    itemsize = jnp.dtype(w.dtype).itemsize
    grid = (n // tn, group.shape[0])
    plane_bytes = w.shape[0] * k * n * itemsize
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((m, n), xs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, v, o, g, t, c: (t[v], 0)),
                pl.BlockSpec((None, k, tn),
                             lambda j, v, o, g, t, c: (g[v], 0, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, v, o, g, t, c: (t[v], j))),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT,
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=plane_bytes
            + (grid[0] * m * k + m * n) * itemsize),
        name="moe_grouped_matmul",
        interpret=interpret,
    )(offsets, group, tile, n_visits, xs, w)


@jax.custom_vjp
def _streamed(xs, w, sizes):
    m = xs.shape[0]
    k, n = w.shape[1:]
    tm = _row_tile(m)
    return _grouped_matmul_pallas(
        xs, w, *_visit_table(sizes, m=m, tm=tm), tm=tm,
        tn=_col_tile(k, n, jnp.dtype(w.dtype).itemsize),
        interpret=not on_tpu())


def _streamed_fwd(xs, w, sizes):
    return _streamed(xs, w, sizes), (xs, w, sizes)


def _streamed_bwd(saved, g):
    # a scalar-prefetch ``pallas_call`` has no reverse-mode rule: the
    # cotangents are ``ragged_dot``'s, which the forward equals
    xs, w, sizes = saved
    _, pull = jax.vjp(lambda a, b: jax.lax.ragged_dot(a, b, sizes), xs, w)
    return (*pull(g), None)


_streamed.defvjp(_streamed_fwd, _streamed_bwd)


def grouped_matmul(xs, w, sizes):
    """``jax.lax.ragged_dot(xs, w, sizes)`` for rows sorted by group:
    ``xs`` [m, K], ``w`` [G, K, N], ``sizes`` [G] int32 -> [m, N], rows
    past ``sum(sizes)`` zero.  Where the gate refuses, the call is
    ``ragged_dot``'s; the decision is counted in
    ``pallas.moe_experts.route``.  Differentiable in ``xs`` and ``w``
    through either body."""
    if not should_use_pallas(xs, w):
        return jax.lax.ragged_dot(xs, w, sizes)
    return _streamed(xs, w, sizes)
