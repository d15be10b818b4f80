"""Mamba-2's selective state-space recurrence (SSD): the per-head matrix
state of a state-space mixer, in the three forms the serving path needs.

A head ``h`` keeps a state ``S`` [P, N] (head channels by state channels,
float32).  A token with input ``x`` [P], step ``dt`` > 0 and decay rate ``A``
< 0 (scalars a head), and ``B``, ``C`` [N] of the head's group (heads
``g * H/G .. (g+1) * H/G - 1`` read group ``g``) does::

    S = exp(dt A) S + dt x B^T
    y = S C + D x

* ``ssd_recurrent``: that, a token at a time under ``lax.scan``; the plain
  form the other two are tested against.
* ``ssd_chunk``: the chunked (SSD) form of a prefill chunk.  Inside a chunk
  of ``chunk`` rows the decays are a segment-sum of ``dt A``: ``y_t =
  sum_{s<=t} exp(a_t - a_s) (C_t . B_s) dt_s x_s + exp(a_t) S_0 C_t`` with
  ``a`` the inclusive cumulative sum, and the state leaves the chunk as
  ``exp(a_last) S_0 + sum_s exp(a_last - a_s) dt_s x_s B_s^T``; every
  exponent is at most 0, so nothing is rescaled.  A ``lax.scan`` carries
  ``S`` between chunks: it crosses HBM once a chunk, not once a row.
* ``ssd_decode_step``: one token of every slot, the state read from and
  written to the slot-state arena **in place**: on the chip a Pallas kernel
  over (rows, head blocks) whose blocks are addressed by slot through scalar
  prefetch and whose output aliases the arena, so the state crosses HBM once
  in and once out and no second copy of the arena exists
  (``kda.kda_decode_step``'s pattern); elsewhere a ``jnp`` body of the same
  signature.  ``pallas.ssd_decode.route`` counts which.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import on_tpu, pallas_enabled, refused_for_partitioning

__all__ = ["ssd_recurrent", "ssd_chunk", "ssd_decode_step",
           "SSD_ROUTE_REASONS"]

HI = jax.lax.Precision.HIGHEST

# The closed vocabulary of the ``pallas.ssd_decode.route`` counter's
# ``reason`` axis: every string ``_ssd_route_reason`` can return (graftlint
# vocab pass).  ``state_ok`` means the kernel dispatched.
SSD_ROUTE_REASONS = (
    "state_ok", "pallas_unavailable", "gspmd_partitioned",
    "state_dtype", "head_align", "lane_align",
)

_HEAD_BLOCK = 8         # heads a grid step: 1 MiB of state in, 1 MiB out
_VMEM_LIMIT = 32 << 20


def _f32(*arrays):
    return tuple(a.astype(jnp.float32) for a in arrays)


def _heads(v, heads):
    """[..., G, N] of the groups -> [..., heads, N]: head ``h`` reads group
    ``h // (heads / G)``."""
    return jnp.repeat(v, heads // v.shape[-2], axis=-2)


def ssd_recurrent(x, B, C, dt, A, D, s0, n_valid=None):
    """Token by token.  x [Bt, T, H, P], B C [Bt, T, G, N], dt [Bt, T, H]
    (after the softplus), A D [H], s0 [Bt, H, P, N].  Rows at or past
    ``n_valid[b]`` leave the state as it is.  Returns (y [Bt, T, H, P] float32,
    the state after the last valid row)."""
    x, B, C, dt, A, D, s0 = _f32(x, B, C, dt, A, D, s0)
    h, t = x.shape[2], x.shape[1]
    keep = jnp.ones(x.shape[:2], bool) if n_valid is None else \
        jnp.arange(t)[None, :] < n_valid[:, None]

    def body(S, xs):
        xt, bt, ct, dtt, on = xs
        Sn = jnp.exp(dtt * A)[..., None, None] * S \
            + (dtt[..., None] * xt)[..., :, None] * _heads(bt, h)[..., None, :]
        S = jnp.where(on[:, None, None, None], Sn, S)
        y = jnp.sum(Sn * _heads(ct, h)[..., None, :], axis=-1) \
            + D[:, None] * xt
        return S, y

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (x, B, C, dt, keep))
    S, y = jax.lax.scan(body, s0, xs)
    return jnp.moveaxis(y, 0, 1), S


def ssd_chunk(x, B, C, dt, A, D, s0, n_valid=None, chunk=128):
    """The chunked form; arguments and result as ``ssd_recurrent``'s.  ``T``
    is padded up to a multiple of ``chunk`` with rows whose ``dt`` is 0,
    which leave the state as it is; rows at or past ``n_valid`` are taken
    out the same way, so the state returned is the one after the last valid
    row."""
    with jax.named_scope("ssd_chunk"):
        x, B, C, dt, A, D, s0 = _f32(x, B, C, dt, A, D, s0)
        b, t, h, p = x.shape
        g, n = B.shape[2], B.shape[3]
        if n_valid is not None:
            dt = jnp.where(jnp.arange(t)[None, :, None]
                           < n_valid[:, None, None], dt, 0.0)
        pad = -t % chunk
        if pad:
            x, B, C = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                       for a in (x, B, C))
            dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        nc, hg = (t + pad) // chunk, h // g
        # [B, T, K, d] -> [nc, B, K, chunk, d]: the scan runs over nc
        blk = lambda a: jnp.transpose(  # noqa: E731
            a.reshape(b, nc, chunk, a.shape[2], -1), (1, 0, 3, 2, 4))
        xdt = blk(x * dt[..., None])                    # [nc, B, H, Q, P]
        Bc, Cc = blk(B), blk(C)                         # [nc, B, G, Q, N]
        a = jnp.cumsum(blk(dt[..., None] * A[:, None])[..., 0], axis=-1)
        row = jnp.arange(chunk)
        lower = row[:, None] >= row[None, :]
        seg = jnp.exp(jnp.where(lower, a[..., :, None] - a[..., None, :],
                                -jnp.inf))              # [nc, B, H, Q, Q]
        mm = functools.partial(jnp.einsum, precision=HI)
        cb = mm("...gtn,...gsn->...gts", Cc, Bc)        # [nc, B, G, Q, Q]
        grp = lambda v: v.reshape(v.shape[:2] + (g, hg) + v.shape[3:])  # noqa: E731
        y_in = mm("...ghts,...ghsp->...ghtp", grp(seg) * cb[:, :, :, None],
                  grp(xdt))                             # [nc, B, G, hg, Q, P]
        last = a[..., -1:]
        # what each chunk writes into the state, decayed on to its end
        written = mm("...ghsp,...gsn->...ghpn",
                     grp(xdt * jnp.exp(last - a)[..., None]), Bc)
        decay_in = grp(jnp.exp(a))[..., None]           # [nc, B, G, hg, Q, 1]
        decay_all = grp(jnp.exp(last))[..., None]           # [.., hg, 1, 1]

        def body(S, xs):
            c_c, d_in, w_c, d_all = xs
            y_from = mm("bgtn,bghpn->bghtp", c_c, S) * d_in
            return d_all * S + w_c, y_from

        S, y_from = jax.lax.scan(body, grp(s0[None])[0],
                                 (Cc, decay_in, written, decay_all))
        y = (y_in + y_from).reshape(nc, b, h, chunk, p)
        y = jnp.transpose(y, (1, 0, 3, 2, 4)).reshape(b, nc * chunk, h, p)
        y = y[:, :t] + D[:, None] * x[:, :t]
        return y, S.reshape(b, h, p, n)


# -- the decode step: state in place in the slot-state arena ------------------------

def _ssd_route_reason(arena, heads, groups):
    """Why the gate routed as it did: one of ``SSD_ROUTE_REASONS``, from
    what the code can observe (platform, partitioning, shapes, dtype)."""
    if not pallas_enabled():
        if refused_for_partitioning():
            return "gspmd_partitioned"
        return "pallas_unavailable"
    if jnp.dtype(arena.dtype) != jnp.float32:
        return "state_dtype"
    if (heads // groups) % _HEAD_BLOCK:
        # a head block is transposed in whole tiles and reads one group
        return "head_align"
    if arena.shape[-1] % 128 or arena.shape[-2] % 128:
        return "lane_align"
    return "state_ok"


_route_counter_inst = None


def _route_counter():
    # the process-default registry, resolved once (as
    # ``decode_attention._route_counter``)
    global _route_counter_inst
    if _route_counter_inst is None:
        from ...observability import metrics as _obs
        _route_counter_inst = _obs.get_registry().counter(
            "pallas.ssd_decode.route",
            "dispatch decisions of the state-space (SSD) decode step (pallas "
            "kernel in place in the state arena vs the jnp body, with the "
            "gating reason)",
            labels=("decision", "reason"))
    return _route_counter_inst


def should_use_pallas(arena, heads, groups) -> bool:
    """The gate, counted once a call at trace time."""
    reason = _ssd_route_reason(arena, heads, groups)
    use = reason == "state_ok"
    _route_counter().inc(decision="pallas" if use else "xla", reason=reason)
    return use


def _decode_kernel(rows_ref, live_ref, xa_ref, bc_ref, s_ref, y_ref,
                   s_out_ref, *, hb):
    """One (row, head block): ``xa_ref`` [1, 2, hb, P] holds ``dt x`` and the
    decay ``exp(dt A)`` (broadcast along P) with the head channels in lanes;
    each is transposed once so that a head's vector is a column that
    broadcasts along the state's lanes.  ``bc_ref`` [1, 1, 2, N] is the head
    block's group's B and C.  A row that is not live starts from zeros
    (selected, not multiplied: its block may hold anything)."""
    del rows_ref
    live = live_ref[pl.program_id(0)] != 0
    xdt, decay = xa_ref[0, 0].T, xa_ref[0, 1].T                 # [P, hb]
    b_row, c_row = bc_ref[0, 0, 0:1, :], bc_ref[0, 0, 1:2, :]   # [1, N]
    lane = jax.lax.broadcasted_iota(jnp.int32, xdt.shape, 1)
    y = jnp.zeros(xdt.shape, jnp.float32)
    for h in range(hb):
        S = jnp.where(live, s_ref[0, 0, h], 0.0)                # [P, N]
        S = decay[:, h:h + 1] * S + xdt[:, h:h + 1] * b_row
        s_out_ref[0, 0, h] = S
        y = jnp.where(lane == h, jnp.sum(S * c_row, axis=1, keepdims=True),
                      y)
    y_ref[0] = y.T


def _decode_pallas(arena, layer, rows, live, xa, bc):
    b, _, h, p = xa.shape
    hg = h // bc.shape[1]
    hb = _HEAD_BLOCK
    state_spec = pl.BlockSpec(
        (1, 1, hb) + arena.shape[-2:],
        lambda i, j, rows, live: (rows[i], layer, j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h // hb),
        in_specs=[
            pl.BlockSpec((1, 2, hb, p), lambda i, j, rows, live: (i, 0, j, 0)),
            pl.BlockSpec((1, 1) + bc.shape[2:],
                         lambda i, j, rows, live: (i, j * hb // hg, 0, 0)),
            state_spec],
        out_specs=[
            pl.BlockSpec((1, hb, p), lambda i, j, rows, live: (i, j, 0)),
            state_spec])
    y, arena = pl.pallas_call(
        functools.partial(_decode_kernel, hb=hb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, h, p), jnp.float32),
                   jax.ShapeDtypeStruct(arena.shape, arena.dtype)],
        # operands count the two prefetched scalars: the arena is the fifth
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT,
            dimension_semantics=("arbitrary", "arbitrary")),
        name="ssd_decode",
        interpret=not on_tpu(),
    )(rows, live.astype(jnp.int32), xa, bc, arena)
    return y, arena


def _decode_jnp(arena, layer, rows, live, xa, bc):
    """The same step on a gathered copy of the rows' states: off the chip,
    under a partitioned program, or where the gate refuses."""
    h = xa.shape[2]
    xdt, decay = xa[:, 0], xa[:, 1, :, :1]
    B, C = _heads(bc[:, :, 0], h), _heads(bc[:, :, 1], h)       # [b, H, N]
    S = jnp.where(live[:, None, None, None], arena[rows, layer], 0.0)
    S = decay[..., None] * S + xdt[..., :, None] * B[:, :, None, :]
    return (jnp.sum(S * C[:, :, None, :], axis=-1),
            arena.at[rows, layer].set(S))


def ssd_decode_step(arena, layer, rows, live, x, B, C, dt, A, D):
    """One token of every row against the state arena ``[slots + 1, layers,
    H, P, N]`` (float32), in place.  Row ``i`` reads and writes
    ``arena[rows[i], layer]``; a row that is not ``live`` (vacant, frozen,
    stale) starts from zeros and is sent by the caller to the arena's last
    row, the one nobody reads.  x [B, H, P], B C [B, G, N], dt [B, H], A D
    [H].  Returns (y [B, H, P] float32, the arena)."""
    with jax.named_scope("ssd_decode"):
        x, B, C, dt, A, D = _f32(x, B, C, dt, A, D)
        h, p = x.shape[1:]
        # what both bodies take: ``dt x`` and the decay a head broadcast
        # along P ([B, 2, H, P]), and each group's B and C ([B, G, 2, N])
        xa = jnp.stack([dt[..., None] * x, jnp.broadcast_to(
            jnp.exp(dt * A)[..., None], x.shape)], axis=1)
        bc = jnp.stack([B, C], axis=2)
        step = _decode_pallas if should_use_pallas(arena, h, B.shape[1]) \
            else _decode_jnp
        y, arena = step(arena, int(layer), rows.astype(jnp.int32), live,
                        xa, bc)
        return y + D[:, None] * x, arena
