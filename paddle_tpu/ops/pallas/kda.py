"""Gated delta-rule linear attention with a per-channel decay (KDA): the
matrix-valued recurrent state of a linear-attention layer, in the three
forms the serving path needs.

A head keeps a state ``S`` [dk, dv] (key by value, float32).  A token with
query ``q`` (normalised and scaled by the caller), key ``k``, value ``v``,
log-decay ``g`` [dk] (``alpha = exp(g)`` in (0, 1)) and write strength
``beta`` does::

    S' = diag(alpha) S
    S  = S' + beta k (v - S'^T k)^T        # = (I - beta k k^T) S' + beta k v^T
    o  = S^T q

* ``kda_recurrent``: that, a token at a time under ``lax.scan``; the plain
  form the other two are tested against.
* ``kda_chunk``: the chunkwise-parallel form for a prefill chunk.  Inside a
  sub-chunk of ``SUB`` rows the deltas ``u_t = beta_t (v_t - S'_t^T k_t)``
  solve ``(I + diag(beta) tril(A, -1)) U = diag(beta) (V - (K e^G) S_0)``
  with ``A_ts = (k_t e^{G_t}) . (k_s e^{-G_s})`` and ``G`` the log-decay
  cumulated inside the sub-chunk; a ``lax.scan`` carries ``S`` between
  sub-chunks.  The state crosses HBM once a sub-chunk, not once a row.
* ``kda_decode_step``: one token of every slot, the state read from and
  written to the slot-state arena **in place**: on the chip a Pallas kernel
  over (rows, head blocks) whose blocks are addressed by slot through scalar
  prefetch and whose output aliases the arena, so the state crosses HBM once
  in and once out and no second copy of the arena exists; elsewhere a
  ``jnp`` body of the same signature.  ``pallas.kda_decode.route`` counts
  which.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import on_tpu, pallas_enabled, refused_for_partitioning

__all__ = ["kda_recurrent", "kda_chunk", "kda_decode_step",
           "KDA_ROUTE_REASONS"]

HI = jax.lax.Precision.HIGHEST
# Rows of a sub-chunk.  ``A`` is built from two factors decayed from the
# sub-chunk's middle row, ``k e^{G - G_mid}`` and ``k e^{G_mid - G}``, so
# that the exponents span half a sub-chunk: float32 holds them while the
# mean decay of 32 consecutive rows stays above e^-88/32 = 0.064 a row.
SUB = 64

# The closed vocabulary of the ``pallas.kda_decode.route`` counter's
# ``reason`` axis: every string ``_kda_route_reason`` can return (graftlint
# vocab pass).  ``state_ok`` means the kernel dispatched.
KDA_ROUTE_REASONS = (
    "state_ok", "pallas_unavailable", "gspmd_partitioned",
    "state_dtype", "head_align", "lane_align",
)

_HEAD_BLOCK = 16        # heads a grid step: 1 MiB of state in, 1 MiB out
_VMEM_LIMIT = 32 << 20


def _token(S, q, k, v, g, beta):
    """One token of one batch of heads: S [..., dk, dv], q k g [..., dk],
    v [..., dv], beta [...].  Returns (S, o [..., dv])."""
    S = jnp.exp(g)[..., :, None] * S
    u = beta[..., None] * (v - jnp.sum(k[..., :, None] * S, axis=-2))
    S = S + k[..., :, None] * u[..., None, :]
    return S, jnp.sum(q[..., :, None] * S, axis=-2)


def kda_recurrent(q, k, v, g, beta, s0, n_valid=None):
    """Token by token.  q k g [B, T, H, dk], v [B, T, H, dv], beta
    [B, T, H], s0 [B, H, dk, dv]; all float32.  Rows at or past
    ``n_valid[b]`` leave the state as it is.  Returns (o [B, T, H, dv],
    the state after the last valid row)."""
    t = q.shape[1]
    keep = jnp.ones(q.shape[:2], bool) if n_valid is None else \
        jnp.arange(t)[None, :] < n_valid[:, None]

    def body(S, xs):
        qt, kt, vt, gt, bt, on = xs
        Sn, o = _token(S, qt, kt, vt, gt, bt)
        return jnp.where(on[:, None, None, None], Sn, S), o

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta, keep))
    S, o = jax.lax.scan(body, s0, xs)
    return jnp.moveaxis(o, 0, 1), S


def kda_chunk(q, k, v, g, beta, s0, n_valid=None):
    """The chunkwise-parallel form; arguments and result as
    ``kda_recurrent``'s.  ``T`` is padded up to a multiple of ``SUB`` with
    rows that leave the state as it is (``g = 0``, ``beta = 0``), which is
    also how rows at or past ``n_valid`` are taken out: the state returned
    is the one after the last valid row."""
    with jax.named_scope("kda_chunk"):
        b, t, h, dk = q.shape
        dv = v.shape[-1]
        if n_valid is not None:
            keep = jnp.arange(t)[None, :, None] < n_valid[:, None, None]
            g = jnp.where(keep[..., None], g, 0.0)
            beta = jnp.where(keep, beta, 0.0)
        pad = -t % SUB
        if pad:
            q, k, v, g = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                          for a in (q, k, v, g))
            beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
        n = (t + pad) // SUB
        # [B, n, SUB, H, d] -> [n, B, H, SUB, d]: the scan runs over n
        blk = lambda a: jnp.transpose(  # noqa: E731
            a.reshape(b, n, SUB, h, -1), (1, 0, 3, 2, 4))
        q, k, v, g = blk(q), blk(k), blk(v), blk(g)
        beta = blk(beta[..., None])                     # [n, B, H, SUB, 1]
        G = jnp.cumsum(g, axis=-2)                      # inclusive
        mid = G[..., SUB // 2:SUB // 2 + 1, :]
        k_up, k_down = k * jnp.exp(G - mid), k * jnp.exp(mid - G)
        mm = functools.partial(jnp.einsum, precision=HI)
        a_kk = mm("...td,...sd->...ts", k_up, k_down)
        a_qk = mm("...td,...sd->...ts", q * jnp.exp(G - mid), k_down)
        row = jnp.arange(SUB)
        lower = row[:, None] > row[None, :]
        t_mat = jnp.eye(SUB) + jnp.where(lower, beta * a_kk, 0.0)
        a_qk = jnp.where(lower | jnp.eye(SUB, dtype=bool), a_qk, 0.0)
        k_in = k * jnp.exp(G)               # decayed from the sub-chunk's start
        # U = T^-1 diag(beta) (V - K_in S_0): both parts solved here, for
        # every sub-chunk at once; the scan only applies them to its S_0
        solved = jax.scipy.linalg.solve_triangular(
            t_mat, beta * jnp.concatenate([v, k_in], axis=-1), lower=True,
            unit_diagonal=True)
        u_v, u_k = solved[..., :dv], solved[..., dv:]
        q_in = q * jnp.exp(G)
        last = G[..., -1:, :]
        k_out = k * jnp.exp(last - G)       # decayed on to the sub-chunk's end
        decay = jnp.exp(last[..., 0, :])[..., None]     # [n, B, H, dk, 1]

        def body(S, xs):
            u_v, u_k, q_in, a_qk, k_out, decay = xs
            u = u_v - mm("...td,...de->...te", u_k, S)
            o = mm("...td,...de->...te", q_in, S) \
                + mm("...ts,...se->...te", a_qk, u)
            return decay * S + mm("...sd,...se->...de", k_out, u), o

        S, o = jax.lax.scan(body, s0, (u_v, u_k, q_in, a_qk, k_out, decay))
        o = jnp.transpose(o, (1, 0, 3, 2, 4)).reshape(b, n * SUB, h, dv)
        return o[:, :t], S


# -- the decode step: state in place in the slot-state arena ------------------------

def _kda_route_reason(arena, heads):
    """Why the gate routed as it did: one of ``KDA_ROUTE_REASONS``, from
    what the code can observe (platform, partitioning, shapes, dtype)."""
    if not pallas_enabled():
        if refused_for_partitioning():
            return "gspmd_partitioned"
        return "pallas_unavailable"
    if jnp.dtype(arena.dtype) != jnp.float32:
        return "state_dtype"
    if heads % 8:
        return "head_align"         # a head block is transposed in whole tiles
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
            "pallas.kda_decode.route",
            "dispatch decisions of the gated delta-rule decode step (pallas "
            "kernel in place in the state arena vs the jnp body, with the "
            "gating reason)",
            labels=("decision", "reason"))
    return _route_counter_inst


def should_use_pallas(arena, heads) -> bool:
    """The gate, counted once a call at trace time."""
    reason = _kda_route_reason(arena, heads)
    use = reason == "state_ok"
    _route_counter().inc(decision="pallas" if use else "xla", reason=reason)
    return use


def _decode_kernel(rows_ref, live_ref, cols_ref, bv_ref, s_ref, o_ref,
                   s_out_ref, *, hb):
    """One (row, head block): ``cols_ref`` [1, 4, hb, dk] holds alpha, k,
    beta k and q with the key channels in lanes; each is transposed once so
    that a head's vector is a column that broadcasts along the state's value
    lanes.  ``bv_ref`` [1, hb, dv] is beta v.  A row that is not live starts
    from zeros (selected, not multiplied: its block may hold anything)."""
    del rows_ref
    live = live_ref[pl.program_id(0)] != 0
    alpha, k, bk, q = (cols_ref[0, j].T for j in range(4))      # [dk, hb]
    for h in range(hb):
        S = jnp.where(live, s_ref[0, 0, h], 0.0)                # [dk, dv]
        S = alpha[:, h:h + 1] * S
        u = bv_ref[0, h:h + 1, :] - jnp.sum(bk[:, h:h + 1] * S, axis=0,
                                            keepdims=True)
        S = S + k[:, h:h + 1] * u
        s_out_ref[0, 0, h] = S
        o_ref[0, h:h + 1, :] = jnp.sum(q[:, h:h + 1] * S, axis=0,
                                       keepdims=True)


def _decode_pallas(arena, layer, rows, live, cols, bv):
    b, _, h, dk = cols.shape
    dv = bv.shape[-1]
    hb = _HEAD_BLOCK if h % _HEAD_BLOCK == 0 else 8
    state_spec = pl.BlockSpec(
        (1, 1, hb, dk, dv), lambda i, j, rows, live: (rows[i], layer, j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h // hb),
        in_specs=[
            pl.BlockSpec((1, 4, hb, dk), lambda i, j, rows, live: (i, 0, j, 0)),
            pl.BlockSpec((1, hb, dv), lambda i, j, rows, live: (i, j, 0)),
            state_spec],
        out_specs=[
            pl.BlockSpec((1, hb, dv), lambda i, j, rows, live: (i, j, 0)),
            state_spec])
    o, arena = pl.pallas_call(
        functools.partial(_decode_kernel, hb=hb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, h, dv), jnp.float32),
                   jax.ShapeDtypeStruct(arena.shape, arena.dtype)],
        # operands count the two prefetched scalars: the arena is the fifth
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT,
            dimension_semantics=("arbitrary", "arbitrary")),
        name="kda_decode",
        interpret=not on_tpu(),
    )(rows, live.astype(jnp.int32), cols, bv, arena)
    return o, arena


def _decode_jnp(arena, layer, rows, live, cols, bv):
    """The same step on a gathered copy of the rows' states: off the chip,
    under a partitioned program, or where the gate refuses."""
    alpha, k, bk, q = (cols[:, j] for j in range(4))
    S = jnp.where(live[:, None, None, None], arena[rows, layer], 0.0)
    S = alpha[..., :, None] * S
    u = bv - jnp.sum(bk[..., :, None] * S, axis=-2)
    S = S + k[..., :, None] * u[..., None, :]
    return (jnp.sum(q[..., :, None] * S, axis=-2),
            arena.at[rows, layer].set(S))


def _decode_operands(q, k, v, g, beta):
    """What both bodies take: the four key-channel vectors a head stacked
    ([B, 4, H, dk]: alpha, k, beta k, q) and beta v [B, H, dv], float32.
    beta is folded in here: ``u = beta v - (beta k)^T S'``."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    q, k, v, g, beta = f32(q), f32(k), f32(v), f32(g), f32(beta)
    return (jnp.stack([jnp.exp(g), k, beta[..., None] * k, q], axis=1),
            beta[..., None] * v)


def kda_decode_step(arena, layer, rows, live, q, k, v, g, beta):
    """One token of every row against the state arena ``[slots + 1, layers,
    H, dk, dv]`` (float32), in place.  Row ``i`` reads and writes
    ``arena[rows[i], layer]``; a row that is not ``live`` (vacant, frozen,
    stale) starts from zeros and is sent by the caller to the arena's last
    row, the one nobody reads.  q k g [B, H, dk], v [B, H, dv], beta [B, H].
    Returns (o [B, H, dv] float32, the arena)."""
    with jax.named_scope("kda_decode"):
        step = _decode_pallas if should_use_pallas(arena, q.shape[1]) \
            else _decode_jnp
        return step(arena, int(layer), rows.astype(jnp.int32), live,
                    *_decode_operands(q, k, v, g, beta))
