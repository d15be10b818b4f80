"""Autoregressive generation over a static KV cache — the TPU-native
decode-serving engine.

Capability analogue of the reference's fused decode stack:
``paddle/fluid/operators/fused/fused_multi_transformer_op.cu`` (cached-KV
transformer decode) layered over ``masked_multihead_attention`` (single
decode step; our tested functional lives in
``incubate/nn/functional/__init__.py``) and PaddleNLP's ``generate()``
loop.  TPU-first design decisions:

- The WHOLE generation (prefill + every decode step) is one compiled
  XLA call: ``lax.scan`` over the step body with a static step count.
  One dispatch per request instead of one per token — a per-token
  dispatch pays the host's dispatch cost every step, next to a
  weight-streaming step of a few ms at serving batch 1.
- The KV cache is a static-shape ``[B, max_cache_len, H_kv*D]`` ring
  of slots per layer (all heads of a slot contiguous in lanes — tile-
  aligned at rest, one contiguous DMA per prefix chunk); new tokens
  land via batched row scatter and validity masking hides unwritten
  slots — the static-shape formulation of the reference's in-place
  growing cache (its mmha kernel writes at ``sequence_lengths`` the
  same way).  Decode attention streams ONLY the valid prefix
  (ops/pallas/decode_attention.py).
- Float params are cast to the serving compute dtype ONCE per call,
  outside the scan: XLA materializes an optimally-tiled bf16 copy that
  streams at the measured ~975 GB/s, vs ~340 GB/s for bf16-stored
  arrays (v5e layout trap, BASELINE.md) — and the scan body then reads
  the fast copy every step.
- Decode attention is GQA-aware grouped einsum with fp32 softmax; the
  per-step HBM cost is exactly one cache sweep, which together with one
  weight sweep is the decode roofline: tokens/s ~= HBM_BW /
  (param_bytes/B + kv_bytes_per_token).

Greedy and sampled decoding (temperature / top-k) with EOS tracking are
supported; the compiled program is cached per (shape, option) bucket.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..core import tape as _tape


def _unwrap(x):
    return x._value if isinstance(x, Tensor) else jnp.asarray(x)


@dataclass(frozen=True)
class GenerationConfig:
    """Static (trace-time) generation options.

    Reference analogue: PaddleNLP ``GenerationConfig`` feeding the
    fused_multi_transformer serving path; every field here is a compile
    -time constant of the exported program.
    """
    max_new_tokens: int = 32
    do_sample: bool = False
    temperature: float = 1.0
    top_k: int = 0                   # 0 = full softmax
    top_p: float = 1.0               # nucleus sampling; 1.0 = off
    num_beams: int = 1               # >1 = beam search (greedy scoring)
    length_penalty: float = 0.0      # beam score /= len**alpha at selection
    eos_token_id: Optional[int] = None
    pad_token_id: int = 0
    compute_dtype: str = "bfloat16"  # serving precision; params cast once
    cache_dtype: Optional[str] = None  # default: compute_dtype


def init_kv_cache(num_layers, batch, max_cache_len, num_kv_heads, head_dim,
                  dtype):
    """Per-layer (k, v) static slot buffers.  Packed ``[B, S, H_kv*D]``
    (all heads of one slot contiguous in lanes) when the head geometry
    allows, else plain [B, S, H_kv, D]
    (ops/pallas/decode_attention.cache_shape).

    Round-5 layout: a trailing D=64 dim lane-pads every row at rest
    (TPU arrays tile to (sublane, 128)) — 2x HBM and half-rate
    streaming (~373 GB/s measured in-model).  The packed form is
    exactly tile-aligned, keeps the decode scatter a plain row scatter,
    and lets the flash-decode kernel stream ONLY the valid prefix in
    contiguous chunks.
    """
    from ..ops.pallas.decode_attention import cache_shape
    shape = cache_shape(batch, num_kv_heads, max_cache_len, head_dim)
    return [(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
            for _ in range(num_layers)]


def cache_scatter(cache, lens, new_kv):
    """Write one new [B, H_kv, D] entry at each sequence's slot
    (row ``lens[b]`` of the packed [B, S, W] cache — one contiguous
    W-lane row per sequence; [B, S, H, D] fallback caches take the
    same row write unreshaped).

    Batched scatter (not a one-hot multiply): touches only the written
    rows, so the per-step write cost is O(B*H_kv*D) instead of a full
    cache rewrite — the decode loop's HBM budget is spent on the READ
    sweep only.
    """
    b = cache.shape[0]
    if cache.ndim == 3:
        new_kv = new_kv.reshape(b, -1)
    return cache.at[jnp.arange(b), lens].set(new_kv.astype(cache.dtype))


def init_paged_kv_arena(num_layers, num_blocks, block_len, num_kv_heads,
                        head_dim, dtype):
    """Per-layer (k, v) PAGED block arenas for the serving engine: one
    ``[num_blocks + 1, block_len, ...]`` pool per layer
    (ops/pallas/decode_attention.paged_arena_shape), shared by every
    slot through per-slot block tables.  The extra trailing row is the
    TRASH block: statically-shaped scatters from vacant/frozen slots
    and from pad positions of a prefill chunk are redirected there, so
    a masked write can never touch another sequence's blocks.  Zero
    init matters only for the trash/never-written rows: reads past a
    row's ``lens`` are masked to weight 0, which is exact only against
    finite stale data (0 * NaN = NaN).

    ``dtype="int8"`` selects the QUANTIZED cache: each layer yields a
    4-tuple ``(k_codes, v_codes, k_scales, v_scales)`` — int8 code
    arenas plus parallel ``[num_blocks + 1, block_len, H_kv]`` f32
    absmax-scale arenas (``quantize_kv_heads``); every other dtype
    yields the plain (k, v) pair."""
    from ..ops.pallas.decode_attention import (paged_arena_shape,
                                               paged_scale_shape)
    shape = paged_arena_shape(num_blocks + 1, num_kv_heads, block_len,
                              head_dim)
    if jnp.dtype(dtype) == jnp.int8:
        # quantized arenas carry parallel per-entry per-kv-head absmax
        # scale planes (quantize_kv_heads); the trash row exists in the
        # scale arenas too, for the same masked-write reason.  f32
        # scales: a bf16 scale would stack ~0.4% scale error on top of
        # the int8 step, and the scale planes are 4/D of the codes'
        # bytes — not worth the precision trade.
        sshape = paged_scale_shape(num_blocks + 1, num_kv_heads,
                                   block_len)
        return [(jnp.zeros(shape, jnp.int8), jnp.zeros(shape, jnp.int8),
                 jnp.zeros(sshape, jnp.float32),
                 jnp.zeros(sshape, jnp.float32))
                for _ in range(num_layers)]
    return [(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
            for _ in range(num_layers)]


class LatentCacheSpec(NamedTuple):
    """What ``kv_cache_spec()`` answers for a model whose cached layers keep
    ONE row of ``row`` values a token (a compressed KV row with the shared
    positional key behind it), not keys and values of ``H_kv x D``: one
    arena a layer, and a layer's paged entry is ``(arena, tables)``."""
    layers: int
    row: int


def init_paged_latent_arena(num_layers, num_blocks, block_len, row, dtype):
    """Per-layer ``(arena,)`` of a latent paged cache: one
    ``[num_blocks + 1, block_len, row padded to lanes]`` pool a layer
    (``ops/pallas/decode_attention.paged_latent_shape``), trash block last
    and zero-initialised as ``init_paged_kv_arena``'s are."""
    from ..ops.pallas.decode_attention import paged_latent_shape
    shape = paged_latent_shape(num_blocks + 1, block_len, row)
    return [(jnp.zeros(shape, dtype),) for _ in range(num_layers)]


class SlotStateError(NotImplementedError):
    """A feature that shares, moves or rewinds KV blocks was asked of a model
    that keeps per-slot state beside them (``slot_state_spec``), which the
    feature cannot carry: served tokens would come from a stale or zero
    state."""

    def __init__(self, model, feature):
        names = ", ".join(entry[0] for entry in slot_state_spec(model))
        super().__init__(
            f"{feature} cannot carry the per-slot state ({names}) that "
            f"{type(model).__name__} keeps beside its paged KV")


def slot_state_spec(model):
    """``[(name, shape a slot)]`` of what a slot of ``model`` keeps beside
    its blocks, an entry that is not of the compute dtype as ``(name, shape,
    dtype)``; empty for a model whose only state is keys and values."""
    spec = getattr(model, "slot_state_spec", None)
    return list(spec()) if spec is not None else []


def init_slot_state(spec, num_slots, dtype):
    """One arena a spec entry, ``[num_slots + 1, *shape]``: row ``s`` is slot
    ``s``'s state, and the extra last row takes the masked writes of vacant
    and frozen rows, as the trash block does for keys and values.  An entry
    is of ``dtype`` unless it names its own (a float32 recurrent state
    beside bfloat16 convolution tails)."""
    return [jnp.zeros((num_slots + 1,) + tuple(entry[1]),
                      entry[2] if len(entry) > 2 else dtype)
            for entry in spec]


class StateRows(NamedTuple):
    """Where a decode step finds its rows' matrix states in place: row ``i``
    is ``arena[rows[i], layer]``, from zeros where not ``live`` (the arena of
    a recurrent model's ``slot_state_spec`` entry, ``[slots + 1, layers,
    ...]`` float32; a row that is not live is sent to its last row)."""
    arena: jax.Array
    layer: int
    rows: jax.Array         # [B] int32
    live: jax.Array         # [B] bool


def poison_first_rows(arenas, finished):
    """``poison_slot_state`` of a recurrent model whose slot keeps ``[tails,
    states]``, the states a matrix a head (``[slots + 1, layers, heads, rows,
    lanes]``, float32): the tails whole, the states in their first row a
    head, which the next read of the matrix carries into every output.  A
    whole state is megabytes a slot; a row is its lanes a head."""
    from ..inference.llm import _poison_rows
    tails, states = arenas
    b = finished.shape[0]
    first = states[:b, :, :, 0]
    return [_poison_rows(tails, finished),
            states.at[:b, :, :, 0].set(jnp.where(
                finished[:, None, None, None], jnp.nan, first))]


def generate_by_forward(forward, input_ids, seq_lens=None, max_new_tokens=32):
    """Greedy tokens [B, max_new_tokens] after the (right-padded) prompts, by
    a whole-sequence ``forward(ids [B, S]) -> logits [B, S, V]`` over a buffer
    that grows a token a step: no cache and no state to carry, so it is the
    plain answer an engine's tokens are compared with, at a toy size.  The
    operators must be causal: what lies past a row's end cannot reach the
    logits of its last position."""
    ids = jnp.asarray(getattr(input_ids, "_value", input_ids), jnp.int32)
    b, s = ids.shape
    lens = jnp.full((b,), s, jnp.int32) if seq_lens is None else \
        jnp.asarray(getattr(seq_lens, "_value", seq_lens), jnp.int32)
    buf = jnp.concatenate(
        [ids, jnp.zeros((b, int(max_new_tokens)), jnp.int32)], axis=1)
    rows = jnp.arange(b)
    step = jax.jit(forward)
    out = []
    for i in range(int(max_new_tokens)):
        nxt = jnp.argmax(step(buf)[rows, lens + i - 1], axis=-1)
        out.append(nxt.astype(jnp.int32))
        buf = buf.at[rows, lens + i].set(out[-1])
    return Tensor(jnp.stack(out, axis=1))


def quantize_kv_heads(kv):
    """Per-entry per-kv-head absmax int8 quantization of K/V planes.

    ``kv`` is any ``[..., H_kv, D]`` stack of head vectors; returns
    ``(codes int8 [..., H_kv, D], scales f32 [..., H_kv])`` with
    ``codes * scales[..., None] ~= kv``.  The scale granularity is the
    quantization design decision of the int8 KV cache (notes.md has the
    full rationale): one absmax scale per WRITTEN ENTRY per kv head —
    every append quantizes exactly what it writes and nothing else, so
    writers stay pure scatters (no read-modify-requantize of
    neighbouring block rows) and a value's dequantized form never
    changes after its write (prefix-cached blocks stay bit-identical,
    spec-decode rewind leaves no requantization residue).  absmax is
    clamped so an all-zero plane (pad tails, zero-init rows) yields a
    tiny finite scale, codes 0 and an exact dequant of 0."""
    f = kv.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(f), axis=-1)
    scales = jnp.maximum(absmax, 1e-8) / 127.0
    codes = jnp.clip(jnp.round(f / scales[..., None]), -127, 127)
    return codes.astype(jnp.int8), scales


def _paged_decode_route(arena, tables, lens):
    """(blk, off) arena coordinates for one [B] decode append at slot
    ``lens[b]``: arena row ``tables[b, lens[b] // L]``, offset
    ``lens[b] % L``.  The SINGLE source of the decode trash-routing
    index math — both the code-arena scatter and its ``_q`` scale-plane
    twin route through here, so the two planes can never desynchronize
    (the arena argument only supplies ``shape[1] == L``; code and scale
    arenas agree on it)."""
    b = tables.shape[0]
    block_len = arena.shape[1]
    blk = tables[jnp.arange(b), lens // block_len]
    off = lens % block_len
    return blk, off


def paged_cache_scatter(arena, tables, lens, new_kv):
    """Write one new [B, H_kv, D] decode entry at each sequence's slot
    ``lens[b]``, routed through its block table
    (``_paged_decode_route``).  Vacant and frozen rows carry all-trash
    tables, so their (repeated) writes land in the trash block instead
    of a block another sequence may now own — the paged replacement for
    the dense engine's "done rows overwrite their own dead row"
    contract.  Same O(B*H_kv*D) batched-scatter cost as
    ``cache_scatter``."""
    with jax.named_scope("kv_append"):
        blk, off = _paged_decode_route(arena, tables, lens)
        if arena.ndim == 3:
            new_kv = new_kv.reshape(tables.shape[0], -1)
        return arena.at[blk, off].set(new_kv.astype(arena.dtype))


def paged_cache_scatter_q(arena, scales, tables, lens, new_kv):
    """Quantize-on-append twin of ``paged_cache_scatter`` for the int8
    cache: the new [B, H_kv, D] entry is absmax-quantized per kv head
    (``quantize_kv_heads``) and its codes + scales are scattered through
    the block table with the SAME trash-routing discipline (vacant/
    frozen rows carry all-trash tables, so both planes of a masked
    write land in the trash row).  Returns ``(arena, scales)``."""
    with jax.named_scope("kv_append"):
        codes, s = quantize_kv_heads(new_kv)
        arena = paged_cache_scatter(arena, tables, lens, codes)
        blk, off = _paged_decode_route(arena, tables, lens)
        return arena, scales.at[blk, off].set(s)


def _paged_span_route(arena, tables, start, stop, c):
    """(blk, off) [B, c] arena coordinates of each row's ``c`` consecutive
    positions ``start[b] .. start[b]+c-1`` through its table; a position
    at or past ``stop[b]`` routes to the trash row.  The SINGLE source of
    the row form's trash-routing index math, shared by the code arenas
    and the ``_q`` scale planes."""
    block_len = arena.shape[1]
    trash = arena.shape[0] - 1
    pos = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    idx = jnp.minimum(pos // block_len, tables.shape[1] - 1)
    blk = jnp.where(pos < stop[:, None],
                    jnp.take_along_axis(tables, idx, axis=1), trash)
    return blk, pos % block_len


def _paged_span_blocks(arena, tables, start, stop, new):
    """The block form of a span write: ``(blk [B * nb], slabs [B * nb, L,
    ...])``, each row's ``nb = C // L + 1`` destination blocks from the one
    holding ``start[b]`` on, and what each is to hold whole.  A slab holds
    the new rows at ``[start, min(start + C, stop))`` and the block's own
    rows elsewhere (the rows before ``start`` in the first block, those at
    or past ``stop`` in the last one written): only those two blocks are
    read, every one between is new rows throughout.  A block that gets no
    new row, or whose table index lies past the table (clamped, so it
    would repeat a block), is sent to the trash block: no two slabs name
    one live block."""
    b, c = new.shape[:2]
    block_len, trash = arena.shape[1], arena.shape[0] - 1
    nb, rest = c // block_len + 1, arena.shape[2:]
    first = start // block_len
    j = first[:, None] + jnp.arange(nb, dtype=jnp.int32)[None, :]
    pos = j[:, :, None] * block_len + jnp.arange(block_len, dtype=jnp.int32)
    hi = jnp.minimum(start + c, stop)
    fresh = (pos >= start[:, None, None]) & (pos < hi[:, None, None])
    live = fresh.any(axis=-1) & (j < tables.shape[1])
    blk = jnp.where(live, jnp.take_along_axis(
        tables, jnp.minimum(j, tables.shape[1] - 1), axis=1), trash)
    # the new rows laid on block boundaries: row r of slab k is position
    # (first + k) * L + r, new row k * L + r - (start - first * L); a slice
    # of the rows padded by a block each side, which fuses with the merge
    padded = jnp.pad(new, ((0, 0), (block_len, block_len))
                     + ((0, 0),) * len(rest))
    at = block_len - start + first * block_len
    lay = jax.vmap(lambda row, i: jax.lax.dynamic_slice_in_dim(
        row, i, nb * block_len))(padded, at)
    lay = lay.reshape((b, nb, block_len) + rest)
    last = jnp.clip((hi - 1) // block_len - first, 0, nb - 1)
    edge = arena[jnp.stack([blk[:, 0], blk[jnp.arange(b), last]], axis=1)]
    tail = (1,) * len(rest)
    old = jnp.where((jnp.arange(nb) == 0).reshape((1, nb, 1) + tail),
                    edge[:, :1], edge[:, 1:])
    slabs = jnp.where(fresh.reshape(fresh.shape + tail), lay, old)
    return blk.reshape(-1), slabs.reshape((b * nb, block_len) + rest)


def paged_span_write(arena, tables, start, stop, new):
    """Write ``new`` [B, C, ...] (trailing dims reshaped to the arena's
    row) at each row's positions ``start[b] .. start[b]+C-1`` through its
    table; positions at or past ``stop[b]`` are left as they are (the pad
    tail of a prompt's last chunk, a verify row's draft pad).

    Where the span covers whole blocks' worth of positions (``C`` a
    multiple of the block length) the write goes a BLOCK at a time
    (``_paged_span_blocks``, then one scatter of whole slabs): a block is
    whole tiles in one contiguous run, while a row of a wide arena fills
    one sublane of each of its tiles, and a row scatter pays a partial
    write a tile.  Otherwise (a decode or verify step's few columns) a
    row scatter, positions past ``stop`` sent to the trash row.  Every
    block but the trash block ends the same either way.  The shape is
    the only selector."""
    b, c = new.shape[:2]
    new = new.reshape((b, c) + arena.shape[2:]).astype(arena.dtype)
    if c % arena.shape[1] == 0:
        blk, slabs = _paged_span_blocks(arena, tables, start, stop, new)
        return arena.at[blk].set(slabs)
    blk, off = _paged_span_route(arena, tables, start, stop, c)
    return arena.at[blk, off].set(new)


def paged_chunk_scatter(arena, tables, start, n_valid, new_kv):
    """Write a batch-1 prefill chunk's K/V planes ([C, H_kv, D]) at
    global positions ``start .. start+C-1`` through the slot's block
    table (``tables`` is [1, max_blocks]).  Positions ``>= n_valid``
    (the pad tail of the prompt's last chunk) are not written: the chunk
    shape is static, and masking is done by redirecting or merging,
    never by shrinking the shape (``paged_span_write``)."""
    with jax.named_scope("kv_append"):
        return paged_span_write(arena, tables, jnp.reshape(start, (1,)),
                                jnp.reshape(n_valid, (1,)), new_kv[None])


def paged_chunk_scatter_q(arena, scales, tables, start, n_valid, new_kv):
    """Quantize-on-append twin of ``paged_chunk_scatter``: the chunk's
    [C, H_kv, D] planes quantize per position per kv head and both
    codes and scales are written through the table by the same rule,
    pad-tail positions (``>= n_valid``) left out of BOTH arenas.  Returns
    ``(arena, scales)``."""
    with jax.named_scope("kv_append"):
        codes, s = quantize_kv_heads(new_kv)
        start, stop = jnp.reshape(start, (1,)), jnp.reshape(n_valid, (1,))
        return (paged_span_write(arena, tables, start, stop, codes[None]),
                paged_span_write(scales, tables, start, stop, s[None]))


def paged_verify_scatter(arena, tables, lens, n_valid, new_kv):
    """Write a speculative verify forward's K/V planes ([B, C, H_kv, D])
    at per-row global positions ``lens[b] .. lens[b]+C-1`` through each
    row's block table — the batched generalization of
    ``paged_chunk_scatter`` (that one is batch-1 with a shared start;
    this one is per-row starts over the decode mix; the recurrent
    families' chunk and decode steps write through it too).  Columns
    ``>= n_valid[b]`` (draft-pad tail, rows not in spec mode this step)
    are not written (``paged_span_write``).  The ``n_valid`` mask is
    also the rollback guarantee's other half: a draft position can only
    ever land inside its own row's blocks at a slot ``> lens`` that the
    row itself overwrites before its ``lens`` advances past it, so a
    rejected draft's K/V is finite garbage behind the ``lens`` mask,
    never another sequence's data."""
    with jax.named_scope("kv_append"):
        return paged_span_write(arena, tables, lens, lens + n_valid, new_kv)


def paged_verify_scatter_q(arena, scales, tables, lens, n_valid, new_kv):
    """Quantize-on-append twin of ``paged_verify_scatter``: the verify
    forward's [B, C, H_kv, D] planes quantize per position per kv head;
    codes and scales are written with the same per-row mask (columns
    ``>= n_valid[b]``), so the rollback guarantee carries over to both
    planes — a rejected draft's codes AND its scales are finite garbage
    behind the ``lens`` mask, overwritten before lens reaches them.
    Returns ``(arena, scales)``."""
    with jax.named_scope("kv_append"):
        codes, s = quantize_kv_heads(new_kv)
        stop = lens + n_valid
        return (paged_span_write(arena, tables, lens, stop, codes),
                paged_span_write(scales, tables, lens, stop, s))


def cache_prefill_write(cache, kv_bshd):
    """Write prompt K/V planes ([B, S, H_kv, D] as produced by the
    prefill attention) into the cache from slot 0."""
    kv = kv_bshd.astype(cache.dtype)
    if cache.ndim == 3:
        b, s = kv.shape[0], kv.shape[1]
        kv = kv.reshape(b, s, -1)
        return jax.lax.dynamic_update_slice(cache, kv, (0, 0, 0))
    return jax.lax.dynamic_update_slice(cache, kv, (0, 0, 0, 0))


def cached_decode_attention(q, k_cache, v_cache, lens):
    """One-token GQA attention over the valid cache prefix.

    q: [B, H_q, D]; k_cache/v_cache: packed [B, S_max, H_kv*D] (or the
    [B, S_max, H_kv, D] fallback for odd geometries); lens: [B] =
    index of the LAST valid slot (the just-written token) — slots
    ``<= lens`` participate.  fp32 logits/softmax accumulation on the
    MXU, output in q.dtype.  On TPU this routes to the fused
    flash-decode Pallas kernel (ops/pallas/decode_attention.py — one
    pass over the cache, prefix-aware streaming; the reference
    ``masked_multihead_attention`` / ``fused_multi_transformer_op.cu``
    role), with an XLA einsum fallback elsewhere.
    """
    from ..ops.pallas.decode_attention import decode_attention
    return decode_attention(q, k_cache, v_cache, lens)


def filter_top_k_top_p(lg, top_k, top_p):
    """Per-row temperature-scaled-logits filtering: dynamic top-k
    (``top_k[b] <= 0`` keeps everything) then nucleus top-p on the
    top-k-filtered distribution (``top_p[b] = 1`` keeps everything).
    One descending sort serves both: each filter keeps a PREFIX of
    sorted order, so the cut is a per-row threshold logit and ties at
    the threshold are kept (the standard over-inclusive tie rule).

    The single implementation of the nucleus prefix/tie rule — the
    whole-batch ``sample_token`` config and the serving engine's
    per-request planes (``inference/sampling.py``) both call it, so
    ``generate()`` and ``ServingEngine`` can never drift apart on
    top-k/top-p semantics."""
    v = lg.shape[-1]
    srt = jnp.sort(lg, axis=-1)[..., ::-1]
    j = jnp.arange(v)
    keep_k = (top_k[..., None] <= 0) | (j < top_k[..., None])
    probs = jax.nn.softmax(jnp.where(keep_k, srt, -jnp.inf), axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # smallest prefix with cumulative mass >= p; position 0 always kept
    keep = keep_k & ((cum - probs) < top_p[..., None])
    nkeep = jnp.maximum(keep.sum(-1), 1)
    kth = jnp.take_along_axis(srt, (nkeep - 1)[..., None], axis=-1)
    return jnp.where(lg < kth, -jnp.inf, lg)


def sample_token(logits, key, cfg: GenerationConfig):
    """Greedy argmax or temperature/top-k/top-p categorical.
    logits: [B, V].  Filter order is the conventional warp sequence
    (temperature, then top-k, then nucleus top-p over the already
    top-k-filtered distribution) via :func:`filter_top_k_top_p` with
    the static config broadcast to per-row planes; per-REQUEST planes
    live in ``inference/sampling.py`` — this is the static whole-batch
    config of ``generate()`` / ``LLMPredictor``."""
    if not cfg.do_sample:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    lg = logits.astype(jnp.float32) / jnp.maximum(cfg.temperature, 1e-6)
    if cfg.top_k and cfg.top_k > 0 and cfg.top_p >= 1.0:
        # pure top-k keeps the cheap lax.top_k threshold (same
        # keep-ties-at-kth rule as the full filter, without its
        # whole-vocab sort)
        kth = jax.lax.top_k(lg, cfg.top_k)[0][..., -1:]
        lg = jnp.where(lg < kth, -jnp.inf, lg)
    elif (cfg.top_k and cfg.top_k > 0) or cfg.top_p < 1.0:
        rows = lg.shape[:-1]
        lg = filter_top_k_top_p(
            lg,
            jnp.full(rows, int(cfg.top_k or 0), jnp.int32),
            jnp.full(rows, float(cfg.top_p), jnp.float32))
    return jax.random.categorical(key, lg, axis=-1).astype(jnp.int32)


def _cast_params(values, dtype):
    dt = jnp.dtype(dtype)
    return [v.astype(dt) if jnp.issubdtype(v.dtype, jnp.floating) else v
            for v in values]


def model_arrays(model):
    """(parameters, buffers) backing a serving model.  Buffers matter:
    int8-converted layers (QuantizedLinearInfer) keep qweight/scales as
    buffers, and baking them as jit constants would bloat and
    de-donate the executable."""
    return list(model.parameters()), list(model.buffers())


def swap_call(params, buffers, p_values, b_values, compute_dtype, fn):
    """Run ``fn()`` with the model's params swapped for traced arrays
    (params cast to the serving dtype once — the hoisted fast-layout
    copy; buffers passed through uncast: int8 weights stay int8 and
    quant scales stay fp32)."""
    if len(params) != len(p_values) or len(buffers) != len(b_values):
        raise RuntimeError(
            f"swap_call structure mismatch: captured {len(params)} params/"
            f"{len(buffers)} buffers but got {len(p_values)}/{len(b_values)} "
            "values — the model was structurally mutated (e.g. "
            "weight_only_quantize) after a generate() program was compiled; "
            "the stale executable cannot be reused")
    pv = _cast_params(p_values, compute_dtype)
    saved_p = [p._value for p in params]
    saved_b = [b._value for b in buffers]
    try:
        for p, a in zip(params, pv):
            p._value = a
        for b, a in zip(buffers, b_values):
            b._value = a
        with _tape.no_grad():
            return fn()
    finally:
        for p, s in zip(params, saved_p):
            p._value = s
        for b, s in zip(buffers, saved_b):
            b._value = s


def decode_scan_body(model, cfg: GenerationConfig):
    """The shared per-token scan body: decode_step -> sample -> EOS mask
    -> lens advance.  carry = (tok, lens, kvs, key, done); emits the
    sampled token.  Used by both GenerationMixin.generate and the
    LLMPredictor serving blocks so their semantics cannot diverge."""
    def body(carry, _):
        tok, lens_c, kvs_c, key_c, done = carry
        logits_t, kvs_c = model.decode_step(tok, lens_c, kvs_c)
        if cfg.do_sample:
            key_t, key_c = jax.random.split(key_c)
        else:
            key_t = key_c
        nxt = sample_token(logits_t, key_t, cfg)
        if cfg.eos_token_id is not None:
            nxt = jnp.where(done, cfg.pad_token_id, nxt)
            done_n = done | (nxt == cfg.eos_token_id)
        else:
            done_n = done
        lens_n = jnp.where(done, lens_c, lens_c + 1)
        return (nxt, lens_n, kvs_c, key_c, done_n), nxt
    return body


def beam_scan_body(model, cfg: GenerationConfig, b, k):
    """Per-token beam-search scan body over a [B*K]-batched KV cache.

    The beam-reorder step — the part greedy decode never exercises — is
    a batched GATHER on every cache buffer (``cache[parent_rows]``),
    exactly the role of the reference's cell-state gather in
    ``python/paddle/nn/decode.py:544`` and the cache reordering of its
    beam serving path.  All shapes static; one fused top-k over
    ``K * vocab`` candidates per step.

    carry = (tok [B*K], lens [B*K], kvs, log_probs [B,K],
    beam_len [B,K], done [B,K]); emits (token [B,K], parent [B,K],
    log_probs [B,K], beam_len [B,K]) per step — the per-step scores let
    a block-serving host truncate the tree mid-block and still score
    consistently (LLMPredictor); unused emits are DCE'd by XLA in the
    single-scan generate path.
    """
    neg_inf = jnp.float32(-1e9)

    def body(carry, _):
        tok, lens_c, kvs_c, lp, blen, done = carry
        logits_t, kvs_c = model.decode_step(tok, lens_c, kvs_c)  # [B*K,V]
        vocab = logits_t.shape[-1]
        step_lp = jax.nn.log_softmax(
            logits_t.astype(jnp.float32), axis=-1).reshape(b, k, vocab)
        if cfg.eos_token_id is not None:
            # finished beams contribute exactly one candidate: EOS at
            # zero added cost (score frozen)
            only_eos = jnp.full((vocab,), neg_inf
                                ).at[cfg.eos_token_id].set(0.0)
            step_lp = jnp.where(done[:, :, None], only_eos[None, None, :],
                                step_lp)
        flat = (lp[:, :, None] + step_lp).reshape(b, k * vocab)
        top_lp, top_idx = jax.lax.top_k(flat, k)                 # [B,K]
        parent = top_idx // vocab
        tok_idx = (top_idx % vocab).astype(jnp.int32)
        rows = (jnp.arange(b)[:, None] * k + parent).reshape(-1)  # [B*K]
        kvs_c = [(kc[rows], vc[rows]) for kc, vc in kvs_c]
        lens_g = lens_c[rows]
        barange = jnp.arange(b)[:, None]
        done_g = done[barange, parent]
        blen_g = blen[barange, parent]
        if cfg.eos_token_id is not None:
            emit = jnp.where(done_g, cfg.pad_token_id, tok_idx)
            done_n = done_g | (tok_idx == cfg.eos_token_id)
        else:
            emit = tok_idx
            done_n = done_g
        lens_n = jnp.where(done_g.reshape(-1), lens_g, lens_g + 1)
        blen_n = blen_g + (~done_g).astype(jnp.int32)
        carry_n = (emit.reshape(-1), lens_n, kvs_c, top_lp, blen_n,
                   done_n)
        return carry_n, (emit, parent.astype(jnp.int32), top_lp, blen_n)
    return body


# single backtrace implementation, shared with nn.functional.gather_tree
from ..nn.functional.decoding import _gather_tree_arrays  # noqa: E402


class GenerationMixin:
    """Adds ``generate`` to a causal LM that implements

    - ``prefill(input_ids, seq_lens, kv_caches) ->
        (last_logits [B, V], kv_caches)``: full-context forward over the
        (right-padded) prompt, writing prompt K/V into the caches.
    - ``decode_step(tokens [B], seq_lens, kv_caches) ->
        (logits [B, V], kv_caches)``: one cached decode step; writes the
        token's K/V at slot ``seq_lens`` and attends over ``<= seq_lens``.
    - ``kv_cache_spec() -> (num_layers, num_kv_heads, head_dim)``.

    The compiled program: cast params -> prefill -> scan(decode_step),
    cached per (prompt shape, max_cache_len, GenerationConfig).
    """

    def _generate_compiled(self, b, s_prompt, max_cache_len,
                           cfg: GenerationConfig, arrays):
        cache = getattr(self, "_generate_exe_cache", None)
        if cache is None:
            cache = self._generate_exe_cache = {}
        params, buffers = arrays
        # The compiled closure captures THESE param/buffer Tensor lists;
        # key on their structure so a structural mutation (e.g.
        # weight_only_quantize swapping Linears for quantized twins, which
        # moves weights from params to buffers) misses the cache instead of
        # silently mis-pairing values in swap_call.
        struct = (tuple(id(p) for p in params),
                  tuple(id(bf) for bf in buffers))
        keyt = (b, s_prompt, max_cache_len, cfg, struct)
        hit = cache.get(keyt)
        if hit is not None:
            return hit
        # Entries traced against a different param/buffer structure are
        # permanently unreachable AND their closures pin the old weight
        # lists on device — evict them instead of leaking executables.
        for stale in [k for k in cache if k[4] != struct]:
            del cache[stale]

        n_layers, hkv, d = self.kv_cache_spec()
        cache_dtype = jnp.dtype(cfg.cache_dtype or cfg.compute_dtype)
        model = self

        def run_greedy_or_sampled(ids, lens, key):
            kvs = init_kv_cache(n_layers, b, max_cache_len, hkv, d,
                                cache_dtype)
            logits, kvs = model.prefill(ids, lens, kvs)
            key0, keyr = (jax.random.split(key)
                          if cfg.do_sample else (key, key))
            tok0 = sample_token(logits, key0, cfg)
            done0 = (jnp.zeros((b,), bool) if cfg.eos_token_id is None
                     else tok0 == cfg.eos_token_id)

            if cfg.max_new_tokens > 1:
                (_, lens_f, _, _, _), rest = jax.lax.scan(
                    decode_scan_body(model, cfg),
                    (tok0, lens, kvs, keyr, done0), None,
                    length=cfg.max_new_tokens - 1)
                toks = jnp.concatenate(
                    [tok0[:, None], rest.T.astype(jnp.int32)], axis=1)
            else:
                toks = tok0[:, None]
                lens_f = lens
            return toks, lens_f + 1  # prompt + emitted

        def run_beam(ids, lens):
            """Prefill once at batch B, expand the caches to B*K rows,
            then scan the beam body; backtrace with gather_tree and pick
            the best beam per batch under the length penalty."""
            k = cfg.num_beams
            kvs = init_kv_cache(n_layers, b, max_cache_len, hkv, d,
                                cache_dtype)
            logits, kvs = model.prefill(ids, lens, kvs)        # [B, V]
            lp0 = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
            top_lp, tok0 = jax.lax.top_k(lp0, k)               # [B, K]
            tok0 = tok0.astype(jnp.int32)
            done0 = (jnp.zeros((b, k), bool)
                     if cfg.eos_token_id is None
                     else tok0 == cfg.eos_token_id)
            kvs = [(jnp.repeat(kc, k, axis=0), jnp.repeat(vc, k, axis=0))
                   for kc, vc in kvs]
            lens_bk = jnp.repeat(lens, k, axis=0)              # [B*K]
            blen0 = jnp.ones((b, k), jnp.int32)
            if cfg.max_new_tokens > 1:
                carry = (tok0.reshape(-1), lens_bk, kvs, top_lp, blen0,
                         done0)
                (_, _, _, lp_f, blen_f, _), (toks, parents, _, _) = \
                    jax.lax.scan(beam_scan_body(model, cfg, b, k), carry,
                                 None, length=cfg.max_new_tokens - 1)
                ids_seq = jnp.concatenate([tok0[None], toks], axis=0)
                par_seq = jnp.concatenate(
                    [jnp.tile(jnp.arange(k, dtype=jnp.int32)[None, None],
                              (1, b, 1)), parents], axis=0)
                seqs = _gather_tree_arrays(ids_seq, par_seq)  # [T, B, K]
            else:
                seqs = tok0[None]
                lp_f, blen_f = top_lp, blen0
            if cfg.length_penalty:
                score = lp_f / (blen_f.astype(jnp.float32)
                                ** jnp.float32(cfg.length_penalty))
            else:
                score = lp_f
            best = jnp.argmax(score, axis=-1)                  # [B]
            out = jnp.swapaxes(seqs, 0, 1)                     # [B, T, K]
            toks_best = out[jnp.arange(b), :, best].astype(jnp.int32)
            return toks_best, lens + blen_f[jnp.arange(b), best]

        def pure(p_values, b_values, ids, lens, key):
            def run():
                if cfg.num_beams > 1:
                    return run_beam(ids, lens)
                return run_greedy_or_sampled(ids, lens, key)
            return swap_call(params, buffers, p_values, b_values,
                             cfg.compute_dtype, run)

        compiled = jax.jit(pure)
        cache[keyt] = compiled
        return compiled

    def generate(self, input_ids, seq_lens=None, max_new_tokens=32,
                 do_sample=False, temperature=1.0, top_k=0, top_p=1.0,
                 num_beams=1,
                 length_penalty=0.0, eos_token_id=None, pad_token_id=0,
                 max_cache_len=None, compute_dtype="bfloat16",
                 cache_dtype=None, seed=0):
        """Generate ``max_new_tokens`` tokens after the (right-padded)
        prompt ``input_ids [B, S]``; ``seq_lens [B]`` are true prompt
        lengths (default: full S).  Returns a Tensor [B, max_new_tokens]
        of int32 token ids (``pad_token_id`` after EOS).

        Reference analogue: PaddleNLP generate() over the
        fused_multi_transformer decode path; see module docstring for
        the TPU formulation.
        """
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if num_beams < 1:
            raise ValueError(f"num_beams must be >= 1, got {num_beams}")
        if num_beams > 1 and do_sample:
            raise ValueError(
                "num_beams > 1 is greedy beam search; do_sample=True is "
                "not supported together with beams")
        ids = _unwrap(input_ids).astype(jnp.int32)
        b, s = ids.shape
        if seq_lens is None:
            lens = jnp.full((b,), s, jnp.int32)
        else:
            import numpy as np
            lens_np = np.asarray(_unwrap(seq_lens))
            if lens_np.shape != (b,) or (lens_np < 1).any() or \
                    (lens_np > s).any():
                # jit-side gathers clamp out-of-range indices silently
                raise ValueError(
                    f"seq_lens must be [{b}] ints in [1, {s}], got "
                    f"{lens_np.tolist()}")
            lens = jnp.asarray(lens_np, jnp.int32)
        if max_cache_len is None:
            max_cache_len = s + max_new_tokens
        if max_cache_len < s + max_new_tokens:
            raise ValueError(
                f"max_cache_len ({max_cache_len}) < prompt + new tokens "
                f"({s} + {max_new_tokens})")
        cfg = GenerationConfig(
            max_new_tokens=int(max_new_tokens), do_sample=bool(do_sample),
            temperature=float(temperature), top_k=int(top_k),
            top_p=float(top_p),
            num_beams=int(num_beams),
            length_penalty=float(length_penalty),
            eos_token_id=eos_token_id, pad_token_id=int(pad_token_id),
            compute_dtype=str(compute_dtype),
            cache_dtype=None if cache_dtype is None else str(cache_dtype))
        params, buffers = model_arrays(self)
        fn = self._generate_compiled(b, s, int(max_cache_len), cfg,
                                     arrays=(params, buffers))
        key = jax.random.PRNGKey(seed)
        # Decode must never run dropout: force eval for the traced call
        # (LLMPredictor already does model.eval(); the plain generate()
        # entry point gets the same guarantee), restoring modes after.
        saved_modes = [(layer, layer.training)
                       for layer in self.sublayers(include_self=True)]
        try:
            self.eval()
            toks, _ = fn([p._value for p in params],
                         [bf._value for bf in buffers], ids, lens, key)
        finally:
            for layer, mode in saved_modes:
                layer.training = mode
        return Tensor(toks)
