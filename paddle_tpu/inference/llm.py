"""LLM serving: a KV-cache decode session for the Predictor stack.

Reference analogue: the fused decode-serving path —
``paddle/fluid/operators/fused/fused_multi_transformer_op.cu`` (+ its
int8 twin) driven step-by-step under AnalysisPredictor with persistent
cache tensors.  TPU formulation:

- ``LLMPredictor`` owns the session state (token, lengths, done flags,
  per-layer KV buffers) as device arrays between calls — the session is
  the cache's lifetime, like the reference's cache_kv variables living
  in the predictor scope.
- Decode runs in BLOCKS of ``steps_per_call`` tokens: one compiled call
  (``lax.scan`` inside) emits K tokens, so the per-dispatch host cost
  amortizes over K steps while the session stays incremental.  The float->compute-dtype weight cast also
  amortizes per block.
- ``save()`` exports the prefill and decode-block programs as portable
  StableHLO (jax.export, same mechanism as ``paddle.jit.save``) plus a
  weights pickle; ``LLMPredictor.load()`` rebuilds the session without
  the model's Python class.  Artifacts carry the FULL decode
  configuration: greedy, sampled (temperature/top-k with the PRNG key
  threaded through the block programs), or beam search (the block ships
  per-step token/parent/score planes; the host backtraces the beam tree
  with ``gather_tree`` once at the end).
"""

from __future__ import annotations

import contextlib
import os
import pickle
from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.generation import (GenerationConfig, LatentCacheSpec,
                                 beam_scan_body, decode_scan_body,
                                 init_kv_cache, model_arrays, sample_token,
                                 slot_state_spec, swap_call,
                                 _gather_tree_arrays)


def _flatten_kvs(kvs):
    flat = []
    for k, v in kvs:
        flat.append(k)
        flat.append(v)
    return flat


def _unflatten_kvs(flat):
    return [(flat[i], flat[i + 1]) for i in range(0, len(flat), 2)]


def normalize_weight_dtype(weight_dtype):
    """Validate a ``weight_dtype=`` argument.  Returns ``None`` for
    full-precision serving (``None`` or any float dtype name — weights
    then stream at the compute dtype, today's behavior) or the
    canonical ``"int8"``/``"int4"`` string for quantized weight planes.
    The allowed set is deliberately distinct from ``kv_cache_dtype``'s
    (which admits float dtypes or ``"int8"`` only)."""
    if weight_dtype is None:
        return None
    s = str(weight_dtype)
    if s in ("int8", "int4"):
        return s
    try:
        dt = jnp.dtype(weight_dtype)
    except TypeError:
        raise ValueError(
            f"weight_dtype must be a float dtype (full-precision "
            f"weights), 'int8' or 'int4' (quantized code+scale planes); "
            f"got {weight_dtype!r}")
    if jnp.issubdtype(dt, jnp.floating):
        return None
    raise ValueError(
        f"weight_dtype must be a float dtype, 'int8' or 'int4'; got "
        f"{weight_dtype!r} — integer weight arenas other than int8/int4 "
        "have no code+scale discipline")


class WeightQuantPlan:
    """One model's quantized-weight planes plus the bookkeeping that
    threads them through the serving programs: per (layer_idx, target)
    an int8 code plane ([K, N]; int4 packs to [K//2, N]) and a
    per-output-channel f32 scale plane [N], calibrated through
    ``quantization.observers`` (the ONE quant rule — see
    ``absmax_to_scales``).  ``flat_values()`` appends to the engine's
    swapped param/buffer list (ONE positional list argument, so donation
    index tuples never shift); ``bind()`` rebuilds the trace-time
    context from the traced values inside a program."""

    def __init__(self, dtype_str, bits, entries, max_m=256):
        self.dtype = dtype_str
        self.bits = bits
        # entries: (layer_idx, target, param_pos, codes, scales) in
        # deterministic (layer, declaration) order
        self.entries = entries
        self.max_m = max_m
        self.param_positions = frozenset(e[2] for e in entries)

    def flat_values(self):
        flat = []
        for _li, _t, _pos, codes, scales in self.entries:
            flat.append(codes)
            flat.append(scales)
        return flat

    def bind(self, flat):
        from ..models.wquant import WeightQuantContext
        planes = {}
        for i, (li, t, _pos, _c, _s) in enumerate(self.entries):
            planes[(li, t)] = (flat[2 * i], flat[2 * i + 1])
        return WeightQuantContext(planes, self.bits, self.max_m)

    def bytes_swept(self):
        """Modeled HBM bytes one forward streams for the quantized
        planes (codes at their packed width + f32 scales)."""
        return sum(int(c.nbytes) + int(s.nbytes)
                   for _li, _t, _pos, c, s in self.entries)

    def placeholder_params(self, params):
        """The swapped param value list with every quantized weight's
        slot replaced by a ZERO-SIZE placeholder: a projection site that
        fails to divert through ``wq_linear`` hits a shape error at
        trace time instead of silently streaming a stale float plane."""
        return [jnp.zeros((0,), p._value.dtype)
                if i in self.param_positions else p._value
                for i, p in enumerate(params)]


def build_weight_quant_plan(model, weight_dtype) -> WeightQuantPlan:
    """Quantize ``model``'s hot projections once at load.  Scales go
    through the PerChannelAbsmaxObserver path (``quantization/
    observers.py``) so PTQ calibration and the serving loader share one
    bit-exact rule; codes are ``quantize_channelwise`` of the same rule;
    int4 packs two codes per byte (``pack_int4``)."""
    from ..nn import Linear
    from ..quantization.observers import (PerChannelAbsmaxObserver,
                                          absmax_to_scales,
                                          quantize_channelwise)
    from ..ops.pallas.quantized_matmul import pack_int4
    bits = {"int8": 8, "int4": 4}[weight_dtype]
    from ..nn import RoutedExperts
    if any(isinstance(l, RoutedExperts) for l in model.sublayers()):
        raise ValueError(
            f"weight_dtype={weight_dtype!r}: {type(model).__name__} has "
            "expert planes (nn.RoutedExperts), which the weight "
            "quantisation plan does not cover — the grouped matmul reads "
            "them at the compute dtype")
    if not hasattr(model, "quant_projections"):
        raise ValueError(
            f"weight_dtype={weight_dtype!r} needs a model exposing "
            "quant_projections() (llama/gpt); got "
            f"{type(model).__name__}")
    params, _buffers = model_arrays(model)
    pos = {id(p): i for i, p in enumerate(params)}
    entries = []
    for li, layer in enumerate(model.quant_projections()):
        for target, lin in layer.items():
            if not isinstance(lin, Linear):
                raise ValueError(
                    f"weight_dtype={weight_dtype!r} supports plain "
                    f"nn.Linear projections only; layer {li} {target} is "
                    f"{type(lin).__name__} (tensor-parallel serving "
                    "quantization is not wired)")
            obs = PerChannelAbsmaxObserver(quant_axis=-1, bit_length=bits)
            obs.observe(lin.weight)
            scales = absmax_to_scales(obs.scales()._value, bits)
            codes = quantize_channelwise(lin.weight._value, scales, bits,
                                         quant_axis=-1)
            if bits == 4:
                codes = pack_int4(codes)
            entries.append((li, target, pos[id(lin.weight)],
                            codes, scales))
    return WeightQuantPlan(weight_dtype, bits, entries)


def _param_swapper(model, cfg: GenerationConfig, wq=None):
    """The closure every serving program shares: positional
    params+buffers values in, the model's weights swapped for the traced
    arrays for the duration of the call (floats cast ONCE to the serving
    compute dtype — the hoisted fast-layout copy).

    ``wq`` (a WeightQuantPlan) appends the quantized code/scale planes
    to the SAME positional list: the trailing ``2 * len(entries)``
    values are split off, bound into a trace-time wquant context
    (``models/wquant.py``), and the projection sites route through them
    — the core params at quantized positions are zero-size placeholders
    that fail loudly if any site misses the diversion."""
    params, buffers = model_arrays(model)

    if wq is None:
        def _with_params(pb_values, fn):
            p_values = pb_values[:len(params)]
            b_values = pb_values[len(params):]
            return swap_call(params, buffers, p_values, b_values,
                             cfg.compute_dtype, fn)
        return _with_params

    from ..models.wquant import wquant_context
    n_core = len(params) + len(buffers)

    def _with_params_wq(pb_values, fn):
        core = pb_values[:n_core]
        ctx = wq.bind(list(pb_values[n_core:]))
        p_values = core[:len(params)]
        b_values = core[len(params):]

        def run():
            with wquant_context(ctx):
                return fn()
        return swap_call(params, buffers, p_values, b_values,
                         cfg.compute_dtype, run)

    return _with_params_wq


def _build_decode_block(model, cfg: GenerationConfig, steps_per_call,
                        wq=None):
    """Pure greedy/sampled decode block: ``lax.scan`` of
    ``steps_per_call`` steps of the shared ``decode_scan_body``.

    Slot-granular serving contract (ServingEngine): every op in the
    body is row-independent — per-row cache scatter, per-row prefix
    attention, per-row EOS/length masking — so a batch row decodes
    identically whatever mix of fill levels the other slots hold.
    Occupancy is pure DATA (``lens``/``done`` vectors), never shape:
    one compiled block serves every occupancy mix, and rows with
    ``done=True`` freeze (lens stops advancing, emits are pad), which
    is how both finished and vacant slots ride along for free.
    ``wq`` (a WeightQuantPlan) appends quantized code/scale planes to
    the positional param list — see ``_param_swapper``.
    """
    _with_params = _param_swapper(model, cfg, wq=wq)

    def block_pure(p_values, tok, lens, done, key, *flat_kvs):
        def run():
            kvs = _unflatten_kvs(list(flat_kvs))
            (tok_f, lens_f, kvs_f, key_f, done_f), toks = jax.lax.scan(
                decode_scan_body(model, cfg), (tok, lens, kvs, key, done),
                None, length=steps_per_call)
            return ((toks.T.astype(jnp.int32), tok_f, lens_f, done_f,
                     key_f) + tuple(_flatten_kvs(kvs_f)))
        return _with_params(p_values, run)

    return block_pure


def build_slot_prefill(model, max_cache_len, cfg: GenerationConfig):
    """Slot-granular prefill for continuous batching (ServingEngine):
    prefill ONE sequence (a batch-1 compiled prompt pass) and write its
    K/V into row ``slot`` of a shared B-slot cache pool.

    The whole ``max_cache_len`` cache row is written — prompt K/V
    followed by the zeros of the batch-1 scratch cache — so admission
    unconditionally scrubs the previous occupant's stale K/V (defense
    in depth on top of the ``lens`` masking that already hides slots
    past the valid prefix).  ``slot`` is a TRACED scalar: one compiled
    program admits into any slot.  Signature:
    ``(p_values, slot, ids [1, P], lens [1], key, *flat_kvs) ->
    (tok0 [1], key', *flat_kvs)``.
    """
    if cfg.num_beams > 1:
        raise ValueError(
            "slot-granular prefill is greedy/sampled only — beam search "
            "expands to K cache rows per request, which does not fit a "
            "one-slot-per-request pool")
    n_layers, hkv, d = model.kv_cache_spec()
    cache_dtype = jnp.dtype(cfg.cache_dtype or cfg.compute_dtype)
    _with_params = _param_swapper(model, cfg)

    def slot_prefill_pure(p_values, slot, ids, lens, key, *flat_kvs):
        def run():
            small = init_kv_cache(n_layers, 1, max_cache_len, hkv, d,
                                  cache_dtype)
            logits, small = model.prefill(ids, lens, small)
            if cfg.do_sample:
                key0, keyr = jax.random.split(key)
            else:
                key0 = keyr = key
            tok0 = sample_token(logits, key0, cfg)
            big = _unflatten_kvs(list(flat_kvs))
            out = []
            for (bk, bv), (sk, sv) in zip(big, small):
                zero = (0,) * (bk.ndim - 1)
                out.append((
                    jax.lax.dynamic_update_slice(bk, sk, (slot,) + zero),
                    jax.lax.dynamic_update_slice(bv, sv, (slot,) + zero)))
            return (tok0, keyr) + tuple(_flatten_kvs(out))
        return _with_params(p_values, run)

    return slot_prefill_pure


class ArenaSharding(NamedTuple):
    """Mesh recipe for tensor-parallel paged serving: every arena plane
    (float K/V, int8 codes, AND their f32 scale planes) shards its
    LAST axis — kv-heads; ``Hkv*D`` for packed planes, ``Hkv`` for
    scales — over the mesh's ``model`` axis, so one ``NamedSharding``
    covers all of them and each shard owns ``Hkv / n_shards`` whole
    heads (the engine enforces the divisibility).  Block tables,
    token/length/done planes and sampling state stay replicated: the
    byte-deterministic host plan is the SAME program input on every
    shard, which is what keeps scheduling identical to single-chip.
    ``n_shards`` rides along so trace-time code (the kernel route
    gate) can report the shard geometry without re-deriving it from
    the sharding object."""
    kv: object        # jax.sharding.NamedSharding over the arena axes
    n_shards: int


def _shard_scope(shard):
    """Trace-time marker: inside this scope the paged kernel gates
    report the ``sharded_ok``/``mesh_geom`` route overlay (see
    ``ops/pallas/decode_attention.shard_dispatch_scope``).  A ``None``
    shard is the single-chip build — no scope, no overlay counters."""
    if shard is None:
        return contextlib.nullcontext()
    from ..ops.pallas import decode_attention as _da
    return _da.shard_dispatch_scope(shard.n_shards)


def _constrain_arenas(flat, shard):
    """Pin every arena plane to the shard recipe inside a traced
    program (``with_sharding_constraint``): on the way IN it makes
    GSPMD propagation decisive through the scan carry, on the way OUT
    it guarantees the donated round-trip keeps the input sharding
    (donation only reuses buffers when in/out layouts match — an
    unconstrained output that propagated to replicated would silently
    re-shard every dispatch).  No-op for single-chip builds."""
    if shard is None:
        return list(flat)
    return [jax.lax.with_sharding_constraint(a, shard.kv) for a in flat]


def _kv_latent(model):
    """Does ``model`` keep a latent cache (``LatentCacheSpec``): one arena a
    cached layer, not keys and values?"""
    return isinstance(model.kv_cache_spec(), LatentCacheSpec)


def _pack_paged_kvs(flat_arenas, tables, kv_int8, latent=False):
    """Per-layer kv entries from the engine's flat arena list: the
    (k, v, tables) triple of the float cache, the
    (k_codes, v_codes, k_scales, v_scales, tables) 5-tuple of the int8
    cache (4 donated arrays per layer instead of 2), or the
    (arena, tables) pair of a ``latent`` cache."""
    stride = 1 if latent else 4 if kv_int8 else 2
    return [tuple(flat_arenas[i:i + stride]) + (tables,)
            for i in range(0, len(flat_arenas), stride)]


def _flatten_paged_kvs(kvs):
    """Inverse of ``_pack_paged_kvs`` minus the tables: the flat arena
    list handed back out of a serving program (donation-matched)."""
    flat = []
    for entry in kvs:
        flat += list(entry[:-1])
    return flat


def _split_slot_state(model, flat_arenas):
    """A program's trailing donated arrays as (KV arenas, slot-state
    arenas): the state arenas of ``slot_state_spec`` ride behind the KV
    arenas, so a model without such state sees exactly its KV arenas."""
    flat = list(flat_arenas)
    n_kv = len(flat) - len(slot_state_spec(model))
    return flat[:n_kv], flat[n_kv:]


def _poison_rows(arena, finished):
    """A slot-state arena with the rows of the slots that ``finished``
    inside this block set to NaN (a float arena; another is left as it
    is).  Nothing may read a finished slot's state: its next prompt starts
    from zeros (``prefill_chunk`` selects, it does not multiply), and a
    decode program skips the rows that enter it ``stale``.  A program that
    loses that reset would otherwise serve plausible tokens from a stale
    convolution tail or recurrent state, wrong by less than bfloat16's
    own noise; from NaN it serves garbage, which every comparison sees."""
    if not jnp.issubdtype(arena.dtype, jnp.inexact):
        return arena
    b = finished.shape[0]
    rows = finished.reshape((b,) + (1,) * (arena.ndim - 1))
    return arena.at[:b].set(jnp.where(rows, jnp.nan, arena[:b]))


def _poison_state(model, arenas, finished):
    """Every state arena poisoned for the slots that ``finished``: whole
    rows (``_poison_rows``), unless the model says which part of a slot's
    row is enough to turn everything read from it into NaN
    (``poison_slot_state``: a matrix state of megabytes a slot is poisoned
    in one key row a head, not rewritten whole once a block)."""
    own = getattr(model, "poison_slot_state", None)
    if own is not None:
        return own(arenas, finished)
    return [_poison_rows(a, finished) for a in arenas]


def _build_paged_decode_block(model, cfg: GenerationConfig, steps_per_call,
                              kv_int8=False,
                              samp_flags=(False, False, False, False),
                              lora=False, wq=None, shard=None):
    """Paged twin of ``_build_decode_block``: the cache is the shared
    block arena plus per-slot block tables instead of per-slot
    contiguous rows.  The tables ride into the scan closure as a
    loop-invariant traced value (a request's table never changes during
    its decode life — all its blocks are mapped at admission), so the
    per-step transfer is ONLY the small [B, max_blocks] int32 table
    push; the arenas stay donated device buffers.  ``kv_int8`` selects
    the quantized cache: ``flat_arenas`` then interleaves
    (k_codes, v_codes, k_scales, v_scales) per layer and the models'
    decode path quantizes on append / dequantizes on read.

    ``samp_flags = (sampled, filtered, penalty, bias)`` statically selects the
    per-row sampling machinery (``inference/sampling.py``): the
    all-False build is the exact greedy program (argmax only), and each
    flag compiles in only the planes its mix needs — the ``samp``
    pytree's structure is determined by the same flags, so program
    variants and plane dicts stay in lockstep.  Signature:
    ``(p_values, tok, lens, done, budget, samp, tables, *flat_arenas)
    -> (toks [B, n], tok', lens', done', budget', *flat_arenas)``.

    Dispatch-ahead contract: every output is an UN-MATERIALIZED
    device array (JAX async dispatch) and the carries ``tok'``/
    ``lens'``/``done'``/``budget'`` are valid INPUTS to the next block
    call as-is — the caller may enqueue iteration N+1 feeding them
    directly and force iteration N's outputs to host afterwards (the
    ServingEngine plan/harvest split).  Done rows self-freeze in-trace
    (pad emits, held lens), which is what makes one-step-stale host
    truth safe.  ``done'`` is the IN-TRACE FINISH BITMAP: it flips on
    an emitted EOS *and* on budget exhaustion (``budget`` [B] int32 is
    the per-row remaining-token count, decremented per live emit), so
    a depth-S pipeline can keep dispatching on stale truth and poll
    the bitmap at harvest instead of syncing every iteration — see
    ``serving.ASYNC_SYNC_REASONS`` for where a sync is still
    semantically required.

    ``lora=True`` compiles the batched multi-adapter variant: a
    ``lora`` pytree argument (``{"ids": [B] int32, "a"/"b": {target:
    stacked arena}}``) is inserted after ``samp`` and the scan traces
    under an active adapter context (``models/lora.py``) — per-row
    gathered A/B einsums add each request's low-rank delta inside the
    attention projections.  The gather is hoisted out of the scan
    (ids are loop-invariant), and the ``lora=False`` build keeps
    today's exact signature and program.

    ``wq`` (a WeightQuantPlan) selects quantized-weight serving: the
    plan's code/scale planes ride as trailing entries of ``p_values``
    (one positional list — donation indices over the trailing arena
    args never shift) and the scan traces under an active weight-quant
    context (``models/wquant.py``)."""
    from .sampling import sampled_decode_scan_body
    from ..models.lora import gather_lora, lora_context
    _with_params = _param_swapper(model, cfg, wq=wq)
    sampled, _filtered, penalty, _bias = samp_flags

    counts = getattr(model, "init_block_counters", None)

    def _scan(tok, lens, done, budget, samp, tables, flat_arenas):
        flat_kv, state = _split_slot_state(model, flat_arenas)
        kvs = _pack_paged_kvs(_constrain_arenas(flat_kv, shard),
                              tables, kv_int8, _kv_latent(model))
        if state or counts is not None:
            # the second kind of state rides the scan carry as one more
            # entry of ``kvs``; the scan body never looks inside.
            # ``stale``: the rows that were done when the block began,
            # whose state rows hold nothing to read (vacant, prefilling,
            # or poisoned below by the block they finished in).  A model
            # that counts (its experts' load) adds its counters to it,
            # beside its state or, where it keeps none, alone
            kvs.append({"state": state, "stale": done})
            if counts is not None:
                kvs[-1]["counters"] = counts()
        pos0 = samp["pos"] if sampled else jnp.zeros_like(lens)
        pres0 = samp["presence"] if penalty else None
        with _shard_scope(shard):
            (tok_f, lens_f, kvs_f, _pos_f, _pres_f, done_f, budget_f), \
                toks = jax.lax.scan(
                    sampled_decode_scan_body(model, cfg, samp, samp_flags),
                    (tok, lens, kvs, pos0, pres0, done, budget),
                    None, length=steps_per_call)
        tail = ()
        if state or counts is not None:
            slot_state = kvs_f.pop()
            tail = tuple(_poison_state(model, slot_state["state"],
                                       done_f & ~done))
            if counts is not None:
                tail += (slot_state["counters"],)
        return ((toks.T.astype(jnp.int32), tok_f, lens_f, done_f,
                 budget_f) + tuple(_constrain_arenas(
                     _flatten_paged_kvs(kvs_f), shard)) + tail)

    if lora:
        def block_pure(p_values, tok, lens, done, budget, samp,
                       lora_planes, tables, *flat_arenas):
            def run():
                with lora_context(gather_lora(lora_planes)):
                    return _scan(tok, lens, done, budget, samp, tables,
                                 flat_arenas)
            return _with_params(p_values, run)
    else:
        def block_pure(p_values, tok, lens, done, budget, samp, tables,
                       *flat_arenas):
            return _with_params(
                p_values,
                lambda: _scan(tok, lens, done, budget, samp, tables,
                              flat_arenas))

    return block_pure


def build_fused_decode_window(model, cfg: GenerationConfig,
                              steps_per_iter, iters, **build_kw):
    """Fused multi-iteration decode dispatch (PR 14): ``iters``
    scheduler iterations of a ``steps_per_iter``-step decode block as
    ONE compiled program — the ``steps_per_call`` amortization of
    ``decode_scan_body`` lifted from intra-block to inter-iteration.

    Because the per-token scan body already self-feeds its carries
    (done rows freeze in-trace; the finish bitmap flips on EOS and
    budget exhaustion), S iterations of an n-step block ARE one
    ``lax.scan`` of S*n steps: the builder reuses
    ``_build_paged_decode_block`` with ``steps_per_call = S * n``, so
    a fused window and a plain (S*n)-step block share one compiled
    program (the engine's block cache keys on total steps).

    This is NOT ``steps_per_call=S*n`` at the engine level:
    ``steps_per_call`` is a static engine-wide granularity the
    scheduler honors every iteration in which some rider is owed a
    whole block (riders owed less finish inside it and freeze; it
    drops to 1 only when every rider is inside its last
    ``steps_per_call - 1`` tokens or a masked row is live, and such
    a block harvests synchronously), while a fused window is a
    PER-ITERATION choice the plan phase makes only when the window is
    provably eventless (no chunk-final, no mask/penalty rows, no spec,
    no queue, budget headroom > S*n for every rider) — and the harvest
    still accounts the window as S logical iterations (per-iteration
    flight-recorder events, ledger splits and KV-sweep modeling), so
    token streams and per-request stories stay iteration-exact."""
    return _build_paged_decode_block(
        model, cfg, int(steps_per_iter) * int(iters), **build_kw)


def build_swap_out_gather(shard=None):
    """Swap-out reader for the host-RAM block tier (ServingEngine):
    gather a row of block ids out of EVERY arena in one compiled call
    — ``(ids [W], *flat_arenas) -> tuple of [W, ...] row stacks``.
    Two consumers share ONE compiled shape (``W = max_blocks``,
    trash-padded): preemption gathers a slot's full table row, and the
    tiered prefix cache demotes each alloc's reclaimed batch through
    the same program (wider reclaims page through it) — demotion costs
    a dispatch per admission, not per block, and adds no second
    compile.  The gathered rows are the EXACT at-rest bytes of
    the blocks — float K/V, or int8 codes plus their f32 scale planes,
    whichever the arena holds — which is what makes preempt/resume
    (and a host-tier prefix hit) byte-identical rather than
    recompute-and-hope.  Trash-row gathers past the allocation are
    finite garbage the resume scatter routes straight back to the
    trash row."""
    def gather_pure(ids, *flat_arenas):
        return tuple(jnp.take(a, ids, axis=0)
                     for a in _constrain_arenas(flat_arenas, shard))
    return gather_pure


def build_swap_in_scatter(n_arenas, shard=None):
    """Donation-matched re-scatter for host-RAM -> arena restores:
    write saved block rows into freshly allocated arena rows —
    ``(ids [W], *rows (n_arenas of [W, ...]), *flat_arenas) ->
    flat_arenas`` with the arenas donated, same discipline as the
    decode/chunk/verify programs (steady-state serving never
    materializes a second arena copy).  ONE compiled program serves
    both preemption RESUME and the tiered prefix cache's host-hit
    promotion (``W = max_blocks`` for both; promotion packs its k
    parcels into the leading rows).  ``ids`` is the destination row:
    entries past the payload point at the trash row, so pad rows of
    the saved stack land there (the write-masking contract of every
    other paged writer) and duplicate trash writes only ever
    overwrite finite garbage with finite garbage."""
    def scatter_pure(ids, *rows_and_arenas):
        rows = rows_and_arenas[:n_arenas]
        arenas = _constrain_arenas(rows_and_arenas[n_arenas:], shard)
        return tuple(_constrain_arenas(
            [a.at[ids].set(r.astype(a.dtype))
             for a, r in zip(arenas, rows)], shard))
    return scatter_pure


def build_chunk_prefill(model, cfg: GenerationConfig, kv_int8=False,
                        samp_flags=(False, False, False, False),
                        lora=False, wq=None, shard=None):
    """Chunked-prefill program for the paged ServingEngine: ONE prompt
    chunk of ONE sequence (batch-1; the static chunk length is the ids
    shape) computed at global positions ``start .. start+C-1``, K/V
    written through the slot's block table (``models.*.prefill_chunk``).
    A token is sampled from the logits at prompt position
    ``n_valid - 1`` every call; it is only meaningful on the chunk that
    covers that position — the engine ignores earlier chunks' sample
    and never advances decode state from them.  ``kv_int8`` selects the
    quantized cache and ``samp_flags`` the per-request sampling
    machinery (see ``_build_paged_decode_block``; the batch-1 ``samp``
    planes carry the request's params at PRNG position 0 — the
    first output token's draw is chunk-layout- and prefix-hit-
    independent by construction).  Signature:
    ``(p_values, ids [1, C], start [], n_valid [], tables
    [1, max_blocks], samp, *flat_arenas) -> (tok [1],
    *flat_arenas)``.

    Dispatch-ahead contract: the outputs are un-materialized device
    arrays; only the FINAL chunk's ``tok`` is host truth (the
    request's first token), so the engine forces exactly that one —
    non-final chunks are pure enqueues whose compute overlaps
    subsequent host scheduling.

    ``lora=True`` inserts a ``lora`` pytree argument after ``samp``
    (batch-1 ids: the request's adapter slot) and traces the chunk
    under an active adapter context — so a LoRA request's PROMPT K/V
    is computed through its adapter too, exactly what its merged-
    weights twin would have written (see ``_build_paged_decode_block``
    for the plane layout; ``lora=False`` keeps today's program).
    ``wq`` selects quantized-weight serving (see
    ``_build_paged_decode_block``) — the prompt pass runs through the
    same codes+scales the decode blocks do."""
    if cfg.num_beams > 1:
        raise ValueError(
            "chunked prefill is greedy/sampled only — beam search "
            "expands to K cache rows per request, which does not fit a "
            "one-slot-per-request block table")
    from .sampling import sample_rows
    from ..models.lora import gather_lora, lora_context
    _with_params = _param_swapper(model, cfg, wq=wq)
    penalty = samp_flags[2]

    def _chunk(ids, start, n_valid, tables, samp, flat_arenas):
        flat_kv, state = _split_slot_state(model, flat_arenas)
        kvs = _pack_paged_kvs(_constrain_arenas(flat_kv, shard),
                              tables, kv_int8, _kv_latent(model))
        if state:
            # ``tables`` carries the slot index behind the slot's blocks
            # ([1, max_blocks + 1]): the chunk reads and writes that row of
            # the state arenas, with no argument and no copy of its own
            kvs = [kv[:-1] + (tables[:, :-1],) for kv in kvs]
            kvs.append({"state": state, "slot": tables[0, -1]})
        with _shard_scope(shard):
            logits, kvs_f = model.prefill_chunk(ids, start, n_valid, kvs)
        tok = sample_rows(logits, samp, samp_flags,
                          samp["presence"] if penalty else None)
        tail = tuple(kvs_f.pop()["state"]) if state else ()
        return (tok,) + tuple(_constrain_arenas(
            _flatten_paged_kvs(kvs_f), shard)) + tail

    if lora:
        def chunk_pure(p_values, ids, start, n_valid, tables, samp,
                       lora_planes, *flat_arenas):
            def run():
                with lora_context(gather_lora(lora_planes)):
                    return _chunk(ids, start, n_valid, tables, samp,
                                  flat_arenas)
            return _with_params(p_values, run)
    else:
        def chunk_pure(p_values, ids, start, n_valid, tables, samp,
                       *flat_arenas):
            return _with_params(
                p_values,
                lambda: _chunk(ids, start, n_valid, tables, samp,
                               flat_arenas))

    return chunk_pure


def _build_serving_fns(model, batch, max_cache_len,
                       cfg: GenerationConfig, steps_per_call, wq=None):
    """Pure (params, ...) -> (...) functions for prefill and one decode
    block; the exported/jitted serving programs.

    Three serving modes, all artifact-exportable (the reference's
    AnalysisPredictor serves the full decode configuration from the
    artifact alone — ``paddle/fluid/inference/api/analysis_predictor.h:94``):

    - greedy / sampled (``cfg.do_sample``): the prefill emits the first
      token and a threaded PRNG key; each block scans ``steps_per_call``
      decode steps, splitting the key per step.
    - beam (``cfg.num_beams > 1``): the prefill top-k-expands to
      ``[B*K]`` cache rows; each block scans the beam body and emits
      per-step (token, parent) pairs — the HOST accumulates them and
      backtraces once at the end (beam results are only final after the
      last step, so the block protocol ships the tree, not sequences).
    """
    n_layers, hkv, d = model.kv_cache_spec()
    cache_dtype = jnp.dtype(cfg.cache_dtype or cfg.compute_dtype)
    k = cfg.num_beams
    _with_params = _param_swapper(model, cfg, wq=wq)

    if k > 1:
        def prefill_pure(p_values, ids, lens):
            def run():
                kvs = init_kv_cache(n_layers, batch, max_cache_len, hkv,
                                    d, cache_dtype)
                logits, kvs = model.prefill(ids, lens, kvs)   # [B, V]
                lp0 = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
                top_lp, tok0 = jax.lax.top_k(lp0, k)          # [B, K]
                tok0 = tok0.astype(jnp.int32)
                done0 = (jnp.zeros((batch, k), bool)
                         if cfg.eos_token_id is None
                         else tok0 == cfg.eos_token_id)
                kvs = [(jnp.repeat(kc, k, axis=0),
                        jnp.repeat(vc, k, axis=0)) for kc, vc in kvs]
                lens_bk = jnp.repeat(lens, k, axis=0)
                blen0 = jnp.ones((batch, k), jnp.int32)
                return ((tok0, lens_bk, done0, top_lp, blen0)
                        + tuple(_flatten_kvs(kvs)))
            return _with_params(p_values, run)

        def block_pure(p_values, tok, lens, done, lp, blen, *flat_kvs):
            def run():
                kvs = _unflatten_kvs(list(flat_kvs))
                carry = (tok.reshape(-1), lens, kvs, lp, blen, done)
                (tok_f, lens_f, kvs_f, lp_f, blen_f, done_f), \
                    (toks, parents, lps, blens) = jax.lax.scan(
                        beam_scan_body(model, cfg, batch, k), carry,
                        None, length=steps_per_call)
                # toks/parents/lps/blens: [steps, B, K] — per-step scores
                # let the host truncate the tree mid-block and still pick
                # the best beam at exactly max_new_tokens
                return ((toks, parents, lps, blens,
                         tok_f.reshape(batch, k), lens_f, done_f, lp_f,
                         blen_f) + tuple(_flatten_kvs(kvs_f)))
            return _with_params(p_values, run)

        return prefill_pure, block_pure

    def prefill_pure(p_values, ids, lens, key):
        def run():
            kvs = init_kv_cache(n_layers, batch, max_cache_len, hkv, d,
                                cache_dtype)
            logits, kvs = model.prefill(ids, lens, kvs)
            if cfg.do_sample:
                key0, keyr = jax.random.split(key)
            else:
                key0 = keyr = key
            tok0 = sample_token(logits, key0, cfg)
            done0 = (jnp.zeros((batch,), bool)
                     if cfg.eos_token_id is None
                     else tok0 == cfg.eos_token_id)
            return (tok0, lens, done0, keyr) + tuple(_flatten_kvs(kvs))
        return _with_params(p_values, run)

    return prefill_pure, _build_decode_block(model, cfg, steps_per_call,
                                             wq=wq)


class LLMPredictor:
    """Cached-KV generative serving session (see module docstring).

    Shapes are static per predictor: ``batch`` sequences, right-padded
    prompts of ``prompt_len``, cache capacity ``max_cache_len``.
    ``start()`` prefills and returns the first generated token;
    ``decode(n)`` continues n more tokens; ``generate()`` is both.
    """

    def __init__(self, model=None, *, batch, prompt_len,
                 max_cache_len=None, steps_per_call=16,
                 eos_token_id=None, pad_token_id=0,
                 do_sample=False, temperature=1.0, top_k=0, top_p=1.0,
                 num_beams=1, length_penalty=0.0,
                 compute_dtype="bfloat16", cache_dtype=None,
                 weight_dtype=None, _loaded=None):
        self.batch = int(batch)
        self.prompt_len = int(prompt_len)
        self.max_cache_len = int(max_cache_len or (prompt_len + 256))
        self.steps_per_call = int(steps_per_call)
        if self.max_cache_len < self.prompt_len + 1:
            raise ValueError(
                f"max_cache_len ({self.max_cache_len}) must be >= "
                f"prompt_len + 1 ({self.prompt_len + 1}) — the cache "
                "holds the prompt plus at least the first generated "
                "token's K/V")
        if num_beams > 1 and do_sample:
            raise ValueError("num_beams > 1 with do_sample=True is not "
                             "supported (beam search scores greedily)")
        self.cfg = GenerationConfig(
            do_sample=bool(do_sample), temperature=float(temperature),
            top_k=int(top_k), top_p=float(top_p),
            num_beams=int(num_beams),
            length_penalty=float(length_penalty),
            eos_token_id=eos_token_id, pad_token_id=int(pad_token_id),
            compute_dtype=str(compute_dtype),
            cache_dtype=None if cache_dtype is None else str(cache_dtype))
        self._state = None       # (tok, lens, done, flat_kvs)
        self._written = 0        # python-side high-water mark
        # a block emits steps_per_call tokens; tokens beyond what the
        # caller asked for are buffered here and drained first on the
        # next decode() (the device carry is always block-aligned)
        self._pending: Optional[np.ndarray] = None
        self.weight_dtype = normalize_weight_dtype(weight_dtype)
        self._wq = None
        if _loaded is not None:
            if self.weight_dtype is not None:
                raise ValueError(
                    "weight_dtype is a load-time quantization of the "
                    "in-process model; a deserialized artifact carries "
                    "its weights baked into the exported programs")
            (self._prefill, self._block, self._param_values) = _loaded
            self._model = None
            return
        if model is None:
            raise ValueError("LLMPredictor needs a model (or .load(path))")
        self._model = model
        model.eval()
        if self.weight_dtype is not None:
            self._wq = build_weight_quant_plan(model, self.weight_dtype)
        prefill, block = _build_serving_fns(
            model, self.batch, self.max_cache_len, self.cfg,
            self.steps_per_call, wq=self._wq)
        self._prefill = jax.jit(prefill)
        self._block = jax.jit(block)
        params, buffers = model_arrays(model)
        if self._wq is not None:
            self._param_values = self._wq.placeholder_params(params) + \
                [bf._value for bf in buffers] + self._wq.flat_values()
        else:
            self._param_values = [p._value for p in params] + \
                [bf._value for bf in buffers]

    # -- session --
    def _check_prompt(self, input_ids, seq_lens):
        ids = np.asarray(getattr(input_ids, "_value", input_ids))
        if ids.shape != (self.batch, self.prompt_len):
            raise ValueError(
                f"prompt must be [{self.batch}, {self.prompt_len}], got "
                f"{list(ids.shape)}")
        lens = (np.full((self.batch,), self.prompt_len, np.int32)
                if seq_lens is None
                else np.asarray(getattr(seq_lens, "_value", seq_lens)))
        if lens.shape != (self.batch,) or (lens < 1).any() or \
                (lens > self.prompt_len).any():
            # jit-side gathers clamp out-of-range indices silently, which
            # would decode plausible-but-wrong tokens — fail loudly here
            raise ValueError(
                f"seq_lens must be [{self.batch}] ints in "
                f"[1, {self.prompt_len}], got {lens.tolist()}")
        return ids, lens

    def start(self, input_ids, seq_lens=None, seed: int = 0) -> np.ndarray:
        """Prefill the prompt; returns the first generated token [B]
        (greedy/sampled) or the initial beams [B, K] (beam mode)."""
        ids, lens = self._check_prompt(input_ids, seq_lens)
        if self.cfg.num_beams > 1:
            out = self._prefill(self._param_values,
                                jnp.asarray(ids, jnp.int32),
                                jnp.asarray(lens, jnp.int32))
            tok0, lens_bk, done, lp, blen = out[:5]
            self._state = (tok0, lens_bk, done, lp, blen, list(out[5:]))
            # host-side beam tree: ids/parents/scores [T, B, K]
            k = self.cfg.num_beams
            self._tree_ids = [np.asarray(tok0)[None]]
            self._tree_parents = [np.tile(
                np.arange(k, dtype=np.int32)[None, None],
                (1, self.batch, 1))]
            self._tree_lp = [np.asarray(lp)[None]]
            self._tree_blen = [np.asarray(blen)[None]]
        else:
            key = jnp.asarray(
                np.asarray(jax.random.PRNGKey(seed), np.uint32))
            out = self._prefill(self._param_values,
                                jnp.asarray(ids, jnp.int32),
                                jnp.asarray(lens, jnp.int32), key)
            tok0, lens_d, done, key = out[0], out[1], out[2], out[3]
            self._state = (tok0, lens_d, done, key, list(out[4:]))
        self._written = int(lens.max()) + 1
        self._pending = None
        return np.asarray(out[0])

    def _run_block(self):
        if self.cfg.num_beams > 1:
            tok, lens, done, lp, blen, flat = self._state
            out = self._block(self._param_values, tok, lens, done, lp,
                              blen, *flat)
            toks, parents = np.asarray(out[0]), np.asarray(out[1])
            self._tree_lp.append(np.asarray(out[2]))
            self._tree_blen.append(np.asarray(out[3]))
            self._state = (out[4], out[5], out[6], out[7], out[8],
                           list(out[9:]))
            self._tree_ids.append(toks)
            self._tree_parents.append(parents)
            return None  # beam tokens are final only after backtrace
        tok, lens, done, key, flat = self._state
        out = self._block(self._param_values, tok, lens, done, key, *flat)
        toks = np.asarray(out[0])
        self._state = (out[1], out[2], out[3], out[4], list(out[5:]))
        return toks

    def decode(self, n: int) -> np.ndarray:
        """Decode ``n`` more tokens; returns [B, n] int32.  Beam mode
        has no incremental token stream (beams reorder retroactively):
        use ``generate()``."""
        if self.cfg.num_beams > 1:
            raise RuntimeError(
                "decode() is not available with num_beams > 1 — beam "
                "tokens are only final after the last step's backtrace; "
                "use generate(), which returns the best sequences")
        if self._state is None:
            raise RuntimeError("call start() before decode()")
        if n <= 0:
            return np.zeros((self.batch, 0), np.int32)
        buffered = 0 if self._pending is None else self._pending.shape[1]
        need_blocks = max(0, -(-(n - buffered) // self.steps_per_call))
        if self._written + need_blocks * self.steps_per_call \
                > self.max_cache_len + 1:
            raise ValueError(
                f"decoding {n} more tokens exceeds max_cache_len "
                f"({self.max_cache_len}); session has written "
                f"{self._written}")
        chunks: List[np.ndarray] = ([] if self._pending is None
                                    else [self._pending])
        for _ in range(need_blocks):
            chunks.append(self._run_block())
            self._written += self.steps_per_call
        all_toks = np.concatenate(chunks, axis=1)
        self._pending = all_toks[:, n:] if all_toks.shape[1] > n else None
        return all_toks[:, :n]

    def generate(self, input_ids, seq_lens=None,
                 max_new_tokens: int = 32, seed: int = 0) -> np.ndarray:
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        first = self.start(input_ids, seq_lens, seed=seed)
        if self.cfg.num_beams > 1:
            n_blocks = -(-(max_new_tokens - 1) // self.steps_per_call)
            if self._written + n_blocks * self.steps_per_call \
                    > self.max_cache_len + 1:
                raise ValueError(
                    f"decoding {max_new_tokens} tokens exceeds "
                    f"max_cache_len ({self.max_cache_len})")
            for _ in range(n_blocks):
                self._run_block()
                self._written += self.steps_per_call
            return self._finalize_beams(max_new_tokens)
        if max_new_tokens == 1:
            return first[:, None]
        rest = self.decode(max_new_tokens - 1)
        return np.concatenate([first[:, None], rest], axis=1)

    def _finalize_beams(self, max_new_tokens: int) -> np.ndarray:
        """Backtrace the accumulated (token, parent) tree and return the
        best beam per batch row under the length penalty."""
        ids = jnp.asarray(
            np.concatenate(self._tree_ids, axis=0)[:max_new_tokens])
        parents = jnp.asarray(
            np.concatenate(self._tree_parents, axis=0)[:max_new_tokens])
        seqs = np.asarray(_gather_tree_arrays(ids, parents))  # [T, B, K]
        # scores AT step T (not at the block boundary the scan ran to)
        lp = np.concatenate(self._tree_lp, axis=0)[max_new_tokens - 1]
        blen = np.concatenate(self._tree_blen,
                              axis=0)[max_new_tokens - 1].astype(
                                  np.float32)
        if self.cfg.length_penalty:
            scores = lp / (blen ** self.cfg.length_penalty)
        else:
            scores = lp
        best = scores.argmax(-1)                              # [B]
        return np.swapaxes(seqs, 0, 1)[
            np.arange(self.batch), :, best].astype(np.int32)

    # -- artifact --
    def save(self, path: str):
        """Export prefill + decode-block as portable StableHLO plus a
        weights pickle (one ``.ptpu_llm`` file).  The FULL decode
        configuration — greedy, sampled (temperature/top-k, PRNG key
        threaded through the artifact), or beam (num_beams, length
        penalty) — is baked into the exported programs, so a loaded
        artifact serves it without the model class (the reference's
        AnalysisPredictor deployment contract)."""
        if self._model is None:
            raise RuntimeError("save() needs the in-process model")
        if self._wq is not None:
            raise NotImplementedError(
                "save() with weight_dtype='int8'/'int4' is not wired — "
                "the exported artifact's weights pickle would carry the "
                "code/scale planes without the loader knowing the plan "
                "layout; quantized-weight predictors serve in-process")
        from jax import export as jax_export
        prefill, block = _build_serving_fns(
            self._model, self.batch, self.max_cache_len, self.cfg,
            self.steps_per_call)
        p_shapes = [jax.ShapeDtypeStruct(v.shape, v.dtype)
                    for v in self._param_values]
        b = self.batch
        k = self.cfg.num_beams
        ids_s = jax.ShapeDtypeStruct((b, self.prompt_len), jnp.int32)
        i32 = jax.ShapeDtypeStruct((b,), jnp.int32)
        booln = jax.ShapeDtypeStruct((b,), jnp.bool_)
        key_s = jax.ShapeDtypeStruct((2,), jnp.uint32)
        n_layers, hkv, d = self._model.kv_cache_spec()
        cache_dtype = jnp.dtype(self.cfg.cache_dtype
                                or self.cfg.compute_dtype)
        cache_rows = b * k
        from ..ops.pallas.decode_attention import cache_shape
        kv_s = [jax.ShapeDtypeStruct(
            cache_shape(cache_rows, hkv, self.max_cache_len, d),
            cache_dtype)
            for _ in range(2 * n_layers)]

        def _export(fn, *shapes):
            return jax_export.export(
                jax.jit(fn), platforms=("cpu", "tpu"))(*shapes).serialize()

        if k > 1:
            bk_i32 = jax.ShapeDtypeStruct((b, k), jnp.int32)
            bk_f32 = jax.ShapeDtypeStruct((b, k), jnp.float32)
            bk_bool = jax.ShapeDtypeStruct((b, k), jnp.bool_)
            rows_i32 = jax.ShapeDtypeStruct((cache_rows,), jnp.int32)
            pre_blob = _export(prefill, p_shapes, ids_s, i32)
            blk_blob = _export(block, p_shapes, bk_i32, rows_i32,
                               bk_bool, bk_f32, bk_i32, *kv_s)
        else:
            pre_blob = _export(prefill, p_shapes, ids_s, i32, key_s)
            blk_blob = _export(block, p_shapes, i32, i32, booln, key_s,
                               *kv_s)
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path + ".ptpu_llm", "wb") as f:
            pickle.dump({
                "version": 2,  # v2: PRNG key threaded / beam planes
                "prefill": pre_blob, "block": blk_blob,
                "values": [np.asarray(v) for v in self._param_values],
                "meta": {
                    "batch": self.batch, "prompt_len": self.prompt_len,
                    "max_cache_len": self.max_cache_len,
                    "steps_per_call": self.steps_per_call,
                    "eos_token_id": self.cfg.eos_token_id,
                    "pad_token_id": self.cfg.pad_token_id,
                    "do_sample": self.cfg.do_sample,
                    "temperature": self.cfg.temperature,
                    "top_k": self.cfg.top_k,
                    "top_p": self.cfg.top_p,
                    "num_beams": self.cfg.num_beams,
                    "length_penalty": self.cfg.length_penalty,
                    "compute_dtype": self.cfg.compute_dtype,
                    "cache_dtype": self.cfg.cache_dtype,
                }}, f)

    @classmethod
    def load(cls, path: str) -> "LLMPredictor":
        """Rebuild a serving session from a ``.ptpu_llm`` artifact —
        no model class needed (the Predictor deployment path)."""
        from jax import export as jax_export
        with open(path + ".ptpu_llm", "rb") as f:
            blob = pickle.load(f)
        if blob.get("version", 1) < 2:
            raise ValueError(
                "this .ptpu_llm artifact was saved by an older "
                "LLMPredictor whose serving programs lack the threaded "
                "PRNG key / beam planes — re-export it with save() "
                "(the block call protocol changed; a silent load would "
                "mis-slice the block outputs)")
        meta = blob["meta"]
        pre = jax_export.deserialize(blob["prefill"])
        blk = jax_export.deserialize(blob["block"])
        values = [jnp.asarray(v) for v in blob["values"]]
        return cls(
            batch=meta["batch"], prompt_len=meta["prompt_len"],
            max_cache_len=meta["max_cache_len"],
            steps_per_call=meta["steps_per_call"],
            eos_token_id=meta["eos_token_id"],
            pad_token_id=meta["pad_token_id"],
            do_sample=meta.get("do_sample", False),
            temperature=meta.get("temperature", 1.0),
            top_k=meta.get("top_k", 0),
            top_p=meta.get("top_p", 1.0),
            num_beams=meta.get("num_beams", 1),
            length_penalty=meta.get("length_penalty", 0.0),
            compute_dtype=meta["compute_dtype"],
            cache_dtype=meta["cache_dtype"],
            _loaded=(lambda pv, *a: pre.call(pv, *a),
                     lambda pv, *a: blk.call(pv, *a),
                     values))
