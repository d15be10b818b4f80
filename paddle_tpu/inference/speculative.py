"""Speculative decoding: drafters + the paged greedy verifier.

The decode loop after PR 1-3 still pays one target-model forward per
emitted token — the weight sweep that IS the decode roofline.
Speculative decoding (Leviathan et al., 2023) amortizes it: a cheap
DRAFTER proposes K candidate tokens, and ONE target forward scores all
K+1 positions against the paged KV arena (the K-wide generalization of
the chunked-prefill machinery); the longest draft prefix whose tokens
match the target's own greedy argmax is accepted and the first
mismatch position's argmax is emitted as the correction token.  Every
emitted token is therefore a token the sequential greedy loop would
have produced — output is token-for-token identical to ``generate()``,
only the forward count changes (1 + K positions per forward instead of
1, with mean accepted length deciding the win).

Two drafters, one interface (``Drafter.propose``):

- ``NGramDrafter`` — prompt-lookup / self-drafting (the vLLM
  ``prompt_lookup`` / transformers ``prompt_lookup_num_tokens``
  scheme): match the sequence's own trailing n-gram against its
  prompt+output history and propose the tokens that followed the most
  recent prior occurrence.  No second model, no device work,
  deterministic — it wins exactly on repetitive/structured streams
  (code, JSON, extraction, long copies) where history predicts the
  continuation.
- ``ModelDrafter`` — a small draft model sharing the target's
  tokenizer, run greedily through the existing compiled generation
  path (``GenerationMixin.generate`` — prefill + ``decode_scan_body``,
  ONE cached executable per drafter since the context is padded to a
  fixed capacity grid).  It wins when a distilled/smaller model tracks
  the target on ordinary text where n-gram lookup misses.

The VERIFIER lives half here (``build_spec_verify`` — the compiled
K+1-position target forward over the paged arena, greedy argmax at
every position) and half in the engine (host-side
``accept_drafts`` + per-slot length rewind).  KV rollback costs
nothing: the verify forward scatters all K+1 positions' K/V through
the slot's block table (pad/overflow columns trash-routed,
``models.generation.paged_verify_scatter``), and rejecting a draft
suffix simply does NOT advance the slot's ``lens`` past it — the
rejected entries are finite garbage behind the ``lens`` mask, inside
the slot's own blocks, and are overwritten by the next verify/decode
forward before ``lens`` ever reaches them (the same trash-block
discipline the serving engine already relies on for vacant rows).
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax.numpy as jnp
import numpy as np


class Drafter:
    """Draft-proposal interface for speculative decoding.

    ``propose(context, k)`` returns up to ``k`` candidate continuation
    tokens (1-D int32, possibly empty) for a sequence whose full token
    history — prompt plus everything emitted so far, INCLUDING the
    still-un-fed last token — is ``context``.  Proposals are pure
    suggestions: the verifier guarantees output correctness whatever
    comes back, so a drafter may be arbitrarily wrong, only ever
    arbitrarily slow."""

    def propose(self, context: np.ndarray, k: int) -> np.ndarray:
        raise NotImplementedError


class NGramDrafter(Drafter):
    """Prompt-lookup self-drafting: propose the continuation of the
    most recent PRIOR occurrence of the sequence's trailing n-gram.

    Longest n first (``max_ngram`` down to ``min_ngram``): a longer
    match is a stronger signal, and the first n with any prior
    occurrence wins.  Among occurrences the MOST RECENT one that still
    has a full k-token continuation is used — repetitive generation
    (loops, list items, copied spans) is best predicted by its latest
    iteration, but a match flush against the end of the context can
    only propose its truncated tail (on a constant run the latest
    match ends at the last token and would propose ONE token forever),
    so recency is traded for continuation length when needed.  Pure
    host-side numpy; deterministic; zero device work."""

    def __init__(self, max_ngram: int = 3, min_ngram: int = 1):
        if not 1 <= min_ngram <= max_ngram:
            raise ValueError(
                f"need 1 <= min_ngram <= max_ngram, got "
                f"{min_ngram}..{max_ngram}")
        self.max_ngram = int(max_ngram)
        self.min_ngram = int(min_ngram)

    def propose(self, context: np.ndarray, k: int) -> np.ndarray:
        ctx = np.asarray(context).reshape(-1).astype(np.int32)
        n_ctx = int(ctx.size)
        if k < 1 or n_ctx < self.min_ngram + 1:
            return np.zeros((0,), np.int32)
        from numpy.lib.stride_tricks import sliding_window_view
        for n in range(min(self.max_ngram, n_ctx - 1),
                       self.min_ngram - 1, -1):
            pattern = ctx[n_ctx - n:]
            # windows over ctx[:-1]: window i covers ctx[i:i+n], so its
            # end i+n <= n_ctx-1 — always a PRIOR occurrence, never the
            # trailing n-gram matching itself
            windows = sliding_window_view(ctx[:-1], n)
            hits = np.nonzero((windows == pattern).all(axis=1))[0]
            if hits.size:
                starts = hits + n              # just past each match
                full = starts[starts <= n_ctx - k]
                i = int(full[-1]) if full.size else int(starts[0])
                cont = ctx[i:i + k]
                if cont.size:
                    return cont.astype(np.int32)
        return np.zeros((0,), np.int32)


class ModelDrafter(Drafter):
    """Draft-model proposals through the existing compiled generation
    path: greedy ``generate()`` of the draft model continues the
    context by ``max_draft`` tokens in ONE cached-executable dispatch
    (prefill + ``decode_scan_body`` scan — the same machinery the
    target serves with, at draft-model size).

    The context is right-padded onto a fixed ``max_context`` grid (and
    LEFT-truncated to it when longer — drafts are suggestions, a
    sliding window only costs acceptance, never correctness), so every
    call reuses one compiled program.  The draft model must share the
    target's tokenizer/vocab; it needs no relation to the target
    otherwise — the verifier owns correctness."""

    def __init__(self, model, *, max_context: int, max_draft: int = 8,
                 compute_dtype: str = "float32", pad_token_id: int = 0):
        if max_context < 1 or max_draft < 1:
            raise ValueError(
                f"max_context/max_draft must be >= 1, got "
                f"{max_context}/{max_draft}")
        model.eval()
        self._model = model
        self._cap = int(max_context)
        self._k = int(max_draft)
        self._dtype = str(compute_dtype)
        self._pad = int(pad_token_id)

    def propose(self, context: np.ndarray, k: int) -> np.ndarray:
        if k < 1:
            return np.zeros((0,), np.int32)
        ctx = np.asarray(context).reshape(-1).astype(np.int32)
        ctx = ctx[-self._cap:]
        ids = np.full((1, self._cap), self._pad, np.int32)
        ids[0, :ctx.size] = ctx
        out = self._model.generate(
            ids, seq_lens=np.array([ctx.size], np.int32),
            max_new_tokens=self._k,
            max_cache_len=self._cap + self._k,
            compute_dtype=self._dtype)
        return np.asarray(out._value)[0, :min(k, self._k)].astype(
            np.int32)


def accept_drafts_sampled(drafts, u_row, accept_p_row, resample_row,
                          sample_row,
                          eos_token_id: Optional[int] = None
                          ) -> Tuple[List[int], int, int]:
    """The stochastic acceptance rule (speculative SAMPLING — Leviathan
    et al. 2023; Chen et al. 2023), specialized to one-hot draft
    distributions: draft j is accepted iff its accept-test uniform
    ``u_row[j]`` falls under ``accept_p_row[j] = p_j(draft_j)``
    (``min(1, p/q)`` at ``q = 1``); the first rejection emits the
    in-trace draw from the normalized residual ``max(0, p - q)``
    (``resample_row[j]``), and full acceptance emits the bonus draw
    from ``p_K`` (``sample_row[K]``).  Every draw was made in-trace
    with position-keyed PRNG (``sampling.spec_sampling_draws``), so
    this host walk only COMPARES and SELECTS — it consumes exactly one
    lane-1 draw per emitted stream position, which is what makes the
    output distribution equal the non-speculative sampled engine's and
    the PRNG rewind under rollback sound.  An accepted EOS stops
    acceptance (same contract as the greedy rule).

    Returns ``(emitted, accepted, resamples)`` — the emitted token
    list, the accepted-draft count, and whether a residual resample
    was consumed (0/1)."""
    emitted: List[int] = []
    a = 0
    while a < len(drafts) and float(u_row[a]) < float(accept_p_row[a]):
        emitted.append(int(drafts[a]))
        a += 1
        if eos_token_id is not None and emitted[-1] == eos_token_id:
            return emitted, a, 0
    if a < len(drafts):
        emitted.append(int(resample_row[a]))
        return emitted, a, 1
    emitted.append(int(sample_row[a]))
    return emitted, a, 0


def accept_drafts(greedy_row, drafts,
                  eos_token_id: Optional[int] = None
                  ) -> Tuple[List[int], int]:
    """The greedy acceptance rule: longest draft prefix matching the
    target's own argmax, plus one correction/bonus token.

    ``greedy_row[j]`` is the target's argmax AFTER consuming the last
    emitted token and drafts ``< j`` — i.e. the token the sequential
    greedy loop would emit at that point.  Draft j is accepted iff
    ``drafts[j] == greedy_row[j]``; at the first mismatch the target's
    own token is emitted instead (the correction), and when every
    draft survives the position after the last draft yields a free
    BONUS token — a verify forward always emits at least 1 and at most
    ``len(drafts) + 1`` tokens, all of them exactly the sequential
    greedy stream.  An accepted EOS stops acceptance (the sequential
    loop would have frozen there; tokens conditioned on a post-EOS
    context would diverge from its pad stream).

    Returns ``(emitted, accepted)`` — the emitted token list and the
    number of accepted draft tokens."""
    emitted: List[int] = []
    a = 0
    while a < len(drafts) and int(drafts[a]) == int(greedy_row[a]):
        emitted.append(int(drafts[a]))
        a += 1
        if eos_token_id is not None and emitted[-1] == eos_token_id:
            return emitted, a
    emitted.append(int(greedy_row[a]))
    return emitted, a


def build_spec_verify(model, cfg, steps: int, kv_int8: bool = False,
                      samp_flags=(False, False, False, False),
                      lora=False, wq=None, shard=None):
    """The compiled verifier program: ONE target forward scores
    ``steps`` positions per slot (the last emitted token plus up to
    ``steps - 1`` draft candidates) against the paged KV arena.

    Generalizes the chunked-prefill program (``build_chunk_prefill``)
    from batch-1 x shared-start to per-row starts over the whole slot
    mix (``models.*.verify_step`` / ``paged_verify_scatter`` /
    ``decode_attention_paged_multi``), and the decode block from 1 to
    ``steps`` positions per dispatch.  ``samp_flags`` (see
    ``_build_paged_decode_block``) selects the output protocol:

    - all-greedy mix: every position's argmax of the processed logits
      — the longest-matching-prefix acceptance path (``accept_drafts``)
      — and nothing else; bit-exact with the pre-sampling program for
      default rows.
    - sampled mix: argmax PLUS the position-keyed stochastic-sampling
      draws (``sampling.spec_sampling_draws``: the accept-test
      uniforms, per-draft acceptance probabilities ``p_j(d_j)``,
      residual resamples and full samples) consumed by
      ``accept_drafts_sampled`` — the distribution-preserving
      speculative-sampling protocol.  Greedy rows inside a sampled mix
      still walk the argmax path on the host; their extra draws are
      discarded.

    Token-mask constrained rows never reach a verify (the engine
    rejects ``mask_processor`` + ``spec_decode`` at submit: a draft
    position's mask depends on host state the drafter bypasses), so
    the bias flag is structurally False here.  ``kv_int8`` selects the
    quantized paged cache — the verify forward then reads int8 codes +
    scales and its K/V writes quantize on append, so drafting/
    acceptance runs against exactly the arena the decode path
    maintains.  Signature:
    ``(p_values, toks [B, C], lens [B], n_valid [B],
    tables [B, max_blocks], samp, *flat_arenas) ->
    (greedy [B, C][, u, accept_p, resample, sample], *flat_arenas)``.

    ``lora=True`` inserts a ``lora`` pytree argument after ``samp``
    (per-row adapter slot ids + stacked arenas; see
    ``_build_paged_decode_block``) and traces the verify under an
    active adapter context — each spec row's draft positions are
    scored by ITS adapter's target distribution, so greedy acceptance
    stays token-exact against that adapter's sequential stream.

    ``wq`` selects quantized-weight serving (see
    ``_build_paged_decode_block``): the verify forward scores draft
    positions through the SAME codes+scales the decode path emits
    with, so acceptance compares like with like."""
    if cfg.num_beams > 1:
        raise ValueError(
            "speculative verification does not support beam search — "
            "it scores K beams per request, not K draft positions of "
            "one stream")
    if steps < 1:
        raise ValueError(f"verify steps must be >= 1, got {steps}")
    if samp_flags[3]:
        raise ValueError(
            "token-mask constrained decoding cannot ride a verify "
            "forward (mask state is host-side and per emitted token)")
    from .llm import (_constrain_arenas, _flatten_paged_kvs, _kv_latent,
                      _pack_paged_kvs, _param_swapper, _shard_scope)
    from .sampling import spec_greedy_rows, spec_sampling_draws
    from ..models.lora import gather_lora, lora_context

    _with_params = _param_swapper(model, cfg, wq=wq)
    sampled, _filtered, penalty, _bias = samp_flags

    def _verify(toks, lens, n_valid, tables, samp, flat_arenas):
        kvs = _pack_paged_kvs(_constrain_arenas(flat_arenas, shard),
                              tables, kv_int8, _kv_latent(model))
        with _shard_scope(shard):
            logits, kvs_f = model.verify_step(toks, lens, n_valid, kvs)
        pres = samp["presence"] if penalty else None
        flat_f = tuple(_constrain_arenas(_flatten_paged_kvs(kvs_f),
                                         shard))
        if sampled:
            draws = spec_sampling_draws(logits, toks, samp,
                                        samp_flags, pres)
            return draws + flat_f
        greedy = spec_greedy_rows(logits, toks, samp, samp_flags,
                                  pres)
        return (greedy,) + flat_f

    if lora:
        def verify_pure(p_values, toks, lens, n_valid, tables, samp,
                        lora_planes, *flat_arenas):
            def run():
                with lora_context(gather_lora(lora_planes)):
                    return _verify(toks, lens, n_valid, tables, samp,
                                   flat_arenas)
            return _with_params(p_values, run)
    else:
        def verify_pure(p_values, toks, lens, n_valid, tables, samp,
                        *flat_arenas):
            return _with_params(
                p_values,
                lambda: _verify(toks, lens, n_valid, tables, samp,
                                flat_arenas))

    return verify_pure
