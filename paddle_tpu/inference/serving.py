"""Continuous-batching LLM serving engine over a PAGED KV-cache block
pool (iteration-level scheduler + block-granular memory manager +
shared-prefix caching + chunked prefill).

The first engine generation reserved a contiguous ``num_slots x
max_cache_len`` KV region per slot and prefilled every prompt whole in
a batch-1 pass: short requests stranded HBM at worst-case capacity,
shared system prompts were recomputed on every admission, and one long
prefill stalled every decoding slot for the full prompt pass.  This
module keeps that engine's scheduler contract (iteration-level
admission, mixed-fill decode blocks, donated caches, greedy parity
with per-request ``generate()``) and rebuilds the memory system along
the PagedAttention (Kwon et al., vLLM) + Sarathi-Serve (chunked
prefill) design, restricted to what XLA's static shapes allow:

- **Block pool**: each layer's K/V live in ONE ``[num_blocks + 1,
  block_len, H_kv*D]`` arena (the ``+1`` row is the trash block —
  statically-shaped writes from vacant/frozen slots and prompt pad
  tails are redirected there instead of being shape-masked).  A
  host-side free-list (``BlockPool``) maps logical blocks to arena
  rows; per-slot block tables ``[num_slots, max_blocks_per_slot]``
  int32 are the only NEW per-step host->device transfer.  Effective
  concurrency is bounded by blocks actually USED
  (``ceil((prompt + new - 1) / block_len)`` per request), not by
  ``num_slots x max_cache_len``.
- **Tiered radix-tree prefix caching** (``prefix_cache_mode="radix"``,
  the default — see ``inference/prefixcache.py``): prompts are matched
  token-level against a radix tree whose nodes own runs of token ids
  mapped to block spans (RadixAttention, SGLang).  Admission maps the
  matched span's FULL blocks straight into the new slot's table and
  prefill starts after them; at least the block holding the prompt's
  last token is always recomputed (its hidden state is needed to
  sample the first token), so shared blocks are immutable by
  construction and no copy-on-write is ever needed.  Unpinned cached
  blocks park in an LRU — and when the free list runs dry, reclaim
  DEMOTES their exact at-rest bytes to a host-RAM tier instead of
  forgetting them: a later hit on a host-resident span allocates
  fresh blocks and swaps the bytes back in (the same gather/scatter
  programs preemption uses), byte-identical to never having evicted.
  Admission is cache-aware: within a scheduling class, queued
  requests whose matched prefix is HBM-resident admit first, then
  host-resident, then cold — a strict tie-break, so traces with no
  shared prefixes schedule exactly as before.
  ``prefix_cache_mode="none"`` turns the cache off.
- **Chunked prefill**: prompts are computed ``chunk_len`` tokens at a
  time alongside the shared decode block — a long prompt no longer
  stalls in-flight decoding for its full prompt pass, and TTFT of
  queued requests overlaps decode instead of serializing behind it.
  For each decode step its block will run, a ``step()`` runs ONE
  chunk while no more than ``steps_per_call`` slots wait for their
  prompt, and a ``steps_per_call``-th of the waiting slots' chunks
  beyond that (``_prefill_chunks``): a backlog of prompts (many
  slots, or a burst of arrivals) is worked off geometrically instead
  of one chunk a step, which would hold a wide batch at a fraction of
  its slots, and a slot vacated inside a block rides the next.  A
  step's chunks are enqueued back to back, and the first tokens of the
  prompts that finish among them LAND TOGETHER after the last chunk is
  enqueued (``_land_first_tokens``: one fetch, then each request's
  first-token bookkeeping in enqueue order), so the chip empties once
  a step and not after every prompt's final chunk.
- **Decode blocks**: a ``step()`` dispatches ONE compiled program of
  ``steps_per_call`` decode steps whenever some rider is owed that
  many tokens; riders owed fewer finish inside the block on the
  device (the in-trace finish bitmap freezes them at their budget as
  at an EOS) and the harvest hands each exactly its tokens.  Only a
  mix whose every rider is inside its last ``steps_per_call - 1``
  tokens, or one that holds a mask-constrained row, takes single
  steps.  The host round trip (plan, table push, dispatch, fetch,
  harvest) is paid once a block, not once a token.
- **Paged reads**: decode attention goes through the block table — the
  Pallas flash-decode kernel gained a block-table DMA variant
  (``decode_attention_paged``; gate reasons ``paged_ok`` /
  ``paged_block_len``) with a gather-based XLA path as the universal
  fallback.  Chunk prefill always uses the gather-based XLA path.
- **Donated arenas**: the arenas are donated into both compiled
  programs (chunk prefill and decode block), so steady-state serving
  still allocates no per-step HBM and never materializes a second
  copy of the pool.

Greedy output stays token-for-token identical to per-request
``generate()`` across block reuse, prefix hits and chunked prefill:
every position of a sequence's dense view is either masked (past
``lens``) or was written by exactly the math the dense engine ran at
that position, and row-independence of the decode body is unchanged.

**Int8 KV cache** (``kv_cache_dtype="int8"``): decode at scale is
KV-bandwidth-bound — the step streams the arena once per token — so
the arenas can be stored QUANTIZED: int8 codes plus parallel
per-entry per-kv-head f32 absmax scale arenas.  Every writer
(chunked prefill, decode scatter, the speculative verify scatter)
quantizes on append (``models.generation.*_q``); every reader
dequantizes on read through ``paged_dequant_view``, on the chip and on
the CPU alike (route reason ``int8_scale_lanes``: the cache has no
Pallas kernel).  Twice the KV blocks fit the same arena budget;
scheduling is unchanged — block tables, the radix tree, trash-block
discipline and spec-decode rollback all operate on block indices,
never on cache bytes.

**Speculative decoding** is a per-request mode on top
(``submit(spec_decode=K)``, greedy engines only): each scheduler
iteration runs at most one batched K+1-position verify forward over
the spec-mode slots (drafter proposals + the paged verify machinery of
``inference/speculative.py``) alongside the prefill chunk and the
plain decode block, emitting the accepted draft prefix plus one
correction token per slot — token-for-token the sequential greedy
stream, at a fraction of the target forwards when drafts verify.

**Overload resilience** (preemption + host-RAM swap + SLO-aware
scheduling): under sustained overload a FIFO scheduler has no
graceful-degradation story — a long-tail request wedges the pool
behind the head-of-line valve and an unbounded queue just grows.
This engine degrades deliberately instead:

- ``submit(priority=, deadline_s=, max_queue_delay_s=)`` makes the
  queue a priority-then-EDF order (higher priority first, earlier
  deadline first within a priority, FIFO within a class — so traces
  that never pass the new kwargs schedule exactly as before);
- a bounded queue (``max_queue=``) sheds on arrival: a full queue
  either evicts its worst queued request (strictly lower class than
  the arrival, state ``"shed"``) or rejects the arrival with a typed
  ``AdmissionError`` — never silent unbounded growth;
- queued requests whose wait exceeds their ``max_queue_delay_s``
  finish with state ``"timeout"`` instead of being served late;
- when admission cannot allocate blocks, the scheduler PREEMPTS a
  strictly-worse victim (policy: lowest priority, then latest
  deadline, then most remaining work): the victim's pinned blocks are
  copied out of the arenas into a host-RAM tier at EXACT at-rest
  bytes (float K/V or int8 codes + scale planes; ``llm.py``'s
  ``build_swap_out_gather``), its HBM blocks release, and it parks on
  a swap list.  Re-admission re-allocates fresh blocks and re-scatters
  the saved bytes (``build_swap_in_scatter``, donation-matched) and
  restores the slot's ``tok``/``lens`` carries — so the resumed
  request's greedy output stays token-for-token identical to
  uninterrupted ``generate()``, and the position-keyed per-request
  PRNG (PR 6) makes resumed SAMPLED streams free too.
- ``run(wall_timeout_s=...)`` turns a wedged pool into a diagnosable
  ``EngineStalledError``; ``inference/faultinject.py`` injects
  allocation exhaustion / forced swaps / step stalls so tests prove
  no wedge, no block leak and no refcount drift
  (``BlockPool.check()``) under adversarial schedules.

**Dispatch-ahead step pipeline** (``async_dispatch=True``, the
default): JAX dispatch is asynchronous — a compiled call returns
device futures immediately — and the lockstep engine used to throw
that away by materializing every output (``np.asarray``) right after
every dispatch, so the host scheduler (admit, block tables, sampling
planes, ledger) ran SERIALLY with device compute.  This engine splits
``step()`` into a host-only PLAN phase and a deferred HARVEST phase:

- the decode block's outputs (``toks``/``tok``/``lens``/``done``
  carries) stay un-materialized device arrays in a pending-harvest
  record; the NEXT iteration plans on one-step-stale host truth,
  feeds the device carries straight back into its own dispatch
  (double-buffered — the traced scan self-feeds tokens, so staleness
  never reaches the math; sampled rows get their position-keyed PRNG
  plane advanced by the in-flight block's size), and only AFTER that
  dispatch is enqueued forces the previous outputs to host — the
  host-scheduler slice PR 9 measured now runs under device time.
- a harvest is deferred ONLY on iterations whose scheduling is
  provably output-independent: no rider can finish (no EOS configured,
  no budget exhausting inside the block), no token-mask / repetition-
  penalty row needs the emitted token host-side, and no speculative
  slot needs an accept/rollback decision.  Everywhere host truth is
  semantically required the iteration degrades to today's sync
  behavior and charges one ``serving.async.syncs{reason=}`` — so the
  async engine's outputs are token-for-token ``generate()``-exact and
  its scheduling (admissions, dispatch counts, flight-recorder event
  sequence modulo wall and harvest lag) is byte-identical to the
  ``async_dispatch=False`` kill-switch arm BY CONSTRUCTION.
- a prompt's first token is such a place, once a step: the final
  chunk's sampled token stays a device array while the step's further
  chunks are enqueued, and all of a step's first tokens become host
  truth together before the verify and the plan read them (one
  ``chunk_final`` flush and one fetch, after the last chunk is
  enqueued).  The kill-switch arm lands each behind its own chunk through
  the same function, so between the arms the events a landing emits
  (a finish at the first token, a handoff) move behind the step's
  later ``prefill_chunk`` events and nothing else moves: every
  request's events, the finished order and the counters are equal.
- a block whose riders end by their budget, and can end no other way,
  is the plan's to know: before its sync harvest the slots they leave
  are admitted into and the next step's first chunks enqueued
  (``_admit_ahead``), their first tokens landing with the next step's.
  Both arms take that order, so it moves no request's events.
- the tiered prefix cache's demote gather rides the same pipeline:
  reclaim ENQUEUES the at-rest-bytes gather during plan and the host
  copies reconcile lazily at the next harvest point (the PR-8
  "overlapped swap-in" leftover; promotion scatters were already
  enqueue-only).
- time spent blocking on a PREVIOUS iteration's arrays lands in
  ``serving.step.overlap_seconds`` (never in ``host_seconds``), and
  injected fault stalls in ``serving.fault.stall_seconds``.
- **depth-S** (``async_depth=S``, default 1): the decode block's
  ``done`` carry is an IN-TRACE FINISH BITMAP (EOS hit or budget
  exhausted — a ``budget`` carry counts each row's remaining tokens
  down in-trace), so at S >= 2 an EOS-configured engine stops
  syncing every iteration: the pending record becomes a bounded
  FIFO deque, the host polls the bitmap at harvest — one dispatch
  late — and a finished rider's slot frees one plan later (a
  deterministic, flight-recorder-stamped lag; dispatches enqueued
  before the finish was observable ride out with the row frozen
  device-side and are skipped at harvest, so ledger/sweep/token
  accounting stays exactly lockstep's).  Provably eventless windows
  (nothing queued/swapped, no chunk, no mask/penalty/spec row,
  budget headroom beyond the window) dispatch S iterations as ONE
  fused scan program, re-split per iteration at harvest.  Depth 1
  keeps PR 10's scheduling-identity contract bit-for-bit.

**Multi-tenant batched LoRA serving** (``adapter_store=`` +
``submit(adapter=, tenant=)``): K fine-tuned LoRA variants of the one
base model decode in the same continuous batch — a paged
``AdapterStore`` (``inference/lora.py``: stacked per-target A/B
arenas + free list + pins + LRU + host-tier demotion, the BlockPool
discipline applied to adapter weights) holds the hot variants in HBM,
admission pins a request's adapter resident (head-of-line wait when
every slot is pinned, exactly like block exhaustion), and dispatches
whose riding mix has >= 1 adapter row compile gathered-BGMV program
variants (``models/lora.py``): per-row slot ids gather stacked A/B
and two small einsums add each row's low-rank delta inside the
attention projections.  Base rows gather the all-zero null row (an
exact ``+ 0.0``), adapter-free dispatches keep today's exact
programs, and K=1 batched output is token-for-token the
merged-weights ``generate()`` of that adapter.  Adapter ids are pure
host-plan state pinned with the riding set, so the dispatch-ahead
pipeline carries them one-step-stale with no new sync reason.
**Fair-share admission** rides along: ``submit(tenant=)`` buckets
requests, and within a priority/EDF class the candidate order becomes
deficit-weighted round-robin — the least weight-normalized-served
tenant admits next (service charged at admission as prompt + budget),
so a bursty tenant cannot starve a steady one; single-tenant traces
see a constant fair term and schedule byte-identically to the
pre-tenant engine.  The goodput ledger and SLO-attainment counters
carry a per-tenant label, and admit flight-recorder events carry
``adapter``/``tenant``/``deficit``.

**Token streaming** (``submit(stream=True)``): the front-door half of
PR 12 — a :class:`TokenStream` handle whose ``read()`` drains the
tokens that are already host truth, which on the dispatch-ahead
engine means exactly the harvest points: streaming forces nothing,
adds no entry to ``ASYNC_SYNC_REASONS``, and the concatenated flushes
are token-for-token the non-streamed output.  ``load_report()`` is
the matching scheduler-facing surface: one host-side snapshot (queue
depth, blocks free, HBM-resident adapters, radix root stats) the
replica router of ``inference/router.py`` reads as its load signal.
"""

from __future__ import annotations

import logging
import time
import warnings
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.generation import (GenerationConfig, LatentCacheSpec,
                                 SlotStateError, init_paged_kv_arena,
                                 init_paged_latent_arena, init_slot_state,
                                 model_arrays, slot_state_spec)
from ..observability import collector as obs_collector
from ..observability import metrics as obs_metrics
from ..observability.flightrec import ENGINE_EVENT, FlightRecorder
from ..observability.spans import instant as _span_instant
from ..observability.spans import span as _span
from ..ops.pallas import decode_attention as _decode_attn
from .llm import (ArenaSharding, _build_paged_decode_block,
                  build_chunk_prefill, build_fused_decode_window,
                  build_swap_in_scatter, build_swap_out_gather,
                  build_weight_quant_plan, normalize_weight_dtype)
from .prefixcache import HostTier, RadixPrefixCache
from .sampling import (MASK_BIAS, SamplingParams, base_key, flags_of,
                       row_planes)
from .speculative import (NGramDrafter, accept_drafts,
                          accept_drafts_sampled, build_spec_verify)


class AdmissionError(RuntimeError):
    """A bounded queue (``ServingEngine(max_queue=N)``) refused an
    arrival: the queue is full and no queued request is of strictly
    lower scheduling class than the new one, so the ARRIVAL is the
    right thing to shed.  Typed so callers can degrade (retry with
    backoff, spill to another replica, fail the RPC with 429) instead
    of pattern-matching a message."""

    def __init__(self, msg, *, queue_depth=None, max_queue=None):
        super().__init__(msg)
        self.queue_depth = queue_depth
        self.max_queue = max_queue


class EngineStalledError(RuntimeError):
    """``run(wall_timeout_s=...)`` exceeded its wall budget without
    draining — the diagnosable form of a wedged scheduler (pool
    exhausted with nothing running, an injected fault, a dispatch that
    never returns).  The message carries the queue / slot / block-pool
    state at the moment of the raise so the wedge is debuggable from
    the exception alone.  ``step()`` also raises it directly under an
    injected PERMANENT stall (``FaultInjector.stall_forever``) — the
    watchdog's verdict on a dispatch that will never return, and one
    of the three replica fault signals the router's health model
    consumes."""


class ReplicaKilledError(RuntimeError):
    """The replica died: an injected kill (``FaultInjector.
    kill_at_step``) raised at the top of ``step()``, modeling what a
    multi-process deployment sees as a lost connection to a crashed
    worker.  Device state (arenas, in-flight dispatches) is gone;
    host-side request records and host-RAM swap parcels survive —
    which is exactly the split the router's failover recovery
    (migrate reachable parcels, recompute the rest) leans on."""


class PoisonedDispatchError(RuntimeError):
    """A dispatch came back corrupted: the engine's harvest validation
    found token ids outside the model vocabulary — the int-token
    analogue of non-finite logits (a device fault, a corrupted
    collective, an OOB write).  Raised BEFORE the corrupt outputs are
    adopted as host truth, so no request's token stream ever carries
    a poisoned value; the router treats the raise as a replica-fatal
    health signal and fails the replica's requests over."""


# the goodput ledger's closed waste vocabulary: every dispatched
# token-position is either useful or charged to exactly one of these
# (serving.goodput.wasted_tokens{reason=}).  ``recompute_preempt`` is
# structurally ZERO in this engine — preemption swaps exact at-rest
# bytes, never recomputes — and is kept in the vocabulary as the
# ledger's proof of that (a recompute-mode preemption path would
# charge it; see notes.md PR 9).
GOODPUT_REASONS = (
    "spec_reject",
    "recompute_preempt",   # graftlint: disable=vocab — structurally
    #                        zero by design (exact-bytes preemption
    #                        never recomputes); the entry IS the proof,
    #                        so no emit site exists on purpose
    "recompute_cache",
    "pad",
)

# the dispatch-ahead pipeline's closed forced-sync vocabulary: every
# iteration that must materialize device outputs EARLY — instead of
# after the next dispatch was enqueued — charges exactly ONE of these
# to serving.async.syncs{reason=}.  The vocabulary is closed so tests
# (and dashboards) can assert that syncs happen only for documented,
# semantically-required reasons:
ASYNC_SYNC_REASONS = (
    "eos",          # EOS detection must observe every emitted token
    "budget",       # a rider's token budget can exhaust inside the block
    "mask",         # a token-mask row's host state machine needs the token
    "penalty",      # a repetition-penalty presence plane is host-built
    "spec",         # speculative accept/rollback is a host decision
    "chunk_final",  # a prompt's final chunk samples the first token:
    #                 a step's first tokens land together, the pending
    #                 block flushed first, after its last chunk is
    #                 enqueued (``_land_first_tokens``)
    "resume",       # a swap-in rewrites the slot's host carries
    "preempt",      # a swap-out reads the slot's host carries
    "cancel",       # cancel() must know which tokens already exist
    "drain",        # run() is about to raise/hand control to the caller
)

# the terminal request states shared by the engine and the router: a
# request in any of these will never emit another token.  "failed" is
# the router's failover terminal — a request whose replica died and
# whose bounded retry budget ran out; the engine itself never assigns
# it (an engine-local request either finishes or is dropped by its
# caller)
TERMINAL_STATES = ("finished", "timeout", "shed", "cancelled", "failed")

# closed label vocabularies for the swap/shed/cancel counters (shared
# by the engine and the router; graftlint's vocab pass resolves every
# literal label site against these and flags drift/dead entries):
# which tier traffic a swap moved ("preempt" = a victim's blocks,
# "cache" = prefix-cache demotion/promotion) …
SWAP_REASONS = ("preempt", "cache")
# … why a request was shed from a bounded queue ("evicted" = displaced
# by a strictly-higher-class arrival, "rejected" = the arrival itself
# was refused with AdmissionError) …
SHED_REASONS = ("evicted", "rejected")
# … and which phase a cancel() caught the request in ("router" is the
# front-door queue above any engine).  "prefill"/"decode" reach the
# counter dynamically via req.state, so the vocab pass checks literal
# membership but skips dead-entry detection for this one.
CANCEL_PHASES = ("queued", "prefill", "decode", "swapped", "router")

# why a request left a prefill-role replica with its KV parcel instead
# of decoding in place (serving.handoff.requests{reason=}).  Today the
# only trigger is the disaggregation point itself — the prompt's final
# chunk sampled tok0, so decode belongs on a decode-capable replica —
# kept closed so dashboards can assert no undocumented handoff exists.
HANDOFF_REASONS = ("chunk_final",)

# the role axis of disaggregated serving (ROADMAP item 2): "both" is
# the monolithic default (byte-identical to every pre-role trace),
# "prefill" replicas run prompt chunks and hand each request off at
# its final chunk, "decode" replicas only ever resume migrated
# parcels — they reject fresh submits and never dispatch a prefill
# chunk.
ENGINE_ROLES = ("prefill", "decode", "both")

# sub-ms resolution for the host-vs-dispatch step split: on real
# accelerators the host scheduler slice this histogram isolates is the
# tens-of-microseconds gap the dispatch-ahead pipeline (ROADMAP item 2)
# must hide under device time
_STEP_BUCKETS = (
    0.00005, 0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
    0.025, 0.05, 0.1, 0.25, 1.0, 5.0,
)


class _ServingInstruments:
    """The engine's registry handles plus per-engine baselines.

    Instruments live in a (usually process-wide) ``MetricsRegistry`` —
    a second engine in the same process shares them — so each engine
    snapshots its counters at construction and ``stats()`` reports the
    delta while the registry keeps the process-wide totals an exporter
    scrapes.  Two sharing caveats: (1) the delta is exact for engines
    used SEQUENTIALLY on one registry; engines running interleaved on
    the same registry see each other's increments — pass each a
    private ``registry=`` for exact isolation; (2) disabling the
    registry freezes the counters, so ``stats()`` stops advancing too
    (the price of stats() being registry-derived); (3) the Pallas
    route counter (``pallas.decode_attention.route``) always lives in
    the process-default registry — the dispatch gate has no engine
    context — so a private registry's export carries no route series."""

    def __init__(self, registry):
        self.registry = registry
        r = registry
        self.prefills = r.counter(
            "serving.prefills", "prompt prefills completed (requests "
            "that reached their first token)")
        self.first_token_fetches = r.counter(
            "serving.first_token_fetches", "host fetches of first "
            "tokens: one for all the prompts whose final chunk a step "
            "enqueued (a dispatch-ahead engine), one a prompt on the "
            "lockstep arm; serving.prefills over this is the first "
            "tokens one wait on the device buys")
        self.admits_ahead = r.counter(
            "serving.admits_ahead", "admissions into a slot that the "
            "decode block in flight leaves by its rider's budget, made "
            "before the block's outputs are fetched (a dispatch-ahead "
            "engine); serving.prefills is what it is a share of")
        self.prefill_chunks = r.counter(
            "serving.prefill_chunks", "prompt chunks computed (chunked-"
            "prefill dispatches; prefix-cached blocks never become "
            "chunks)")
        self.decode_steps = r.counter(
            "serving.decode_steps", "decode steps executed (block size "
            "x dispatches)")
        self.busy_slot_steps = r.counter(
            "serving.busy_slot_steps",
            "decode step x slot cells holding a live PLAIN-decode "
            "request: the cells each rider of a block is owed, so the "
            "cells a row spends frozen behind its budget are not in it "
            "(spec-mode slots progress via verify forwards, not decode "
            "steps, and are excluded — see serving.spec.*)")
        self.block_dispatches = r.counter(
            "serving.block_dispatches", "compiled decode block calls")
        self.moe_expert_tokens = r.counter(
            "moe.expert_tokens", "live decode rows routed to each expert, "
            "summed over decode steps and expert layers (fed at harvest "
            "from the decode block's counters)", labels=("expert",))
        self.moe_layer_steps = r.counter(
            "moe.layer_steps", "(expert layer, decode step) pairs counted")
        self.moe_experts_touched = r.counter(
            "moe.experts_touched", "experts that got at least one live "
            "row, summed over the (expert layer, decode step) pairs")
        self.moe_zero_expert_picks = r.counter(
            "moe.zero_expert_picks", "live decode rows' top-k picks that "
            "fell on zero-compute (identity) experts, summed over decode "
            "steps and expert layers (a router that has such experts)")
        self.moe_expert_picks = r.counter(
            "moe.expert_picks", "live decode rows' top-k picks, real and "
            "zero-compute experts alike, summed as moe.zero_expert_picks "
            "is")
        self.tokens_emitted = r.counter(
            "serving.tokens_emitted", "tokens emitted to requests "
            "(prefill first-tokens + the cells each rider of a decode "
            "block is owed, counted at dispatch; exact to the budget, "
            "while a request hitting EOS mid-block still counts the "
            "cells behind it)")
        self.requests_submitted = r.counter(
            "serving.requests_submitted", "requests accepted into the queue")
        self.requests_finished = r.counter(
            "serving.requests_finished", "requests retired (EOS or budget)")
        self.requests_cancelled = r.counter(
            "serving.requests_cancelled",
            "requests dropped by cancel(); the label says which phase "
            "the request was cancelled from (queued / prefill / "
            "decode / swapped)", labels=("phase",))
        self.preempts = r.counter(
            "serving.preempt.requests",
            "in-flight requests preempted (KV blocks swapped to the "
            "host-RAM tier, slot freed) so a higher-class request "
            "could be admitted — or a fault-injection forced swap")
        self.preempt_resumes = r.counter(
            "serving.preempt.resumes",
            "preempted requests re-admitted from the swap list (fresh "
            "blocks allocated, saved bytes re-scattered, decode state "
            "restored)")
        self.swap_out_blocks = r.counter(
            "serving.swap.blocks_out",
            "KV blocks copied out of the arenas into the host-RAM "
            "tier; reason='preempt' at preemption, reason='cache' "
            "when the prefix cache demotes a reclaimed block",
            labels=("reason",))
        self.swap_in_blocks = r.counter(
            "serving.swap.blocks_in",
            "KV blocks re-scattered from the host-RAM tier into "
            "freshly allocated arena rows; reason='preempt' at "
            "resume, reason='cache' at a host-tier prefix hit",
            labels=("reason",))
        self.swap_out_bytes = r.counter(
            "serving.swap.bytes_out",
            "at-rest KV bytes (codes + scale planes for the int8 "
            "cache) swapped out to host RAM, by reason",
            labels=("reason",))
        self.swap_in_bytes = r.counter(
            "serving.swap.bytes_in",
            "at-rest KV bytes swapped back into the arenas, by reason",
            labels=("reason",))
        self.swap_host_blocks = r.gauge(
            "serving.swap.host_blocks",
            "KV blocks currently parked in the host-RAM tier (hwm = "
            "peak footprint in blocks); reason='preempt' = swapped "
            "requests awaiting resume, reason='cache' = demoted "
            "prefix-cache spans", labels=("reason",))
        self.handoff_requests = r.counter(
            "serving.handoff.requests",
            "requests that left a prefill-role replica with their KV "
            "parcel staged for a decode replica instead of decoding "
            "in place, by closed reason vocabulary (HANDOFF_REASONS: "
            "today only 'chunk_final' — the disaggregation point "
            "itself)", labels=("reason",))
        self.handoff_blocks = r.counter(
            "serving.handoff.blocks",
            "KV blocks gathered into handoff parcels at chunk-final "
            "(exact at-rest bytes; the decode replica re-scatters "
            "the same count, so a fleet's migrated-block ledger "
            "balances)")
        self.handoff_bytes = r.counter(
            "serving.handoff.bytes",
            "at-rest KV bytes (codes + scale planes for the int8 "
            "cache) gathered into handoff parcels at chunk-final")
        self.role = r.gauge(
            "serving.role",
            "1 for this engine's disaggregation role ('prefill', "
            "'decode', or the monolithic default 'both'); a fleet "
            "registry's per-label sum counts replicas by role",
            labels=("role",))
        self.shed = r.counter(
            "serving.shed.requests",
            "requests shed by the bounded queue: 'evicted' = a queued "
            "request displaced by a strictly-higher-class arrival, "
            "'rejected' = an arrival refused with AdmissionError",
            labels=("reason",))
        self.timeouts = r.counter(
            "serving.timeout.requests",
            "queued requests finished with status 'timeout' because "
            "their wait exceeded max_queue_delay_s — shed-by-deadline "
            "instead of served-late")
        self.prefix_hits = r.counter(
            "serving.prefix_hits", "prompt blocks mapped from the prefix "
            "cache at admission instead of being recomputed")
        self.prefix_misses = r.counter(
            "serving.prefix_misses", "matchable prompt blocks that had "
            "to be computed (no cached twin at admission)")
        self.prefix_hit_tokens = r.counter(
            "serving.prefix.hit_tokens",
            "prompt tokens served from the prefix cache at admission "
            "(mapped blocks x block_len — token-granular cache "
            "effectiveness; PR-3's serving.prefix_hits counts whole "
            "blocks only)")
        self.prefix_partial_hits = r.counter(
            "serving.prefix.partial_hits",
            "admissions whose token-level radix match extended past "
            "the last mappable full block (the partial tail was "
            "recomputed)")
        self.prefix_host_hits = r.counter(
            "serving.prefix.host_hits",
            "admissions whose matched span included >= 1 host-RAM-"
            "resident block (served by exact-bytes swap-in instead of "
            "recompute)")
        self.prefix_host_swapin = r.counter(
            "serving.prefix.host_swapin_blocks",
            "blocks promoted host-RAM -> HBM on prefix-cache hits "
            "(the cache-reason slice of serving.swap.blocks_in)")
        self.queue_depth = r.gauge(
            "serving.queue_depth", "requests waiting for a slot")
        self.slot_occupancy = r.gauge(
            "serving.slot_occupancy", "slots holding a live request")
        self.slots_total = r.gauge(
            "serving.slots_total", "KV-cache slot pool size")
        self.blocks_free = r.gauge(
            "serving.blocks_free", "KV block-pool blocks with refcount 0 "
            "(free list + reclaimable prefix-cached)")
        self.blocks_in_use = r.gauge(
            "serving.blocks_in_use", "KV block-pool blocks pinned by "
            "live or queued requests (hwm = high-water mark)")
        self.latency = r.histogram(
            "serving.request_latency_seconds",
            "request latency, arrival -> last token")
        self.ttft = r.histogram(
            "serving.ttft_seconds",
            "time to first token, arrival -> the token's landing on "
            "the host (after the last chunk of the step that ran the "
            "prompt's final chunk)")
        self.chunk_latency = r.histogram(
            "serving.prefill_chunk_seconds",
            "wall time of one chunked-prefill dispatch: a pure "
            "enqueue on a dispatch-ahead engine, for a final chunk as "
            "for any other (a step's first tokens are fetched together "
            "after its last chunk, under serving.prefill.wait); "
            "enqueue, compute and materialization on the lockstep arm's "
            "non-final chunks")
        self.spec_verifies = r.counter(
            "serving.spec.verify_steps", "speculative verify forwards "
            "dispatched (one K+1-position target forward per scheduler "
            "iteration with >= 1 spec-mode slot) — against "
            "serving.block_dispatches this is the plain-vs-speculative "
            "decode route split")
        self.spec_draft_hits = r.counter(
            "serving.spec.draft_hits",
            "drafter proposals that produced >= 1 candidate token")
        self.spec_draft_misses = r.counter(
            "serving.spec.draft_misses", "drafter proposals that came "
            "back empty (the verify degrades to a plain 1-token step "
            "for that slot)")
        self.spec_draft_tokens = r.counter(
            "serving.spec.draft_tokens",
            "candidate tokens proposed by the drafter")
        self.spec_accepted_tokens = r.counter(
            "serving.spec.accepted_tokens", "draft tokens accepted by "
            "the verifier (each saved one target forward)")
        self.spec_accepted_len = r.histogram(
            "serving.spec.accepted_length",
            "accepted draft-prefix length per spec slot per verify "
            "forward (tokens; the +1 correction/bonus emit is not "
            "counted)",
            buckets=(0.0, 1.0, 2.0, 3.0, 4.0, 6.0, 8.0, 12.0, 16.0,
                     24.0, 32.0))
        self.sample_sampled_tokens = r.counter(
            "serving.sample.sampled_tokens",
            "tokens emitted by rows with a stochastic sampling config "
            "(temperature > 0 and top_k != 1), across decode blocks, "
            "chunk-final prefills and speculative verifies — against "
            "serving.sample.greedy_tokens this is the engine's "
            "sampled-vs-greedy route split")
        self.sample_greedy_tokens = r.counter(
            "serving.sample.greedy_tokens",
            "tokens emitted by greedy rows (no sampling config, "
            "temperature 0, or top_k=1) — the bit-exact argmax route")
        self.sample_masked_tokens = r.counter(
            "serving.sample.masked_tokens",
            "tokens emitted under an active token-mask constraint "
            "(a per-request TokenMaskProcessor biased the row's "
            "logits this step)")
        self.sample_resamples = r.counter(
            "serving.sample.resamples",
            "residual resamples consumed by stochastic speculative "
            "sampling (one per verify forward whose draft prefix was "
            "cut by the accept test; the residual draw preserves the "
            "output distribution)")
        self.kv_bytes_swept = r.counter(
            "serving.kv.bytes_swept",
            "modeled KV-arena bytes read by decode/verify/prefill-chunk "
            "dispatches, at the paged kernels' block-DMA granularity "
            "(valid prefix rounded up to whole blocks; codes + scale "
            "planes for the int8 cache) — a roofline denominator")
        self.kv_quant_dtype = r.gauge(
            "serving.kv.quant_dtype",
            "1 for each KV-cache at-rest dtype an engine in this "
            "process serves with (the label carries the dtype name)",
            labels=("dtype",))
        self.weights_bytes_swept = r.counter(
            "serving.weights.bytes_swept",
            "modeled model-weight bytes streamed from HBM by decode/"
            "verify/prefill-chunk dispatches: one full weight sweep per "
            "forward (non-quantized params at the compute dtype; "
            "quantized projections at their code width — int8 codes, "
            "packed int4 nibbles — plus f32 scale planes).  The "
            "weight-side twin of serving.kv.bytes_swept")
        self.shard_groups = r.gauge(
            "serving.shard.groups",
            "1 per engine serving as a tensor-parallel shard group "
            "over a device mesh, 0 for single-chip engines — a fleet "
            "registry's sum counts its live shard groups")
        self.shard_width = r.gauge(
            "serving.shard.width",
            "kv-head tensor-parallel degree of this engine's paged "
            "arenas (shards per group; 1 = single-chip or the "
            "replicated mesh_geom fallback)")
        self.weights_quant_dtype = r.gauge(
            "serving.weights.quant_dtype",
            "1 for each weight at-rest dtype an engine in this process "
            "serves with — the compute dtype name for full-precision "
            "engines, 'int8'/'int4' for quantized weight planes (the "
            "label carries the dtype name)",
            labels=("dtype",))
        self.goodput_useful = r.counter(
            "serving.goodput.useful_tokens",
            "dispatched token-positions that produced kept work: "
            "first-time prompt prefill positions and emitted output "
            "tokens that survive in the request's final stream; the "
            "tenant label attributes the work to the submitting "
            "tenant ('default' for tenant-less requests)",
            labels=("tenant",))
        self.goodput_wasted = r.counter(
            "serving.goodput.wasted_tokens",
            "dispatched token-positions that produced discarded work, "
            "by reason: 'spec_reject' = rejected/cut speculative draft "
            "positions, 'recompute_preempt' = positions recomputed "
            "after preemption (structurally 0 under exact-bytes swap), "
            "'recompute_cache' = prompt positions the prefix cache "
            "matched token-level but could not map (partial tails, "
            "dropped host parcels, tier-evict holes), 'pad' = grid/"
            "mask padding (chunk-grid tails, post-EOS block tails, "
            "masked verify lanes); the tenant label attributes the "
            "waste to the submitting tenant",
            labels=("reason", "tenant"))
        self.goodput_dispatched = r.counter(
            "serving.goodput.dispatched_tokens",
            "total dispatched token-positions over participating rows "
            "(the _count_kv_sweep convention: vacant/frozen rows are "
            "excluded), per submitting tenant — conservation: useful "
            "+ wasted == this, exactly, by construction of the ledger "
            "helper (and per tenant label too, since every call "
            "charges one tenant)", labels=("tenant",))
        self.tpot = r.histogram(
            "serving.tpot_seconds",
            "per-output-token decode latency, one observation per "
            "finished request with >= 2 output tokens: (last token - "
            "first token) / (n_tokens - 1)")
        self.step_host = r.histogram(
            "serving.step.host_seconds",
            "host-side scheduler time of one step(): step wall minus "
            "the time spent inside compiled dispatches — the lockstep "
            "gap a dispatch-ahead pipeline must hide under device "
            "time (observed only for steps that dispatched work)",
            buckets=_STEP_BUCKETS)
        self.step_offcpu = r.histogram(
            "serving.step.offcpu_seconds",
            "time one step() was off the CPU outside a device wait: "
            "the step's wall less its device waits (serving.harvest."
            "wait, serving.prefill.wait) and injected fault stalls, "
            "less the thread's CPU time over the same stretch, at 0 "
            "at the least — a descheduled host, a blocking enqueue, a "
            "lock (observed only for steps that dispatched work)",
            buckets=_STEP_BUCKETS)
        self.step_dispatch = r.histogram(
            "serving.step.dispatch_seconds",
            "time one step() spent inside compiled dispatches (chunk "
            "prefill, decode block, spec verify, swap gathers/"
            "scatters), including output materialization for sync-"
            "harvested dispatches; a DEFERRED dispatch contributes its "
            "enqueue time here and its materialization wait to "
            "serving.step.overlap_seconds", buckets=_STEP_BUCKETS)
        self.step_overlap = r.histogram(
            "serving.step.overlap_seconds",
            "time spent blocking on a PREVIOUS iteration's in-flight "
            "device outputs — deferred-harvest materialization and "
            "lazy host-tier parcel resolution; one observation per "
            "wait.  This is the slice the dispatch-ahead pipeline "
            "hides under device time: it is excluded from "
            "serving.step.host_seconds, which stays pure "
            "host-scheduler work", buckets=_STEP_BUCKETS)
        self.stall_seconds = r.histogram(
            "serving.fault.stall_seconds",
            "injected fault-stall sleep time (FaultInjector."
            "stall_steps), one observation per stalled step — charged "
            "here so fault-injection runs never pollute the "
            "serving.step.host_seconds baseline the dispatch-ahead "
            "pipeline is judged against", buckets=_STEP_BUCKETS)
        self.async_syncs = r.counter(
            "serving.async.syncs",
            "dispatch-ahead iterations that forced an EARLY harvest "
            "(materialized device outputs before the next dispatch "
            "was enqueued) because host truth was semantically "
            "required, by closed reason vocabulary (ASYNC_SYNC_"
            "REASONS: eos/budget/mask/penalty/spec/chunk_final/"
            "resume/preempt/cancel/drain)", labels=("reason",))
        self.async_harvests = r.counter(
            "serving.async.harvests",
            "deferred harvests completed at the pipeline's natural "
            "point — AFTER the next compiled dispatch was enqueued — "
            "i.e. iterations whose host-scheduler work actually "
            "overlapped device time")
        self.async_depth = r.gauge(
            "serving.async.depth",
            "un-harvested in-flight decode dispatches right now (hwm "
            "= peak pipeline depth reached; bounded by the engine's "
            "async_depth — 1 for the default double-buffered pipeline)")
        self.slo_attained = r.counter(
            "serving.slo.attained",
            "SLO-carrying requests (deadline_s or max_queue_delay_s "
            "set) that finished within their deadline; the class "
            "label is the priority class (p<N>) and the tenant label "
            "the submitting tenant ('default' when unset) — per-"
            "tenant SLO attainment is one exporter group-by away",
            labels=("class", "tenant"))
        self.slo_missed = r.counter(
            "serving.slo.missed",
            "SLO-carrying requests that finished past their deadline "
            "or were shed/timed out before running, by priority "
            "class and submitting tenant; cancelled requests are a "
            "user action, not an SLO outcome, and count in neither",
            labels=("class", "tenant"))
        self.fairshare_served = r.counter(
            "serving.fairshare.served_tokens",
            "tokens of service charged to each tenant at admission "
            "(prompt + decode budget — the reservation the fair-share "
            "layer accounts, charged when the request leaves the "
            "queue) — the deficit-weighted round-robin's ledger",
            labels=("tenant",))
        self.fairshare_deficit = r.gauge(
            "serving.fairshare.deficit",
            "each tenant's fair-share deficit: the most-served "
            "tenant's weight-normalized service minus this tenant's "
            "(>= 0; the largest deficit admits next within a "
            "scheduling class).  0 for every tenant on single-tenant "
            "traces — the fair-share layer is then inert",
            labels=("tenant",))
        self.fairshare_reorders = r.counter(
            "serving.fairshare.reorders",
            "admissions where the deficit-weighted round-robin chose "
            "a candidate that was NOT the FIFO head of the best "
            "scheduling class — each one is a starvation the plain "
            "priority/EDF/FIFO order would have inflicted on the "
            "chosen tenant")
        self._base = {}
        for c in (self.prefills, self.first_token_fetches,
                  self.admits_ahead, self.prefill_chunks, self.decode_steps,
                  self.busy_slot_steps, self.block_dispatches,
                  self.requests_finished, self.requests_cancelled,
                  self.prefix_hits, self.prefix_misses,
                  self.spec_verifies, self.spec_draft_hits,
                  self.spec_draft_misses, self.spec_draft_tokens,
                  self.spec_accepted_tokens, self.kv_bytes_swept,
                  self.weights_bytes_swept,
                  self.prefix_hit_tokens, self.prefix_partial_hits,
                  self.prefix_host_hits, self.prefix_host_swapin,
                  self.sample_sampled_tokens, self.sample_greedy_tokens,
                  self.sample_masked_tokens, self.sample_resamples,
                  self.preempts, self.preempt_resumes,
                  self.swap_out_blocks, self.swap_in_blocks,
                  self.swap_out_bytes, self.swap_in_bytes,
                  self.handoff_requests, self.handoff_blocks,
                  self.handoff_bytes,
                  self.shed, self.timeouts,
                  self.goodput_useful, self.goodput_wasted,
                  self.goodput_dispatched,
                  self.async_syncs, self.async_harvests,
                  self.slo_attained, self.slo_missed,
                  self.fairshare_served, self.fairshare_reorders):
            # total() sums label sets, so labeled counters (cancelled
            # by phase, shed by reason) baseline the same way the
            # unlabeled ones do
            self._base[c.name] = c.total()
        # per-reason forced-sync baselines: the reason vocabulary is
        # closed, so stats() reports exact per-engine per-reason
        # deltas on a shared registry the same way since_init does for
        # totals.  (The per-reason WASTED-token breakdown moved to a
        # host-side mirror in the engine when the goodput counters
        # grew the open-vocabulary tenant label — see
        # ServingEngine._wasted_reason.)
        self._syncs_base = {reason: self.async_syncs.value(reason=reason)
                            for reason in ASYNC_SYNC_REASONS}

    def syncs_since(self, reason: str) -> float:
        """Per-reason forced-sync delta attributable to THIS engine."""
        return (self.async_syncs.value(reason=reason)
                - self._syncs_base.get(reason, 0))

    def since_init(self, counter) -> float:
        """Counter delta attributable to THIS engine (summed over
        label sets for labeled counters)."""
        return counter.total() - self._base.get(counter.name, 0)


class _Phase(_span):
    """One phase of a step, delimited ONCE: a span on the profiler's
    clock whose two boundaries are also the two reads of the engine's
    clock that the step's accumulators (``_disp_s``, ``_overlap_s``,
    ``_stall_s``, the host remainder) are charged from.  ``seconds``
    is the phase's duration once it has closed; ``stop()`` closes it
    from inside the ``with`` suite and returns that."""

    __slots__ = ("_clock", "_t0", "seconds")

    def __init__(self, clock, name: str, **attrs):
        super().__init__(name, **attrs)
        self._clock = clock
        self.seconds = None

    def __enter__(self):
        super().__enter__()
        self._t0 = self._clock()
        return self

    def stop(self) -> float:
        if self.seconds is None:
            self.seconds = self._clock() - self._t0
            super().__exit__(None, None, None)
        return self.seconds

    def __exit__(self, *exc):
        self.stop()
        return False


class _Wait(_Phase):
    """A phase in which the step's thread waits for the device (or
    sleeps an injected stall): its wall time and the thread's CPU time
    over it are left out of the step's off-CPU account
    (``serving.step.offcpu_seconds``)."""

    __slots__ = ("_eng", "_cpu0")

    def __init__(self, eng, name: str, **attrs):
        super().__init__(eng._clock, name, **attrs)
        self._eng = eng

    def __enter__(self):
        super().__enter__()
        self._cpu0 = time.thread_time_ns()
        return self

    def stop(self) -> float:
        if self.seconds is None:
            cpu = time.thread_time_ns() - self._cpu0
            super().stop()
            if self._eng._in_step:
                self._eng._wait_s += self.seconds
                self._eng._wait_cpu_ns += cpu
        return self.seconds


def _call_quiet(fn, *args):
    """Invoke a compiled serving program with the donation warning
    suppressed for THIS call only: cache donation is a no-op (with a
    warning) on backends without donation support (CPU CI), and the
    engine's per-block calls would spam it — but the filter must not
    leak to user code (a process-global filter would hide the same
    warning for the user's own donate_argnums jits)."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        return fn(*args)


_INF = float("inf")


def _neg_deadline(deadline: Optional[float]) -> float:
    """Deadline term of the "worseness" ordering: no deadline sorts as
    infinitely late (most shed-able / most preempt-able), and later
    deadlines sort before earlier ones."""
    return -(deadline if deadline is not None else _INF)


class BlockPool:
    """Host-side allocator for the device block arena: a free list over
    ``num_blocks`` logical blocks plus a refcounted prefix cache.

    Lifecycle of a block: ``alloc`` hands it out with refcount 1;
    ``pin``/``unpin`` move the refcount as prefix sharers map it in and
    requests retire; a block whose refcount drops to 0 returns to the
    free list UNLESS the radix tree of ``inference/prefixcache.py``
    holds it (``tree_hold``/``tree_touch``) — then it parks in
    ``_tree_lru``, still mapped, and is reclaimed only when the free
    list runs dry.  The extra arena row ``trash`` is not managed here:
    it is the fixed write-masking target and never allocated.

    Purely host state — the device never sees refcounts, only the
    int32 block tables (the "no per-step sync of the arena" contract).

    When ``alloc`` reclaims tree-held blocks, ``reclaim_cb`` (the
    engine's demote path) fires once with the reclaimed list before
    alloc returns — the caller has not written the rows yet, so their
    bytes can still be gathered to the host tier in one batched
    dispatch.  ``audit_hooks`` let the owning cache fold its own
    invariants into ``check()``."""

    def __init__(self, num_blocks: int, block_len: int):
        self.num_blocks = int(num_blocks)
        self.block_len = int(block_len)
        self.trash = self.num_blocks           # extra arena row index
        self._free = list(range(self.num_blocks - 1, -1, -1))
        self._ref = [0] * self.num_blocks
        self._tree_ref = set()                 # radix-tree-held blocks
        self._tree_lru: OrderedDict = OrderedDict()  # block -> True
        self.reclaim_cb = None                 # fires on tree-LRU reclaim
        self.audit_hooks = []                  # extra check() invariants

    def available(self) -> int:
        """Blocks allocatable right now (free + reclaimable cached)."""
        return len(self._free) + len(self._tree_lru)

    def in_use(self) -> int:
        """Blocks pinned by live or queued requests (refcount > 0)."""
        return self.num_blocks - self.available()

    def cached(self) -> int:
        """Unpinned blocks kept mapped for future prefix hits."""
        return len(self._tree_lru)

    def pin(self, block: int):
        if self._ref[block] == 0:
            self._tree_lru.pop(block, None)
        self._ref[block] += 1

    def unpin(self, block: int):
        if self._ref[block] <= 0:
            raise RuntimeError(
                f"block {block} unpinned below refcount 0 — double free")
        self._ref[block] -= 1
        if self._ref[block] == 0:
            if block in self._tree_ref:
                self._tree_lru[block] = True   # reclaimable, still mapped
            else:
                self._free.append(block)

    def tree_hold(self, block: int):
        """Mark a block referenced by the radix prefix tree.  The
        caller must hold a pin (registration and promotion both run
        under the owning request's refcount), so a held block is never
        immediately reclaimable."""
        if not (0 <= block < self.num_blocks):
            raise RuntimeError(f"tree_hold of non-pool block {block}")
        if self._ref[block] <= 0:
            raise RuntimeError(
                f"tree_hold of unpinned block {block} — registration "
                f"must run under the owning request's refcount")
        self._tree_ref.add(block)

    def tree_touch(self, block: int):
        """LRU-refresh a tree-held reclaimable block on a cache hit."""
        if block in self._tree_lru:
            self._tree_lru.move_to_end(block)

    def alloc(self, n: int) -> Optional[List[int]]:
        """``n`` blocks with refcount 1 each, reclaiming the oldest
        refcount-0 tree-held blocks when the free list runs dry; None
        when the pool cannot serve ``n``.  Reclaimed blocks fire
        ``reclaim_cb`` so the radix cache can demote their bytes to the
        host tier before the row is overwritten."""
        if n > self.available():
            return None
        out = []
        reclaimed = []
        for _ in range(n):
            if self._free:
                b = self._free.pop()
            else:
                b, _ = self._tree_lru.popitem(last=False)
                self._tree_ref.discard(b)
                reclaimed.append(b)
            self._ref[b] = 1
            out.append(b)
        if reclaimed and self.reclaim_cb is not None:
            # ONE callback per alloc, not per block: the engine's
            # demote path gathers every reclaimed block's bytes in one
            # batched dispatch.  The caller has not written the rows
            # yet (it only receives them when alloc returns), so the
            # at-rest bytes are still intact here.
            self.reclaim_cb(reclaimed)
        return out

    def check(self) -> bool:
        """Full invariant audit; raises ``RuntimeError`` listing every
        violation, returns True when clean.  Called by tests and the
        fault-injection harness after adversarial schedules — the
        invariants that define "no leak, no double-free, no refcount
        drift":

        - conservation: free + pinned (ref > 0) + cached (tree LRU)
          covers every block exactly once;
        - the free list has no duplicates and no pinned/cached member;
        - free blocks are never tree-referenced;
        - every refcount-0 tree-referenced block sits in the tree LRU
          (no unreclaimable limbo), and every tree-LRU member is
          tree-referenced;
        - no negative refcount (``unpin`` raises before one can form,
          so a violation here means state was corrupted directly);
        - every registered ``audit_hooks`` entry (the radix tree's
          node <-> block-span bijection and host-tier consistency)
          returns no errors."""
        errs = []
        free_set = set(self._free)
        if len(free_set) != len(self._free):
            errs.append(f"free list holds duplicates: {self._free}")
        tlru_set = set(self._tree_lru)
        pinned = 0
        for b in range(self.num_blocks):
            ref = self._ref[b]
            cached_here = b in tlru_set
            if ref < 0:
                errs.append(f"block {b}: negative refcount {ref}")
            if ref > 0:
                pinned += 1
                if b in free_set or cached_here:
                    errs.append(
                        f"block {b}: refcount {ref} but on the "
                        f"{'free list' if b in free_set else 'LRU'}")
            elif not (b in free_set or cached_here):
                errs.append(f"block {b}: refcount 0 but neither free "
                            f"nor cached — leaked")
            if b in free_set and cached_here:
                errs.append(f"block {b}: both free and LRU-cached")
            if b in free_set and b in self._tree_ref:
                errs.append(f"block {b}: free but tree-referenced")
            if b in self._tree_ref and ref == 0 and b not in tlru_set:
                errs.append(f"block {b}: tree-referenced at refcount 0 "
                            f"but not in the tree LRU — unreclaimable")
            if b in tlru_set and b not in self._tree_ref:
                errs.append(f"block {b}: in the tree LRU but not "
                            f"tree-referenced")
        if len(self._free) + pinned + len(self._tree_lru) \
                != self.num_blocks:
            errs.append(
                f"conservation: free({len(self._free)}) + "
                f"pinned({pinned}) + cached({len(self._tree_lru)}) != "
                f"num_blocks({self.num_blocks})")
        for hook in self.audit_hooks:
            errs.extend(hook())
        if errs:
            raise RuntimeError(
                "BlockPool.check failed:\n  " + "\n  ".join(errs))
        return True


@dataclass
class _PendingBlock:
    """One dispatched-but-not-yet-harvested decode dispatch — an entry
    of the pipeline's bounded pending deque (depth 1 = the PR-10
    double buffer).  ``toks_d``/``tok_d``/``lens_d``/``done_d``/
    ``budget_d`` are the compiled call's UN-MATERIALIZED device
    outputs: the carries feed the next dispatch directly (device ->
    device, no host round-trip) and the whole record is forced to host
    only at harvest.  ``done_d`` is the in-trace FINISH BITMAP (EOS
    hit or budget exhausted): at ``async_depth >= 2`` the host polls
    it at harvest — one dispatch late — instead of syncing every
    iteration (a finished rider's slot frees one plan later; the lag
    is deterministic and flight-recorder-stamped).

    A FUSED dispatch covers ``iters`` logical scheduler iterations of
    ``per_iter`` scanned steps each (``n = iters * per_iter`` total);
    the harvest re-splits it iteration by iteration so accounting,
    ledger and flight-recorder granularity match the unfused engine.
    ``pre_lens`` is the HOST-TRUE per-slot lens entering this dispatch
    (the KV-sweep model needs it); ``active``/``reqs`` pin the riding
    set — a rider that finished in an EARLIER pending dispatch rides
    later in-flight ones frozen (device-side pad emits) and is skipped
    at their harvest."""
    step_idx: int
    n: int                         # scanned steps in this dispatch
    per_iter: int                  # steps per logical iteration
    iters: int                     # logical iterations (n//per_iter)
    active: List[int]              # riding slot indices
    reqs: List[Request]            # parallel to ``active``
    pre_lens: np.ndarray           # host lens mirror entering dispatch
    toks_d: object                 # [B, n] device tokens
    tok_d: object                  # carries out: tok / lens / done /
    lens_d: object                 # remaining budget (the last two
    done_d: object                 # form the finish-bitmap protocol)
    budget_d: object
    counters_d: object = None      # the model's block counters, if any


class _LazyStacks:
    """One deferred demote gather: the device row stacks captured at
    enqueue time (JAX arrays are immutable values, so later donated
    overwrites of the arenas can never reach them), materialized to
    host numpy ONCE on first need.  Shared by every host-tier parcel
    the gather page covered — resolving any parcel resolves the page."""

    __slots__ = ("_dev", "_np")

    def __init__(self, dev_stacks):
        self._dev = list(dev_stacks)
        self._np = None

    @property
    def resolved(self) -> bool:
        return self._np is not None

    def resolve(self) -> List[np.ndarray]:
        if self._np is None:
            self._np = [np.asarray(s) for s in self._dev]
            self._dev = None
        return self._np

    def block_rows(self, j: int) -> List[np.ndarray]:
        """Parcel rows for gathered row ``j``: one ``[1, ...]``
        contiguous slice per flat arena (the ``_HostEntry.rows``
        shape contract)."""
        return [np.ascontiguousarray(s[j:j + 1]) for s in self.resolve()]


@dataclass
class _SwapRecord:
    """A preempted request's device state, parked in the shared
    ``HostTier`` (reason ``"preempt"``).

    ``host_key`` names the tier parcel holding one ``[n_blocks, ...]``
    numpy stack per flat arena — the request's real blocks at the
    arena's exact at-rest dtype (float K/V, or int8 codes plus f32
    scale planes), sliced out of the fixed-shape full-table gather so
    the tier holds exactly the bytes its accounting reports; resume
    re-pads to table width (pad rows scatter into the trash row).
    ``tok``/``lens`` are the slot's device carries at preemption; with
    them and the bytes restored, the resumed request is bit-identical
    to one that was never preempted."""
    host_key: int
    n_blocks: int
    tok: int
    lens: int
    state: str                     # "prefill" | "decode"
    # the slot's rows of the per-slot state arenas (``slot_state_spec``
    # models), which travel with the blocks: one host array an arena
    slot_state: Optional[List[np.ndarray]] = None


@dataclass
class Request:
    """One serving request and its lifecycle accounting.

    ``tokens`` accumulates generated ids as blocks are harvested; after
    EOS the stream is ``pad_token_id`` (same convention as
    ``generate()``), and ``output`` is always exactly
    ``max_new_tokens`` long — token-for-token what a static-batch
    greedy ``generate()`` of this request alone would return.
    ``state`` walks queued -> prefill -> decode -> finished, with the
    overload detours: ``swapped`` (preempted to the host-RAM tier,
    resumes into prefill/decode), ``timeout`` (queue wait exceeded
    ``max_queue_delay_s``), ``shed`` (displaced from a full bounded
    queue) and ``cancelled`` (dropped from any live phase).

    ``priority`` (higher = more important) and ``deadline`` (absolute
    clock time, None = no deadline) define the scheduling class:
    admission is priority-then-EDF, preemption victims come from
    strictly lower classes only.
    """
    request_id: int
    prompt: np.ndarray                 # [prompt_len] padded
    seq_len: int
    max_new_tokens: int
    arrival_time: float
    pad_token_id: int = 0
    tokens: List[int] = field(default_factory=list)
    remaining: int = 0                 # decode-step budget left
    slot: Optional[int] = None
    submit_time: float = 0.0
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None
    state: str = "queued"
    priority: int = 0                  # higher admits/survives first
    deadline: Optional[float] = None   # absolute clock() time
    max_queue_delay_s: Optional[float] = None
    swap: Optional[_SwapRecord] = None
    preempt_count: int = 0
    spec_k: Optional[int] = None       # speculative mode: drafts/verify
    adapter: Optional[str] = None      # LoRA adapter name (None = base)
    adapter_slot: Optional[int] = None  # pinned arena slot while admitted
    tenant: str = "default"            # fair-share accounting bucket
    sampling: Optional[SamplingParams] = None  # None = plain greedy
    samp_base: Optional[np.ndarray] = None     # [2] u32 PRNG base key
    pf_pos: int = 0                    # next prompt position to compute
    matched: List[int] = field(default_factory=list)   # prefix-hit blocks
    host_pins: List[int] = field(default_factory=list)  # pinned tier keys
    rspan: List = field(default_factory=list)  # radix span at last probe
    rmatch_tokens: int = 0             # token-level match at last probe
    # goodput ledger: prompt positions in [gp_recompute_from,
    # gp_recompute_to) were matched token-level by the prefix cache at
    # admission but could NOT be mapped (partial tail past the last
    # full block, dropped host parcels, tier-evict holes) — their
    # prefill recompute is charged wasted{reason="recompute_cache"}
    gp_recompute_from: int = 0
    gp_recompute_to: int = 0
    n_emitted: int = 0                 # tokens at finish, before padding
    blocks: List[int] = field(default_factory=list)    # full block map
    registered: int = 0                # blocks published so far
    chunk_ids: Optional[np.ndarray] = None  # prompt padded to chunk grid

    @property
    def output(self) -> np.ndarray:
        return np.asarray(self.tokens, np.int32)

    @property
    def latency(self) -> Optional[float]:
        if self.finish_time is None:
            return None
        return self.finish_time - self.arrival_time

    @property
    def ttft(self) -> Optional[float]:
        """Time to first token (arrival -> last prefill chunk)."""
        if self.first_token_time is None:
            return None
        return self.first_token_time - self.arrival_time


class TokenStream:
    """Incremental token stream of one streaming request
    (``submit(stream=True)`` returns one; so does the router's).

    A stream handle never drives the device: ``read()`` drains the
    tokens that are ALREADY host truth — i.e. everything the engine
    has harvested so far — and advances a cursor.  On a
    dispatch-ahead engine the tokens of a deferred block become host
    truth at the harvest point (after the NEXT dispatch was
    enqueued), so the stream's flush boundaries ARE the pipeline's
    harvest points: streaming adds no materialization the engine was
    not already doing, no new entry in ``ASYNC_SYNC_REASONS``, and
    the concatenation of every flush is token-for-token the
    non-streamed ``Request.output`` (terminal pad tail included — the
    ``generate()`` convention).

    ``owner`` is whatever schedules the request (a ``ServingEngine``
    or a ``Router``): iterating the stream calls ``owner.step()``
    between flushes, so ``for chunk in stream: ...`` is a working
    chat loop.  ``read()``/``finished`` are the primitives for
    callers that drive the scheduler themselves."""

    def __init__(self, owner, target):
        self._owner = owner
        self._target = target
        self._pos = 0
        # generous safety cap for __iter__: a healthy drain finishes a
        # request in far fewer steps than this; a wedged pool raises
        # instead of spinning silently
        self._max_iter_steps = 100_000

    @property
    def request(self):
        """The underlying request handle (engine ``Request``, or the
        router's ``RoutedRequest``)."""
        return self._target

    @property
    def finished(self) -> bool:
        return self._target.state in TERMINAL_STATES

    @property
    def n_read(self) -> int:
        """Tokens delivered through this handle so far."""
        return self._pos

    def read(self) -> np.ndarray:
        """Every token that became host truth since the last read
        (possibly empty) — never blocks, never forces a pending
        harvest.  The cursor NEVER moves backward: during a failover
        recompute the underlying token list transiently restarts from
        the prompt, and the replayed prefix is bit-identical to what
        was already flushed (the position-keyed PRNG contract), so the
        stream splices at the last flushed token — new tokens appear
        once the replay passes the cursor, and nothing is ever
        double-emitted."""
        toks = self._target.tokens
        new = toks[self._pos:]
        self._pos = max(self._pos, len(toks))
        return np.asarray(new, np.int32)

    def __iter__(self):
        steps = 0
        while True:
            chunk = self.read()
            if chunk.size:
                yield chunk
            if self.finished:
                tail = self.read()   # terminal pad landed after the
                if tail.size:        # last scheduler flush
                    yield tail
                return
            self._owner.step()
            steps += 1
            if steps > self._max_iter_steps:
                raise RuntimeError(
                    f"TokenStream iteration exceeded "
                    f"{self._max_iter_steps} scheduler steps without "
                    f"the request reaching a terminal state")


class ServingEngine:
    """Continuous-batching serving session over a paged KV block pool.

    ``submit()`` enqueues requests (optionally with a future
    ``arrival_time`` for trace replay); ``cancel()`` drops a
    still-queued one; ``step()`` runs one scheduler iteration (admit +
    the step's prefill chunks + one decode block); ``run()`` drains
    everything and returns the finished requests.  Greedy output is
    token-for-token identical to per-request static ``generate()`` —
    see ``_build_decode_block``'s row-independence contract and the
    module docstring's paged-exactness argument.
    """

    def __init__(self, model, *, num_slots, prompt_len,
                 max_cache_len=None, steps_per_call=1,
                 block_len=16, num_blocks=None, chunk_len=None,
                 prefix_cache_mode="radix",
                 host_cache_blocks=None, drafter=None,
                 eos_token_id=None, pad_token_id=0,
                 do_sample=False, temperature=1.0, top_k=0, top_p=1.0,
                 compute_dtype="bfloat16", cache_dtype=None,
                 kv_cache_dtype=None, weight_dtype=None,
                 seed=0, clock=time.perf_counter,
                 registry=None, max_queue=None,
                 fault_injector=None, flight_recorder=None,
                 async_dispatch=True, async_depth=1,
                 adapter_store=None, tenant_weights=None, mesh=None,
                 role="both"):
        self.num_slots = int(num_slots)
        # disaggregation role (ROADMAP item 2): pure POLICY over the
        # landed exact-bytes migration mechanism.  "both" (default) is
        # byte-identical to every pre-role trace; "prefill" hands each
        # request off at its final chunk; "decode" only ever resumes
        # migrated parcels (fresh submits are rejected at the door).
        self.role = str(role)
        if self.role not in ENGINE_ROLES:
            raise ValueError(
                f"role must be one of {ENGINE_ROLES}, got {role!r}")
        self.max_queue = None if max_queue is None else int(max_queue)
        if self.max_queue is not None and self.max_queue < 1:
            raise ValueError(
                f"max_queue must be >= 1 (or None = unbounded), got "
                f"{max_queue}")
        self._fault = fault_injector
        self.prompt_len = int(prompt_len)
        self.max_cache_len = int(max_cache_len or (prompt_len + 256))
        self.steps_per_call = int(steps_per_call)
        self.block_len = int(block_len)
        # prefix-cache mode: "radix" (the default — token-level radix
        # tree with host-RAM tiering) or "none"
        mode = str(prefix_cache_mode)
        if mode not in ("radix", "none"):
            raise ValueError(
                f"prefix_cache_mode must be 'radix' or 'none', got "
                f"{prefix_cache_mode!r}")
        # a model that keeps per-slot state beside its blocks
        # (slot_state_spec: a convolution tail, a recurrent state) can
        # take nothing that shares, moves or rewinds blocks without the
        # state: a prefix hit would start the suffix from a zero state.
        # Such a model is served with the prefix cache off, and every
        # other such feature refuses it by name where it is asked for.
        self._state_spec = slot_state_spec(model)
        self.prefix_cache_disabled = bool(self._state_spec) and \
            mode != "none"
        if self.prefix_cache_disabled:
            logging.getLogger(__name__).info(
                "%s keeps per-slot state (%s) beside its paged KV: serving "
                "it with prefix_cache_mode='none' (asked: %r)",
                type(model).__name__,
                ", ".join(e[0] for e in self._state_spec), mode)
            mode = "none"
        if self._state_spec:
            if drafter is not None:
                raise SlotStateError(model, "speculative decoding (a "
                                     "drafter)")
            if self.role != "both":
                raise SlotStateError(
                    model, f"a disaggregated handoff parcel (role="
                    f"{self.role!r})")
            if host_cache_blocks:
                raise SlotStateError(
                    model, f"HostTier demotion and swap-in of cached "
                    f"blocks (host_cache_blocks={host_cache_blocks})")
            if mesh is not None and "model" in mesh.axis_names and \
                    int(mesh.shape["model"]) > 1:
                raise SlotStateError(model, "a mesh with mp > 1")
        self.prefix_cache_mode = mode
        if self.num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if self.steps_per_call < 1:
            raise ValueError(
                f"steps_per_call must be >= 1, got {steps_per_call}")
        if self.block_len < 1:
            raise ValueError(f"block_len must be >= 1, got {block_len}")
        if self.max_cache_len < self.prompt_len + 1:
            raise ValueError(
                f"max_cache_len ({self.max_cache_len}) must be >= "
                f"prompt_len + 1 ({self.prompt_len + 1})")
        # per-slot table width; a slot's dense view spans max_blocks *
        # block_len >= max_cache_len slots (the tail rounds up)
        self.max_blocks = -(-self.max_cache_len // self.block_len)
        self.num_blocks = (int(num_blocks) if num_blocks is not None
                           else self.num_slots * self.max_blocks)
        if self.num_blocks < 1:
            raise ValueError(
                f"num_blocks must be >= 1, got {self.num_blocks}")
        self.chunk_len = (int(chunk_len) if chunk_len is not None
                          else self.prompt_len)
        if self.chunk_len < 1:
            raise ValueError(f"chunk_len must be >= 1, got {chunk_len}")
        self.cfg = GenerationConfig(
            do_sample=bool(do_sample), temperature=float(temperature),
            top_k=int(top_k), top_p=float(top_p),
            eos_token_id=eos_token_id,
            pad_token_id=int(pad_token_id),
            compute_dtype=str(compute_dtype),
            cache_dtype=None if cache_dtype is None else str(cache_dtype))
        # engine-level sampling knobs become the DEFAULT per-request
        # SamplingParams (requests may override via submit(sampling=));
        # default-sampled requests draw from streams seeded by
        # fold_in(engine seed, request_id), so the engine-level mode is
        # restart-deterministic too without every request sharing one
        # stream
        self._default_sampling = (SamplingParams(
            temperature=float(temperature), top_k=int(top_k),
            top_p=float(top_p)).validate() if do_sample else None)
        model.eval()
        self._model = model
        params, buffers = model_arrays(model)
        # weight_dtype: "int8"/"int4" quantizes the hot projections once
        # at load (codes + per-output-channel f32 scales, the PR-5 KV
        # discipline applied to weights; inference/llm.py
        # build_weight_quant_plan).  The planes append to the SAME
        # positional p_values list every program already takes — the
        # donation index tuples over the trailing arena args never
        # shift — and the quantized params' own slots become zero-size
        # placeholders (a missed projection diversion fails loudly at
        # trace time).  None or any float dtype = full-precision
        # weights, today's exact programs.
        wq_dtype = normalize_weight_dtype(weight_dtype)
        if wq_dtype is not None:
            self._wq = build_weight_quant_plan(model, wq_dtype)
            self.weight_dtype = wq_dtype
            p_values = self._wq.placeholder_params(params)
        else:
            self._wq = None
            self.weight_dtype = str(jnp.dtype(self.cfg.compute_dtype).name)
            p_values = [p._value for p in params]
        self._pb = p_values + [bf._value for bf in buffers] + \
            (self._wq.flat_values() if self._wq is not None else [])
        # modeled bytes ONE forward streams for the whole weight set:
        # float params at the compute dtype (the hoisted cast is what
        # the dispatch actually reads), buffers and quantized planes at
        # their own at-rest widths
        cd_item = jnp.dtype(self.cfg.compute_dtype).itemsize
        wbytes = 0
        skip = self._wq.param_positions if self._wq is not None \
            else frozenset()
        for i, p in enumerate(params):
            if i in skip:
                continue
            item = (cd_item if jnp.issubdtype(p._value.dtype, jnp.floating)
                    else p._value.dtype.itemsize)
            wbytes += int(p._value.size) * item
        for bf in buffers:
            wbytes += int(bf._value.nbytes)
        if self._wq is not None:
            wbytes += self._wq.bytes_swept()
        self._weight_sweep_bytes = wbytes

        # a latent cache keeps one row a token a layer, shared by every
        # query head: one arena a layer, one "KV head" as wide as the row
        spec = model.kv_cache_spec()
        self._kv_latent = isinstance(spec, LatentCacheSpec)
        n_layers, hkv, d = (spec.layers, 1, spec.row) if self._kv_latent \
            else spec
        # kv_cache_dtype overrides the arena dtype only; "int8" selects
        # the QUANTIZED cache — int8 code arenas + parallel f32 absmax
        # scale arenas, quantize-on-append in every writer and
        # dequantize-on-read in every reader (models.generation
        # quantize_kv_heads / ops.pallas.decode_attention int8 paths).
        # The compute dtype (weights, activations, softmax) is
        # untouched: only the at-rest cache and its HBM sweep shrink.
        kvdt = (kv_cache_dtype if kv_cache_dtype is not None
                else (self.cfg.cache_dtype or self.cfg.compute_dtype))
        try:
            cdt = jnp.dtype(kvdt)
        except TypeError as e:
            raise ValueError(f"unknown kv_cache_dtype {kvdt!r}") from e
        if cdt != jnp.dtype(jnp.int8) and \
                not jnp.issubdtype(cdt, jnp.floating):
            # any float dtype is a valid at-rest cache; "int8" selects
            # the quantized cache.  Every other integer dtype would
            # silently cast K/V into an arena with no scale planes —
            # garbage outputs, so reject loudly.  kv_cache_dtype's
            # allowed set is NOT weight_dtype's: weights additionally
            # admit "int4" (packed nibbles unpacked in-kernel), the KV
            # cache does not — its scatter/attention paths have no
            # nibble discipline.
            hint = (" — 'int4' is a WEIGHT dtype: pass "
                    "weight_dtype='int4' instead (the KV cache has no "
                    "int4 mode)" if str(kvdt) == "int4" else "")
            raise ValueError(
                f"kv_cache_dtype must be a float dtype or 'int8' (the "
                f"quantized KV cache), got {kvdt!r}{hint}")
        self.kv_cache_dtype = str(jnp.dtype(cdt).name)
        self._kv_int8 = cdt == jnp.dtype(jnp.int8)
        self._n_layers = n_layers
        if self._kv_latent:
            if self._kv_int8:
                raise ValueError(
                    "kv_cache_dtype='int8': a latent cache has no "
                    "quantized form (its row is one vector, not heads "
                    "with a scale each)")
            arenas = init_paged_latent_arena(n_layers, self.num_blocks,
                                             self.block_len, d, cdt)
        else:
            arenas = init_paged_kv_arena(n_layers, self.num_blocks,
                                         self.block_len, hkv, d, cdt)
        self._arenas: List = []
        for entry in arenas:
            self._arenas += list(entry)
        # the second kind of state: one arena a spec entry, indexed by
        # slot, donated through the chunk and decode programs behind the
        # KV arenas (empty for a model whose only state is keys and values)
        self._slot_state: List = init_slot_state(
            self._state_spec, self.num_slots,
            jnp.dtype(self.cfg.compute_dtype))
        self._slot_state_bytes = sum(int(a.nbytes)
                                     for a in self._slot_state)
        self._state_read_fn = None
        self._state_write_fn = None
        # -- tensor-parallel serving over a device mesh (PR 18) --
        # ``mesh=Mesh(...)`` shards every arena plane's kv-head axis
        # (codes [NB+1, L, Hkv*D] and int8 scales [NB+1, L, Hkv] both
        # shard axis 2) over the mesh's ``model`` axis and replicates
        # the params, so the paged decode/verify/chunk programs
        # partition per-head under GSPMD while block tables, token/
        # length/done carries and sampling planes stay replicated host
        # inputs — the byte-deterministic plan drives all shards
        # unchanged, which is what keeps a sharded engine scheduling-
        # identical (and, with per-request keyed PRNG, token-exact) to
        # single-chip.  Sharding is pjit annotations ONLY (no
        # shard_map), so no new sync reason exists.  A geometry that
        # cannot split whole kv-heads (hkv % n_shards != 0, or a
        # 1-wide model axis) is the exact single-chip engine on the
        # mesh's FIRST device — not on the process default, and not
        # replicated: a multi-device program could take no Pallas
        # kernel (ops/pallas/_common.pallas_enabled) — and says so
        # once on the route counter (decision="xla",
        # reason="mesh_geom").  The committed weights and arenas pull
        # every uncommitted host push (tables, carries, sampling
        # planes) onto the same device(s) at dispatch.
        self._shard = None
        self.shard_group = None
        if mesh is not None:
            if "model" not in mesh.axis_names:
                raise ValueError(
                    f"ServingEngine(mesh=...) shards kv-heads over the "
                    f"mesh's 'model' axis; got axes {mesh.axis_names}")
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as _P
            n_sh = int(mesh.shape["model"])
            devs = [int(dv.id) for dv in mesh.devices.flat]
            tp_ok = n_sh > 1 and hkv % n_sh == 0
            if tp_ok:
                rep = NamedSharding(mesh, _P())
                kv_ns = NamedSharding(mesh, _P(None, None, "model"))
                self._shard = ArenaSharding(kv=kv_ns, n_shards=n_sh)
            else:
                kv_ns = rep = jax.sharding.SingleDeviceSharding(
                    mesh.devices.flat[0])
                _decode_attn.count_shard_route(hkv, n_sh, False)
            self._arenas = [jax.device_put(a, kv_ns)
                            for a in self._arenas]
            self._slot_state = [jax.device_put(a, rep)
                                for a in self._slot_state]
            self._pb = [jax.device_put(v, rep) for v in self._pb]
            self.shard_group = {
                "n_shards": n_sh if tp_ok else 1,
                "requested": n_sh,
                "sharded": tp_ok,
                "devices": devs,
                "label": (f"tp{n_sh}@d{devs[0]}" if tp_ok
                          else f"rep@d{devs[0]}"),
            }
        # modeled per-row KV sweep bytes across all layers, at the
        # Pallas kernels' block-DMA granularity (serving.kv.bytes_swept)
        if self._kv_latent:     # the row as it rests, lane padding included
            row_bytes = arenas[0][0].shape[-1] * jnp.dtype(cdt).itemsize
        else:
            row_bytes = 2 * hkv * d * (1 if self._kv_int8
                                       else jnp.dtype(cdt).itemsize)
        if self._kv_int8:
            row_bytes += 2 * hkv * 4       # f32 scale planes
        self._kv_row_bytes = row_bytes * n_layers
        self._pool = BlockPool(self.num_blocks, self.block_len)
        # ONE host-RAM block store for both host-tier uses: preemption
        # swap-outs (reason="preempt", pinned until resume) and prefix-
        # cache demotions (reason="cache", LRU-evicted under the
        # capacity bound).  host_cache_blocks bounds only the cache
        # half (0 = demotions drop, PR-3 forget semantics; default 4x
        # the HBM pool — the host/HBM capacity multiplier).
        cache_cap = (int(host_cache_blocks)
                     if host_cache_blocks is not None
                     else 4 * self.num_blocks)
        if cache_cap < 0:
            raise ValueError(
                f"host_cache_blocks must be >= 0, got {host_cache_blocks}")
        self._host_cache_cap = cache_cap    # kept for crash_reset()
        self._host_tier = HostTier(cache_capacity_blocks=cache_cap)
        self._radix: Optional[RadixPrefixCache] = None
        if mode == "radix":
            self._radix = RadixPrefixCache(self.block_len, self._pool,
                                           self._host_tier)
            self._pool.reclaim_cb = self._demote_blocks
            self._host_tier.evict_cb = self._radix.drop_host
            self._pool.audit_hooks.append(
                lambda: self._radix.audit(self._pool))
        self._pool.audit_hooks.append(self._audit_host_tier)
        # host-side block tables; pushed (small int32) per dispatch —
        # the ONLY new per-step transfer; the arenas never leave the
        # device and are donated into both compiled programs so
        # steady-state serving does not churn a second copy of the
        # pool through HBM every step.
        # args: (pb, ids, start, n_valid, tables, samp, *arenas) /
        #       (pb, tok, lens, done, samp, tables, *arenas)
        self._tables = np.full((self.num_slots, self.max_blocks),
                               self._pool.trash, np.int32)
        # arena positions differ per program family: chunk prefill and
        # spec verify take (pb, <4 planes>, samp, *arenas); the decode
        # block grew the finish-bitmap ``budget`` carry, shifting its
        # arenas one right
        n_donated = len(self._arenas) + len(self._slot_state)
        self._donate = tuple(range(6, 6 + n_donated))
        self._donate_blk = tuple(range(7, 7 + n_donated))
        # compiled programs are cached per (static shape, sampling
        # feature flags): an all-greedy engine compiles exactly the
        # argmax-only program shapes, and each sampling feature
        # (sampler planes / repetition-penalty presence / mask bias)
        # is compiled in only for dispatches whose active mix needs it
        self._chunk_fns = {}           # samp flags -> jitted fn
        self._blocks = {}              # (block size, flags) -> jitted fn
        self._vocab = int(model.config.vocab_size)
        # speculative decoding: per-request mode (submit(spec_decode=K));
        # the drafter is engine-level (host-side, shared by every spec
        # request) and defaults to prompt-lookup self-drafting the
        # first time a spec request arrives
        self._drafter = drafter
        self._verify_fns = {}          # (verify width, flags) -> jitted fn
        self._spec_k_max = 0           # engine-lifetime max spec_decode
        self._spec_fallback = set()    # per-iteration: spec slots that
        #                                ride the plain block instead

        # device-carried occupancy state, mirrored host-side ([B] ints
        # are cheap to push; the arenas never leave the device)
        self._tok = np.zeros((self.num_slots,), np.int32)
        self._lens = np.zeros((self.num_slots,), np.int32)
        self._done = np.ones((self.num_slots,), bool)
        # per-request PRNG replaced the old engine-carried key chain:
        # every draw is keyed by (request base key, output position),
        # never by dispatch order — see inference/sampling.py
        self._seed = int(seed)

        # multi-tenant batched LoRA serving (inference/lora.py): the
        # paged adapter store is engine-external (several engines may
        # share one); submit(adapter=) names a registered variant,
        # admission pins its arena slot, every dispatch with >= 1
        # adapter row compiles/uses the gathered-einsum program
        # variants.  The store's arenas must be at the serving compute
        # dtype — the gathered deltas contract against activations.
        self._adapters = adapter_store
        if adapter_store is not None:
            want = jnp.dtype(self.cfg.compute_dtype)
            if jnp.dtype(adapter_store.dtype) != want:
                raise ValueError(
                    f"adapter_store dtype {adapter_store.dtype} != "
                    f"engine compute_dtype {want} — the gathered LoRA "
                    f"einsums contract against activations of the "
                    f"compute dtype")
            if adapter_store.n_layers != n_layers:
                raise ValueError(
                    f"adapter_store holds {adapter_store.n_layers} "
                    f"layers but the model has {n_layers}")
        # fair-share admission (deficit-weighted round-robin): per-
        # tenant token-service accounting; weights scale each tenant's
        # fair share (2.0 = entitled to twice the service of a
        # weight-1 tenant).  Single-tenant traces keep every candidate
        # at one normalized-service value, so the fair term is a
        # constant and scheduling is byte-identical to priority/EDF/
        # FIFO (the determinism contract tests assert).
        self._tenant_weights = {}
        for t, w in dict(tenant_weights or {}).items():
            w = float(w)
            if w <= 0:
                raise ValueError(
                    f"tenant_weights[{t!r}] must be > 0, got {w}")
            self._tenant_weights[str(t)] = w
        self._tenant_served: dict = {}     # tenant -> tokens charged
        self._lora_dispatches = 0          # gathered-einsum dispatches
        # host-side per-reason wasted-token mirror (the goodput
        # counters' tenant label is open-vocabulary; this keeps the
        # closed per-reason breakdown exact per engine)
        self._wasted_reason = {r: 0 for r in GOODPUT_REASONS}
        self._slots: List[Optional[Request]] = [None] * self.num_slots
        self._queue: deque = deque()
        self._prefilling: deque = deque()
        # (request, device token) of the prompts whose final chunk is
        # enqueued and whose first token has not landed yet: emptied by
        # the next landing (``_land_first_tokens``), in the same step or,
        # for a chunk enqueued ahead of a harvest, the next
        self._first_owed: list = []
        # the next step's chunk pacing where the step before began it
        # (``_admit_ahead``), else None
        self._pacing = None
        self._swapped: List[Request] = []   # preempted, host-RAM KV
        self._swap_out_fn = None            # lazy: engines that never
        self._swap_in_fn = None             # swap compile neither
        self._finished: List[Request] = []
        self._clock = clock
        self._next_id = 0
        # scheduler accounting lives in the observability registry
        # (stats() reads per-engine counter deltas back out of it);
        # peak_queue/peak_blocks mirror the gauges' high-water marks as
        # plain ints so stats() stays exact even if the registry is
        # disabled mid-run
        self._m = _ServingInstruments(
            registry if registry is not None else obs_metrics.get_registry())
        # the collector's pauses, process-wide (runtime.gc.pause_seconds)
        obs_collector.install()
        self._m.slots_total.set(self.num_slots)
        self._m.kv_quant_dtype.set(1, dtype=self.kv_cache_dtype)
        self._m.weights_quant_dtype.set(1, dtype=self.weight_dtype)
        self._m.swap_host_blocks.set(0, reason="preempt")
        self._m.swap_host_blocks.set(0, reason="cache")
        self._m.slot_occupancy.set(0)
        self._m.blocks_free.set(self.num_blocks)
        self._m.blocks_in_use.set(0)
        self._m.shard_groups.set(1 if self.shard_group is not None else 0)
        self._m.shard_width.set(self._shard.n_shards
                                if self._shard is not None else 1)
        self._m.role.set(1, role=self.role)
        # chunk-final handoff staging (prefill-role engines only):
        # requests whose final chunk just sampled tok0 and whose KV
        # parcel now sits in the host tier awaiting router pickup
        # (Router._place_handoffs drains this via take_handoffs())
        self._handoff_ready = []
        # step-rate estimate for the arrival-aware fused window
        # (_step_inner): the last explicit step(now=) value and the
        # last observed positive now-delta; 0.0 = no estimate (wall-
        # clock-driven or first steps), which keeps the conservative
        # queued-arrival fusing block
        self._last_now = None
        self._step_dt = 0.0
        self._peak_queue = 0
        self._peak_blocks = 0
        # per-request flight recorder: every lifecycle transition emits
        # a structured event.  The default is a DISABLED instance so
        # the emit sites stay uniform (one bool test per call) and
        # ``engine.flight_recorder.enable()`` can be flipped live;
        # pass ``flight_recorder=FlightRecorder()`` for a recording
        # engine.  bind_clock puts event wall times on the ENGINE's
        # clock (one time base with request arrival/finish times, a
        # replay/fake engine clock included) unless the recorder was
        # constructed with an explicit clock of its own.
        self._fr = (flight_recorder if flight_recorder is not None
                    else FlightRecorder(enabled=False))
        self._fr.bind_clock(clock)
        # scheduler iteration index: stamped into every flight-recorder
        # event ("preempted at step 12") and incremented at step() start;
        # submit()/cancel() events between steps carry the last index
        self._step_idx = 0
        # dispatch-time accumulator for the host-vs-dispatch step split
        # (serving.step.{host,dispatch}_seconds); reset at step() start,
        # fed by every compiled-call site incl. swap gathers/scatters
        self._disp_s = 0.0
        # dispatch-ahead pipeline (async_dispatch=True, the default):
        # _pend_q holds the dispatched-but-unharvested decode
        # dispatches, bounded by async_depth; _overlap_s/_stall_s
        # carve harvest waits and injected stalls out of the step's
        # host-seconds attribution; the _lazy_stacks list tracks
        # demote gathers enqueued during plan and reconciled at the
        # next harvest point.
        # async_dispatch=False is the exact lockstep kill-switch.
        # async_depth=1 (the default) keeps PR 10's double-buffered
        # pipeline AND its scheduling-identity contract (every
        # EOS-configured iteration still syncs, so dispatch counts
        # match lockstep exactly).  async_depth=S >= 2 opts into the
        # finish-bitmap protocol: EOS leaves the per-iteration sync
        # path (the device bitmap is polled one harvest late — a
        # finished rider's slot frees one plan later, deterministic
        # and flight-recorder-stamped) and provably eventless windows
        # dispatch S iterations as ONE fused program.
        self.async_dispatch = bool(async_dispatch)
        self.async_depth = int(async_depth)
        if self.async_depth < 1:
            raise ValueError(
                f"async_depth must be >= 1, got {async_depth}")
        if self.async_depth > 1 and not self.async_dispatch:
            raise ValueError(
                f"async_depth={self.async_depth} needs "
                f"async_dispatch=True — the lockstep kill-switch arm "
                f"has no pipeline to deepen")
        self._pend_q: deque = deque()
        self._overlap_s = 0.0
        self._stall_s = 0.0
        self._wait_s = 0.0           # this step's device waits and stalls
        self._wait_cpu_ns = 0        # the thread's CPU time inside them
        self._in_step = False
        self._lazy_parcels: List[int] = []   # tier keys awaiting rows
        # finishes discovered by a flush OUTSIDE a step (cancel()
        # between steps, run()'s pre-raise drain): handed to the next
        # step()'s return so run() never loses a terminal request
        self._flush_finishes: List[Request] = []
        self._m.async_depth.set(0)

    @property
    def _pending(self) -> Optional[_PendingBlock]:
        """The OLDEST un-harvested dispatch (None = pipeline empty) —
        the depth-1 spelling tests and tools grew up with."""
        return self._pend_q[0] if self._pend_q else None

    # -- block accounting --
    def _blocks_needed(self, n: int, m: int) -> int:
        """Blocks a request writes: prompt + generated K/V is n + m - 1
        slots (the last sampled token is emitted, never fed back)."""
        return -(-(n + m - 1) // self.block_len)

    def _update_block_gauges(self):
        free = self._pool.available()
        in_use = self._pool.in_use()
        self._m.blocks_free.set(free)
        self._m.blocks_in_use.set(in_use)
        self._peak_blocks = max(self._peak_blocks, in_use)

    def _count_kv_sweep(self, last_indices):
        """Model one dispatch's KV read traffic into
        ``serving.kv.bytes_swept``: one entry per (row, scanned step)
        giving that sweep's last valid index; each is rounded up to
        whole blocks (the paged kernels' ``length // L + 1`` DMA
        granularity, clamped to the table span — the kernel never
        streams past ``max_blocks``) and charged the per-row per-layer
        byte cost (codes + scale planes for int8).  Modeled, not
        measured, and PARTICIPATING rows only: vacant/frozen rows in
        the same dispatch do DMA their (trash-routed) frontier, but
        that waste traffic is excluded so the counter reads as useful
        KV bytes, a conservative roofline basis."""
        blocks = np.minimum(
            np.asarray(last_indices, np.int64) // self.block_len + 1,
            self.max_blocks)
        self._m.kv_bytes_swept.inc(
            int(blocks.sum()) * self.block_len * self._kv_row_bytes)

    def _count_weight_sweep(self, forwards: int):
        """Modeled weight-streaming traffic: every dispatched forward
        (one decode scan step, one prefill chunk, one verify pass)
        streams the whole weight set from HBM once — non-quantized
        params at the compute dtype, quantized projections at their
        code+scale width (``_weight_sweep_bytes``).  Modeled like
        ``_count_kv_sweep``, and charged for EVERY engine (full-
        precision included) so a quantized and a full-precision engine
        on the same trace read strictly ordered bytes."""
        self._m.weights_bytes_swept.inc(
            int(forwards) * self._weight_sweep_bytes)

    # -- goodput ledger --
    def _ledger(self, useful: int, tenant: str = "default",
                **wasted: int):
        """Account one dispatch's token-positions into the goodput
        ledger.  Conservation (useful + wasted == dispatched) holds BY
        CONSTRUCTION: the dispatched counter is incremented by exactly
        the sum of the classified parts, so the registry identity can
        never drift — what CAN go wrong is a call site mis-splitting a
        dispatch, which the negative guard and the tier-1 cross-checks
        (wasted{spec_reject} == flight-recorder rejected sums, decode
        positions == busy_slot_steps) catch.  Positions are counted
        over PARTICIPATING rows only, the ``_count_kv_sweep``
        convention: vacant/frozen rows in the same compiled dispatch
        do burn FLOPs, but counting them would make goodput a function
        of slot-pool geometry instead of scheduling quality.
        ``tenant`` attributes the whole call to one tenant (call sites
        split multi-tenant dispatches per rider), so conservation
        holds per tenant label too."""
        total = useful
        for reason, n in wasted.items():
            if reason not in GOODPUT_REASONS:
                raise ValueError(
                    f"unknown goodput waste reason {reason!r} — known: "
                    f"{GOODPUT_REASONS}")
            if n < 0:
                raise ValueError(
                    f"goodput ledger: negative {reason} count {n} — a "
                    f"dispatch was mis-split")
            total += n
        if useful < 0:
            raise ValueError(
                f"goodput ledger: negative useful count {useful}")
        if total == 0:
            return
        self._m.goodput_dispatched.inc(total, tenant=tenant)
        if useful:
            self._m.goodput_useful.inc(useful, tenant=tenant)
        for reason, n in wasted.items():
            if n:
                self._m.goodput_wasted.inc(n, reason=reason,
                                           tenant=tenant)
                # host-side per-reason mirror: the tenant label made
                # the counter's label space open-vocabulary, so the
                # closed per-reason breakdown stats() reports is kept
                # exactly here (per engine by construction)
                self._wasted_reason[reason] += n

    @staticmethod
    def _slo_class(req: Request) -> str:
        """The SLO-attainment class label: the priority class
        (``p<N>``); the counters carry the submitting tenant as a
        second label, so per-tenant/per-adapter attainment is one
        exporter group-by away."""
        return f"p{req.priority}"

    def _slo_account(self, req: Request):
        """Score a terminal request against its SLO, by class.  Only
        SLO-carrying requests (a deadline or a queue-delay bound)
        count; ``deadline_s`` never kills a request (PR 7), so a late
        finish is the 'missed' outcome deadline feeds.  Cancelled
        requests are a user action, not an SLO outcome."""
        if req.deadline is None and req.max_queue_delay_s is None:
            return
        cls = self._slo_class(req)
        if req.state == "finished" and (
                req.deadline is None or req.finish_time <= req.deadline):
            self._m.slo_attained.inc(**{"class": cls,
                                        "tenant": req.tenant})
        elif req.state in ("finished", "timeout", "shed"):
            self._m.slo_missed.inc(**{"class": cls,
                                      "tenant": req.tenant})

    def _release_blocks(self, req: Request):
        """Unpin every block the request holds and trash its table
        row.  IDEMPOTENT by construction: the block list is cleared
        before returning, so a second call (a finish racing a cancel,
        a fault-handler retry) unpins nothing — double-release is a
        no-op here, and an unpin below refcount 0 still raises inside
        the pool as the backstop."""
        for b in req.blocks:
            self._pool.unpin(b)
        req.blocks = []
        req.matched = []
        if req.adapter_slot is not None:
            # the adapter pin has exactly the blocks' lifetime (held
            # admission -> retirement/preemption); the None guard
            # keeps this as idempotent as the block release
            self._adapters.release(req.adapter)
            req.adapter_slot = None
        if req.slot is not None:
            self._tables[req.slot] = self._pool.trash
        self._update_block_gauges()

    def _alloc(self, n: int) -> Optional[List[int]]:
        """``BlockPool.alloc`` behind the fault-injection hook: an
        armed allocation failure makes the pool look dry to exactly
        this call — admission back-off, the valve and preemption all
        exercise their real paths."""
        if self._fault is not None and self._fault.take_alloc_failure():
            return None
        return self._pool.alloc(n)

    def _phase(self, name: str, **attrs) -> _Phase:
        return _Phase(self._clock, name, **attrs)

    def _wait(self, name: str, **attrs) -> _Wait:
        return _Wait(self, name, **attrs)

    # -- dispatch-ahead pipeline (plan / harvest) --
    def _charge_overlap(self, dt: float):
        """Account time spent blocking on a PREVIOUS iteration's
        device arrays: observed into serving.step.overlap_seconds and
        carved out of this step's host-seconds remainder."""
        self._m.step_overlap.observe(dt)
        if self._in_step:
            self._overlap_s += dt

    def _block_sync_reason(self, n: int, active: List[int],
                           lag: int = 0):
        """Why THIS decode dispatch's outputs cannot be deferred (None
        = deferrable).  A harvest may be deferred only when the next
        iteration's scheduling is provably output-independent: no
        host-built logit plane (mask bias, repetition-penalty
        presence) needs the emitted token before the next dispatch, no
        speculative slot needs a host accept/rollback decision, and no
        rider's token BUDGET can exhaust inside the dispatch (the plan
        knows budgets exactly — ``lag`` corrects host truth for steps
        still in flight — so budget finishes always harvest sync and
        retire on the lockstep schedule; a ``steps_per_call`` block
        under load nearly always holds one, since the plan dispatches
        it while ANY rider needs all of it).  EOS is depth-dependent: the
        depth-1 pipeline keeps PR 10's contract (scheduling identity
        with lockstep ⇒ every EOS-configured iteration syncs), while
        async_depth >= 2 engines read EOS from the in-trace finish
        bitmap at harvest instead — one dispatch late, the lag
        deterministic — so ``eos`` leaves the per-iteration sync path
        and is charged only when the pipeline runs DRY on in-flight
        finishes (the depth-flush path in ``_step_inner``).  The first
        matching reason is charged to serving.async.syncs."""
        if not self.async_dispatch:
            # kill-switch arm: never charged to the counter (the inc
            # below is gated on async_dispatch), so deliberately NOT
            # an ASYNC_SYNC_REASONS member
            return "off"              # graftlint: disable=vocab
        if self.cfg.eos_token_id is not None and self.async_depth == 1:
            return "eos"
        for i in active:
            r = self._slots[i]
            if r is None or r.state != "decode":
                continue              # retired by a same-step harvest
            if r.remaining - lag <= n:
                return "budget"
            sp = r.sampling
            if sp is not None and sp.mask_processor is not None:
                return "mask"
            if sp is not None and sp.needs_penalty:
                return "penalty"
            if r.spec_k is not None:
                return "spec"
        # any spec-mode decode slot anywhere (verifying, not riding)
        # keeps the iteration sync: the verify path reads host mirrors
        if any(r is not None and r.spec_k is not None
               and r.state == "decode" for r in self._slots):
            return "spec"
        return None

    # graftlint: plan-phase
    def _harvest_next(self, out: List[Request], reason: str):
        """Force the OLDEST pending dispatch's outputs to host and
        absorb them — the finish-bitmap poll site: the materialized
        ``done`` carry says which riders finished on device (EOS or
        budget) while later dispatches were already in flight.
        Harvest order is FIFO, so host truth (tokens, remaining, lens
        mirrors) is fresh up to the popped dispatch.  The wait charges
        to serving.step.overlap_seconds, never to host_seconds — this
        is the slice the pipeline hides under device time.  ``reason``
        (``deferred``: the pipeline's own overlap point; else the
        forced sync's reason) labels the ``serving.harvest`` span."""
        if not self._pend_q:
            return
        with _span("serving.harvest", reason=reason):
            p = self._pend_q.popleft()
            self._m.async_depth.set(len(self._pend_q))
            with self._wait("serving.harvest.wait") as wait:
                toks = np.asarray(p.toks_d)
                tok = np.array(p.tok_d)   # np.array: writable host copies
                lens = np.array(p.lens_d)
                done = np.array(p.done_d)     # the finish bitmap
                self._count_experts(p)
                self._charge_overlap(wait.stop())
            n_before = len(out)
            with _span("serving.harvest.absorb"):
                toks = self._checked_harvest(toks)
                self._absorb_block(p, toks, tok, lens, done, out)
            if self.async_depth == 1 and len(out) > n_before:
                # the PR-10 contract at depth 1: deferral is legal
                # ONLY when no rider can finish inside the block (EOS
                # syncs, budget syncs) — a finish here means the defer
                # predicate regressed, and silent off-schedule
                # retirement is worse than a loud failure
                raise RuntimeError(
                    "deferred harvest produced a finish at "
                    "async_depth=1 — the defer predicate "
                    "(_block_sync_reason) is broken")
            self._reconcile_host_tier()

    def _flush_async(self, reason: str,
                     out: Optional[List[Request]] = None):
        """Harvest EVERY pending dispatch EARLY (oldest first) because
        host truth is semantically required right now; charged ONCE to
        serving.async.syncs{reason=} however deep the pipeline ran.  A
        no-op (and not counted) when nothing is pending.  Finishes the
        flush discovers (possible at async_depth >= 2 — the finish
        bitmap defers them) land in ``out`` when the caller is inside
        a step, else carry over to the next step()'s return via
        ``_flush_finishes``."""
        if not self._pend_q:
            return
        if reason not in ASYNC_SYNC_REASONS:
            raise ValueError(
                f"unknown forced-sync reason {reason!r} — known: "
                f"{ASYNC_SYNC_REASONS}")
        self._m.async_syncs.inc(reason=reason)
        sink = out if out is not None else self._flush_finishes
        while self._pend_q:
            self._harvest_next(sink, reason)

    def _reconcile_host_tier(self):
        """Materialize every demote parcel enqueued during plan (the
        overlapped prefix-cache swap-out), at a harvest point instead
        of serially inside admission.  Resolution happens PER ENTRY —
        each parcel ends up owning its contiguous per-block copies and
        flips ``resolved`` (so ``HostTier.audit`` shape checks apply
        from here on) — and once every live entry of a gather page has
        resolved, the page itself (table-width, trash rows included)
        is garbage, so host residency converges to exactly what the
        tier's block accounting says.  Dropped/evicted/promoted keys
        are skipped.  Idempotent and cheap when nothing is
        outstanding."""
        if not self._lazy_parcels:
            return
        keys, self._lazy_parcels = self._lazy_parcels, []
        t0 = self._clock()
        for k in keys:
            e = self._host_tier.entry(k)
            if e is not None and not e.resolved:
                e.rows    # the property materializes on first access
        self._charge_overlap(self._clock() - t0)

    def _resolve_entries(self, entries):
        """Force still-lazy host-tier parcels a consumer (promotion,
        resume) needs NOW; the wait is a block on a previous
        iteration's gather, so it charges to overlap, not host."""
        lazy = [e for e in entries if e is not None and not e.resolved]
        if not lazy:
            return
        t0 = self._clock()
        for e in lazy:
            e.rows        # the property materializes on first access
        self._charge_overlap(self._clock() - t0)

    def _checked_harvest(self, toks: np.ndarray) -> np.ndarray:
        """Validate one decode harvest BEFORE its outputs become host
        truth: every materialized token id must lie in the model
        vocabulary (vacant/frozen rows emit the pad token, which
        does).  Out-of-range ids are the int-token-stream analogue of
        non-finite logits — a poisoned dispatch — and adopting them
        would corrupt request streams, the prefix tree and every
        downstream sharer, so the harvest raises
        :class:`PoisonedDispatchError` instead and leaves the token
        streams untouched (the router fails the replica over).  The
        fault injector's ``poison_at_step`` corrupts the materialized
        array right here, upstream of the same validation a real
        device fault would hit."""
        if self._fault is not None and \
                self._fault.take_poison(self._step_idx):
            # model the corrupted dispatch: the validation below is
            # the engine's real (always-on) detector
            toks = np.full_like(toks, -1)
        if toks.size and (int(toks.min()) < 0
                          or int(toks.max()) >= self._vocab):
            raise PoisonedDispatchError(
                f"decode harvest at step {self._step_idx} produced "
                f"token ids outside [0, {self._vocab}) — poisoned "
                f"dispatch (non-finite logits / corrupted outputs); "
                f"the harvest was NOT adopted as host truth")
        return toks

    def _absorb_block(self, p: _PendingBlock, toks: np.ndarray,
                      tok: np.ndarray, lens: np.ndarray,
                      done: np.ndarray, out: List[Request]):
        """The harvest half of one decode dispatch: adopt the
        materialized carries as host truth, account the KV sweep and
        the goodput ledger, extend each rider's token stream, emit the
        flight-recorder events (stamped with the DISPATCH step; a
        ``lag`` attr records how many steps later the harvest ran) and
        retire riders whose finish bitmap flipped.  Shared verbatim by
        the sync path (immediately after dispatch) and the deferred
        path (after later dispatches were enqueued).

        A fused dispatch (``p.iters > 1``) is re-split into its
        logical iterations here, ITERATION-MAJOR, so token streams,
        per-iteration ledger splits, KV-sweep modeling and the
        decode_block event sequence are byte-identical (modulo
        step/lag) to the unfused engine running ``p.iters`` separate
        blocks.  Two rider classes are skipped per iteration, both
        frozen device-side so their cells held pad: GHOST riders
        (finished in an EARLIER pending dispatch — at depth >= 2 the
        plan could not know yet) and riders that finished in an
        earlier iteration of THIS dispatch.  Skipped cells follow the
        ``_count_kv_sweep`` convention (frozen rows excluded), which
        keeps the ledger and sweep counters exactly what a lockstep
        engine would have charged.

        A rider may also finish INSIDE its segment, at its budget (the
        plan dispatches the whole block while any rider needs all of
        it) or at an EOS: one freeze protocol.  It takes exactly the
        tokens up to its finish and retires there; the cells behind
        held a frozen row, so they are pad in the ledger, absent from
        the KV-sweep model, and no token of the request.

        A row whose occupant did not ride ``p`` (admitted since: into a
        vacant slot, or ahead of this harvest into a slot that one of
        ``p``'s riders leaves) keeps the carries the host wrote for it."""
        per, active = p.per_iter, p.active
        riders = dict(zip(active, p.reqs))
        for i, r in enumerate(self._slots):
            if r is not None and riders.get(i) is not r:
                tok[i], lens[i], done[i] = \
                    self._tok[i], self._lens[i], self._done[i]
        self._tok = tok
        self._lens = lens
        eos = self.cfg.eos_token_id
        t = self._clock()
        lag = self._step_idx - p.step_idx
        attrs = {"lag": lag} if lag else {}
        steps = np.arange(per)
        sweep: List[np.ndarray] = []
        for j in range(p.iters):
            gp: dict = {}      # tenant -> [useful, pad] this iteration
            for idx, i in enumerate(active):
                req = p.reqs[idx]
                if req.state != "decode":
                    continue           # ghost / finished-earlier rider
                # the cells this rider was live in: its budget may end
                # inside the segment, and an EOS may end it sooner; the
                # cells behind either held a frozen row
                owed = min(per, req.remaining)
                row = toks[i, j * per:j * per + owed]
                at_eos = (np.flatnonzero(row == eos) if eos is not None
                          else ())
                hit_eos = len(at_eos) > 0
                took = int(at_eos[0]) + 1 if hit_eos else owed
                # per-step frontier, not the final lens: scanned step
                # s scatters at index pre_lens+s and attends up to it
                sweep.append(int(p.pre_lens[i]) + j * per + steps[:took])
                cell = gp.setdefault(req.tenant, [0, 0])
                cell[0] += took
                cell[1] += per - took
                # ``lag`` is deterministic (a step delta, never wall):
                # parity comparisons against a sync engine strip it
                self._fr.emit("decode_block", req.request_id,
                              p.step_idx, steps=took, **attrs)
                req.tokens.extend(row[:took].tolist())
                req.remaining -= took
                if hit_eos or req.remaining == 0 or (
                        req.sampling is not None and
                        req.sampling.mask_processor is not None and
                        self._mask_dead_end(req)):
                    # the finish bitmap observed host-side: EOS in
                    # this iteration's segment, the budget ran out, or
                    # (per == 1 for mask rows, clamped at dispatch) the
                    # grammar has no continuation — co-resident rows
                    # are untouched
                    if self._slots[i] is req:
                        self._slots[i] = None
                        done[i] = True     # freeze the row until re-use
                    else:
                        # the slot was handed over ahead of this harvest:
                        # the row and its table are the new occupant's
                        req.slot = None
                    self._release_blocks(req)
                    self._finish(req, t, out, lag=lag)
            for tenant, (u, pad) in gp.items():
                self._ledger(u, tenant=tenant, pad=pad)
        if sweep:
            self._count_kv_sweep(np.concatenate(sweep))
        # every scanned decode step streamed the whole weight set once
        self._count_weight_sweep(per * p.iters)
        self._done = done
        self._m.slot_occupancy.set(
            sum(r is not None for r in self._slots))

    # -- host tier (shared by preemption swap + prefix-cache demotion) --
    # graftlint: plan-phase
    def _gather_rows(self, ids_row: np.ndarray,
                     materialize: bool = True):
        """Read ``ids_row``'s arena rows (EXACT at-rest bytes: float
        K/V, or int8 codes + scale planes) — the ONE gather discipline
        behind preemption swap-out and prefix-cache demotion.
        ``ids_row`` is table-width (one compiled shape); trash-row
        entries gather finite garbage the callers slice away or
        ignore.  ``materialize=True`` forces host numpy stacks (the
        preemption path: a swap record's bytes are correctness-
        bearing); ``materialize=False`` returns the un-forced device
        stacks — the dispatch-ahead demote path wraps them in a
        ``_LazyStacks`` and reconciles at the next harvest point."""
        t0 = self._clock()
        dev = self._swap_out()(jnp.asarray(ids_row), *self._arenas)
        if materialize:
            # a swap record's bytes are correctness-bearing, so the
            # preemption path forces them NOW (the caller charged the
            # flush); the demote path below stays lazy and reconciles
            # at a harvest point
            out = [np.asarray(r) for r in dev]     # sync: preempt
        else:
            out = list(dev)
        self._disp_s += self._clock() - t0
        return out

    def _scatter_rows(self, ids_row: np.ndarray,
                      stacks: List[np.ndarray]):
        """Write per-arena row ``stacks`` (k <= table-width rows each)
        into the arena rows named by ``ids_row`` through the ONE
        donation-matched swap-in program — shared by preemption resume
        and prefix-cache promotion.  Stacks are zero-padded to table
        width; the caller's ``ids_row`` routes pad rows at the trash
        row (the write-masking contract of every paged writer)."""
        t0 = self._clock()
        padded = []
        for s in stacks:
            pr = np.zeros((self.max_blocks,) + s.shape[1:], s.dtype)
            pr[:s.shape[0]] = s
            padded.append(jnp.asarray(pr))
        outp = self._swap_in()(jnp.asarray(ids_row), *padded,
                               *self._arenas)
        self._arenas = list(outp)
        self._disp_s += self._clock() - t0

    def _update_host_gauge(self):
        self._m.swap_host_blocks.set(
            self._host_tier.blocks("preempt"), reason="preempt")
        self._m.swap_host_blocks.set(
            self._host_tier.blocks("cache"), reason="cache")

    def _demote_blocks(self, blocks: List[int]):
        """``BlockPool.reclaim_cb`` (radix mode): instead of forgetting
        reclaimed cached blocks, gather their EXACT at-rest bytes out
        of every arena (codes + scale planes for the int8 cache) and
        demote them to the host tier; the radix tree relabels the
        positions host-resident so a later hit swaps the bytes back in
        rather than recomputing.  ONE batched gather per alloc —
        through the same compiled table-width program preemption uses
        (ids padded with the trash row; wider reclaims page through
        it) — so demotion costs a dispatch per admission, not per
        block.  When the tier cannot take parcels (capacity 0 /
        pinned-full) the positions become holes — the gather is
        skipped entirely, and the next miss recomputes and refills
        them."""
        if not self._host_tier.would_accept(1):
            for b in blocks:
                self._radix.drop_hbm(b)
            return
        demoted = 0
        w = self.max_blocks
        with _span("serving.cache_swap_out", blocks=len(blocks)):
            for i in range(0, len(blocks), w):
                chunk = blocks[i:i + w]
                ids = np.full((w,), self._pool.trash, np.int32)
                ids[:len(chunk)] = chunk
                if self.async_dispatch:
                    # overlapped swap-out: ENQUEUE the gather now (the
                    # device values are captured functionally — later
                    # donated arena overwrites cannot reach them) and
                    # hand each parcel a lazy row view; the host copy
                    # materializes at the next harvest point
                    # (_reconcile_host_tier) instead of serially here
                    ls = _LazyStacks(
                        self._gather_rows(ids, materialize=False))
                    for j, b in enumerate(chunk):
                        thunk = (lambda ls=ls, j=j: ls.block_rows(j))
                        key = self._radix.demote(b, thunk)
                        if key is not None:
                            demoted += 1
                            self._lazy_parcels.append(key)
                else:
                    stacks = self._gather_rows(ids)
                    for j, b in enumerate(chunk):
                        rows = [np.ascontiguousarray(s[j:j + 1])
                                for s in stacks]
                        if self._radix.demote(b, rows) is not None:
                            demoted += 1
        if demoted:
            self._m.swap_out_blocks.inc(demoted, reason="cache")
            self._m.swap_out_bytes.inc(
                demoted * self.block_len * self._kv_row_bytes,
                reason="cache")
            # engine-scoped (the pool demotes on behalf of the cache,
            # not of one request) — lane -1 in the chrome export
            self._fr.emit("swap_out", ENGINE_EVENT, self._step_idx,
                          blocks=demoted, reason="cache")
        self._update_host_gauge()

    def _audit_host_tier(self):
        """BlockPool.check() hook: tier-internal invariants plus the
        preempt-key <-> swap-list bijection (cache keys are audited
        against the tree by ``RadixPrefixCache.audit``)."""
        errs = list(self._host_tier.audit())
        want = sorted(r.swap.host_key for r in self._swapped)
        got = sorted(self._host_tier.keys("preempt"))
        if want != got:
            errs.append(
                f"host tier preempt keys {got} != swap-list records "
                f"{want}")
        return errs

    # -- request intake --
    def submit(self, prompt_ids, seq_len=None, max_new_tokens=32,
               arrival_time=None, spec_decode=None,
               sampling: Optional[SamplingParams] = None,
               priority: int = 0, deadline_s: Optional[float] = None,
               max_queue_delay_s: Optional[float] = None,
               adapter: Optional[str] = None,
               tenant: Optional[str] = None,
               stream: bool = False):
        """Enqueue one request.  ``prompt_ids`` is a 1-D id array of at
        most ``prompt_len`` tokens (right-padded internally);
        ``arrival_time`` (in ``clock()`` units) lets a trace replay
        future arrivals — the scheduler will not admit a request before
        it has "arrived".  ``sampling=SamplingParams(...)`` gives THIS
        request its own decode configuration (temperature / top-k /
        top-p / repetition penalty / seed / token-mask processor);
        omitted, the request inherits the engine-level default
        (``do_sample=True`` knobs, or plain greedy).  ``spec_decode=K``
        puts THIS request in speculative-decoding mode: its decode
        phase runs drafter proposals of up to K tokens through the
        K+1-position verify forward instead of riding the plain decode
        block.  Greedy spec requests keep the argmax-prefix acceptance
        (output token-for-token unchanged); sampled spec requests run
        stochastic speculative sampling (accept draft i with prob
        ``min(1, p/q)``, resample the residual on reject — the output
        DISTRIBUTION is unchanged, per-seed streams differ from the
        non-spec engine).  The one unsupported combination is
        ``spec_decode`` + a ``mask_processor``: a draft position's
        mask depends on host state the drafter bypasses.  With
        prefix caching on, the prompt's full blocks are probed against
        the cache here and any hits are PINNED so they cannot be
        reclaimed while the request waits.

        SLO knobs: ``priority`` (int, higher admits first and is
        preempted last; default 0), ``deadline_s`` (seconds from
        arrival — EDF order within a priority and the tie-breaker for
        victim selection; never itself a kill switch) and
        ``max_queue_delay_s`` (a QUEUE-WAIT bound: a request still
        queued after this many seconds finishes with state
        ``"timeout"`` instead of being served late — once admitted it
        always runs to completion).  With ``max_queue=N`` set on the
        engine, a full queue sheds — AFTER every validation, so an
        invalid submission never displaces anyone: expired queued
        entries are first swept to ``"timeout"``, then either some
        queued request of strictly lower class than this arrival is
        displaced (state ``"shed"``) or THIS submit raises
        ``AdmissionError`` and nothing is enqueued.

        Multi-tenant LoRA: ``adapter=`` names a variant registered in
        the engine's ``AdapterStore`` — admission pins its arena slot
        (swapping its weights in from host RAM when demoted) and the
        request decodes through its gathered low-rank delta,
        token-exact vs running alone on merged weights.  ``tenant=``
        names the fair-share accounting bucket (default one shared
        ``"default"`` bucket = plain FIFO-within-class): within a
        priority/EDF class, admission order becomes deficit-weighted
        round-robin over tenants, so one tenant's burst cannot starve
        another's steady stream.

        ``stream=True`` returns a :class:`TokenStream` over the
        request instead of the request itself (``handle.request``
        recovers it): incremental tokens drain through ``read()`` at
        the engine's harvest boundaries, token-for-token identical to
        the non-streamed output — see the TokenStream docstring."""
        if self.role == "decode":
            # role enforcement at the door: a decode replica owns no
            # prefill budget — fresh prompts belong on a prefill-
            # capable replica; only migrate_in() parcels land here
            raise AdmissionError(
                "decode-role engine does not accept fresh submits "
                "(prompts route to prefill-capable replicas; this "
                "replica only resumes migrated KV parcels)")
        ids = np.asarray(getattr(prompt_ids, "_value", prompt_ids))
        ids = np.asarray(ids).reshape(-1).astype(np.int32)
        if ids.size < 1 or ids.size > self.prompt_len:
            raise ValueError(
                f"prompt must be 1..{self.prompt_len} tokens, got "
                f"{ids.size}")
        n = int(seq_len) if seq_len is not None else int(ids.size)
        if n < 1 or n > ids.size:
            raise ValueError(
                f"seq_len must be in [1, {ids.size}], got {n}")
        m = int(max_new_tokens)
        if m < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {m}")
        if sampling is not None:
            if not isinstance(sampling, SamplingParams):
                raise ValueError(
                    f"sampling must be a SamplingParams, got "
                    f"{type(sampling).__name__}")
            sampling.validate()
        sp = sampling if sampling is not None else self._default_sampling
        spec_k = None
        if spec_decode is not None:
            if self._slot_state:
                raise SlotStateError(self._model, "speculative decoding "
                                     "(submit(spec_decode=))")
            spec_k = int(spec_decode)
            if spec_k < 1:
                raise ValueError(
                    f"spec_decode must be >= 1 draft tokens, got "
                    f"{spec_decode}")
            if sp is not None and sp.mask_processor is not None:
                raise ValueError(
                    "spec_decode cannot compose with a token-mask "
                    "processor: a draft position's mask depends on "
                    "host-side state the drafter bypasses — submit "
                    "the request without spec_decode (sampling "
                    "without a mask composes fine)")
        if n + m - 1 > self.max_cache_len:
            raise ValueError(
                f"prompt ({n}) + max_new_tokens ({m}) - 1 = {n + m - 1} "
                f"tokens ({self._blocks_needed(n, m)} blocks of "
                f"{self.block_len}) exceeds max_cache_len "
                f"({self.max_cache_len} tokens = {self.max_blocks} "
                f"blocks per slot)")
        if self._blocks_needed(n, m) > self.num_blocks:
            raise ValueError(
                f"request needs {self._blocks_needed(n, m)} blocks of "
                f"{self.block_len} ({n + m - 1} tokens) but the pool "
                f"only has num_blocks={self.num_blocks} — it could "
                f"never be admitted")
        if adapter is not None:
            adapter = str(adapter)
            if self._adapters is None:
                raise ValueError(
                    f"submit(adapter={adapter!r}) needs an engine "
                    f"constructed with adapter_store= (no AdapterStore "
                    f"is attached)")
            if self._adapters.state(adapter) is None:
                raise ValueError(
                    f"adapter {adapter!r} is not registered in the "
                    f"adapter store — known: {self._adapters.names()}")
        prio = int(priority)
        if deadline_s is not None and float(deadline_s) <= 0:
            raise ValueError(
                f"deadline_s must be > 0 seconds from arrival, got "
                f"{deadline_s}")
        if max_queue_delay_s is not None and float(max_queue_delay_s) < 0:
            raise ValueError(
                f"max_queue_delay_s must be >= 0, got {max_queue_delay_s}")
        padded = np.full((self.prompt_len,), self.cfg.pad_token_id,
                         np.int32)
        padded[:ids.size] = ids
        now = self._clock()
        arrival = now if arrival_time is None else float(arrival_time)
        deadline = None if deadline_s is None \
            else arrival + float(deadline_s)
        req = Request(self._next_id, padded, n, m, arrival,
                      pad_token_id=self.cfg.pad_token_id)
        req.submit_time = now
        req.spec_k = spec_k
        req.adapter = adapter
        req.tenant = "default" if tenant is None else str(tenant)
        # a waiting tenant must exist in the service ledger at 0 so
        # the deficit gauges (and the WRR choice) see it immediately
        self._tenant_served.setdefault(req.tenant, 0)
        req.sampling = sp
        req.priority = prio
        req.deadline = deadline
        req.max_queue_delay_s = (None if max_queue_delay_s is None
                                 else float(max_queue_delay_s))
        if sp is not None and not sp.is_greedy:
            # an explicit seed draws from the USER's stream (the
            # seeded-determinism contract: same seed => same stream,
            # whatever the batch around it looked like); seedless
            # sampled requests — explicit params with seed=None or the
            # engine default — fold the request id into the engine
            # seed, so concurrent streams stay independent of each
            # other but a replayed trace (same submission order)
            # reproduces
            req.samp_base = (base_key(sp.seed) if sp.seed is not None
                             else np.asarray(jax.random.fold_in(
                                 jax.random.PRNGKey(self._seed),
                                 req.request_id), np.uint32))
        # chunk grid: any slice [start, start + chunk_len) with
        # start < seq_len must be in range
        req.chunk_ids = np.full((self.prompt_len + self.chunk_len,),
                                self.cfg.pad_token_id, np.int32)
        req.chunk_ids[:self.prompt_len] = padded
        # everything past this point runs with prefix-probe pins
        # potentially held: any failure (a raising instrument/span hook,
        # a future validation added below the probe) must UNPIN the
        # probed blocks and drop the request, or each failed submit
        # would leak refcounts until the pool wedges
        try:
            if self._radix is not None:
                # token-level probe: pin the span's HBM blocks against
                # reclaim and its host parcels against tier eviction
                # while the request queues; the admission re-probe
                # revalidates (and usually extends) the match
                self._probe_radix(req)
                if req.matched:
                    self._update_block_gauges()
            if sp is not None and sp.mask_processor is not None:
                # host state-machine init + width check, AFTER the
                # prefix probe: a raise here (bad table width, a
                # processor rejecting the prompt) rolls back through
                # the same unpin path as any other post-probe failure
                sp.mask_processor.begin(ids[:n])
                allowed0 = np.asarray(sp.mask_processor.allowed(), bool)
                if allowed0.size != self._vocab:
                    raise ValueError(
                        f"mask_processor.allowed() is {allowed0.size} "
                        f"wide but the model vocabulary is {self._vocab}")
                if not allowed0.any():
                    # an all-banned state would make the bias plane a
                    # uniform shift (no constraint at all) and the
                    # emitted token illegal — reject up front; mid-
                    # stream dead ends instead FINISH the request (see
                    # the advance sites)
                    raise ValueError(
                        "mask_processor allows no token in its start "
                        "state — the grammar has no legal first output")
            # bounded queue LAST, after EVERY validation above: an
            # invalid submission must never destroy an innocent queued
            # victim.  Expired (past-max_queue_delay_s) entries are
            # swept first so dead weight the next step would drop as
            # timeouts neither blocks a fresh admission nor gets
            # mislabeled "shed".  Then either the WORST queued request
            # (lowest priority, then latest deadline, then newest
            # submission) is marked for displacement — only a STRICTLY
            # lower class than the arrival; within a class the earlier
            # submission keeps its place — or the arrival is rejected.
            # The victim is shed only after the new request is safely
            # enqueued, so a late failure (a raising span hook) rolls
            # the arrival back without having harmed the victim.
            evict = None
            if self.max_queue is not None and \
                    len(self._queue) >= self.max_queue:
                self._sweep_timeouts(now, [])
            if self.max_queue is not None and \
                    len(self._queue) >= self.max_queue:
                worst = min(reversed(self._queue), key=self._shed_key)
                if self._shed_key(worst) < (prio,
                                            _neg_deadline(deadline)):
                    evict = worst
                else:
                    self._m.shed.inc(reason="rejected")
                    _span_instant("serving.request.reject",
                                  queue_depth=len(self._queue))
                    raise AdmissionError(
                        f"queue full ({len(self._queue)} >= max_queue="
                        f"{self.max_queue}) and no queued request is "
                        f"of strictly lower class than this arrival "
                        f"(priority={prio}, deadline_s={deadline_s})",
                        queue_depth=len(self._queue),
                        max_queue=self.max_queue)
            if spec_k is not None:
                # only AFTER every validation AND the bounded-queue
                # decision above: a rejected submit — ValueError or
                # AdmissionError — must not widen the engine-lifetime
                # verify width (or install the default drafter) for a
                # request that never ran
                if self._drafter is None:
                    self._drafter = NGramDrafter()
                self._spec_k_max = max(self._spec_k_max, spec_k)
            self._next_id += 1
            self._queue.append(req)
            _span_instant("serving.request.queued",
                          request=req.request_id, seq_len=n, max_new=m)
            self._fr.emit("submit", req.request_id, self._step_idx,
                          seq_len=n, max_new=m, priority=prio,
                          queue_depth=len(self._queue))
            if evict is not None:
                self._shed(evict, now)
            # peak AFTER a pending eviction: the one-element overshoot
            # between append and shed is submit-internal, not a depth
            # the scheduler ever saw
            self._peak_queue = max(self._peak_queue, len(self._queue))
            # counters LAST: a failure above (e.g. a raising span hook)
            # rolls the queue and pins back, but a Counter cannot be
            # decremented — incrementing only once nothing can raise
            # keeps submitted == finished + queued + active consistent
            self._m.requests_submitted.inc()
            self._m.queue_depth.set(len(self._queue))
        except BaseException:
            if self._queue and self._queue[-1] is req:
                self._queue.pop()
            for b in req.matched:
                self._pool.unpin(b)
            req.matched = []
            for k in req.host_pins:
                self._host_tier.unpin(k)
            req.host_pins = []
            self._update_block_gauges()
            self._m.queue_depth.set(len(self._queue))
            raise
        if stream:
            return TokenStream(self, req)
        return req

    def migrate_in(self, prompt_ids, *, seq_len=None, max_new_tokens=32,
                   arrival_time=None, spec_decode=None,
                   sampling: Optional[SamplingParams] = None,
                   priority: int = 0, deadline_s: Optional[float] = None,
                   max_queue_delay_s: Optional[float] = None,
                   adapter: Optional[str] = None,
                   tenant: Optional[str] = None,
                   samp_base: Optional[np.ndarray] = None,
                   tokens=(), first_token_time: Optional[float] = None,
                   parcel: Optional[dict] = None) -> Request:
        """Adopt a request recovered from a FAILED replica — the
        migration entry point the router's failover uses.  Two paths:

        - ``parcel=None``: deterministic **recompute-from-prompt** —
          the request re-enters this engine's queue cold and re-runs
          prefill + decode from position 0.  Token-exactness is the
          determinism stack's job: greedy rows are deterministic by
          construction, sampled rows replay bit-identically because
          ``samp_base`` carries the VICTIM's PRNG base key (the
          position-keyed PRNG of PR 6 makes the restart free — a
          seedless sampled request's stream is pinned by its original
          base key, not by this engine's seed or the new request id).
        - ``parcel={key, n_blocks, tok, lens, phase, pf_pos}``:
          **exact-bytes KV migration** — the victim's swap parcel was
          already transferred into THIS engine's host tier
          (``HostTier.transfer``, reason ``"preempt"``) and the
          request parks on the swap list exactly as if this engine
          had preempted it: the normal ``_try_resume`` path allocates
          fresh blocks and re-scatters the saved bytes through the
          one donation-matched swap-in program, so the resumed stream
          is bit-identical to never having failed.  ``tokens`` is the
          host-truth output emitted before the failure (decode phase;
          prefill-phase parcels carry none), ``tok``/``lens`` the
          victim slot's carries at its last consistent point.

        ``max_queue_delay_s`` should be passed only for requests that
        were still QUEUED on the victim (the PR-7 rule: once admitted,
        a request always runs to completion — a migrated or
        recomputed request was already admitted once, so its
        queue-delay SLO does not restart).  A full bounded queue
        refuses the recompute path with ``AdmissionError`` (no local
        victim is displaced for a foreign re-admission; the caller
        spills to another replica); parcel re-admissions join the
        swap list, which is never bounded (exactly like preemption).
        """
        if parcel is not None and self._slot_state:
            raise SlotStateError(self._model, "an exact-bytes migration "
                                 "parcel (migrate_in(parcel=))")
        ids = np.asarray(getattr(prompt_ids, "_value", prompt_ids))
        ids = np.asarray(ids).reshape(-1).astype(np.int32)
        if ids.size < 1 or ids.size > self.prompt_len:
            raise ValueError(
                f"prompt must be 1..{self.prompt_len} tokens, got "
                f"{ids.size}")
        n = int(seq_len) if seq_len is not None else int(ids.size)
        if n < 1 or n > ids.size:
            raise ValueError(
                f"seq_len must be in [1, {ids.size}], got {n}")
        m = int(max_new_tokens)
        if m < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {m}")
        if n + m - 1 > self.max_cache_len:
            raise ValueError(
                f"prompt ({n}) + max_new_tokens ({m}) - 1 = {n + m - 1} "
                f"tokens exceeds max_cache_len ({self.max_cache_len}) "
                f"— migration requires replica-homogeneous geometry")
        if self._blocks_needed(n, m) > self.num_blocks:
            raise ValueError(
                f"request needs {self._blocks_needed(n, m)} blocks "
                f"but the pool only has num_blocks={self.num_blocks}")
        if sampling is not None:
            if not isinstance(sampling, SamplingParams):
                raise ValueError(
                    f"sampling must be a SamplingParams, got "
                    f"{type(sampling).__name__}")
            sampling.validate()
        sp = sampling if sampling is not None else self._default_sampling
        spec_k = None if spec_decode is None else int(spec_decode)
        if spec_k is not None:
            if spec_k < 1:
                raise ValueError(
                    f"spec_decode must be >= 1 draft tokens, got "
                    f"{spec_decode}")
            if sp is not None and sp.mask_processor is not None:
                raise ValueError(
                    "spec_decode cannot compose with a token-mask "
                    "processor: a draft position's mask depends on "
                    "host-side state the drafter bypasses — recover "
                    "the request without spec_decode")
        if adapter is not None:
            adapter = str(adapter)
            if self._adapters is None or \
                    self._adapters.state(adapter) is None:
                raise ValueError(
                    f"adapter {adapter!r} is not registered on this "
                    f"engine — migration requires replica-homogeneous "
                    f"adapter registration")
        if parcel is None and self.max_queue is not None and \
                len(self._queue) >= self.max_queue:
            raise AdmissionError(
                f"queue full ({len(self._queue)} >= max_queue="
                f"{self.max_queue}) — this engine refuses the "
                f"recovered request (spill to another replica)",
                queue_depth=len(self._queue), max_queue=self.max_queue)
        now = self._clock()
        arrival = now if arrival_time is None else float(arrival_time)
        req = Request(self._next_id, np.full(
            (self.prompt_len,), self.cfg.pad_token_id, np.int32),
            n, m, arrival, pad_token_id=self.cfg.pad_token_id)
        req.prompt[:ids.size] = ids
        req.submit_time = now
        req.spec_k = spec_k
        req.adapter = adapter
        req.tenant = "default" if tenant is None else str(tenant)
        self._tenant_served.setdefault(req.tenant, 0)
        req.sampling = sp
        req.priority = int(priority)
        req.deadline = (None if deadline_s is None
                        else arrival + float(deadline_s))
        req.max_queue_delay_s = (None if max_queue_delay_s is None
                                 else float(max_queue_delay_s))
        if sp is not None and not sp.is_greedy:
            # the victim's base key pins the stream (restart-exact);
            # without one this engine derives its own, exactly like a
            # fresh submit
            req.samp_base = (np.asarray(samp_base, np.uint32)
                             if samp_base is not None
                             else base_key(sp.seed)
                             if sp.seed is not None
                             else np.asarray(jax.random.fold_in(
                                 jax.random.PRNGKey(self._seed),
                                 req.request_id), np.uint32))
        req.chunk_ids = np.full((self.prompt_len + self.chunk_len,),
                                self.cfg.pad_token_id, np.int32)
        req.chunk_ids[:self.prompt_len] = req.prompt
        if spec_k is not None:
            if self._drafter is None:
                self._drafter = NGramDrafter()
            self._spec_k_max = max(self._spec_k_max, spec_k)
        if parcel is not None:
            ent = self._host_tier.entry(int(parcel["key"]))
            if ent is None or ent.reason != "preempt":
                raise ValueError(
                    f"parcel key {parcel['key']!r} is not a preempt "
                    f"entry in this engine's host tier — transfer the "
                    f"victim's parcel first (HostTier.transfer)")
            if ent.n_blocks != int(parcel["n_blocks"]):
                raise ValueError(
                    f"parcel holds {ent.n_blocks} blocks but the swap "
                    f"record says {parcel['n_blocks']}")
            phase = str(parcel["phase"])
            if phase not in ("prefill", "decode"):
                raise ValueError(
                    f"parcel phase must be 'prefill' or 'decode', got "
                    f"{phase!r}")
            req.tokens = [int(x) for x in tokens]
            if phase == "decode":
                req.remaining = m - len(req.tokens)
                if req.remaining <= 0:
                    raise ValueError(
                        f"parcel carries {len(req.tokens)} emitted "
                        f"tokens of a {m}-token budget — nothing left "
                        f"to decode (the victim should have finished "
                        f"it)")
            req.pf_pos = int(parcel.get("pf_pos", 0))
            req.first_token_time = first_token_time
            req.swap = _SwapRecord(
                host_key=int(parcel["key"]),
                n_blocks=int(parcel["n_blocks"]),
                tok=int(parcel["tok"]), lens=int(parcel["lens"]),
                state=phase)
            req.state = "swapped"
            self._next_id += 1
            self._swapped.append(req)
            # the parcel entered this tier behind the engine's back
            # (HostTier.transfer from the router) — settle the gauge
            # now, not at the next unrelated swap event
            self._update_host_gauge()
            self._fr.emit("submit", req.request_id, self._step_idx,
                          seq_len=n, max_new=m, priority=req.priority,
                          migrated_blocks=int(parcel["n_blocks"]))
        else:
            # the recompute path re-enters the queue cold; submit's
            # unpin-on-error discipline applies to the prefix probe
            try:
                if self._radix is not None:
                    self._probe_radix(req)
                    if req.matched:
                        self._update_block_gauges()
                self._next_id += 1
                self._queue.append(req)
                self._fr.emit("submit", req.request_id, self._step_idx,
                              seq_len=n, max_new=m,
                              priority=req.priority,
                              queue_depth=len(self._queue),
                              recovered=1)
            except BaseException:
                if self._queue and self._queue[-1] is req:
                    self._queue.pop()
                for b in req.matched:
                    self._pool.unpin(b)
                req.matched = []
                for k in req.host_pins:
                    self._host_tier.unpin(k)
                req.host_pins = []
                self._update_block_gauges()
                raise
            self._m.queue_depth.set(len(self._queue))
            self._peak_queue = max(self._peak_queue, len(self._queue))
        self._m.requests_submitted.inc()
        return req

    def crash_reset(self) -> dict:
        """Model a replica RESTART after a fatal fault (kill, poisoned
        dispatch, permanent stall): every in-flight dispatch is
        dropped un-harvested (the device work is lost or untrusted),
        every live request is STRIPPED — returned to the caller by
        phase, with no terminal bookkeeping, because the failover
        layer above owns their recovery now — and the whole memory
        system (block pool, tables, radix tree, host tier) comes back
        empty, exactly like a freshly constructed engine over the same
        model.  Arena CONTENTS deliberately survive as garbage: every
        new occupant writes its KV before reading it and the trash-row
        discipline is content-independent, so no wipe dispatch is
        needed (or possible — the device may be the thing that died).

        The caller must read any host-tier parcels it intends to
        migrate BEFORE calling this (``HostTier.transfer``): the reset
        replaces the tier, dropping preempt parcels of stripped
        requests and every demoted cache span.  Adapter pins release
        back to the (engine-external, surviving) ``AdapterStore``;
        compiled program caches and the request-id counter survive —
        a restart recompiles nothing here because the model is
        unchanged, and ids stay monotonic.  Returns ``{"queued": [..],
        "active": [..], "swapped": [..]}`` in scheduler order."""
        stripped = {
            "queued": list(self._queue),
            "active": [r for r in self._slots if r is not None],
            # handoff-ready requests are swapped-by-phase: their
            # parcel is host-tier-staged exactly like a preemption's,
            # so the failover layer migrates them the same way
            "swapped": list(self._swapped) + list(self._handoff_ready),
        }
        for r in stripped["active"]:
            if r.adapter_slot is not None:
                self._adapters.release(r.adapter)
                r.adapter_slot = None
        self._queue.clear()
        self._prefilling.clear()
        self._first_owed = []
        self._pacing = None
        self._swapped = []
        self._handoff_ready = []
        self._slots = [None] * self.num_slots
        self._pend_q.clear()
        self._lazy_parcels = []
        self._flush_finishes = []
        self._spec_fallback = set()
        # fresh memory system, re-wired exactly like __init__
        self._pool = BlockPool(self.num_blocks, self.block_len)
        self._host_tier = HostTier(
            cache_capacity_blocks=self._host_cache_cap)
        if self.prefix_cache_mode == "radix":
            self._radix = RadixPrefixCache(self.block_len, self._pool,
                                           self._host_tier)
            self._pool.reclaim_cb = self._demote_blocks
            self._host_tier.evict_cb = self._radix.drop_host
            self._pool.audit_hooks.append(
                lambda: self._radix.audit(self._pool))
        self._pool.audit_hooks.append(self._audit_host_tier)
        self._tables = np.full((self.num_slots, self.max_blocks),
                               self._pool.trash, np.int32)
        self._tok = np.zeros((self.num_slots,), np.int32)
        self._lens = np.zeros((self.num_slots,), np.int32)
        self._done = np.ones((self.num_slots,), bool)
        self._m.queue_depth.set(0)
        self._m.slot_occupancy.set(0)
        self._m.async_depth.set(0)
        self._update_block_gauges()
        self._update_host_gauge()
        return stripped

    def cancel(self, request_id: int) -> bool:
        """Drop a request from ANY live phase.  Queued: removed from
        the queue, submit-time prefix pins released.  Swapped: the
        host-RAM copy is dropped (its HBM blocks were already freed at
        preemption).  In-flight (prefill or decode): the slot freezes
        through the existing trash-block discipline — ``done=True``
        plus an all-trash table row means any write the frozen row
        still issues lands in the trash block, never in a block a new
        occupant owns — and its blocks release immediately instead of
        at retirement.  The ``serving.requests_cancelled`` counter's
        ``phase`` label records which phase paid.  Every cancelled
        request is uniformly terminal — ``finish_time`` set, output
        padded to ``max_new_tokens`` — like the shed/timeout
        terminals.  Returns False for unknown or already-terminal
        requests."""
        now = self._clock()
        for req in self._queue:
            if req.request_id == request_id:
                self._drop_queued(req, now, "cancelled")
                self._m.requests_cancelled.inc(phase="queued")
                _span_instant("serving.request.cancel",
                              request=req.request_id, phase="queued")
                self._fr.emit("cancel", req.request_id, self._step_idx,
                              phase="queued")
                return True
        for req in self._swapped:
            if req.request_id == request_id:
                self._swapped.remove(req)
                self._host_tier.drop(req.swap.host_key)
                self._update_host_gauge()
                req.swap = None
                self._terminate(req, now, "cancelled")
                self._m.requests_cancelled.inc(phase="swapped")
                _span_instant("serving.request.cancel",
                              request=req.request_id, phase="swapped")
                self._fr.emit("cancel", req.request_id, self._step_idx,
                              phase="swapped")
                return True
        for i, req in enumerate(self._slots):
            if req is not None and req.request_id == request_id:
                # only an IN-FLIGHT cancel needs host truth (the
                # terminal output pads from the tokens that already
                # exist, and a pending harvest must not outlive its
                # riding set) — queued/swapped/unknown targets leave
                # the pipeline deferred
                self._flush_async("cancel")
                if req.state in TERMINAL_STATES:
                    # the flush itself retired the request (its finish
                    # bit was already set on device — the depth >= 2
                    # finish-bitmap protocol): it FINISHED, it was not
                    # cancelled, and the documented already-terminal
                    # contract applies (the finish reaches the next
                    # step()'s return via _flush_finishes)
                    return False
                phase = req.state
                if req in self._prefilling:
                    self._prefilling.remove(req)
                # a first token still owed is dropped with the request
                self._first_owed = [o for o in self._first_owed
                                    if o[0] is not req]
                self._release_blocks(req)   # also trashes the table row
                self._slots[i] = None
                self._done[i] = True
                req.slot = None
                self._terminate(req, now, "cancelled")
                self._m.requests_cancelled.inc(phase=phase)
                self._m.slot_occupancy.set(
                    sum(r is not None for r in self._slots))
                _span_instant("serving.request.cancel",
                              request=req.request_id, phase=phase)
                self._fr.emit("cancel", req.request_id, self._step_idx,
                              phase=phase)
                return True
        return False

    # -- scheduler --
    def _finish(self, req: Request, t: float, out: List[Request],
                lag: int = 0):
        req.finish_time = t
        req.state = "finished"
        req.slot = None
        self._m.requests_finished.inc()
        if req.latency is not None:
            self._m.latency.observe(req.latency)
        # per-output-token latency (TPOT), one observation per request
        # with >= 2 tokens: the decode-rate SLO metric TTFT cannot see
        # (len(req.tokens) is exact here: a rider that froze inside its
        # final block took only the tokens up to its finish)
        n_out = len(req.tokens)
        req.n_emitted = n_out
        if req.first_token_time is not None and n_out >= 2:
            self._m.tpot.observe(
                (t - req.first_token_time) / (n_out - 1))
        self._slo_account(req)
        _span_instant("serving.request.finish", request=req.request_id,
                      tokens=len(req.tokens))
        # the finish-bitmap poll story: a deferred harvest observed
        # this finish ``lag`` steps after the device produced it — the
        # event is stamped with the DISPATCH step and the lag attr is
        # a deterministic step delta ("finished on device at step N,
        # host observed N+lag"); parity comparisons strip it
        fattrs = {"tokens": n_out}
        if lag:
            fattrs["lag"] = lag
        self._fr.emit("finish", req.request_id,
                      self._step_idx - lag, **fattrs)
        # pad the stream out to max_new_tokens (the static generate()
        # convention: pad after EOS) so output shapes are uniform
        req.tokens.extend(
            [self.cfg.pad_token_id] *
            (req.max_new_tokens - len(req.tokens)))
        self._finished.append(req)
        out.append(req)

    # -- SLO scheduling keys --
    @staticmethod
    def _sched_key(r: Request):
        """Admission order (smaller admits first): highest priority,
        then earliest deadline (EDF; no deadline sorts last within the
        priority).  Sorting is STABLE over submission order, so within
        one (priority, deadline) class the queue stays FIFO — a trace
        that never passes the SLO kwargs schedules exactly as before."""
        return (-r.priority, r.deadline if r.deadline is not None
                else _INF)

    @staticmethod
    def _shed_key(r: Request):
        """"Worseness" (smaller = worse = shed/preempt first): lowest
        priority, then latest deadline (no deadline = latest)."""
        return (r.priority, _neg_deadline(r.deadline))

    @staticmethod
    def _remaining_work(r: Request) -> int:
        """Victim tie-breaker: tokens of compute still owed (prompt
        positions left to prefill plus the decode budget) — preempting
        the LONGEST remaining tail frees its blocks for the longest
        time per swap."""
        if r.state == "prefill":
            return (r.seq_len - r.pf_pos) + r.max_new_tokens
        return r.remaining

    def _terminate(self, req: Request, now: float, state: str):
        """Mark a request terminal without it running to completion —
        the ONE terminal shape shared by shed, timeout and cancel:
        terminal state, ``finish_time`` set, output padded to exactly
        ``max_new_tokens`` (the Request docstring's uniform-output
        contract)."""
        req.state = state
        req.finish_time = now
        req.tokens.extend([self.cfg.pad_token_id]
                          * (req.max_new_tokens - len(req.tokens)))
        # shed/timeout are SLO outcomes (missed); cancel is skipped
        # inside _slo_account by state
        self._slo_account(req)

    def _drop_queued(self, req: Request, now: float, state: str):
        """The ONE teardown for a queued request leaving without
        running (shed by the bounded queue, timed out past its
        queue-delay SLO, or cancelled from the queue): remove from the
        queue, release submit-time prefix pins (HBM blocks and host-
        tier parcels both), mark terminal, refresh the queue/block
        gauges.  The caller adds its own counter and span."""
        self._queue.remove(req)
        for b in req.matched:
            self._pool.unpin(b)
        req.matched = []
        for k in req.host_pins:
            self._host_tier.unpin(k)
        req.host_pins = []
        self._terminate(req, now, state)
        self._m.queue_depth.set(len(self._queue))
        self._update_block_gauges()

    def _shed(self, req: Request, now: float):
        """Displace a queued request from a full bounded queue:
        terminal, like timeout, but charged to queue pressure."""
        self._drop_queued(req, now, "shed")
        self._m.shed.inc(reason="evicted")
        _span_instant("serving.request.shed", request=req.request_id)
        self._fr.emit("shed", req.request_id, self._step_idx)

    def _sweep_timeouts(self, now: float, out: List[Request]):
        """Finish queued requests whose wait exceeded their
        ``max_queue_delay_s`` with state ``"timeout"`` — the SLO says
        a late answer is worthless, so the scheduler sheds it instead
        of serving it late.  Only QUEUED requests can time out:
        admitted (and swapped — they already ran) requests always
        complete."""
        for r in [r for r in self._queue
                  if r.max_queue_delay_s is not None
                  and now - r.arrival_time > r.max_queue_delay_s]:
            self._drop_queued(r, now, "timeout")
            self._m.timeouts.inc()
            _span_instant("serving.request.timeout",
                          request=r.request_id,
                          waited_ms=round(
                              (now - r.arrival_time) * 1e3, 3))
            # no waited_ms attr here: flight-recorder attrs must stay
            # wall-free so replayed traces compare event-identical
            self._fr.emit("timeout", r.request_id, self._step_idx)
            out.append(r)

    # -- preemption + host-RAM swap --
    def _read_slot_state(self, slot: int):
        """Slot ``slot``'s rows of the per-slot state arenas as host
        arrays (None for a model without such state): what a swap record
        carries beside the blocks, since this engine preempts by
        swapping bytes and never by recomputing."""
        if not self._slot_state:
            return None
        if self._state_read_fn is None:
            self._state_read_fn = jax.jit(
                lambda i, *arenas: tuple(a[i] for a in arenas))
        rows = self._state_read_fn(jnp.asarray(slot, jnp.int32),
                                   *self._slot_state)
        return [np.asarray(r) for r in rows]        # sync: preempt

    def _write_slot_state(self, slot: int, rows):
        """Put a swap record's state rows into slot ``slot``'s rows of
        the state arenas (donated, as the swap-in scatter's are)."""
        if not self._slot_state:
            return
        if rows is None:
            raise SlotStateError(self._model, "a swap record without "
                                 "state rows")
        if self._state_write_fn is None:
            n = len(self._slot_state)
            self._state_write_fn = jax.jit(
                lambda i, *a: tuple(arena.at[i].set(row) for row, arena
                                    in zip(a[:n], a[n:])),
                donate_argnums=tuple(range(1 + n, 1 + 2 * n)))
        self._slot_state = list(self._state_write_fn(
            jnp.asarray(slot, jnp.int32),
            *[jnp.asarray(r) for r in rows], *self._slot_state))

    def _swap_out(self):
        if self._swap_out_fn is None:
            self._swap_out_fn = jax.jit(
                build_swap_out_gather(shard=self._shard))
        return self._swap_out_fn

    def _swap_in(self):
        if self._swap_in_fn is None:
            n = len(self._arenas)
            self._swap_in_fn = jax.jit(
                build_swap_in_scatter(n, shard=self._shard),
                donate_argnums=tuple(range(1 + n, 1 + 2 * n)))
        return self._swap_in_fn

    # graftlint: plan-phase
    def _preempt(self, req: Request, reason: str = "pressure",
                 out=None):
        """Swap an in-flight request out to the host-RAM tier: gather
        its table row's EXACT at-rest bytes out of every arena (float
        K/V, or int8 codes + scale planes), save the slot's
        ``tok``/``lens`` carries, release its HBM blocks and park it
        on the swap list.  The request's host truth (``tokens``,
        ``pf_pos``, sampling state machine, position-keyed PRNG) needs
        no saving — it never lived on the device.  Returns False when
        the harvest flush itself RETIRED the chosen victim (the
        finish-bitmap protocol at depth >= 2: its EOS was already on
        device, so its blocks are free and there is nothing left to
        swap), True after a real swap-out."""
        # the swap record saves the slot's HOST tok/lens carries — a
        # deferred harvest must land first or a pending-active victim
        # would resume one block behind its own KV bytes.  Flush
        # BEFORE validating: at depth >= 2 the flush can discover the
        # victim finished on device, and the stale pre-flush truth
        # must not be acted on.
        self._flush_async("preempt", out)
        if any(r is req for r, _ in self._first_owed):
            # its prompt is computed and its first token, owed since a
            # chunk enqueued ahead of a harvest, still on the device:
            # land it (with the others owed) so that it swaps out as the
            # decode row it is
            self._land_first_tokens(
                out if out is not None else self._flush_finishes)
        slot = req.slot
        if req.state in TERMINAL_STATES:
            return False            # retired by the flush — done
        if slot is None or req.state not in ("prefill", "decode"):
            raise RuntimeError(
                f"request {req.request_id} is not in flight "
                f"(state={req.state}, slot={slot}) — only admitted "
                f"prefill/decode requests can be preempted")
        ids = self._tables[slot].copy()     # BEFORE release trashes it
        n = len(req.blocks)
        with _span("serving.swap_out", request=req.request_id,
                   blocks=n):
            # the gather reads the full table row (ONE compiled shape
            # for the engine's lifetime; entries past the allocation
            # hit the trash row) but only the request's n real blocks
            # are KEPT host-side — the swap tier's actual footprint is
            # exactly what swap.host_blocks / swap_out_bytes report
            rows = [np.ascontiguousarray(r[:n])
                    for r in self._gather_rows(ids)]
        key = self._host_tier.put(rows, n, "preempt")
        req.swap = _SwapRecord(host_key=key, n_blocks=n,
                               tok=int(self._tok[slot]),
                               lens=int(self._lens[slot]),
                               state=req.state,
                               slot_state=self._read_slot_state(slot))
        if req in self._prefilling:
            self._prefilling.remove(req)
        self._release_blocks(req)
        self._slots[slot] = None
        self._done[slot] = True
        req.slot = None
        req.state = "swapped"
        req.preempt_count += 1
        self._swapped.append(req)
        nbytes = n * self.block_len * self._kv_row_bytes
        self._m.preempts.inc()
        self._m.swap_out_blocks.inc(n, reason="preempt")
        self._m.swap_out_bytes.inc(nbytes, reason="preempt")
        self._update_host_gauge()
        self._m.slot_occupancy.set(
            sum(r is not None for r in self._slots))
        _span_instant("serving.request.preempt", request=req.request_id,
                      blocks=n, reason=reason)
        self._fr.emit("preempt", req.request_id, self._step_idx,
                      blocks=n, reason=reason, phase=req.swap.state)
        self._fr.emit("swap_out", req.request_id, self._step_idx,
                      blocks=n, reason="preempt")
        return True

    def _preempt_for(self, cand: Request, needed: int,
                     out=None) -> bool:
        """Free blocks for ``cand`` by swapping out strictly-worse
        victims (victim policy: lowest priority first, then latest
        deadline, then most remaining work) until ``needed`` blocks
        are allocatable.  Eligibility is STRICT — a victim must be of
        lower priority, or same priority with a later deadline — so a
        resumed victim can never preempt its preemptor back and two
        equal requests never thrash.  Returns True when the target was
        reached (victims may have been swapped either way; they resume
        when pressure clears)."""
        cand_key = self._shed_key(cand)
        while self._pool.available() < needed:
            # eligibility and victim choice are BOTH the one
            # "worseness" ordering (_shed_key: lowest priority, then
            # latest deadline) — preemption and bounded-queue shedding
            # can never drift apart on who is expendable; remaining
            # work breaks the final tie
            eligible = [
                r for r in self._slots
                if r is not None and r.state in ("prefill", "decode")
                and self._shed_key(r) < cand_key]
            if not eligible:
                return False
            victim = min(eligible, key=lambda v: (
                self._shed_key(v) + (-self._remaining_work(v),)))
            self._preempt(victim, out=out)
        return True

    # graftlint: plan-phase
    def _try_resume(self, req: Request, slot: int,
                    out=None) -> bool:
        """Re-admit a swapped request: allocate fresh blocks (leaning
        on the valve and preemption under pressure), re-scatter the
        saved bytes through the donation-matched swap-in program, and
        restore the slot carries.  The fresh block list preserves
        logical block ORDER, so the rebuilt table row maps the same
        dense view the request decoded against before — resumed greedy
        output is bit-identical to never-preempted output."""
        rec = req.swap
        # the adapter pin was released at preemption (a swapped
        # request needs no arena residency); re-acquire before any
        # block work — failure leaves the request a valid swap-list
        # member, exactly like block exhaustion
        acquired = False
        if req.adapter is not None:
            if self._adapters.acquire(req.adapter) is None:
                return False
            acquired = True
        fresh = self._alloc(rec.n_blocks)
        if fresh is None and \
                not any(r is not None for r in self._slots):
            self._release_queue_pins()
            fresh = self._alloc(rec.n_blocks)
        if fresh is None and self._preempt_for(req, rec.n_blocks, out):
            fresh = self._alloc(rec.n_blocks)
        if fresh is None:
            if acquired:
                self._adapters.release(req.adapter)
            return False
        # the resume REWRITES the slot's host tok/lens carries, so the
        # next decode dispatch must come from host mirrors — harvest
        # the pending block first.  Flushed only HERE, after blocks
        # are secured: a resume attempt that cannot allocate keeps the
        # pipeline deferred (it changed no carries)
        self._flush_async("resume", out)
        row = np.full((self.max_blocks,), self._pool.trash, np.int32)
        row[:rec.n_blocks] = fresh
        # the dispatch runs BEFORE any scheduler-state commit, and a
        # failure (a raising span hook, an argument-prep error) unpins
        # the fresh blocks — the same rollback discipline as submit():
        # the request must stay a valid swap-list member or become a
        # fully-mapped slot occupant, never something in between
        try:
            with _span("serving.swap_in", request=req.request_id,
                       blocks=rec.n_blocks):
                # saved stacks are allocation-width; _scatter_rows
                # re-pads to the fixed table width (pad rows scatter
                # into the trash row through the trash-padded ``row``)
                self._scatter_rows(
                    row, self._host_tier.entry(rec.host_key).rows)
                self._write_slot_state(slot, rec.slot_state)
        except BaseException:
            for b in fresh:
                self._pool.unpin(b)
            if acquired:
                self._adapters.release(req.adapter)
            self._update_block_gauges()
            raise
        self._swapped.remove(req)
        if acquired:
            req.adapter_slot = self._adapters.slot_of(req.adapter)
        req.blocks = list(fresh)
        req.matched = []
        self._tables[slot] = row
        req.slot = slot
        self._slots[slot] = req
        self._tok[slot] = rec.tok
        self._lens[slot] = rec.lens
        req.state = rec.state
        if rec.state == "prefill":
            self._done[slot] = True       # not decoding yet
            self._prefilling.append(req)
        else:
            # spec-mode rows stay frozen out of the plain decode block
            # (their progress happens in the verify dispatch)
            self._done[slot] = req.spec_k is not None
        req.swap = None
        self._host_tier.drop(rec.host_key)
        self._m.preempt_resumes.inc()
        self._m.swap_in_blocks.inc(rec.n_blocks, reason="preempt")
        self._m.swap_in_bytes.inc(
            rec.n_blocks * self.block_len * self._kv_row_bytes,
            reason="preempt")
        self._update_host_gauge()
        self._update_block_gauges()
        _span_instant("serving.request.resume", request=req.request_id,
                      slot=slot, blocks=rec.n_blocks)
        self._fr.emit("swap_in", req.request_id, self._step_idx,
                      blocks=rec.n_blocks, reason="preempt", slot=slot)
        return True

    def _release_queue_pins(self):
        """Head-of-line valve body: nothing is running, so the only
        refcounts are queued requests' submit-time prefix pins —
        release them all (the cached blocks stay mapped, just
        reclaimable again; host parcels likewise become evictable)."""
        for r in self._queue:
            for b in r.matched:
                self._pool.unpin(b)
            r.matched = []
            for k in r.host_pins:
                self._host_tier.unpin(k)
            r.host_pins = []
            r.rspan = []
            r.rmatch_tokens = 0   # else a valve (cold) admission would
            #                       count a spurious partial hit

    # -- radix prefix cache (tiered) --
    def _probe_radix(self, req: Request):
        """Probe the radix tree for ``req``'s prompt and pin the
        matched span: HBM blocks against pool reclaim, host parcels
        against tier eviction.  Sets ``req.matched`` (HBM blocks, in
        span order interleaved with host positions removed),
        ``req.host_pins`` (tier keys) and ``req.rspan``/
        ``req.rmatch_tokens``.  The span is capped at the block before
        the prompt's last token — the PR-3 rule: sampling the first
        output token needs that block's hidden state."""
        n = req.seq_len
        m_tok, span = self._radix.match(req.prompt[:n])
        span = span[:(n - 1) // self.block_len]
        self._radix.touch_span(span)
        for kind, ref in span:
            if kind == "hbm":
                self._pool.pin(ref)
                req.matched.append(ref)
            else:
                self._host_tier.pin(ref)
                req.host_pins.append(ref)
        req.rmatch_tokens = min(m_tok, n - 1)
        req.rspan = span

    def _reprobe_radix(self, req: Request):
        """Admission-time revalidation of the submit-time probe: the
        tree may have grown (a sharer prefilled while this request
        queued), demoted spans to host, or promoted them back.  Old
        pins release first so pin accounting stays exact (host-side
        and atomic with respect to the scheduler — nothing can reclaim
        between the unpin and the re-pin).  An armed swap-in fault
        degrades the span here, BEFORE allocation is sized: the host
        parcels drop (their bytes are the thing that "failed") and the
        span truncates to its directly-mapped HBM prefix, so the
        request recomputes the tail — a prefix miss, never a wedge or
        a token drift."""
        for b in req.matched:
            self._pool.unpin(b)
        req.matched = []
        for k in req.host_pins:
            self._host_tier.unpin(k)
        req.host_pins = []
        self._probe_radix(req)
        if any(kind == "host" for kind, _ in req.rspan) and \
                self._fault is not None and \
                self._fault.take_swapin_failure():
            keep = []
            for kind, ref in req.rspan:
                if kind != "hbm":
                    break
                keep.append((kind, ref))
            for kind, ref in req.rspan[len(keep):]:
                if kind == "hbm":
                    self._pool.unpin(ref)
                    req.matched.remove(ref)
                else:
                    self._host_tier.unpin(ref)
                    req.host_pins.remove(ref)
                    self._host_tier.drop(ref)
                    self._radix.drop_host(ref)
            req.rspan = keep
            self._update_host_gauge()
        self._update_block_gauges()

    # graftlint: plan-phase
    def _map_radix_span(self, req: Request, fresh: List[int]):
        """Resolve the matched span into arena blocks: HBM entries map
        directly, host entries are PROMOTED — their exact at-rest
        bytes re-scatter into the leading ``fresh`` blocks through the
        shared donation-matched swap-in program, and the tree relabels
        them HBM-resident (so the whole chain of sharers benefits).
        Returns ``(mapped, leftover_fresh, n_promoted)`` with
        ``mapped`` in span order; ``n_promoted`` counts the blocks
        ACTUALLY promoted from the host tier — the ground truth the
        admit-time ``prefix_hit`` event's tier label rides on.  A
        raise mid-promotion unpins every fresh block AND releases the
        request's probe pins (HBM blocks and tier parcels both,
        span metadata cleared), leaving the request a valid queue
        member with NOTHING held — the submit() rollback discipline,
        hardened: the next admission attempt re-probes from scratch
        anyway (``_reprobe_radix`` rebuilds the span), and a caller
        that never retries must not leave parcels pinned forever —
        a pinned cache entry can never be capacity-evicted, so a
        leaked pin slowly wedges the whole tier."""
        span = req.rspan
        host_keys = [ref for kind, ref in span if kind == "host"]
        n_promote = len(host_keys)
        if n_promote:
            dest = fresh[:n_promote]
            entries = [self._host_tier.entry(k) for k in host_keys]
            self._resolve_entries(entries)
            ids_row = np.full((self.max_blocks,), self._pool.trash,
                              np.int32)
            ids_row[:n_promote] = dest
            try:
                with _span("serving.cache_swap_in",
                           request=req.request_id, blocks=n_promote):
                    self._scatter_rows(ids_row, [
                        np.concatenate([e.rows[ai] for e in entries],
                                       axis=0)
                        for ai in range(len(self._arenas))])
            except BaseException:
                for b in fresh:
                    self._pool.unpin(b)
                # release the probe pins too — symmetric teardown, so
                # a caller that never retries leaks nothing (a pinned
                # parcel is un-evictable); the parcels themselves stay
                # reachable in the tree, just unprotected, and the
                # next admission attempt re-probes from scratch
                for b in req.matched:
                    self._pool.unpin(b)
                req.matched = []
                for k in req.host_pins:
                    self._host_tier.unpin(k)
                req.host_pins = []
                req.rspan = []
                req.rmatch_tokens = 0
                self._update_block_gauges()
                raise
            for k, b in zip(host_keys, dest):
                self._host_tier.unpin(k)       # the probe pin
                self._radix.promote(k, b)      # consumes the parcel
                req.host_pins.remove(k)
            nbytes = n_promote * self.block_len * self._kv_row_bytes
            self._m.swap_in_blocks.inc(n_promote, reason="cache")
            self._m.swap_in_bytes.inc(nbytes, reason="cache")
            self._m.prefix_host_hits.inc()
            self._m.prefix_host_swapin.inc(n_promote)
            self._fr.emit("swap_in", req.request_id, self._step_idx,
                          blocks=n_promote, reason="cache")
            self._update_host_gauge()
        it = iter(fresh[:n_promote])
        mapped = [ref if kind == "hbm" else next(it)
                  for kind, ref in span]
        return mapped, fresh[n_promote:], n_promote

    def _residency_rank(self, r: Request) -> int:
        """Fresh radix probe (no pinning) classifying a queued
        request's matched prefix: 0 = some of it is HBM-resident,
        1 = host-resident only, 2 = cold."""
        _m, span = self._radix.match(r.prompt[:r.seq_len])
        span = span[:(r.seq_len - 1) // self.block_len]
        if any(kind == "hbm" for kind, _ in span):
            return 0
        return 1 if span else 2

    # -- fair-share (deficit-weighted round-robin over tenants) --
    def _fair_norm(self, tenant: str) -> float:
        """A tenant's weight-normalized service: tokens charged at
        admission divided by its fair-share weight.  The WRR invariant
        is "the LEAST-normalized-served tenant in a scheduling class
        admits next"; integer token counts over deterministic weights
        make the ordering byte-deterministic."""
        return (self._tenant_served.get(tenant, 0)
                / self._tenant_weights.get(tenant, 1.0))

    def _update_deficits(self):
        """Refresh the per-tenant deficit gauges: the most-served
        tenant's normalized service minus each tenant's own (>= 0;
        largest deficit admits next within a class)."""
        if not self._tenant_served:
            return
        top = max(self._fair_norm(t) for t in self._tenant_served)
        for t in self._tenant_served:
            self._m.fairshare_deficit.set(
                round(top - self._fair_norm(t), 3), tenant=t)

    def _charge_tenant(self, req: Request):
        """Charge a leaving-the-queue request's reservation (prompt +
        decode budget) to its tenant's service ledger — the moment the
        WRR ordering advances."""
        cost = req.seq_len + req.max_new_tokens
        self._tenant_served[req.tenant] = \
            self._tenant_served.get(req.tenant, 0) + cost
        self._m.fairshare_served.inc(cost, tenant=req.tenant)
        self._update_deficits()

    # graftlint: plan-phase
    def _admit(self, now: float, out: List[Request],
               leaving: Optional[dict] = None) -> int:
        """Admit the best-class candidates into vacant slots; returns how
        many were admitted.  ``leaving`` ({slot: rider}, ``_admit_ahead``)
        counts the slots whose riders the block in flight ends by budget
        as vacant too, in slot order with the others, as the next step
        would find them; such an admission neither preempts nor resumes
        (a victim's carries, and a resumed row's, are a block behind
        until the harvest), and a head candidate that would stops it.  The
        candidate order is priority-then-EDF over the swap list plus
        the arrived queue (swapped requests sort ahead of queued ones
        within a class: they hold host memory and are closest to
        done); within a class the order is FIFO, so default traces
        schedule exactly as the pre-SLO engine.  Queue-delay timeouts
        are swept first — a request must not be admitted after its
        wait already broke its SLO.  When the pool cannot serve the
        head candidate, the head-of-line valve (nothing running) and
        then PREEMPTION of strictly-worse victims are tried before
        giving up until blocks retire.  Admission is head-of-line:
        a stuck best candidate is never skipped for a worse one that
        would fit (no priority inversion by backfill)."""
        self._sweep_timeouts(now, out)
        # candidate order: _sched_key (priority, then EDF) extended by
        # the FAIR-SHARE term and a residency rank — inside a class,
        # the least-normalized-served tenant admits first (deficit-
        # weighted round-robin; a constant on single-tenant traces, so
        # they schedule byte-identically to the pre-tenant engine),
        # then swapped requests (they hold host memory and are closest
        # to done), then queued requests whose matched prefix is HBM-
        # resident, then host-resident, then cold.  The rank is a
        # STRICT tie-break inside a (class, tenant-deficit) bucket and
        # the sort is stable over submission order, so a trace with no
        # shared prefixes (or an engine without the prefix cache, where
        # the rank is constant) keeps FIFO within its bucket.  Ranks are probed
        # once per candidate per _admit CALL (memoized — not once per
        # sort comparison or per freed slot): the tree only improves
        # mid-call (promotion/registration), and a call-stale rank
        # costs order quality, never correctness.  The fair term is
        # NOT memoized — each admission charges its tenant, and the
        # re-sort on the next loop iteration must see the new ledger
        # (that is the round-robin).
        ranks: dict = {}

        def _state_rank(r):
            if r.state == "swapped":
                return -1
            if self._radix is None:
                return 0
            rank = ranks.get(r.request_id)
            if rank is None:
                rank = self._residency_rank(r)
                ranks[r.request_id] = rank
            return rank

        def _cand_key(r):
            return (self._sched_key(r) + (self._fair_norm(r.tenant),)
                    + (_state_rank(r),))

        def _fifo_key(r):
            # the pre-fair ordering (priority/EDF/residency/FIFO) —
            # what the head would have been without the WRR term; a
            # divergence is a counted "reorder" (a starvation the
            # plain order would have inflicted)
            return self._sched_key(r) + (_state_rank(r),)

        leaving = leaving or {}
        admitted = 0
        while True:
            slot = next((i for i, r in enumerate(self._slots)
                         if r is None or r is leaving.get(i)), None)
            if slot is None:
                break
            arrived = [r for r in self._queue if r.arrival_time <= now]
            cands = sorted(self._swapped + arrived, key=_cand_key)
            if not cands:
                break
            req = cands[0]
            if leaving and req.state == "swapped":
                break
            # a "reorder" = the WRR term promoted a different request
            # over the plain priority/EDF/FIFO head (the starvation
            # the old order would have inflicted); only possible — and
            # only worth the O(n) head scan — with > 1 tenant.  min()
            # over the pre-sort submission order IS the stable-sorted
            # head (first minimal element wins ties), without a second
            # full sort on the admission path.
            reorder = (len(self._tenant_served) > 1 and
                       req is not min(self._swapped + arrived,
                                      key=_fifo_key))
            if req.state == "swapped":
                if not self._try_resume(req, slot, out):
                    break
                if reorder:
                    # a fairness-promoted RESUME is a reorder too —
                    # the counter covers every admission decision, not
                    # just queue departures
                    self._m.fairshare_reorders.inc()
                continue
            if self._radix is not None:
                # the tree may have grown while this request queued (a
                # sharer prefilled, a span was promoted) — re-probe and
                # re-pin before sizing the allocation
                self._reprobe_radix(req)
                n_hbm = len(req.matched)
            else:
                n_hbm = 0
            # adapter residency before block sizing: the gathered
            # dispatch needs the arena slot pinned for the request's
            # whole admitted life.  None = every slot is pinned by
            # running requests — head-of-line wait, exactly like KV-
            # block exhaustion (pins release as requests retire).
            acquired = False
            if req.adapter is not None:
                if self._adapters.acquire(req.adapter) is None:
                    break
                acquired = True
            total = self._blocks_needed(req.seq_len, req.max_new_tokens)
            fresh = self._alloc(total - n_hbm)
            if fresh is None and \
                    not any(r is not None for r in self._slots):
                # head-of-line valve: release every queued submit-time
                # pin (including this request's own) and retry at full
                # width; the submit() capacity guard makes this retry
                # infallible against real exhaustion (an injected
                # fault can still fail it).  The valve admission is
                # COLD — the released span (host parcels included) is
                # no longer protected, so nothing of it is mapped.
                self._release_queue_pins()
                n_hbm = 0
                fresh = self._alloc(total)
            if fresh is None and not leaving and \
                    self._preempt_for(req, total - n_hbm, out):
                fresh = self._alloc(total - n_hbm)
            if fresh is None:
                if acquired:
                    self._adapters.release(req.adapter)
                break                     # pool drains as requests retire
            matchable = ((req.seq_len - 1) // self.block_len
                         if self._radix is not None else 0)
            if self._radix is not None:
                # host-resident span entries swap their exact at-rest
                # bytes back into the leading fresh blocks (one batched
                # scatter); a raise leaves the request queued and the
                # fresh blocks unpinned (_map_radix_span's rollback) —
                # and the adapter pin rolls back with them
                try:
                    mapped, fresh, n_promoted = \
                        self._map_radix_span(req, fresh)
                except BaseException:
                    if acquired:
                        self._adapters.release(req.adapter)
                    raise
                req.blocks = mapped + fresh
                hit_tokens = len(mapped) * self.block_len
                partial = req.rmatch_tokens > hit_tokens
                if partial:
                    self._m.prefix_partial_hits.inc()
                # goodput: positions the tree matched token-level but
                # could not map (partial tail, dropped host parcels,
                # evict holes) will be recomputed by the prefill —
                # charge them wasted{recompute_cache} as they compute
                req.gp_recompute_from = hit_tokens
                req.gp_recompute_to = max(hit_tokens, req.rmatch_tokens)
                if mapped or partial:
                    # tier rides the ACTUAL promotion count out of
                    # _map_radix_span, never the pre-map span shape
                    self._fr.emit(
                        "prefix_hit", req.request_id, self._step_idx,
                        tier=("host" if n_promoted else
                              "hbm" if mapped else "partial"),
                        blocks=len(mapped), tokens=hit_tokens,
                        partial=int(partial))
                req.matched = []
                req.rspan = []
            else:
                mapped, hit_tokens = [], 0
                req.blocks = fresh
            self._m.prefix_hit_tokens.inc(hit_tokens)
            self._queue.remove(req)
            if acquired:
                req.adapter_slot = self._adapters.slot_of(req.adapter)
            # fair-share bookkeeping at the admission decision: the
            # deficit is this tenant's shortfall vs the most-served
            # tenant BEFORE this admission's charge moved the ledger
            # (a deterministic token count, so the admit event stays
            # replay-identical); tenant-less default traces skip the
            # extra attrs entirely and keep their event streams
            # byte-identical to the pre-tenant engine
            extra = {}
            if req.adapter is not None:
                extra["adapter"] = req.adapter
            if req.tenant != "default" or reorder:
                top = max(self._fair_norm(t)
                          for t in self._tenant_served)
                extra["tenant"] = req.tenant
                extra["deficit"] = round(
                    top - self._fair_norm(req.tenant), 3)
            if reorder:
                self._m.fairshare_reorders.inc()
            self._charge_tenant(req)
            self._m.prefix_hits.inc(len(mapped))
            self._m.prefix_misses.inc(matchable - len(mapped))
            row = np.full((self.max_blocks,), self._pool.trash, np.int32)
            row[:len(req.blocks)] = req.blocks
            self._tables[slot] = row
            req.slot = slot
            req.state = "prefill"
            req.pf_pos = len(mapped) * self.block_len
            self._slots[slot] = req
            self._done[slot] = True       # not decoding yet
            self._lens[slot] = 0
            self._prefilling.append(req)
            self._m.queue_depth.set(len(self._queue))
            self._update_block_gauges()
            _span_instant("serving.request.admit", request=req.request_id,
                          slot=slot, matched_blocks=len(mapped))
            self._fr.emit("admit", req.request_id, self._step_idx,
                          slot=slot, matched_blocks=len(mapped),
                          **extra)
            admitted += 1
        self._m.slot_occupancy.set(
            sum(r is not None for r in self._slots))
        return admitted

    def _build_samp(self, reqs, pos_lag: int = 0):
        """The ``samp`` plane pytree of one dispatch: ``reqs`` is the
        dispatch's batch view (one Optional[Request] per row; None =
        vacant/frozen/not-riding).  Flags come from the ACTIVE rows
        only, so the planes and the compiled program variant stay in
        lockstep; rows without a request get NEUTRAL values (greedy
        mask on, temp 1, zero bias) — their draws are computed-and-
        discarded, never consumed.  PRNG positions are re-derived from
        host truth (``len(req.tokens)``) on every dispatch, which is
        the whole rewind story: a speculative rollback shrinks
        ``tokens``, so the rolled-back positions are simply keyed and
        drawn again next forward.  ``pos_lag`` corrects that host
        truth on a DEFERRED dispatch: the pending block's tokens are
        not yet harvested, so every riding row's true PRNG position is
        ``len(tokens) + pending.n`` — the correction that keeps
        sampled streams bit-identical to the lockstep engine."""
        flags = flags_of([r.sampling for r in reqs if r is not None])
        sampled, _filtered, penalty, bias = flags
        if pos_lag and (penalty or bias):
            raise RuntimeError(
                "deferred dispatch with a host-built logit plane "
                "(penalty/bias) — the defer predicate must have "
                "forced a sync for these rows")
        n = len(reqs)
        samp = {}
        if sampled:
            base = np.zeros((n, 2), np.uint32)
            pos = np.zeros((n,), np.int32)
            temp = np.ones((n,), np.float32)
            top_k = np.zeros((n,), np.int32)
            top_p = np.ones((n,), np.float32)
            greedy = np.ones((n,), bool)
            for i, r in enumerate(reqs):
                if r is None:
                    continue
                temp[i], top_k[i], top_p[i], greedy[i] = \
                    row_planes(r.sampling)
                pos[i] = len(r.tokens) + pos_lag
                if r.samp_base is not None:
                    base[i] = r.samp_base
            samp.update(
                base=jnp.asarray(base), pos=jnp.asarray(pos),
                temp=jnp.asarray(temp), top_k=jnp.asarray(top_k),
                top_p=jnp.asarray(top_p), greedy=jnp.asarray(greedy))
        if penalty:
            rep = np.ones((n,), np.float32)
            presence = np.zeros((n, self._vocab), bool)
            for i, r in enumerate(reqs):
                if r is None or r.sampling is None \
                        or not r.sampling.needs_penalty:
                    continue
                rep[i] = r.sampling.repetition_penalty
                presence[i, r.prompt[:r.seq_len]] = True
                if r.tokens:
                    presence[i, np.asarray(r.tokens, np.int32)] = True
            samp["rep"] = jnp.asarray(rep)
            samp["presence"] = jnp.asarray(presence)
        if bias:
            bias_p = np.zeros((n, self._vocab), np.float32)
            for i, r in enumerate(reqs):
                if r is None or r.sampling is None \
                        or r.sampling.mask_processor is None:
                    continue
                allowed = np.asarray(
                    r.sampling.mask_processor.allowed(), bool)
                bias_p[i, ~allowed] = MASK_BIAS
            samp["bias"] = jnp.asarray(bias_p)
        return flags, samp

    def _build_lora(self, reqs):
        """The ``lora`` plane pytree of one dispatch (the gathered-
        einsum arguments of ``models/lora.py``): ``reqs`` is the
        dispatch's batch view, exactly like ``_build_samp``'s.
        Returns ``(lora_on, planes)`` — ``(False, None)`` when no
        riding row selected an adapter, so adapter-free dispatches
        keep compiling (and running) today's exact programs.  Rows
        without an adapter gather the arenas' all-zero NULL row: their
        delta is an exact ``+ 0.0``, which is what keeps base rows in
        a mixed batch token-identical to the non-LoRA engine.  Adapter
        ids are pure host-plan state (pinned at admission, constant
        for the request's admitted life), so the dispatch-ahead
        pipeline's one-step-stale planning carries them with no new
        sync reason — a deferred harvest can never change which
        adapter a riding row uses."""
        if self._adapters is None or not any(
                r is not None and r.adapter is not None for r in reqs):
            return False, None
        ids = np.full((len(reqs),), self._adapters.null_slot, np.int32)
        for i, r in enumerate(reqs):
            if r is not None and r.adapter is not None:
                ids[i] = self._adapters.slot_of(r.adapter)
        planes = self._adapters.arena_planes()
        planes["ids"] = jnp.asarray(ids)
        self._adapters.count_gather()
        self._lora_dispatches += 1
        return True, planes

    def _count_sample_route(self, reqs_tokens):
        """Classify emitted tokens into the serving.sample.* route
        counters; ``reqs_tokens`` is (request, n_emitted) pairs."""
        for r, k in reqs_tokens:
            sp = r.sampling
            if sp is None or sp.is_greedy:
                self._m.sample_greedy_tokens.inc(k)
            else:
                self._m.sample_sampled_tokens.inc(k)
            if sp is not None and sp.mask_processor is not None:
                self._m.sample_masked_tokens.inc(k)

    def _mask_dead_end(self, req: Request) -> bool:
        """Advance the request's token-mask state machine past its
        LAST emitted token and report whether the grammar completed:
        an ``allowed()`` with no legal continuation is the EOS of a
        constrained stream (the natural encoding of an accept state in
        a DFA that does not map EOS), and the caller finishes the
        request there.  The ONE advance site semantics for both the
        chunk-final and decode-block paths — call only for LIVE mask
        requests (finished requests need no future mask)."""
        mp = req.sampling.mask_processor
        mp.advance(int(req.tokens[-1]))
        return not np.asarray(mp.allowed(), bool).any()

    def _block_steps(self, riders: List[int], lag: int):
        """``(n, masked)``: the decode steps the block of ``riders`` runs,
        read from their budgets and masks alone.  ``steps_per_call`` when
        some rider is owed that many tokens or more (``remaining`` less
        the ``lag`` steps still in flight), so the block is never longer
        than work that exists: riders owed fewer finish inside it, on the
        device.  One step when every rider is inside its last
        ``steps_per_call - 1`` tokens, or when a mask-constrained row
        (``masked``) must show the host each token before the next."""
        masked = any(self._slots[i].sampling is not None and
                     self._slots[i].sampling.mask_processor is not None
                     for i in riders)
        if masked or not riders:
            return 1, masked
        most = max(self._slots[i].remaining for i in riders) - lag
        return (self.steps_per_call if most >= self.steps_per_call
                else 1), masked

    # graftlint: plan-phase
    def _prefill_chunks(self, out: List[Request]):
        """This step's prompt chunks, paced by the decode steps its block
        will run: for each of those steps one chunk, and where more than
        ``steps_per_call`` slots wait for their prompt, one for every
        ``steps_per_call`` of them (FIFO, so the head of the line may
        take several), until no slot waits.  The block's length is read
        from the riders that are there before the chunks run
        (``_block_steps``); the prompts that finish here only lengthen
        it.  One chunk a step would admit about one request a step
        whatever the slots: a batch of hundreds of slots whose requests
        live a hundred decode steps never fills, and every decode step
        pays its whole weight sweep for a fraction of the rows.  So a
        prompt waits for its chunks no longer than a decoded token waits
        for its harvest, one block: a slot vacated inside a block is
        admitted, prefilled and riding by the next.  The live rows'
        stall a decoded token stays that share of the backlog, whatever
        the block's length.

        The chunks are enqueued back to back and the first tokens of the
        prompts that finished among them land once, after the last
        (``_land_first_tokens``): nothing between two prompts' chunks
        needs the first one's token, so the chip runs chunk after chunk
        while the host prepares the next.  Where the step before began
        this pacing ahead of its harvest (``_admit_ahead``), it goes on
        from there, with the first tokens owed from then landing here."""
        pacing, self._pacing = self._pacing, None
        if pacing is None:
            pacing = self._chunk_pacing(sum(p.n for p in self._pend_q))
        try:
            for _ in pacing:
                self._prefill_chunk(out)
        finally:
            # a chunk that raises leaves the prompts enqueued before it
            # landed, as it did when each landed behind its own chunk
            self._land_first_tokens(out)

    def _chunk_pacing(self, lag: int, leaving=()):
        """Yield once before each chunk of a step's pacing (see
        ``_prefill_chunks``), reading the block's length from the plain
        riders that are there less ``leaving``, ``lag`` steps on."""
        riders = [i for i, r in enumerate(self._slots)
                  if r is not None and r.state == "decode"
                  and r.spec_k is None and i not in leaving]
        n, _ = self._block_steps(riders, lag)
        for _ in range(n):
            for _ in range(max(1, -(-len(self._prefilling)
                                    // self.steps_per_call))):
                yield
            if not self._prefilling:
                return

    # graftlint: plan-phase
    def _prefill_chunk(self, out: List[Request], land: bool = True):
        """Enqueue at most ONE prompt chunk (FIFO over admissions), with
        everything of it the plan knows: counters, the goodput ledger, the
        flight recorder's event, the prefix cache (completed full blocks
        are published as soon as they are written).  A prompt's final
        chunk samples the request's first token: it stays on the device,
        owed to the request (``_first_owed``), and the request leaves the
        line so that the next call takes the next prompt.  The lockstep
        arm reads every token at once, so there the token lands here
        (``land``; a chunk enqueued ahead of a harvest lands its token
        with the next step's on both arms)."""
        if not self._prefilling:
            return
        req = self._prefilling[0]
        start, c = req.pf_pos, self.chunk_len
        is_final = start + c >= req.seq_len
        with _span("serving.prefill", request=req.request_id,
                   slot=req.slot, start=start):
            flags, samp = self._build_samp([req])
            lora_on, lora_planes = self._build_lora([req])
            lora_args = (lora_planes,) if lora_on else ()
            with self._phase("serving.prefill.dispatch") as ph:
                # the scalars go as host values, uploaded with the call's
                # arguments: an eager conversion would queue on the device
                # behind the work enqueued before it, and wait for it
                outp = _call_quiet(
                    self._chunk_fn(flags, lora_on), self._pb,
                    jnp.asarray(req.chunk_ids[None, start:start + c]),
                    np.int32(start), np.int32(req.seq_len),
                    jnp.asarray(self._chunk_tables(req.slot)), samp,
                    *lora_args, *self._arenas, *self._slot_state)
                self._adopt_arenas(outp[1:])
                if not (is_final or self.async_dispatch):
                    # a non-final chunk's sampled token is meaningless
                    # (the engine never advances decode state from it):
                    # the dispatch-ahead engine leaves it un-forced, so
                    # the chunk computes under the NEXT iterations' host
                    # work; the lockstep arm waits for every dispatch
                    outp[0].block_until_ready()
            self._m.prefill_chunks.inc()
            self._m.chunk_latency.observe(ph.seconds)
            self._disp_s += ph.seconds
            self._count_kv_sweep([min(start + c, req.seq_len) - 1])
            self._count_weight_sweep(1)
            # goodput: the dispatch computed chunk_len positions for this
            # row — valid prompt positions split first-time-useful vs
            # cache-known recompute (the [gp_recompute_from, _to) span set
            # at admission), the grid tail past seq_len is pad
            valid = min(start + c, req.seq_len) - start
            rc = max(0, (min(start + valid, req.gp_recompute_to)
                         - max(start, req.gp_recompute_from)))
            self._ledger(valid - rc, tenant=req.tenant,
                         recompute_cache=rc, pad=c - valid)
            self._fr.emit("prefill_chunk", req.request_id, self._step_idx,
                          start=start, tokens=valid)
            req.pf_pos = start + c
            if self._radix is not None:
                full = min(req.pf_pos, req.seq_len) // self.block_len
                if full > req.registered:
                    # token runs + block spans go into the tree as soon as
                    # the blocks are fully written (first writer wins; the
                    # request's pin keeps them alive until release, after
                    # which they park tree-held in the reclaimable LRU)
                    self._radix.insert(req.prompt, req.blocks, full,
                                       start_block=req.registered)
                    req.registered = full
            if is_final:
                self._prefilling.popleft()
                self._first_owed.append((req, outp[0]))
        if land and not self.async_dispatch:
            self._land_first_tokens(out)

    # graftlint: plan-phase
    def _land_first_tokens(self, out: List[Request]):
        """Make the first tokens owed (``_first_owed``: the prompts whose
        final chunk is enqueued and whose token is still a device array)
        host truth: ONE ``jax.device_get`` over all of them, so the copies
        start together and the host waits once, for the last chunk; then
        the requests in the order their chunks were enqueued, so the
        finished list, the slot releases and the handoffs come out in the
        order they did when each prompt landed behind its own chunk.
        Called once a step after its last chunk is enqueued (before the
        verify and the plan, which read the carries written here), and
        after every final chunk on the lockstep arm, which fetches each
        token on its own.

        A landed prompt joins the next block's riders, so a block still
        pending harvests first (``chunk_final``): here, after the step's
        chunks, so that they are enqueued behind it, not behind its
        fetch."""
        if not self._first_owed:
            return
        self._flush_async("chunk_final", out)
        owed, self._first_owed = self._first_owed, []
        for batch in ([owed] if self.async_dispatch
                      else [[o] for o in owed]):
            with _span("serving.prefill", landed=len(batch)):
                with self._wait("serving.prefill.wait") as wait:
                    # sync: chunk_final
                    toks = jax.device_get([tok_d for _, tok_d in batch])
                # the materialization is part of the chunks' dispatch, as
                # the sync tail's is of the block's
                self._disp_s += wait.seconds
                self._m.first_token_fetches.inc()
                for (req, _), tok in zip(batch, toks):
                    self._land_first_token(req, int(tok[0]), out)

    def _land_first_token(self, req: Request, tok0: int,
                          out: List[Request]):
        """``tok0`` is ``req``'s first generated token: stamp it, then
        finish the request there (budget of one, EOS, a grammar with no
        continuation), hand it to a decode replica (prefill role) or flip
        it into the decode mix."""
        self._m.prefills.inc()
        self._m.tokens_emitted.inc()
        t = self._clock()
        req.first_token_time = t
        if req.ttft is not None:
            self._m.ttft.observe(req.ttft)
        req.tokens.append(tok0)
        req.remaining = req.max_new_tokens - 1
        self._count_sample_route([(req, 1)])
        slot = req.slot
        if (self.cfg.eos_token_id is not None and
                tok0 == self.cfg.eos_token_id) or req.remaining == 0 or (
                req.sampling is not None and
                req.sampling.mask_processor is not None and
                self._mask_dead_end(req)):
            # finished at the first token: never enters the decode mix
            self._slots[slot] = None
            self._done[slot] = True
            self._release_blocks(req)
            self._finish(req, t, out)
            return
        if self.role == "prefill":
            # the disaggregation point (ROADMAP item 2): a prefill-
            # role replica never decodes in place — gather the
            # request's KV parcel at exact at-rest bytes and stage it
            # for router pickup; the chosen decode replica resumes
            # token-exact through the unchanged migrate_in/_try_resume
            # path (tok0 travels in the parcel's tok carry)
            self._handoff_out(req, tok0, slot)
            return
        req.state = "decode"
        self._tok[slot] = tok0
        self._lens[slot] = req.seq_len
        # spec-mode rows never ride the plain decode block: their row
        # stays done=True there (frozen lens, trash-routed writes, pad
        # emits) and all progress happens in the verify dispatch, which
        # reads its own host-side truth (req.tokens / self._lens)
        self._done[slot] = req.spec_k is not None

    def _handoff_out(self, req: Request, tok0: int, slot: int):
        """Chunk-final handoff swap-out (prefill-role engines only):
        the ``_preempt`` gather applied at the moment the final chunk
        sampled ``tok0`` — exact at-rest bytes into the host tier, a
        ``_SwapRecord`` with the DECODE-phase carries (``tok=tok0``,
        ``lens=seq_len``), blocks/slot released — except the request
        parks on the handoff-ready list for ``take_handoffs()``
        instead of this engine's own swap list: its decode belongs to
        another replica now.  No pipeline flush is needed: the final
        chunk already synced (reason ``chunk_final``) before
        dispatching, and its outputs materialized with ``tok0``."""
        ids = self._tables[slot].copy()     # BEFORE release trashes it
        n = len(req.blocks)
        with _span("serving.handoff_out", request=req.request_id,
                   blocks=n):
            rows = [np.ascontiguousarray(r[:n])
                    for r in self._gather_rows(ids)]
        key = self._host_tier.put(rows, n, "preempt")
        req.swap = _SwapRecord(host_key=key, n_blocks=n,
                               tok=int(tok0), lens=int(req.seq_len),
                               state="decode")
        self._release_blocks(req)
        self._slots[slot] = None
        self._done[slot] = True
        req.slot = None
        req.state = "swapped"
        self._handoff_ready.append(req)
        nbytes = n * self.block_len * self._kv_row_bytes
        self._m.handoff_requests.inc(reason="chunk_final")
        self._m.handoff_blocks.inc(n)
        self._m.handoff_bytes.inc(nbytes)
        self._update_host_gauge()
        self._m.slot_occupancy.set(
            sum(r is not None for r in self._slots))
        _span_instant("serving.request.handoff",
                      request=req.request_id, blocks=n)
        self._fr.emit("handoff", req.request_id, self._step_idx,
                      blocks=n, reason="chunk_final")

    def take_handoffs(self) -> List[Request]:
        """Drain the chunk-final handoff staging: requests whose KV
        parcel awaits a decode replica (state ``"swapped"``, parcel in
        this engine's host tier under ``req.swap.host_key``).  The
        caller — the router's handoff orchestration — owns them after
        this call: it transfers each parcel through its staging tier
        and places the request via the destination's ``migrate_in``.
        Empty on every step of a ``"both"``/``"decode"`` engine."""
        out, self._handoff_ready = self._handoff_ready, []
        return out

    def _lora_donate(self, lora_on: bool, donate=None):
        """Arena donation positions of a serving program: the ``lora``
        pytree argument (inserted after ``samp``) shifts the flat-
        arena positions by one.  ``donate`` is the program family's
        base positions (chunk/verify vs the decode block, whose
        ``budget`` carry sits one to the left of ``samp``).  The
        adapter arenas themselves are READ-ONLY program inputs and are
        never donated — a swap-in between dispatches replaces them
        functionally."""
        if donate is None:
            donate = self._donate
        if not lora_on:
            return donate
        return tuple(p + 1 for p in donate)

    def _chunk_fn(self, flags, lora_on: bool = False):
        fn = self._chunk_fns.get((flags, lora_on))
        if fn is None:
            fn = jax.jit(
                build_chunk_prefill(self._model, self.cfg,
                                    kv_int8=self._kv_int8,
                                    samp_flags=flags, lora=lora_on,
                                    wq=self._wq, shard=self._shard),
                donate_argnums=self._lora_donate(lora_on))
            self._chunk_fns[(flags, lora_on)] = fn
        return fn

    def _block_fn(self, steps: int, flags, lora_on: bool = False,
                  iters: int = 1):
        """The decode-block program for ``steps`` total scanned steps.
        A fused depth-S window (``iters`` iterations of steps/iters
        each, built by ``llm.build_fused_decode_window``) compiles to
        the SAME program as a plain ``steps``-step block — the cache
        keys on total steps, so windows and blocks share
        compilations."""
        fn = self._blocks.get((steps, flags, lora_on))
        if fn is None:
            if iters > 1:
                build = build_fused_decode_window(
                    self._model, self.cfg, steps // iters, iters,
                    kv_int8=self._kv_int8, samp_flags=flags,
                    lora=lora_on, wq=self._wq, shard=self._shard)
            else:
                build = _build_paged_decode_block(
                    self._model, self.cfg, steps,
                    kv_int8=self._kv_int8, samp_flags=flags,
                    lora=lora_on, wq=self._wq, shard=self._shard)
            fn = jax.jit(
                build,
                donate_argnums=self._lora_donate(lora_on,
                                                 self._donate_blk))
            self._blocks[(steps, flags, lora_on)] = fn
        return fn

    def _block_rides(self, i: int, r: Request) -> bool:
        """Does slot ``i`` ride THIS iteration's plain decode block?
        Plain-decode rows always do; a spec-mode row only on an
        iteration where the whole spec mix drafted nothing
        (``_spec_fallback``) — a zero-draft verify would pay the
        K+1-wide forward for one token, so those iterations ride the
        shared block instead (which may scan up to ``steps_per_call``
        tokens: drafting opportunities inside that span are forgone,
        a deliberate trade — the drafter just missed, so the stream is
        locally unpredictable anyway; tokens stay exactly the
        sequential greedy stream either way)."""
        return r.state == "decode" and (r.spec_k is None
                                        or i in self._spec_fallback)

    def _chunk_tables(self, slot: int) -> np.ndarray:
        """The chunk program's table row ``[1, max_blocks]``; for a model
        with per-slot state the slot's index rides behind its blocks
        (``[1, max_blocks + 1]``), so that the program finds the slot's
        row of the state arenas with no argument of its own."""
        row = self._tables[slot][None, :]
        if not self._slot_state:
            return row
        return np.concatenate(
            [row, np.full((1, 1), slot, np.int32)], axis=1)

    def _adopt_arenas(self, donated):
        """Take back what a chunk or decode program returns behind its
        own outputs: the KV arenas, then the per-slot state arenas, then
        (decode programs of a model that counts) the block's counters,
        which are returned for the harvest to fetch."""
        n_kv, n_st = len(self._arenas), len(self._slot_state)
        self._arenas = list(donated[:n_kv])
        self._slot_state = list(donated[n_kv:n_kv + n_st])
        rest = donated[n_kv + n_st:]
        return rest[0] if rest else None

    def _count_experts(self, p: _PendingBlock):
        """Fetch a harvested decode block's expert-load array, where the
        model counts one (rows routed to each expert; where the model's
        router has zero-compute experts, ``counts_picks``, the picks that
        fell on them and all picks; then the (layer, step) pairs, then the
        experts that got a row summed over the pairs), and feed it to the
        registry."""
        if p.counters_d is None:
            return
        counters = np.asarray(p.counters_d)
        tail = 4 if getattr(self._model, "counts_picks", False) else 2
        for e in np.flatnonzero(counters[:-tail]):
            self._m.moe_expert_tokens.inc(int(counters[e]), expert=str(e))
        if tail == 4:
            self._m.moe_zero_expert_picks.inc(int(counters[-4]))
            self._m.moe_expert_picks.inc(int(counters[-3]))
        self._m.moe_layer_steps.inc(int(counters[-2]))
        self._m.moe_experts_touched.inc(int(counters[-1]))

    def _decode_tables(self) -> np.ndarray:
        """The decode block's table view: real rows for slots riding
        this block, all-trash rows for vacant/prefilling/spec-verify
        slots — a frozen row's statically-shaped write at its pinned
        ``lens`` must never land in a block another sequence now owns
        (a verifying spec row's blocks are live: the verify dispatch
        owns them)."""
        tbl = np.full_like(self._tables, self._pool.trash)
        for i, r in enumerate(self._slots):
            if r is not None and self._block_rides(i, r):
                tbl[i] = self._tables[i]
        return tbl

    def _verify_fn(self, steps: int, flags, lora_on: bool = False):
        fn = self._verify_fns.get((steps, flags, lora_on))
        if fn is None:
            fn = jax.jit(
                build_spec_verify(self._model, self.cfg, steps,
                                  kv_int8=self._kv_int8,
                                  samp_flags=flags, lora=lora_on,
                                  wq=self._wq, shard=self._shard),
                donate_argnums=self._lora_donate(lora_on))
            self._verify_fns[(steps, flags, lora_on)] = fn
        return fn

    # graftlint: plan-phase
    def _spec_verify(self, out: List[Request]):
        """One speculative iteration over every spec-mode decode slot:
        draft (host), verify (ONE batched K+1-position target forward),
        accept (host), advance/rewind per-slot lengths.

        The verify width is the ENGINE-LIFETIME ``max(spec_decode) + 1``
        (not the current mix's max, which would oscillate and
        jit-compile a fresh program every time the widest request
        retires): at most one compile per new high-water K, with
        narrower rows (smaller spec_k, fewer drafts proposed, tail of
        the token budget) masked by ``n_valid`` rather than
        recompiled.  Rollback is the length
        bookkeeping itself: ``self._lens[slot]`` advances by exactly
        the emitted count, so rejected draft positions stay behind the
        mask (re-masking the tail of the last block) until the next
        forward overwrites them."""
        spec = [i for i, r in enumerate(self._slots)
                if r is not None and r.state == "decode"
                and r.spec_k is not None]
        if not spec:
            return
        # defensive: the defer predicate never leaves a harvest
        # pending while spec slots decode (spec entry goes through a
        # chunk_final sync), but the verify below reads host lens
        # mirrors — a stale mirror here would verify against the
        # wrong frontier, so sync loudly rather than drift silently
        self._flush_async("spec", out)
        drafts = {}
        for i in spec:
            req = self._slots[i]
            # budget clamp: a verify emits <= k_eff + 1 tokens and its
            # last WRITE lands at lens + k_eff <= seq_len + max_new - 2
            # — never past the request's allocated blocks
            k_eff = min(req.spec_k, req.remaining - 1)
            d = self._drafter.propose(
                np.concatenate([req.prompt[:req.seq_len],
                                np.asarray(req.tokens, np.int32)]),
                k_eff) if k_eff > 0 else np.zeros((0,), np.int32)
            d = np.asarray(d).reshape(-1).astype(np.int32)[:k_eff]
            if k_eff > 0:
                # hit/miss score the DRAFTER; budget-clamped tails
                # (k_eff == 0) never consulted it and count as neither
                if d.size:
                    self._m.spec_draft_hits.inc()
                else:
                    self._m.spec_draft_misses.inc()
                self._m.spec_draft_tokens.inc(int(d.size))
            drafts[i] = d
        if not any(drafts[i].size for i in spec):
            # nothing drafted anywhere: a verify would pay the K+1-wide
            # forward to emit one token per slot — ride the plain block
            # this iteration instead (same greedy tokens; the block may
            # scan steps_per_call of them, see _block_rides).  With
            # >= 1 drafted row the verify's cost is fixed at B x width
            # anyway, so empty rows then ride it for free.
            self._spec_fallback = set(spec)
            return
        width = self._spec_k_max + 1
        toks = np.full((self.num_slots, width), self.cfg.pad_token_id,
                       np.int32)
        n_valid = np.zeros((self.num_slots,), np.int32)
        tbl = np.full_like(self._tables, self._pool.trash)
        for i in spec:
            req = self._slots[i]
            d = drafts[i]
            toks[i, 0] = req.tokens[-1]   # the still-un-fed last token
            toks[i, 1:1 + d.size] = d
            n_valid[i] = 1 + d.size
            tbl[i] = self._tables[i]
        spec_set = set(spec)
        riding = [r if i in spec_set else None
                  for i, r in enumerate(self._slots)]
        flags, samp = self._build_samp(riding)
        lora_on, lora_planes = self._build_lora(riding)
        lora_args = (lora_planes,) if lora_on else ()
        with self._phase("serving.spec_verify", width=width,
                         active=len(spec)) as ph:
            outp = _call_quiet(
                self._verify_fn(width, flags, lora_on), self._pb,
                jnp.asarray(toks),
                jnp.asarray(self._lens), jnp.asarray(n_valid),
                jnp.asarray(tbl), samp, *lora_args, *self._arenas)
            if flags[0]:
                # sampled mix: the verify also returned the position-
                # keyed stochastic-sampling draws ([B, width] each)
                greedy, u, accept_p, resample, sample = (
                    np.asarray(x) for x in outp[:5])
                self._arenas = list(outp[5:])
            else:
                greedy = np.asarray(outp[0])            # [B, width]
                self._arenas = list(outp[1:])
        self._disp_s += ph.seconds
        self._m.spec_verifies.inc()
        # the K-wide kernel DMAs the STATIC width's frontier
        # (lens + cq - 1) for every spec row, however few positions
        # n_valid marks valid — model exactly that
        self._count_kv_sweep([int(self._lens[i]) + width - 1
                              for i in spec])
        self._count_weight_sweep(1)
        t = self._clock()
        gp: dict = {}          # tenant -> [useful, spec_reject, pad]
        for i in spec:
            req = self._slots[i]
            sp = req.sampling
            if sp is not None and not sp.is_greedy:
                emitted, accepted, resamples = accept_drafts_sampled(
                    drafts[i], u[i], accept_p[i], resample[i],
                    sample[i], self.cfg.eos_token_id)
                self._m.sample_resamples.inc(resamples)
            else:
                emitted, accepted = accept_drafts(
                    greedy[i], drafts[i], self.cfg.eos_token_id)
            self._m.spec_accepted_len.observe(float(accepted))
            self._m.spec_accepted_tokens.inc(accepted)
            self._m.tokens_emitted.inc(len(emitted))
            self._count_sample_route([(req, len(emitted))])
            # goodput: this row dispatched ``width`` positions —
            # emitted tokens are useful, rejected/EOS-cut draft
            # positions (they were computed AND written, then rolled
            # back behind the lens) are spec_reject, the masked tail
            # past n_valid is pad
            n_val = int(n_valid[i])
            cell = gp.setdefault(req.tenant, [0, 0, 0])
            cell[0] += len(emitted)
            cell[1] += n_val - len(emitted)
            cell[2] += width - n_val
            req.tokens.extend(emitted)
            req.remaining -= len(emitted)
            self._lens[i] += len(emitted)
            self._tok[i] = emitted[-1]
            _span_instant("serving.spec.accept", request=req.request_id,
                          drafted=int(drafts[i].size), accepted=accepted)
            self._fr.emit("spec_verify", req.request_id, self._step_idx,
                          drafted=int(drafts[i].size), accepted=accepted,
                          rejected=n_val - len(emitted),
                          emitted=len(emitted))
            hit_eos = (self.cfg.eos_token_id is not None
                       and emitted[-1] == self.cfg.eos_token_id)
            if hit_eos or req.remaining == 0:
                self._slots[i] = None
                self._done[i] = True
                self._release_blocks(req)
                self._finish(req, t, out)
        for tenant, (u, rej, pad) in gp.items():
            self._ledger(u, tenant=tenant, spec_reject=rej, pad=pad)

    def step(self, now: Optional[float] = None) -> List[Request]:
        """One scheduler iteration: sweep queue-delay timeouts and
        admit/resume into vacant slots (preempting strictly-worse
        victims under block pressure), run the step's prefill chunks
        and land their first tokens (``_prefill_chunks``),
        then one speculative verify forward over the spec-mode slots
        and one decode block over the plain-decode mix — the phases
        coexist in the same iteration.  A block harvested in the same
        step that ends riders by their budget admits into their slots
        and enqueues the next step's first chunks before its outputs
        are fetched (``_admit_ahead``).  Returns the requests that
        reached a terminal state this iteration (finished or
        timeout).

        Also attributes the iteration's wall time: every compiled-
        dispatch site (chunk prefill, verify, decode block, swap
        gathers/scatters) accumulates into ``serving.step.
        dispatch_seconds``, time spent blocking on a PREVIOUS
        iteration's deferred outputs into ``serving.step.
        overlap_seconds``, injected fault stalls into ``serving.fault.
        stall_seconds``, and the remainder is ``serving.step.
        host_seconds`` — the pure host-scheduler slice the
        dispatch-ahead pipeline hides under device time.  The thread's
        CPU time, read at the step's and its device waits' boundaries,
        gives ``serving.step.offcpu_seconds``: the step's wall outside
        its waits and stalls that the thread spent off the CPU.  Steps
        that dispatched nothing (idle admission polls) observe neither
        host nor dispatch nor off-CPU time.

        Each phase is delimited ONCE (``_phase``): the same boundaries
        open and close a span, so under a profiler session the
        iteration reads ``serving.step`` > ``serving.admit``,
        ``serving.prefill`` (a chunk, > ``.dispatch``; the step's first
        tokens landing, > ``.wait``), ``serving.spec_verify``,
        ``serving.plan``, ``serving.decode_block``, ``serving.harvest``
        (> ``.wait``, then ``.absorb``: ``_checked_harvest`` and
        ``_absorb_block``) on the device trace's clock."""
        self._step_idx += 1
        self._disp_s = 0.0
        self._overlap_s = 0.0
        self._stall_s = 0.0
        self._wait_s = 0.0
        self._wait_cpu_ns = 0
        self._in_step = True
        with self._phase("serving.step", step=self._step_idx,
                         queued=len(self._queue)) as whole:
            cpu0 = time.thread_time_ns()
            try:
                out = self._step_inner(now)
                # reconcile any demote gathers this step enqueued so
                # their wait is attributed HERE (and the device copies
                # do not outlive the step)
                self._reconcile_host_tier()
            finally:
                self._in_step = False
                cpu = time.thread_time_ns() - cpu0
        disp = self._disp_s
        if disp > 0.0:
            self._m.step_dispatch.observe(disp)
            self._m.step_host.observe(
                max(whole.seconds - disp - self._overlap_s
                    - self._stall_s, 0.0))
            self._m.step_offcpu.observe(max(
                whole.seconds - self._wait_s
                - (cpu - self._wait_cpu_ns) * 1e-9, 0.0))
        return out

    # graftlint: plan-phase
    def _step_inner(self, now: Optional[float] = None) -> List[Request]:
        # finishes a between-steps flush discovered (cancel(), a
        # wall-timeout drain) hand over to THIS step's return
        finished: List[Request] = self._flush_finishes
        self._flush_finishes = []
        t_now = self._clock() if now is None else now
        # step-rate estimate for the arrival-aware fused window:
        # tracked ONLY from explicit step(now=) clocks (the
        # deterministic-trace contract) — a wall-clock-driven engine
        # must never size windows from its own nondeterministic rate
        if now is not None:
            if self._last_now is not None and t_now > self._last_now:
                self._step_dt = t_now - self._last_now
            self._last_now = t_now
        else:
            self._step_dt = 0.0
            self._last_now = None
        with _span("serving.admit", queued=len(self._queue)):
            if self._fault is not None:
                # replica-fatal faults raise BEFORE any scheduling work
                # mutates state: a killed/wedged replica did not run this
                # step, and the router's failover recovers from the last
                # consistent host truth
                if self._fault.take_kill(self._step_idx):
                    raise ReplicaKilledError(
                        f"injected replica kill at step {self._step_idx} "
                        f"(latched until the injector's replica restart)")
                if self._fault.take_permanent_stall():
                    raise EngineStalledError(
                        f"injected permanent stall at step "
                        f"{self._step_idx}: the dispatch will never "
                        f"return (latched until the injector's replica "
                        f"restart)")
                stall = self._fault.take_stall()
                if stall:
                    with self._wait("serving.fault.stall",
                                    seconds=stall) as ph:
                        time.sleep(stall)
                    dt = ph.seconds
                    # charge the injected sleep to its OWN histogram and
                    # carve it out of host_seconds: a fault-injection run
                    # must not pollute the host-scheduler baseline the
                    # dispatch-ahead pipeline is judged against
                    self._stall_s += dt
                    self._m.stall_seconds.observe(dt)
                for rid in self._fault.take_forced_swaps():
                    for r in self._slots:
                        if r is not None and r.request_id == rid \
                                and r.state in ("prefill", "decode"):
                            self._preempt(r, reason="forced",
                                          out=finished)
                            break
                n_evict = self._fault.take_tier_evicts()
                if n_evict:
                    applied = 0
                    for _ in range(n_evict):
                        if not self._host_tier.evict_one():
                            break
                        applied += 1
                    self._fault.record_tier_evicts(applied)
                    self._update_host_gauge()
            self._admit(t_now, finished)
        self._prefill_chunks(finished)
        self._spec_fallback = set()
        self._spec_verify(finished)
        # re-assert spec rows' block state for THIS iteration: fallback
        # rows thaw into the shared block, verifying rows stay frozen
        # — and a thawing row's fed token comes from HOST truth
        # (req.tokens[-1]), because a frozen row's device carry emits
        # pad into tok (the previous block's done-row convention)
        for i, r in enumerate(self._slots):
            if r is not None and r.state == "decode" \
                    and r.spec_k is not None:
                self._done[i] = i not in self._spec_fallback
                if i in self._spec_fallback:
                    self._tok[i] = r.tokens[-1]
        active = [i for i, r in enumerate(self._slots)
                  if r is not None and self._block_rides(i, r)]
        if not active:
            if self._pend_q:
                # the depth-flush path of the finish-bitmap protocol:
                # the pipeline ran DRY because every rider finished
                # inside an in-flight dispatch (EOS observed on
                # device; budget finishes always harvest sync) —
                # flush the ghost tail so the finishes retire, charged
                # to the eos the pipeline deferred
                self._flush_async("eos", finished)
            self._m.slot_occupancy.set(
                sum(r is not None for r in self._slots))
            return finished
        with _span("serving.plan", active=len(active),
                   queued=len(self._queue)):
            # the whole block whenever some rider needs all of it (see
            # ``_block_steps``): riders whose budget ends inside it finish
            # on the device, freeze there, and the harvest hands each
            # exactly the tokens it was owed.  Only a mix whose every
            # rider is inside its last ``steps_per_call - 1`` tokens (a
            # draining engine, a lone request's tail) takes exact single
            # steps.  Mask-constrained rows clamp the mix to single steps
            # too: their bias plane is valid for exactly ONE emitted
            # token — the host state machine must observe it before the
            # next bias can be built.  The clamp prices ALL co-resident
            # rows at one dispatch per token while a masked row is live
            # (deliberate: masked workloads are latency-shaped and the
            # alternative — freezing masked rows out of the n-step block
            # via the done plane and feeding them a second 1-step
            # dispatch per iteration — doubles dispatches and accounting
            # paths for a mix this engine rarely sees)
            pend = self._pend_q[-1] if self._pend_q else None
            if pend is not None:
                # structurally impossible either way (new decode entrants
                # sync via chunk_final/resume, cancel and preempt flush) —
                # a drift means the invariant broke and dispatching would
                # corrupt carries: fail loudly.  At depth 1 the set must
                # match EXACTLY (no rider can finish while deferred — the
                # PR-10 contract); at depth >= 2 riders legally LEAVE a
                # deferred set by finishing on device, so only growth is
                # a breach.
                if self.async_depth == 1:
                    if pend.active != active:
                        raise RuntimeError(
                            f"dispatch-ahead riding set drifted while a "
                            f"harvest was deferred: pending {pend.active} "
                            f"vs now {active}")
                elif not set(active) <= set(pend.active):
                    raise RuntimeError(
                        f"dispatch-ahead riding set grew while a harvest "
                        f"was deferred: pending {pend.active} vs now "
                        f"{active}")
            # stale-truth correction: while harvests are deferred, each
            # rider's host truth (remaining, len(tokens), lens mirror) is
            # behind by exactly the steps still in flight (every rider
            # rides every pending dispatch — it entered before the oldest
            # and can only leave by finishing, which is discovered AT
            # harvest)
            lag = sum(p.n for p in self._pend_q)
            min_budget = min(self._slots[i].remaining for i in active) - lag
            n, masked = self._block_steps(active, lag)
            # fused multi-iteration window (async_depth >= 2): when the
            # next S iterations are PROVABLY eventless — nothing queued or
            # swapped to admit, no chunk to ride, the dispatch itself
            # deferrable (no mask/penalty/spec row) and budget headroom
            # strictly beyond the whole window for every rider — dispatch
            # S iterations as ONE fused scan program, amortizing the
            # per-dispatch host cost the way decode_scan_body amortizes
            # the per-token cost.  EOS inside the window is legal: the
            # finish bitmap freezes the row in-trace and the harvest
            # re-splits the window iteration by iteration.
            iters = 1
            fuse_cap = self.async_depth
            if self._queue:
                # a queued request normally blocks fusing outright (its
                # admission is an event inside the window).  Arrival-aware
                # sizing (PR 14's open follow-on): when every queued entry
                # is a known FUTURE arrival and the trace drives step(now=)
                # on a monotonic clock, the last observed per-step
                # now-delta bounds the steps until the earliest arrival —
                # fuse min(S, steps_until_arrival), so the window SHRINKS
                # to close at the arrival step instead of degrading to
                # unfused.  Already-arrived entries (or no step-rate
                # estimate) keep the conservative outright block.
                fuse_cap = 0
                if self._step_dt > 0 and \
                        all(r.arrival_time > t_now for r in self._queue):
                    nxt = min(r.arrival_time for r in self._queue)
                    until = int(-(-(nxt - t_now) // self._step_dt))
                    fuse_cap = min(self.async_depth, until)
            if (self.async_depth > 1 and not masked
                    and not self._prefilling and not self._swapped
                    and fuse_cap > 1
                    and min_budget > self.async_depth * n
                    and self._block_sync_reason(n, active, lag) is None):
                iters = fuse_cap
            n_total = n * iters
            active_set = set(active)
            riding = [self._slots[i] if i in active_set else None
                      for i in range(self.num_slots)]
            flags, samp = self._build_samp(riding, pos_lag=lag)
            # adapter ids are host-plan state pinned with the riding set
            # (which cannot grow while a harvest is deferred), so the
            # dispatch-ahead pipeline carries them one-step-stale for free
            lora_on, lora_planes = self._build_lora(riding)
            lora_args = (lora_planes,) if lora_on else ()
            pre_lens = np.array(self._lens)
            if pend is not None:
                # every current rider rode every pending dispatch (subset
                # check above), so its true pre-dispatch lens is the host
                # mirror + the in-flight steps (rows an in-flight EOS
                # already froze advance less — the harvest's sweep model
                # clamps to their final lens)
                pre_lens[active] += lag
                # double-buffered carries: feed the newest in-flight
                # dispatch's device outputs straight into this one — no
                # host round-trip, no wait.  budget rides the same carry
                # chain (the finish-bitmap protocol).
                tok_in, lens_in, done_in, budget_in = \
                    pend.tok_d, pend.lens_d, pend.done_d, pend.budget_d
            else:
                budget = np.zeros((self.num_slots,), np.int32)
                for i in active:
                    budget[i] = self._slots[i].remaining
                # copies: an admission ahead of the harvest writes the
                # host carries while the block may still read them (a CPU
                # ``asarray`` can alias the host buffer)
                tok_in = jnp.asarray(self._tok.copy())
                lens_in = jnp.asarray(self._lens.copy())
                done_in = jnp.asarray(self._done.copy())
                budget_in = jnp.asarray(budget)
            tables_in = jnp.asarray(self._decode_tables())
        with self._phase("serving.decode_block", steps=n_total,
                         active=len(active)) as ph:
            out = _call_quiet(
                self._block_fn(n_total, flags, lora_on, iters=iters),
                self._pb, tok_in, lens_in, done_in, budget_in, samp,
                *lora_args, tables_in, *self._arenas, *self._slot_state)
            counters_d = self._adopt_arenas(out[5:])
        self._disp_s += ph.seconds
        # plan-known accounting lands at DISPATCH (same step as the
        # lockstep engine); output-dependent accounting (KV sweep,
        # ledger, token streams, flight-recorder events) lands at
        # harvest inside _absorb_block.  A rider counts the cells it is
        # owed, which the plan knows exactly (budget less the steps in
        # flight): the cells a row spends frozen behind its budget are
        # device steps wasted, not busy slot-steps.  Where an EOS can
        # end a rider early (or already did, unobserved, at
        # async_depth >= 2) the plan cannot know without the sync this
        # protocol removes — there these counters are documented
        # approximate; the harvest-side ledger stays exact.
        owed = [(self._slots[i],
                 min(n_total, max(self._slots[i].remaining - lag, 0)))
                for i in active]
        live_cells = sum(k for _, k in owed)
        self._m.decode_steps.inc(n_total)
        self._m.busy_slot_steps.inc(live_cells)
        self._m.block_dispatches.inc()
        self._m.tokens_emitted.inc(live_cells)
        self._count_sample_route(owed)
        new_pend = _PendingBlock(
            step_idx=self._step_idx, n=n_total, per_iter=n,
            iters=iters, active=list(active),
            reqs=[self._slots[i] for i in active], pre_lens=pre_lens,
            toks_d=out[0], tok_d=out[1], lens_d=out[2], done_d=out[3],
            budget_d=out[4], counters_d=counters_d)
        self._pend_q.append(new_pend)
        # THE overlap points: older dispatches' outputs are forced
        # only now, after this iteration's host work ran and its
        # dispatch was enqueued — harvest down to the configured depth
        while len(self._pend_q) > self.async_depth:
            self._harvest_next(finished, "deferred")
            self._m.async_harvests.inc()
        # defer or sync the tail.  Riders a same-step harvest just
        # retired are skipped inside _block_sync_reason; the remaining
        # in-flight steps (older pendings minus the new dispatch)
        # correct host truth for the budget check.
        reason = self._block_sync_reason(
            n_total, active,
            lag=sum(p.n for p in self._pend_q) - n_total)
        if reason is None:
            # steady-state pipeline depth (the transient enqueue->
            # harvest overshoot is not a depth the scheduler sustains,
            # and a sync iteration never counts as depth)
            self._m.async_depth.set(len(self._pend_q))
        else:
            if self.async_dispatch:
                self._m.async_syncs.inc(reason=reason)
            # older dispatches flush first, FIFO (their waits charge
            # to overlap — they did run under later host work) ...
            while len(self._pend_q) > 1:
                self._harvest_next(finished, reason)
            self._pend_q.pop()
            self._m.async_depth.set(0)
            try:
                self._admit_ahead(t_now, n_total, active, finished)
            finally:
                with _span("serving.harvest", reason=reason):
                    with self._wait("serving.harvest.wait") as wait:
                        toks = np.asarray(new_pend.toks_d)      # [B, n]
                        tok = np.array(new_pend.tok_d)  # writable copies
                        lens = np.array(new_pend.lens_d)
                        done = np.array(new_pend.done_d)
                        self._count_experts(new_pend)
                    # ... and the new dispatch's sync materialization is
                    # part of the dispatch, exactly the lockstep engine's
                    # attribution
                    self._disp_s += wait.seconds
                    with _span("serving.harvest.absorb"):
                        toks = self._checked_harvest(toks)
                        self._absorb_block(new_pend, toks, tok, lens,
                                           done, finished)
        return finished

    def _admit_ahead(self, now: float, n: int, active: List[int],
                     out: List[Request]):
        """Before the sync harvest of the ``n``-step block just enqueued:
        where the riders it ends by their budget are all the finishes it
        can hold (``_leaving``) and a request waits, admit into their
        slots now and enqueue the next step's first chunks, one an
        admission, so that the chip runs them while the host fetches and
        absorbs the block.  A new request rides the block it would have
        ridden had it been admitted after the harvest: only the slot
        index is handed over (the retiring rider's blocks release at the
        harvest, which leaves the row alone), and the chunk program takes
        the arenas and state rows the block returns, so the device runs
        it after the block.  Both arms take this order; the lockstep
        arm's block is not in flight, so its admissions do not count in
        ``serving.admits_ahead``."""
        leaving = self._leaving(n, active)
        if not leaving or not (self._swapped or any(
                r.arrival_time <= now for r in self._queue)):
            return
        with _span("serving.admit", queued=len(self._queue),
                   leaving=len(leaving)):
            admitted = self._admit(now, out, leaving)
        if not admitted:
            return
        if self.async_dispatch:
            self._m.admits_ahead.inc(sum(
                self._slots[i] is not r for i, r in leaving.items()))
        self._pacing = self._chunk_pacing(n, leaving)
        for _ in zip(range(admitted), self._pacing):
            self._prefill_chunk(out, land=False)

    def _leaving(self, n: int, active: List[int]) -> dict:
        """{slot: rider} of the riders an ``n``-step block ends by their
        budget, where nothing else can end one inside it: no EOS token,
        no rider whose token a mask or a penalty plane reads and no
        speculative row (their finishes, and their host truth, wait for
        the harvest); else empty."""
        if self.cfg.eos_token_id is not None or any(
                r is not None and r.spec_k is not None
                and r.state == "decode" for r in self._slots):
            return {}
        leaving = {}
        for i in active:
            r = self._slots[i]
            if r is None or r.state != "decode":
                continue              # retired by a same-step harvest
            sp = r.sampling
            if sp is not None and (sp.mask_processor is not None
                                   or sp.needs_penalty):
                return {}
            if r.remaining <= n:
                leaving[i] = r
        return leaving

    def _stall_diagnosis(self, wall_timeout_s: float) -> str:
        """The state dump an ``EngineStalledError`` carries: enough to
        tell an exhausted pool from an injected fault from a trace
        whose arrivals simply lie beyond the wall budget."""
        active = {r.request_id: r.state for r in self._slots
                  if r is not None}
        return (
            f"serving loop exceeded wall_timeout_s={wall_timeout_s} "
            f"without draining: queued={len(self._queue)} "
            f"(arrived={sum(r.arrival_time <= self._clock() for r in self._queue)}), "
            f"swapped={len(self._swapped)}, active slots={active}, "
            f"prefilling={len(self._prefilling)}, blocks free="
            f"{self._pool.available()} in_use={self._pool.in_use()} "
            f"cached={self._pool.cached()} of {self.num_blocks}, "
            f"fault_injector={'armed' if self._fault is not None else 'none'}")

    def run(self, max_iters: Optional[int] = None,
            wall_timeout_s: Optional[float] = None) -> List[Request]:
        """Drain the queue: admit/prefill/decode until every submitted
        request has reached a terminal state.  Sleeps only when idle
        ahead of a future arrival.  ``wall_timeout_s`` bounds the
        WHOLE drain in wall-clock time: a wedged pool (exhaustion with
        nothing running, an injected fault, a stalled dispatch) raises
        a diagnosable ``EngineStalledError`` — with queue / slot /
        block-pool state in the message — instead of spinning in the
        idle loop forever; the engine stays consistent and a later
        ``run()`` continues where it stopped.  Returns this call's
        terminal requests (finished and timed-out) in submission
        order."""
        finished: List[Request] = []
        iters = 0
        start = self._clock()
        while self._queue or self._swapped \
                or any(r is not None for r in self._slots):
            now = self._clock()
            if wall_timeout_s is not None and \
                    now - start > wall_timeout_s:
                # flush the in-flight harvest BEFORE raising: every
                # token the device already produced reaches its
                # request, the deferred ledger/flight-recorder events
                # land, and the engine the caller inspects is
                # self-consistent (a later run() continues cleanly)
                self._flush_async("drain")
                self._reconcile_host_tier()
                raise EngineStalledError(
                    self._stall_diagnosis(wall_timeout_s))
            if (not any(r is not None for r in self._slots)
                    and not self._swapped and self._queue):
                next_arrival = min(r.arrival_time for r in self._queue)
                if next_arrival > now:
                    time.sleep(min(0.005, next_arrival - now))
                    continue
            n_before = len(finished)
            finished.extend(self.step(now))
            if len(finished) == n_before and \
                    not any(r is not None for r in self._slots):
                # the step ran nothing and retired nothing — queued or
                # swapped work that cannot be admitted/resumed (pool
                # wedged / injected fault): nap instead of hot-spinning
                # the scheduler until wall_timeout_s or the fault
                # clears.  Any real progress leaves a slot occupied
                # (admission, prefill, decode), so this never slows a
                # healthy drain.
                time.sleep(0.001)
            iters += 1
            if max_iters is not None and iters > max_iters:
                self._flush_async("drain")
                self._reconcile_host_tier()
                raise RuntimeError(
                    f"serving loop exceeded max_iters={max_iters} with "
                    f"{len(self._queue)} queued / "
                    f"{len(self._swapped)} swapped / "
                    f"{sum(r is not None for r in self._slots)} active")
        # at async_depth == 1 a drained loop cannot leave a harvest
        # pending (the last rider's final block is always a forced
        # budget/eos sync); at depth >= 2 the finish-bitmap protocol
        # CAN — the dispatches enqueued after an in-flight EOS ride
        # out as device-frozen ghosts — so the drain flush absorbs
        # them here and run() never hands back stale truth
        self._flush_async("drain", finished)
        self._reconcile_host_tier()
        finished.extend(self._flush_finishes)
        self._flush_finishes = []
        return sorted(finished, key=lambda r: r.request_id)

    def stats(self) -> dict:
        """Scheduler counters, read back out of the observability
        registry as per-engine deltas (``_ServingInstruments`` — see
        its docstring for the shared-registry and disabled-registry
        caveats).  ``mean_slot_occupancy`` is the fraction of (decode
        step x slot) cells that held a live PLAIN-decode request — the
        utilization static batching forfeits on mixed-length traces;
        spec-mode slots progress via verify forwards, not decode
        steps, and are excluded from both numerator and step count.
        ``prefix_hit_rate`` is block-granular over matchable prompt
        blocks; ``peak_blocks_in_use`` is the pool's refcount>0
        high-water mark (host-mirrored, registry-independent).
        ``mean_latency_s``/``mean_ttft_s`` are means over THIS engine's
        finished requests and are ``None`` — never a division by zero —
        while that set is empty.  The ``spec_*`` keys cover the
        speculative route: ``spec_mean_accepted_len`` is accepted draft
        tokens per verify forward, AGGREGATED over the spec slots that
        forward covered — a verify emits accepted + (one correction/
        bonus per spec slot) tokens, so the per-forward multiplier is
        n_spec_slots + this value (1 + it only at a single spec slot);
        ``spec_acceptance_rate`` is token-granular over drafted
        tokens.  ``sampled_tokens``/``greedy_tokens`` split emitted
        tokens by sampling route (``masked_tokens`` of them carried an
        active token-mask constraint); ``sample_resamples`` counts
        residual draws consumed by stochastic speculative sampling.
        The overload keys: ``preemptions``/``preempt_resumes`` count
        swap-outs and re-admissions, ``swap_blocks_out/in`` and
        ``swap_bytes_out`` the block traffic through the host-RAM
        tier (reason-label-summed: preemption AND prefix-cache
        demotion/promotion traffic), ``swap_host_blocks``/
        ``swapped_waiting`` the preempt half's CURRENT footprint and
        ``host_cache_blocks`` the cache half's, and ``shed``/
        ``timeouts`` the requests the bounded queue and the
        queue-delay SLO dropped (label-summed; ``cancelled`` likewise
        sums its per-phase label).  The tiered-prefix-cache keys:
        ``prefix_hit_tokens`` is token-granular served-from-cache
        volume (mapped blocks x block_len), ``prefix_partial_hits``
        counts admissions whose token-level match ran past the last
        mappable block, ``prefix_host_hits``/``host_swapin_blocks``
        the hits served by exact-bytes host->HBM swap-in.
        The goodput-ledger keys: ``useful_tokens`` + ``wasted_tokens``
        == ``dispatched_tokens`` EXACTLY (conservation by construction
        of ``_ledger``), ``goodput`` is the useful fraction and
        ``wasted_by_reason`` the per-reason breakdown over the closed
        ``GOODPUT_REASONS`` vocabulary.  ``mean_tpot_s`` is the mean
        per-output-token decode latency over finished requests with
        >= 2 tokens (None while that set is empty);
        ``slo_attained``/``slo_missed`` are class-label-summed SLO
        outcomes over requests that carried a deadline or queue-delay
        bound."""
        decode_steps = self._m.since_init(self._m.decode_steps)
        busy = self._m.since_init(self._m.busy_slot_steps)
        occ = (busy / (decode_steps * self.num_slots)
               if decode_steps else 0.0)
        hits = self._m.since_init(self._m.prefix_hits)
        misses = self._m.since_init(self._m.prefix_misses)
        lats = [r.latency for r in self._finished
                if r.latency is not None]
        ttfts = [r.ttft for r in self._finished if r.ttft is not None]
        verifies = self._m.since_init(self._m.spec_verifies)
        drafted = self._m.since_init(self._m.spec_draft_tokens)
        accepted = self._m.since_init(self._m.spec_accepted_tokens)
        useful = int(self._m.since_init(self._m.goodput_useful))
        wasted = int(self._m.since_init(self._m.goodput_wasted))
        dispatched = int(self._m.since_init(self._m.goodput_dispatched))
        tpots = [(r.finish_time - r.first_token_time) / (r.n_emitted - 1)
                 for r in self._finished
                 if r.state == "finished" and r.first_token_time is not None
                 and r.finish_time is not None and r.n_emitted > 1]
        return {
            "num_slots": self.num_slots,
            "kv_cache_dtype": self.kv_cache_dtype,
            "kv_bytes_swept": int(
                self._m.since_init(self._m.kv_bytes_swept)),
            "weight_dtype": self.weight_dtype,
            "weight_bytes_swept": int(
                self._m.since_init(self._m.weights_bytes_swept)),
            "decode_steps": int(decode_steps),
            "busy_slot_steps": int(busy),
            "block_dispatches": int(
                self._m.since_init(self._m.block_dispatches)),
            "prefills": int(self._m.since_init(self._m.prefills)),
            "first_token_fetches": int(
                self._m.since_init(self._m.first_token_fetches)),
            "admits_ahead": int(self._m.since_init(self._m.admits_ahead)),
            "prefill_chunks": int(
                self._m.since_init(self._m.prefill_chunks)),
            "mean_slot_occupancy": occ,
            "peak_queue": self._peak_queue,
            "finished": int(
                self._m.since_init(self._m.requests_finished)),
            "cancelled": int(
                self._m.since_init(self._m.requests_cancelled)),
            "block_len": self.block_len,
            "num_blocks": self.num_blocks,
            "blocks_in_use": self._pool.in_use(),
            "peak_blocks_in_use": self._peak_blocks,
            "prefix_cached_blocks": self._pool.cached(),
            "prefix_hits": int(hits),
            "prefix_misses": int(misses),
            "prefix_hit_rate": (hits / (hits + misses)
                                if hits + misses else 0.0),
            "prefix_hit_tokens": int(
                self._m.since_init(self._m.prefix_hit_tokens)),
            "prefix_partial_hits": int(
                self._m.since_init(self._m.prefix_partial_hits)),
            "prefix_host_hits": int(
                self._m.since_init(self._m.prefix_host_hits)),
            "host_swapin_blocks": int(
                self._m.since_init(self._m.prefix_host_swapin)),
            "mean_latency_s": (sum(lats) / len(lats)) if lats else None,
            "mean_ttft_s": (sum(ttfts) / len(ttfts)) if ttfts else None,
            "spec_verify_steps": int(verifies),
            "spec_draft_hits": int(
                self._m.since_init(self._m.spec_draft_hits)),
            "spec_draft_misses": int(
                self._m.since_init(self._m.spec_draft_misses)),
            "spec_draft_tokens": int(drafted),
            "spec_accepted_tokens": int(accepted),
            "spec_acceptance_rate": (accepted / drafted
                                     if drafted else 0.0),
            "spec_mean_accepted_len": (accepted / verifies
                                       if verifies else 0.0),
            "sampled_tokens": int(
                self._m.since_init(self._m.sample_sampled_tokens)),
            "greedy_tokens": int(
                self._m.since_init(self._m.sample_greedy_tokens)),
            "masked_tokens": int(
                self._m.since_init(self._m.sample_masked_tokens)),
            "sample_resamples": int(
                self._m.since_init(self._m.sample_resamples)),
            "preemptions": int(self._m.since_init(self._m.preempts)),
            "preempt_resumes": int(
                self._m.since_init(self._m.preempt_resumes)),
            "swap_blocks_out": int(
                self._m.since_init(self._m.swap_out_blocks)),
            "swap_blocks_in": int(
                self._m.since_init(self._m.swap_in_blocks)),
            "swap_bytes_out": int(
                self._m.since_init(self._m.swap_out_bytes)),
            "swap_bytes_in": int(
                self._m.since_init(self._m.swap_in_bytes)),
            "swap_host_blocks": self._host_tier.blocks("preempt"),
            "host_cache_blocks": self._host_tier.blocks("cache"),
            "swapped_waiting": len(self._swapped),
            "shed": int(self._m.since_init(self._m.shed)),
            "timeouts": int(self._m.since_init(self._m.timeouts)),
            "useful_tokens": useful,
            "wasted_tokens": wasted,
            "dispatched_tokens": dispatched,
            "goodput": (useful / dispatched if dispatched else 0.0),
            "wasted_by_reason": dict(self._wasted_reason),
            # the goodput ledger's handoff lane: requests that left
            # this (prefill-role) engine at chunk-final with their KV
            # parcel instead of decoding in place.  Deliberately NOT a
            # wasted_by_reason entry — a handoff moves exact bytes and
            # recomputes nothing, and these counters are the proof
            # (zero on every "both"/"decode" engine)
            "handoffs": int(
                self._m.since_init(self._m.handoff_requests)),
            "handoff_blocks": int(
                self._m.since_init(self._m.handoff_blocks)),
            "handoff_bytes": int(
                self._m.since_init(self._m.handoff_bytes)),
            "role": self.role,
            # the second kind of state (slot_state_spec models): the bytes
            # of the per-slot state arenas, and whether the prefix cache
            # that was asked for is off because a hit cannot carry them
            "slot_state_bytes": self._slot_state_bytes,
            "kv_arena_bytes": sum(int(a.nbytes) for a in self._arenas),
            "prefix_cache_disabled": self.prefix_cache_disabled,
            "mean_tpot_s": (sum(tpots) / len(tpots)) if tpots else None,
            "slo_attained": int(
                self._m.since_init(self._m.slo_attained)),
            "slo_missed": int(self._m.since_init(self._m.slo_missed)),
            # multi-tenant fair share + batched LoRA: the per-tenant
            # service ledger the deficit-WRR orders by (tokens charged
            # at admission), the count of admissions where fairness
            # overrode plain FIFO, and the gathered-einsum dispatch
            # count (the LoRA-vs-base route split)
            "tenant_served_tokens": dict(self._tenant_served),
            "fair_reorders": int(
                self._m.since_init(self._m.fairshare_reorders)),
            "lora_dispatches": self._lora_dispatches,
            "adapters_resident": (
                None if self._adapters is None else sum(
                    1 for name in self._adapters.names()
                    if self._adapters.resident(name))),
            # dispatch-ahead pipeline: forced early harvests by closed
            # reason vocabulary vs harvests that completed AFTER the
            # next dispatch was enqueued (the overlap wins).  While a
            # harvest is in flight the output-dependent counters above
            # (ledger, kv_bytes_swept) lag by at most one dispatch;
            # run() always returns with the pipeline flushed.
            "async_dispatch": self.async_dispatch,
            "async_depth": self.async_depth,
            "async_syncs": int(self._m.since_init(self._m.async_syncs)),
            "async_harvests": int(
                self._m.since_init(self._m.async_harvests)),
            "async_syncs_by_reason": {
                reason: int(self._m.syncs_since(reason))
                for reason in ASYNC_SYNC_REASONS},
        }

    def load_report(self) -> dict:
        """One host-side load/residency snapshot for schedulers ABOVE
        the engine (the router's load signal and affinity probes; a
        future external scheduler reads the same dict instead of
        scraping gauges).  Pure host state — no dispatch, no pending-
        harvest flush — so polling it every routing decision is free:

        - ``queue_depth`` / ``active_slots`` / ``prefilling`` /
          ``swapped_waiting``: outstanding work by phase (active_slots
          counts occupied slots, prefilling rows included);
        - ``slots_total`` / ``blocks_free`` / ``blocks_in_use`` /
          ``blocks_total`` / ``block_len``: capacity headroom
          (blocks_free counts free + reclaimable-cached, the pool's
          ``available()`` convention);
        - ``hbm_adapters``: adapter names resident in the HBM arena
          right now (``[]`` without an AdapterStore) — the adapter-
          affinity signal;
        - ``radix``: the prefix tree's root stats (hbm/host block
          counts + root fanout; ``None`` off radix mode) — tree SIZE
          only; a router scores prefix affinity by calling
          ``prefix_match()`` per prompt;
        - ``kv_cache_dtype``: the at-rest cache dtype (replica
          homogeneity check)."""
        return {
            "queue_depth": len(self._queue),
            "active_slots": sum(r is not None for r in self._slots),
            "prefilling": len(self._prefilling),
            "swapped_waiting": len(self._swapped),
            "slots_total": self.num_slots,
            "blocks_free": self._pool.available(),
            "blocks_in_use": self._pool.in_use(),
            "blocks_total": self.num_blocks,
            "block_len": self.block_len,
            "hbm_adapters": (self._adapters.hbm_resident()
                             if self._adapters is not None else []),
            "radix": (self._radix.root_stats()
                      if self._radix is not None else None),
            "kv_cache_dtype": self.kv_cache_dtype,
            "weight_dtype": self.weight_dtype,
            # shard-group identity (PR 18): None for single-chip
            # engines; a mesh engine reports its tensor-parallel
            # geometry so the router's fleet_snapshot()/stats() carry
            # which shard group served what without a second probe
            "shard_group": self.shard_group,
            # disaggregation role (ROADMAP item 2): the router's
            # phase-routing key — "prefill"/"both" replicas take
            # fresh arrivals, "decode"/"both" take handoff parcels
            "role": self.role,
        }

    def engine_spec(self) -> dict:
        """The engine's IMMUTABLE identity as one JSON-safe dict —
        what a wire handshake advertises (PR 19's ``welcome`` frame)
        and what the router's replica-homogeneity validation reads:
        geometry (``prompt_len`` / ``max_cache_len`` / ``block_len``
        / ``num_blocks`` / ``num_slots`` / ``chunk_len``), at-rest
        dtypes, the pad token, the per-block KV row stride the
        migration byte accounting multiplies by, registered adapter
        names (``None`` without an AdapterStore — "no store" and
        "empty store" route differently) and the shard-group
        identity.  Pure host attrs, free to call."""
        return {
            "prompt_len": self.prompt_len,
            "max_cache_len": self.max_cache_len,
            "block_len": self.block_len,
            "num_blocks": self.num_blocks,
            "num_slots": self.num_slots,
            "chunk_len": self.chunk_len,
            "kv_cache_dtype": self.kv_cache_dtype,
            "weight_dtype": self.weight_dtype,
            "pad_token_id": int(self.cfg.pad_token_id),
            "kv_row_bytes": int(self._kv_row_bytes),
            "kv_layout": "latent" if self._kv_latent else "kv",
            "slot_state_bytes": int(self._slot_state_bytes),
            "adapters": (None if self._adapters is None
                         else list(self._adapters.names())),
            "shard_group": self.shard_group,
            # disaggregation role: rides the PR-19 welcome frame so a
            # multi-process fleet phase-routes exactly like a local one
            "role": self.role,
        }

    def prefix_match(self, prompt_ids) -> int:
        """Token-granular longest-prefix match of ``prompt_ids``
        against THIS engine's prefix index (0 off radix mode) —
        read-only (no pin, no LRU touch): the router's prefix-affinity
        probe.  The admission-time re-probe still decides what
        actually maps."""
        if self._radix is None:
            return 0
        ids = np.asarray(getattr(prompt_ids, "_value", prompt_ids))
        ids = np.asarray(ids).reshape(-1).astype(np.int32)
        matched, _span = self._radix.match(ids)
        return int(matched)

    @property
    def metrics_registry(self):
        """The MetricsRegistry this engine records into (the process
        default unless one was passed at construction)."""
        return self._m.registry

    @property
    def flight_recorder(self) -> FlightRecorder:
        """The per-request flight recorder (a disabled default unless
        one was passed at construction — ``.enable()`` flips it live)."""
        return self._fr

    def explain(self, request_id: int) -> str:
        """Human-readable lifecycle of one request, from the flight
        recorder ("waited 3 steps behind req 7, preempted at step 12,
        resumed via 6 host blocks ...")."""
        return self._fr.explain(request_id)
