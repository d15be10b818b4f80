"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two hot paths once, through the entry points a user calls, at
the full width of the repo's 1.1B Llama configuration (hidden 2048,
intermediate 8192, 16 layers, 32 query / 8 KV heads, vocab 32000, bf16;
weights random from a seed):

1. serving — ``ServingEngine.submit()/run()`` over mixed-length requests
   in five variants (bf16, int8 KV, speculative, int8 and int4 weights),
   each checked on the route counters: every decision is the one named
   here, and none is ``pallas_unavailable``;
2. agreement — logits of the paged path (chunk prefill, K-wide verify,
   decode, all through the arenas with the kernels routed) against the
   model's plain full forward in float32 at ``highest`` precision, and
   of the int8 KV cache against the float one;
3. moe_experts — the routed-expert layer's grouped matmul
   (``ops/pallas/grouped_matmul.py``) at the two shapes of the sparse
   serving cell, through its gate, against ``jax.lax.ragged_dot``:
   results and gradients;
4. training — ``TrainStep`` at b8 x s2048 with the fused AdamW kernel:
   loss finite and falling, ``tpu_custom_call`` in the lowered step;
5. with four or more devices, both paths sharded and one engine per chip.

It needs a TPU and says so at once when there is none; there is no CPU
mode and no size switch.  One process holds the chip from start to end:
nothing here starts a child.  The phases are functions of a model config
and a ``Sizes`` so that ``tests/test_chip_smoke.py`` can walk the control
flow at ``tiny_llama_config()`` on the CPU.  Nothing printed here is a
benchmark result.

Run: ``python chip_smoke.py`` (on the chip: ``chiprun -- python3 chip_smoke.py``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import sys
import time

import numpy as np

# Agreement bands, as max|dlogit| over the RMS of the reference logits,
# the max taken over 3 sequences x 7 positions x 32000 logits.
#
# PAGED_TOL: the paged path computes in bf16 (2^-9 relative rounding on
# every stored activation) through 16 layers of residual adds; the
# reference is float32 at ``highest`` on the same bf16-valued weights.
# Measured 0.042 on the v5e, the same in both runs (chip run, PR 22): a
# per-logit noise under 1 % of RMS, seen ~5 sigma out among 672k logits,
# with every argmax agreeing.  0.10 leaves a factor of 2.4; a wrong mask,
# a mis-routed block or a stale scratch row moves logits by the order of
# the RMS itself.
PAGED_TOL = 0.10
# KV_INT8_TOL: int8 KV against the float paged path, the same programs
# otherwise.  Per-entry absmax codes put a rounding of 2^-8 of each
# head's largest value on every K and V element; measured 0.063 on the
# v5e (chip run, PR 22).  0.15 leaves the same factor.
KV_INT8_TOL = 0.15
# SHARDED_TOL: mp=4 against one chip.  The partitioned program takes the
# XLA attention path, the one-chip program the Pallas kernels: two bf16
# computations, each within PAGED_TOL of the float32 forward.  Measured
# 0.047 on the four-chip host (chip run, PR 22).
SHARDED_TOL = 0.10
# MOE_TOL: the grouped-matmul kernel against ``jax.lax.ragged_dot``, both
# bf16 roundings of a float32 accumulation over the whole of K; as
# max|d| over the RMS of ragged_dot's result.  Measured 0.0 on the v5e at
# both shapes (chip run, PR 34: the same bits); one bf16 step on the
# largest entry, about five times the RMS, would read 0.02.
MOE_TOL = 0.02
# rows, K, N of the sparse serving cell's two grouped matmuls (w1/w3 and
# w2 of LFM2-24B-A2B at 256 slots x 4 experts a token), and its experts
MOE_SHAPES = ((1024, 2048, 1536), (1024, 1536, 2048))
MOE_GROUPS = 64


class SmokeFailure(AssertionError):
    """A phase produced a wrong result."""


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Engine and batch geometry of one smoke run."""
    num_slots: int
    prompt_len: int
    max_cache_len: int
    steps_per_call: int
    block_len: int
    spec_k: int
    train_batch: int
    train_seq: int
    train_steps: int
    dtype: str


# the 1.1B configuration's serving geometry and training batch
FULL = Sizes(num_slots=8, prompt_len=128, max_cache_len=1024,
             steps_per_call=8, block_len=16, spec_k=4, train_batch=8,
             train_seq=2048, train_steps=4, dtype="bfloat16")


def full_config(**kw):
    from paddle_tpu.models import LlamaConfig
    return LlamaConfig(vocab_size=32000, hidden_size=2048,
                       intermediate_size=8192, num_hidden_layers=16,
                       num_attention_heads=32, num_key_value_heads=8, **kw)


# ---------------------------------------------------------------------------
# clocks and counters
# ---------------------------------------------------------------------------

class CompileClock:
    """Seconds XLA spent compiling programs (or fetching them from the
    persistent cache), and the cache's hits and misses, read from
    ``jax.monitoring``.  Tracing and lowering are host work that a warm
    cache does not remove; they stay in a phase's run seconds."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._evt)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _evt(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._dur)
        jax.monitoring.unregister_event_listener(self._evt)


def _bytes_in_use():
    """Per device; None where the backend keeps no statistics (CPU)."""
    import jax
    return [(d.memory_stats() or {}).get("bytes_in_use")
            for d in jax.devices()]


_ROUTES = ("pallas.decode_attention.route", "pallas.quantized_matmul.route",
           "pallas.moe_experts.route")


def _route_snapshot():
    from paddle_tpu.observability.metrics import get_registry
    snap = get_registry().snapshot()
    return {n: dict(snap.get(n, {}).get("values", {})) for n in _ROUTES}


def _route_delta(before):
    after = _route_snapshot()
    out = {}
    for name in _ROUTES:
        short = name.split(".")[1]
        for key, v in after[name].items():
            d = v - before[name].get(key, 0)
            if d:
                out[f"{short}:{key}"] = int(d)
    return out


def require_routes(routes, expected):
    """Fail unless the route decisions taken are exactly the
    ``expected`` ``counter:decision:reason`` set: each of them at least
    once and no other.  An unexpected XLA fallback at the smoke's own
    geometry — ``pallas_unavailable`` above all — is what this script
    exists to catch; an expected one names the gate rule the chip
    taught (``int8_scale_lanes``)."""
    want = set()
    for entry in expected:
        short, decision, reason = entry.split(":")
        want.add(f"{short}:decision={decision},reason={reason}")
    if set(routes) != want:
        raise SmokeFailure(
            f"route decisions {sorted(routes)} != expected {sorted(want)}")


@contextlib.contextmanager
def phase(name, clock, report):
    """Time one phase, split into compile and run seconds, and print its
    line.  An exception passes through: a failed phase ends the run."""
    import jax
    c0, h0, m0 = clock.seconds, clock.hits, clock.misses
    t0 = time.perf_counter()
    entry = {}
    yield entry
    wall = time.perf_counter() - t0
    comp = clock.seconds - c0
    stats = jax.devices()[0].memory_stats() or {}
    entry.update(compile_s=round(comp, 2), run_s=round(wall - comp, 2),
                 cache_hits=clock.hits - h0, cache_misses=clock.misses - m0,
                 peak_bytes_in_use=stats.get("peak_bytes_in_use"))
    report[name] = entry
    print(f"[{name}] " + json.dumps(entry, sort_keys=True), flush=True)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def build_model(cfg, dtype, seed=0):
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM
    paddle.seed(seed)
    model = LlamaForCausalLM(cfg)
    model.eval()
    if dtype != "float32":
        model.to(dtype=dtype)
    return model


def make_requests(vocab, sizes, seed=0):
    """Two waves of (prompt ids, max_new_tokens, spec_decode).  Wave 2
    opens with a prompt sharing its first half with wave 1's last one,
    so its admission finds those blocks in the prefix cache whatever the
    scheduler did in wave 1.  The spec prompt is a tiled pattern."""
    rng = np.random.default_rng(seed)
    p = sizes.prompt_len
    # one token from the prefill plus whole decode blocks: 17..57 at
    # eight steps a call.  Every decode dispatch is then the one
    # steps_per_call program; a tail would compile the one-step program
    # too, a third more compiling for no kernel this run has not seen
    news = sizes.steps_per_call * np.array([4, 7, 2, 5, 3, 6, 4]) + 1
    shared = rng.integers(0, vocab, p // 2)

    def prompt(n, prefix=None):
        ids = rng.integers(0, vocab, n).astype(np.int32)
        if prefix is not None:
            ids[:prefix.size] = prefix
        return ids

    pattern = np.tile(rng.integers(0, vocab, 8), p)[:p - 3].astype(np.int32)
    wave1 = [(prompt(p), news[0], None), (prompt(p // 4), news[1], None),
             (prompt(3 * p // 4), news[2], None),
             (pattern, news[3], sizes.spec_k),
             (prompt(p - 5, shared), news[4], None)]
    wave2 = [(prompt(p // 2 + p // 4, shared), news[5], None),
             (prompt(p // 3), news[6], None)]
    return wave1, wave2


class _PatternDrafter:
    """Prompt-lookup drafting that never comes back empty: with random
    weights the stream leaves the prompt's vocabulary at once and
    n-gram lookup finds nothing, so the verify program would never be
    dispatched.  Wrong drafts are the verifier's normal case."""

    def __init__(self):
        from paddle_tpu.inference.speculative import NGramDrafter
        self._ngram = NGramDrafter()

    def propose(self, context, k):
        got = self._ngram.propose(context, k)
        if got.size:
            return got
        ctx = np.asarray(context).reshape(-1).astype(np.int32)
        return ctx[:k]


def serve_variant(model, sizes, waves, *, spec=False, **engine_kw):
    """One fresh engine on ``model``'s weights: submit both waves, drain,
    check every request.  Returns counts, stats and the route deltas."""
    from paddle_tpu.inference.serving import ServingEngine
    before = _route_snapshot()
    eng = ServingEngine(
        model, num_slots=sizes.num_slots, prompt_len=sizes.prompt_len,
        max_cache_len=sizes.max_cache_len,
        steps_per_call=sizes.steps_per_call, compute_dtype=sizes.dtype,
        block_len=sizes.block_len,
        drafter=_PatternDrafter() if spec else None, **engine_kw)
    vocab = int(model.config.vocab_size)
    done = []
    for wave in waves:
        for ids, new, spec_k in wave:
            eng.submit(ids, max_new_tokens=int(new),
                       spec_decode=spec_k if spec else None)
        done += eng.run(wall_timeout_s=900.0)
    want = [int(new) for wave in waves for _, new, _ in wave]
    if len(done) != len(want):
        raise SmokeFailure(f"{len(done)} of {len(want)} requests returned")
    for req, new in zip(done, want):
        out = req.output
        if req.state != "finished" or out.shape != (new,) \
                or out.min() < 0 or out.max() >= vocab:
            raise SmokeFailure(
                f"request {req.request_id}: state={req.state} "
                f"tokens={out.shape} want ({new},) in [0, {vocab})")
    st = eng.stats()
    if st["prefix_hit_tokens"] < 1:
        raise SmokeFailure("wave 2's shared prefix missed the prefix cache")
    if spec and st["spec_verify_steps"] < 1:
        raise SmokeFailure("no verify forward was dispatched")
    arena = eng._arenas[0]
    return {"requests": len(done), "tokens": int(sum(want)),
            "block_dispatches": st["block_dispatches"],
            "prefill_chunks": st["prefill_chunks"],
            "prefix_hit_tokens": st["prefix_hit_tokens"],
            "spec_verify_steps": st["spec_verify_steps"],
            # where the arenas and weights really are, read while the
            # engine still holds them
            "devices": sorted(d.id for d in arena.devices()),
            "shard_width": arena.shape[2]       # the kv-head axis
            // arena.addressable_shards[0].data.shape[2],
            "weight_devices": sorted(d.id for d in eng._pb[0].devices()),
            "bytes_in_use": _bytes_in_use(),
            "routes": _route_delta(before),
            "first_tokens": [int(r.output[0]) for r in done]}


# ---------------------------------------------------------------------------
# agreement
# ---------------------------------------------------------------------------

def paged_logits(model, sizes, ids, n0, *, kv_int8=False, mesh=None):
    """Teacher-forced logits of ``ids`` [B, T] through the paged path:
    each row's first ``n0[b]`` tokens by chunk prefill, the next
    ``spec_k + 1`` by one K-wide verify forward, one more by a decode
    step — the three programs a request passes through, over one shared
    arena with shuffled block tables at the engine's own geometry.
    Returns float32 [B, spec_k + 3, vocab] for positions ``n0 - 1 ..
    n0 + spec_k + 1`` of each row."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.inference.llm import (ArenaSharding, _constrain_arenas,
                                          _flatten_paged_kvs,
                                          _pack_paged_kvs, _param_swapper,
                                          _shard_scope)
    from paddle_tpu.models.generation import (GenerationConfig,
                                              init_paged_kv_arena,
                                              model_arrays)
    b, t = ids.shape
    c = sizes.spec_k + 1
    blk = sizes.block_len
    n_layers, hkv, d = model.kv_cache_spec()
    mb = -(-sizes.max_cache_len // blk)
    adt = jnp.int8 if kv_int8 else jnp.dtype(sizes.dtype)
    flat = [a for entry in init_paged_kv_arena(n_layers, b * mb, blk, hkv,
                                               d, adt) for a in entry]
    params, buffers = model_arrays(model)
    pb = [p._value for p in params] + [bf._value for bf in buffers]
    shard = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        shard = ArenaSharding(kv=NamedSharding(mesh, P(None, None, "model")),
                              n_shards=int(mesh.shape["model"]))
        flat = [jax.device_put(a, shard.kv) for a in flat]
        pb = [jax.device_put(v, NamedSharding(mesh, P())) for v in pb]
    tables = jnp.asarray(np.random.default_rng(1).permutation(b * mb)
                         .reshape(b, mb).astype(np.int32))
    swap = _param_swapper(model, GenerationConfig(compute_dtype=sizes.dtype))

    def wrap(step):
        def pure(pb_values, tbl, *args_and_arenas):
            n_arg = len(args_and_arenas) - len(flat)
            args, arenas = args_and_arenas[:n_arg], args_and_arenas[n_arg:]

            def run():
                kvs = _pack_paged_kvs(_constrain_arenas(arenas, shard),
                                      tbl, kv_int8)
                with _shard_scope(shard):
                    logits, kvs_f = step(*args, kvs)
                return (logits.astype(jnp.float32),) + tuple(
                    _constrain_arenas(_flatten_paged_kvs(kvs_f), shard))
            return swap(pb_values, run)
        return jax.jit(pure)

    chunk = wrap(lambda row, n, kvs: model.prefill_chunk(
        row, jnp.int32(0), n, kvs))
    verify = wrap(lambda toks, lens, kvs: model.verify_step(
        toks, lens, jnp.full((b,), c, jnp.int32), kvs))
    decode = wrap(model.decode_step)

    ids_d = jnp.asarray(ids)
    n0_d = jnp.asarray(n0, jnp.int32)
    first = []
    for r in range(b):
        out = chunk(pb, tables[r:r + 1],
                    ids_d[r:r + 1, :sizes.prompt_len], n0_d[r], *flat)
        first.append(out[0])
        flat = list(out[1:])
    rows = jnp.arange(b)[:, None]
    toks = ids_d[rows, n0_d[:, None] + jnp.arange(c)[None, :]]
    out = verify(pb, tables, toks, n0_d, *flat)
    mid, flat = out[0], list(out[1:])
    out = decode(pb, tables, ids_d[jnp.arange(b), n0_d + c], n0_d + c, *flat)
    return np.concatenate([np.stack([np.asarray(f[0]) for f in first])[:, None],
                           np.asarray(mid), np.asarray(out[0])[:, None]],
                          axis=1)


def reference_logits(model, ids, n0, c):
    """The model's plain full forward on the same ids: float32
    activations on the model's own weight values, ``highest`` matmul
    precision, no Pallas kernel anywhere.  Returns [B, c + 2, vocab] at
    positions ``n0 - 1 .. n0 + c``."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models.generation import model_arrays, swap_call
    params, buffers = model_arrays(model)

    def pure(p_values, b_values, toks):
        return swap_call(params, buffers, p_values, b_values, "float32",
                         lambda: model(Tensor(toks))._value)

    saved = paddle.get_flags("FLAGS_prefer_pallas_kernels")
    paddle.set_flags({"FLAGS_prefer_pallas_kernels": False})
    try:
        with jax.default_matmul_precision("highest"):
            full = jax.jit(pure)([p._value for p in params],
                                 [bf._value for bf in buffers],
                                 jnp.asarray(ids))
    finally:
        paddle.set_flags(saved)
    pos = np.asarray(n0)[:, None] - 1 + np.arange(c + 2)[None, :]
    return np.asarray(full, np.float32)[np.arange(ids.shape[0])[:, None],
                                        pos]


def rel_err(got, ref):
    return float(np.max(np.abs(got - ref)) / np.sqrt(np.mean(ref ** 2)))


def agreement_inputs(vocab, sizes, seed=2):
    """A few sequences of mixed prompt length, long enough for the
    verify span and one decode step; the total length is kept off the
    128 grid so the reference's attention is XLA's."""
    rng = np.random.default_rng(seed)
    p = sizes.prompt_len
    n0 = np.asarray([p, p // 3 + 1, 3 * p // 4 - 1], np.int32)
    t = p + sizes.spec_k + 3
    return rng.integers(0, vocab, (n0.size, t)).astype(np.int32), n0


def agreement(model, sizes):
    """Paged float path against the full forward, and paged int8-KV
    against paged float.  Returns the two relative errors and the route
    deltas of each paged run."""
    ids, n0 = agreement_inputs(int(model.config.vocab_size), sizes)
    ref = reference_logits(model, ids, n0, sizes.spec_k + 1)
    before = _route_snapshot()
    flt = paged_logits(model, sizes, ids, n0)
    routes_f = _route_delta(before)
    before = _route_snapshot()
    q = paged_logits(model, sizes, ids, n0, kv_int8=True)
    routes_q = _route_delta(before)
    if not (np.isfinite(flt).all() and np.isfinite(q).all()):
        raise SmokeFailure("non-finite logits on the paged path")
    return {"paged_vs_full": rel_err(flt, ref),
            "kv_int8_vs_paged": rel_err(q, flt),
            "argmax_agree": float((flt.argmax(-1) == ref.argmax(-1)).mean()),
            "logit_rms": float(np.sqrt(np.mean(ref ** 2))),
            "routes_float": routes_f, "routes_int8": routes_q,
            "paged_float": flt}


# ---------------------------------------------------------------------------
# the expert layer's grouped matmul
# ---------------------------------------------------------------------------

def moe_experts(shapes, groups, seed=3):
    """``grouped_matmul`` through its gate at each (rows, K, N), against
    ``jax.lax.ragged_dot`` on the same operands: three groups in four get
    rows, of unequal sizes, as the serving cell's do.  Returns the largest
    relative error of the results and of the gradients, and the route
    deltas."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul
    rng = np.random.default_rng(seed)
    before = _route_snapshot()
    errs, grad_errs = [], []
    for m, k, n in shapes:
        share = rng.random(groups) * (rng.random(groups) < 0.75)
        share[0] += 1e-3
        sizes = np.floor(share / share.sum() * m).astype(np.int32)
        sizes[0] += m - sizes.sum()
        xs = jnp.asarray(rng.standard_normal((m, k), np.float32),
                         jnp.bfloat16)
        w = jnp.asarray(rng.standard_normal((groups, k, n), np.float32)
                        * 0.02, jnp.bfloat16)
        sz = jnp.asarray(sizes)
        got = jax.jit(grouped_matmul)(xs, w, sz)
        ref = jax.jit(jax.lax.ragged_dot)(xs, w, sz)
        got, ref = (np.asarray(a, np.float32) for a in (got, ref))
        if not np.isfinite(got).all():
            raise SmokeFailure("non-finite grouped matmul")
        errs.append(rel_err(got, ref))
        # the layer is differentiable through either body
        pull = jnp.asarray(rng.standard_normal((m, n), np.float32))

        def grads(body):
            return jax.jit(jax.grad(
                lambda a, b: jnp.sum(body(a, b, sz) * pull), (0, 1)))(xs, w)
        for a, b in zip(grads(grouped_matmul), grads(jax.lax.ragged_dot)):
            grad_errs.append(rel_err(np.asarray(a, np.float32),
                                     np.asarray(b, np.float32)))
    return {"vs_ragged_dot": max(errs), "grad_vs_ragged_dot": max(grad_errs),
            "routes": _route_delta(before)}


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def train(cfg, sizes, *, fleet_mp=0, seed=0):
    """``TrainStep`` for ``sizes.train_steps`` steps on one repeated
    batch with ``FLAGS_use_fused_adamw_kernel`` on.  ``fleet_mp > 0``
    runs it under ``fleet`` with that model-parallel degree and the
    remaining devices data-parallel, and first computes the same
    weights' loss on one chip.  Returns the losses, whether the lowered
    step holds a ``tpu_custom_call``, and each device's bytes in use."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.distributed.topology import (get_global_mesh,
                                                 set_global_mesh)
    from paddle_tpu.jit.train_step import TrainStep
    from paddle_tpu.models import (LlamaForCausalLM,
                                   LlamaPretrainingCriterion)

    criterion = LlamaPretrainingCriterion(cfg)

    def loss_fn(net, tokens, labels):
        if cfg.fused_linear_loss:
            return net(tokens, labels=labels)[0]
        return criterion(net(tokens), labels)

    rng = np.random.default_rng(seed)
    shape = (sizes.train_batch, sizes.train_seq)
    tokens = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, shape).astype(np.int32))
    labels = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, shape).astype(np.int32))

    saved_mesh = get_global_mesh()
    paddle.set_flags({"FLAGS_use_fused_adamw_kernel": True})
    try:
        if fleet_mp:
            from paddle_tpu.distributed import fleet
            from paddle_tpu.distributed.fleet.fleet_base import \
                DistributedStrategy
            strategy = DistributedStrategy()
            strategy.hybrid_configs = {
                "dp_degree": len(jax.devices()) // fleet_mp,
                "mp_degree": fleet_mp, "pp_degree": 1,
                "sharding_degree": 1, "sep_degree": 1}
            fleet.init(is_collective=True, strategy=strategy)
            if get_global_mesh() is None:
                raise SmokeFailure("fleet.init built no mesh")
        paddle.seed(seed)
        model = LlamaForCausalLM(cfg)
        model.train()
        if sizes.dtype != "float32":
            model.to(dtype=sizes.dtype)
        one_chip_loss = None
        if fleet_mp:
            # the same weights' forward loss on device 0 alone, before
            # any update: what the mesh's first step must reproduce
            from paddle_tpu.core.tensor import Tensor
            from paddle_tpu.models.generation import (model_arrays,
                                                      swap_call)
            mesh = get_global_mesh()
            params, buffers = model_arrays(model)
            dev0 = jax.devices()[0]

            def forward_loss(p_values, b_values, tok, lab):
                return swap_call(
                    params, buffers, p_values, b_values, sizes.dtype,
                    lambda: loss_fn(model, Tensor(tok), Tensor(lab))._value)

            set_global_mesh(None)
            try:
                one_chip_loss = float(jax.jit(forward_loss)(
                    [jax.device_put(p._value, dev0) for p in params],
                    [jax.device_put(b._value, dev0) for b in buffers],
                    jax.device_put(tokens._value, dev0),
                    jax.device_put(labels._value, dev0)))
            finally:
                set_global_mesh(mesh)
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=model.parameters(),
                                     multi_precision=False)
        step = TrainStep(model, loss_fn, opt)
        losses = [float(step(tokens, labels))
                  for _ in range(sizes.train_steps)]
        lowered = step._compiled.lower(
            [p._value for p in step._params], step._state, step._gm_state,
            jax.random.PRNGKey(0), jnp.float32(1e-4),
            [b._value for b in step._buffers],
            step._shard_batch(tokens), step._shard_batch(labels))
        in_hlo = "tpu_custom_call" in lowered.as_text()
        bytes_in_use = _bytes_in_use()
    finally:
        paddle.set_flags({"FLAGS_use_fused_adamw_kernel": False})
        set_global_mesh(saved_mesh)
    if not np.isfinite(losses).all() or not losses[-1] < losses[0]:
        raise SmokeFailure(f"loss not finite and falling: {losses}")
    return {"losses": [round(x, 4) for x in losses],
            "one_chip_loss": one_chip_loss, "tpu_custom_call": in_hlo,
            "bytes_in_use": bytes_in_use}


# ---------------------------------------------------------------------------
# several chips
# ---------------------------------------------------------------------------

def _spread(bytes_in_use, what):
    """Fail when one device holds it all."""
    if None in bytes_in_use:
        return                      # the CPU backend reports no statistics
    lo, hi = min(bytes_in_use), max(bytes_in_use)
    if lo < 0.5 * hi:
        raise SmokeFailure(
            f"{what} not spread over the devices: bytes_in_use="
            f"{bytes_in_use}")


def multichip_serving(model, sizes, waves, one_chip_logits):
    """The serving path over four chips: one engine tensor-parallel over
    ``build_mesh(mp=4)`` on the same requests, its paged logits against
    the one-chip paged logits, then four one-chip engines, one per
    device, in this one process."""
    import jax
    from paddle_tpu.distributed.topology import build_mesh
    devs = jax.devices()[:4]
    ids4 = sorted(d.id for d in devs)
    mesh = build_mesh(mp=4, devices=devs)
    base = _bytes_in_use()
    tp = serve_variant(model, sizes, waves, mesh=mesh)
    grown = [None if a is None else a - b
             for a, b in zip(tp["bytes_in_use"], base)]
    print(f"[multichip_serving] mp=4 bytes_in_use={tp['bytes_in_use']} "
          f"grown={grown}", flush=True)
    if tp["devices"] != ids4 or tp["weight_devices"] != ids4 \
            or tp["shard_width"] != 4 \
            or any(g is not None and g <= 0 for g in grown[:4]):
        raise SmokeFailure(
            f"mp=4 engine is not spread as its sharding says: arenas on "
            f"{tp['devices']} in {tp['shard_width']} shards, weights on "
            f"{tp['weight_devices']}, bytes grown {grown}")
    ids, n0 = agreement_inputs(int(model.config.vocab_size), sizes)
    sharded = paged_logits(model, sizes, ids, n0, mesh=mesh)
    err = rel_err(sharded, one_chip_logits)
    replicas = []
    for d in devs:
        r = serve_variant(model, sizes, waves,
                          mesh=build_mesh(mp=1, devices=[d]))
        if r["devices"] != [d.id] or r["weight_devices"] != [d.id]:
            raise SmokeFailure(
                f"engine built for device {d.id} keeps its arenas on "
                f"{r['devices']} and its weights on {r['weight_devices']}")
        replicas.append(r)
    firsts = [r["first_tokens"] for r in replicas]
    if any(f != firsts[0] for f in firsts):
        raise SmokeFailure(f"one-chip replicas disagree: {firsts}")
    return {"tp_routes": tp["routes"], "tp_vs_one_chip": err,
            "tp_bytes_in_use": tp["bytes_in_use"], "tp_bytes_grown": grown,
            "replica_routes": [r["routes"] for r in replicas]}


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def result_line(device):
    """The last line of stdout, parsed by whoever runs the script: the
    keys ``ok`` and ``device`` (``platform``, ``kind``, ``count``) and
    no other.  Everything else the run has to say goes on the lines
    before it."""
    return json.dumps({"ok": True, "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


def main():
    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"chip_smoke: platform={device['platform']} "
          f"device_kind={device['kind']} devices={device['count']} "
          f"jax={jax.__version__}", flush=True)
    if device["platform"] != "tpu":
        print("chip_smoke: no TPU; this script has no CPU mode",
              file=sys.stderr)
        return 2

    from paddle_tpu import runtime
    from paddle_tpu.ops.pallas.autotune import applied_schedules
    from paddle_tpu.utils import enable_compile_cache
    cache_dir = enable_compile_cache()
    print(f"chip_smoke: compile_cache={cache_dir} "
          f"native_runtime={runtime.NATIVE_AVAILABLE}", flush=True)

    sizes = FULL
    clock = CompileClock()
    report = {}
    cfg = full_config(max_position_embeddings=4096)

    with phase("build", clock, report):
        model = build_model(cfg, sizes.dtype)
    waves = make_requests(cfg.vocab_size, sizes)

    paged, multi = "decode_attention:pallas:paged_ok", \
        "decode_attention:pallas:paged_multi_ok"
    # the v5e cannot DMA the int8 cache's 8-lane scale planes, so its
    # gate answers XLA under this reason and no other (PERF.md, PR 22)
    int8_kv = "decode_attention:xla:int8_scale_lanes"
    variants = [
        ("serve_bf16", {}, [paged]),
        ("serve_kv_int8", {"kv_cache_dtype": "int8"}, [int8_kv]),
        ("serve_spec", {"spec": True}, [paged, multi]),
        ("serve_w_int8", {"weight_dtype": "int8"},
         [paged, "quantized_matmul:pallas:int8_ok"]),
        ("serve_w_int4", {"weight_dtype": "int4"},
         [paged, "quantized_matmul:pallas:int4_ok"]),
    ]
    for name, kw, expected in variants:
        with phase(name, clock, report) as out:
            out.update(serve_variant(model, sizes, waves, **kw))
            require_routes(out["routes"], expected)

    with phase("agreement", clock, report) as out:
        agree = agreement(model, sizes)
        one_chip_logits = agree.pop("paged_float")
        out.update(agree)
        require_routes(agree["routes_float"], [paged, multi])
        require_routes(agree["routes_int8"], [int8_kv])
        if agree["paged_vs_full"] > PAGED_TOL:
            raise SmokeFailure(
                f"paged path off the full forward by "
                f"{agree['paged_vs_full']:.4f} > {PAGED_TOL}")
        if agree["kv_int8_vs_paged"] > KV_INT8_TOL:
            raise SmokeFailure(
                f"int8 KV off the float paged path by "
                f"{agree['kv_int8_vs_paged']:.4f} > {KV_INT8_TOL}")

    with phase("moe_experts", clock, report) as out:
        out.update(moe_experts(MOE_SHAPES, MOE_GROUPS))
        require_routes(out["routes"], ["moe_experts:pallas:grouped_ok"])
        for key in ("vs_ragged_dot", "grad_vs_ragged_dot"):
            if not out[key] <= MOE_TOL:
                raise SmokeFailure(
                    f"grouped matmul {key} {out[key]:.4f} > {MOE_TOL}")

    if len(devs) >= 4:
        with phase("multichip_serving", clock, report) as out:
            out.update(multichip_serving(model, sizes, waves,
                                         one_chip_logits))
            # a GSPMD-partitioned program takes no Pallas kernel
            # (ops/pallas/_common.pallas_enabled): the tensor-parallel
            # engine decodes on the XLA path and says why
            require_routes(out["tp_routes"],
                           ["decode_attention:xla:gspmd_partitioned",
                            "decode_attention:xla:sharded_ok"])
            for routes in out["replica_routes"]:
                require_routes(routes,
                               [paged, "decode_attention:xla:mesh_geom"])
            if out["tp_vs_one_chip"] > SHARDED_TOL:
                raise SmokeFailure(
                    f"mp=4 paged logits off one chip by "
                    f"{out['tp_vs_one_chip']:.4f} > {SHARDED_TOL}")

    del model
    gc.collect()

    train_kw = dict(max_position_embeddings=sizes.train_seq, recompute=True,
                    recompute_policy="save_attn_mlp",
                    recompute_policy_alt="save_attn",
                    recompute_policy_stride=3)
    with phase("train", clock, report) as out:
        out.update(train(full_config(fused_linear_loss=True, **train_kw),
                         sizes))
        if not out["tpu_custom_call"]:
            raise SmokeFailure("no tpu_custom_call in the lowered step")
    gc.collect()

    if len(devs) >= 4:
        with phase("multichip_train", clock, report) as out:
            out.update(train(full_config(tensor_parallel=True, **train_kw),
                             sizes, fleet_mp=2))
            _spread(out["bytes_in_use"], "sharded training state")
            first, ref = out["losses"][0], out["one_chip_loss"]
            if abs(first - ref) > 0.02 * abs(ref):
                raise SmokeFailure(
                    f"dp x mp first-step loss {first} != one-chip {ref}")

    clock.close()
    tuned = applied_schedules()
    print(f"chip_smoke: tuned schedules applied: {sorted(tuned) or 'none'}")
    print("chip_smoke: summary " + json.dumps({
        "compile_s": round(clock.seconds, 1), "cache_hits": clock.hits,
        "cache_misses": clock.misses,
        "phases": {k: {"compile_s": v["compile_s"], "run_s": v["run_s"]}
                   for k, v in report.items()},
        "claim": None}))
    print(result_line(device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
