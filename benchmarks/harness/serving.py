"""What serving drivers share: building the engine on seeded weights,
warming the cell's own programs, and timing every request from outside.

The benchmark times a request itself: due (when the schedule wanted it sent),
sent, admitted (first seen out of the queue), first token, last token, all read
by ``time.perf_counter`` after each ``eng.step()``.  With eight decode steps a
call, tokens reach the host in blocks; a token's time is that of the step that
handed it over, which is what a client would see.
"""

from __future__ import annotations

import re
import time

import numpy as np

from . import weights
from .clocks import percentile


def build_model(cell, seed, phases, **build_kw):
    """The program's model, built by the cell's family, with the benchmark's
    seeded weights put into its parameters, each on the sharding the program
    gave it."""
    import paddle_tpu as paddle
    family, cfg = cell.family, cell.config["model"]
    dtype = cfg["dtype"]
    paddle.seed(int(seed) % 2147483629)
    model = family.build(cfg, **build_kw)
    if dtype != "float32":
        model.to(dtype=dtype)
    phases.mark("model_init")
    named = list(model.named_parameters())
    specs = family.leaf_specs(cfg)
    got = [(n, tuple(p.shape)) for n, p in named]
    want = [(n, tuple(s)) for n, s, _ in specs]
    if got != want:
        raise RuntimeError(f"the model's parameters are not the configuration's: "
                           f"{[g for g, w in zip(got, want) if g != w][:3]} ...")
    values = weights.make(family, cfg, seed, dtype, shardings=param_shardings(
        [p._value for _, p in named]))
    for (_, p), v in zip(named, values):
        p.set_value(v)
    values[-1].block_until_ready()
    phases.mark("weights")
    return model


def param_shardings(values):
    """Each value's own sharding; where some are spread over a mesh, the
    others are replicated over it (as ``TrainStep`` pins them), since one
    jitted call cannot mix a mesh with a single device."""
    from jax.sharding import NamedSharding, PartitionSpec
    meshes = [v.sharding.mesh for v in values
              if isinstance(v.sharding, NamedSharding)]
    if not meshes:
        return [v.sharding for v in values]
    rep = NamedSharding(meshes[0], PartitionSpec())
    return [v.sharding if isinstance(v.sharding, NamedSharding) else rep
            for v in values]


def build_engine(model, cfg, geometry):
    import jax
    from paddle_tpu.distributed.topology import build_mesh
    from paddle_tpu.inference.serving import ServingEngine
    # a one-chip replica is pinned to its device, always: the engine then
    # commits its weights and arenas there, and the first dispatch does not
    # compile a form of its own for arrays that no device holds yet
    mesh = build_mesh(mp=1, devices=[jax.devices()[0]])
    return ServingEngine(
        model, mesh=mesh, num_slots=geometry["num_slots"], prompt_len=geometry["prompt_len"],
        max_cache_len=geometry["max_cache_len"], block_len=geometry["block_len"],
        num_blocks=geometry["num_blocks"], chunk_len=geometry["chunk_len"],
        steps_per_call=geometry["steps_per_call"],
        host_cache_blocks=geometry["host_cache_blocks"],
        compute_dtype=cfg["dtype"])


def warm_up(eng, vocab, geometry):
    """Drive the programs this cell's traffic uses, and no others: the chunk
    program (a prompt of two chunks), the whole decode block and the one-step
    program that every request's tail takes.  Each decode program has two
    compiled forms, one fed from the host after a sync and one fed from the
    previous dispatch's device outputs, so the requests run several blocks and
    several tail steps in a row, alone and together."""
    rng = np.random.default_rng(0)
    spc, chunk = geometry["steps_per_call"], geometry["chunk_len"]
    for wave in (((chunk + chunk // 2, 1 + 3 * spc + 3), (chunk // 2, 1 + spc + 2),
                  (chunk // 4, 3)), ((chunk // 2, 1 + 2 * spc),)):
        for n_prompt, n_new in wave:
            eng.submit(rng.integers(0, vocab, max(n_prompt, 1))
                       .astype(np.int32), max_new_tokens=n_new)
        done = eng.run(wall_timeout_s=1500.0)
        if len(done) != len(wave) or any(r.state != "finished" for r in done):
            raise RuntimeError("warm-up requests did not finish")


ROUTE_COUNTER = re.compile(r"^pallas\.([\w\-]+)\.route$")


def check_routes(expected):
    """The kernels this cell expects to have run, from the program's route
    counters, whichever the registry holds (``pallas.<kernel>.route``; an
    expected route reads ``<kernel>:<key>``); a cell whose decode fell back
    to XLA is another cell."""
    from paddle_tpu.observability.metrics import get_registry
    seen = {}
    for name, counter in get_registry().snapshot().items():
        kernel = ROUTE_COUNTER.match(name)
        if not kernel:
            continue
        for key, v in counter.get("values", {}).items():
            if v:
                seen[f"{kernel.group(1)}:{key}"] = int(v)
    print(f"routes {seen}", flush=True)
    missing = [e for e in expected if not any(e in k for k in seen)]
    if missing:
        raise RuntimeError(f"expected routes {missing} not taken; seen {seen}")
    return seen


class Tracked:
    """One request as the benchmark sees it."""
    __slots__ = ("spec", "req", "due", "sent", "admitted", "first", "last",
                 "n_seen", "n_at", "pf_at", "client", "error")

    def __init__(self, spec, due, client=None):
        self.spec, self.due, self.client = spec, due, client
        self.req = None
        self.sent = self.admitted = self.first = self.last = None
        self.n_seen = 0
        self.n_at = {}        # mark -> tokens seen at that mark
        self.pf_at = {}       # mark -> prompt positions computed at that mark
        self.error = None


class Session:
    """Submits requests, steps the engine and watches every live request."""

    def __init__(self, eng, cfg, annotate):
        self.eng, self.cfg, self.annotate = eng, cfg, annotate
        self.live = []
        self.done = []
        self.steps = 0
        self.block_samples = []

    def submit(self, tr):
        tr.sent = time.perf_counter()
        try:
            tr.req = self.eng.submit(tr.spec["prompt"],
                                     max_new_tokens=tr.spec["max_new"])
        except Exception as e:          # a refusal is the system's failure
            tr.error = f"{type(e).__name__}: {e}"
            tr.last = time.perf_counter()
            self.done.append(tr)
            return
        self.live.append(tr)

    def step(self):
        with self.annotate("bench.eng_step"):
            self.eng.step()
        self.steps += 1
        now = time.perf_counter()
        with self.annotate("bench.sample"):
            finished = []
            still = []
            for tr in self.live:
                r = tr.req
                if tr.admitted is None and r.state != "queued":
                    tr.admitted = now
                n = len(r.tokens)
                if n > tr.n_seen:
                    if tr.n_seen == 0:
                        tr.first = now
                    tr.n_seen, tr.last = n, now
                if r.state in ("finished", "timeout", "shed", "cancelled"):
                    finished.append(tr)
                else:
                    still.append(tr)
            self.live = still
            self.done += finished
        return finished

    def mark(self, name):
        """Remember how far every request sent so far is, for sums over a
        span (a finished one too, or the span would count all of it)."""
        for tr in self.live + self.done:
            if tr.req is None:
                continue
            tr.n_at[name] = tr.n_seen
            tr.pf_at[name] = min(tr.req.pf_pos, tr.req.seq_len)

    def sample_blocks(self, stats):
        self.block_samples.append(stats["blocks_in_use"] / stats["num_blocks"])


def failed_reason(tr, vocab):
    """Why the system failed this request, or None."""
    if tr.error:
        return tr.error
    r = tr.req
    if r.state != "finished":
        return f"state {r.state}"
    out = r.output
    if out.shape != (tr.spec["max_new"],):
        return f"{out.shape[0]} tokens for {tr.spec['max_new']}"
    if out.min() < 0 or out.max() >= vocab:
        return "token outside the vocabulary"
    return None


def span_work(cfg, tracked, a, b):
    """Work done between marks ``a`` and ``b`` over ``tracked`` requests:
    prompt positions computed, tokens emitted, decode tokens (all but each
    request's first), the keys those decode steps attended to, and the keys
    all processed positions attended to."""
    prompt = out = decode = decode_ctx = ctx = 0
    for tr in tracked:
        if tr.req is None:
            continue
        n0 = tr.n_at.get(a, 0)
        n1 = tr.n_at.get(b, tr.n_seen)
        p0 = tr.pf_at.get(a, 0)
        p1 = tr.pf_at.get(b, tr.req.seq_len if tr.n_seen else
                          min(tr.req.pf_pos, tr.req.seq_len))
        plen = tr.req.seq_len
        prompt += max(p1 - p0, 0)
        ctx += (p1 * (p1 + 1) - p0 * (p0 + 1)) // 2
        out += n1 - n0
        # token j (1-based) beyond the first comes from a decode step that
        # attends to plen + j - 1 keys
        lo, hi = max(n0, 1), n1
        if hi > lo:
            decode += hi - lo
            decode_ctx += sum(plen + j - 1 for j in range(lo + 1, hi + 1))
    return {"prompt_tokens": prompt, "output_tokens": out,
            "decode_tokens": decode, "decode_context": decode_ctx,
            "context": ctx + decode_ctx}


def latency_metrics(tracked):
    """TTFT from due, TPOT, queue wait and the generator's lateness, over
    every request sent; milliseconds."""
    ttft = [1e3 * (tr.first - tr.due) for tr in tracked if tr.first is not None]
    tpot = [1e3 * (tr.last - tr.first) / (tr.n_seen - 1)
            for tr in tracked if tr.first is not None and tr.n_seen > 1]
    wait = [1e3 * (tr.admitted - tr.due) for tr in tracked
            if tr.admitted is not None]
    late = [1e3 * (tr.sent - tr.due) for tr in tracked if tr.sent is not None]
    return {"ttft_p95_ms": percentile(ttft, 95), "ttft_p50_ms": percentile(ttft, 50),
            "tpot_p95_ms": percentile(tpot, 95), "tpot_p50_ms": percentile(tpot, 50),
            "queue_wait_p95_ms": percentile(wait, 95),
            "generator_late_p95_ms": percentile(late, 95),
            "n_ttft": len(ttft), "n_tpot": len(tpot)}


def serve_flops_of(cell, work):
    return cell.family.serve_flops(
        cell.config["model"], work["prompt_tokens"] + work["decode_tokens"],
        work["context"])
