"""Set-up, accounting and the check of a serving driver; a driver of another
kind of serving traffic (an open loop) shares them."""

from __future__ import annotations

import gc
import time

import numpy as np

from ..harness import compare, serving


class ServeRun:
    """Builds the engine for a cell; the driver then runs its loop on it."""

    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.cell.config["model"]
        self.vocab = ctx.cell.family.vocab_size(self.cfg)
        self.geo = ctx.cell.config["serving"]
        self.mix = ctx.cell.traffic
        self.model = serving.build_model(ctx.cell, ctx.seed, ctx.phases)
        self.eng = serving.build_engine(self.model, self.cfg, self.geo)
        ctx.phases.mark("engine")
        serving.warm_up(self.eng, self.vocab, self.geo)
        ctx.phases.mark("warm_up")
        self.routes = serving.check_routes(
            ctx.cell.config.get("expected_routes", []))
        self.sess = serving.Session(self.eng, self.cfg, ctx.annotate)
        self.stats0 = None
        self.trace = None
        self.trace_stats = None

    # -- the traced span ----------------------------------------------------------
    def maybe_trace(self, elapsed, end):
        """Open the traced span ``trace_seconds`` before ``end`` and close its
        annotation at ``end``; the profiler is stopped after the window."""
        ctx = self.ctx
        if not ctx.trace_on:
            return
        if self.trace_stats is None and elapsed >= end - ctx.trace_seconds():
            self.trace_stats = [self.eng.stats(), None]
            self.sess.mark("trace0")
            self.trace_steps0 = self.sess.steps
            ctx.trace_begin()
        elif self.trace_stats and elapsed >= end:
            self._close_span()

    def _close_span(self):
        if self.trace_stats[1] is None:
            self.ctx.trace_close_span()
            self.trace_stats[1] = self.eng.stats()
            self.sess.mark("trace1")
            self.trace_steps1 = self.sess.steps

    def finish_trace(self):
        if self.ctx.trace_on and self.trace_stats:
            self._close_span()
            self.trace = self.ctx.trace_end()

    # -- after the window -----------------------------------------------------------
    def observed(self, t0, t_end, tracked, stats1):
        """Counts over the whole window and over the traced span."""
        ctx, cfg = self.ctx, self.cfg
        d = {k: stats1[k] - self.stats0[k] for k in
             ("decode_steps", "busy_slot_steps", "preemptions",
              "prefill_chunks", "block_dispatches")}
        work = serving.span_work(cfg, tracked, "window0", "none")
        obs = {"window_s": t_end - t0, "work": work, "stats": d,
               "num_slots": self.geo["num_slots"],
               "eng_steps": self.sess.steps,
               "block_samples": self.sess.block_samples,
               "compiles_in_window": ctx.compiles_in_window(),
               "latency": serving.latency_metrics(tracked),
               "serve_flops": serving.serve_flops_of(ctx.cell, work)}
        if self.trace is not None:
            s0, s1 = self.trace_stats
            tw = serving.span_work(cfg, tracked, "trace0", "trace1")
            obs["traced"] = {
                "work": tw, "eng_steps": self.trace_steps1 - self.trace_steps0,
                "decode_steps": s1["decode_steps"] - s0["decode_steps"],
                "prefill_chunks": s1["prefill_chunks"] - s0["prefill_chunks"]}
        return obs

    def conclude(self, obs, answered, control):
        """The end of either loop: count what the system failed among the
        requests ``answered``, read the device, free the program's state and
        only then let the reference take the chip."""
        from ..harness.context import device_info
        whys = [serving.failed_reason(tr, self.vocab) for tr in answered]
        for why in [w for w in whys if w][:5]:
            print(f"failed request: {why}", flush=True)
        pairs = [(np.asarray(tr.spec["prompt"]), np.asarray(tr.req.output))
                 for tr, why in zip(answered, whys) if why is None]
        obs.update(attempted=len(answered), failed=sum(w is not None for w in whys),
                   device=device_info(self.ctx.cell.chips), trace=self.trace)
        self.sess = self.eng = self.model = None
        gc.collect()
        rows, ok, obs["check"] = check(self.ctx, pairs, control)
        return obs, rows, ok


def check(ctx, finished, control=None):
    """Compare a seeded sample of the finished requests with the reference.
    ``finished``: [(prompt ids, served ids)]."""
    cell = ctx.cell
    cfg = cell.config["model"]
    chk = cell.config["check"]["serve"]
    t = time.perf_counter()
    sample = compare.pick_sample(finished, int(chk["requests"]), ctx.seed)
    w = compare.reference_weights(cell, ctx.seed)
    got = compare.served_gaps(cell.reference, w, cfg, sample,
                              bucket=int(chk["bucket"]), control=control)
    got["reference_s"] = time.perf_counter() - t
    print(f"check {got}", flush=True)
    if not sample:          # nothing finished soundly: nothing proves correct
        got["served_gap_max"] = 1e30
    rows, ok = compare.judge(got, chk["limits"])
    got["in_place"] = compare.judge_in_place(
        {m: {"served_gap_max": g} for m, g in got["control_gap_max"].items()},
        chk["limits"])
    return rows, ok, got
