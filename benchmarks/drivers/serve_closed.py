"""Closed loop: ``clients`` callers, each taking the next request of the mix's
sequence when its last one ends.  The window is cut at ``--seconds``: tokens handed over inside
it count, requests still running at the cut are neither attempted nor failed.
"""

from __future__ import annotations

import time

from ..harness import serving, traffic
from .serve_common import ServeRun


def run(ctx, control=None):
    run_ = ServeRun(ctx)
    mix, sess, eng = run_.mix, run_.sess, run_.eng
    todo = iter(traffic.closed_sequence(mix, ctx.seed, run_.vocab))
    tracked = []

    def send(client, due):
        spec = next(todo, None)
        if spec is None:
            raise RuntimeError("the clients ran out of requests before the cut: "
                               "raise repeats in the mix")
        tr = serving.Tracked(spec, due, client)
        tracked.append(tr)
        sess.submit(tr)

    run_.stats0 = eng.stats()
    t0 = ctx.open_window()
    for c in range(int(mix["clients"])):
        send(c, t0)
    sess.mark("window0")
    every = int(mix.get("block_sample_every", 8))
    while True:
        elapsed = time.perf_counter() - t0
        if elapsed >= ctx.seconds:
            break
        run_.maybe_trace(elapsed, ctx.seconds)
        for tr in sess.step():
            send(tr.client, time.perf_counter())
        if sess.steps % every == 0:
            sess.sample_blocks(eng.stats())
    t_end = time.perf_counter()
    run_.finish_trace()
    stats1 = eng.stats()
    obs = run_.observed(t0, t_end, tracked, stats1)
    done = list(sess.done)
    out_tokens = sum(tr.n_seen for tr in tracked)
    obs.update(out_tok_s=out_tokens / (t_end - t0), out_tokens=out_tokens)
    print(f"window {obs['window_s']:.3f}s finished={len(done)} in_flight="
          f"{len(tracked) - len(done)} out_tokens={out_tokens} "
          f"generator_late_p95_ms={obs['latency']['generator_late_p95_ms']}",
          flush=True)
    del sess, eng, send
    return run_.conclude(obs, done, control)
