"""The expert layer's grouped matmul alone on the chip, ``jax.lax.ragged_dot``
beside it in the same process: the serving cell's shapes and group sizes.

    python3 tools/bench_grouped_matmul.py [--sweep] [--out chiprun_out/x.jsonl]

One JSON line a (case, variant): microseconds a call (``calls`` calls chained
in one jit over ``planes`` distinct weight arrays, ``reps`` repeats, timed to
``block_until_ready``), the share of the HBM rate against the bytes of the
planes touched, and the largest difference from ``ragged_dot``.  ``--sweep``
adds the row and column tiles around the module's rule.  No CPU mode and no
size switch: without a TPU it exits at once (``tests/test_grouped_matmul.py``
holds the kernel to ``ragged_dot`` in interpret mode).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HBM_GBPS = 819.0


def cell_sizes(rng, slots, live, top_k, groups, open_groups):
    """Group sizes drawn as the cell's are: ``live`` rows route to ``top_k``
    distinct experts of the ``open_groups`` the biases leave open, by unequal
    odds; the vacant slots hold one stale row and all go the same way."""
    import numpy as np
    odds = np.exp(0.45 * rng.standard_normal(open_groups))
    odds /= odds.sum()
    opened = rng.permutation(groups)[:open_groups]
    sizes = np.zeros(groups, np.int64)
    for _ in range(live):
        sizes[opened[rng.choice(open_groups, top_k, replace=False, p=odds)]] += 1
    stale = opened[rng.choice(open_groups, top_k, replace=False, p=odds)]
    sizes[stale] += slots - live
    return sizes.astype(np.int32)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--out", default="")
    ap.add_argument("--calls", type=int, default=24)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--planes", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops.pallas import grouped_matmul as gm

    if jax.devices()[0].platform != "tpu":
        sys.exit(f"bench_grouped_matmul: no TPU "
                 f"({jax.devices()[0].platform}): a time or a share of the "
                 f"HBM rate comes only from the chip")
    rng = np.random.default_rng(args.seed)
    groups, top_k = 64, 4
    wide, narrow = 2048, 1536
    slots = 256
    out = open(args.out, "w") if args.out else None

    def emit(row):
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    emit({"device": jax.devices()[0].device_kind, "jax": jax.__version__,
          "calls": args.calls, "reps": args.reps, "planes": args.planes,
          "row_tile_rule": gm._ROW_TILE, "stream_budget": gm._STREAM_BUDGET})

    decode = cell_sizes(rng, slots, slots * 226 // 256, top_k, groups,
                        groups * 3 // 4)
    every = cell_sizes(rng, slots, slots, top_k, groups, groups)
    chunk = cell_sizes(rng, 2 * slots, 2 * slots, top_k, groups, groups)
    cases = [
        ("decode_w13", decode, wide, narrow),
        ("decode_w2", decode, narrow, wide),
        ("decode_all_w13", every, wide, narrow),
        ("chunk_w13", chunk, wide, narrow),
        ("chunk_w2", chunk, narrow, wide),
    ]
    planes = {}

    def weights(k, n):
        if (k, n) not in planes:
            planes[k, n] = [jnp.asarray(
                rng.standard_normal((groups, k, n), np.float32) * 0.02,
                jnp.bfloat16) for _ in range(args.planes)]
        return planes[k, n]

    def timed(fn, xs, ws, sizes):
        # every call of the chain has operands of its own, or XLA makes one
        # call of those that share theirs
        xss = [xs * (1 + i / 64) for i in range(-(-args.calls // len(ws)))]

        def chain(xss, ws, sizes):
            acc = jnp.zeros((), jnp.float32)
            for i in range(args.calls):
                y = fn(xss[i // len(ws)], ws[i % len(ws)], sizes)
                acc += y[0, 0].astype(jnp.float32)
            return acc
        run = jax.jit(chain)
        run(xss, ws, sizes).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(args.reps):
            r = run(xss, ws, sizes)
        r.block_until_ready()
        return (time.perf_counter() - t0) / (args.reps * args.calls) * 1e6

    def kernel_at(tm, tn):
        def fn(xs, w, sizes):
            m = xs.shape[0]
            rows = min(tm, m)
            return gm._grouped_matmul_pallas(
                xs, w, *gm._visit_table(sizes, m=m, tm=rows), tm=rows, tn=tn,
                interpret=False)
        return fn

    for name, sizes, k, n in cases:
        m = int(sizes.sum())
        touched = int((sizes > 0).sum())
        xs = jnp.asarray(rng.standard_normal((m, k), np.float32), jnp.bfloat16)
        ws, sz = weights(k, n), jnp.asarray(sizes)
        ref = jax.jit(jax.lax.ragged_dot)(xs, ws[0], sz)
        floor_us = touched * k * n * 2 / (HBM_GBPS * 1e3)
        base = {"case": name, "m": m, "k": k, "n": n, "touched": touched,
                "max_over_mean": round(float(sizes.max() / sizes.mean()), 2),
                "floor_us": round(floor_us, 1)}
        us = timed(jax.lax.ragged_dot, xs, ws, sz)
        emit(dict(base, variant="ragged_dot", us_per_call=us,
                  hbm_pct=100 * floor_us / us))
        rule_tn = gm._col_tile(k, n, 2)
        variants = [("rule", gm._ROW_TILE, rule_tn)]
        if args.sweep and name in ("decode_w13", "decode_w2", "chunk_w13"):
            tns = sorted({t for t in (256, 512, 768, 1024, n) if n % t == 0})
            variants += [(f"tm{tm}_tn{tn}", tm, tn)
                         for tm in (64, 128, 256) for tn in tns]
        for label, tm, tn in variants:
            fn = kernel_at(tm, tn)
            try:
                got = jax.jit(fn)(xs, ws[0], sz)
                err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                            - ref.astype(jnp.float32))))
                us = timed(fn, xs, ws, sz)
            except Exception as e:      # a tile Mosaic refuses is a finding
                emit(dict(base, variant=label, tm=tm, tn=tn,
                          error=f"{type(e).__name__}: {str(e)[:300]}"))
                continue
            emit(dict(base, variant=label, tm=tm, tn=tn, us_per_call=us,
                      hbm_pct=100 * floor_us / us, max_abs_err=err))

    # rows that are no multiple of the tile, fewer rows than a tile, rows
    # past the groups': compiled and compared, not timed
    few = np.zeros(groups, np.int32)
    few[[1, 5]] = (17, 11)
    for m, sizes in ((1000, np.minimum(decode, 1000 // groups + 1)),
                     (40, few)):
        sizes = sizes.astype(np.int32)
        xs = jnp.asarray(rng.standard_normal((m, wide), np.float32),
                         jnp.bfloat16)
        w, sz = weights(wide, narrow)[0], jnp.asarray(sizes)
        got = jax.jit(kernel_at(gm._ROW_TILE, gm._col_tile(wide, narrow, 2)))(
            xs, w, sz)
        ref = jax.lax.ragged_dot(xs, w, sz)
        total = int(sizes.sum())
        emit({"case": f"odd_rows_{m}", "sum_sizes": total,
              "max_abs_err_in_groups": float(jnp.max(jnp.abs(
                  got[:total].astype(jnp.float32)
                  - ref[:total].astype(jnp.float32)))),
              "tail_abs_max": float(jnp.max(jnp.abs(
                  got[total:].astype(jnp.float32)))),
              "ragged_dot_tail_abs_max": float(jnp.max(jnp.abs(
                  ref[total:].astype(jnp.float32))))})

    # does an inner jit make a program's call sites share one lowering?
    k, n = wide, narrow
    spec = (jax.ShapeDtypeStruct((4 * slots, k), jnp.bfloat16),
            [jax.ShapeDtypeStruct((groups, k, n), jnp.bfloat16)] * 8,
            jax.ShapeDtypeStruct((groups,), jnp.int32))

    def layers(call):
        def f(xs, ws, sizes):
            acc = jnp.zeros((), jnp.float32)
            for w in ws:
                for rows in (xs, xs + 1):
                    acc += call(rows, w, sizes)[0, 0].astype(jnp.float32)
            return acc
        return f

    def inlined(xs, w, sizes):
        m = xs.shape[0]
        return gm._grouped_matmul_pallas.__wrapped__(
            xs, w, *gm._visit_table(sizes, m=m, tm=gm._row_tile(m)),
            tm=gm._row_tile(m), tn=gm._col_tile(k, n, 2), interpret=False)

    for label, call in (("inner_jit", kernel_at(gm._ROW_TILE,
                                                gm._col_tile(k, n, 2))),
                        ("inlined", inlined),
                        ("ragged_dot", jax.lax.ragged_dot)):
        t0 = time.perf_counter()
        lowered = jax.jit(layers(call)).lower(*spec)
        t1 = time.perf_counter()
        text = lowered.as_text()
        t2 = time.perf_counter()
        lowered.compile()
        emit({"case": "lowering_16_call_sites", "variant": label,
              "lower_s": t1 - t0, "compile_s": time.perf_counter() - t2,
              "custom_calls_in_text": text.count("tpu_custom_call"),
              "funcs_in_text": text.count("func.func")})


if __name__ == "__main__":
    main()
