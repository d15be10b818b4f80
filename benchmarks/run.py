"""One process, one cell, once:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's model on the device from the seed, warms the cell's own
program shapes, measures for ``--seconds``, checks what the timed path produced
against the plain reference, and prints as its last line the one JSON object
of the benchmark's contract.  It needs a TPU listed in the peaks table and as
many chips as the cell asks for; there is no CPU mode and no size switch.
"""

import time

T_START = time.perf_counter()

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def metric_line(value, unit):
    return {"value": float(value), "unit": unit}


def collect(cell, ctx, obs, traced):
    """End-to-end metrics of an untraced run, per-layer metrics of a traced
    one; a reader that finds nothing to read leaves its metric out."""
    from benchmarks.harness.spec import resolve
    out = {}
    if not traced:
        for m in cell.end_to_end:
            v = ctx.setup_s if m["name"] == "setup_s" else obs.get(m["name"])
            if v is not None:
                out[m["name"]] = metric_line(v, m["unit"])
        return out
    for m in cell.per_layer:
        v = resolve(m["reader"])(obs, ctx, **m.get("args", {}))
        if v is not None:
            out[m["name"]] = metric_line(v, m["unit"])
    return out


def run_cell(argv=None, control=None):
    """One run: (exit code, ``correct``, what the driver observed).
    ``control`` (modes of the reference, tools/control_run.py) reads the
    control beside the reference; a benchmark run never sets it."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "paddle_tpu")):
        print("benchmarks/run.py: no paddle_tpu beside the benchmark; it "
              "measures the program of its own checkout", file=sys.stderr)
        return 2, None, None
    from benchmarks.harness.spec import Cell, load_json
    cell = Cell(load_json(os.path.join(ROOT, "BENCHMARK.json")), args.workload)

    import jax
    from benchmarks.harness.peaks import DEVICE_PEAKS, peaks_of
    devs = jax.devices()
    if devs[0].platform != "tpu" or devs[0].device_kind not in DEVICE_PEAKS \
            or len(devs) < cell.chips:
        print(f"benchmarks/run.py: needs {cell.chips} chip(s) of a kind in "
              f"{sorted(DEVICE_PEAKS)}; found {len(devs)} x "
              f"{devs[0].platform}/{devs[0].device_kind}", file=sys.stderr)
        return 3, None, None
    from paddle_tpu.utils import enable_compile_cache
    cache_dir = enable_compile_cache()
    print(f"run workload={cell.name} seed={args.seed} seconds={args.seconds} "
          f"trace={args.trace} device={devs[0].device_kind} x{len(devs)} "
          f"jax={jax.__version__} compile_cache={cache_dir}", flush=True)

    from benchmarks.harness.context import Context, emit
    ctx = Context(cell, args.seed, args.seconds, args.trace, T_START,
                  peaks=peaks_of(devs[0].device_kind))
    ctx.phases.mark("imports")
    obs, compared, ok = cell.driver().run(ctx, control=control)
    device = dict(obs["device"])
    breakdown = None
    trace = obs.get("trace")
    if args.trace:
        if trace is None:
            print("benchmarks/run.py: the traced run recorded no trace",
                  file=sys.stderr)
            return 4, None, None
        device["busy_s"] = trace.busy_s()
        device["window_s"] = trace.window_s
        breakdown = {"device_ops": [[k, v] for k, v in trace.top_ops(10)],
                     "idle_gaps": [[k, v] for k, v in trace.idle_gaps(10)]}
    metrics = collect(cell, ctx, obs, bool(args.trace))
    print("observed " + json.dumps(
        {k: v for k, v in obs.items() if isinstance(v, (int, float, str))}),
        flush=True)
    emit(ok, obs["attempted"], obs["failed"], metrics, device, compared,
         breakdown)
    return 0, ok, obs


if __name__ == "__main__":
    sys.exit(run_cell()[0])
