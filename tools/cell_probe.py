"""A benchmark cell's run with its programs' lowering times printed after the
result line.

    python3 tools/cell_probe.py --workload ... --seed ... --seconds ... --trace 0|1

The arguments are ``benchmarks/run.py``'s and the run is its ``run_cell`` of
the checkout in the working directory (a parent commit unpacked elsewhere is
measured by running this file from there).  Nothing of the harness is
replaced or read: the one addition is a listener on ``jax.monitoring``.

``lowering_times``: the seconds JAX spent tracing, lowering and compiling each
program, from ``jax.monitoring``'s three durations summed by program name.
Tracing and lowering are host work that a warm compile cache does not remove:
they are what a new kernel's call sites add to ``setup_s``.
"""

import collections
import json
import os
import sys

EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_s",
}
# programs under this many seconds in all are summed under "other"
SHOWN_FROM_S = 0.05


def main():
    sys.path.insert(0, os.getcwd())
    import jax
    from benchmarks import run

    seen = collections.defaultdict(lambda: collections.defaultdict(float))
    lowerings = collections.Counter()

    def listen(event, duration, fun_name="?", **_):
        if event in EVENTS:
            seen[fun_name][EVENTS[event]] += duration
            lowerings[fun_name] += EVENTS[event] == "lower_s"

    jax.monitoring.register_event_duration_secs_listener(listen)
    code = run.run_cell(sys.argv[1:])[0]
    rows, other = {}, collections.defaultdict(float)
    for name, parts in seen.items():
        if sum(parts.values()) >= SHOWN_FROM_S:
            rows[name] = {k: round(v, 3) for k, v in parts.items()}
            rows[name]["lowerings"] = lowerings[name]
        else:
            for k, v in parts.items():
                other[k] += v
    total = {k: round(sum(p.get(k, 0.0) for p in seen.values()), 3)
             for k in EVENTS.values()}
    print("lowering_times " + json.dumps({
        "programs": rows, "other": {k: round(v, 3) for k, v in other.items()},
        "total": total}), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
