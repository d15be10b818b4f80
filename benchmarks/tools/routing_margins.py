"""A run of a cell of the sparse hybrid family that keeps, for every served
token the check compares, its gap under the reference's best, the gap of each
control's pick, and the position's least routing margin in the reference: what
the reference's ``ROUTING_EPS`` was chosen from.

    python3 benchmarks/tools/routing_margins.py --out chiprun_out/x.npz \
        [--control fp8,int8] [--fault top_k_less_one] \
        --workload ... --seed ... --seconds ... --trace 0

The gaps are taken on the reference's rows before any position is left out.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def recording(path, honest):
    import jax.numpy as jnp
    import numpy as np

    def served_gaps(ref_module, ref_weights, cfg, sample, bucket=512,
                    control=None):
        kept = {"gap": [], "margin": [], "request": []}
        for mode in (control.split(",") if control else []):
            kept["gap_" + mode] = []
        for i, (prompt, served) in enumerate(sample):
            plen, n_out = int(prompt.size), int(served.size)
            total = -(-(plen + bucket) // bucket) * bucket
            ids = np.zeros((total,), np.int32)
            ids[:plen] = prompt
            ids[plen:plen + n_out] = served
            logits, margin = ref_module.logits_and_margins(
                ref_weights, cfg, ids, plen - 1, bucket)
            logits = logits[:n_out]
            best = jnp.max(logits, axis=-1)
            took = jnp.take_along_axis(
                logits, jnp.asarray(served)[:, None], -1)[:, 0]
            kept["gap"].append(np.asarray(best - took))
            kept["margin"].append(np.asarray(margin[:n_out]))
            kept["request"].append(np.full((n_out,), i))
            for mode in (control.split(",") if control else []):
                low = ref_module.sequence_logits(ref_weights, cfg, ids,
                                                 plen - 1, bucket, mode=mode)
                first = jnp.argmax(low[:n_out], axis=-1)
                took = jnp.take_along_axis(logits, first[:, None], -1)[:, 0]
                kept["gap_" + mode].append(np.asarray(best - took))
        np.savez(path, **{k: np.concatenate(v) for k, v in kept.items()})
        return honest(ref_module, ref_weights, cfg, sample, bucket=bucket,
                      control=control)

    return served_gaps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--fault", default="")
    args, rest = ap.parse_known_args()
    import contextlib
    from benchmarks import run
    from benchmarks.harness import compare
    from benchmarks.tools.faults import FAULTS
    from benchmarks.tools.faults_lfm2 import LFM2_FAULTS
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    compare.served_gaps = recording(args.out, compare.served_gaps)
    planted = {**FAULTS, **LFM2_FAULTS}[args.fault]() if args.fault \
        else contextlib.nullcontext()
    with planted:
        code = run.run_cell(rest, control=args.control or None)[0]
    from paddle_tpu.observability.metrics import get_registry
    snap = get_registry().snapshot()
    print("expert_load " + " ".join(
        f"{name}={sum(snap[name]['values'].values())}"
        for name in ("moe.layer_steps", "moe.experts_touched")
        if name in snap), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
