"""The comparisons that decide ``correct``: what the timed path produced
against the plain reference, each number beside its limit."""

from __future__ import annotations

import statistics

import numpy as np

from . import weights


def reference_weights(cell, seed, shard=None):
    """The seed's weights as the reference wants them: float32 values of the
    stored type's values, made by the benchmark, nothing taken from the
    program.  ``shard`` places each leaf (a function of its shape)."""
    import jax.numpy as jnp
    family, cfg = cell.family, cell.config["model"]
    specs = family.leaf_specs(cfg)
    sh = None if shard is None else [shard(s[1]) for s in specs]
    leaves = weights.make(family, cfg, seed, jnp.dtype(cfg["dtype"]),
                          shardings=sh)
    return family.as_reference(cfg, leaves)


def pick_sample(finished, k, seed):
    """``k`` finished requests drawn from the seed, the longest among them."""
    if not finished:
        return []
    rng = np.random.default_rng([int(seed), 0xc4ec])
    longest = max(range(len(finished)),
                  key=lambda i: finished[i][0].size + finished[i][1].size)
    rest = [i for i in range(len(finished)) if i != longest]
    chosen = [longest] + list(rng.permutation(rest)[:max(k - 1, 0)])
    return [finished[i] for i in chosen]


def served_gaps(ref_module, ref_weights, cfg, sample, bucket=512, control=None):
    """Widest gap by which a served token's logit lies below the reference's
    best, over ``sample`` = [(prompt ids, served ids)].  With ``control`` (a
    mode of the reference, or several with commas) also the widest gap of the
    token that mode puts first, at the same positions."""
    import jax.numpy as jnp
    controls = control.split(",") if control else []
    worst, worst_ctl, n_tokens = 0.0, {m: 0.0 for m in controls}, 0
    for prompt, served in sample:
        plen, n_out = int(prompt.size), int(served.size)
        if n_out > bucket:
            raise ValueError(f"{n_out} served tokens exceed the bucket {bucket}")
        total = -(-(plen + bucket) // bucket) * bucket
        ids = np.zeros((total,), np.int32)
        ids[:plen] = prompt
        ids[plen:plen + n_out] = served
        logits = ref_module.sequence_logits(ref_weights, cfg, ids, plen - 1, bucket)
        logits = logits[:n_out]
        best = jnp.max(logits, axis=-1)
        took = jnp.take_along_axis(logits, jnp.asarray(served)[:, None], -1)[:, 0]
        worst = max(worst, float(jnp.max(best - took)))
        n_tokens += n_out
        for mode in controls:
            low = ref_module.sequence_logits(ref_weights, cfg, ids, plen - 1,
                                             bucket, mode=mode)[:n_out]
            first = jnp.argmax(low, axis=-1)
            took = jnp.take_along_axis(logits, first[:, None], -1)[:, 0]
            worst_ctl[mode] = max(worst_ctl[mode], float(jnp.max(best - took)))
    return {"served_gap_max": worst, "control_gap_max": worst_ctl,
            "tokens": n_tokens, "requests": len(sample)}


def leaf_gap(program, reference, skip=None):
    """Worst leaf: |program's norm - reference's norm| over the reference's
    norm of that leaf or of the median leaf, whichever is larger."""
    ref = np.asarray(reference, np.float64)
    got = np.asarray(program, np.float64)
    floor = statistics.median(ref.tolist())
    gaps = np.abs(got - ref) / np.maximum(ref, floor)
    if skip is not None:
        gaps = np.where(skip, 0.0, gaps)
    worst = int(np.argmax(gaps))
    return float(gaps[worst]), worst


def train_numbers(program, reference):
    """The numbers of a training cell from the program's and the reference's
    readings: losses of the steps followed, the first gradient's norms and the
    parameters' change, the last two by the worst leaf.  Leaves whose
    reference gradient is under a thousandth of the median leaf's move by
    round-off alone and are left out of the change."""
    n = len(reference["losses"])
    loss_gap = max(abs(p - r) / abs(r) for p, r in
                   zip(program["losses"][:n], reference["losses"]))
    grad_gap, grad_leaf = leaf_gap(program["grad_norms"], reference["grad_norms"])
    ref_g = np.asarray(reference["grad_norms"])
    tiny = ref_g < 1e-3 * statistics.median(ref_g.tolist())
    change_gap, change_leaf = leaf_gap(program["change_norms"],
                                       reference["change_norms"], skip=tiny)
    return {"loss_gap_max": loss_gap, "grad_norm_gap_max": grad_gap,
            "change_norm_gap_max": change_gap, "grad_leaf": grad_leaf,
            "change_leaf": change_leaf, "leaves_left_out": int(tiny.sum())}


def judge(numbers, limits):
    """[(name, value, limit)] and whether every value is within its limit."""
    rows = [(k, float(numbers[k]), float(lim)) for k, lim in limits.items()]
    ok = all(np.isfinite(v) and v <= lim for _, v, lim in rows)
    return rows, ok


def judge_in_place(readings, limits):
    """Each control or fault of ``readings`` ({mode: numbers}) put in the
    program's place and judged as a run is; one line a mode.  Returns
    {mode: correct}: a control or a fault has to come out False."""
    out = {}
    for mode, numbers in readings.items():
        rows, ok = judge(numbers, limits)
        print(f"in_place {mode} correct={ok} " + " ".join(
            f"{k}={v:.6g}/{lim:g}" for k, v, lim in rows), flush=True)
        out[mode] = ok
    return out

