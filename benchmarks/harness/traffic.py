"""The one general traffic generator.  A mix is a data file of parameters; the
seed reorders and re-times the traffic and never resizes it.

Lengths are the stratified quantiles ``(i + 0.5) / n`` of the mix's
distributions, so every seed draws the same multiset; the seed decides which
prompt length meets which output length, the order and the token ids.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def stratified(dist, n):
    """``n`` integer lengths: the quantiles (i + 0.5) / n of ``dist``."""
    kind = dist["dist"]
    qs = [(i + 0.5) / n for i in range(n)]
    if kind == "lognormal":
        mu, sigma = math.log(dist["median"]), dist["sigma"]
        vals = [math.exp(mu + sigma * NormalDist().inv_cdf(q)) for q in qs]
    elif kind == "uniform":
        vals = [dist["min"] + q * (dist["max"] - dist["min"]) for q in qs]
    elif kind == "fixed":
        vals = [dist["value"]] * n
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    lo, hi = dist.get("min", 1), dist.get("max", math.inf)
    return [int(min(max(round(v), lo), hi)) for v in vals]


def requests(mix, n, seed, vocab):
    """``n`` requests as dicts ``prompt`` (int32 ids), ``max_new``: the fixed
    multisets of prompt and output lengths, paired and ordered by the seed."""
    rng = np.random.default_rng(np.append(np.asarray(seed, np.int64).ravel(), 0x5eed))
    prompts = np.array(stratified(mix["prompt_tokens"], n))
    outputs = np.array(stratified(mix["output_tokens"], n))
    prompts = prompts[rng.permutation(n)]
    outputs = outputs[rng.permutation(n)]
    return [{"prompt": rng.integers(0, vocab, int(p)).astype(np.int32),
             "max_new": int(o)} for p, o in zip(prompts, outputs)]


def closed_sequence(mix, seed, vocab):
    """The requests of a closed loop in the order the clients take them: one
    fixed multiset of ``multiset`` requests, repeated ``repeats`` times, each
    repeat paired and ordered anew by the seed.  A window cut by time then
    serves whole copies of the multiset and a part of one more, so the work
    inside it hardly depends on the seed."""
    n, repeats = int(mix["multiset"]), int(mix["repeats"])
    out = []
    for k in range(repeats):
        out += requests(mix, n, [int(seed), k], vocab)
    return out


def token_rows(n_rows, seq, vocab, seed):
    """Rows of ``seq + 1`` token ids for training: tokens are ``row[:-1]``,
    labels ``row[1:]``.  All rows differ."""
    rng = np.random.default_rng([int(seed), 0x7a1b])
    return rng.integers(0, vocab, (n_rows, seq + 1)).astype(np.int32)
