"""A scripted ``Drafter`` for the speculative-decoding tests.

``NGramDrafter`` proposes only where a sequence repeats itself, and a
random-weight model's greedy stream need not: whether a verify forward
ever runs, and whether anything is accepted, then hangs on the weights.
This drafter is told each request's own greedy continuation and plants
a wrong token at fixed output positions, so acceptances, rejections
and the rollback behind them happen whatever the weights."""

import numpy as np

from paddle_tpu.inference.speculative import Drafter


class ScriptedDrafter(Drafter):
    """``scripts`` is a list of ``(prompt, continuation)`` pairs, the
    continuation being the greedy output of that prompt.  For a context
    that starts with a scripted prompt and holds ``pos`` emitted tokens
    it proposes ``continuation[pos:pos + k]``, with every output
    position ``j`` where ``j % wrong_every == wrong_every - 1`` replaced
    by another token id: drafts before a planted position verify, the
    planted one is rejected and everything after it rolled back.  An
    unscripted context gets no proposal."""

    def __init__(self, scripts, vocab_size, wrong_every=3):
        self._scripts = sorted(
            ((np.asarray(p, np.int32).reshape(-1),
              np.asarray(c, np.int32).reshape(-1)) for p, c in scripts),
            key=lambda pc: -pc[0].size)
        self._vocab = int(vocab_size)
        self._wrong_every = int(wrong_every)

    def propose(self, context, k):
        ctx = np.asarray(context, np.int32).reshape(-1)
        for prompt, cont in self._scripts:
            n = prompt.size
            if ctx.size > n and np.array_equal(ctx[:n], prompt):
                pos = ctx.size - n
                d = cont[pos:pos + k].copy()
                at = np.arange(pos, pos + d.size)
                wrong = at % self._wrong_every == self._wrong_every - 1
                d[wrong] = (d[wrong] + 1) % self._vocab
                return d
        return np.zeros((0,), np.int32)
