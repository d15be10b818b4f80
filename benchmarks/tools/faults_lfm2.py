"""Faults of the sparse hybrid decoder's own mechanisms, planted in the program
underneath a run as ``tools/faults.py`` plants the general ones; ``correct``
has to come out false for each.

    python3 benchmarks/tools/faults_lfm2.py --fault top_k_less_one \
        --workload lfm2moe_chat_sat --seed ... --seconds ... --trace 0

This file adds its faults to ``tools/faults.py``'s table and hands over to
``tools/control_run.py``, so ``--control`` works here too.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.tools.faults import FAULTS, _patched  # noqa: E402


def top_k_less_one():
    """One expert fewer a token than the configuration says (top-3 for
    top-4); the weights are normalised over those."""
    from paddle_tpu.nn.layer import experts
    honest = experts.route_tokens
    return _patched(experts, "route_tokens",
                    lambda u, router, bias, top_k, **kw: honest(
                        u, router, bias, top_k - 1, **kw))


def biased_weights():
    """The combine weights taken from the biased score, which may only pick."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.nn.layer import experts

    def route(u, router, expert_bias, top_k, *, norm_topk_prob=True,
              scaling=1.0, score_dtype=jnp.float32):
        s = jax.nn.sigmoid(jnp.dot(u.astype(score_dtype),
                                   router.astype(score_dtype)))
        w, chosen = jax.lax.top_k(s + expert_bias.astype(score_dtype), top_k)
        if norm_topk_prob:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
        return chosen.astype(jnp.int32), w * scaling

    return _patched(experts, "route_tokens", route)


def state_not_reset():
    """A slot's convolution state not zeroed when the slot is reused: the
    next prompt starts from what the last request left (NaN, where that
    request finished in a decode program).  It shows on reused slots only:
    give the run a window in which some of those finish, 30 s or more."""
    from paddle_tpu.models import lfm2
    return _patched(lfm2.Lfm2MoeForCausalLM, "_chunk_tails",
                    staticmethod(lambda arena, slot, start: arena[slot][None]))


def state_shifted():
    """The convolution state kept one row too early: the tail handed on ends
    one row before the last valid one."""
    from paddle_tpu.models import lfm2
    honest = lfm2.Lfm2ShortConv.step
    return _patched(lfm2.Lfm2ShortConv, "step",
                    lambda self, u, n_valid, tail: honest(
                        self, u, n_valid - 1, tail))


LFM2_FAULTS = {"top_k_less_one": top_k_less_one,
               "biased_weights": biased_weights,
               "state_not_reset": state_not_reset,
               "state_shifted": state_shifted}


if __name__ == "__main__":
    from benchmarks.tools import control_run
    FAULTS.update(LFM2_FAULTS)
    sys.exit(control_run.main())
