"""Faults of the Kimi Linear decoder's own mechanisms, planted in the program
underneath a run as ``tools/faults.py`` plants the general ones; ``correct``
has to come out false for each.

    python3 benchmarks/tools/faults_kimi.py --fault decay_left_out \
        --workload kimilinear_reason_sat --seed ... --seconds ... --trace 0

This file adds its faults to ``tools/faults.py``'s table and hands over to
``tools/control_run.py``, so ``--control`` works here too.
"""

import contextlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.tools.faults import FAULTS, _patched  # noqa: E402


@contextlib.contextmanager
def _delta_rule(change):
    """Both forms of the delta rule the model calls, with ``change(g, beta)
    -> (g, beta)`` applied to what they are given."""
    from paddle_tpu.models import kimi_linear
    chunk, step = kimi_linear.kda_chunk, kimi_linear.kda_decode_step

    def chunk_(q, k, v, g, beta, s0, n_valid=None):
        return chunk(q, k, v, *change(g, beta), s0, n_valid)

    def step_(arena, layer, rows, live, q, k, v, g, beta):
        return step(arena, layer, rows, live, q, k, v, *change(g, beta))

    with _patched(kimi_linear, "kda_chunk", chunk_), \
            _patched(kimi_linear, "kda_decode_step", step_):
        yield


def decay_left_out():
    """``alpha`` = 1: the state never forgets."""
    return _delta_rule(lambda g, beta: (0.0 * g, beta))


def beta_left_out():
    """``beta`` = 1: every token writes at full strength."""
    return _delta_rule(lambda g, beta: (g, 0.0 * beta + 1.0))


def shared_expert_left_out():
    """An expert layer gives the routed experts' part alone."""
    from paddle_tpu.models import kimi_linear
    return _patched(kimi_linear.KimiSparseMoe, "apply",
                    kimi_linear.KimiSparseMoe.routed)


def k_pe_left_out():
    """The shared positional key's part of the score left out: the cached
    row's last ``qk_rope_head_dim`` values never written."""
    from paddle_tpu.models import kimi_linear
    honest = kimi_linear.KimiMLAttention.step

    def step(self, u, pos0, n_valid, kv):
        proj = self.kv_a_proj_with_mqa
        import jax.numpy as jnp
        keep = jnp.arange(self.rank + self.rope) < self.rank
        weight = proj.weight._value
        proj.weight._value = jnp.where(keep[None, :], weight, 0)
        try:
            return honest(self, u, pos0, n_valid, kv)
        finally:
            proj.weight._value = weight

    return _patched(kimi_linear.KimiMLAttention, "step", step)


def state_not_reset():
    """A slot's states not zeroed when the slot is reused: the next prompt
    starts from what the last request left (NaN, where that request finished
    in a decode program).  It shows on reused slots only: give the run a
    window in which some of those finish, 30 s or more."""
    from paddle_tpu.models import kimi_linear
    return _patched(kimi_linear.KimiLinearForCausalLM, "_chunk_state",
                    staticmethod(lambda arena, slot, start: arena[slot][None]))


def top_k_less_one():
    """One expert fewer a token than the configuration says (top-7 for
    top-8); the weights are normalised over those."""
    from paddle_tpu.nn.layer import experts
    honest = experts.route_tokens
    return _patched(experts, "route_tokens",
                    lambda u, router, bias, top_k, **kw: honest(
                        u, router, bias, top_k - 1, **kw))


KIMI_FAULTS = {"decay_left_out": decay_left_out,
               "beta_left_out": beta_left_out,
               "shared_expert_left_out": shared_expert_left_out,
               "k_pe_left_out": k_pe_left_out,
               "state_not_reset": state_not_reset,
               "top_k_less_one": top_k_less_one}


if __name__ == "__main__":
    from benchmarks.tools import control_run
    FAULTS.update(KIMI_FAULTS)
    sys.exit(control_run.main())
