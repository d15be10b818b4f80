"""Faults of the Falcon-H1 decoder's own mechanisms, planted in the program
underneath a run as ``tools/faults.py`` plants the general ones; ``correct``
has to come out false for each.

    python3 benchmarks/tools/faults_falcon.py --fault decay_left_out \
        --workload falconh1_reason_sat --seed ... --seconds ... --trace 0

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
def _recurrence(change):
    """Both forms of the recurrence the model calls, with ``change(dt, A) ->
    (dt, A)`` applied to what they are given."""
    from paddle_tpu.models import falcon_h1
    honest_chunk, step = falcon_h1.ssd_chunk, falcon_h1.ssd_decode_step

    def chunk_(x, B, C, dt, A, D, s0, n_valid=None, chunk=128):
        return honest_chunk(x, B, C, *change(dt, A), D, s0, n_valid,
                            chunk=chunk)

    def step_(arena, layer, rows, live, x, B, C, dt, A, D):
        return step(arena, layer, rows, live, x, B, C, *change(dt, A), D)

    with _patched(falcon_h1, "ssd_chunk", chunk_), \
            _patched(falcon_h1, "ssd_decode_step", step_):
        yield


def decay_left_out():
    """``A`` = 0: the state never forgets."""
    return _recurrence(lambda dt, A: (dt, 0.0 * A))


def state_not_reset():
    """A slot's states not zeroed when the slot is reused: the next prompt
    starts from what the last request left (NaN, where that request finished
    in a decode program).  It shows on reused slots only: give the run a
    window in which some of those finish, 30 s or more."""
    from paddle_tpu.models import falcon_h1
    return _patched(falcon_h1.FalconH1ForCausalLM, "_chunk_state",
                    staticmethod(lambda arena, slot, start: arena[slot][None]))


def key_multiplier_left_out():
    """The attention's keys not scaled by ``key_multiplier`` before RoPE and
    the cache: a sharper softmax over the same keys."""
    from paddle_tpu.models import falcon_h1
    honest = falcon_h1.FalconH1Attention.step

    def step(self, u, pos0, n_valid, kv):
        scale, self.key_multiplier = self.key_multiplier, 1.0
        try:
            return honest(self, u, pos0, n_valid, kv)
        finally:
            self.key_multiplier = scale

    return _patched(falcon_h1.FalconH1Attention, "step", step)


FALCON_FAULTS = {"decay_left_out": decay_left_out,
                 "state_not_reset": state_not_reset,
                 "key_multiplier_left_out": key_multiplier_left_out}


if __name__ == "__main__":
    from benchmarks.tools import control_run
    FAULTS.update(FALCON_FAULTS)
    sys.exit(control_run.main())
