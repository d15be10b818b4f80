"""The comparison has been shown to fail: the rest of a run is driven at a tiny
size with the timed path broken underneath (``tools/faults.py``), once for each
fault a cell can have, and ``correct`` comes out false.  And the control (the
reference put in the program's place, one precision down) comes out as not
correct through the harness's own ``judge``.
"""

import pytest

from benchmarks.tools import faults
from test_rehearsal import drive, no_mesh_left_behind  # noqa: F401


def test_served_token_altered_where_it_is_produced():
    with faults.altered_token():
        _, _, (obs, rows, ok) = drive("tiny_sat")
    assert not ok and obs["failed"] == 0, rows


def test_step_that_returns_its_state_unchanged():
    with faults.state_unchanged():
        _, _, (obs, rows, ok) = drive("tiny_train")
    assert not ok, rows
    assert dict((r[0], r[1]) for r in rows)["change_norm_gap_max"] == pytest.approx(1.0)


@pytest.mark.parametrize("name", ["tiny_train", "tiny_train_dp2mp2"])
def test_half_of_the_batch_left_out(name):
    with faults.half_batch():
        _, _, (obs, rows, ok) = drive(name)
    assert not ok, rows


def test_a_fault_is_lifted_when_its_block_ends():
    from paddle_tpu.inference import serving
    honest = serving.Request.output
    with faults.altered_token():
        assert serving.Request.output is not honest
    assert serving.Request.output is honest


@pytest.mark.parametrize("name, control", [("tiny_train", "int8,half"),
                                           ("tiny_sat", "fp8"),
                                           ("gpt_tiny_sat", "fp8")])
def test_the_control_comes_out_not_correct(name, control):
    """int8 (training) and float8 (serving, where a few dozen tokens of a toy
    model seldom flip under int8) in the reference's matmuls, put in the
    program's place and judged as a run is; the sound run beside it is
    correct."""
    _, _, (obs, rows, ok) = drive(name, control=control)
    assert ok, rows
    assert obs["check"]["in_place"] == {m: False for m in control.split(",")}
