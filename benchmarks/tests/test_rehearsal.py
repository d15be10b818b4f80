"""The whole control flow at a tiny size on the CPU: closed loop with its cut,
train on one device and under dp2 x mp2, traced and untraced, and the key set
of the last line.  The drivers are called directly:
``run.py`` itself refuses a CPU.  Nothing here is a device metric.
"""

import io
import json
import os
import re
import time
from contextlib import redirect_stderr, redirect_stdout

import pytest

from benchmarks.harness.context import Context, emit
from benchmarks.harness.spec import BENCH_DIR, Cell, load_json

REH = os.path.join(BENCH_DIR, "rehearsal")


@pytest.fixture(autouse=True)
def no_mesh_left_behind():
    """A run is a process of its own; in one test process the mesh that
    ``fleet.init`` set must not reach the next test."""
    yield
    from paddle_tpu.distributed.topology import set_global_mesh
    set_global_mesh(None)


def cell(name):
    return Cell(load_json(os.path.join(REH, "workloads.json")), name,
                traffic_dir=os.path.join(REH, "traffic"))


def drive(name, seed=3, seconds=1.5, trace=0, **kw):
    c = cell(name)
    ctx = Context(c, seed, seconds, trace, time.perf_counter(),
                  trace_dir=os.path.join(REH, ".trace_" + name))
    ctx.phases.mark("imports")
    return c, ctx, c.driver().run(ctx, **kw)


@pytest.mark.parametrize("name", ["tiny_sat", "tiny_train",
                                  "tiny_train_dp2mp2", "gpt_tiny_sat"])
def test_untraced_run_is_correct(name):
    c, ctx, (obs, rows, ok) = drive(name)
    assert ok, rows
    assert obs["failed"] == 0 and obs["attempted"] > 0
    assert obs["compiles_in_window"] == 0
    for m in c.end_to_end:
        if m["name"] != "setup_s":
            assert obs[m["name"]] > 0
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        emit(ok, obs["attempted"], obs["failed"], {}, obs["device"], rows)
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert err.getvalue().startswith("compared ")


@pytest.mark.parametrize("name", ["tiny_sat", "tiny_train", "gpt_tiny_sat"])
def test_traced_run_reads_its_trace(name):
    c, ctx, (obs, rows, ok) = drive(name, trace=1)
    assert ok, rows
    trace = obs["trace"]
    assert trace is not None and 0.3 < trace.window_s < 1.5
    assert any(s[0].startswith("bench.") for s in trace.spans)
    assert not os.path.exists(ctx.trace_dir)


def test_control_reads_beside_the_reference():
    _, _, (obs, rows, ok) = drive("tiny_train", control="int8,fp8")
    ctl = obs["check"]["control"]
    assert ctl["fp8"]["loss_gap_max"] > ctl["int8"]["loss_gap_max"] \
        > 10 * obs["check"]["loss_gap_max"]


def test_a_second_family_needs_its_own_files_and_no_other():
    """What a ``model_config`` PR may do: files and entries.  The rehearsal's
    GPT family is reached by the names in its configuration's file alone: the
    harness, the drivers and the readers do not know it, nor the first
    family, nor any leaf or published key of either."""
    c = cell("gpt_tiny_sat")
    assert c.family.__name__ == "benchmarks.rehearsal.families.gpt_tiny"
    assert c.reference.__name__ == "benchmarks.rehearsal.reference.gpt_tiny"
    assert c.config["model"]["architectures"] == ["GPT2LMHeadModel"]
    assert cell("tiny_sat").family is not c.family
    words = re.compile(r"gpt|llama|q_proj|qkv|intermediate_size|n_embd", re.I)
    for d in ("harness", "drivers", "readers"):
        for f in sorted(os.listdir(os.path.join(BENCH_DIR, d))):
            if f.endswith(".py"):
                with open(os.path.join(BENCH_DIR, d, f)) as fh:
                    found = [line for line in fh if words.search(line)]
                assert not found, (d, f, found)
    # the first family does not know the second either
    with open(os.path.join(BENCH_DIR, "families", "llama_dense.py")) as fh:
        assert not re.search(r"gpt", fh.read(), re.I)
