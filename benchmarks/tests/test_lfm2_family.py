"""The sparse hybrid family (``families/lfm2_moe.py``) and the rehearsal of its
cell: the leaves are the model's, the counts of the published configuration
are the arithmetic of ISSUE 33, the tiny cell runs through the closed-loop
driver unchanged on the CPU and comes out correct, its float8 control and a
planted fault of the mechanism do not, and nothing that was under
``benchmarks/`` was edited to get there.
"""

import os
import subprocess
import time

import pytest

from benchmarks.families import lfm2_moe as family
from benchmarks.harness.context import Context
from benchmarks.harness.spec import BENCH_DIR, ROOT, Cell, load_json
from benchmarks.tools import faults_lfm2
from test_rehearsal import no_mesh_left_behind  # noqa: F401

REH = os.path.join(BENCH_DIR, "rehearsal")
PARENT = "7141b4dea45daa71d2d26820a139cc5aeae87ab0"


def published():
    return Cell(load_json(os.path.join(ROOT, "BENCHMARK.json")),
                "lfm2moe_chat_sat").config["model"]


def tiny_cell():
    return Cell(load_json(os.path.join(REH, "workloads_lfm2.json")),
                "lfm2_tiny_sat", traffic_dir=os.path.join(REH, "traffic"))


def drive(seed=2147483999, seconds=1.5, **kw):
    c = tiny_cell()
    ctx = Context(c, seed, seconds, 0, time.perf_counter(),
                  trace_dir=os.path.join(REH, ".trace_lfm2"))
    ctx.phases.mark("imports")
    return c, ctx, c.driver().run(ctx, **kw)


def test_leaf_specs_are_the_models_parameters():
    cfg = tiny_cell().config["model"]
    model = family.build(cfg)
    got = [(n, tuple(p.shape)) for n, p in model.named_parameters()]
    assert got == [(n, tuple(s)) for n, s, _ in family.leaf_specs(cfg)]
    # built without a byte of initial values
    from paddle_tpu.nn.lazy import Unmaterialized
    assert all(isinstance(p._value, Unmaterialized)
               for p in model.parameters())
    with pytest.raises(ValueError, match="sliding_window"):
        family.build(dict(cfg, sliding_window=128))


def test_the_published_configuration_counts_as_issue_33_says():
    cfg = published()
    assert cfg["layer_types"] == ["conv", "conv", "full_attention", "conv",
                                  "conv", "conv", "full_attention", "conv",
                                  "conv", "conv"]
    specs = family.leaf_specs(cfg)
    n = 0
    for _, shape, _ in specs:
        size = 1
        for s in shape:
            size *= s
        n += size
    pc = family.param_counts(cfg)
    assert n == pc["total"] == 5_267_090_176
    assert pc["expert"] == 603_979_776 and pc["sparse_layers"] == 8
    # 2 x the parameters a token touches: 4 experts a layer, not 64
    assert family.serve_flops(cfg, 1, 0) == 2.0 * pc["touched"]
    assert 1.46e9 < family.serve_flops(cfg, 1, 0) < 1.48e9
    assert family.kv_bytes_per_token(cfg) == 4096
    assert family.state_bytes_per_slot(cfg) == 65536
    # a decode step of 220 live rows reads every expert, the weights outside
    # them once (10.53 GB, 9.66 of it experts), 4 KB a key (0.45 GB at 500
    # keys a row) and the tails twice (0.03 GB)
    flops, nbytes = family.decode_step_work(cfg, 8, 8 * 220, 8 * 220 * 500)
    e_flops, e_bytes = family.expert_work(cfg, 8, 8 * 220)
    assert e_bytes == 8 * 8 * 603_979_776 * 2
    assert 10.9e9 < nbytes / 8 < 11.1e9 and 0.86 < e_bytes / nbytes < 0.89
    assert family.decode_attention_work(cfg, 1000)[1] == 4096 * 1000
    # a lone row reads its four experts a layer and no more
    assert family.experts_read(cfg, 8, 8) == 4.0


def test_the_tiny_cell_is_correct_and_its_control_is_not():
    c, ctx, (obs, rows, ok) = drive(control="fp8")
    assert ok, rows
    assert obs["failed"] == 0 and obs["attempted"] > 0
    assert obs["compiles_in_window"] == 0
    assert obs["check"]["in_place"] == {"fp8": False}
    # the readers this PR adds find the program's counters
    from benchmarks.readers import moe_load, moe_work
    assert 0 < moe_load.touched_pct(obs, ctx) <= 100
    assert moe_load.max_over_mean(obs, ctx) >= 1.0
    assert moe_work.decode_experts({"traced": None}, ctx) is None


@pytest.mark.parametrize("fault", sorted(faults_lfm2.LFM2_FAULTS))
def test_a_planted_fault_of_the_mechanism_is_not_correct(fault):
    with faults_lfm2.LFM2_FAULTS[fault]():
        _, _, (obs, rows, ok) = drive()
    assert not ok and obs["failed"] == 0, rows


def test_nothing_that_was_under_benchmarks_is_modified():
    if not os.path.isdir(os.path.join(ROOT, ".git")):
        pytest.skip("not a git checkout")
    out = subprocess.run(
        ["git", "diff", "--name-status", PARENT, "--", "benchmarks"],
        cwd=ROOT, capture_output=True, text=True, check=True).stdout
    changed = [line for line in out.splitlines() if not line.startswith("A")]
    assert not changed, changed
