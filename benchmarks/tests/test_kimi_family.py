"""The Kimi Linear family (``families/kimi_linear.py``) and the rehearsal of
its cell: the leaves are the model's, the counts of the shipped configuration
are the arithmetic of ISSUE 37 and of its own ``deployment``, the vocabulary is
the slice, the tiny cell (one chip's share: experts 4-7 of 8, half the
vocabulary) runs through the closed-loop driver unchanged on the CPU and comes
out correct, its float8 control and each planted fault of the mechanisms do
not, and nothing that was under ``benchmarks/`` was edited to get there.
"""

import os
import re
import subprocess
import time

import pytest

from benchmarks.families import kimi_linear as family
from benchmarks.harness.context import Context
from benchmarks.harness.spec import BENCH_DIR, ROOT, Cell, load_json
from benchmarks.tools import faults_kimi
from test_rehearsal import no_mesh_left_behind  # noqa: F401

REH = os.path.join(BENCH_DIR, "rehearsal")
PARENT = "69db0e544bd0d8682c2a4315ac7f55d03d79b93b"


def shipped():
    return Cell(load_json(os.path.join(ROOT, "BENCHMARK.json")),
                "kimilinear_reason_sat")


def tiny_cell():
    return Cell(load_json(os.path.join(REH, "workloads_kimi.json")),
                "kimi_tiny_sat", traffic_dir=os.path.join(REH, "traffic"))


def drive(seed=2147483999, seconds=1.5, **kw):
    c = tiny_cell()
    ctx = Context(c, seed, seconds, 0, time.perf_counter(),
                  trace_dir=os.path.join(REH, ".trace_kimi"))
    ctx.phases.mark("imports")
    return c, ctx, c.driver().run(ctx, **kw)


def test_leaf_specs_are_the_models_parameters():
    cfg = tiny_cell().config["model"]
    model = family.build(cfg)
    got = [(n, tuple(p.shape)) for n, p in model.named_parameters()]
    assert got == [(n, tuple(s)) for n, s, _ in family.leaf_specs(cfg)]
    assert family.vocab_size(cfg) == 128 == model.config.vocab_size
    assert model.config.num_experts == 8 and \
        model.config.experts_held == (4, 4)
    # built without a byte of initial values
    from paddle_tpu.nn.lazy import Unmaterialized
    assert all(isinstance(p._value, Unmaterialized)
               for p in model.parameters())
    with pytest.raises(ValueError, match="sliding_window"):
        family.build(dict(cfg, sliding_window=128))


def test_the_shipped_configuration_counts_as_its_deployment_says():
    cell = shipped()
    cfg = cell.config["model"]
    lin = cfg["linear_attn_config"]
    assert (lin["kda_layers"], lin["full_attn_layers"]) == ([1, 2, 3, 5], [4])
    n = 0
    for _, shape, _ in family.leaf_specs(cfg):
        size = 1
        for s in shape:
            size *= s
        n += size
    pc = family.param_counts(cfg)
    stated = int(re.search(r"([\d,]+) parameters",
                           cell.config["deployment"]).group(1)
                 .replace(",", ""))
    assert n == pc["total"] == stated == 4_282_936_192
    assert pc["one_expert"] == 7_077_888 and pc["held"] == 905_969_664
    assert pc["sparse_layers"] == 4
    # the vocabulary is the slice, the router the published width
    assert family.vocab_size(cfg) == 81_920 == \
        cell.config["assumed"]["published"]["vocab_size"] // 2
    assert family.experts_counted(cfg) == 256 == \
        cell.config["assumed"]["published"]["num_experts"]
    assert sorted(cell.config["reduced"]) == sorted(
        cell.config_entry["reduced"]) == sorted(
        ["num_hidden_layers", "linear_attn_config", "num_experts",
         "vocab_size"])
    # a slot's state: 4 x 32 x 128 x 128 float32, and 4 x 3 rows of 3 x 4096
    assert family.kda_state_bytes_per_slot(cfg) == 8_388_608
    assert family.tail_bytes_per_slot(cfg) == 294_912
    assert family.latent_bytes_per_token(cfg) == 1152
    # a decode step of 245 live rows at 600 cached rows each: the held planes
    # 7.25 GB, the other weights 0.94 GB, the state in and out 4.1 GB, the
    # latent rows 0.17 GB
    flops, nbytes = family.decode_step_work(cfg, 1, 245, 245 * 600)
    assert family.expert_work(cfg, 1, 245)[1] == 4 * 905_969_664 * 2
    assert family.kda_decode_work(cfg, 245)[1] == 245 * 2 * 8_388_608
    assert 12.5e9 < nbytes < 12.7e9
    assert family.decode_attention_work(cfg, 1000)[1] == 1152 * 1000
    # of a token's 8 experts half are held here
    assert family.serve_flops(cfg, 1, 0) == pytest.approx(
        2.0 * pc["touched_here"] + 7.0 * 4 * 32 * 128 * 128)


def test_the_tiny_cell_is_correct_and_its_control_is_not():
    c, ctx, (obs, rows, ok) = drive(control="fp8")
    assert ok, rows
    assert obs["failed"] == 0 and obs["attempted"] > 0
    assert obs["compiles_in_window"] == 0
    assert obs["check"]["in_place"] == {"fp8": False}
    from benchmarks.readers import kda_work, moe_load
    assert 0 < moe_load.touched_pct(obs, ctx) <= 100
    assert kda_work.decode_state({"traced": None}, ctx) is None
    traced = {"traced": {"work": {"decode_tokens": 10}}}
    assert kda_work.decode_state(traced, ctx) == \
        family.kda_decode_work(c.config["model"], 10)


@pytest.mark.parametrize("fault", sorted(faults_kimi.KIMI_FAULTS))
def test_a_planted_fault_of_the_mechanism_is_not_correct(fault):
    with faults_kimi.KIMI_FAULTS[fault]():
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
