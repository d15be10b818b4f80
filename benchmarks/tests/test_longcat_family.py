"""The LongCat-Flash family (``families/longcat_flash.py``) and the rehearsal
of its cell: the leaves are the model's, the counts of the shipped
configuration are the arithmetic of its sizing and of its own ``deployment``,
every published key is answered or refused, the tiny cell (one chip's share:
experts 4-7 of 8 beside 4 zero-compute ones) runs through the closed-loop
driver unchanged on the CPU and comes out correct, its float8 control and the
planted faults of the mechanisms do not, the new readers read, and nothing
that was under ``benchmarks/`` was edited to get there.
"""

import os
import re
import subprocess
import time

import pytest

from benchmarks.families import longcat_flash as family
from benchmarks.harness.context import Context
from benchmarks.harness.spec import BENCH_DIR, ROOT, Cell, load_json
from benchmarks.tools import faults_longcat
from test_rehearsal import no_mesh_left_behind  # noqa: F401

REH = os.path.join(BENCH_DIR, "rehearsal")
PARENT = "1a35c55ee72500974ff3b3845a2f857681aff991"
# the published config's keys that shape the model, as the source's
# config.json gives them
PUBLISHED = dict(
    attention_bias=False, vocab_size=131072, hidden_size=6144,
    ffn_hidden_size=12288, expert_ffn_hidden_size=2048, num_layers=28,
    num_attention_heads=64, kv_lora_rank=512, q_lora_rank=1536,
    qk_rope_head_dim=64, v_head_dim=128, qk_nope_head_dim=128,
    mla_scale_q_lora=True, mla_scale_kv_lora=True,
    routed_scaling_factor=6, n_routed_experts=512,
    max_position_embeddings=131072, rms_norm_eps=1e-05,
    rope_theta=10000000, attention_method="MLA", zero_expert_num=256,
    zero_expert_type="identity", moe_topk=12)


def shipped():
    return Cell(load_json(os.path.join(ROOT, "BENCHMARK.json")),
                "longcat_reason_sat")


def tiny_cell():
    return Cell(load_json(os.path.join(REH, "workloads_longcat.json")),
                "longcat_tiny_sat", traffic_dir=os.path.join(REH, "traffic"))


def drive(seed=2147483999, seconds=1.5, **kw):
    c = tiny_cell()
    ctx = Context(c, seed, seconds, 0, time.perf_counter(),
                  trace_dir=os.path.join(REH, ".trace_longcat"))
    ctx.phases.mark("imports")
    return c, ctx, c.driver().run(ctx, **kw)


def test_leaf_specs_are_the_models_parameters():
    cfg = tiny_cell().config["model"]
    model = family.build(cfg)
    got = [(n, tuple(p.shape)) for n, p in model.named_parameters()]
    assert got == [(n, tuple(s)) for n, s, _ in family.leaf_specs(cfg)]
    assert family.vocab_size(cfg) == 128 == model.config.vocab_size
    assert model.config.n_routed_experts == 8 and \
        model.config.experts_held == (4, 4)
    assert model.model.layers[0].mlp.router.shape == [64, 12]
    # built without a byte of initial values
    from paddle_tpu.nn.lazy import Unmaterialized
    assert all(isinstance(p._value, Unmaterialized)
               for p in model.parameters())


def test_every_published_key_is_answered_or_refused():
    """The published config whole (28 layers, 512 experts) builds; a key
    with one answer here refuses another value; a key nobody knows is
    refused."""
    published = dict(PUBLISHED, dtype="bfloat16")
    assert set(published) - {"dtype"} <= set(family.PASSED) | \
        set(family.OWN)
    cell = shipped()
    cfg = cell.config["model"]
    cut = {"num_layers", "n_routed_experts", "vocab_size"}
    assert {k: v for k, v in published.items() if k not in cut} == \
        {k: v for k, v in cfg.items() if k in published and k not in cut}
    assert {k: published[k] for k in cut} == \
        cell.config["assumed"]["published"]
    model = family.build(published)
    assert len(model.model.layers) == 28
    assert model.config.experts_held == (0, 512)
    for key, value in [("attention_method", "GQA"), ("attention_bias", True),
                       ("zero_expert_type", "constant")]:
        with pytest.raises(ValueError, match=key):
            family.build(dict(cfg, **{key: value}))
    with pytest.raises(ValueError, match="sliding_window"):
        family.build(dict(cfg, sliding_window=4096))


def test_the_shipped_configuration_counts_as_its_deployment_says():
    cell = shipped()
    cfg = cell.config["model"]
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
    assert n == pc["total"] == stated == 5_172_749_312
    # by part: an MLA sublayer 90.57M, a dense SwiGLU 226.49M, an
    # expert 37.75M, 16 of them 604.0M; 10.35 GB in bfloat16
    assert pc["mla"] == 90_570_752 and pc["ffn"] == 226_492_416
    assert pc["one_expert"] == 37_748_736 and pc["held"] == 603_979_776
    assert 2 * n / 1e9 == pytest.approx(10.35, abs=0.005)
    assert sorted(cell.config["reduced"]) == sorted(
        cell.config_entry["reduced"]) == sorted(
        ["num_layers", "n_routed_experts", "vocab_size"])
    # the vocabulary is an eighth, the router the published width
    assert family.vocab_size(cfg) == 16_384 == \
        cell.config["assumed"]["published"]["vocab_size"] // 8
    assert family.experts_counted(cfg) == 512 == \
        cell.config["assumed"]["published"]["n_routed_experts"]
    assert family.real_picks(cfg) == 8.0
    # 8 arenas of 576 values a token, 640 at rest: 10,240 B a token, and the
    # geometry's 16,385 blocks of 16 take 2.68 GB beside the weights
    assert family.latent_bytes_per_token(cfg) == 9216
    assert family.cache_bytes_per_token(cfg) == 10_240
    geo = cell.config["serving"]
    cache = (geo["num_blocks"] + 1) * geo["block_len"] * 10_240
    assert cache / 1e9 == pytest.approx(2.68, abs=0.005)
    assert geo["num_blocks"] * geo["block_len"] == \
        geo["num_slots"] * geo["max_cache_len"]
    # a decode step of 128 live rows at 600 cached rows each: the held
    # planes 4.83 GB of 10.85
    flops, nbytes = family.decode_step_work(cfg, 1, 128, 128 * 600)
    assert family.expert_work(cfg, 1, 128)[1] == 4 * 603_979_776 * 2
    assert 10.8e9 < nbytes < 10.9e9
    assert family.decode_attention_work(cfg, 1000) == \
        (2.0 * 8 * 64 * (2 * 512 + 64) * 1000, 9216 * 1000)
    # of a token's 8 real experts a 32nd is held here; a zero-compute pick
    # costs nothing
    assert family.serve_flops(cfg, 1, 0) == pytest.approx(
        2.0 * (pc["dense"] + 4 * 8 * 37_748_736 / 32))


def test_the_tiny_cell_is_correct_and_its_control_is_not():
    c, ctx, (obs, rows, ok) = drive(control="fp8")
    assert ok, rows
    assert obs["failed"] == 0 and obs["attempted"] > 0
    assert obs["compiles_in_window"] == 0
    assert obs["check"]["in_place"] == {"fp8": False}
    from benchmarks.readers import moe_load, zero_picks
    assert 0 < moe_load.touched_pct(obs, ctx) <= 100
    assert 0 < zero_picks.pct(obs, ctx) < 100


@pytest.mark.parametrize("fault", sorted(faults_longcat.LONGCAT_FAULTS))
def test_a_planted_fault_of_the_mechanism_is_not_correct(fault):
    with faults_longcat.LONGCAT_FAULTS[fault]():
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
