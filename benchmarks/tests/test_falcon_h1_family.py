"""The Falcon-H1 family (``families/falcon_h1.py``) and the rehearsal of its
cell: the leaves are the model's, the counts of the shipped configuration are
the arithmetic of ISSUE 40 and of its own ``deployment``, every published key
is answered or refused, the tiny cell runs through the closed-loop driver
unchanged on the CPU and comes out correct, its float8 control and the
planted faults of the mechanisms do not, and nothing that was under
``benchmarks/`` was edited to get there.
"""

import json
import os
import re
import subprocess
import time

import pytest

from benchmarks.families import falcon_h1 as family
from benchmarks.harness.context import Context
from benchmarks.harness.spec import BENCH_DIR, ROOT, Cell, load_json
from benchmarks.tools import faults_falcon
from test_rehearsal import no_mesh_left_behind  # noqa: F401

REH = os.path.join(BENCH_DIR, "rehearsal")
PARENT = "ad3779ba3f9a6f34d81fbd7b0940d4da0a06f79c"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def shipped():
    return Cell(load_json(os.path.join(ROOT, "BENCHMARK.json")),
                "falconh1_reason_sat")


def tiny_cell():
    return Cell(load_json(os.path.join(REH, "workloads_falcon_h1.json")),
                "falcon_h1_tiny_sat", traffic_dir=os.path.join(REH, "traffic"))


def drive(seed=2147483999, seconds=1.5, **kw):
    c = tiny_cell()
    ctx = Context(c, seed, seconds, 0, time.perf_counter(),
                  trace_dir=os.path.join(REH, ".trace_falcon"))
    ctx.phases.mark("imports")
    return c, ctx, c.driver().run(ctx, **kw)


def test_leaf_specs_are_the_models_parameters():
    cfg = tiny_cell().config["model"]
    model = family.build(cfg)
    got = [(n, tuple(p.shape)) for n, p in model.named_parameters()]
    assert got == [(n, tuple(s)) for n, s, _ in family.leaf_specs(cfg)]
    assert family.vocab_size(cfg) == 4096 == model.config.vocab_size
    # built without a byte of initial values
    from paddle_tpu.nn.lazy import Unmaterialized
    assert all(isinstance(p._value, Unmaterialized)
               for p in model.parameters())


def test_every_published_key_is_answered_or_refused():
    """The catalog's row whole (72 layers) builds; each key with one answer
    here refuses another value; a key nobody knows is refused."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f
                   if '"Falcon-H1-34B-Instruct"' in line)
    published = dict(row["config"], dtype="bfloat16")
    assert set(published) - {"dtype"} <= set(family.PASSED) | \
        set(family.STATED)
    cfg = shipped().config["model"]
    assert {k: v for k, v in cfg.items() if k != "num_hidden_layers"} == \
        {k: v for k, v in published.items() if k != "num_hidden_layers"}
    model = family.build(published)
    assert len(model.model.layers) == 72
    for key, value in [("attn_layer_indices", [0, 1]),
                       ("mamba_use_mlp", False), ("mlp_expansion_factor", 4),
                       ("num_logits_to_keep", 2), ("projectors_bias", True),
                       ("mamba_chunk_size", 256), ("mamba_rms_norm", False),
                       ("model_type", "falcon_mamba")]:
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
    assert n == pc["total"] == stated == 4_394_354_048
    assert pc["layer"] == 430_120_032 and pc["head"] == 1_336_934_400
    assert cell.config["reduced"] == cell.config_entry["reduced"] == \
        ["num_hidden_layers"]
    assert cell.config["assumed"]["published"]["num_hidden_layers"] == 72
    # a slot's state: 4 layers x 32 x 128 x 256 float32, and 4 x 3 rows of
    # 5120 channels in bfloat16; 4 layers x 2 x 4 heads x 128 a token
    assert family.ssd_state_bytes_per_slot(cfg) == 16_777_216
    assert family.tail_bytes_per_slot(cfg) == 122_880
    assert family.kv_bytes_per_token(cfg) == 8192
    # a decode step of 128 live rows: layer weights 3.44 GB, head 2.67 GB,
    # the state in and out 4.29 GB, the tails 0.03 GB; 450 cached tokens a
    # row 0.47 GB; 10.9 GB in all
    flops, nbytes = family.decode_step_work(cfg, 1, 128, 128 * 450)
    ssd_flops, ssd_bytes = family.ssd_decode_work(cfg, 128)
    assert ssd_bytes == 128 * 2 * 16_777_216
    assert ssd_flops == 128 * 5 * 4 * 32 * 128 * 256
    assert 10.8e9 < nbytes < 11.0e9
    assert ssd_bytes / nbytes == pytest.approx(0.39, abs=0.01)
    assert family.decode_attention_work(cfg, 1000) == \
        (4.0 * 4 * 20 * 128 * 1000, 8192 * 1000)
    matmul = 4 * (430_120_032 - 20_480 - 5_120 - 96 - 4_096 - 10_240) \
        + 1_336_934_400
    assert family.serve_flops(cfg, 1, 0) == pytest.approx(
        2.0 * matmul + 5.0 * 4 * 32 * 128 * 256)


def test_the_tiny_cell_is_correct_and_its_control_is_not():
    c, ctx, (obs, rows, ok) = drive(control="fp8")
    assert ok, rows
    assert obs["failed"] == 0 and obs["attempted"] > 0
    assert obs["compiles_in_window"] == 0
    assert obs["check"]["in_place"] == {"fp8": False}
    from benchmarks.readers import ssd_work
    assert ssd_work.decode_state({"traced": None}, ctx) is None
    traced = {"traced": {"work": {"decode_tokens": 10}}}
    assert ssd_work.decode_state(traced, ctx) == \
        family.ssd_decode_work(c.config["model"], 10)


# ``decay_left_out`` is not among them: at this toy width ``C . B`` is
# nothing beside ``D x`` (the draws are set for the published widths), so a
# lost decay reads 0 here; it is read at the cell's size on the chip
@pytest.mark.parametrize("fault", ["key_multiplier_left_out",
                                   "state_not_reset"])
def test_a_planted_fault_of_the_mechanism_is_not_correct(fault):
    with faults_falcon.FALCON_FAULTS[fault]():
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
