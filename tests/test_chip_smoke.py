"""chip_smoke.py's phases at ``tiny_llama_config()`` on the CPU: control
flow only — the routes, sizes and times that matter come from the chip."""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import chip_smoke
from paddle_tpu import models

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = chip_smoke.Sizes(
    num_slots=4, prompt_len=16, max_cache_len=64, steps_per_call=2,
    block_len=8, spec_k=2, train_batch=2, train_seq=16, train_steps=3,
    dtype="float32")


def _tiny_config(**kw):
    # one layer: every program here is compiled once per tier-1 run
    return dataclasses.replace(models.tiny_llama_config(),
                               num_hidden_layers=1, **kw)


@pytest.fixture(scope="module")
def tiny():
    cfg = _tiny_config()
    return cfg, chip_smoke.build_model(cfg, TINY.dtype)


def test_refuses_to_start_without_a_tpu():
    """``python chip_smoke.py`` with no chip: non-zero, no result line,
    before any model exists (well under a model build's time)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert "platform=cpu" in proc.stdout
    assert '"ok"' not in proc.stdout and "[build]" not in proc.stdout


def test_result_line_has_the_contract_keys_only():
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    line = chip_smoke.result_line(dev)
    assert "\n" not in line
    assert json.loads(line) == {"ok": True, "device": dev}


def test_serving_variants_and_route_check(tiny):
    cfg, model = tiny
    waves = chip_smoke.make_requests(cfg.vocab_size, TINY)
    out = chip_smoke.serve_variant(model, TINY, waves, spec=True,
                                   kv_cache_dtype="int8")
    assert out["requests"] == 7 and out["spec_verify_steps"] >= 1
    assert out["prefix_hit_tokens"] >= 1
    # on the CPU every gate answers pallas_unavailable, which is exactly
    # what the chip run must never see
    want = ["decode_attention:xla:int8_scale_lanes"]
    assert list(out["routes"]) == [
        "decode_attention:decision=xla,reason=pallas_unavailable"]
    with pytest.raises(chip_smoke.SmokeFailure, match="pallas_unavailable"):
        chip_smoke.require_routes(out["routes"], want)
    chip_smoke.require_routes(
        {"decode_attention:decision=xla,reason=int8_scale_lanes": 3}, want)
    with pytest.raises(chip_smoke.SmokeFailure):        # one is missing
        chip_smoke.require_routes(
            {"decode_attention:decision=pallas,reason=paged_ok": 2},
            ["decode_attention:pallas:paged_ok",
             "decode_attention:pallas:paged_multi_ok"])


def test_agreement(tiny):
    cfg, model = tiny
    got = chip_smoke.agreement(model, TINY)
    # float32 on the CPU: the paged programs ARE the full forward
    assert got["paged_vs_full"] < 1e-4
    assert 0 < got["kv_int8_vs_paged"] < chip_smoke.KV_INT8_TOL


def test_moe_experts_phase():
    out = chip_smoke.moe_experts(((32, 256, 128), (32, 128, 256)), 8)
    # on the CPU the gate answers ragged_dot, which is what it is held to
    assert out["vs_ragged_dot"] == 0.0 and out["grad_vs_ragged_dot"] == 0.0
    assert out["routes"] == {
        "moe_experts:decision=xla,reason=pallas_unavailable": 4}
    with pytest.raises(chip_smoke.SmokeFailure, match="route decisions"):
        chip_smoke.require_routes(out["routes"],
                                  ["moe_experts:pallas:grouped_ok"])


def test_train_phase():
    out = chip_smoke.train(_tiny_config(), TINY)
    assert len(out["losses"]) == TINY.train_steps
    assert out["losses"][-1] < out["losses"][0]
    assert out["tpu_custom_call"] is False      # no Mosaic on the CPU
    assert out["one_chip_loss"] is None
    with pytest.raises(chip_smoke.SmokeFailure, match="not spread"):
        chip_smoke._spread([10, 1000, 1000, 1000], "state")
    chip_smoke._spread([None] * 4, "state")


@pytest.mark.slow
def test_multichip_phases():
    # four kv heads, so that mp=4 splits whole heads
    cfg = _tiny_config(num_key_value_heads=4)
    model = chip_smoke.build_model(cfg, TINY.dtype)
    waves = chip_smoke.make_requests(cfg.vocab_size, TINY)
    one = chip_smoke.agreement(model, TINY)["paged_float"]
    out = chip_smoke.multichip_serving(model, TINY, waves, one)
    assert out["tp_vs_one_chip"] < 1e-4
    out = chip_smoke.train(_tiny_config(tensor_parallel=True), TINY,
                           fleet_mp=2)
    assert np.isclose(out["losses"][0], out["one_chip_loss"], rtol=1e-4)
