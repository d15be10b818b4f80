"""``readers/registry.py``: a ratio of two of the program's registry counters,
and nothing where the program lacks the second (the parent of the PR that
brought the counter)."""

import os
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks.harness.spec import load_json, resolve
from benchmarks.readers import registry
from paddle_tpu.observability.metrics import get_registry

METRIC = load_json(os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "metrics", "engine.first_tokens_per_fetch.json"))


def test_the_ratio_sums_over_labels_and_reads_nothing_without_a_counter(
        monkeypatch):
    snap = {"a": {"values": {"x=1": 3, "x=2": 5}}, "b": {"values": {"": 4}},
            "zero": {"values": {"": 0}}}
    monkeypatch.setattr("paddle_tpu.observability.metrics.get_registry",
                        lambda: SimpleNamespace(snapshot=lambda: snap))
    assert registry.ratio({}, None, "a", "b") == 2.0
    assert registry.ratio({}, None, "a", "missing") is None
    assert registry.ratio({}, None, "a", "zero") is None


def test_first_tokens_per_fetch_reads_the_engines_counters():
    """An engine on the default registry, three one-chunk prompts in its
    first step: three prefills for one fetch."""
    import paddle_tpu as paddle
    from paddle_tpu import models
    from paddle_tpu.inference import ServingEngine
    args = METRIC["args"]

    def totals():
        snap = get_registry().snapshot()
        return {k: sum(snap.get(k, {}).get("values", {}).values())
                for k in args.values()}
    before = totals()
    paddle.seed(39)
    net = models.LlamaForCausalLM(models.tiny_llama_config())
    net.eval()
    eng = ServingEngine(net, num_slots=6, prompt_len=8, max_cache_len=24,
                        steps_per_call=2, block_len=4, chunk_len=8,
                        compute_dtype="float32")
    for n in (5, 8, 3, 6, 7, 4):
        eng.submit(np.arange(1, n + 1, dtype=np.int32), max_new_tokens=2)
    eng.step()
    after = totals()
    assert after[args["num"]] - before[args["num"]] == 3
    assert after[args["den"]] - before[args["den"]] == 1
    assert resolve(METRIC["reader"])({}, None, **args) == pytest.approx(
        after[args["num"]] / after[args["den"]])
