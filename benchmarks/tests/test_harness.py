"""The yardstick's own arithmetic: traffic that the seed reorders and never
resizes, the trace reduction on one small recorded trace, and the data files'
agreement with ``BENCHMARK.json``."""

import json
import os
import re

import numpy as np
import pytest

from benchmarks.harness import flops, traffic
from benchmarks.harness.clocks import percentile
from benchmarks.harness.spec import BENCH_DIR, ROOT, load_json
from benchmarks.harness.trace import Trace, short_name

BENCH = load_json(os.path.join(ROOT, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def mixes(kind):
    """The cells' mixes and the rehearsal's, of one kind."""
    out = []
    for d in ("traffic", os.path.join("rehearsal", "traffic")):
        for f in sorted(os.listdir(os.path.join(BENCH_DIR, d))):
            mix = load_json(os.path.join(BENCH_DIR, d, f))
            if mix["kind"] == kind:
                out.append(mix)
    return out


@pytest.mark.parametrize("mix", mixes("serve_closed"))
def test_closed_loop_deals_one_multiset(mix):
    seen, firsts = set(), set()
    n = mix["multiset"]
    for seed in range(12):
        seq = traffic.closed_sequence(mix, seed, 64000)
        assert len(seq) == n * mix["repeats"]
        flat = [(r["prompt"].size, r["max_new"]) for r in seq]
        for k in range(3):      # every repeat is the same multiset
            part = flat[k * n:(k + 1) * n]
            seen.add((tuple(sorted(a for a, _ in part)),
                      tuple(sorted(b for _, b in part))))
        firsts.add(flat[0])
    assert len(seen) == 1 and len(firsts) > 6


def test_lengths_keep_to_the_mix_limits_and_large_seeds_work():
    mix = load_json(os.path.join(BENCH_DIR, "traffic", "chat_sat.json"))
    reqs = traffic.requests(mix, 100, 2 ** 31 + 12345, 64000)
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    assert all(p["min"] <= r["prompt"].size <= p["max"] for r in reqs)
    assert all(o["min"] <= r["max_new"] <= o["max"] for r in reqs)
    rows = traffic.token_rows(8, 16, 100, 2 ** 31 + 7)
    assert rows.shape == (8, 17) and len({r.tobytes() for r in rows}) == 8


def test_percentile_is_nearest_rank():
    assert percentile(list(range(1, 101)), 95) == 95
    assert percentile([3.0], 95) == 3.0 and percentile([], 95) is None


def test_flop_counts_of_the_configuration():
    yi = load_json(os.path.join(BENCH_DIR, "configs", "yicoder-1.5b.json"))
    assert flops.param_counts(yi)["total"] == 1476495360
    assert round(flops.train_flops_per_token(yi, 2048) / 1e9, 2) == 8.68
    assert flops.kv_bytes_per_token(yi) == 196608


# -- the trace reduction, on a trace recorded on a v5e (PR 27): three rounds of
# a 1024^3 bf16 matmul under bench.eng_step and an add under bench.sample ----------

@pytest.fixture(scope="module")
def tiny_trace():
    return Trace(os.path.join(BENCH_DIR, "tests", "data", "tiny.xplane.pb"))


def test_trace_busy_and_idle(tiny_trace):
    t = tiny_trace
    assert len(t.devices) == 1
    assert t.window_s == pytest.approx(0.013403869, rel=1e-6)
    assert t.busy_s() == pytest.approx(5.306e-05, rel=1e-3)
    assert t.idle_pct() == pytest.approx(99.604, abs=1e-2)


def test_trace_time_by_pattern(tiny_trace):
    t = tiny_trace
    seconds, n = t.module_seconds("^jit_mm")
    assert n == 2 and seconds == pytest.approx(3.163e-05, rel=1e-3)
    seconds, n = t.op_seconds("fusion", "^jit_add")
    assert n == 3 and seconds == pytest.approx(2.1441e-05, rel=1e-3)
    assert t.op_seconds("no_such_op") == (0.0, 0)


def test_trace_breakdown_and_gap_attribution(tiny_trace):
    t = tiny_trace
    ops = dict(t.top_ops())
    assert "jit_mm:convolution_tanh_fusion:bf16[1024,1024]" in ops
    gaps = dict(t.idle_gaps())
    assert set(gaps) >= {"bench.eng_step", "bench.sample"}
    assert sum(gaps.values()) == pytest.approx(t.window_s - t.busy_s(), rel=1e-6)


def test_short_name_keeps_the_custom_call_target():
    text = ('%custom-call.7 = bf16[48,16,1,128]{3,2,1,0} custom-call(bf16[48] %a), '
            'custom_call_target="tpu_custom_call"')
    assert short_name(text) == "custom-call:tpu_custom_call:bf16[48,16,1,128]"


# -- the data files ------------------------------------------------------------------

def test_names_and_units_keep_to_the_allowed_characters():
    names = [c["name"] for c in BENCH["configs"]]
    names += [w[k] for w in BENCH["workloads"] for k in ("name", "config", "traffic")]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    assert all(NAME.match(n) for n in names), names
    assert len(json.dumps(BENCH)) < 64 * 1024
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 4)


def test_every_metric_file_agrees_with_the_benchmark():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells)) for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        data = load_json(os.path.join(BENCH_DIR, "metrics", m["name"] + ".json"))
        for key in ("layer", "unit", "moves", "workloads"):
            assert data[key] == m[key], (m["name"], key)
        assert data["workloads"] and set(data["workloads"]) <= cells
        assert set(data["workloads"]) <= e2e[data["moves"]], m["name"]
        assert ":" in data["reader"]
    for cell in cells:
        assert any(cell in m["workloads"] for m in BENCH["per_layer"])
