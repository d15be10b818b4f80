"""The yardstick's own arithmetic: traffic that the seed reorders and never
resizes, the seeded leaves and the work counts through the family, the route
check, the trace reduction on two small recorded traces, and the data files'
agreement with ``BENCHMARK.json``."""

import hashlib
import json
import os
import re

import numpy as np
import pytest

from benchmarks.harness import serving, traffic, weights, xplane
from benchmarks.harness.clocks import percentile
from benchmarks.harness.spec import (BENCH_DIR, HARNESS_SECTIONS, ROOT, Cell,
                                     load_json)
from benchmarks.harness.trace import Trace, given_name, short_name

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
    cell = Cell(BENCH, "yicoder_chat_sat")
    flops, yi = cell.family, cell.config["model"]
    assert flops.param_counts(yi)["total"] == 1476495360
    assert round(flops.train_flops_per_token(yi, 2048) / 1e9, 2) == 8.68
    assert flops.kv_bytes_per_token(yi) == 196608
    # the two counts the rooflines take, as the parent composed them
    n, ctx = flops.param_counts(yi)["matmul"], 48 * 700
    assert flops.decode_step_work(yi, 8, 8 * 48, ctx) == (
        2.0 * n * 8 * 48 + 4.0 * 24 * 2048 * ctx, 8 * 2 * n + ctx * 196608)
    assert flops.decode_attention_work(yi, ctx) == (4.0 * 24 * 2048 * ctx,
                                                    ctx * 196608)


# sha256 over the leaves' bytes in order, made by the parent's weights.make
# (commit bdd6837, before the layout moved into the family) on the CPU from
# the rehearsal's configuration, which has yicoder-1.5b's layout
PARENT_LEAVES = {
    ("float32", 3):
        "433f0fe4abaf59dab2c2531ae078f68255fcd17c7323b63b5bfbca502cb5a3c5",
    ("float32", 2147484030):
        "ce37935540e70b18f99ef1f4cef8aa0c81ab7a51811155d0948ec0b158a4845c",
    ("bfloat16", 3):
        "d61e612d09a51dc6becb064aba156e4f9e3bac2a4fff272dca5753fc376b2d9f",
    ("bfloat16", 2147484030):
        "29e107fdff5a19fd50759c24a3555f46dd823f45641f61dd5f643f12f810cdcc",
}


@pytest.mark.parametrize("dtype, seed", sorted(PARENT_LEAVES))
def test_the_family_makes_the_leaves_the_parent_made(dtype, seed):
    import jax.numpy as jnp
    reh = os.path.join(BENCH_DIR, "rehearsal")
    tiny = Cell(load_json(os.path.join(reh, "workloads.json")), "tiny_sat",
                traffic_dir=os.path.join(reh, "traffic"))
    yi = Cell(BENCH, "yicoder_chat_sat")
    assert tiny.family is yi.family
    leaves = weights.make(tiny.family, tiny.config["model"], seed,
                          jnp.dtype(dtype))
    digest = hashlib.sha256()
    for a in leaves:
        digest.update(np.asarray(a).tobytes())
    assert len(leaves) == 21
    assert digest.hexdigest() == PARENT_LEAVES[dtype, seed]


def test_the_published_keys_reach_the_family_whole():
    cell = Cell(BENCH, "yicoder_chat_sat")
    model = cell.config["model"]
    assert model["architectures"] == ["LlamaForCausalLM"]      # a list
    assert not set(model) & set(HARNESS_SECTIONS)
    assert set(model) | set(HARNESS_SECTIONS) >= set(cell.config) - {"model"}
    # nothing of the file is dead: a published key is either passed to the
    # program's class, or has the one value the family is built for
    fam = cell.family
    assert set(model) == set(fam.PASSED) | set(fam.STATED) | {"torch_dtype",
                                                              "dtype"}
    assert cell.config["source"] == cell.config_entry["source"]
    assert cell.config["reduced"] == cell.config_entry["reduced"]


def test_routes_are_read_from_whatever_counter_counts():
    from paddle_tpu.observability.metrics import get_registry
    want = ["rehearsal_scan:decision=pallas,reason=scan_ok"]
    with pytest.raises(RuntimeError, match="rehearsal_scan"):
        serving.check_routes(want)
    counter = get_registry().counter(
        "pallas.rehearsal_scan.route", "a kernel the harness never heard of",
        labels=("decision", "reason"))
    counter.inc(decision="xla", reason="too_small")
    with pytest.raises(RuntimeError, match="rehearsal_scan"):
        serving.check_routes(want)
    counter.inc(decision="pallas", reason="scan_ok")
    seen = serving.check_routes(want)
    assert seen["rehearsal_scan:decision=pallas,reason=scan_ok"] == 1
    assert seen["rehearsal_scan:decision=xla,reason=too_small"] == 1


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


# -- a second recorded trace (v5e, PR 30): two rounds of a matmul under a
# named scope with two tiny Pallas kernels after it, one called with name= and
# one without, then the program's paged decode kernel, its flash attention
# forward and backward and its fused AdamW, each in a program of its own -------

KERNELS = os.path.join(BENCH_DIR, "tests", "data", "kernels.xplane.pb")


@pytest.fixture(scope="module")
def kernel_trace():
    return Trace(KERNELS)


def test_the_reader_agrees_with_the_profilers_own():
    from jax.profiler import ProfileData
    mine = {p: dict(lines) for p, lines in xplane.read(KERNELS)}
    n = 0
    for plane in ProfileData.from_file(KERNELS).planes:
        for line in plane.lines:
            theirs = list(line.events)
            assert len(theirs) == len(mine[plane.name][line.name])
            for (name, _, s, e), ev in zip(mine[plane.name][line.name], theirs):
                assert (name, s, e) == (ev.name, ev.start_ns * 1e-9,
                                        (ev.start_ns + ev.duration_ns) * 1e-9)
                n += 1
    assert n > 500


def test_an_operation_keeps_the_name_the_program_gave_it(kernel_trace):
    t = kernel_trace
    given = {g for _, _, _, g in t.devices[0]["ops"]}
    assert any(g.startswith("jit(mm)/probe_scope/dot_general @ ") for g in given)
    assert any(g.startswith("jit(mm)/bench_probe_named/pallas_call @ ")
               for g in given)
    assert any(re.search(r"^jit\(block\)/pallas_call @ \S*paddle_tpu/ops/pallas/"
                         r"decode_attention\.py:\d+$", g) for g in given)
    assert given_name({"tf_op": "jit(f)/add:", "source": os.path.join(
        ROOT, "paddle_tpu", "x.py") + ":7"}) == "jit(f)/add @ paddle_tpu/x.py:7"
    assert given_name({}) == ""


def test_trace_time_by_kernel_name(kernel_trace):
    t = kernel_trace
    decode = r"pallas_call @ \S*ops/pallas/decode_attention\.py"
    flash = r"pallas_call @ \S*ops/pallas/flash_attention\.py"
    # every Pallas kernel is a tpu_custom_call and the other way round here
    assert t.op_seconds(kernel="pallas_call @ ") == t.op_seconds("tpu_custom_call")
    assert t.op_seconds("tpu_custom_call")[1] == 9
    # one decode kernel a round (the window cuts the first round's away),
    # forward and backward of flash attention in both rounds
    seconds, n = t.op_seconds(kernel=decode, module_pattern="^jit_block")
    assert n == 1 and seconds == pytest.approx(1.1349e-05, rel=1e-3)
    assert t.op_seconds(kernel=decode, module_pattern="^jit_flash") == (0.0, 0)
    assert t.op_seconds(kernel=flash)[1] == 4
    # the kernel's own name, where the program gave one
    assert t.op_seconds(kernel="/bench_probe_named/pallas_call")[1] == 1
    # both patterns have to hold
    assert t.op_seconds("tpu_custom_call", kernel="probe_scope") == (0.0, 0)
    ops = dict(t.top_ops(20))
    assert any(k.startswith("jit_block:block:tpu_custom_call:bf16[8,16,128]"
                            "@decode_attention.py:") for k in ops)
    assert "jit_mm:convolution_tanh_fusion:bf16[1024,1024]" in ops


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
    """A metric's cells are listed in ``BENCHMARK.json`` and nowhere else; its
    file says how it is read."""
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells)) for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        data = load_json(os.path.join(BENCH_DIR, "metrics", m["name"] + ".json"))
        assert set(data) == {"layer", "unit", "moves", "reader", "args", "what"}
        for key in ("layer", "unit", "moves"):
            assert data[key] == m[key], (m["name"], key)
        assert m["workloads"] and set(m["workloads"]) <= cells
        assert set(m["workloads"]) <= e2e[m["moves"]], m["name"]
        assert ":" in data["reader"]
    for cell in cells:
        assert any(cell in m["workloads"] for m in BENCH["per_layer"])
    files = {f[:-len(".json")] for f in os.listdir(os.path.join(BENCH_DIR, "metrics"))}
    assert files == {m["name"] for m in BENCH["per_layer"]}
