"""Quantized weight arenas (int8/int4) through the serving stack:
loader/observer scale unification, the weight_dtype/kv_cache_dtype
validation cross products, the tier-1 lockstep parity trace (an
int8-weight engine must make IDENTICAL scheduling decisions to the
float engine while its greedy tokens agree above threshold and its
modeled weight sweep shrinks), composition with spec-decode + LoRA +
dispatch-ahead, and the LLMPredictor surface.

Tier-1 budget discipline: ONE module-scoped tiny model shared by every
test; the parity trace reuses the kv_int8 trace shape (same prompts,
same slot pressure) so both quantization disciplines are scored by the
same yardstick."""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import models
from paddle_tpu.inference.llm import (LLMPredictor,
                                      build_weight_quant_plan,
                                      normalize_weight_dtype)
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.observability.flightrec import FlightRecorder
from paddle_tpu.observability.metrics import MetricsRegistry
from scripted_drafter import ScriptedDrafter

P, C = 6, 32


@pytest.fixture(scope="module")
def netm():
    paddle.seed(2024)
    cfg = models.tiny_llama_config()
    net = models.LlamaForCausalLM(cfg)
    net.eval()
    return cfg, net


def _trace_prompts(cfg):
    """The kv_int8 parity trace's prompt mix: 4 mixed-length requests,
    two sharing one full block_len=4 prefix block."""
    rng = np.random.default_rng(7)
    shared = rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
    specs = [(6, 7), (5, 2), (5, 7), (4, 4)]
    prompts = []
    for i, (n, _m) in enumerate(specs):
        ids = rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
        if i in (0, 2):
            ids[:4] = shared
        prompts.append(ids)
    return prompts, specs


def _build(net, wd, **kw):
    eng = ServingEngine(net, num_slots=2, prompt_len=P, max_cache_len=C,
                        steps_per_call=3, block_len=4, chunk_len=4,
                        compute_dtype="float32", weight_dtype=wd,
                        registry=MetricsRegistry(), **kw)
    return eng


# -- validation cross products -----------------------------------------------

def test_weight_dtype_validation(netm):
    cfg, net = netm
    # unknown / non-int8-int4 integer dtypes name weight_dtype's OWN
    # allowed set (distinct from kv_cache_dtype's)
    with pytest.raises(ValueError, match="weight_dtype"):
        normalize_weight_dtype("int7")
    with pytest.raises(ValueError, match="int8.*int4|int4.*int8"):
        normalize_weight_dtype("int32")
    # float spellings mean full precision (None), quant spellings
    # canonicalize
    assert normalize_weight_dtype(None) is None
    assert normalize_weight_dtype("bfloat16") is None
    assert normalize_weight_dtype("float32") is None
    assert normalize_weight_dtype("int8") == "int8"
    assert normalize_weight_dtype("int4") == "int4"
    with pytest.raises(ValueError, match="weight_dtype"):
        _build(net, "uint8")


def test_kv_cache_dtype_rejects_int4_with_hint(netm):
    """The KV cache has no int4 discipline: kv_cache_dtype='int4' must
    reject CLEARLY, pointing at weight_dtype='int4' (the knob that does
    exist) — the two dtype arguments report distinct allowed sets."""
    cfg, net = netm
    with pytest.raises(ValueError, match="weight_dtype='int4'"):
        _build(net, None, kv_cache_dtype="int4")
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        _build(net, None, kv_cache_dtype="int16")


def test_int4_weights_compose_with_int8_kv(netm):
    """int4 weights + int8 KV is a legal (and the most compressed)
    configuration; both dtype surfaces report through stats()."""
    cfg, net = netm
    eng = _build(net, "int4", kv_cache_dtype="int8")
    assert eng.weight_dtype == "int4"
    assert eng.kv_cache_dtype == "int8"
    st = eng.stats()
    assert st["weight_dtype"] == "int4"
    assert st["kv_cache_dtype"] == "int8"


# -- scale-rule unification --------------------------------------------------

def test_observer_scales_match_loader_bitexact(netm):
    """PTQ calibration and the serving loader share ONE quant rule:
    the plan's scales must equal the PerChannelAbsmaxObserver path
    BIT-EXACTLY (same floor-then-divide order), and the codes must be
    quantize_channelwise of those scales."""
    from paddle_tpu.quantization.observers import (
        PerChannelAbsmaxObserver, absmax_to_scales, quantize_channelwise)
    cfg, net = netm
    plan = build_weight_quant_plan(net, "int8")
    layers = net.quant_projections()
    checked = 0
    for li, target, _pos, codes, scales in plan.entries:
        lin = layers[li][target]
        obs = PerChannelAbsmaxObserver(quant_axis=-1, bit_length=8)
        obs.observe(lin.weight)
        want_scales = absmax_to_scales(obs.scales()._value, 8)
        np.testing.assert_array_equal(np.asarray(scales),
                                      np.asarray(want_scales))
        want_codes = quantize_channelwise(lin.weight._value, want_scales,
                                          8, quant_axis=-1)
        np.testing.assert_array_equal(np.asarray(codes),
                                      np.asarray(want_codes))
        assert np.asarray(codes).dtype == np.int8
        checked += 1
    # every hot projection of every layer is in the plan
    assert checked == len(layers) * 7


def test_int4_plan_packs_and_roundtrips(netm):
    """The int4 plan's code planes are byte-packed ([K//2, N]) and
    unpack to codes within the int4 range, derived from the same rule
    at bit_length=4."""
    from paddle_tpu.ops.pallas.quantized_matmul import unpack_int4
    cfg, net = netm
    plan8 = build_weight_quant_plan(net, "int8")
    plan4 = build_weight_quant_plan(net, "int4")
    assert plan4.bits == 4 and plan8.bits == 8
    by_key8 = {(li, t): (c, s) for li, t, _p, c, s in plan8.entries}
    for li, target, _pos, codes, scales in plan4.entries:
        c8, _s8 = by_key8[(li, target)]
        assert codes.shape == (c8.shape[0] // 2, c8.shape[1])
        unpacked = np.asarray(unpack_int4(codes))
        assert unpacked.min() >= -7 and unpacked.max() <= 7
    assert plan4.bytes_swept() < plan8.bytes_swept()


# -- the tier-1 lockstep parity trace ----------------------------------------

@pytest.fixture(scope="module")
def trace_runs(netm):
    """ONE run of the parity trace per weight dtype, shared by every
    trace-shaped test in the module (tier-1 budget: each engine build
    compiles the full serving program set).  float and int8 step
    LOCKSTEP so per-step block-table equality is observed while both
    schedulers are live; int4 free-runs the same trace."""
    cfg, net = netm
    prompts, specs = _trace_prompts(cfg)

    def build(wd):
        rec = FlightRecorder(clock=lambda: 0.0)
        eng = _build(net, wd, flight_recorder=rec)
        reqs = [eng.submit(p, max_new_tokens=m, arrival_time=0.0)
                for p, (_n, m) in zip(prompts, specs)]
        return {"eng": eng, "reqs": reqs, "rec": rec}

    runs = {None: build(None), "int8": build("int8")}
    lockstep_ok = True
    for _ in range(200):
        fin_f = [r.request_id
                 for r in runs[None]["eng"].step(now=0.0)]
        fin_q = [r.request_id
                 for r in runs["int8"]["eng"].step(now=0.0)]
        lockstep_ok = lockstep_ok and fin_f == fin_q and bool(
            np.array_equal(runs[None]["eng"]._tables,
                           runs["int8"]["eng"]._tables))
        if all(r.state == "finished" for r in runs[None]["reqs"]):
            break
    runs["int4"] = build("int4")
    for _ in range(200):
        runs["int4"]["eng"].step(now=0.0)
        if all(r.state == "finished" for r in runs["int4"]["reqs"]):
            break
    return {"runs": runs, "lockstep_ok": lockstep_ok}


def test_int8_weight_parity_trace_and_scheduling(netm, trace_runs):
    """The weight-quant acceptance contract on the kv_int8 trace: an
    engine with ``weight_dtype="int8"`` must make IDENTICAL scheduling
    decisions to the full-precision engine — admissions, block tables,
    dispatch counts and the flight-recorder event sequence are
    token-independent with eos=None — while its greedy tokens agree
    above threshold (int8 weight noise may flip a near-tie argmax) and
    its modeled weight sweep is strictly below the float engine's."""
    f, q = trace_runs["runs"][None], trace_runs["runs"]["int8"]
    e_f, r_f, rec_f = f["eng"], f["reqs"], f["rec"]
    e_q, r_q, rec_q = q["eng"], q["reqs"], q["rec"]
    assert e_f.weight_dtype == "float32"
    assert e_q.weight_dtype == "int8"
    # per-step finish lists and block tables matched while stepping
    assert trace_runs["lockstep_ok"]
    assert all(r.state == "finished" for r in r_f)
    assert all(r.state == "finished" for r in r_q)
    s_f, s_q = e_f.stats(), e_q.stats()
    for key in ("prefills", "prefill_chunks", "decode_steps",
                "block_dispatches", "prefix_hits", "prefix_misses",
                "peak_blocks_in_use", "finished"):
        assert s_f[key] == s_q[key], key
    # the flight recorders saw the same lifecycle, event for event
    seq_f = [(e.step, e.request, e.kind) for e in rec_f.events()]
    seq_q = [(e.step, e.request, e.kind) for e in rec_q.events()]
    assert seq_f == seq_q
    agree = np.concatenate([a.output == b.output
                            for a, b in zip(r_f, r_q)])
    assert agree.mean() >= 0.9
    # the whole point: quantized projections sweep strictly fewer
    # modeled bytes per forward (embeddings/norms/lm_head stay float,
    # so the ratio is well under the raw 4x of the planes themselves)
    assert s_q["weight_dtype"] == "int8"
    assert 0 < s_q["weight_bytes_swept"] < s_f["weight_bytes_swept"]
    # both engines charged the same number of forwards
    assert s_f["weight_bytes_swept"] % e_f._weight_sweep_bytes == 0
    assert (s_f["weight_bytes_swept"] // e_f._weight_sweep_bytes
            == s_q["weight_bytes_swept"] // e_q._weight_sweep_bytes)


def test_int4_engine_runs_trace_and_bytes_order(netm, trace_runs):
    """int4 weights run the same trace with the same scheduling; the
    modeled weight sweep orders strictly bf16/f32 > int8 > int4 (the
    bench A/B's deterministic gate, in miniature)."""
    sweeps = {}
    for wd in (None, "int8", "int4"):
        run = trace_runs["runs"][wd]
        assert all(r.state == "finished" for r in run["reqs"])
        st = run["eng"].stats()
        sweeps[wd] = (st["weight_bytes_swept"], st["block_dispatches"])
    # identical dispatch counts across arms, strictly decreasing bytes
    assert sweeps[None][1] == sweeps["int8"][1] == sweeps["int4"][1]
    assert sweeps[None][0] > sweeps["int8"][0] > sweeps["int4"][0] > 0


# -- composition -------------------------------------------------------------

def test_weight_quant_composes_spec_lora_async(netm):
    """One engine holding every serving feature at once: int8 weights +
    dispatch-ahead depth 2 + a LoRA-adapter request + a spec-decode
    request.  All requests must finish with exact token budgets; the
    spec verify and LoRA gather paths must actually run (their counters
    advance) while the weight planes sweep."""
    from paddle_tpu.inference.lora import AdapterStore, LoraAdapter
    cfg, net = netm
    reg = MetricsRegistry()
    store = AdapterStore(net, slots=2, max_rank=4, dtype="float32",
                         registry=reg)
    store.register(LoraAdapter.random(cfg, "a", rank=2, seed=3,
                                      scale=0.2))
    prompts, _specs = _trace_prompts(cfg)
    pat = np.random.default_rng(11).integers(
        0, cfg.vocab_size, (3,)).astype(np.int32)
    spec_ids = np.tile(pat, 2)
    # the spec row's own greedy stream under int8 weights, from a plain
    # engine: the scripted drafter proposes it with wrong tokens
    # planted, so the row really drafts, accepts and rolls back
    ref = _build(net, "int8")
    r_ref = ref.submit(spec_ids, max_new_tokens=8)
    ref.run(max_iters=200)
    # steps_per_call=1 so the drafter gets a drafting opportunity
    # every iteration (the spec suite's discipline)
    eng = ServingEngine(net, num_slots=2, prompt_len=P, max_cache_len=C,
                        steps_per_call=1, block_len=4, chunk_len=4,
                        compute_dtype="float32", weight_dtype="int8",
                        adapter_store=store, async_depth=2,
                        registry=reg,
                        drafter=ScriptedDrafter([(spec_ids, r_ref.output)],
                                                cfg.vocab_size))
    r_lora = eng.submit(prompts[0], max_new_tokens=8, arrival_time=0.0,
                        adapter="a")
    r_spec = eng.submit(spec_ids, max_new_tokens=8,
                        arrival_time=0.0, spec_decode=2)
    r_plain = eng.submit(prompts[2], max_new_tokens=8, arrival_time=0.0)
    done = eng.run(max_iters=200)
    assert {r.request_id for r in done} == \
        {r_lora.request_id, r_spec.request_id, r_plain.request_id}
    for r in (r_lora, r_spec, r_plain):
        assert r.state == "finished"
        assert len(r.output) == 8
    np.testing.assert_array_equal(r_spec.output, r_ref.output)
    reg = eng.metrics_registry
    assert reg.get("serving.spec.verify_steps").value() > 0
    assert reg.get("serving.spec.accepted_tokens").value() > 0
    assert reg.get("serving.lora.gathers").value() > 0
    assert reg.get("serving.weights.bytes_swept").value() > 0
    assert reg.get("serving.weights.quant_dtype").value(dtype="int8") == 1


# -- LLMPredictor ------------------------------------------------------------

@pytest.mark.slow
def test_llm_predictor_weight_dtype(netm):
    """The static-batch predictor takes the same weight_dtype= knob:
    int8 weights through _build_serving_fns (placeholder params + plan
    planes on the positional list), tokens agreeing with the float
    predictor above threshold; save() refuses (the artifact pickle has
    no plan layout)."""
    cfg, net = netm
    rng = np.random.default_rng(23)
    ids = rng.integers(1, cfg.vocab_size, (2, P)).astype(np.int32)

    def run(wd):
        pred = LLMPredictor(net, batch=2, prompt_len=P, max_cache_len=C,
                            steps_per_call=4, compute_dtype="float32",
                            weight_dtype=wd)
        first = pred.start(paddle.to_tensor(ids))
        toks = pred.decode(8)
        return pred, np.concatenate([first[:, None], toks], axis=1)

    p_f, t_f = run(None)
    p_q, t_q = run("int8")
    assert p_f.weight_dtype is None and p_q.weight_dtype == "int8"
    assert t_f.shape == t_q.shape == (2, 9)
    assert (t_f == t_q).mean() >= 0.9
    with pytest.raises(NotImplementedError, match="weight_dtype"):
        p_q.save("/tmp/_wq_pred.ptpu_llm")


@pytest.mark.slow
def test_gpt_projections_route_through_wquant(netm):
    """The GPT family quantizes too (qkv/out/fc_in/fc_out): forward
    logits under an active int8 context match the float forward within
    quantization tolerance — proof the fused-QKV sites divert."""
    paddle.seed(7)
    gcfg = models.tiny_gpt_config()
    gpt = models.GPTForCausalLM(gcfg)
    gpt.eval()
    layers = gpt.quant_projections()
    assert sorted(layers[0].keys()) == ["fc_in", "fc_out", "out_proj",
                                        "qkv_proj"]
    plan = build_weight_quant_plan(gpt, "int8")
    assert len(plan.entries) == len(layers) * 4
    from paddle_tpu.models.wquant import wquant_context
    ids = paddle.to_tensor(
        np.random.default_rng(5).integers(
            1, gcfg.vocab_size, (1, 8)).astype(np.int64))
    ref = np.asarray(gpt(ids)._value, np.float32)
    with wquant_context(plan.bind(plan.flat_values())):
        out = np.asarray(gpt(ids)._value, np.float32)
    assert out.shape == ref.shape
    # int8 per-channel weight noise, not garbage: close but not equal
    assert np.abs(out - ref).max() < 0.15 * max(1.0, np.abs(ref).max())
    assert not np.array_equal(out, ref)
