"""Inference engine: save -> Config/create_predictor -> IO handles -> run,
clone-per-thread sharing, persistent compile cache config."""

import os
import threading

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import inference


@pytest.fixture(scope="module")
def saved_model(tmp_path_factory):
    paddle.seed(7)
    net = paddle.nn.Sequential(
        paddle.nn.Linear(8, 16), paddle.nn.ReLU(), paddle.nn.Linear(16, 4))
    prefix = str(tmp_path_factory.mktemp("infer") / "model")
    paddle.jit.save(net, prefix,
                    input_spec=[paddle.static.InputSpec([2, 8], "float32")])
    x = np.random.default_rng(0).standard_normal((2, 8)).astype("float32")
    expected = net(paddle.to_tensor(x)).numpy()
    return prefix, x, expected


def test_predictor_handle_workflow(saved_model):
    prefix, x, expected = saved_model
    config = inference.Config(prefix)
    config.enable_memory_optim()
    predictor = inference.create_predictor(config)
    in_names = predictor.get_input_names()
    assert in_names == ["input_0"]
    h = predictor.get_input_handle(in_names[0])
    assert h.shape() == [2, 8]
    h.copy_from_cpu(x)
    assert predictor.run() is True
    out_names = predictor.get_output_names()
    out = predictor.get_output_handle(out_names[0]).copy_to_cpu()
    np.testing.assert_allclose(out, expected, rtol=1e-5, atol=1e-5)


def test_predictor_direct_run(saved_model):
    prefix, x, expected = saved_model
    predictor = inference.create_predictor(inference.Config(prefix))
    outs = predictor.run([x])
    np.testing.assert_allclose(outs[0], expected, rtol=1e-5, atol=1e-5)


def test_predictor_clone_shares_weights(saved_model):
    prefix, x, expected = saved_model
    p1 = inference.create_predictor(inference.Config(prefix))
    p2 = p1.clone()
    assert p2._param_values is p1._param_values
    results = {}

    def serve(pred, key):
        results[key] = pred.run([x])[0]

    t1 = threading.Thread(target=serve, args=(p1, "a"))
    t2 = threading.Thread(target=serve, args=(p2, "b"))
    t1.start(); t2.start(); t1.join(); t2.join()
    np.testing.assert_allclose(results["a"], expected, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(results["b"], expected, rtol=1e-5, atol=1e-5)


def test_predictor_errors(saved_model):
    prefix, _, _ = saved_model
    predictor = inference.create_predictor(inference.Config(prefix))
    with pytest.raises(RuntimeError, match="not set"):
        predictor.run()
    with pytest.raises(RuntimeError, match="run"):
        predictor.get_output_names()
    with pytest.raises(ValueError, match="model path"):
        inference.create_predictor(inference.Config())


@pytest.fixture(autouse=True)
def _keep_suite_compile_cache():
    """A Predictor with a configured cache directory moves JAX's
    persistent cache there (``utils/compile_cache.py``); put the suite's
    own directory back so the modules that follow keep their warm
    cache."""
    import jax
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", before[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      before[1])


def test_compilation_cache_dir(saved_model, tmp_path, monkeypatch):
    import jax
    prefix, x, expected = saved_model
    cache = str(tmp_path / "xla_cache")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    config = inference.Config(prefix)
    config.set_compilation_cache_dir(cache)
    predictor = inference.create_predictor(config)
    outs = predictor.run([x])
    np.testing.assert_allclose(outs[0], expected, rtol=1e-5, atol=1e-5)
    assert jax.config.jax_compilation_cache_dir == cache
    assert os.path.isdir(cache)


def test_bf16_export_precision_and_config_knobs(tmp_path):
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.static import InputSpec

    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 4))
    net.eval()
    p = str(tmp_path / "m_bf16")
    paddle.jit.save(net, p, input_spec=[InputSpec((2, 8), "float32")],
                    precision="bfloat16")
    p32 = str(tmp_path / "m_fp32")
    paddle.jit.save(net, p32, input_spec=[InputSpec((2, 8), "float32")])

    cfg = Config(p)
    cfg.enable_memory_optim(True)
    cfg.set_tpu_device_id(0)
    cfg.set_cpu_math_library_num_threads(2)
    assert cfg.memory_optim_enabled() and cfg.tpu_device_id() == 0
    assert "xla" in cfg.pass_builder().all_passes()[0]
    # pass_builder controls the real predictor-level passes
    assert "input_donation" in cfg.pass_builder().all_passes()
    cfg.delete_pass("input_donation")
    assert not cfg.memory_optim_enabled()
    cfg.set_compilation_cache_dir(str(tmp_path / "cache"))
    assert "persistent_compile_cache" in cfg.pass_builder().all_passes()
    cfg.enable_memory_optim(True)
    # ir_optim(False) GATES the passes; toggling back restores settings
    cfg.switch_ir_optim(False)
    assert not cfg.memory_optim_enabled()
    assert "persistent_compile_cache" not in cfg.pass_builder().all_passes()
    cfg.switch_ir_optim(True)
    assert cfg.memory_optim_enabled()
    assert "persistent_compile_cache" in cfg.pass_builder().all_passes()
    pred = create_predictor(cfg)
    assert pred.precision_mode() == "bfloat16"

    x = np.random.default_rng(0).standard_normal((2, 8)).astype(np.float32)
    out_bf16 = pred.run([x])[0]
    pred32 = create_predictor(Config(p32))
    assert pred32.precision_mode() is None
    out_fp32 = pred32.run([x])[0]
    # bf16 program tracks fp32 within bf16 tolerance but not exactly
    np.testing.assert_allclose(out_bf16.astype(np.float32), out_fp32,
                               atol=0.1, rtol=0.05)
    assert not np.array_equal(out_bf16.astype(np.float32), out_fp32)
    # exported weights actually stored in bf16
    import pickle
    with open(p + ".ptpu_params", "rb") as f:
        meta = pickle.load(f)
    assert str(meta["values"][0].dtype) == "bfloat16"
    # clone keeps precision metadata
    assert pred.clone().precision_mode() == "bfloat16"
