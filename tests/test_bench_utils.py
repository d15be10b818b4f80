"""run_steps multi-step scan, low-precision optimizer dtype stability, and
the jaxpr MXU-FLOPs counter (``utils/flops.py``)."""

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu import nn
from paddle_tpu.jit.train_step import TrainStep
from paddle_tpu.utils.flops import count_matmul_flops


def _mlp():
    paddle.seed(7)
    return nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))


def _loss_fn(net, x, y):
    return F.cross_entropy(net(x), y).mean()


def _batch():
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((4, 8)).astype(np.float32))
    y = paddle.to_tensor(rng.integers(0, 4, (4,)).astype(np.int64))
    return x, y


def test_run_steps_matches_sequential_calls():
    x, y = _batch()

    net_a = _mlp()
    opt_a = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                      parameters=net_a.parameters())
    step_a = TrainStep(net_a, _loss_fn, opt_a)
    for _ in range(5):
        loss_seq = step_a(x, y)

    net_b = _mlp()
    opt_b = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                      parameters=net_b.parameters())
    step_b = TrainStep(net_b, _loss_fn, opt_b)
    loss_scan = step_b.run_steps(x, y, steps=5)

    np.testing.assert_allclose(float(loss_seq), float(loss_scan),
                               rtol=1e-5, atol=1e-6)
    for pa, pb in zip(net_a.parameters(), net_b.parameters()):
        np.testing.assert_allclose(np.asarray(pa._value),
                                   np.asarray(pb._value),
                                   rtol=1e-5, atol=1e-6)


def test_run_steps_trains_and_is_resumable():
    net = _mlp()
    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    parameters=net.parameters())
    step = TrainStep(net, _loss_fn, opt)
    x, y = _batch()
    first = float(step.run_steps(x, y, steps=3))
    later = float(step.run_steps(x, y, steps=3))
    assert later < first


@pytest.mark.parametrize("opt_name", ["Momentum", "SGD"])
def test_low_precision_update_keeps_param_dtype(opt_name):
    # fp32 lr must not promote bf16 params (regression: second step of a
    # bf16 conv net crashed with a conv dtype mismatch)
    net = _mlp()
    net.to(dtype="bfloat16")
    if opt_name == "Momentum":
        opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                        parameters=net.parameters())
    else:
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())
    step = TrainStep(net, _loss_fn, opt)
    x, y = _batch()
    x = x.astype("bfloat16")
    for _ in range(2):  # the second step sees the updated params
        step(x, y)
    for p in net.parameters():
        assert str(p._value.dtype) == "bfloat16"


def test_count_matmul_flops_dot_and_conv():
    import jax.numpy as jnp

    a = jnp.ones((32, 64), jnp.float32)
    b = jnp.ones((64, 16), jnp.float32)
    assert count_matmul_flops(lambda x, y: x @ y, a, b) == 2 * 32 * 64 * 16

    x = jnp.ones((2, 8, 16, 16), jnp.float32)   # NCHW
    w = jnp.ones((4, 8, 3, 3), jnp.float32)     # OIHW
    got = count_matmul_flops(
        lambda xa: F.conv2d(paddle.Tensor(xa), paddle.Tensor(w),
                            padding=1)._value, x)
    assert got == 2 * (2 * 4 * 16 * 16) * 8 * 9


def test_count_matmul_flops_scan_multiplies():
    import jax
    import jax.numpy as jnp

    a = jnp.ones((16, 16), jnp.float32)

    def fn(x):
        def body(c, _):
            return c @ a, None
        out, _ = jax.lax.scan(body, x, None, length=5)
        return out

    assert count_matmul_flops(fn, a) == 5 * 2 * 16 ** 3
