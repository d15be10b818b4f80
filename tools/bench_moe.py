"""On-chip MoE block bench: dense [T,E,C]-einsum dispatch vs the
Megablocks-style scatter dispatch, capacity/expert sweeps, and an
expert-compute-only probe that isolates dispatch+combine cost
(VERDICT r3 item 4; reference moe_layer.py:263's global_scatter role).

Usage: python tools/bench_moe.py            # full sweep (TPU)
"""
import sys
sys.path.insert(0, "/root/repo")
import time

import numpy as np


def bench_case(E, cf, mode, T=8192, D=2048, F=8192, top_k=2, steps=(2, 8)):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.incubate.distributed.models.moe import MoELayer

    paddle.seed(0)
    layer = MoELayer(d_model=D, d_hidden=F, num_experts=E, top_k=top_k,
                     capacity_factor=cf, dispatch_mode=mode)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((T, D)), jnp.bfloat16)
    layer.to(dtype="bfloat16")
    params = [p for p in layer.parameters()]

    def fn(pv, xa, k):
        saved = [p._value for p in params]
        try:
            for p, a in zip(params, pv):
                p._value = a

            def body(carry, _):
                out = layer(paddle.Tensor(xa + carry))._value
                m = out.mean().astype(xa.dtype)
                return jnp.zeros_like(xa) + m * 1e-6, m

            _, outs = jax.lax.scan(body, jnp.zeros_like(xa), None,
                                   length=k)
            return outs.sum()
        finally:
            for p, s in zip(params, saved):
                p._value = s

    jfn = jax.jit(fn, static_argnums=2)
    pv = [p._value for p in params]

    def run(k):
        np.asarray(jfn(pv, x, k))

    run(steps[0])
    t0 = time.perf_counter()
    run(steps[0])
    t_s = time.perf_counter() - t0
    run(steps[1])
    t0 = time.perf_counter()
    run(steps[1])
    t_l = time.perf_counter() - t0
    ms = (t_l - t_s) / (steps[1] - steps[0]) * 1e3
    C = layer.gate.capacity(T)
    # useful expert FLOPs (in+out matmuls over the capacity buffers)
    flops = 2 * E * C * D * F * 2
    return ms, C, flops


def bench_expert_only(E, cf, T=8192, D=2048, F=8192, top_k=2,
                      steps=(2, 8)):
    """The two expert einsums on a pre-shaped [E, C, D] buffer — no
    gate, no dispatch/combine."""
    import jax
    import jax.numpy as jnp
    C = max(int(cf * T * top_k / E), top_k)
    rng = np.random.default_rng(0)
    xe = jnp.asarray(rng.standard_normal((E, C, D)), jnp.bfloat16)
    wi = jnp.asarray(rng.standard_normal((E, D, F)) * 0.02, jnp.bfloat16)
    wo = jnp.asarray(rng.standard_normal((E, F, D)) * 0.02, jnp.bfloat16)

    # weights ride as ARGUMENTS: closed-over arrays bake into the HLO as
    # constants (268 MB of expert weights inside the program text)
    def fn(xa, wia, woa, k):
        def body(carry, _):
            h = jnp.einsum("ecd,edf->ecf", xa + carry, wia)
            h = jax.nn.gelu(h)
            out = jnp.einsum("ecf,efd->ecd", h, woa)
            m = out.mean().astype(xa.dtype)
            return jnp.zeros_like(xa) + m * 1e-6, m

        _, outs = jax.lax.scan(body, jnp.zeros_like(xa), None, length=k)
        return outs.sum()

    jfn = jax.jit(fn, static_argnums=3)

    def run(k):
        np.asarray(jfn(xe, wi, wo, k))

    run(steps[0])
    t0 = time.perf_counter()
    run(steps[0])
    t_s = time.perf_counter() - t0
    run(steps[1])
    t0 = time.perf_counter()
    run(steps[1])
    t_l = time.perf_counter() - t0
    return (t_l - t_s) / (steps[1] - steps[0]) * 1e3


def main():
    peak = 197e12
    print(f"{'case':<28}{'C':>6}{'dense ms':>10}{'scatter ms':>11}"
          f"{'expert ms':>10}{'scat MFU':>9}")
    for E, cf in [(8, 1.25), (16, 1.25), (32, 1.25), (8, 1.0), (8, 2.0)]:
        exp_ms = bench_expert_only(E, cf)
        d_ms, C, flops = bench_case(E, cf, "dense")
        s_ms, _, _ = bench_case(E, cf, "scatter")
        mfu = flops / (s_ms / 1e3) / peak
        print(f"E={E:<3} top2 cf={cf:<12}{C:>6}{d_ms:>10.2f}{s_ms:>11.2f}"
              f"{exp_ms:>10.2f}{mfu:>9.3f}")


if __name__ == "__main__":
    main()
