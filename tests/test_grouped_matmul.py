"""The expert layer's grouped matmul (``ops/pallas/grouped_matmul.py``): the
kernel in interpret mode against ``jax.lax.ragged_dot`` at small shapes, the
gate's refusals and what they count, and the kernel compiled for a described
v5e at the serving cell's widths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.nn.layer import experts
from paddle_tpu.observability.metrics import get_registry
from paddle_tpu.ops.pallas import _common
from paddle_tpu.ops.pallas import grouped_matmul as gm


def _operands(m, k, n, groups, dtype, seed=0):
    rng = np.random.default_rng(seed)
    xs = jnp.asarray(rng.standard_normal((m, k)), dtype)
    w = jnp.asarray(rng.standard_normal((groups, k, n)) * 0.1, dtype)
    return xs, w


def _route_counts():
    values = get_registry().snapshot().get(
        "pallas.moe_experts.route", {}).get("values", {})
    return dict(values)


def _delta(before):
    return {k: v - before.get(k, 0) for k, v in _route_counts().items()
            if v - before.get(k, 0)}


def _kernel_case(m, k, n, sizes, row_tile, tn, dtype):
    def check(monkeypatch):
        xs, w = _operands(m, k, n, len(sizes), dtype)
        sz = jnp.asarray(sizes, jnp.int32)
        tm = min(row_tile, m)
        got = gm._grouped_matmul_pallas(
            xs, w, *gm._visit_table(sz, m=m, tm=tm), tm=tm, tn=tn or n,
            interpret=True)
        want = jax.lax.ragged_dot(xs, w, sz)
        assert got.shape == (m, n) and got.dtype == xs.dtype
        tol = 1e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)
        total = int(np.sum(sizes))
        assert not np.asarray(got[total:], np.float32).any()
    return check


def _refusal_case(reason, make, partitioned=False):
    """The gate on a chip (the platform patched in) refuses ``make()``'s
    operands under ``reason``, and the call is ``ragged_dot``'s."""
    def check(monkeypatch):
        monkeypatch.setattr(_common, "on_tpu", lambda: True)
        xs, w, sz = make()
        before = _route_counts()
        with _common.partitioned_scope(partitioned):
            got = gm.grouped_matmul(xs, w, sz)
        assert _delta(before) == {f"decision=xla,reason={reason}": 1}
        np.testing.assert_array_equal(
            np.asarray(got, np.float32),
            np.asarray(jax.lax.ragged_dot(xs, w, sz), np.float32))
    return check


def _bf16(m, k, n, groups=4):
    def make():
        xs, w = _operands(m, k, n, groups, jnp.bfloat16)
        return xs, w, jnp.asarray([m // groups] * groups, jnp.int32)
    return make


def _f32():
    xs, w = _operands(32, 128, 128, 4, jnp.float32)
    return xs, w, jnp.asarray([8] * 4, jnp.int32)


def _on_cpu(monkeypatch):
    xs, w, sz = _bf16(32, 128, 128)()
    before = _route_counts()
    with _common.partitioned_scope(True):
        gm.grouped_matmul(xs, w, sz)
    gm.grouped_matmul(xs, w, sz)
    assert _delta(before) == {"decision=xla,reason=pallas_unavailable": 2}


def _accepted(monkeypatch):
    """What the gate accepts goes through the kernel (here interpreted) and
    counts ``grouped_ok``; the geometry rule itself accepts the cell's
    shapes."""
    assert gm._geometry_reason(
        jax.ShapeDtypeStruct((1024, 2048), jnp.bfloat16),
        jax.ShapeDtypeStruct((64, 2048, 1536), jnp.bfloat16)) == "grouped_ok"
    monkeypatch.setattr(gm, "_moe_route_reason", gm._geometry_reason)
    xs, w, sz = _bf16(48, 256, 128)()
    sz = sz.at[1].set(0)
    before = _route_counts()
    got = jax.jit(gm.grouped_matmul)(xs, w, sz)
    assert _delta(before) == {"decision=pallas,reason=grouped_ok": 1}
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(jax.lax.ragged_dot(xs, w, sz), np.float32),
        rtol=2e-2, atol=2e-2)


def _through_kernel(monkeypatch, row_tile=16):
    monkeypatch.setattr(gm, "_moe_route_reason", lambda *a: "grouped_ok")
    monkeypatch.setattr(gm, "_ROW_TILE", row_tile)


def _gradient(monkeypatch):
    """``jax.grad`` through the kernel's route gives ``ragged_dot``'s
    cotangents, the tail rows' among them (a scalar-prefetch
    ``pallas_call`` has no reverse-mode rule of its own)."""
    xs, w = _operands(40, 128, 256, 4, jnp.float32)
    sz = jnp.asarray([9, 0, 20, 5], jnp.int32)
    pull = jnp.asarray(np.random.default_rng(1).standard_normal((40, 256)),
                       jnp.float32)

    def grads(body):
        return jax.jit(jax.grad(
            lambda a, b: jnp.sum(body(a, b, sz) * pull), (0, 1)))(xs, w)
    want = grads(jax.lax.ragged_dot)
    _through_kernel(monkeypatch)
    before = _route_counts()
    got = grads(gm.grouped_matmul)
    assert _delta(before) == {"decision=pallas,reason=grouped_ok": 1}
    for g, r in zip(got, want):
        assert g.shape == r.shape and np.asarray(r).any()
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-5, atol=1e-5)


def _layer_operands(held, seed=3):
    rng = np.random.default_rng(seed)
    n, h, mid, e, k = 24, 128, 256, 8, 2
    u = jnp.asarray(rng.standard_normal((n, h)), jnp.float32)
    chosen = jnp.asarray(
        np.stack([rng.permutation(e)[:k] for _ in range(n)]), jnp.int32)
    weights = jnp.asarray(rng.random((n, k)), jnp.float32)
    w1, w3 = (jnp.asarray(rng.standard_normal((held[1], h, mid)) * 0.1,
                          jnp.float32) for _ in range(2))
    w2 = jnp.asarray(rng.standard_normal((held[1], mid, h)) * 0.1,
                     jnp.float32)
    return u, chosen, weights, w1, w3, w2


def _layer_through_kernel(held):
    """``grouped_experts`` through the kernel and through ``ragged_dot``:
    the same rows, combined the same way, absent experts' rows zero."""
    def check(monkeypatch):
        args = _layer_operands(held)
        want = experts.grouped_experts(*args, held)
        _through_kernel(monkeypatch)
        before = _route_counts()
        got = experts.grouped_experts(*args, held)
        assert _delta(before) == {"decision=pallas,reason=grouped_ok": 3}
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)
    return check


def _layer_gradient(monkeypatch):
    """The layer trains through either body: the planes' and the rows'
    gradients of a held part of the experts."""
    held = (2, 4)
    u, chosen, weights, *planes = _layer_operands(held)

    def grads():
        return jax.grad(lambda u, *p: jnp.sum(experts.grouped_experts(
            u, chosen, weights, *p, held) ** 2), (0, 1, 2, 3))(u, *planes)
    want = grads()
    _through_kernel(monkeypatch)
    for g, r in zip(grads(), want):
        assert np.asarray(r).any()
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-4, atol=1e-5)


def _stale_tail(monkeypatch):
    """What a grouped matmul leaves in an absent expert's rows does not
    reach the layer's result (on the chip ``ragged_dot`` leaves what the
    memory held there)."""
    held = (2, 4)
    args = _layer_operands(held)
    want = experts.grouped_experts(*args, held)

    def dirty(xs, w, sizes):
        y = jax.lax.ragged_dot(xs, w, sizes)
        row = jnp.arange(y.shape[0])[:, None]
        return jnp.where(row < jnp.sum(sizes), y, jnp.nan)
    monkeypatch.setattr(experts, "grouped_matmul", dirty)
    got = experts.grouped_experts(*args, held)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


_BF, _F32 = jnp.bfloat16, jnp.float32
CASES = {
    # the kernel against ragged_dot
    "empty_groups": _kernel_case(64, 128, 256, [10, 0, 20, 0, 34, 0], 16,
                                 128, _BF),
    "one_group_takes_every_row": _kernel_case(64, 128, 128, [0, 0, 64, 0],
                                              16, None, _BF),
    "tail_comes_back_zero": _kernel_case(64, 256, 128, [5, 7, 0, 3], 16,
                                         None, _F32),
    "no_group_has_a_row": _kernel_case(48, 128, 128, [0, 0, 0], 16, None,
                                       _BF),
    "groups_straddle_row_tiles": _kernel_case(96, 128, 256, [3, 40, 1, 52],
                                              16, 128, _F32),
    "rows_no_multiple_of_the_tile": _kernel_case(50, 256, 128,
                                                 [11, 0, 26, 9], 16, None,
                                                 _F32),
    "fewer_rows_than_a_tile": _kernel_case(20, 128, 128, [7, 13], 128, None,
                                           _BF),
    "k_over_n": _kernel_case(64, 384, 128, [20, 30, 14], 32, None, _BF),
    "n_over_k": _kernel_case(64, 128, 384, [20, 30, 14], 32, 128, _BF),
    # the gate
    "refuses_float32": _refusal_case("dtype_not_bf16", _f32),
    "refuses_k_align": _refusal_case("k_align", _bf16(32, 192, 128)),
    "refuses_n_align": _refusal_case("n_align", _bf16(32, 128, 192)),
    "refuses_vmem_budget": _refusal_case(
        "vmem_budget", _bf16(8, (gm._STREAM_BUDGET >> 9) + 128, 128, 1)),
    "refuses_no_rows": _refusal_case("no_rows", _bf16(0, 128, 128)),
    "refuses_partitioned": _refusal_case(
        "gspmd_partitioned", _bf16(32, 128, 128), partitioned=True),
    "flag_off_counts_pallas_unavailable": lambda mp: (
        mp.setattr(_common, "flag", lambda name: False),
        _refusal_case("pallas_unavailable", _bf16(32, 128, 128))(mp)),
    "cpu_counts_pallas_unavailable": _on_cpu,
    "accepted_counts_grouped_ok": _accepted,
    "gradient_is_ragged_dots": _gradient,
    # the layer through either body
    "layer_holds_every_expert": _layer_through_kernel((0, 8)),
    "layer_holds_some_experts": _layer_through_kernel((2, 4)),
    "layer_gradient_through_kernel": _layer_gradient,
    "layer_ignores_stale_tail_rows": _stale_tail,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_grouped_matmul(case, monkeypatch):
    CASES[case](monkeypatch)


def test_reasons_are_a_closed_set():
    assert len(set(gm.MOE_ROUTE_REASONS)) == len(gm.MOE_ROUTE_REASONS)
    xs, w, sz = _bf16(32, 128, 128)()
    assert gm._moe_route_reason(xs, w) in gm.MOE_ROUTE_REASONS


# -- compiled for the chip, without the chip ---------------------------------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("m,k,n", [(1024, 2048, 1536), (1024, 1536, 2048),
                                   (1000, 2048, 1536), (2048, 2304, 1024),
                                   (2048, 1024, 2304)])
def test_compiles_for_the_v5e_at_the_cell_widths(one_chip, m, k, n):
    """Mosaic takes the kernel at the serving cell's shapes and the tiles
    the module's rule gives them (interpret mode cannot show a refused
    slice or a VMEM overrun)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    groups = 64

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def call(xs, w, sizes):
        return gm._grouped_matmul_pallas(
            xs, w, *gm._visit_table(sizes, m=m, tm=gm._row_tile(m)),
            tm=gm._row_tile(m), tn=gm._col_tile(k, n, 2), interpret=False)

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        compiled = jax.jit(call).lower(
            spec((m, k), jnp.bfloat16), spec((groups, k, n), jnp.bfloat16),
            spec((groups,), jnp.int32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()
    assert "tpu_custom_call" in compiled.as_text()


def _compile_for_the_chip(fn, *specs, donate=()):
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        return jax.jit(fn, donate_argnums=donate).lower(*specs).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def test_kda_decode_kernel_compiles_for_the_v5e_in_place(one_chip,
                                                         monkeypatch):
    """Mosaic takes the delta-rule decode kernel at the served widths (256
    rows, 32 heads of 128 x 128, a four-layer arena of 257 slots), and the
    arena goes in and comes out as one buffer: no second 2.2 GB copy."""
    from paddle_tpu.ops.pallas import kda
    monkeypatch.setattr(kda, "on_tpu", lambda: True)
    b, h, d = 256, 32, 128
    spec = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)

    def step(arena, rows, live, q, k, v, g, beta):
        return kda._decode_pallas(arena, 2, rows, live,
                                  *kda._decode_operands(q, k, v, g, beta))

    arena = spec((257, 4, h, d, d))
    compiled = _compile_for_the_chip(
        step, arena, spec((b,), jnp.int32), spec((b,), jnp.bool_),
        *[spec((b, h, d))] * 4, spec((b, h)), donate=0)
    assert "tpu_custom_call" in compiled.as_text()
    memory = compiled.memory_analysis()
    nbytes = 257 * 4 * h * d * d * 4
    assert memory.alias_size_in_bytes >= nbytes
    assert memory.temp_size_in_bytes < nbytes // 8


def test_latent_decode_kernel_compiles_for_the_v5e(one_chip, monkeypatch):
    """Mosaic takes the streaming kernel over the latent arena at the served
    widths: 32 query heads over rows of 576 values resting as 640, values
    the first 512."""
    from paddle_tpu.ops.pallas import decode_attention as da
    monkeypatch.setattr(da, "on_tpu", lambda: True)
    spec = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    w = da.paged_latent_shape(1, 16, 576)[-1]

    def step(q, arena, tables, lens):
        return da._latent_stream(q, arena, tables, lens, 512, 192 ** -0.5)

    compiled = _compile_for_the_chip(
        step, spec((256, 32, w), jnp.bfloat16),
        spec((32769, 16, w), jnp.bfloat16), spec((256, 128), jnp.int32),
        spec((256,), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_decode_kernel_compiles_for_the_v5e_in_place(one_chip,
                                                         monkeypatch):
    """Mosaic takes the state-space decode kernel at the served widths (128
    rows, 32 heads of 128 x 256 in 2 groups, a four-layer arena of 129
    slots), and the arena goes in and comes out as one buffer: no second
    2.2 GB copy."""
    from paddle_tpu.ops.pallas import ssd
    monkeypatch.setattr(ssd, "on_tpu", lambda: True)
    b, h, p, g, n = 128, 32, 128, 2, 256
    spec = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)

    def step(arena, rows, live, xa, bc):
        return ssd._decode_pallas(arena, 2, rows, live, xa, bc)

    compiled = _compile_for_the_chip(
        step, spec((129, 4, h, p, n)), spec((b,), jnp.int32),
        spec((b,), jnp.bool_), spec((b, 2, h, p)), spec((b, g, 2, n)),
        donate=0)
    assert "tpu_custom_call" in compiled.as_text()
    memory = compiled.memory_analysis()
    nbytes = 129 * 4 * h * p * n * 4
    assert memory.alias_size_in_bytes >= nbytes
    assert memory.temp_size_in_bytes < nbytes // 8
