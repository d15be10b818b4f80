"""The paged cache's block write (``models.generation.paged_span_write``)
against the row scatter it replaces, written out here: every block but the
trash block must come out bit for bit the same, for starts on and off a block
boundary, pad tails of every length, 3-D, 4-D and latent arenas, the int8
cache's codes and scales, per-row starts, and tables whose entries run past a
slot's blocks.  The write compiled for a described v5e updates the arena in
place.  Then one tiny engine a family serves the same tokens with the row
scatter put back in its place."""

import importlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness.spec import HARNESS_SECTIONS
from paddle_tpu.inference import ServingEngine
from paddle_tpu.models import generation
from paddle_tpu.models.generation import (
    _paged_span_blocks, paged_chunk_scatter, paged_chunk_scatter_q,
    paged_span_write, paged_verify_scatter, paged_verify_scatter_q,
    quantize_kv_heads)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
L = 4           # block length of the toy arenas
NB = 12         # blocks, the trash block after them


def row_write(arena, tables, start, stop, new):
    """The row scatter: position ``start[b] + i`` goes to row ``pos % L`` of
    block ``tables[b, min(pos // L, max_blocks - 1)]``, or to the trash block
    where it is at or past ``stop[b]``."""
    b, c = new.shape[:2]
    block_len, trash = arena.shape[1], arena.shape[0] - 1
    pos = start[:, None] + jnp.arange(c)[None, :]
    idx = jnp.minimum(pos // block_len, tables.shape[1] - 1)
    blk = jnp.where(pos < stop[:, None],
                    jnp.take_along_axis(tables, idx, axis=1), trash)
    new = new.reshape((b, c) + arena.shape[2:]).astype(arena.dtype)
    return arena.at[blk, pos % block_len].set(new)


def arena_of(rest, dtype, seed=0):
    """A toy arena already holding values everywhere, so that a row the
    write must keep is told from one it zeroed."""
    v = jax.random.normal(jax.random.PRNGKey(seed), (NB + 1, L) + rest)
    return (v * 8 if dtype == jnp.int8 else v).astype(dtype)


def tables_of(b, max_blocks=6, allocated=None):
    """Each row's blocks, distinct across rows; entries past ``allocated``
    blocks are the trash block, as the engine leaves them."""
    perm = np.random.default_rng(b).permutation(NB)[:b * max_blocks]
    t = perm.reshape(b, max_blocks)
    if allocated is not None:
        t[:, allocated:] = NB
    return jnp.asarray(t, jnp.int32)


def assert_same_but_trash(got, want):
    np.testing.assert_array_equal(np.asarray(got)[:-1], np.asarray(want)[:-1])


# (start, C, stop) of a batch-1 chunk, L = 4
SPANS = {
    "aligned_whole": (0, 8, 8),
    "aligned_multiple": (4, 12, 16),
    "equal_to_block": (8, 4, 12),
    "off_boundary": (3, 8, 11),
    "off_boundary_short": (5, 8, 9),
    "tail_inside_last_block": (4, 8, 10),
    "tail_whole_blocks_pad": (0, 12, 3),
    "all_pad": (8, 8, 8),
    "rows_not_a_multiple": (2, 6, 7),
}

ARENAS = {
    "packed_bf16": ((256,), jnp.bfloat16),
    "heads_f32": ((2, 8), jnp.float32),
    "latent_640": ((640,), jnp.bfloat16),
    "packed_int8": ((256,), jnp.int8),
}


@pytest.mark.parametrize("span", list(SPANS))
@pytest.mark.parametrize("arena", list(ARENAS))
def test_chunk_write_is_the_row_scatter(span, arena):
    start, c, stop = SPANS[span]
    rest, dtype = ARENAS[arena]
    a = arena_of(rest, dtype)
    tables = tables_of(1)
    new = jax.random.normal(jax.random.PRNGKey(1), (c,) + rest)
    got = paged_chunk_scatter(a, tables, jnp.int32(start), jnp.int32(stop),
                              new)
    want = row_write(a, tables, jnp.array([start]), jnp.array([stop]),
                     new[None])
    assert_same_but_trash(got, want)


@pytest.mark.parametrize("span", ["aligned_whole", "off_boundary",
                                  "tail_whole_blocks_pad",
                                  "rows_not_a_multiple"])
def test_int8_codes_and_scales_are_the_row_scatter(span):
    start, c, stop = SPANS[span]
    codes = arena_of((2, 8), jnp.int8)
    scales = arena_of((2,), jnp.float32, seed=2)
    tables = tables_of(1)
    new = jax.random.normal(jax.random.PRNGKey(3), (c, 2, 8))
    got_c, got_s = paged_chunk_scatter_q(codes, scales, tables,
                                         jnp.int32(start), jnp.int32(stop),
                                         new)
    q, s = quantize_kv_heads(new)
    st, sp = jnp.array([start]), jnp.array([stop])
    assert_same_but_trash(got_c, row_write(codes, tables, st, sp, q[None]))
    assert_same_but_trash(got_s, row_write(scales, tables, st, sp, s[None]))


@pytest.mark.parametrize("c", [4, 8, 5])
def test_per_row_starts_are_the_row_scatter(c):
    """B = 3 rows at their own starts and counts, as the recurrent families'
    steps and a verify forward write."""
    a = arena_of((64,), jnp.float32)
    tables = tables_of(3, max_blocks=4)
    lens = jnp.array([0, 6, 9], jnp.int32)
    n_valid = jnp.array([c, 1, 0], jnp.int32)
    new = jax.random.normal(jax.random.PRNGKey(4), (3, c, 4, 16))
    got = paged_verify_scatter(a, tables, lens, n_valid, new)
    want = row_write(a, tables, lens, lens + n_valid, new)
    assert_same_but_trash(got, want)
    codes, scales = arena_of((4, 16), jnp.int8), arena_of((4,), jnp.float32)
    got_c, got_s = paged_verify_scatter_q(codes, scales, tables, lens,
                                          n_valid, new)
    q, s = quantize_kv_heads(new)
    assert_same_but_trash(got_c, row_write(codes, tables, lens,
                                           lens + n_valid, q))
    assert_same_but_trash(got_s, row_write(scales, tables, lens,
                                           lens + n_valid, s))


@pytest.mark.parametrize("start,stop,allocated", [
    (12, 20, 4),        # the span runs into entries past the slot's blocks
    (16, 24, 6),        # ... and past the table's end, clamped
    (20, 24, 6),        # wholly past the table's end
    (20, 28, 6),        # live positions past the table's end: not written
])
def test_entries_past_the_allocation_reach_the_trash_block(start, stop,
                                                           allocated):
    a = arena_of((128,), jnp.float32)
    tables = tables_of(1, max_blocks=6, allocated=allocated)
    new = jax.random.normal(jax.random.PRNGKey(5), (1, 8, 128))
    st, sp = jnp.array([start]), jnp.array([stop])
    blk, _ = _paged_span_blocks(a, tables, st, sp, new)
    blk = np.asarray(blk)
    live = blk[blk != NB]
    assert len(set(live.tolist())) == len(live), blk
    j = start // L + np.arange(len(blk))
    assert (blk[j >= allocated] == NB).all(), blk
    if stop <= tables.shape[1] * L:
        # past the table's end the row scatter writes the last table
        # entry's block again, over the rows before them
        assert_same_but_trash(paged_span_write(a, tables, st, sp, new),
                              row_write(a, tables, st, sp, new))


def test_the_block_form_is_chosen_by_the_shape_alone(monkeypatch):
    """A span of whole blocks' worth goes a block at a time; a few columns
    (a decode or verify step) through the row scatter."""
    calls = []
    real = generation._paged_span_blocks
    monkeypatch.setattr(generation, "_paged_span_blocks",
                        lambda *a: calls.append(a[-1].shape[1]) or real(*a))
    a, tables = arena_of((64,), jnp.float32), tables_of(2, max_blocks=4)
    lens = jnp.array([1, 5], jnp.int32)
    for c in (1, 5, 4, 8):
        new = jnp.ones((2, c, 64))
        paged_verify_scatter(a, tables, lens, jnp.full((2,), c), new)
    assert calls == [4, 8]


@pytest.fixture
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("shape,dtype,chunk", [
    ((3073, 16, 2048), jnp.bfloat16, 256),    # the dense cell
    ((32769, 16, 640), jnp.bfloat16, 512),    # Kimi's latent arena
    ((24577, 16, 512), jnp.bfloat16, 512),    # LFM2's rows
    ((16385, 16, 512), jnp.bfloat16, 512),    # Falcon's rows
    ((3073, 16, 1024), jnp.int8, 256),        # an int8 cache's codes
])
def test_the_block_write_compiles_for_the_v5e_in_place(one_chip, shape,
                                                       dtype, chunk):
    """A prompt chunk's write at the serving cells' arenas, compiled for a
    described v5e with the arena donated: the arena goes in and comes out
    as one buffer, no operation copies it whole, and the slabs' scatter is
    named by the ``kv_append`` scope the trace metric reads."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    spec = lambda shp, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shp, dt, sharding=one_chip)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        compiled = jax.jit(paged_chunk_scatter, donate_argnums=0).lower(
            spec(shape, dtype), spec((1, 64), jnp.int32),
            spec((), jnp.int32), spec((), jnp.int32),
            spec((chunk, shape[-1]), jnp.bfloat16)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()
    text = compiled.as_text()
    arena = "%s[%s]" % (jnp.dtype(dtype).name.replace("bfloat16", "bf16")
                        .replace("int8", "s8"), ",".join(map(str, shape)))
    assert not re.search(re.escape(arena) + r"\{[^}]*\} copy\(", text)
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        jnp.dtype(dtype).itemsize * np.prod(shape)
    writes = re.findall(re.escape(arena) + r"\{[^}]*\} (?:fusion|scatter)\("
                        r".*", text)
    assert writes and all("/kv_append/scatter" in w for w in writes)


# -- one tiny engine a family -------------------------------------------------

FAMILIES = ["tiny", "lfm2_tiny", "kimi_tiny", "falcon_h1_tiny"]


def seeded_engine(name):
    with open(os.path.join(ROOT, "benchmarks", "rehearsal", "configs",
                           name + ".json")) as f:
        whole = json.load(f)
    family = importlib.import_module(whole["family"])
    cfg = {k: v for k, v in whole.items() if k not in HARNESS_SECTIONS}
    model = family.build(cfg)
    model.eval()
    key = jax.random.PRNGKey(0)
    for i, ((_, p), (_, shape, kind)) in enumerate(
            zip(model.named_parameters(), family.leaf_specs(cfg))):
        p.set_value(family.leaf(key, i, shape, kind, jnp.float32))
    geo = {k: v for k, v in whole["serving"].items() if k != "why"}
    geo.update(num_slots=2, compute_dtype="float32")
    return ServingEngine(model, **geo)


def serve(eng):
    rng = np.random.default_rng(9)
    vocab = 128
    reqs = [eng.submit(rng.integers(0, vocab, (n,)).astype(np.int32),
                       max_new_tokens=m)
            for n, m in ((30, 5), (9, 6), (17, 4))]
    eng.run()
    return [list(r.output) for r in reqs]


@pytest.mark.parametrize("name", FAMILIES)
def test_an_engine_serves_the_row_scatters_tokens(name, monkeypatch):
    """chunk_len 16 over blocks of 8: every prompt chunk takes the block
    form; with the row scatter in its place the tokens are the same."""
    got = serve(seeded_engine(name))
    monkeypatch.setattr(generation, "paged_span_write", row_write)
    want = serve(seeded_engine(name))
    assert got == want
