"""Pallas kernels vs the core/sketch.py reference path (not just the jnp
oracles in kernels/ref.py): same params, same stream => same table, same
estimates, across the paper's three spec families, both table dtypes, and
table widths that are not a multiple of the kernel tile."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sketch as sk
from repro.core.hashing import KeySchema
from repro.kernels.ops import KernelSketch

_SCHEMA = KeySchema(domains=(1 << 32, 1 << 32))


def _spec_cases():
    # (name, spec, tile_h): every table_size is deliberately NOT a multiple
    # of its tile so the padding path is always exercised
    return [
        ("count-min", sk.count_min_spec(_SCHEMA, 1000, 3), 256),
        ("equal", sk.equal_sketch_spec(_SCHEMA, 1100, 2), 512),
        ("mod", sk.mod_sketch_spec(_SCHEMA, [(0,), (1,)], (48, 90), 4), 512),
        ("mod-joint", sk.mod_sketch_spec(
            KeySchema(domains=(256,) * 4), [(0, 2), (1, 3)], (36, 45), 3), 256),
    ]


def _stream_for(spec, rng, b):
    items = np.stack(
        [rng.integers(0, d, b, dtype=np.uint64).astype(np.uint32)
         for d in spec.schema.domains], axis=1)
    freqs = rng.integers(1, 1 << 12, size=(b,)).astype(np.int32)
    return items, freqs


@pytest.mark.parametrize("name,spec,tile_h", _spec_cases())
def test_update_and_query_parity_int32(name, spec, tile_h):
    rng = np.random.default_rng(abs(hash(name)) % 2**32)
    assert spec.table_size % tile_h != 0, "case must exercise padding"
    ks = KernelSketch(spec, jax.random.PRNGKey(7), tile_h=tile_h,
                      block_b=128, interpret=True)
    items, freqs = _stream_for(spec, rng, 500)
    ks.update(items, freqs)

    core = sk.SketchState(
        params=ks.params,
        table=jnp.zeros((spec.width, spec.table_size), jnp.int32))
    core = sk.update_jit(spec, core, jnp.asarray(items), jnp.asarray(freqs))

    np.testing.assert_array_equal(np.asarray(ks.state().table),
                                  np.asarray(core.table))
    q = items[rng.choice(len(items), 97, replace=False)]
    np.testing.assert_array_equal(
        ks.query(q), np.asarray(sk.query_jit(spec, core, jnp.asarray(q))))


@pytest.mark.slow
@pytest.mark.parametrize("name,spec,tile_h", _spec_cases())
def test_update_parity_float32(name, spec, tile_h):
    """f32 tables (gradient sketches): one MXU contraction, tolerance-based
    because float accumulation order differs between the paths."""
    rng = np.random.default_rng(abs(hash(name + "f32")) % 2**32)
    ks = KernelSketch(spec, jax.random.PRNGKey(9), tile_h=tile_h,
                      block_b=128, dtype=jnp.float32, interpret=True)
    items, _ = _stream_for(spec, rng, 500)
    vals = rng.standard_normal(500).astype(np.float32)
    ks.update(items, vals)

    core = sk.SketchState(
        params=ks.params,
        table=jnp.zeros((spec.width, spec.table_size), jnp.float32))
    core = sk.update_jit(spec, core, jnp.asarray(items), jnp.asarray(vals))

    np.testing.assert_allclose(np.asarray(ks.state().table),
                               np.asarray(core.table), rtol=1e-5, atol=1e-4)


def _conservative_reference(spec, params, items, freqs, dtype):
    core = sk.SketchState(
        params=params,
        table=jnp.zeros((spec.width, spec.table_size), dtype))
    return sk.update_conservative(spec, core, jnp.asarray(items),
                                  jnp.asarray(freqs))


@pytest.mark.parametrize("name,spec,tile_h", _spec_cases())
def test_conservative_parity_int32(name, spec, tile_h):
    """Acceptance: conservative Pallas kernel bit-exact vs
    core.sketch.update_conservative, with duplicate keys inside one block
    (the sequential-dependence case) and non-tile-multiple widths."""
    rng = np.random.default_rng(abs(hash(name + "cons")) % 2**32)
    assert spec.table_size % tile_h != 0, "case must exercise padding"
    ks = KernelSketch(spec, jax.random.PRNGKey(7), tile_h=tile_h,
                      block_b=128, interpret=True, mode="conservative")
    items, freqs = _stream_for(spec, rng, 500)
    items[40:90] = items[0]       # heavy duplication inside block 0
    items[130:140] = items[129]   # ... and across the block-1 boundary
    ks.update(items, freqs)

    core = _conservative_reference(spec, ks.params, items, freqs, jnp.int32)
    np.testing.assert_array_equal(ks.table_view(), np.asarray(core.table))
    q = items[rng.choice(len(items), 97, replace=False)]
    np.testing.assert_array_equal(
        ks.query(q), np.asarray(sk.query_jit(spec, core, jnp.asarray(q))))


def test_conservative_parity_float32_bit_exact():
    """No MXU contraction in the conservative kernel => f32 is bit-exact
    too (gather/min/add/max in reference order), unlike the linear kernel's
    tolerance-based f32 parity."""
    spec = sk.mod_sketch_spec(_SCHEMA, [(0,), (1,)], (48, 90), 4)
    rng = np.random.default_rng(1)
    items, _ = _stream_for(spec, rng, 300)
    items[50:80] = items[49]
    vals = (rng.standard_normal(300).astype(np.float32) ** 2)  # >= 0
    ks = KernelSketch(spec, jax.random.PRNGKey(9), tile_h=512, block_b=128,
                      dtype=jnp.float32, interpret=True, mode="conservative")
    ks.update(items, vals)
    core = _conservative_reference(spec, ks.params, items, vals, jnp.float32)
    np.testing.assert_array_equal(ks.table_view(), np.asarray(core.table))


def test_conservative_chunked_b_variant_matches():
    """Small VMEM budget => chunk_b < B (chunked-B grid); same result."""
    from repro.kernels.hashes import make_plan
    from repro.kernels.sketch_update import padded_table_size
    from repro.kernels.sketch_update_conservative import (
        conservative_chunk_b,
        sketch_update_conservative_pallas,
    )

    spec = sk.mod_sketch_spec(_SCHEMA, [(0,), (1,)], (100, 41), 2)
    plan = make_plan(spec)
    params = sk.init_params(spec, jax.random.PRNGKey(3))
    rng = np.random.default_rng(2)
    items, freqs = _stream_for(spec, rng, 256)
    items[10:60] = items[9]
    chunks = spec.schema.module_chunks(jnp.asarray(items))
    h_pad = padded_table_size(spec.table_size, 128)
    t0 = jnp.zeros((spec.width, h_pad), jnp.int32)

    table_bytes = 2 * spec.width * h_pad * 4
    tight = table_bytes + 4 * 64 * (chunks.shape[1] * 4 + 4)
    chunk = conservative_chunk_b(256, chunks.shape[1], spec.width, h_pad, 4,
                                 vmem_limit_bytes=tight)
    assert 1 <= chunk < 256, chunk
    got = sketch_update_conservative_pallas(
        plan, t0, chunks, jnp.asarray(freqs), params.q, params.r,
        chunk_b=chunk, interpret=True)
    full = sketch_update_conservative_pallas(
        plan, t0, chunks, jnp.asarray(freqs), params.q, params.r,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(full))
    # table alone over budget => no chunk fits; wrapper takes reference path
    assert conservative_chunk_b(256, chunks.shape[1], spec.width, h_pad, 4,
                                vmem_limit_bytes=table_bytes - 1) is None
    # regression: non-power-of-two blocks must get a chunk that divides b
    # (the old halving loop returned e.g. 62 for b=1000 and crashed the
    # kernel's divisibility check), and a budget that fits the table but
    # not even one item's inputs must fall back to the reference path
    for b in (1000, 288, 7):
        ch = conservative_chunk_b(b, chunks.shape[1], spec.width, h_pad, 4,
                                  vmem_limit_bytes=tight)
        assert ch is not None and b % ch == 0, (b, ch)
    assert conservative_chunk_b(256, chunks.shape[1], spec.width, h_pad, 4,
                                vmem_limit_bytes=table_bytes + 1) is None


def test_conservative_vmem_fallback_reference_path(monkeypatch):
    """When the table working set exceeds VMEM the wrapper must refuse and
    name the jnp reference path -- never swap it in silently -- and the
    named path gives the conservative table bit-for-bit."""
    import repro.kernels.ops as ops_mod

    spec = sk.mod_sketch_spec(_SCHEMA, [(0,), (1,)], (48, 90), 4)
    rng = np.random.default_rng(5)
    items, freqs = _stream_for(spec, rng, 200)
    items[20:50] = items[19]
    monkeypatch.setattr(ops_mod, "conservative_chunk_b",
                        lambda *a, **k: None)
    ks = KernelSketch(spec, jax.random.PRNGKey(7), tile_h=512, block_b=128,
                      interpret=True, mode="conservative")
    with pytest.raises(ValueError, match="update_conservative_jit"):
        ks.update(items, freqs)
    assert ks.table_view().max() == 0
    core = _conservative_reference(spec, ks.params, items, freqs, jnp.int32)
    state = sk.update_conservative_jit(
        spec, sk.SketchState(params=ks.params, table=jnp.zeros(
            (spec.width, spec.table_size), jnp.int32)),
        jnp.asarray(items), jnp.asarray(freqs))
    np.testing.assert_array_equal(np.asarray(state.table),
                                  np.asarray(core.table))


def test_freq_guard_rejects_negative_and_large_magnitude():
    """Regression: the old guard only checked max >= 2^24, so negative and
    large-magnitude-negative frequencies slipped into the 12-bit limb
    split.  Int tables must reject both; f32 tables keep negatives
    (gradient sketches)."""
    spec = sk.mod_sketch_spec(_SCHEMA, [(0,), (1,)], (100, 41), 2)
    rng = np.random.default_rng(0)
    items, _ = _stream_for(spec, rng, 8)

    ks = KernelSketch(spec, jax.random.PRNGKey(3), tile_h=128, block_b=8,
                      interpret=True)
    with pytest.raises(ValueError, match="negative"):
        ks.update(items, np.array([1, -1, 1, 1, 1, 1, 1, 1], np.int32))
    with pytest.raises(ValueError, match="2\\^24"):
        ks.update(items, np.full(8, -(1 << 30), np.int64))
    with pytest.raises(ValueError, match="2\\^24"):
        ks.update(items, np.full(8, 1 << 24, np.int64))
    assert ks.table_view().max() == 0  # nothing leaked into the table

    # f32 linear: negatives allowed (turnstile / gradient values)
    ksf = KernelSketch(spec, jax.random.PRNGKey(3), tile_h=128, block_b=8,
                       dtype=jnp.float32, interpret=True)
    ksf.update(items, np.array([0.5, -0.5] * 4, np.float32))

    # conservative rejects negatives on any dtype (silent no-op otherwise)
    for dtype in (jnp.int32, jnp.float32):
        ksc = KernelSketch(spec, jax.random.PRNGKey(3), tile_h=128, block_b=8,
                           dtype=dtype, interpret=True, mode="conservative")
        with pytest.raises(ValueError, match="non-negative"):
            ksc.update(items, np.array([1, -2] * 4, np.int32))

    # ... but has no limb split, so f >= 2^24 stays valid and bit-exact
    ksc = KernelSketch(spec, jax.random.PRNGKey(3), tile_h=128, block_b=8,
                       interpret=True, mode="conservative")
    big = np.full(8, 1 << 25, np.int64)
    ksc.update(items, big)
    core = _conservative_reference(spec, ksc.params, items, big, jnp.int32)
    np.testing.assert_array_equal(ksc.table_view(), np.asarray(core.table))
    # values past the int32 table range would wrap negative in the cast and
    # silently no-op: rejected instead
    with pytest.raises(ValueError, match="table range"):
        ksc.update(items, np.full(8, 1 << 31, np.int64))

    # NaN weights would poison every touched f32 cell (query would then
    # UNDERestimate); the guard must catch them, not just f < 0
    ksf32c = KernelSketch(spec, jax.random.PRNGKey(3), tile_h=128, block_b=8,
                          dtype=jnp.float32, interpret=True,
                          mode="conservative")
    nan_f = np.array([1.0, 1.0, np.nan, 1.0, 1.0, 1.0, 1.0, 1.0], np.float32)
    with pytest.raises(ValueError, match="non-negative"):
        ksf32c.update(items, nan_f)


def test_block_padding_is_neutral():
    """Stream length not a multiple of block_b: zero-padded tail items must
    not change any estimate (they hash somewhere but add freq 0)."""
    spec = sk.mod_sketch_spec(_SCHEMA, [(0,), (1,)], (100, 41), 2)
    rng = np.random.default_rng(0)
    items, freqs = _stream_for(spec, rng, 131)  # 131 % 128 != 0
    ks = KernelSketch(spec, jax.random.PRNGKey(3), tile_h=128, block_b=128,
                      interpret=True)
    ks.update(items, freqs)
    core = sk.SketchState(
        params=ks.params,
        table=jnp.zeros((spec.width, spec.table_size), jnp.int32))
    core = sk.update_jit(spec, core, jnp.asarray(items), jnp.asarray(freqs))
    np.testing.assert_array_equal(np.asarray(ks.state().table),
                                  np.asarray(core.table))
