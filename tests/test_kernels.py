"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sketch as sk
from repro.core.hashing import KeySchema
from repro.kernels import ref
from repro.kernels.hashes import make_plan
from repro.kernels.ops import KernelSketch
from repro.kernels.sketch_query import sketch_query_pallas
from repro.kernels.sketch_update import padded_table_size, sketch_update_pallas

CASES = [
    # (domains, partition, ranges, w, tile_h, B)
    (((1 << 32), (1 << 32)), [(0, 1)], (1000,), 1, 256, 64),
    (((1 << 32), (1 << 32)), [(0,), (1,)], (48, 90), 4, 512, 128),
    ((256,) * 4, [(0,), (1,), (2,), (3,)], (8, 8, 8, 8), 5, 512, 200),
    ((256,) * 4, [(0, 2), (1, 3)], (64, 64), 3, 1024, 100),
    (((1 << 16), (1 << 16)), [(0,), (1,)], (100, 41), 2, 128, 333),
]


@pytest.mark.parametrize("domains,part,ranges,w,tile_h,b", CASES)
def test_update_kernel_matches_oracle_int32(domains, part, ranges, w, tile_h, b):
    rng = np.random.default_rng(hash((w, tile_h, b)) % 2**32)
    schema = KeySchema(domains=domains)
    spec = sk.mod_sketch_spec(schema, part, ranges, w)
    plan = make_plan(spec)
    params = sk.init_params(spec, jax.random.PRNGKey(0))
    items = np.stack([rng.integers(0, d, b, dtype=np.uint64).astype(np.uint32)
                      for d in domains], axis=1)
    freqs = rng.integers(1, 1 << 14, size=(b,)).astype(np.int32)
    chunks = schema.module_chunks(jnp.asarray(items))
    h_pad = padded_table_size(spec.table_size, tile_h)
    t0 = jnp.zeros((w, h_pad), jnp.int32)
    # oracle first: the pallas wrapper DONATES its table arg, so t0 is
    # consumed by the kernel call
    want = ref.sketch_update_ref(plan, t0, chunks, jnp.asarray(freqs),
                                 params.q, params.r)
    got = sketch_update_pallas(plan, t0, chunks, jnp.asarray(freqs),
                               params.q, params.r, tile_h=tile_h,
                               interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("domains,part,ranges,w,tile_h,b", CASES[:3])
def test_update_kernel_matches_oracle_float32(domains, part, ranges, w, tile_h, b):
    rng = np.random.default_rng(0)
    schema = KeySchema(domains=domains)
    spec = sk.mod_sketch_spec(schema, part, ranges, w)
    plan = make_plan(spec)
    params = sk.init_params(spec, jax.random.PRNGKey(1))
    items = np.stack([rng.integers(0, d, b, dtype=np.uint64).astype(np.uint32)
                      for d in domains], axis=1)
    vals = rng.standard_normal(b).astype(np.float32)
    chunks = schema.module_chunks(jnp.asarray(items))
    h_pad = padded_table_size(spec.table_size, tile_h)
    t0 = jnp.zeros((w, h_pad), jnp.float32)
    want = ref.sketch_update_ref(plan, t0, chunks, jnp.asarray(vals),
                                 params.q, params.r)
    got = sketch_update_pallas(plan, t0, chunks, jnp.asarray(vals),
                               params.q, params.r, tile_h=tile_h,
                               interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("domains,part,ranges,w,tile_h,b", CASES)
def test_query_kernel_matches_oracle(domains, part, ranges, w, tile_h, b):
    rng = np.random.default_rng(42)
    schema = KeySchema(domains=domains)
    spec = sk.mod_sketch_spec(schema, part, ranges, w)
    plan = make_plan(spec)
    params = sk.init_params(spec, jax.random.PRNGKey(2))
    items = np.stack([rng.integers(0, d, b, dtype=np.uint64).astype(np.uint32)
                      for d in domains], axis=1)
    freqs = rng.integers(1, 1000, size=(b,)).astype(np.int32)
    chunks = schema.module_chunks(jnp.asarray(items))
    h_pad = padded_table_size(spec.table_size, tile_h)
    table = ref.sketch_update_ref(plan, jnp.zeros((w, h_pad), jnp.int32),
                                  chunks, jnp.asarray(freqs), params.q,
                                  params.r)
    got = sketch_query_pallas(plan, table, chunks[:61], params.q, params.r,
                              interpret=True)
    want = ref.sketch_query_ref(plan, table, chunks[:61], params.q, params.r)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_kernel_sketch_end_to_end_matches_core_path():
    rng = np.random.default_rng(5)
    schema = KeySchema(domains=(1 << 32, 1 << 32))
    spec = sk.mod_sketch_spec(schema, [(0,), (1,)], (100, 41), 4)
    ks = KernelSketch(spec, jax.random.PRNGKey(0), tile_h=512, block_b=128)
    items = rng.integers(0, 1 << 32, size=(1000, 2), dtype=np.uint64).astype(np.uint32)
    freqs = rng.integers(1, 100, size=(1000,)).astype(np.int32)
    ks.update(items, freqs)
    core = sk.SketchState(params=ks.params,
                          table=jnp.zeros((4, spec.table_size), jnp.int32))
    core = sk.update_jit(spec, core, jnp.asarray(items), jnp.asarray(freqs))
    np.testing.assert_array_equal(np.asarray(ks.state().table),
                                  np.asarray(core.table))
    np.testing.assert_array_equal(
        ks.query(items[:77]),
        np.asarray(sk.query_jit(spec, core, jnp.asarray(items[:77]))))


def test_kernel_rejects_oversized_frequency():
    schema = KeySchema(domains=(1 << 16, 1 << 16))
    spec = sk.mod_sketch_spec(schema, [(0,), (1,)], (32, 32), 2)
    ks = KernelSketch(spec, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="2\\^24"):
        ks.update(np.zeros((4, 2), np.uint32),
                  np.full((4,), 1 << 25, np.int64))
