"""Signed (Count-Sketch) mode of the kernel stack vs the jnp core.

Everything here is a bit-exactness or mode-contract test: the Pallas signed
update/query kernels (flat and fused-hierarchy), the separable signed
candidate grid, the sharded psum fold, and the ops-layer mode matrix
(merge rules, dtype guards).  Statistical properties of the estimator live
in tests/test_fcm_countsketch.py.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import countsketch as cs
from repro.core import distributed as dist
from repro.core import sketch as sk
from repro.core.hashing import KeySchema
from repro.core.hierarchy import HierarchySpec
from repro.kernels import ops
from repro.kernels.hashes import make_plan
from repro.kernels.hier_query import (
    hier_candidate_query_signed,
    hier_candidate_query_signed_ref,
)
from repro.kernels.hier_update import (
    hier_update_signed_pallas,
    hier_update_signed_ref,
    make_hier_plan,
)
from repro.kernels.sketch_query import sketch_query_signed_pallas
from repro.kernels.sketch_update import (
    padded_table_size,
    sketch_update_signed_pallas,
)


def _spec(w=5):
    schema = KeySchema(domains=(1 << 32, 1 << 20, 256))
    return sk.SketchSpec(schema, ((0,), (1, 2)), (32, 16), w)


def _stream(n=512, seed=0):
    rng = np.random.default_rng(seed)
    items = np.stack([
        rng.integers(0, 1 << 32, size=n, dtype=np.uint64).astype(np.uint32),
        rng.integers(0, 1 << 20, size=n, dtype=np.uint32),
        rng.integers(0, 256, size=n, dtype=np.uint32),
    ], axis=-1)
    freqs = rng.integers(-1000, 1000, size=n).astype(np.int32)
    return items, freqs


def _jnp_state(spec, params, dtype=jnp.int32):
    return cs.CountSketchState(
        params, jnp.zeros((spec.width, spec.table_size), dtype))


def test_flat_signed_update_kernel_bit_exact():
    """Pallas signed fold == jnp scatter reference on int32 tables, with
    negative (turnstile) weights in the stream."""
    spec = _spec()
    params = cs.init_params(spec, jax.random.key(0))
    items, freqs = _stream()
    plan = make_plan(spec)
    h_pad = padded_table_size(spec.table_size, 128)
    table = jnp.zeros((spec.width, h_pad), jnp.int32)
    chunks = jnp.asarray(spec.schema.module_chunks_np(items))
    out = sketch_update_signed_pallas(
        plan, table, chunks, jnp.asarray(freqs), params.base.q,
        params.base.r, params.sign_q, params.sign_r, tile_h=128,
        interpret=True)
    ref = cs.update(spec, _jnp_state(spec, params), jnp.asarray(items),
                    jnp.asarray(freqs))
    np.testing.assert_array_equal(
        np.asarray(out)[:, : spec.table_size], np.asarray(ref.table))


def test_flat_signed_query_kernel_bit_exact():
    spec = _spec()
    params = cs.init_params(spec, jax.random.key(1))
    items, freqs = _stream(seed=1)
    st = cs.update(spec, _jnp_state(spec, params), jnp.asarray(items),
                   jnp.asarray(freqs))
    h_pad = padded_table_size(spec.table_size, 128)
    table = jnp.pad(st.table, ((0, 0), (0, h_pad - spec.table_size)))
    plan = make_plan(spec)
    q_items = items[:100]
    chunks = jnp.asarray(spec.schema.module_chunks_np(q_items))
    rows = sketch_query_signed_pallas(
        plan, table, chunks, params.base.q, params.base.r, params.sign_q,
        params.sign_r, interpret=True)
    ref_rows, ref_med = cs.query_rows(spec, st, jnp.asarray(q_items))
    np.testing.assert_array_equal(np.asarray(rows),
                                  np.asarray(ref_rows).astype(np.int32))
    np.testing.assert_allclose(
        np.median(np.asarray(rows).astype(np.float32), axis=0),
        np.asarray(ref_med))


def test_hier_signed_update_kernel_bit_exact():
    """Fused one-launch hierarchy fold == per-level jnp oracle == cascade."""
    spec = _spec()
    hspec = HierarchySpec.from_spec(spec)
    params = cs.init_params(spec, jax.random.key(2))
    items, freqs = _stream(n=256, seed=2)
    chunks = jnp.asarray(spec.schema.module_chunks_np(items))

    hplan = make_hier_plan(hspec, tile_h=128)
    table = jnp.zeros((spec.width, hplan.padded_cols), jnp.int32)
    out = hier_update_signed_pallas(
        hplan, table, chunks, jnp.asarray(freqs), params.base.q,
        params.base.r, params.sign_q, params.sign_r, interpret=True)
    ref = hier_update_signed_ref(
        hplan, jnp.zeros_like(table), chunks, jnp.asarray(freqs),
        params.base.q, params.base.r, params.sign_q, params.sign_r)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    hier0 = cs.CountSketchHierarchy(
        params, tuple(jnp.zeros((s.width, s.table_size), jnp.int32)
                      for s in hspec.levels))
    casc = cs.hier_update(hspec, hier0, jnp.asarray(items),
                          jnp.asarray(freqs))
    oracle = cs.hier_update_reference(hspec, hier0, jnp.asarray(items),
                                      jnp.asarray(freqs))
    for lvl, (a, b) in enumerate(zip(casc.tables, oracle.tables)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"level {lvl}")
    for lvl in range(hspec.n_levels):
        got = np.asarray(out)[:, hplan.level_offsets[lvl]:
                              hplan.level_offsets[lvl]
                              + hspec.levels[lvl].table_size]
        np.testing.assert_array_equal(got, np.asarray(casc.tables[lvl]),
                                      err_msg=f"fused level {lvl}")


def test_signed_candidate_grid_kernel_bit_exact():
    """Signed candidate-grid kernel == jnp ref == direct flat queries."""
    spec = _spec(w=3)
    hspec = HierarchySpec.from_spec(spec)
    params = cs.init_params(spec, jax.random.key(3))
    items, freqs = _stream(n=256, seed=3)
    hier = cs.CountSketchHierarchy(
        params, tuple(jnp.zeros((s.width, s.table_size), jnp.int32)
                      for s in hspec.levels))
    hier = cs.hier_update(hspec, hier, jnp.asarray(items),
                          jnp.asarray(freqs))

    prefixes = np.unique(items[:, :1], axis=0)[:24]
    values = np.unique(items[:, 1:], axis=0)[:16]
    pp, cp, sp, sc = cs.candidate_signed_partials(
        hspec, params, 1, jnp.asarray(prefixes), jnp.asarray(values))
    ker = hier_candidate_query_signed(hier.tables[1], pp, cp, sp, sc,
                                      interpret=True)
    ref = hier_candidate_query_signed_ref(hier.tables[1], pp, cp, sp, sc)
    np.testing.assert_array_equal(np.asarray(ker).astype(np.float32),
                                  np.asarray(ref))

    grid = np.asarray(jnp.median(ref, axis=0))
    full = np.concatenate([
        np.repeat(prefixes, len(values), axis=0),
        np.tile(values, (len(prefixes), 1)),
    ], axis=1)
    flat = np.asarray(cs.hier_query(hspec, hier, 1, jnp.asarray(full)))
    np.testing.assert_allclose(grid.reshape(-1), flat)


def test_candidate_estimates_kernel_matches_ref_with_chunking():
    spec = _spec(w=3)
    hspec = HierarchySpec.from_spec(spec)
    params = cs.init_params(spec, jax.random.key(4))
    items, freqs = _stream(n=256, seed=4)
    hier = cs.CountSketchHierarchy(
        params, tuple(jnp.zeros((s.width, s.table_size), jnp.int32)
                      for s in hspec.levels))
    hier = cs.hier_update(hspec, hier, jnp.asarray(items),
                          jnp.asarray(freqs))
    prefixes = np.unique(items[:, :1], axis=0)[:17]  # odd: forces pad chunk
    values = np.unique(items[:, 1:], axis=0)[:8]
    a = cs.candidate_estimates(hspec, hier, 1, prefixes, values,
                               use_kernel=True, interpret=True,
                               max_batch=40)
    b = cs.candidate_estimates(hspec, hier, 1, prefixes, values,
                               use_kernel=False)
    np.testing.assert_array_equal(a, b)


def test_ops_signed_sketch_matches_core():
    spec = _spec()
    items, freqs = _stream(seed=5)
    ks = ops.KernelSketch(spec, jax.random.key(5), mode="signed",
                          dtype=jnp.int32, interpret=True)
    ks.update(items[:300], freqs[:300])
    ks.update(items[300:], freqs[300:])
    ref = cs.update(spec, _jnp_state(spec, ks.cs_params),
                    jnp.asarray(items), jnp.asarray(freqs))
    np.testing.assert_array_equal(np.asarray(ks.cs_state().table),
                                  np.asarray(ref.table))
    qi = items[:64]
    np.testing.assert_allclose(
        ks.query(qi), np.asarray(cs.query(spec, ref, jnp.asarray(qi))))


def test_ops_signed_hierarchy_matches_core():
    spec = _spec()
    hspec = HierarchySpec.from_spec(spec)
    items, freqs = _stream(n=300, seed=6)
    kh = ops.KernelHierarchy(hspec, jax.random.key(6), mode="signed",
                             dtype=jnp.int32, interpret=True, tile_h=128,
                             block_b=128)
    kh.update(items, freqs)
    hier = cs.CountSketchHierarchy(
        kh.cs_params, tuple(jnp.zeros((s.width, s.table_size), jnp.int32)
                            for s in hspec.levels))
    hier = cs.hier_update(hspec, hier, jnp.asarray(items),
                          jnp.asarray(freqs))
    for lvl, (a, b) in enumerate(zip(kh.cs_state().tables, hier.tables)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"level {lvl}")


def test_sharded_signed_build_bit_exact():
    spec = _spec()
    params = cs.init_params(spec, jax.random.key(7))
    items, freqs = _stream(n=256, seed=7)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("d",))
    delta = dist.sharded_signed_build(
        spec, params, mesh, ("d",), jnp.asarray(items), jnp.asarray(freqs),
        table_dtype=jnp.int32)
    ref = cs.update(spec, _jnp_state(spec, params), jnp.asarray(items),
                    jnp.asarray(freqs))
    np.testing.assert_array_equal(np.asarray(delta), np.asarray(ref.table))


def test_mode_matrix_contracts():
    spec = _spec()
    items, freqs = _stream(n=64, seed=8)

    # signed merge requires identical params incl. the sign draw
    a = ops.KernelSketch(spec, jax.random.key(8), mode="signed",
                         interpret=True)
    b = ops.KernelSketch(spec, jax.random.key(9), mode="signed",
                         interpret=True)
    with pytest.raises(ValueError):
        a.merge(b)

    # signed x linear cannot merge
    c = ops.KernelSketch(spec, jax.random.key(8), mode="linear",
                         interpret=True)
    with pytest.raises(ValueError):
        a.merge(c)

    # conservative still refused by every distributed surface
    with pytest.raises(ValueError):
        dist.require_linear("conservative", "test")
    dist.require_linear("signed", "test")   # signed is linear: allowed
    dist.require_linear("linear", "test")

    # hierarchy refuses conservative mode outright
    hspec = HierarchySpec.from_spec(spec)
    with pytest.raises(ValueError):
        ops.KernelHierarchy(hspec, jax.random.key(0), mode="conservative")

    # state() is the linear-mode surface; signed exposes cs_state()
    with pytest.raises(ValueError):
        a.state()
    assert a.cs_state().table.shape == (spec.width, spec.table_size)

    # |f| >= 2^24 exceeds the two-limb exactness bound on int tables
    with pytest.raises(ValueError):
        ops.check_signed_kernel_freqs(
            np.array([1 << 24], np.int64), jnp.int32)
    ops.check_signed_kernel_freqs(np.array([-(1 << 23)], np.int64),
                                  jnp.int32)
