"""Fused single-launch hierarchy ingest: shared-family cascade + Pallas
kernel parity.

Covers the PR-5 acceptance surface: the shared per-group hash family
(level params = prefix slices of the finest draw), the mixed-radix index
cascade (one hash pass -> all level indices), bit-parity of the fused
multi-level Pallas kernel vs the per-level jnp reference on int32 and f32
tables (duplicate keys, non-tile-multiple level widths, zero-frequency pad
rows), the endpoint's fused-ingest path, and descent guarantees under the
shared params."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hierarchy as hh
from repro.core import sketch as sk
from repro.core.hashing import KeySchema
from repro.kernels import KernelHierarchy, hier_update_pallas, make_hier_plan
from repro.launch.mesh import make_mesh
from repro.serving.engine import SketchTopKEndpoint
from repro.streams import zipf_hh_workload


def _hier(ranges=(48, 90, 7), w=3,
          domains=(1 << 32, 256, 1000, 4096), part=((1, 2), (0,), (3,))):
    """A 3-level hierarchy with a joint group, a 2-chunk module, and level
    table sizes that are NOT tile multiples."""
    schema = KeySchema(domains=domains)
    base = sk.mod_sketch_spec(schema, [tuple(g) for g in part], ranges, w)
    return hh.HierarchySpec.from_spec(base)


def _stream(hspec, n, seed=0, dup=True):
    rng = np.random.default_rng(seed)
    items = np.stack(
        [rng.integers(0, d, n, dtype=np.uint64).astype(np.uint32)
         for d in hspec.base.schema.domains], axis=1)
    if dup:
        items[n // 10 : n // 4] = items[0]       # heavy duplication
    freqs = rng.integers(1, 1 << 12, n).astype(np.int32)
    return items, freqs


# --------------------------------------------------------------------------
# Shared family + cascade identities
# --------------------------------------------------------------------------

def test_level_params_are_prefix_slices():
    hspec = _hier()
    state = hh.init_hierarchy(hspec, jax.random.PRNGKey(3))
    assert hh.params_share_prefix(state)
    fine = state.states[-1].params
    for l, st in enumerate(state.states):
        nc = hspec.levels[l].schema.total_chunks
        np.testing.assert_array_equal(np.asarray(st.params.q),
                                      np.asarray(fine.q)[:, :nc])
        np.testing.assert_array_equal(np.asarray(st.params.r),
                                      np.asarray(fine.r)[:, : l + 1])
    # a fresh independent draw per level violates the invariant
    keys = jax.random.split(jax.random.PRNGKey(9), hspec.n_levels)
    indep = hh.HierarchyState(states=tuple(
        sk.init_state(s, k) for s, k in zip(hspec.levels, keys)))
    assert not hh.params_share_prefix(indep)


def test_hierarchy_indices_match_per_level_compute_indices():
    """The cascade (one hash pass + integer divisions) must equal every
    level's own compute_indices on its re-cut columns, bit for bit."""
    hspec = _hier()
    state = hh.init_hierarchy(hspec, jax.random.PRNGKey(1))
    items, _ = _stream(hspec, 257, seed=2)
    idxs = hh.hierarchy_indices(hspec, state.states[-1].params,
                                jnp.asarray(items))
    for lvl, (spec_l, st_l) in enumerate(zip(hspec.levels, state.states)):
        want = sk.compute_indices(spec_l, st_l.params,
                                  hspec.level_items(lvl, jnp.asarray(items)))
        np.testing.assert_array_equal(np.asarray(idxs[lvl]),
                                      np.asarray(want))


def test_cascade_update_equals_per_level_reference():
    """hh.update (cascade) and hh.update_jit are bit-identical to the
    per-level reference fold, for both the linear and conservative paths."""
    hspec = _hier()
    key = jax.random.PRNGKey(5)
    items, freqs = _stream(hspec, 400, seed=3)
    it, fr = jnp.asarray(items), jnp.asarray(freqs)

    ref = hh.update_reference(hspec, hh.init_hierarchy(hspec, key), it, fr)
    got = hh.update(hspec, hh.init_hierarchy(hspec, key), it, fr)
    got_jit = hh.update_jit(hspec, hh.init_hierarchy(hspec, key), it, fr)
    for a, b, c in zip(got.states, ref.states, got_jit.states):
        np.testing.assert_array_equal(np.asarray(a.table),
                                      np.asarray(b.table))
        np.testing.assert_array_equal(np.asarray(c.table),
                                      np.asarray(b.table))

    # conservative: same cascade for indices, per-level sequential folds
    cons = hh.update_conservative_jit(
        hspec, hh.init_hierarchy(hspec, key), it, fr)
    want = []
    st0 = hh.init_hierarchy(hspec, key)
    for lvl, (spec_l, st_l) in enumerate(zip(hspec.levels, st0.states)):
        want.append(sk.update_conservative(
            spec_l, st_l, hspec.level_items(lvl, it), fr))
    for a, b in zip(cons.states, want):
        np.testing.assert_array_equal(np.asarray(a.table),
                                      np.asarray(b.table))


# --------------------------------------------------------------------------
# Fused Pallas kernel parity
# --------------------------------------------------------------------------

def test_fused_kernel_bit_parity_int32():
    """Acceptance: one pallas_call over the concatenated padded tables is
    bit-identical to the per-level jnp reference on int32 tables, with
    duplicate keys and non-tile-multiple level widths."""
    hspec = _hier()
    key = jax.random.PRNGKey(7)
    items, freqs = _stream(hspec, 500, seed=0)
    kh = KernelHierarchy(hspec, key, tile_h=128, block_b=128, interpret=True)
    for lvl, pad in zip(hspec.levels, kh.hplan.level_pads):
        assert lvl.table_size % 128 != 0, "cases must exercise padding"
        assert pad % 128 == 0
    kh.update(items, freqs)

    ref = hh.update_reference(hspec, hh.init_hierarchy(hspec, key),
                              jnp.asarray(items), jnp.asarray(freqs))
    for a, b in zip(kh.state().states, ref.states):
        np.testing.assert_array_equal(np.asarray(a.table),
                                      np.asarray(b.table))


def test_fused_kernel_bit_parity_f32_integer_weights():
    """f32 tables: the one-hot contraction sums every cell's multiset of
    weights; with integer-valued f32 weights (< 2^24) all partial sums are
    exactly representable, so parity is bit-exact despite the different
    accumulation order."""
    hspec = _hier()
    key = jax.random.PRNGKey(11)
    rng = np.random.default_rng(4)
    items, _ = _stream(hspec, 300, seed=5)
    vals = rng.integers(1, 1 << 10, 300).astype(np.float32)
    kh = KernelHierarchy(hspec, key, tile_h=128, block_b=128,
                         dtype=jnp.float32, interpret=True)
    kh.update(items, vals)
    ref = hh.update_reference(hspec,
                              hh.init_hierarchy(hspec, key, dtype=jnp.float32),
                              jnp.asarray(items), jnp.asarray(vals))
    for a, b in zip(kh.state().states, ref.states):
        np.testing.assert_array_equal(np.asarray(a.table),
                                      np.asarray(b.table))


def test_fused_kernel_f32_random_weights_close():
    """Arbitrary float weights: tolerance-level parity (accumulation order
    differs between MXU contraction and scatter order)."""
    hspec = _hier(ranges=(32, 16, 5))
    key = jax.random.PRNGKey(13)
    rng = np.random.default_rng(6)
    items, _ = _stream(hspec, 256, seed=7)
    vals = rng.standard_normal(256).astype(np.float32)
    kh = KernelHierarchy(hspec, key, tile_h=128, block_b=256,
                         dtype=jnp.float32, interpret=True)
    kh.update(items, vals)
    ref = hh.update_reference(hspec,
                              hh.init_hierarchy(hspec, key, dtype=jnp.float32),
                              jnp.asarray(items), jnp.asarray(vals))
    for a, b in zip(kh.state().states, ref.states):
        np.testing.assert_allclose(np.asarray(a.table), np.asarray(b.table),
                                   rtol=1e-5, atol=1e-4)


def test_fused_kernel_zero_freq_pad_rows_neutral():
    """A block shorter than block_b is zero-padded; the pad rows hash to
    real cells but add frequency 0, so no table cell may change."""
    hspec = _hier()
    key = jax.random.PRNGKey(17)
    items, freqs = _stream(hspec, 131, seed=8)   # 131 % 128 != 0
    kh = KernelHierarchy(hspec, key, tile_h=128, block_b=128, interpret=True)
    kh.update(items, freqs)
    ref = hh.update_reference(hspec, hh.init_hierarchy(hspec, key),
                              jnp.asarray(items), jnp.asarray(freqs))
    for a, b in zip(kh.state().states, ref.states):
        np.testing.assert_array_equal(np.asarray(a.table),
                                      np.asarray(b.table))
    # explicit zero-frequency items are also no-ops through the kernel
    before = np.asarray(kh.table).copy()
    kh.update(items[:64], np.zeros(64, np.int32))
    np.testing.assert_array_equal(before, np.asarray(kh.table))


def test_fused_kernel_multi_block_matches_one_shot():
    """Streaming through several fixed-size blocks equals one reference
    fold of the whole stream (linearity + in-place donation)."""
    hspec = _hier(ranges=(16, 8, 6), w=2)
    key = jax.random.PRNGKey(19)
    items, freqs = _stream(hspec, 700, seed=9)
    kh = KernelHierarchy(hspec, key, tile_h=128, block_b=256, interpret=True)
    for s, e in ((0, 300), (300, 700)):
        kh.update(items[s:e], freqs[s:e])
    ref = hh.update_reference(hspec, hh.init_hierarchy(hspec, key),
                              jnp.asarray(items), jnp.asarray(freqs))
    for a, b in zip(kh.state().states, ref.states):
        np.testing.assert_array_equal(np.asarray(a.table),
                                      np.asarray(b.table))


def test_kernel_hierarchy_rejects_independent_params():
    """The fused kernel hashes with the finest params only; adopting a
    state whose levels were drawn independently must be refused loudly."""
    hspec = _hier()
    keys = jax.random.split(jax.random.PRNGKey(23), hspec.n_levels)
    indep = hh.HierarchyState(states=tuple(
        sk.init_state(s, k) for s, k in zip(hspec.levels, keys)))
    with pytest.raises(ValueError, match="shared per-group hash family"):
        KernelHierarchy.from_state(hspec, indep)


def test_fused_kernel_freq_guard():
    hspec = _hier()
    kh = KernelHierarchy(hspec, jax.random.PRNGKey(0), tile_h=128,
                         block_b=8, interpret=True)
    items, _ = _stream(hspec, 8, seed=1, dup=False)
    with pytest.raises(ValueError, match="negative"):
        kh.update(items, np.array([1, -1] * 4, np.int32))
    with pytest.raises(ValueError, match="2\\^24"):
        kh.update(items, np.full(8, 1 << 24, np.int64))
    assert np.asarray(kh.table).max() == 0


# --------------------------------------------------------------------------
# Endpoint + descent under the shared family
# --------------------------------------------------------------------------

def test_endpoint_fused_ingest_matches_reference_endpoint():
    """use_update_kernel=True must leave every observable identical: level
    tables bit-exact, same heavy_hitters and topk output."""
    wl = zipf_hh_workload(phi=0.004, n_occurrences=50_000, n_edges=5_000,
                          seed=2)
    spec = sk.mod_sketch_spec(wl.stream.schema, [(0,), (1,)], (128, 128), 3)
    key = jax.random.PRNGKey(0)
    plain = SketchTopKEndpoint(spec, key)
    fused = SketchTopKEndpoint(spec, key, use_update_kernel=True)
    # uneven blocks exercise the kernel's internal padding
    edges = [0, 313, 1200, len(wl.stream.items)]
    for s, e in zip(edges[:-1], edges[1:]):
        plain.ingest(wl.stream.items[s:e], wl.stream.freqs[s:e])
        fused.ingest(wl.stream.items[s:e], wl.stream.freqs[s:e])
    assert fused.total == plain.total
    for a, b in zip(fused.state.states, plain.state.states):
        np.testing.assert_array_equal(np.asarray(a.table),
                                      np.asarray(b.table))
    pi, pe = plain.heavy_hitters(wl.threshold)
    fi, fe = fused.heavy_hitters(wl.threshold)
    np.testing.assert_array_equal(pi, fi)
    np.testing.assert_array_equal(pe, fe)
    ti, te = plain.topk(8)
    ui, ue = fused.topk(8)
    np.testing.assert_array_equal(ti, ui)
    np.testing.assert_array_equal(te, ue)
    # no false negatives through the fused path (exact ground truth)
    exact = {tuple(r) for r in wl.exact_items.tolist()}
    got = {tuple(r) for r in fi.tolist()}
    assert exact <= got, exact - got


def test_endpoint_fused_merge_and_to_sharded_roundtrip():
    """merge_from and to_sharded must work through the fused endpoint's
    state property (tables packed/unpacked losslessly)."""
    wl = zipf_hh_workload(phi=0.01, n_occurrences=10_000, n_edges=2_000,
                          seed=4)
    spec = sk.mod_sketch_spec(wl.stream.schema, [(0,), (1,)], (32, 32), 2)
    key = jax.random.PRNGKey(0)
    half = len(wl.stream.items) // 2
    a = SketchTopKEndpoint(spec, key, use_update_kernel=True)
    b = SketchTopKEndpoint(spec, key, use_update_kernel=True)
    a.ingest(wl.stream.items[:half], wl.stream.freqs[:half])
    b.ingest(wl.stream.items[half:], wl.stream.freqs[half:])
    a.merge_from(b)
    whole = SketchTopKEndpoint(spec, key)
    whole.ingest(wl.stream.items, wl.stream.freqs)
    for x, y in zip(a.state.states, whole.state.states):
        np.testing.assert_array_equal(np.asarray(x.table),
                                      np.asarray(y.table))
    mesh = make_mesh((1,), ("data",))
    svc = a.to_sharded(mesh)
    hi_a, _ = svc.heavy_hitters(wl.threshold)
    hi_w, _ = whole.heavy_hitters(wl.threshold)
    np.testing.assert_array_equal(hi_a, hi_w)


def test_conservative_endpoint_ignores_update_kernel_flag():
    """Conservative mode cannot take the fused linear kernel: asking for it
    is refused at construction (never silently ignored), and the endpoint
    without the flag folds on the jnp per-level path (which still shares
    the cascade's single hash pass), matching the core conservative fold."""
    hspec_spec = sk.mod_sketch_spec(KeySchema(domains=(1 << 16, 1 << 16)),
                                    [(0,), (1,)], (16, 16), 2)
    rng = np.random.default_rng(0)
    items = rng.integers(0, 1 << 12, size=(200, 2)).astype(np.uint32)
    freqs = rng.integers(1, 50, size=200).astype(np.int64)
    key = jax.random.PRNGKey(1)
    with pytest.raises(ValueError, match="use_update_kernel=False"):
        SketchTopKEndpoint(hspec_spec, key, mode="conservative",
                           use_update_kernel=True)
    c1 = SketchTopKEndpoint(hspec_spec, key, mode="conservative")
    assert c1._kh is None
    c1.ingest(items, freqs)
    hspec = hh.HierarchySpec.from_spec(hspec_spec)
    want = hh.update_conservative_jit(
        hspec, hh.init_hierarchy(hspec, key), jnp.asarray(items),
        jnp.asarray(freqs))
    for a, b in zip(c1.state.states, want.states):
        np.testing.assert_array_equal(np.asarray(a.table),
                                      np.asarray(b.table))


def test_cascade_entry_points_reject_independent_params():
    """Regression: hh.update/update_jit/update_conservative_jit derive
    coarse cells from the finest index, which is garbage for independently
    drawn per-level params -- they must refuse such states loudly instead
    of silently corrupting every coarse level (update_reference remains
    the escape hatch)."""
    hspec = _hier(ranges=(16, 8, 4), w=2)
    keys = jax.random.split(jax.random.PRNGKey(29), hspec.n_levels)
    indep = hh.HierarchyState(states=tuple(
        sk.init_state(s, k) for s, k in zip(hspec.levels, keys)))
    items, freqs = _stream(hspec, 64, seed=11, dup=False)
    it, fr = jnp.asarray(items), jnp.asarray(freqs)
    for fold in (hh.update, hh.update_jit, hh.update_conservative,
                 hh.update_conservative_jit):
        with pytest.raises(ValueError, match="shared per-group hash family"):
            fold(hspec, indep, it, fr)
    # update_reference still serves pre-cascade states
    hh.update_reference(hspec, indep, it, fr)


def test_endpoint_ingest_after_to_sharded_keeps_service_alive():
    """Regression: to_sharded must COPY the endpoint's tables -- the
    endpoint's donating ingest would otherwise delete buffers the promoted
    service still reads."""
    wl = zipf_hh_workload(phi=0.01, n_occurrences=8_000, n_edges=1_500,
                          seed=6)
    spec = sk.mod_sketch_spec(wl.stream.schema, [(0,), (1,)], (32, 32), 2)
    ep = SketchTopKEndpoint(spec, jax.random.PRNGKey(0))
    half = len(wl.stream.items) // 2
    ep.ingest(wl.stream.items[:half], wl.stream.freqs[:half])
    mesh = make_mesh((1,), ("data",))
    svc = ep.to_sharded(mesh)
    snapshot = [np.asarray(st.table).copy() for st in svc.state().states]
    # continued single-shard ingest donates the ENDPOINT's tables ...
    ep.ingest(wl.stream.items[half:], wl.stream.freqs[half:])
    # ... and the service must still serve from its own (copied) buffers
    for before, st in zip(snapshot, svc.state().states):
        np.testing.assert_array_equal(before, np.asarray(st.table))
    svc.topk(3)


def test_sharded_build_bit_exact_under_shared_params():
    """sharded_hierarchy_build (one shard_map, cascade fold + psum) on a
    single-device mesh is bit-exact vs the serial cascade build -- the
    multi-device sweep rides in tests/test_sharded_topk.py."""
    hspec = _hier(ranges=(16, 8, 4), w=2)
    key = jax.random.PRNGKey(2)
    items, freqs = _stream(hspec, 512, seed=10)
    mesh = make_mesh((1,), ("data",))
    state0 = hh.init_hierarchy(hspec, key)
    got = hh.sharded_hierarchy_build(hspec, state0, mesh, ("data",),
                                     jnp.asarray(items),
                                     jnp.asarray(freqs.astype(np.int32)))
    want = hh.build_hierarchy(hspec, key, items, freqs)
    for g, w_ in zip(got.states, want.states):
        np.testing.assert_array_equal(np.asarray(g.table),
                                      np.asarray(w_.table))
