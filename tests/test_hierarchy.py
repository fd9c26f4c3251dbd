"""Hierarchical heavy-hitter subsystem: recovery guarantees, kernel/reference
parity, level-spec structure, merge linearity, serving endpoint."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hierarchy as hh
from repro.core import sketch as sk
from repro.core.hashing import KeySchema
from repro.kernels.hier_query import hier_candidate_query, hier_candidate_query_ref
from repro.serving.engine import SketchTopKEndpoint
from repro.streams import (
    exact_heavy_hitters,
    group_candidates,
    ngram_hh_workload,
    zipf_hh_workload,
)


def _build(wl, ranges=(256, 256), w=4, key=0):
    base = sk.mod_sketch_spec(wl.stream.schema, [(0,), (1,)], ranges, w)
    hspec = hh.HierarchySpec.from_spec(base)
    state = hh.build_hierarchy(hspec, jax.random.PRNGKey(key),
                               wl.stream.items, wl.stream.freqs)
    return base, hspec, state


def test_level_spec_structure():
    schema = KeySchema(domains=(1 << 32, 256, 1000))
    base = sk.mod_sketch_spec(schema, [(1, 2), (0,)], (128, 512), 3)
    hspec = hh.HierarchySpec.from_spec(base)
    assert hspec.n_levels == 2
    # coarse level: only group 0's modules, renumbered consecutively
    assert hspec.levels[0].schema.domains == (256, 1000)
    assert hspec.levels[0].ranges == (128,)
    # top level covers the full key (group-major module order) and has the
    # base's table size; candidate strides nest (stride identity)
    assert hspec.levels[1].schema.domains == (256, 1000, 1 << 32)
    assert hspec.levels[1].table_size == base.table_size
    assert hspec.levels[1].strides[0] == hspec.levels[0].strides[0] * 512
    # schema-order round trip
    items = np.arange(12, dtype=np.uint32).reshape(4, 3)
    reordered = np.asarray(hspec.level_items(1, items))
    assert (hspec.to_schema_order(reordered) == items).all()


def test_zipf_recovery_no_false_negatives():
    """Acceptance: 10^5-occurrence zipf(1.1) stream, every item with true
    frequency >= threshold recovered; false positives within the CM
    overestimate slack."""
    wl = zipf_hh_workload(phi=0.002, n_occurrences=100_000, s=1.1, seed=0)
    base, hspec, state = _build(wl)
    got_items, got_est = hh.find_heavy_hitters(
        hspec, state, wl.threshold, wl.candidates(base))

    exact = {tuple(r) for r in wl.exact_items.tolist()}
    got = {tuple(r) for r in got_items.tolist()}
    assert exact <= got, f"false negatives: {exact - got}"

    # false positives: each reported key's true frequency must be within
    # the leaf-level CM slack eps*L of the threshold.  The slack constant
    # accounts for the max-over-candidates selection effect (thousands of
    # keys reach the leaf, so the worst overestimate governs, not the
    # per-key bound) and for the shared per-group family: leaf-colliding
    # keys collide at every ancestor too, so ancestor levels cannot prune
    # leaf-collision false positives (the leaf bound itself is unchanged).
    uniq, inv = np.unique(wl.stream.items, axis=0, return_inverse=True)
    tot = np.bincount(inv, weights=wl.stream.freqs.astype(np.float64))
    true_of = {tuple(k): int(v) for k, v in zip(uniq.tolist(), tot)}
    eps_l = 32.0 / base.table_size * wl.stream.total
    for t in got:
        assert true_of[t] >= wl.threshold - eps_l, (t, true_of[t])
    # estimates are CM overestimates of the truth
    for t, e in zip(got_items.tolist(), got_est.tolist()):
        assert e >= true_of[tuple(t)]


def test_ngram_recovery():
    wl = ngram_hh_workload(vocab_size=512, n=2, phi=0.003, seed=1)
    base, hspec, state = _build(wl, ranges=(128, 128))
    got_items, _ = hh.find_heavy_hitters(
        hspec, state, wl.threshold, wl.candidates(base))
    exact = {tuple(r) for r in wl.exact_items.tolist()}
    got = {tuple(r) for r in got_items.tolist()}
    assert exact <= got


def test_kernel_matches_reference_exactly():
    """Acceptance: the Pallas candidate kernel is bit-identical to the jnp
    reference on int32 tables -- both raw (random partials) and end-to-end
    through the descent."""
    rng = np.random.default_rng(0)
    w, h, p, c = 3, 1000, 17, 29  # h deliberately not a tile multiple
    table = jnp.asarray(rng.integers(0, 1 << 20, (w, h)).astype(np.int32))
    cp = rng.integers(0, 64, (w, c)).astype(np.uint32)
    pp = (rng.integers(0, h // 64, (w, p)) * 64).astype(np.uint32)
    got = hier_candidate_query(table, jnp.asarray(pp), jnp.asarray(cp),
                               interpret=True)
    want = hier_candidate_query_ref(table, jnp.asarray(pp), jnp.asarray(cp))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    wl = zipf_hh_workload(phi=0.004, n_occurrences=50_000, n_edges=5_000)
    base, hspec, state = _build(wl)
    cands = wl.candidates(base)
    ri, re = hh.find_heavy_hitters(hspec, state, wl.threshold, cands,
                                   use_kernel=False)
    ki, ke = hh.find_heavy_hitters(hspec, state, wl.threshold, cands,
                                   use_kernel=True)
    np.testing.assert_array_equal(ri, ki)
    np.testing.assert_array_equal(re, ke)


def test_candidate_separability_equals_direct_query():
    """pp + cp must reproduce compute_indices of the level spec exactly:
    the grid estimates equal a flat sk.query over the materialized children."""
    wl = zipf_hh_workload(phi=0.01, n_occurrences=20_000, n_edges=3_000)
    base, hspec, state = _build(wl, ranges=(64, 64), w=3)
    prefixes = np.unique(wl.stream.items[:, 0])[:40][:, None]
    values = np.unique(wl.stream.items[:, 1])[:50][:, None]
    grid = hh.candidate_estimates(hspec, state, 1, prefixes, values)
    children = np.concatenate(
        [np.repeat(prefixes, len(values), 0),
         np.tile(values, (len(prefixes), 1))], axis=1)
    direct = np.asarray(sk.query(hspec.levels[1], state.states[1],
                                 jnp.asarray(children)))
    np.testing.assert_array_equal(grid.reshape(-1), direct)


def test_hierarchy_merge_linear():
    wl = zipf_hh_workload(phi=0.01, n_occurrences=20_000, n_edges=3_000)
    base = sk.mod_sketch_spec(wl.stream.schema, [(0,), (1,)], (64, 64), 3)
    hspec = hh.HierarchySpec.from_spec(base)
    key = jax.random.PRNGKey(2)
    items, freqs = wl.stream.items, wl.stream.freqs
    half = len(items) // 2
    a = hh.build_hierarchy(hspec, key, items[:half], freqs[:half])
    b = hh.build_hierarchy(hspec, key, items[half:], freqs[half:])
    whole = hh.build_hierarchy(hspec, key, items, freqs)
    merged = hh.merge(a, b)
    for m, w_ in zip(merged.states, whole.states):
        np.testing.assert_array_equal(np.asarray(m.table),
                                      np.asarray(w_.table))


def test_three_module_hierarchy_with_joint_group():
    """Multi-module group at level 0 + 2-chunk module at level 1."""
    schema = KeySchema(domains=(1 << 32, 256, 1000))
    base = sk.mod_sketch_spec(schema, [(1, 2), (0,)], (512, 512), 3)
    hspec = hh.HierarchySpec.from_spec(base)
    rng = np.random.default_rng(3)
    n = 300
    items = np.stack(
        [rng.integers(0, 1 << 32, n, dtype=np.uint64).astype(np.uint32),
         rng.integers(0, 256, n).astype(np.uint32),
         rng.integers(0, 1000, n).astype(np.uint32)], axis=1)
    freqs = (rng.pareto(1.5, n) * 30 + 1).astype(np.int64)
    state = hh.build_hierarchy(hspec, jax.random.PRNGKey(4), items, freqs)
    exact_i, _ = exact_heavy_hitters(items, freqs, 200)
    exact = {tuple(r) for r in exact_i.tolist()}
    cands = group_candidates(base, items)
    gi, _ = hh.find_heavy_hitters(hspec, state, 200, cands,
                                  max_batch=1 << 14)
    got = {tuple(r) for r in gi.tolist()}
    assert exact <= got
    # returned columns are in schema module order
    if len(gi):
        assert (gi[:, 1] < 256).all() and (gi[:, 2] < 1000).all()


def test_find_heavy_hitters_validates_candidates():
    wl = zipf_hh_workload(phi=0.01, n_occurrences=10_000, n_edges=2_000)
    base, hspec, state = _build(wl, ranges=(32, 32), w=2)
    with pytest.raises(ValueError, match="one candidate set per level"):
        hh.find_heavy_hitters(hspec, state, 10,
                              [np.zeros((1, 1), np.uint32)])
    with pytest.raises(ValueError, match="candidates\\[0\\]"):
        hh.find_heavy_hitters(hspec, state, 10,
                              [np.zeros((1, 2), np.uint32)] * 2)


def test_kernel_rejects_non_int32_tables():
    """The Pallas candidate gather covers int32; other dtypes must be
    refused loudly (the dtype-preserving reference path is exercised under
    x64 below)."""
    from repro.kernels.hier_query import hier_candidate_query
    with pytest.raises(ValueError, match="int32 tables only"):
        hier_candidate_query(jnp.zeros((2, 64), jnp.float32),
                             jnp.zeros((2, 1), jnp.uint32),
                             jnp.zeros((2, 1), jnp.uint32))


def test_int64_tables_route_to_dtype_preserving_path():
    """use_kernel on an int64 hierarchy must not wrap counts through the
    kernel's int32 gather: the kernel path refuses and names the reference
    path, which keeps exact 64-bit estimates.  int64 tables only exist
    under jax_enable_x64, so this runs in a subprocess."""
    import os
    import subprocess
    import sys
    import textwrap

    env = dict(os.environ)
    env["JAX_ENABLE_X64"] = "1"
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..", "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import sketch as sk, hierarchy as hh
        from repro.core.hashing import KeySchema
        from repro.streams import group_candidates
        schema = KeySchema(domains=(1 << 16, 1 << 16))
        base = sk.mod_sketch_spec(schema, [(0,), (1,)], (16, 16), 2)
        hspec = hh.HierarchySpec.from_spec(base)
        items = np.array([[7, 9]], np.uint32)
        freqs = np.array([1 << 33], np.int64)
        state = hh.build_hierarchy(hspec, jax.random.PRNGKey(0), items,
                                   freqs, dtype=jnp.int64)
        assert state.states[0].table.dtype == jnp.int64
        cands = group_candidates(base, items)
        gi, ge = hh.find_heavy_hitters(hspec, state, 1 << 33, cands,
                                       use_kernel=False)
        assert gi.tolist() == [[7, 9]], gi
        assert int(ge[0]) >= 1 << 33, ge
        try:
            hh.find_heavy_hitters(hspec, state, 1 << 33, cands,
                                  use_kernel=True)
        except ValueError as e:
            assert "use_kernel=False" in str(e), e
        else:
            raise AssertionError("kernel path accepted an int64 table")
        print("int64 ok")
    """)], capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    assert "int64 ok" in out.stdout


def test_endpoint_pool_admission_space_saving():
    """Space-saving admission (core/summary.py): heavy group values enter
    the candidate pools regardless of arrival order -- early heavies
    survive a flood of light values, and late heavies evict light entries
    instead of being dropped at the cap (the old append-only behaviour)."""
    schema = KeySchema(domains=(1 << 32, 1 << 32))
    spec = sk.mod_sketch_spec(schema, [(0,), (1,)], (16, 16), 2)
    ep = SketchTopKEndpoint(spec, jax.random.PRNGKey(0),
                            max_candidates_per_group=8)
    big = np.full((6, 2), 0xFFFF0000, np.uint32) + np.arange(6, dtype=np.uint32)[:, None]
    ep.ingest(big, np.full(6, 100, np.int64))
    # flood with light values: heavies must not be evicted
    small = np.arange(40, dtype=np.uint32).reshape(20, 2)
    ep.ingest(small, np.ones(20, np.int64))
    for j, cand in enumerate(ep.candidates()):
        assert len(ep._pools[j]) == 8  # at capacity
        assert {int(v) for v in big[:, j]} <= {int(v) for v in cand[:, 0]}
    items, _ = ep.heavy_hitters(100)
    got = {tuple(r) for r in items.tolist()}
    assert {tuple(r) for r in big.tolist()} <= got

    # reverse order: pools full of light values, then late-arriving heavies
    ep2 = SketchTopKEndpoint(spec, jax.random.PRNGKey(0),
                             max_candidates_per_group=8)
    ep2.ingest(small, np.ones(20, np.int64))
    late = np.full((4, 2), 0xAAAA0000, np.uint32) + np.arange(4, dtype=np.uint32)[:, None]
    ep2.ingest(late, np.full(4, 500, np.int64))
    items2, est2 = ep2.heavy_hitters(400)
    got2 = {tuple(r) for r in items2.tolist()}
    assert {tuple(r) for r in late.tolist()} <= got2  # old code dropped these

    # merged shards keep heavy values from both sides within the cap
    ep.merge_from(ep2)
    items3, _ = ep.heavy_hitters(400)
    got3 = {tuple(r) for r in items3.tolist()}
    assert {tuple(r) for r in late.tolist()} <= got3


def test_topk_endpoint_ranks_head():
    wl = zipf_hh_workload(phi=0.002, n_occurrences=50_000, seed=5)
    spec = sk.mod_sketch_spec(wl.stream.schema, [(0,), (1,)], (256, 256), 4)
    ep = SketchTopKEndpoint(spec, jax.random.PRNGKey(0))
    ep.ingest(wl.stream.items, wl.stream.freqs)
    assert ep.total == wl.stream.total
    items, est = ep.topk(5)
    assert items.shape == (5, 2)
    # the true heaviest key must be reported first (estimates only inflate)
    assert tuple(items[0]) == tuple(wl.exact_items[0])
    assert est[0] >= wl.exact_freqs[0]
