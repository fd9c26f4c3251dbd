"""Sharded heavy-hitter serving: shard-invariance harness + properties.

The multi-device tests spawn a fresh interpreter with an XLA host-device
override (pattern from tests/test_distributed.py) so the main test process
keeps its single-device view.  The forced device count defaults to 8 and
can be lowered via REPRO_TEST_DEVICES (the CI device-count matrix leg sets
it); shard-count sweeps adapt to whatever is available.

Single-device properties (merge algebra, shard merges via hh.merge, the
conservative-mode refusals) run in-process so they are part of tier-1.
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from _propcheck import given, settings, st
from repro.launch.mesh import make_mesh

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")
_DEVICES = int(os.environ.get("REPRO_TEST_DEVICES", "8"))


def _run(code: str, devices: int = _DEVICES) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    return out.stdout


# --------------------------------------------------------------------------
# Shard-invariance harness (acceptance): 1/2/4/8 shards, any stream split,
# bit-identical level tables and identical heavy_hitters / topk output.
# --------------------------------------------------------------------------

def test_shard_invariance_tables_and_topk():
    print(_run(f"""
        import jax, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.core import sketch as sk, hierarchy as hh
        from repro.serving.sharded_topk import ShardedTopKService
        from repro.streams import zipf_hh_workload

        wl = zipf_hh_workload(n_occurrences=60_000, n_edges=8_000, seed=3)
        spec = sk.mod_sketch_spec(wl.stream.schema, [(0,), (1,)],
                                  (128, 128), 3)
        key = jax.random.PRNGKey(7)
        items, freqs = wl.stream.items, wl.stream.freqs
        counts = [c for c in (1, 2, 4, 8) if c <= jax.device_count()]
        assert counts[-1] >= 2, f"need >= 2 devices, got {{counts}}"

        ref = None
        for ci, c in enumerate(counts):
            mesh = make_mesh((c,), ("data",))
            svc = ShardedTopKService(spec, key, mesh, sync_every=2)
            # a different split of the same stream for every shard count
            edges = np.linspace(0, len(items), ci + 3).astype(int)
            for s, e in zip(edges[:-1], edges[1:]):
                svc.ingest(items[s:e], freqs[s:e])
            svc.sync()
            assert svc.total == wl.stream.total
            tables = [np.asarray(st.table) for st in svc.state().states]
            hh_i, hh_e = svc.heavy_hitters(wl.threshold)
            tk_i, tk_e = svc.topk(10)
            if ref is None:
                ref = (tables, hh_i, hh_e, tk_i, tk_e)
            else:
                for a, b in zip(ref[0], tables):
                    assert (a == b).all(), f"level table mismatch at {{c}}"
                assert np.array_equal(ref[1], hh_i)
                assert np.array_equal(ref[2], hh_e)
                assert np.array_equal(ref[3], tk_i)
                assert np.array_equal(ref[4], tk_e)

        # same shard count, two different splits: also identical
        mesh = make_mesh((counts[-1],), ("data",))
        svc2 = ShardedTopKService(spec, key, mesh, sync_every=1)
        svc2.ingest(items[:100], freqs[:100])
        svc2.ingest(items[100:], freqs[100:])
        for a, b in zip(ref[0],
                        [np.asarray(st.table) for st in svc2.state().states]):
            assert (a == b).all()
        tk2_i, tk2_e = svc2.topk(10)
        assert np.array_equal(ref[3], tk2_i) and np.array_equal(ref[4], tk2_e)

        # the merged tables equal the single-device build bit-for-bit, and
        # no true heavy hitter is lost (exact ground truth)
        hspec = hh.HierarchySpec.from_spec(spec)
        want = hh.build_hierarchy(hspec, key, items, freqs)
        for a, w in zip(ref[0], want.states):
            assert (a == np.asarray(w.table)).all()
        exact = {{tuple(r) for r in wl.exact_items.tolist()}}
        got = {{tuple(r) for r in ref[1].tolist()}}
        assert exact <= got, exact - got
        print("shard invariance OK", counts)
    """))


def test_sharded_service_sync_cadence_and_kernel_descent():
    """Lazy accumulation across many blocks between syncs must equal
    synchronous per-block syncing, and the Pallas candidate kernel must
    agree with the reference descent on the merged tables."""
    print(_run("""
        import jax, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.core import sketch as sk
        from repro.serving.sharded_topk import ShardedTopKService
        from repro.streams import zipf_hh_workload

        wl = zipf_hh_workload(n_occurrences=30_000, n_edges=4_000, seed=9)
        spec = sk.mod_sketch_spec(wl.stream.schema, [(0,), (1,)], (64, 64), 3)
        key = jax.random.PRNGKey(1)
        items, freqs = wl.stream.items, wl.stream.freqs
        c = min(4, jax.device_count())
        mesh = make_mesh((c,), ("data",))

        lazy = ShardedTopKService(spec, key, mesh, sync_every=None)
        sync = ShardedTopKService(spec, key, mesh, sync_every=1)
        edges = np.linspace(0, len(items), 6).astype(int)
        for s, e in zip(edges[:-1], edges[1:]):
            lazy.ingest(items[s:e], freqs[s:e])
            sync.ingest(items[s:e], freqs[s:e])
        assert lazy._dirty and not sync._dirty
        for a, b in zip(lazy.state().states, sync.state().states):
            assert (np.asarray(a.table) == np.asarray(b.table)).all()

        krn = ShardedTopKService(spec, key, mesh, use_kernel=True)
        krn.ingest(items, freqs)
        ri, re = lazy.heavy_hitters(wl.threshold)
        ki, ke = krn.heavy_hitters(wl.threshold)
        assert np.array_equal(ri, ki) and np.array_equal(re, ke)
        print("sync cadence + kernel descent OK")
    """))


def test_kernel_descent_runs_on_one_replica():
    """The merged tables are replicated over the mesh, and XLA cannot
    partition a Pallas kernel: the kernel descent must get every operand
    on one device (the first replica), before and after a remesh, and
    answer like the reference descent."""
    print(_run("""
        import jax, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.core import hierarchy as hh, sketch as sk
        from repro.kernels import hier_query
        from repro.serving.sharded_topk import ShardedTopKService
        from repro.streams import zipf_hh_workload

        launched = []
        kernel = hier_query.hier_candidate_query_batched

        def one_device_only(table, pp, cp, **kw):
            for a in (table, pp, cp):
                assert len(a.sharding.device_set) == 1, a.sharding
            launched.append(table.shape)
            return kernel(table, pp, cp, **kw)
        hier_query.hier_candidate_query_batched = one_device_only

        wl = zipf_hh_workload(n_occurrences=20_000, n_edges=3_000, seed=5)
        spec = sk.mod_sketch_spec(wl.stream.schema, [(0,), (1,)], (64, 64), 3)
        key = jax.random.PRNGKey(2)
        items, freqs = wl.stream.items, wl.stream.freqs
        ref = ShardedTopKService(spec, key, make_mesh((1,), ("data",)))
        ref.ingest(items, freqs)
        want = ref.heavy_hitters(wl.threshold)

        svc = ShardedTopKService(spec, key, make_mesh((4,), ("data",)),
                                 use_kernel=True)
        svc.ingest(items, freqs)
        assert len(svc.state().states[-1].table.sharding.device_set) == 4
        for mesh in (None, make_mesh((2,), ("data",))):
            if mesh is not None:
                svc.remesh(mesh)     # params now replicated on the mesh too
            got = hh.batched_find_heavy_hitters(
                svc.hspec, svc.state(), [wl.threshold], svc.candidates(),
                use_kernel=True)[0]
            assert np.array_equal(got[0], want[0])
            assert np.array_equal(got[1], want[1])
        assert launched
        print("kernel descent on one replica OK")
    """, devices=4))


def test_endpoint_to_sharded_continuation():
    """Promoting a single-shard endpoint carries tables/pools/total over,
    and continued sharded ingest matches one endpoint fed the full stream."""
    print(_run("""
        import jax, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.core import sketch as sk
        from repro.serving.engine import SketchTopKEndpoint
        from repro.streams import zipf_hh_workload

        wl = zipf_hh_workload(n_occurrences=20_000, n_edges=3_000, seed=1)
        spec = sk.mod_sketch_spec(wl.stream.schema, [(0,), (1,)], (64, 64), 3)
        key = jax.random.PRNGKey(0)
        half = len(wl.stream.items) // 2

        ep = SketchTopKEndpoint(spec, key)
        ep.ingest(wl.stream.items[:half], wl.stream.freqs[:half])
        mesh = make_mesh((min(4, jax.device_count()),), ("data",))
        svc = ep.to_sharded(mesh)
        svc.ingest(wl.stream.items[half:], wl.stream.freqs[half:])

        whole = SketchTopKEndpoint(spec, key)
        whole.ingest(wl.stream.items, wl.stream.freqs)
        assert svc.total == whole.total
        for a, b in zip(svc.state().states, whole.state.states):
            assert (np.asarray(a.table) == np.asarray(b.table)).all()
        # same tables + same candidate *sets* => same estimates
        ti, te = svc.topk(5)
        wi, we = whole.topk(5)
        assert np.array_equal(te, we)
        assert {tuple(r) for r in ti.tolist()} \\
            == {tuple(r) for r in wi.tolist()}
        print("to_sharded continuation OK")
    """))


# --------------------------------------------------------------------------
# Property tests (single device, tier-1): merge algebra + shard merges
# --------------------------------------------------------------------------

def _tiny_hierarchy(seed: int, n_items: int = 200):
    """A small 2-level hierarchy plus a random weighted stream."""
    from repro.core import hierarchy as hh
    from repro.core import sketch as sk
    from repro.core.hashing import KeySchema

    rng = np.random.default_rng(seed)
    schema = KeySchema(domains=(1 << 16, 1 << 16))
    base = sk.mod_sketch_spec(schema, [(0,), (1,)], (16, 32), 3)
    hspec = hh.HierarchySpec.from_spec(base)
    items = rng.integers(0, 1 << 12, size=(n_items, 2)).astype(np.uint32)
    freqs = rng.integers(1, 50, size=n_items).astype(np.int64)
    return hspec, items, freqs


def _assert_states_equal(a, b):
    for sa, sb in zip(a.states, b.states):
        np.testing.assert_array_equal(np.asarray(sa.table),
                                      np.asarray(sb.table))


@given(st.integers(0, 2**31 - 1), st.sampled_from([2, 3, 4]))
@settings(max_examples=10, deadline=None)
def test_hierarchy_merge_commutative_associative(seed, n_parts):
    """Cell-wise hierarchy merge is commutative and associative, and
    folding any shard split of a stream reproduces the unsharded build."""
    from repro.core import hierarchy as hh

    hspec, items, freqs = _tiny_hierarchy(seed)
    key = jax.random.PRNGKey(seed % (1 << 30))
    bounds = np.linspace(0, len(items), n_parts + 1).astype(int)
    parts = [hh.build_hierarchy(hspec, key, items[s:e], freqs[s:e])
             for s, e in zip(bounds[:-1], bounds[1:])]

    _assert_states_equal(hh.merge(parts[0], parts[1]),
                         hh.merge(parts[1], parts[0]))
    if n_parts >= 3:
        _assert_states_equal(
            hh.merge(hh.merge(parts[0], parts[1]), parts[2]),
            hh.merge(parts[0], hh.merge(parts[1], parts[2])))

    folded = parts[0]
    for p in parts[1:]:
        folded = hh.merge(folded, p)
    _assert_states_equal(folded,
                         hh.build_hierarchy(hspec, key, items, freqs))


@given(st.integers(0, 5), st.sampled_from([2, 4]),
       st.sampled_from(["zipf", "ngram"]))
@settings(max_examples=6, deadline=None)
def test_no_false_negatives_survive_shard_merge(seed, n_shards, kind):
    """The no-false-negative guarantee (vs exact ground truth) holds for a
    hierarchy assembled by merging independently built shard states."""
    from repro.core import hierarchy as hh
    from repro.core import sketch as sk
    from repro.streams import ngram_hh_workload, zipf_hh_workload

    if kind == "zipf":
        wl = zipf_hh_workload(phi=0.004, n_occurrences=20_000,
                              n_edges=3_000, seed=seed)
    else:
        wl = ngram_hh_workload(vocab_size=256, n=2, phi=0.004,
                               n_sequences=16, seq_len=128, seed=seed)
    base = sk.mod_sketch_spec(wl.stream.schema, [(0,), (1,)], (64, 64), 3)
    hspec = hh.HierarchySpec.from_spec(base)
    key = jax.random.PRNGKey(seed)
    items, freqs = wl.stream.items, wl.stream.freqs
    bounds = np.linspace(0, len(items), n_shards + 1).astype(int)
    merged = None
    for s, e in zip(bounds[:-1], bounds[1:]):
        part = hh.build_hierarchy(hspec, key, items[s:e], freqs[s:e])
        merged = part if merged is None else hh.merge(merged, part)
    got_i, got_e = hh.find_heavy_hitters(hspec, merged, wl.threshold,
                                         wl.candidates(base))
    exact = {tuple(r) for r in wl.exact_items.tolist()}
    got = {tuple(r) for r in got_i.tolist()}
    assert exact <= got, exact - got


def test_sharded_hierarchy_build_equals_single_device():
    """sharded_hierarchy_build over a real multi-device mesh is bit-exact
    vs build_hierarchy, across a few spec shapes (subprocess sweep)."""
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.core import hierarchy as hh, sketch as sk
        from repro.core.hashing import KeySchema

        mesh = make_mesh((jax.device_count(),), ("data",))
        rng = np.random.default_rng(0)
        for ranges, w, n_items in (((16, 16), 3, 4096), ((32, 8), 2, 2048)):
            schema = KeySchema(domains=(1 << 20, 1 << 20))
            base = sk.mod_sketch_spec(schema, [(0,), (1,)], ranges, w)
            hspec = hh.HierarchySpec.from_spec(base)
            key = jax.random.PRNGKey(w)
            items = rng.integers(0, 1 << 20, size=(n_items, 2),
                                 dtype=np.int64).astype(np.uint32)
            freqs = rng.integers(1, 9, size=n_items).astype(np.int32)
            state0 = hh.init_hierarchy(hspec, key)
            got = hh.sharded_hierarchy_build(
                hspec, state0, mesh, ("data",),
                jnp.asarray(items), jnp.asarray(freqs))
            want = hh.build_hierarchy(hspec, key, items, freqs)
            for g, t in zip(got.states, want.states):
                assert (np.asarray(g.table) == np.asarray(t.table)).all()
        print("sharded build parity OK")
    """))


# --------------------------------------------------------------------------
# Regression: every new sharded entry point refuses conservative mode
# --------------------------------------------------------------------------

def test_conservative_refuses_every_sharded_entry_point():
    from repro.core import distributed as dist
    from repro.core import hierarchy as hh
    from repro.core import sketch as sk
    from repro.core.hashing import KeySchema
    from repro.kernels import KernelSketch
    from repro.serving.engine import SketchTopKEndpoint
    from repro.serving.sharded_topk import ShardedTopKService

    schema = KeySchema(domains=(1 << 16, 1 << 16))
    spec = sk.mod_sketch_spec(schema, [(0,), (1,)], (8, 8), 2)
    hspec = hh.HierarchySpec.from_spec(spec)
    key = jax.random.PRNGKey(0)
    mesh = make_mesh((1,), ("data",))

    with pytest.raises(ValueError, match="single-shard"):
        ShardedTopKService(spec, key, mesh, mode="conservative")
    with pytest.raises(ValueError, match="single-shard"):
        KernelSketch(spec, key, mode="conservative").sharded_update(
            mesh, ("data",), np.zeros((2, 2), np.uint32), np.ones(2))
    with pytest.raises(ValueError, match="single-shard"):
        hh.sharded_hierarchy_build(
            hspec, hh.init_hierarchy(hspec, key), mesh, ("data",),
            np.zeros((2, 2), np.uint32), np.ones(2, np.int32),
            mode="conservative")
    with pytest.raises(ValueError, match="single-shard"):
        dist.lazy_hierarchy_update(hspec, mesh, ("data",), (), (),
                                   np.zeros((2, 2), np.uint32),
                                   np.ones(2, np.int32),
                                   mode="conservative")
    with pytest.raises(ValueError, match="single-shard"):
        SketchTopKEndpoint(spec, key, mode="conservative").to_sharded(mesh)
    # the linear service stays linear: mode is pinned at construction
    svc = ShardedTopKService(spec, key, mesh)
    assert svc.mode == "linear"


def test_kernel_sketch_sharded_update_parity():
    """KernelSketch.sharded_update (jit-cached psum fold, power-of-two
    padding) is bit-exact vs the reference serial build across uneven
    streamed blocks; multi-device coverage rides on the subprocess tests."""
    from repro.core import sketch as sk
    from repro.core.hashing import KeySchema
    from repro.kernels import KernelSketch

    schema = KeySchema(domains=(1 << 20, 1 << 20))
    spec = sk.mod_sketch_spec(schema, [(0,), (1,)], (16, 16), 3)
    key = jax.random.PRNGKey(5)
    rng = np.random.default_rng(5)
    items = rng.integers(0, 1 << 20, size=(700, 2),
                         dtype=np.int64).astype(np.uint32)
    freqs = rng.integers(1, 9, size=700).astype(np.int32)
    mesh = make_mesh((1,), ("data",))

    ks = KernelSketch(spec, key)
    for s, e in ((0, 300), (300, 700)):   # uneven blocks share one compile
        ks.sharded_update(mesh, ("data",), items[s:e], freqs[s:e])
    assert len(ks._sharded_folds) == 1
    want = sk.build_sketch(spec, key, items, freqs)
    np.testing.assert_array_equal(ks.table_view(), np.asarray(want.table))
