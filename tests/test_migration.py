"""Hot spec migration correctness: the double-write / cutover contract.

The contract under test (serving/migration.py):

  * **bit-identity** -- post-cutover, the migrated endpoint/service is
    bit-identical (tables, totals, topk output) to a fresh one built on
    the new spec from the same key and fed exactly the
    post-warmup-start stream;
  * **no false negatives across the window** -- at every point of a
    drifting stream, before / during / after the warmup window,
    ``heavy_hitters(T)`` reports every key whose exact count within the
    endpoint's serving window is >= T (the window is the whole stream
    until cutover, the post-migration-start suffix after);
  * **top-k continuity** -- ``topk`` keeps answering mid-warmup and
    post-cutover, with estimates that upper-bound the window-exact
    counts of every reported key;
  * **shard invariance composes** -- a ShardedTopKService migration is
    bit-identical across 1/2/4 shards (subprocess harness with forced
    host devices, pattern from tests/test_sharded_topk.py);
  * **refusals** -- conservative endpoints cannot begin a migration;
    ``merge_from`` / ``to_sharded`` / a second ``begin_migration`` are
    refused mid-warmup; SpecMigration rejects a non-empty successor.

Property tests randomize the warmup split, stream kind (zipf edges /
token bigrams) and seed through the _propcheck shim (hypothesis when
available, deterministic examples otherwise).
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from _propcheck import given, settings, st

from repro.core import sketch as sk
from repro.launch.mesh import make_mesh
from repro.serving.engine import SketchTopKEndpoint
from repro.serving.migration import SpecMigration, require_not_migrating
from repro.streams import ngram_hh_workload, zipf_hh_workload

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


def _workload(kind: str, seed: int):
    if kind == "zipf":
        wl = zipf_hh_workload(n_src=200, n_tgt=400, n_edges=1_500,
                              n_occurrences=8_000, seed=seed)
    else:
        wl = ngram_hh_workload(vocab_size=64, n=2, n_sequences=8,
                               seq_len=128, seed=seed)
    return wl.stream


def _drifted_blocks(stream, n_blocks: int, seed: int):
    """Cut the compressed stream into blocks with a drifting composition:
    block b is drawn from a rotated slice of the key set, so the heavy
    set of the late stream differs from the early stream."""
    rng = np.random.default_rng(seed)
    order = rng.permutation(stream.items.shape[0])
    items, freqs = stream.items[order], stream.freqs[order]
    edges = np.linspace(0, items.shape[0], n_blocks + 1).astype(int)
    return [(items[s:e], freqs[s:e]) for s, e in zip(edges[:-1], edges[1:])]


def _exact(counts_items, counts_freqs):
    uniq, inv = np.unique(np.concatenate(counts_items, axis=0), axis=0,
                          return_inverse=True)
    tot = np.zeros(len(uniq), dtype=np.int64)
    np.add.at(tot, inv, np.concatenate(counts_freqs))
    return uniq, tot


# --------------------------------------------------------------------------
# Acceptance: migrated endpoint == fresh endpoint on the new spec, bitwise
# --------------------------------------------------------------------------

def test_migrated_endpoint_bitwise_equals_fresh():
    stream = _workload("zipf", seed=3)
    spec_old = sk.mod_sketch_spec(stream.schema, [(0,), (1,)], (64, 16), 4)
    spec_new = sk.mod_sketch_spec(stream.schema, [(0,), (1,)], (16, 64), 4)
    key = jax.random.PRNGKey(0)
    mig_key = jax.random.fold_in(key, 7)
    items, freqs = stream.items, stream.freqs
    n = items.shape[0]
    cut1, cut2 = n // 3, 2 * n // 3
    warm = int(freqs[cut1:cut2].sum())

    ep = SketchTopKEndpoint(spec_old, key)
    ep.ingest(items[:cut1], freqs[:cut1])
    ep.begin_migration(spec_new, mig_key, warmup=warm)
    assert ep.migrating and ep.migration_progress == 0.0
    ep.ingest(items[cut1:cut2], freqs[cut1:cut2])   # hits warmup exactly
    assert not ep.migrating and ep.migration_progress == 1.0
    ep.ingest(items[cut2:], freqs[cut2:])

    fresh = SketchTopKEndpoint(spec_new, mig_key)
    fresh.ingest(items[cut1:cut2], freqs[cut1:cut2])
    fresh.ingest(items[cut2:], freqs[cut2:])

    assert ep.total == fresh.total
    assert ep.hspec == fresh.hspec
    for a, b in zip(ep.state.states, fresh.state.states):
        assert np.array_equal(np.asarray(a.table), np.asarray(b.table))
        assert np.array_equal(np.asarray(a.params.q), np.asarray(b.params.q))
    ia, fa = ep.topk(16)
    ib, fb = fresh.topk(16)
    assert np.array_equal(ia, ib)
    assert np.array_equal(fa, fb)


def test_migration_across_partition_change():
    """Cutover to a spec with a DIFFERENT partition (greedy may combine
    groups): hierarchy depth changes under the endpoint, queries survive."""
    stream = _workload("zipf", seed=5)
    spec_old = sk.mod_sketch_spec(stream.schema, [(0,), (1,)], (32, 32), 4)
    spec_new = sk.mod_sketch_spec(stream.schema, [(0, 1)], (1024,), 4)
    key = jax.random.PRNGKey(2)
    items, freqs = stream.items, stream.freqs
    half = items.shape[0] // 2

    ep = SketchTopKEndpoint(spec_old, key)
    ep.ingest(items[:half], freqs[:half])
    assert ep.hspec.n_levels == 2
    ep.begin_migration(spec_new, key, warmup=1)
    ep.ingest(items[half:], freqs[half:])
    assert not ep.migrating
    assert ep.hspec.n_levels == 1
    ti, tf = ep.topk(8)
    uniq, tot = _exact([items[half:]], [freqs[half:]])
    exact = {tuple(r): t for r, t in zip(uniq.tolist(), tot.tolist())}
    for row, est in zip(ti.tolist(), tf.tolist()):
        assert est >= exact[tuple(row)]     # linear tables overcount only


# --------------------------------------------------------------------------
# Property: no false negatives + top-k continuity across the whole window
# --------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(st.integers(min_value=0, max_value=5),
       st.integers(min_value=1, max_value=3),
       st.sampled_from(["zipf", "ngram"]))
def test_no_false_negatives_through_migration(seed, warm_blocks, kind):
    """At every block boundary -- pre-warmup, mid-warmup, post-cutover --
    heavy_hitters(T) reports every key exactly >= T within the serving
    window (whole stream before cutover, post-migration suffix after)."""
    stream = _workload(kind, seed)
    m = stream.schema.modularity
    groups = [(j,) for j in range(m)]
    spec_old = sk.mod_sketch_spec(stream.schema, groups, (32,) * m, 4)
    spec_new = sk.mod_sketch_spec(stream.schema, groups,
                                  (16,) + (64,) * (m - 1), 4)
    key = jax.random.PRNGKey(100 + seed)
    blocks = _drifted_blocks(stream, 6, seed)
    start_at = 2                      # begin migration after 2 blocks
    warm = int(sum(int(f.sum()) for _, f in
                   blocks[start_at:start_at + warm_blocks]))

    ep = SketchTopKEndpoint(spec_old, key)
    window = []                       # blocks the serving tables have seen
    for b, (bi, bf) in enumerate(blocks):
        if b == start_at:
            ep.begin_migration(spec_new, jax.random.fold_in(key, 1),
                               warmup=warm)
        was_migrating = ep.migrating
        ep.ingest(bi, bf)
        if was_migrating and not ep.migrating:
            window = []               # cutover: window restarts at the
            window_from = start_at    # first double-written block
            window = [blocks[i] for i in range(window_from, b + 1)]
        else:
            window.append((bi, bf))

        uniq, tot = _exact([w[0] for w in window], [w[1] for w in window])
        threshold = max(2, int(tot.max()) // 2)
        hh_items, hh_est = ep.heavy_hitters(threshold)
        got = {tuple(r) for r in hh_items.tolist()}
        exact_hh = {tuple(r) for r, t in zip(uniq.tolist(), tot.tolist())
                    if t >= threshold}
        assert exact_hh <= got, (
            f"false negatives at block {b} (migrating={ep.migrating}): "
            f"{sorted(exact_hh - got)[:4]}")

        # top-k continuity: answers exist and upper-bound window-exact
        ti, tf = ep.topk(8, min_threshold=1)
        assert len(ti) == min(8, len(uniq))
        exact_map = {tuple(r): t for r, t in zip(uniq.tolist(), tot.tolist())}
        for row, est in zip(ti.tolist(), tf.tolist()):
            assert est >= exact_map.get(tuple(row), 0)
    assert not ep.migrating           # warmup fits inside the stream


@settings(max_examples=6, deadline=None)
@given(st.integers(min_value=0, max_value=3))
def test_migration_progress_monotone(seed):
    stream = _workload("zipf", seed)
    spec = sk.mod_sketch_spec(stream.schema, [(0,), (1,)], (32, 32), 4)
    ep = SketchTopKEndpoint(spec, jax.random.PRNGKey(seed))
    blocks = _drifted_blocks(stream, 8, seed)
    ep.begin_migration(spec, jax.random.PRNGKey(seed + 1),
                       warmup=int(stream.freqs.sum()))
    last = 0.0
    for bi, bf in blocks:
        ep.ingest(bi, bf)
        assert ep.migration_progress >= last
        last = ep.migration_progress
    assert not ep.migrating and last == 1.0   # full stream == warmup mass


# --------------------------------------------------------------------------
# Sharded service: migration is shard-count invariant, bitwise
# --------------------------------------------------------------------------

def test_sharded_migration_shard_invariant():
    print(_run("""
        import jax, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.core import sketch as sk
        from repro.serving.sharded_topk import ShardedTopKService
        from repro.streams import zipf_hh_workload

        key = jax.random.PRNGKey(0)
        wl = zipf_hh_workload(n_occurrences=40_000, n_edges=6_000, seed=3)
        spec_old = sk.mod_sketch_spec(wl.stream.schema, [(0,), (1,)],
                                      (64, 16), 4)
        spec_new = sk.mod_sketch_spec(wl.stream.schema, [(0,), (1,)],
                                      (16, 64), 4)
        items, freqs = wl.stream.items, wl.stream.freqs
        n = items.shape[0]; cut1, cut2 = n // 3, 2 * n // 3
        mig_key = jax.random.fold_in(key, 7)
        warm = int(freqs[cut1:cut2].sum())

        counts = [c for c in (1, 2, 4) if c <= jax.device_count()]
        assert counts[-1] >= 2
        results = {}
        for c in counts:
            mesh = make_mesh((c,), ("data",))
            svc = ShardedTopKService(spec_old, key, mesh)
            svc.ingest(items[:cut1], freqs[:cut1])
            svc.begin_migration(spec_new, mig_key, warmup=warm)
            assert svc.migrating
            svc.ingest(items[cut1:cut2], freqs[cut1:cut2])
            assert not svc.migrating
            svc.ingest(items[cut2:], freqs[cut2:])
            ti, tf = svc.topk(16)
            results[c] = (ti, tf, svc.total,
                          [np.asarray(s.table) for s in svc.state().states])
        for c in counts[1:]:
            assert np.array_equal(results[counts[0]][0], results[c][0])
            assert np.array_equal(results[counts[0]][1], results[c][1])
            assert results[counts[0]][2] == results[c][2]
            for ta, tb in zip(results[counts[0]][3], results[c][3]):
                assert np.array_equal(ta, tb)

        # migrated == fresh service on the new spec, post-warmup stream
        mesh = make_mesh((counts[-1],), ("data",))
        fresh = ShardedTopKService(spec_new, mig_key, mesh)
        fresh.ingest(items[cut1:cut2], freqs[cut1:cut2])
        fresh.ingest(items[cut2:], freqs[cut2:])
        fi, ff = fresh.topk(16)
        assert np.array_equal(results[counts[-1]][0], fi)
        assert np.array_equal(results[counts[-1]][1], ff)
        print("sharded migration invariant over", counts, "shards; "
              "migrated == fresh")
    """))


# --------------------------------------------------------------------------
# Refusal paths
# --------------------------------------------------------------------------

def _small_specs():
    stream = _workload("zipf", 1)
    old = sk.mod_sketch_spec(stream.schema, [(0,), (1,)], (32, 32), 4)
    new = sk.mod_sketch_spec(stream.schema, [(0,), (1,)], (16, 64), 4)
    return stream, old, new


def test_conservative_endpoint_refuses_migration():
    stream, old, new = _small_specs()
    ep = SketchTopKEndpoint(old, jax.random.PRNGKey(0), mode="conservative")
    ep.ingest(stream.items, stream.freqs)
    with pytest.raises(ValueError, match="linear"):
        ep.begin_migration(new, jax.random.PRNGKey(1), warmup=1)


def test_mid_warmup_merge_and_shard_refused():
    stream, old, new = _small_specs()
    key = jax.random.PRNGKey(0)
    ep = SketchTopKEndpoint(old, key)
    ep.ingest(stream.items, stream.freqs)
    ep.begin_migration(new, jax.random.PRNGKey(1), warmup=1 << 40)
    other = SketchTopKEndpoint(old, key)
    with pytest.raises(ValueError, match="migration"):
        ep.merge_from(other)
    with pytest.raises(ValueError, match="migration"):
        other.merge_from(ep)          # source side mid-warmup: also refused
    mesh = make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="migration"):
        ep.to_sharded(mesh)
    with pytest.raises(ValueError, match="already in flight"):
        ep.begin_migration(new, jax.random.PRNGKey(2), warmup=1)


def test_spec_migration_holder_invariants():
    stream, old, _ = _small_specs()
    ep = SketchTopKEndpoint(old, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="warmup"):
        SpecMigration(ep, warmup=0)
    ep.ingest(stream.items, stream.freqs)
    with pytest.raises(ValueError, match="start empty"):
        SpecMigration(ep, warmup=10)  # non-empty successor refused
    require_not_migrating(None, "anything")   # no-op without a migration
    with pytest.raises(ValueError, match="warmup window"):
        require_not_migrating(
            SpecMigration(SketchTopKEndpoint(old, jax.random.PRNGKey(1)),
                          warmup=10), "entry")
