"""repro.obs: the counter table, spans on the profiler's clock, and the
counters the serving path keeps (blocks, rows, descent rounds, evictions)."""
import glob

import numpy as np
import pytest

import jax

from repro import obs
from repro.core import sketch as sk
from repro.core.summary import SpaceSaving
from repro.serving.sharded_topk import threshold_descent_topk
from repro.serving.sketch_engine import SketchServeEngine, SketchTopKEndpoint
from repro.streams import zipf_hh_workload

KEY = jax.random.PRNGKey(0)


def _delta(before):
    return {k: v - before.get(k, 0) for k, v in obs.counters().items()
            if v != before.get(k, 0)}


def _stream(seed=3):
    return zipf_hh_workload(n_src=100, n_tgt=200, n_edges=800,
                            n_occurrences=4_000, seed=seed).stream


def _blocks(stream, size):
    it, fr = stream.items, stream.freqs
    return [(it[s:s + size], fr[s:s + size])
            for s in range(0, it.shape[0], size)]


def test_counters_add_and_copy():
    before = obs.counters()
    obs.count("test.obs.a")
    obs.count("test.obs.a", 4)
    obs.count("test.obs.b", np.int64(2))
    got = obs.counters()
    assert _delta(before) == {"test.obs.a": 5, "test.obs.b": 2}
    assert type(got["test.obs.b"]) is int
    got["test.obs.a"] += 100                 # a copy: the table is untouched
    assert obs.counters()["test.obs.a"] == got["test.obs.a"] - 100


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("test.obs."):
                        events[e.name] = (e.start_ns, e.end_ns,
                                          dict(e.stats))
    return events


def test_spans_nest_on_the_profiler_clock_and_are_inert_outside(tmp_path):
    with obs.span("test.obs.before", rows=1):      # no session: inert
        pass
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("test.obs.outer", rows=4096):
            with obs.span("test.obs.inner", level=2):
                jax.numpy.ones(8).block_until_ready()
    ev = _host_events(tmp_path)
    assert set(ev) == {"test.obs.outer", "test.obs.inner"}
    (os_, oe, oargs), (is_, ie, iargs) = ev["test.obs.outer"], \
        ev["test.obs.inner"]
    assert os_ <= is_ < ie <= oe
    assert oargs == {"rows": 4096} and iargs == {"level": 2}


def _endpoint(stream, capacity=1 << 16):
    spec = sk.mod_sketch_spec(stream.schema, [(0,), (1,)], (16, 64), 4)
    return SketchTopKEndpoint(spec, KEY, max_candidates_per_group=capacity)


def test_engine_counts_the_blocks_and_rows_fed():
    stream = _stream()
    eng = SketchServeEngine(_endpoint(stream), max_staleness=0)
    blocks = _blocks(stream, 300)
    before = obs.counters()
    for items, freqs in blocks:
        eng.ingest(items, freqs)
    eng.ingest(stream.items[:0], stream.freqs[:0])     # empty: not a block
    eng.submit_heavy_hitters(50)
    eng.flush()                              # refreshes the snapshot
    got = _delta(before)
    assert got["sketch.snapshots"] == 1
    assert got["sketch.blocks"] == len(blocks)
    assert got["sketch.rows"] == stream.items.shape[0]
    assert got["sketch.fold_launches"] == len(blocks)
    pad = sum((1 << (len(f) - 1).bit_length()) - len(f) for _, f in blocks)
    assert got.get("sketch.fold_pad_rows", 0) == pad


def test_descent_rounds_are_the_thresholds_the_serial_descent_tries():
    stream = _stream()
    ep = _endpoint(stream)
    for items, freqs in _blocks(stream, 256):
        ep.ingest(items, freqs)
    eng = SketchServeEngine(ep, max_staleness=0)
    ks, hh_thresholds = [1, 5, 20, 200], [40, ep.total + 1]
    tried = 0

    def counting(thr, candidates):
        nonlocal tried
        tried += 1
        return ep.heavy_hitters(thr, candidates)

    for k in ks:
        threshold_descent_topk(counting, ep.candidates(), k, total=ep.total,
                               n_modules=ep.hspec.base.schema.modularity)
    before = obs.counters()
    for k in ks:
        eng.submit_topk(k)
    for t in hh_thresholds:
        eng.submit_heavy_hitters(t)
    eng.flush()
    got = _delta(before)
    assert got["sketch.descent_rounds"] == tried + len(hh_thresholds)
    assert got["sketch.requests"] == len(ks) + len(hh_thresholds)
    assert got["sketch.flushes"] == 1
    assert got["sketch.grid_lanes"] >= got["sketch.grid_lanes_useful"] > 0
    assert got["sketch.grid_launches"] >= 1


def _block_rule(count, err, capacity, values, freqs):
    """Plain dict reference of the pool's block rule: the block's exact
    summary unioned in, then the lowest by (count, key) dropped.  Returns
    (rows already resident, rows dropped)."""
    block = {}
    for row, f in zip(map(tuple, values.tolist()), freqs.tolist()):
        block[row] = block.get(row, 0.0) + f
    floor = min(count.values()) if len(count) >= capacity else 0.0
    hits = 0
    for row, f in block.items():
        if f <= 0:
            continue
        hits += row in count
        if row in count:
            count[row] += f
        else:
            count[row], err[row] = floor + f, floor
    extra = max(0, len(count) - capacity)
    for row in sorted(count, key=lambda r: (count[r], r))[:extra]:
        del count[row], err[row]
    return hits, extra


def test_pool_evictions_match_a_pool_driven_row_by_row():
    stream = _stream(seed=7)
    capacity = 24
    pool = SpaceSaving(capacity, 1)
    count, err = {}, {}
    want = got = hits = want_hits = offered = 0
    for items, freqs in _blocks(stream, 150):
        values = items[:, :1]
        before = obs.counters()
        pool.offer(values, freqs)
        d = _delta(before)
        got += d.get("sketch.pool_evicted", 0)
        hits += d.get("sketch.pool_hits", 0)
        offered += d["sketch.pool_offered"]
        h, e = _block_rule(count, err, capacity, values, freqs)
        want_hits += h
        want += e
    assert want > 0 and want_hits > 0
    assert got == want
    assert hits == want_hits
    assert pool.counts() == count
    sd = pool.state_dict()
    assert dict(zip(map(tuple, sd["rows"].tolist()), sd["errs"].tolist())) == err
    assert offered >= len(pool)


def test_pool_hits_count_the_blocks_resident_rows():
    pool = SpaceSaving(8, 1)
    pool.offer(np.asarray([[1], [2], [3]], np.uint32))
    before = obs.counters()
    pool.offer(np.asarray([[1], [1], [4], [2], [5]], np.uint32),
               np.asarray([1, 2, 1, 1, 0]))
    got = _delta(before)
    assert got["sketch.pool_offered"] == 4      # distinct rows, pad included
    assert got["sketch.pool_hits"] == 2         # 1 and 2 were resident
    assert "sketch.pool_evicted" not in got     # under capacity
    assert pool.counts() == {(1,): 4, (2,): 2, (3,): 1, (4,): 1}


@pytest.mark.parametrize("capacity", [8, 1 << 10])
def test_pool_evictions_are_new_rows_less_the_pool_growth(capacity):
    stream = _stream(seed=11)
    pool = SpaceSaving(capacity, 2)
    for items, freqs in _blocks(stream, 200):
        seen = set(pool.counts())
        n0 = len(pool)
        before = obs.counters()
        pool.offer(items, freqs)
        new = {tuple(r) for r in items.tolist()} - seen
        evicted = _delta(before).get("sketch.pool_evicted", 0)
        # rows new to the pool either filled free room or evicted another
        assert evicted == len(new) - (len(pool) - n0)
        if len(pool) < capacity:
            assert evicted == 0


def test_sharded_fold_merge_and_pool_fold_counts():
    """On the sharded service with the fused fold: one fold launch a block
    with its pad rows, one merge per ``shard_sync_every`` blocks carrying
    the whole stack, and one pool fold per flush that follows ingest."""
    from repro.core import hierarchy as hh
    from repro.kernels.hier_update import make_hier_plan
    from repro.launch.mesh import make_mesh
    from repro.serving.sharded_topk import ShardedTopKService

    stream = _stream()
    spec = sk.mod_sketch_spec(stream.schema, [(0,), (1,)], (16, 64), 4)
    svc = ShardedTopKService(spec, KEY, make_mesh((1,), ("data",)),
                             use_update_kernel=True, sync_every=None)
    eng = SketchServeEngine(svc, max_staleness=0, shard_sync_every=2)
    blocks = _blocks(stream, -(-stream.items.shape[0] // 3))
    assert len(blocks) == 3
    before = obs.counters()
    for items, freqs in blocks:
        eng.ingest(items, freqs)
    got = _delta(before)
    assert got["sketch.fold_launches"] == len(blocks)
    assert got["sketch.fold_pad_rows"] == sum(1024 - len(f) for _, f in blocks)
    merges = len(blocks) // 2
    stack = 4 * make_hier_plan(hh.HierarchySpec.from_spec(spec)).padded_cols
    assert got["sketch.merges"] == merges
    assert got["sketch.merge_bytes"] == merges * stack * 4
    assert "sketch.pool_folds" not in got

    before = obs.counters()
    eng.submit_topk(3)
    eng.submit_heavy_hitters(50)
    eng.flush()
    got = _delta(before)
    assert got["sketch.merges"] == 1          # the odd block left unmerged
    assert got["sketch.pool_folds"] == 1
    assert got["sketch.snapshots"] == 1
    before = obs.counters()
    eng.submit_topk(3)
    eng.flush()                               # nothing new: no merge, no fold
    got = _delta(before)
    assert "sketch.merges" not in got and "sketch.pool_folds" not in got
