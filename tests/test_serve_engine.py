"""The async sketch-serving engine: pipeline, staleness, batched descent.

Contracts under test (serving/sketch_engine.py + the split serving stack):

  * **pipeline bit-identity** -- engine ingest through the staged
    stage_indices/fold_indices pipeline leaves tables, totals, and pools
    bit-identical to direct synchronous endpoint ingest; the two-phase
    split itself equals update_jit at the hierarchy level;
  * **staleness-0 parity** -- engine queries with ``max_staleness=0`` are
    bit-identical to the synchronous surfaces (endpoint, sharded service,
    windowed service) fed the same stream;
  * **staleness semantics** -- unbounded staleness freezes the snapshot
    until an explicit sync; a finite bound triggers refresh exactly when
    exceeded; ``advance()`` invalidates the snapshot outright;
  * **batched descent bit-identity** -- batched_find_heavy_hitters equals
    per-request find_heavy_hitters (ref and kernel paths), and the
    engine's submit/flush answers equal the serial topk/heavy_hitters
    calls -- same items, same estimates, same tie order;
  * **one engine protocol** -- both the model stack's SlotScheduler and
    the sketch engine satisfy serving/protocol.ServeEngineProtocol, and
    the pre-split ``repro.serving.engine`` import surface still works;
  * **integration points** -- the AutoTuner ticks on sync and its
    migration runs through the engine without wedging the pipeline.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import hierarchy as hh
from repro.core import sketch as sk
from repro.launch.mesh import make_mesh
from repro.serving.protocol import ServeEngineProtocol
from repro.serving.sketch_engine import (
    SketchQuery,
    SketchServeEngine,
    SketchTopKEndpoint,
)
from repro.streams import zipf_hh_workload


def _stream(seed=1):
    return zipf_hh_workload(n_src=100, n_tgt=200, n_edges=800,
                            n_occurrences=4_000, seed=seed).stream


def _blocks(stream, size=100):
    it, fr = stream.items, stream.freqs
    return [(it[s:s + size], fr[s:s + size])
            for s in range(0, it.shape[0], size)]


def _spec(stream, ranges=(32, 32), w=4):
    return sk.mod_sketch_spec(stream.schema, [(0,), (1,)], ranges, w)


KEY = jax.random.PRNGKey(0)


# -- two-phase ingest == fused update (hierarchy level) ---------------------

def test_stage_fold_equals_update_jit():
    stream = _stream()
    spec = _spec(stream)
    hspec = hh.HierarchySpec.from_spec(spec)
    a = hh.init_hierarchy(hspec, KEY)
    b = hh.init_hierarchy(hspec, KEY)
    for items, freqs in _blocks(stream, 128)[:6]:
        items = jnp.asarray(np.asarray(items, np.uint32))
        freqs = jnp.asarray(np.asarray(freqs))
        a = hh.update_jit(hspec, a, items, freqs)
        b = hh.fold_indices(b, hh.stage_indices(hspec, b, items), freqs)
    for sa, sb in zip(a.states, b.states):
        assert np.array_equal(np.asarray(sa.table), np.asarray(sb.table))


def test_stage_block_refused_off_the_plain_linear_path():
    stream = _stream()
    spec = _spec(stream)
    cons = SketchTopKEndpoint(spec, KEY, mode="conservative")
    with pytest.raises(ValueError, match="plain linear"):
        cons.stage_block(stream.items[:8], stream.freqs[:8])
    krn = SketchTopKEndpoint(spec, KEY, use_update_kernel=True)
    with pytest.raises(ValueError, match="plain linear"):
        krn.stage_block(stream.items[:8], stream.freqs[:8])


# -- pipelined engine ingest == synchronous endpoint ingest -----------------

def test_engine_pipeline_bitwise_equals_direct_ingest():
    stream = _stream()
    spec = _spec(stream)
    ref = SketchTopKEndpoint(spec, KEY)
    ep = SketchTopKEndpoint(spec, KEY)
    eng = SketchServeEngine(ep, max_staleness=None)
    for items, freqs in _blocks(stream):
        ref.ingest(items, freqs)
        eng.ingest(items, freqs)
    eng.drain()
    assert ep.total == ref.total
    for sa, sb in zip(ref.state.states, ep.state.states):
        assert np.array_equal(np.asarray(sa.table), np.asarray(sb.table))
    for pa, pb in zip(ref.candidates(), ep.candidates()):
        assert np.array_equal(np.sort(pa, axis=0), np.sort(pb, axis=0))


def test_engine_staleness0_parity_with_endpoint():
    stream = _stream()
    spec = _spec(stream)
    ref = SketchTopKEndpoint(spec, KEY)
    eng = SketchServeEngine(SketchTopKEndpoint(spec, KEY), max_staleness=0)
    for items, freqs in _blocks(stream):
        ref.ingest(items, freqs)
        eng.ingest(items, freqs)
        # query mid-stream too: parity must hold at every point
    ri, re = ref.topk(10)
    ei, ee = eng.topk(10)
    assert np.array_equal(ri, ei) and np.array_equal(re, ee)
    rh = ref.heavy_hitters(50)
    eh = eng.heavy_hitters(50)
    assert np.array_equal(rh[0], eh[0]) and np.array_equal(rh[1], eh[1])


def test_engine_staleness0_parity_with_sharded_service():
    from repro.serving.sharded_topk import ShardedTopKService

    stream = _stream()
    spec = _spec(stream)
    mesh = make_mesh((1,), ("data",))
    ref = ShardedTopKService(spec, KEY, mesh, sync_every=1)
    svc = ShardedTopKService(spec, KEY, mesh, sync_every=None)
    eng = SketchServeEngine(svc, max_staleness=0, shard_sync_every=3)
    for items, freqs in _blocks(stream):
        ref.ingest(items, freqs)
        eng.ingest(items, freqs)
    ri, re = ref.topk(8)
    ei, ee = eng.topk(8)
    assert np.array_equal(ri, ei) and np.array_equal(re, ee)


def test_engine_staleness0_parity_with_windowed_service():
    from repro.serving.windowed_topk import WindowedTopKService

    stream = _stream()
    spec = _spec(stream)
    ref = WindowedTopKService(spec, KEY, n_epochs=3)
    svc = WindowedTopKService(spec, KEY, n_epochs=3)
    eng = SketchServeEngine(svc, max_staleness=0)
    for i, (items, freqs) in enumerate(_blocks(stream)):
        ref.ingest(items, freqs)
        eng.ingest(items, freqs)
        if i % 3 == 2:
            ref.advance()
            eng.advance()
    ri, re = ref.topk(8)
    ei, ee = eng.topk(8)
    assert np.array_equal(ri, ei) and np.array_equal(re, ee)


# -- staleness semantics ----------------------------------------------------

def test_unbounded_staleness_serves_frozen_snapshot_until_sync():
    stream = _stream()
    spec = _spec(stream)
    blocks = _blocks(stream)
    eng = SketchServeEngine(SketchTopKEndpoint(spec, KEY),
                            max_staleness=None)
    for items, freqs in blocks[:5]:
        eng.ingest(items, freqs)
    eng.sync()
    at_sync = eng.topk(8)
    for items, freqs in blocks[5:10]:
        eng.ingest(items, freqs)
    # snapshot is frozen: post-sync ingest is invisible to queries
    assert eng.staleness == sum(int(np.asarray(f).sum())
                                for _, f in blocks[5:10])
    stale = eng.topk(8)
    assert np.array_equal(at_sync[0], stale[0])
    assert np.array_equal(at_sync[1], stale[1])
    eng.sync()
    assert eng.staleness == 0


def test_bounded_staleness_refreshes_exactly_when_exceeded():
    stream = _stream()
    spec = _spec(stream)
    blocks = _blocks(stream)
    mass0 = int(np.asarray(blocks[0][1]).sum())
    # bound big enough to tolerate block 0, exceeded by block 0+1
    eng = SketchServeEngine(SketchTopKEndpoint(spec, KEY),
                            max_staleness=mass0)
    eng.ingest(*blocks[0])
    snap_before = eng._fresh_snapshot()
    assert snap_before.mass == 0          # within bound: no refresh
    eng.ingest(*blocks[1])
    snap_after = eng._fresh_snapshot()    # bound exceeded: refreshed
    assert snap_after.mass == eng._mass
    assert eng.staleness == 0


def test_advance_invalidates_snapshot_without_mass():
    from repro.serving.windowed_topk import WindowedTopKService

    stream = _stream()
    spec = _spec(stream)
    blocks = _blocks(stream)
    svc = WindowedTopKService(spec, KEY, n_epochs=2)
    eng = SketchServeEngine(svc, max_staleness=None)
    for items, freqs in blocks[:6]:
        eng.ingest(items, freqs)
    eng.sync()
    before = eng.topk(6)
    eng.advance()                          # no stream mass moves, yet ...
    eng.advance()                          # ... the whole window expired
    after = eng.topk(6)                    # snapshot must have refreshed
    ref = WindowedTopKService(spec, KEY, n_epochs=2)
    for items, freqs in blocks[:6]:
        ref.ingest(items, freqs)
    ref.advance()
    ref.advance()
    ri, re = ref.topk(6)
    assert np.array_equal(after[0], ri) and np.array_equal(after[1], re)
    # and the pre-advance answer reflected the live window
    assert not (len(before[1]) == len(after[1])
                and np.array_equal(before[1], after[1]))


# -- batched descent bit-identity -------------------------------------------

def _built_endpoint(use_kernel=False):
    stream = _stream(seed=5)
    spec = _spec(stream, ranges=(16, 64))
    ep = SketchTopKEndpoint(spec, KEY, use_kernel=use_kernel)
    for items, freqs in _blocks(stream, 256):
        ep.ingest(items, freqs)
    return ep


@pytest.mark.parametrize("use_kernel", [False, True])
def test_batched_find_heavy_hitters_bitwise_equals_serial(use_kernel):
    ep = _built_endpoint(use_kernel)
    cands = ep.candidates()
    thresholds = [1, 10, 50, 200, ep.total + 1]
    batched = hh.batched_find_heavy_hitters(
        ep.hspec, ep.state, thresholds, cands, use_kernel=use_kernel)
    for thr, (bi, be) in zip(thresholds, batched):
        si, se = hh.find_heavy_hitters(ep.hspec, ep.state, thr, cands,
                                       use_kernel=use_kernel)
        assert np.array_equal(bi, si), f"items diverge at threshold {thr}"
        assert np.array_equal(be, se), f"estimates diverge at threshold {thr}"


def test_batched_request_chunking_is_bit_neutral():
    ep = _built_endpoint()
    cands = ep.candidates()
    thresholds = [1, 5, 25, 125, 625]
    full = hh.batched_find_heavy_hitters(
        ep.hspec, ep.state, thresholds, cands)
    # max_batch small enough to force request-axis chunking + padding
    chunked = hh.batched_find_heavy_hitters(
        ep.hspec, ep.state, thresholds, cands, max_batch=64)
    for (fi, fe), (ci, ce) in zip(full, chunked):
        assert np.array_equal(fi, ci) and np.array_equal(fe, ce)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_engine_flush_equals_serial_queries(use_kernel):
    ep = _built_endpoint(use_kernel)
    eng = SketchServeEngine(ep, max_staleness=0)
    r_top5 = eng.submit_topk(5)
    r_top20 = eng.submit_topk(20)
    r_hh = eng.submit_heavy_hitters(40)
    r_floor = eng.submit_topk(4, min_threshold=1)
    done = eng.flush()
    assert done == [r_top5, r_top20, r_hh, r_floor]
    assert all(r.done for r in done)
    for r, serial in [
        (r_top5, ep.topk(5)),
        (r_top20, ep.topk(20)),
        (r_hh, ep.heavy_hitters(40)),
        (r_floor, ep.topk(4, min_threshold=1)),
    ]:
        assert np.array_equal(r.items, serial[0])
        assert np.array_equal(r.est, serial[1])


def test_engine_flush_floor_above_total_returns_empty():
    ep = _built_endpoint()
    eng = SketchServeEngine(ep, max_staleness=0)
    r = eng.submit_topk(3, min_threshold=ep.total * 2)
    eng.flush()
    si, se = ep.topk(3, min_threshold=ep.total * 2)
    assert np.array_equal(r.items, si) and np.array_equal(r.est, se)
    assert r.est.shape == (0,)


def test_submit_rejects_unknown_kind():
    eng = SketchServeEngine(_built_endpoint(), max_staleness=0)
    with pytest.raises(ValueError, match="kind"):
        eng.submit(SketchQuery(rid=-1, kind="range"))
    assert eng.flush() == []


# -- the split serving stack ------------------------------------------------

def test_engine_protocol_spans_both_stacks():
    from repro.serving.model_engine import SlotScheduler

    eng = SketchServeEngine(_built_endpoint(), max_staleness=0)
    assert isinstance(eng, ServeEngineProtocol)
    assert isinstance(SlotScheduler.__new__(SlotScheduler),
                      ServeEngineProtocol)


def test_presplit_import_surface_still_works():
    from repro.serving import engine as legacy

    for name in ("Request", "ServeConfig", "ServeEngine", "SlotScheduler",
                 "SketchTopKEndpoint"):
        assert hasattr(legacy, name), f"shim lost {name}"
    from repro.serving.model_engine import ServeEngine
    assert legacy.ServeEngine is ServeEngine
    assert legacy.SketchTopKEndpoint is SketchTopKEndpoint


# -- integration points: tuner + migration through the engine ---------------

def test_tuner_ticks_on_sync_and_migration_runs_through_engine():
    from repro.serving.autotune import AutoTuner

    stream = _stream(seed=7)
    # deliberately lopsided ranges so a ranges re-search has room to win
    spec = _spec(stream, ranges=(2, 512))
    ep = SketchTopKEndpoint(spec, KEY)
    tuner = AutoTuner(ep, jax.random.fold_in(KEY, 1), retune_every=1_000,
                      warmup=500, min_threshold=1, search="ranges")
    eng = SketchServeEngine(ep, max_staleness=0, tuner=tuner)
    for items, freqs in _blocks(stream):
        eng.ingest(items, freqs)
        eng.sync()
    assert tuner.decisions, "tuner never ticked through engine.sync()"
    # pipeline + queries keep working across whatever the tuner decided
    items, est = eng.topk(5)
    assert est.shape[0] <= 5
    if any(d.migrated for d in tuner.decisions):
        assert not ep.migrating or ep.migration_progress < 1.0
