"""Durability & recovery: snapshots, WAL replay, fault injection, remesh.

Contracts under test (serving/recovery.py + serving/faults.py + the
state_dict surfaces grown across serving/* and kernels/ops.py):

  * **state_dict round trips** -- endpoint (linear/conservative/kernel),
    windowed, sharded, and KernelSketch (linear/conservative/signed)
    restore bit-identically: tables, pools, totals, clocks, and top-k
    output all match the snapshotted object, and keep matching after
    further shared ingest;
  * **checkpoint integrity** -- per-array CRC32 catches byte flips
    (CheckpointCorruptionError), AsyncCheckpointer surfaces worker
    exceptions instead of dropping failed writes, transient save failures
    are retried;
  * **WAL semantics** -- ordered replay, torn-tail truncation at reopen,
    duplicate records applied exactly once, gaps refused loudly,
    rotation + pruning bounded by the oldest retained snapshot;
  * **kill-and-recover bit-exactness** (the acceptance matrix) -- for
    endpoint/windowed surfaces and linear/conservative modes, an injected
    crash mid-stream followed by recover() + resumed ingest yields
    tables, pools, totals, and topk output bit-identical to an
    uninterrupted run, including the corrupted-snapshot fallback case;
    the sharded legs (kill/recover + N->M remesh) run on forced
    multi-device CPU meshes in subprocesses;
  * **crash-consistent migration** -- abort_migration() rolls back with
    no double-write residue; a checkpoint mid-warmup refuses with an
    error that names the way out.
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import sketch as sk
from repro.serving.faults import (
    FaultPlan,
    ServingSupervisor,
    corrupt_checkpoint_array,
    drop_wal_record,
    duplicate_wal_record,
)
from repro.serving.recovery import (
    BlockLog,
    DurableSketchEngine,
    WALGapError,
    recover,
)
from repro.serving.sketch_engine import SketchServeEngine, SketchTopKEndpoint
from repro.serving.windowed_topk import WindowedTopKService
from repro.streams import zipf_hh_workload
from repro.training import checkpoint as ckpt

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")
_DEVICES = int(os.environ.get("REPRO_TEST_DEVICES", "8"))

KEY = jax.random.PRNGKey(0)


def _run(code: str, devices: int = _DEVICES) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    return out.stdout


def _stream(seed=1):
    return zipf_hh_workload(n_src=100, n_tgt=200, n_edges=800,
                            n_occurrences=4_000, seed=seed).stream


def _spec(stream, ranges=(32, 32), w=4):
    return sk.mod_sketch_spec(stream.schema, [(0,), (1,)], ranges, w)


def _blocks(stream, size=50):
    it, fr = stream.items, stream.freqs
    return [(it[s:s + size], fr[s:s + size])
            for s in range(0, it.shape[0], size)]


def _assert_same_endpoint(a, b):
    assert a.total == b.total
    for sa, sb in zip(a.state.states, b.state.states):
        assert np.array_equal(np.asarray(sa.table), np.asarray(sb.table))
    for pa, pb in zip(a.candidates(), b.candidates()):
        assert np.array_equal(pa, pb)      # order included: descent order


# --------------------------------------------------------------------------
# state_dict round trips
# --------------------------------------------------------------------------

@pytest.mark.parametrize("kwargs", [
    {}, {"mode": "conservative"}, {"use_update_kernel": True}],
    ids=["linear", "conservative", "kernel"])
def test_endpoint_state_roundtrip_bitwise(kwargs):
    stream = _stream()
    spec = _spec(stream)
    a = SketchTopKEndpoint(spec, KEY, **kwargs)
    blocks = _blocks(stream)
    for it, fr in blocks[:5]:
        a.ingest(it, fr)
    b = SketchTopKEndpoint(spec, KEY, **kwargs)
    b.load_state_dict(a.state_dict())
    _assert_same_endpoint(a, b)
    # the restored endpoint keeps tracking bitwise under further ingest
    for it, fr in blocks[5:]:
        a.ingest(it, fr)
        b.ingest(it, fr)
    _assert_same_endpoint(a, b)
    ia, ea = a.topk(8)
    ib, eb = b.topk(8)
    assert np.array_equal(ia, ib) and np.array_equal(ea, eb)


def test_endpoint_state_fingerprint_mismatch_refused():
    stream = _stream()
    a = SketchTopKEndpoint(_spec(stream), KEY)
    other = SketchTopKEndpoint(_spec(stream, ranges=(16, 64)), KEY)
    with pytest.raises(ValueError, match="fingerprint mismatch"):
        other.load_state_dict(a.state_dict())


@pytest.mark.parametrize("mode", ["tumbling", "landmark", "decay"])
def test_windowed_state_roundtrip_mid_window(mode):
    stream = _stream()
    spec = _spec(stream)
    kw = dict(n_epochs=3, window_mode=mode)
    if mode == "decay":
        kw["decay"] = 0.5
    a = WindowedTopKService(spec, KEY, **kw)
    blocks = _blocks(stream)
    for n, (it, fr) in enumerate(blocks[:6]):
        a.ingest(it, fr)
        if n % 2 == 1:
            a.advance()
    b = WindowedTopKService(spec, KEY, **kw)
    b.load_state_dict(a.state_dict())
    assert b.epoch == a.epoch and b.total == a.total
    # keep streaming both through an expiry boundary
    for it, fr in blocks[6:]:
        a.ingest(it, fr)
        b.ingest(it, fr)
    a.advance()
    b.advance()
    ia, ea = a.topk(8)
    ib, eb = b.topk(8)
    assert np.array_equal(ia, ib) and np.array_equal(ea, eb)


@pytest.mark.parametrize("mode", ["linear", "conservative", "signed"])
def test_kernel_sketch_state_roundtrip_all_modes(mode):
    from repro.kernels.ops import KernelSketch

    stream = _stream()
    spec = _spec(stream)
    blocks = _blocks(stream)
    a = KernelSketch(spec, KEY, mode=mode, block_b=64)
    for it, fr in blocks[:4]:
        a.update(it, fr)
    b = KernelSketch(spec, KEY, mode=mode, block_b=64)
    b.load_state_dict(a.state_dict())
    assert np.array_equal(np.asarray(a.table), np.asarray(b.table))
    for it, fr in blocks[4:]:         # conservative: order-dependent, same order
        a.update(it, fr)
        b.update(it, fr)
    assert np.array_equal(np.asarray(a.table), np.asarray(b.table))
    q = stream.items[:64]
    assert np.array_equal(a.query(q), b.query(q))


def test_kernel_sketch_state_mode_mismatch_refused():
    from repro.kernels.ops import KernelSketch

    stream = _stream()
    spec = _spec(stream)
    a = KernelSketch(spec, KEY, mode="signed")
    b = KernelSketch(spec, KEY, mode="linear")
    with pytest.raises(ValueError, match="fingerprint mismatch"):
        b.load_state_dict(a.state_dict())


# --------------------------------------------------------------------------
# checkpoint layer: CRC, async error surfacing, retry
# --------------------------------------------------------------------------

def test_checkpoint_crc_catches_byte_flip(tmp_path):
    d = str(tmp_path / "ck")
    ckpt.save(d, 1, {"t": {"x": np.arange(32, dtype=np.int64)}})
    # restore_trees verifies and passes on intact data
    step, trees = ckpt.restore_trees(d)
    assert step == 1 and np.array_equal(trees["t"]["x"], np.arange(32))
    # flip a byte inside the archive, manifest untouched
    path = os.path.join(d, "step_00000001", "proc00_shard000.npz")
    with np.load(path) as z:
        arrays = {k: z[k] for k in z.files}
    arrays["t::x"] = arrays["t::x"] + 1
    np.savez(path, **arrays)
    with pytest.raises(ckpt.CheckpointCorruptionError, match="CRC mismatch"):
        ckpt.restore_trees(d)
    # verify=False loads anyway (forensics escape hatch)
    _, trees = ckpt.restore_trees(d, verify=False)
    assert trees["t"]["x"][0] == 1


def test_async_checkpointer_surfaces_worker_error(tmp_path, monkeypatch):
    calls = []

    def boom(*a, **k):
        calls.append(1)
        raise OSError("disk on fire")

    monkeypatch.setattr(ckpt, "save", boom)
    w = ckpt.AsyncCheckpointer(str(tmp_path / "ck"), retries=0)
    w.submit(1, {"t": {"x": np.zeros(4)}})
    with pytest.raises(OSError, match="disk on fire"):
        w.wait()
    # ...and submit() itself surfaces a failed PRIOR write, not drops it
    w.submit(2, {"t": {"x": np.zeros(4)}})
    with pytest.raises(OSError, match="disk on fire"):
        w.submit(3, {"t": {"x": np.zeros(4)}})


def test_async_checkpointer_retries_transient_failure(tmp_path, monkeypatch):
    real_save = ckpt.save
    attempts = []

    def flaky(*a, **k):
        attempts.append(1)
        if len(attempts) == 1:
            raise OSError("transient")
        return real_save(*a, **k)

    monkeypatch.setattr(ckpt, "save", flaky)
    w = ckpt.AsyncCheckpointer(str(tmp_path / "ck"), retries=2,
                               backoff=0.001)
    w.submit(1, {"t": {"x": np.arange(4)}})
    w.wait()                               # retried, no raise
    assert len(attempts) == 2
    step, trees = ckpt.restore_trees(str(tmp_path / "ck"))
    assert step == 1 and np.array_equal(trees["t"]["x"], np.arange(4))


# --------------------------------------------------------------------------
# WAL semantics
# --------------------------------------------------------------------------

def test_wal_roundtrip_and_reopen(tmp_path):
    d = str(tmp_path)
    log = BlockLog(d)
    items = np.arange(12, dtype=np.uint32).reshape(6, 2)
    freqs = np.array([1, 2, 3, 4, 5, 6], dtype=np.int64)
    log.append_block(items, freqs)
    log.append_advance()
    log.append_block(items[:2], freqs[:2].astype(np.float32))
    log.close()
    log2 = BlockLog(d)
    recs = log2.records(0)
    assert [r.kind for r in recs] == ["block", "advance", "block"]
    assert np.array_equal(recs[0].items, items)
    assert np.array_equal(recs[0].freqs, freqs)
    assert recs[2].freqs.dtype == np.float32   # dtype preserved bitwise
    assert log2.next_seq == 3                  # numbering continues


def test_wal_truncates_torn_tail(tmp_path):
    d = str(tmp_path)
    log = BlockLog(d)
    items = np.ones((4, 2), dtype=np.uint32)
    freqs = np.ones(4, dtype=np.int64)
    log.append_block(items, freqs)
    log.append_block(items, freqs)
    log.close()
    # crash mid-append: chop bytes off the tail of the last segment
    seg = sorted(os.listdir(os.path.join(d, "wal")))[-1]
    path = os.path.join(d, "wal", seg)
    size = os.path.getsize(path)
    with open(path, "ab") as f:
        f.truncate(size - 7)
    log2 = BlockLog(d)                         # reopen truncates the tear
    recs = log2.records(0)
    assert len(recs) == 1 and recs[0].seq == 0
    assert log2.next_seq == 1                  # seq 1 was never durable
    log2.append_block(items, freqs)            # and is cleanly re-appended
    assert [r.seq for r in log2.records(0)] == [0, 1]


def test_wal_duplicate_skipped_gap_refused(tmp_path):
    d = str(tmp_path)
    log = BlockLog(d)
    for i in range(4):
        log.append_block(np.full((2, 2), i, dtype=np.uint32),
                         np.ones(2, dtype=np.int64))
    log.close()
    duplicate_wal_record(d, 2)
    recs = BlockLog(d).records(0)
    assert [r.seq for r in recs] == [0, 1, 2, 3]   # applied exactly once
    drop_wal_record(d, 1)
    with pytest.raises(WALGapError, match="missing"):
        BlockLog(d).records(0)


def test_wal_reopen_after_tail_duplicate_keeps_next_seq(tmp_path):
    # A survived-retry duplicate sits at the TAIL with a stale lower seq;
    # reopening must resume at max(seq)+1, not regress the cursor (which
    # would make new appends reuse live seqs and be dropped as duplicates).
    d = str(tmp_path)
    log = BlockLog(d)
    for i in range(4):
        log.append_block(np.full((2, 2), i, dtype=np.uint32),
                         np.ones(2, dtype=np.int64))
    log.close()
    duplicate_wal_record(d, 1)
    log2 = BlockLog(d)
    assert log2.next_seq == 4
    log2.append_block(np.full((2, 2), 9, dtype=np.uint32),
                      np.ones(2, dtype=np.int64))
    recs = log2.records(0)
    assert [r.seq for r in recs] == [0, 1, 2, 3, 4]
    assert np.array_equal(recs[-1].items, np.full((2, 2), 9,
                                                  dtype=np.uint32))
    log2.close()


def test_empty_block_advances_wal_seq_and_supervisor_cursor(tmp_path):
    # Every op maps 1:1 onto a WAL seq, empties included -- otherwise the
    # supervisor's cursor (next_seq) never passes an empty-block op.
    stream = _stream()
    spec = _spec(stream)
    blocks = _blocks(stream)[:4]
    empty = (blocks[0][0][:0], blocks[0][1][:0])
    ops = [("block", *blocks[0]), ("block", *empty),
           ("block", *blocks[1]), ("block", *blocks[2]),
           ("block", *blocks[3])]
    ref = SketchTopKEndpoint(spec, KEY)
    for _, it, fr in ops:
        ref.ingest(it, fr)
    sup = ServingSupervisor(str(tmp_path),
                            lambda: SketchTopKEndpoint(spec, KEY),
                            snapshot_every=2)
    eng, rep = sup.run(ops, FaultPlan(crash_after_ops=3, max_crashes=1))
    assert rep.crashes == 1
    assert eng.log.next_seq == len(ops)
    eng.drain()
    _assert_same_endpoint(ref, eng.backend)
    eng.close()


def test_wal_rotate_and_prune_respects_retained_snapshots(tmp_path):
    stream = _stream()
    spec = _spec(stream)
    eng = DurableSketchEngine(
        SketchServeEngine(SketchTopKEndpoint(spec, KEY)), str(tmp_path),
        keep_snapshots=2)
    blocks = _blocks(stream)
    wal_dir = os.path.join(str(tmp_path), "wal")
    for it, fr in blocks[:2]:
        eng.ingest(it, fr)
    eng.snapshot()
    # one snapshot retained: nothing pruned (its corruption must leave a
    # full-replay path)
    assert len(os.listdir(wal_dir)) >= 2
    for it, fr in blocks[2:4]:
        eng.ingest(it, fr)
    eng.snapshot()
    for it, fr in blocks[4:]:
        eng.ingest(it, fr)
    eng.snapshot()
    # keep_last=2 retains steps {4, 6}; segments below step 4 are pruned
    segs = sorted(os.listdir(wal_dir))
    assert int(segs[0].split("_")[1].split(".")[0]) >= 2
    eng.close()
    # and recovery still works from what remains
    eng2, rep = recover(str(tmp_path), lambda: SketchTopKEndpoint(spec, KEY))
    ref = SketchTopKEndpoint(spec, KEY)
    for it, fr in blocks:
        ref.ingest(it, fr)
    _assert_same_endpoint(ref, eng2.backend)


# --------------------------------------------------------------------------
# kill-and-recover bit-exactness (the acceptance matrix, single-device legs)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("kwargs", [
    {}, {"mode": "conservative"}, {"use_update_kernel": True}],
    ids=["linear", "conservative", "kernel"])
def test_kill_recover_endpoint_bitwise(tmp_path, kwargs):
    stream = _stream()
    spec = _spec(stream)
    blocks = _blocks(stream)
    ops = [("block", it, fr) for it, fr in blocks]
    ref = SketchTopKEndpoint(spec, KEY, **kwargs)
    for it, fr in blocks:
        ref.ingest(it, fr)

    sup = ServingSupervisor(str(tmp_path),
                            lambda: SketchTopKEndpoint(spec, KEY, **kwargs),
                            snapshot_every=3)
    eng, rep = sup.run(ops, FaultPlan(crash_after_ops=4, max_crashes=1))
    assert rep.crashes == 1
    assert rep.recoveries[-1].restored_step is not None
    eng.drain()                    # fold the pipelined block before peeking
    _assert_same_endpoint(ref, eng.backend)
    ri, re_ = ref.topk(10)
    ei, ee = eng.topk(10)
    assert np.array_equal(ri, ei) and np.array_equal(re_, ee)


def test_kill_recover_windowed_mid_window_bitwise(tmp_path):
    stream = _stream()
    spec = _spec(stream)
    ops = []
    for n, (it, fr) in enumerate(_blocks(stream)):
        ops.append(("block", it, fr))
        if n % 3 == 2:
            ops.append(("advance",))
    ref = WindowedTopKService(spec, KEY, n_epochs=3)
    for op in ops:
        ref.ingest(op[1], op[2]) if op[0] == "block" else ref.advance()

    sup = ServingSupervisor(str(tmp_path),
                            lambda: WindowedTopKService(spec, KEY, n_epochs=3),
                            snapshot_every=4)
    eng, rep = sup.run(ops, FaultPlan(crash_after_ops=5, max_crashes=1))
    assert rep.crashes == 1
    assert eng.backend.epoch == ref.epoch
    assert eng.backend.total == ref.total
    ri, re_ = ref.topk(10)
    ei, ee = eng.topk(10)
    assert np.array_equal(ri, ei) and np.array_equal(re_, ee)


def test_kill_recover_corrupted_snapshot_falls_back(tmp_path):
    stream = _stream()
    spec = _spec(stream)
    blocks = _blocks(stream)
    ops = [("block", it, fr) for it, fr in blocks]
    ref = SketchTopKEndpoint(spec, KEY)
    for it, fr in blocks:
        ref.ingest(it, fr)

    sup = ServingSupervisor(str(tmp_path),
                            lambda: SketchTopKEndpoint(spec, KEY),
                            snapshot_every=2)
    plan = FaultPlan(crash_after_ops=3, max_crashes=1,
                     corrupt_newest_snapshot=True)
    eng, rep = sup.run(ops, plan)
    last = rep.recoveries[-1]
    assert last.corrupted_steps, "the corrupted snapshot must be detected"
    eng.drain()
    _assert_same_endpoint(ref, eng.backend)
    ri, re_ = ref.topk(10)
    ei, ee = eng.topk(10)
    assert np.array_equal(ri, ei) and np.array_equal(re_, ee)


def test_repeated_crashes_until_max_restarts(tmp_path):
    stream = _stream()
    spec = _spec(stream)
    ops = [("block", it, fr) for it, fr in _blocks(stream)]
    sup = ServingSupervisor(str(tmp_path),
                            lambda: SketchTopKEndpoint(spec, KEY),
                            snapshot_every=2, max_restarts=1)
    from repro.serving.faults import InjectedCrash

    with pytest.raises(InjectedCrash):
        sup.run(ops, FaultPlan(crash_after_ops=1, max_crashes=10))


def test_engine_watermark_survives_recovery(tmp_path):
    stream = _stream()
    spec = _spec(stream)
    blocks = _blocks(stream)
    eng = DurableSketchEngine(
        SketchServeEngine(SketchTopKEndpoint(spec, KEY)), str(tmp_path))
    for it, fr in blocks[:3]:
        eng.ingest(it, fr)
    eng.snapshot()
    mass = eng.engine.ingested_mass
    assert mass == sum(int(fr.sum()) for _, fr in blocks[:3])
    eng.close()
    eng2, rep = recover(str(tmp_path), lambda: SketchTopKEndpoint(spec, KEY))
    assert eng2.engine.ingested_mass == mass
    assert rep.replayed_blocks == 0        # everything was in the snapshot


def test_recover_empty_directory_starts_fresh(tmp_path):
    stream = _stream()
    spec = _spec(stream)
    eng, rep = recover(str(tmp_path), lambda: SketchTopKEndpoint(spec, KEY))
    assert rep.restored_step is None and rep.replayed_blocks == 0
    it, fr = _blocks(stream)[0]
    eng.ingest(it, fr)
    eng.drain()
    assert eng.backend.total == int(fr.sum())


# --------------------------------------------------------------------------
# crash-consistent migration (satellite)
# --------------------------------------------------------------------------

def test_abort_migration_leaves_no_residue():
    stream = _stream()
    spec = _spec(stream)
    new_spec = _spec(stream, ranges=(16, 64))
    blocks = _blocks(stream)
    ref = SketchTopKEndpoint(spec, KEY)      # never migrates
    ep = SketchTopKEndpoint(spec, KEY)
    for it, fr in blocks[:3]:
        ref.ingest(it, fr)
        ep.ingest(it, fr)
    ep.begin_migration(new_spec, jax.random.PRNGKey(9), warmup=1 << 40)
    for it, fr in blocks[3:5]:               # double-write window open
        ref.ingest(it, fr)
        ep.ingest(it, fr)
    assert ep.migrating
    ep.abort_migration()
    assert not ep.migrating
    _assert_same_endpoint(ref, ep)           # active surface untouched
    for it, fr in blocks[5:]:                # and stays bitwise thereafter
        ref.ingest(it, fr)
        ep.ingest(it, fr)
    _assert_same_endpoint(ref, ep)
    ep.abort_migration()                     # aborting twice is a no-op


def test_checkpoint_mid_warmup_refuses_with_clear_error():
    stream = _stream()
    spec = _spec(stream)
    ep = SketchTopKEndpoint(spec, KEY)
    it, fr = _blocks(stream)[0]
    ep.ingest(it, fr)
    ep.begin_migration(_spec(stream, ranges=(16, 64)), jax.random.PRNGKey(9),
                       warmup=1 << 40)
    with pytest.raises(ValueError, match="abort_migration"):
        ep.state_dict()
    ep.abort_migration()
    ep.state_dict()                          # fine after rollback


# --------------------------------------------------------------------------
# sharded legs: kill/recover + N->M remesh (forced multi-device subprocess)
# --------------------------------------------------------------------------

def test_sharded_remesh_grow_shrink_bitwise():
    print(_run("""
        import jax, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.core import sketch as sk
        from repro.serving.sharded_topk import ShardedTopKService
        from repro.streams import zipf_hh_workload

        wl = zipf_hh_workload(n_occurrences=20_000, n_edges=4_000, seed=3)
        spec = sk.mod_sketch_spec(wl.stream.schema, [(0,), (1,)], (64, 64), 3)
        key = jax.random.PRNGKey(7)
        items, freqs = wl.stream.items, wl.stream.freqs
        blocks = [(items[s:s+500], freqs[s:s+500])
                  for s in range(0, len(items), 500)]
        half = len(blocks) // 2
        assert jax.device_count() >= 4, jax.device_count()
        mesh2 = make_mesh((2,), ("data",))
        mesh4 = make_mesh((4,), ("data",))

        ref = ShardedTopKService(spec, key, mesh2, sync_every=2)
        for it, fr in blocks: ref.ingest(it, fr)
        ri, re = ref.topk(10)
        rt = [np.asarray(st.table) for st in ref.state().states]

        for src, dst in [(mesh2, mesh4), (mesh4, mesh2)]:
            svc = ShardedTopKService(spec, key, src, sync_every=2)
            for it, fr in blocks[:half]: svc.ingest(it, fr)
            svc.remesh(dst)
            # post-remesh queries answer immediately (no drain)
            for it, fr in blocks[half:]: svc.ingest(it, fr)
            ei, ee = svc.topk(10)
            assert np.array_equal(ri, ei) and np.array_equal(re, ee)
            for a, st in zip(rt, svc.state().states):
                assert np.array_equal(a, np.asarray(st.table))
        print("remesh 2->4 and 4->2 bit-exact")
    """))


def test_sharded_snapshot_restores_across_shard_counts():
    print(_run("""
        import jax, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.core import sketch as sk
        from repro.serving.sharded_topk import ShardedTopKService
        from repro.streams import zipf_hh_workload

        wl = zipf_hh_workload(n_occurrences=20_000, n_edges=4_000, seed=3)
        spec = sk.mod_sketch_spec(wl.stream.schema, [(0,), (1,)], (64, 64), 3)
        key = jax.random.PRNGKey(7)
        items, freqs = wl.stream.items, wl.stream.freqs
        blocks = [(items[s:s+500], freqs[s:s+500])
                  for s in range(0, len(items), 500)]
        half = len(blocks) // 2
        mesh2 = make_mesh((2,), ("data",))
        mesh4 = make_mesh((4,), ("data",))

        ref = ShardedTopKService(spec, key, mesh2, sync_every=2)
        for it, fr in blocks: ref.ingest(it, fr)
        ri, re = ref.topk(10)

        src = ShardedTopKService(spec, key, mesh4, sync_every=2)
        for it, fr in blocks[:half]: src.ingest(it, fr)
        sd = src.state_dict()
        # 4-shard snapshot restored into a 2-shard service: pools fold
        for dst_mesh, n in [(mesh4, 4), (mesh2, 2)]:
            dst = ShardedTopKService(spec, key, dst_mesh, sync_every=2)
            dst.load_state_dict(sd)
            assert dst.n_shards == n
            for it, fr in blocks[half:]: dst.ingest(it, fr)
            ei, ee = dst.topk(10)
            assert np.array_equal(ri, ei) and np.array_equal(re, ee)
        print("sharded snapshot restores at 4 and 2 shards, bit-exact")
    """))


def test_sharded_kill_recover_bitwise():
    print(_run("""
        import tempfile, jax, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.core import sketch as sk
        from repro.serving.sharded_topk import ShardedTopKService
        from repro.serving.faults import ServingSupervisor, FaultPlan
        from repro.streams import zipf_hh_workload

        wl = zipf_hh_workload(n_occurrences=20_000, n_edges=4_000, seed=3)
        spec = sk.mod_sketch_spec(wl.stream.schema, [(0,), (1,)], (64, 64), 3)
        key = jax.random.PRNGKey(7)
        items, freqs = wl.stream.items, wl.stream.freqs
        ops = [("block", items[s:s+500], freqs[s:s+500])
               for s in range(0, len(items), 500)]
        mesh = make_mesh((min(4, jax.device_count()),), ("data",))

        def factory():
            return ShardedTopKService(spec, key, mesh, sync_every=2)

        ref = factory()
        for _, it, fr in ops: ref.ingest(it, fr)
        ri, re = ref.topk(10)

        with tempfile.TemporaryDirectory() as d:
            sup = ServingSupervisor(d, factory, snapshot_every=3)
            eng, rep = sup.run(ops, FaultPlan(crash_after_ops=4,
                                              max_crashes=1))
            assert rep.crashes == 1
            ei, ee = eng.topk(10)
            assert np.array_equal(ri, ei) and np.array_equal(re, ee)
            assert eng.backend.total == ref.total
            for a, b in zip(ref.state().states, eng.backend.state().states):
                assert np.array_equal(np.asarray(a.table),
                                      np.asarray(b.table))
        print("sharded kill/recover bit-exact")
    """))
