"""Durability-layer benchmarks -> BENCH_RECOVERY.json.

Run via ``python -m benchmarks.run --only recovery``:

  * ``recovery/snapshot`` -- wall cost of a durable snapshot (drain +
    state_dict + CRC'd write + WAL rotate/prune) as the sketch tables
    grow; the knob that prices the snapshot cadence.
  * ``recovery/replay`` -- crash-recovery wall time and replayed-block
    throughput as a function of snapshot cadence: cadence bounds how much
    WAL a recovery must re-fold, so this row pair is the
    recovery-time-vs-ingest-overhead trade made measurable.
  * ``recovery/wal_overhead`` -- steady-state ingest cost bare vs with a
    WAL (fsync off/on): what durability charges every block that never
    crashes.
  * ``recovery/remesh`` -- N->M shard re-meshing latency across
    1->2->4->8 (as the forced device count allows): sync + pool fold +
    table re-layout + jit-wrapper rebuild, the downtime of an elastic
    resize.

CPU/interpret numbers: orchestration + fsync costs dominate, not device
table speed (docs/benchmarks.md, "interpret-mode caveat").
"""
from __future__ import annotations

import os
import shutil
import tempfile
import time

import jax
import numpy as np

from benchmarks.common import emit
from repro.core import sketch as sk
from repro.launch.mesh import make_mesh
from repro.serving.recovery import DurableSketchEngine, recover
from repro.serving.sketch_engine import SketchServeEngine, SketchTopKEndpoint
from repro.streams import zipf_hh_workload

_KEY = jax.random.PRNGKey(0)
_BLOCK = 500


def _blocks(n_occurrences=40_000, n_edges=8_000, seed=3):
    stream = zipf_hh_workload(n_src=2_000, n_tgt=4_000, n_edges=n_edges,
                              n_occurrences=n_occurrences, seed=seed).stream
    return stream, [(stream.items[s:s + _BLOCK], stream.freqs[s:s + _BLOCK])
                    for s in range(0, stream.items.shape[0], _BLOCK)]


def recovery_snapshot_cost() -> None:
    stream, blocks = _blocks()
    for h in (256, 1024, 4096):
        spec = sk.mod_sketch_spec(stream.schema, [(0,), (1,)], (h, h), 4)
        with tempfile.TemporaryDirectory() as d:
            eng = DurableSketchEngine(
                SketchServeEngine(SketchTopKEndpoint(spec, _KEY)), d,
                fsync=False)
            for it, fr in blocks[:8]:
                eng.ingest(it, fr)
            eng.snapshot()                       # warm the write path
            t0 = time.perf_counter()
            reps = 5
            for _ in range(reps):
                eng.snapshot()
            dt = (time.perf_counter() - t0) / reps
            cells = sum(int(np.prod(st.table.shape))
                        for st in eng.backend.state.states)
            emit("recovery/snapshot", dt * 1e6,
                 f"h={h};table_cells={cells};keep=3")
            eng.close()


def recovery_replay_throughput() -> None:
    stream, blocks = _blocks()
    spec = sk.mod_sketch_spec(stream.schema, [(0,), (1,)], (1024, 1024), 4)
    for cadence in (None, 8, 2):
        with tempfile.TemporaryDirectory() as d:
            eng = DurableSketchEngine(
                SketchServeEngine(SketchTopKEndpoint(spec, _KEY)), d,
                snapshot_every=cadence, fsync=False)
            for it, fr in blocks:
                eng.ingest(it, fr)
            eng.close()
            t0 = time.perf_counter()
            eng2, rep = recover(d, lambda: SketchTopKEndpoint(spec, _KEY),
                                fsync=False)
            dt = time.perf_counter() - t0
            eng2.close()
            blk_s = rep.replayed_blocks / dt if dt > 0 else 0.0
            emit("recovery/replay", dt * 1e6,
                 f"cadence={cadence};replayed={rep.replayed_blocks};"
                 f"blocks_per_s={blk_s:.1f};"
                 f"restored_step={rep.restored_step}")


def recovery_wal_overhead() -> None:
    stream, blocks = _blocks()
    spec = sk.mod_sketch_spec(stream.schema, [(0,), (1,)], (1024, 1024), 4)

    def run(build):
        eng = build()
        for it, fr in blocks[:4]:                # warm-up + jit
            eng.ingest(it, fr)
        t0 = time.perf_counter()
        for it, fr in blocks[4:]:
            eng.ingest(it, fr)
        eng.drain()
        return (time.perf_counter() - t0) / max(1, len(blocks) - 4), eng

    bare_us, eng = run(lambda: SketchServeEngine(SketchTopKEndpoint(spec,
                                                                    _KEY)))
    emit("recovery/wal_overhead", bare_us * 1e6, "wal=off;fsync=-")
    for fsync in (False, True):
        d = tempfile.mkdtemp()
        try:
            dur_us, eng = run(lambda: DurableSketchEngine(
                SketchServeEngine(SketchTopKEndpoint(spec, _KEY)), d,
                fsync=fsync))
            eng.close()
            emit("recovery/wal_overhead", dur_us * 1e6,
                 f"wal=on;fsync={fsync};overhead_x={dur_us / bare_us:.2f}")
        finally:
            shutil.rmtree(d, ignore_errors=True)


def recovery_remesh_latency() -> None:
    n_dev = jax.device_count()
    if n_dev < 2:
        emit("recovery/remesh", 0.0, f"skipped=devices<2;devices={n_dev}")
        return
    from repro.serving.sharded_topk import ShardedTopKService

    stream, blocks = _blocks()
    spec = sk.mod_sketch_spec(stream.schema, [(0,), (1,)], (512, 512), 4)
    ladder = [n for n in (1, 2, 4, 8) if n <= n_dev]
    for src, dst in zip(ladder, ladder[1:]):
        svc = ShardedTopKService(spec, _KEY, make_mesh((src,), ("data",)),
                                 sync_every=4)
        for it, fr in blocks[:12]:
            svc.ingest(it, fr)
        dst_mesh = make_mesh((dst,), ("data",))
        t0 = time.perf_counter()
        svc.remesh(dst_mesh)
        jax.block_until_ready([st.table for st in svc.merged.states])
        dt = time.perf_counter() - t0
        emit("recovery/remesh", dt * 1e6,
             f"src={src};dst={dst};devices={n_dev}")
        # and back down: shrink is the failure-response direction
        src_mesh = make_mesh((src,), ("data",))
        t0 = time.perf_counter()
        svc.remesh(src_mesh)
        jax.block_until_ready([st.table for st in svc.merged.states])
        dt = time.perf_counter() - t0
        emit("recovery/remesh", dt * 1e6,
             f"src={dst};dst={src};devices={n_dev}")


ALL = [recovery_snapshot_cost, recovery_replay_throughput,
       recovery_wal_overhead, recovery_remesh_latency]
