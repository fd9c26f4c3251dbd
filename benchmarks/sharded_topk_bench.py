"""Sharded heavy-hitter serving benchmarks (serving/sharded_topk.py).

Two sweeps, both emitted as the common CSV rows and archived by CI as
BENCH_*.json (run via ``python -m benchmarks.run --only sharded``):

  * ingest throughput vs shard count -- the per-shard lazy fold scales the
    ingest path over the mesh's data axis; shard counts sweep the divisors
    of the available device count (force more CPU devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``, as the CI bench
    job does),
  * sync cadence -- how much of the ingest wall time the psum sync point
    costs as the merge all-reduce is amortized over more blocks.

On a single-device run only the 1-shard rows are produced (the sweep
adapts rather than failing), which keeps the bench usable in any
container.  CPU numbers track the collective/orchestration overheads, not
kernel speed; re-run on hardware for real throughput.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks.common import emit
from repro.core import sketch as sk
from repro.launch.mesh import make_mesh
from repro.serving.sharded_topk import ShardedTopKService
from repro.streams import zipf_hh_workload

_BLOCKS = 8


def _workload():
    wl = zipf_hh_workload(n_occurrences=200_000, n_edges=20_000, seed=0)
    spec = sk.mod_sketch_spec(wl.stream.schema, [(0,), (1,)], (256, 256), 4)
    return wl, spec


def _block_edges(n: int):
    return np.linspace(0, n, _BLOCKS + 1).astype(int)


def sharded_ingest_throughput() -> None:
    wl, spec = _workload()
    items, freqs = wl.stream.items, wl.stream.freqs
    counts = [c for c in (1, 2, 4, 8) if c <= jax.device_count()]
    edges = _block_edges(len(items))
    for c in counts:
        mesh = make_mesh((c,), ("data",))
        svc = ShardedTopKService(spec, jax.random.PRNGKey(0), mesh,
                                 sync_every=None)
        # warmup: compile the per-shard fold for this shard count
        svc.ingest(items[: edges[1]], freqs[: edges[1]])
        svc.sync()
        t0 = time.perf_counter()
        for s, e in zip(edges[:-1], edges[1:]):
            svc.ingest(items[s:e], freqs[s:e])
        jax.block_until_ready(svc._local)
        dt = time.perf_counter() - t0
        t0 = time.perf_counter()
        svc.sync()
        jax.block_until_ready(svc.state().states[0].table)
        dt_sync = time.perf_counter() - t0
        rows_per_s = len(items) / max(dt, 1e-9)
        emit(f"sharded/ingest_s{c}", dt * 1e6 / _BLOCKS,
             f"shards={c};rows_per_s={rows_per_s:.3e};"
             f"sync_us={dt_sync * 1e6:.1f}")


def sharded_sync_cadence() -> None:
    wl, spec = _workload()
    items, freqs = wl.stream.items, wl.stream.freqs
    c = max(c for c in (1, 2, 4, 8) if c <= jax.device_count())
    mesh = make_mesh((c,), ("data",))
    edges = _block_edges(len(items))
    for cadence in (1, 4, _BLOCKS):
        svc = ShardedTopKService(spec, jax.random.PRNGKey(0), mesh,
                                 sync_every=cadence)
        svc.ingest(items[: edges[1]], freqs[: edges[1]])  # warmup/compile
        svc.sync()
        t0 = time.perf_counter()
        for s, e in zip(edges[:-1], edges[1:]):
            svc.ingest(items[s:e], freqs[s:e])
        svc.sync()
        jax.block_until_ready(svc.state().states[0].table)
        dt = time.perf_counter() - t0
        n_syncs = -(-_BLOCKS // cadence)
        emit(f"sharded/sync_every_{cadence}", dt * 1e6 / _BLOCKS,
             f"shards={c};syncs={n_syncs};wall_s={dt:.3f}")


def sharded_query_after_sync() -> None:
    """End-to-end: topk served from the merged tables (descent included)."""
    wl, spec = _workload()
    c = max(cc for cc in (1, 2, 4, 8) if cc <= jax.device_count())
    mesh = make_mesh((c,), ("data",))
    svc = ShardedTopKService(spec, jax.random.PRNGKey(0), mesh)
    svc.ingest(wl.stream.items, wl.stream.freqs)
    t0 = time.perf_counter()
    items, est = svc.topk(16)
    dt = time.perf_counter() - t0
    exact = {tuple(r) for r in wl.exact_items[:16].tolist()}
    got = {tuple(r) for r in items.tolist()}
    emit("sharded/topk16", dt * 1e6,
         f"shards={c};hit16={len(exact & got)};est0={int(est[0])}")


ALL = [sharded_ingest_throughput, sharded_sync_cadence,
       sharded_query_after_sync]
