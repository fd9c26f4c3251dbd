"""Sharded heavy-hitter serving, production shape.

    PYTHONPATH=src python examples/sharded_serving.py

Runs the serving deployment on an 8-device mesh built by
repro.launch.mesh.make_mesh -- 8 virtual CPU devices off a TPU (kernels in
interpret mode; ``python chip_smoke.py --chips 4`` is the on-chip sharded
path):

  1. a single-shard SketchTopKEndpoint handles early traffic,
  2. traffic grows, so the endpoint is promoted in place to a
     ShardedTopKService (to_sharded carries tables, hash params, candidate
     pools, and totals over),
  3. ingest workers feed uneven blocks; the psum sync runs every few
     blocks (lazy local tables between sync points -- no collective on the
     ingest hot path),
  4. top-k and threshold queries serve from the merged level tables, and a
     1-shard reference service run over the identical stream verifies the
     answers are bit-identical (shard-count invariance).
"""
import os
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()

import jax
import numpy as np

from repro.core import sketch as sk
from repro.launch.mesh import make_mesh
from repro.serving.engine import SketchTopKEndpoint
from repro.serving.sharded_topk import ShardedTopKService
from repro.streams import zipf_hh_workload

wl = zipf_hh_workload(n_occurrences=150_000, n_edges=15_000, seed=4)
spec = sk.mod_sketch_spec(wl.stream.schema, [(0,), (1,)], (256, 256), 4)
key = jax.random.PRNGKey(0)
items, freqs = wl.stream.items, wl.stream.freqs

# phase 1: single-shard endpoint takes the first quarter of the stream
q = len(items) // 4
ep = SketchTopKEndpoint(spec, key)
ep.ingest(items[:q], freqs[:q])
print(f"endpoint: ingested {ep.total:,} of {wl.stream.total:,} occurrences")

# phase 2: promote to an 8-shard service on the mesh; conservative
# endpoints would be refused here (non-linear tables cannot psum)
mesh = make_mesh((8,), ("data",))
svc = ep.to_sharded(mesh, sync_every=4)
print(f"promoted to {svc.n_shards} shards over axes {svc.data_axes}")

# phase 3: ingest workers push uneven blocks; sync every 4 blocks
rng = np.random.default_rng(0)
cuts = np.sort(rng.choice(np.arange(q + 1, len(items)), 6, replace=False))
for s, e in zip(np.r_[q, cuts], np.r_[cuts, len(items)]):
    svc.ingest(items[s:e], freqs[s:e])
svc.sync()

# phase 4: serve queries from the merged tables
top_items, top_est = svc.topk(10)
hh_items, hh_est = svc.heavy_hitters(wl.threshold)
exact = {tuple(r) for r in wl.exact_items.tolist()}
got = {tuple(r) for r in hh_items.tolist()}
print(f"topk(10) estimates: {top_est.tolist()}")
print(f"heavy_hitters(>={wl.threshold}): reported={len(got)} "
      f"false_neg={len(exact - got)} false_pos={len(got - exact)}")
assert exact <= got

# verification: a 1-shard service over the identical stream agrees bit-
# for-bit -- linear tables + exact integer psum make sharding invisible
ref = ShardedTopKService(spec, key, make_mesh((1,), ("data",)))
ref.ingest(items, freqs)
for a, b in zip(svc.state().states, ref.state().states):
    assert np.array_equal(np.asarray(a.table), np.asarray(b.table))
r_items, r_est = ref.topk(10)
assert np.array_equal(top_items, r_items) and np.array_equal(top_est, r_est)
print("1-shard reference agrees bit-exactly: shard count is invisible")
