"""Hierarchical heavy hitters in 60 lines.

    PYTHONPATH=src python examples/heavy_hitters.py

Builds a Zipf edge stream and a bigram token stream, stacks a prefix
hierarchy of composite-hash sketches over each, and recovers every key
above a frequency threshold by recursive descent -- comparing the batched
Pallas candidate kernel against the jnp reference and against exact ground
truth, then serves top-k through the SketchTopKEndpoint -- and finally
through the sharded service, whose output is bit-identical at any shard
count.  Off a TPU it forces 8 virtual CPU devices for the meshes and runs
the kernels in interpret mode; on the chip, ``python chip_smoke.py
[--chips 4]`` drives the same serving path with the kernels compiled.
"""
import os
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()

import jax
import numpy as np

from repro.core import hierarchy as hh
from repro.core import sketch as sk
from repro.launch.mesh import make_mesh
from repro.serving.engine import SketchTopKEndpoint
from repro.streams import ngram_hh_workload, zipf_hh_workload

key = jax.random.PRNGKey(0)

for wl, part, ranges in (
    (zipf_hh_workload(n_occurrences=100_000), [(0,), (1,)], (256, 256)),
    (ngram_hh_workload(vocab_size=512, n=2), [(0,), (1,)], (128, 128)),
):
    stream = wl.stream
    base = sk.mod_sketch_spec(stream.schema, part, ranges, 4)
    hspec = hh.HierarchySpec.from_spec(base)
    state = hh.build_hierarchy(hspec, key, stream.items, stream.freqs)
    cands = wl.candidates(base)

    got_ref, est_ref = hh.find_heavy_hitters(hspec, state, wl.threshold, cands)
    got_krn, est_krn = hh.find_heavy_hitters(hspec, state, wl.threshold, cands,
                                             use_kernel=True)
    assert np.array_equal(got_ref, got_krn), "kernel/reference disagree"

    exact = {tuple(r) for r in wl.exact_items.tolist()}
    got = {tuple(r) for r in got_ref.tolist()}
    print(f"{stream.name}: L={stream.total:,} threshold={wl.threshold} "
          f"exact={len(exact)} reported={len(got)} "
          f"false_neg={len(exact - got)} false_pos={len(got - exact)} "
          f"(tables: {hspec.table_cells:,} cells over {hspec.n_levels} levels)")

# serving endpoint: ingest in shards, merge, query top-k
wl = zipf_hh_workload(n_occurrences=100_000, seed=1)
spec = sk.mod_sketch_spec(wl.stream.schema, [(0,), (1,)], (256, 256), 4)
shards = [SketchTopKEndpoint(spec, key) for _ in range(2)]
half = len(wl.stream.items) // 2
shards[0].ingest(wl.stream.items[:half], wl.stream.freqs[:half])
shards[1].ingest(wl.stream.items[half:], wl.stream.freqs[half:])
shards[0].merge_from(shards[1])
items, est = shards[0].topk(10)
true_top = wl.exact_freqs[:10] if len(wl.exact_freqs) >= 10 else wl.exact_freqs
print(f"endpoint top-10 estimates: {est.tolist()}")
print(f"exact top frequencies:     {true_top.tolist()}")

# conservative endpoint: tighter estimates, but single-shard (non-linear
# tables refuse merge_from -- excluded from the cell-wise merge/psum paths)
cons = SketchTopKEndpoint(spec, key, mode="conservative")
cons.ingest(wl.stream.items, wl.stream.freqs)
cons_items, est_cons = cons.topk(10)
# same hash params + same stream => per-key dominance (rank-wise comparison
# would be unsound once the two endpoints' candidate pools diverge)
lin_est = {tuple(k): e for k, e in zip(items.tolist(), est.tolist())}
overlap = [(c, lin_est[tuple(k)])
           for k, c in zip(cons_items.tolist(), est_cons.tolist())
           if tuple(k) in lin_est]
assert overlap and all(c <= l for c, l in overlap), \
    "conservative must be tighter per key"
print(f"conservative top-10:       {est_cons.tolist()} (<= linear per key)")

# sharded service: the same stream through a 1-shard and a 4-shard mesh
# (different block splits!) yields bit-identical level tables and top-k --
# the psum merge of linear tables is exact, so shard count cannot matter
from repro.serving.sharded_topk import ShardedTopKService
svc1 = ShardedTopKService(spec, key, make_mesh((1,), ("data",)))
svc4 = ShardedTopKService(spec, key, make_mesh((4,), ("data",)),
                          sync_every=2)
svc1.ingest(wl.stream.items, wl.stream.freqs)
third = len(wl.stream.items) // 3
for s, e in ((0, third), (third, 2 * third), (2 * third, None)):
    svc4.ingest(wl.stream.items[s:e], wl.stream.freqs[s:e])
for a, b in zip(svc1.state().states, svc4.state().states):
    assert np.array_equal(np.asarray(a.table), np.asarray(b.table))
s1_items, s1_est = svc1.topk(10)
s4_items, s4_est = svc4.topk(10)
assert np.array_equal(s1_items, s4_items) and np.array_equal(s1_est, s4_est)
print(f"sharded top-10 (1==4 shards): {s4_est.tolist()}")
