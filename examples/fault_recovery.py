"""Kill -9 a sketch server and get every bit back.

    PYTHONPATH=src python examples/fault_recovery.py

Walks the durability layer end to end (docs/architecture.md section 9):

  1. wrap a serving engine in DurableSketchEngine: every ingest block is
     WAL-appended before it touches the tables, and periodic snapshots
     (CRC-verified, versioned) bound how much log a recovery replays,
  2. crash it mid-stream through the fault-injection supervisor -- a hard
     kill, no drain, no goodbye snapshot -- then recover() and finish the
     stream: the result is bit-identical to a run that never crashed,
  3. corrupt the newest snapshot on disk before a second crash: the CRC
     check rejects it, recovery falls back to replaying the whole log,
     and the answers are STILL bit-identical,
  4. remesh a sharded service 2 -> 4 shards mid-stream (the elastic
     resize a real fleet does when capacity changes) and verify the
     tables and top-k are bit-identical at any shard count.
"""
import os
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()

import tempfile

import jax
import numpy as np

from repro.core import sketch as sk
from repro.launch.mesh import make_mesh
from repro.serving.faults import FaultPlan, ServingSupervisor
from repro.serving.sharded_topk import ShardedTopKService
from repro.serving.sketch_engine import SketchTopKEndpoint
from repro.streams import zipf_hh_workload

wl = zipf_hh_workload(n_occurrences=60_000, n_edges=8_000, seed=5)
spec = sk.mod_sketch_spec(wl.stream.schema, [(0,), (1,)], (128, 128), 4)
key = jax.random.PRNGKey(0)
items, freqs = wl.stream.items, wl.stream.freqs
BLOCK = 128
ops = [("block", items[s:s + BLOCK], freqs[s:s + BLOCK])
       for s in range(0, len(items), BLOCK)]
print(f"stream: {len(ops)} blocks, {wl.stream.total} total mass")

# the run that never crashes, as ground truth
ref = SketchTopKEndpoint(spec, key)
for _, it, fr in ops:
    ref.ingest(it, fr)
ref_ids, ref_est = ref.topk(10)

# --- 1+2: hard kill mid-stream, recover, finish -------------------------
with tempfile.TemporaryDirectory() as d:
    sup = ServingSupervisor(d, lambda: SketchTopKEndpoint(spec, key),
                            snapshot_every=8)
    eng, rep = sup.run(ops, FaultPlan(crash_after_ops=len(ops) // 2))
    ids, est = eng.topk(10)
    assert np.array_equal(ids, ref_ids) and np.array_equal(est, ref_est)
    r = rep.recoveries[-1]
    print(f"killed after {len(ops)//2} ops: restored snapshot "
          f"step={r.restored_step}, replayed {r.replayed_blocks} WAL "
          f"blocks -> top-10 bit-identical to the uninterrupted run")

# --- 3: the newest snapshot is corrupted on disk ------------------------
with tempfile.TemporaryDirectory() as d:
    sup = ServingSupervisor(d, lambda: SketchTopKEndpoint(spec, key),
                            snapshot_every=8)
    plan = FaultPlan(crash_after_ops=len(ops) // 2,
                     corrupt_newest_snapshot=True)
    eng, rep = sup.run(ops, plan)
    ids, est = eng.topk(10)
    assert np.array_equal(ids, ref_ids) and np.array_equal(est, ref_est)
    r = rep.recoveries[-1]
    print(f"corrupted snapshot(s) {r.corrupted_steps} rejected by CRC, "
          f"fell back and replayed {r.replayed_blocks} blocks -> still "
          f"bit-identical")

# --- 4: elastic 2 -> 4 shard remesh mid-stream --------------------------
svc = ShardedTopKService(spec, key, make_mesh((2,), ("data",)),
                         sync_every=4)
half = len(ops) // 2
for _, it, fr in ops[:half]:
    svc.ingest(it, fr)
svc.remesh(make_mesh((4,), ("data",)))
for _, it, fr in ops[half:]:
    svc.ingest(it, fr)
ids, est = svc.topk(10)
assert np.array_equal(ids, ref_ids) and np.array_equal(est, ref_est)
print(f"remeshed 2 -> 4 shards mid-stream -> top-10 bit-identical "
      f"(total={svc.total})")

print("OK")
