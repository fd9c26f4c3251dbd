"""The four-chip cell ``ipv4-zipf-cm-dp4.ingest``: the harness runs a small
copy of it on four host devices (the sharded service folding through the
fused kernel, interpreted) and ``correct`` finds a shard's lost delta; the
cell's readers on a hand-built two-chip trace; the short window recorded on
a TPU v5e 2x2 host (its printed numbers are checked against the readers by
test_bench_trace.py, with every other recorded window)."""
import json
import os
import subprocess
import sys
import textwrap
import types
from pathlib import Path

import numpy as np
import pytest

from bench import catalog
from bench.tests import tiny
from bench.trace_view import TraceView

DATA = Path(__file__).resolve().parent / "data"
MS = 1e6                                   # ns per ms


def _metric(name):
    return catalog.load_reader(catalog.Metric(
        name, "%", "lower", "device_trace",
        catalog.BENCH_DIR / "metrics" / f"{name}.py"))


@pytest.fixture(scope="module")
def small_dp4_runs():
    """One sound run and one with shard 1's delta dropped at the first
    merge, on four host devices, in a subprocess."""
    code = textwrap.dedent("""
        import json, tempfile
        from pathlib import Path
        from bench.served import Served
        from bench.tests import tiny

        root = tiny.make_root(Path(tempfile.mkdtemp()),
                              config="ipv4-zipf-cm-dp4")
        path = root / "bench" / "configs" / "small.json"
        cfg = json.loads(path.read_text())
        cfg["service"]["use_update_kernel"] = True
        path.write_text(json.dumps(cfg))

        class ShardDeltaDropped(Served):
            def __init__(self, *args):
                super().__init__(*args)
                merge, dropped = self.backend._merge, []

                def lossy(local, tables):
                    if not dropped:
                        dropped.append(True)
                        local = tuple(t.at[1].set(0) for t in local)
                    return merge(local, tables)
                self.backend._merge = lossy

        out = {}
        for name, server in (("sound", None), ("fault", ShardDeltaDropped)):
            got = tiny.run(root, "small.ingest", seed=2**31 + 11,
                           make_server=server)
            out[name] = dict(correct=got["correct"],
                             checks={k: v["value"]
                                     for k, v in got["checks"].items()},
                             count=got["device"]["count"],
                             attempted=got["attempted"])
        print("RESULT", json.dumps(out))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(tiny.REPO), str(tiny.REPO / "src"), env.get("PYTHONPATH", "")])
    out = subprocess.run([sys.executable, "-c", code], cwd=tiny.REPO,
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    line = [x for x in out.stdout.splitlines() if x.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def test_small_dp4_cell_is_correct_on_four_devices(small_dp4_runs):
    got = small_dp4_runs["sound"]
    assert got["count"] == 4 and got["attempted"] > 0
    assert got["correct"], got
    assert all(v == 0 for v in got["checks"].values())


def test_a_shard_delta_lost_at_a_merge_is_not_correct(small_dp4_runs):
    got = small_dp4_runs["fault"]
    assert not got["correct"]
    assert got["checks"]["table_cells_wrong"] > 0


def _hand_trace():
    """A 100 ms window on two chips, every program launched inside an
    ingest span.  Chip 0: fold 10-13 ms, an async all-reduce (start 20-21,
    done 24-26 ms), a synchronous all-reduce 60-62 ms (named after the
    psum, as JAX names it) and an add that reads it, 62-63 ms.  Chip 1:
    fold 10-14 ms, one all-reduce 20-26 ms."""
    spans = {"bench.window": np.array([[0, 100 * MS]]),
             "bench.ingest": np.array([[0, 30 * MS], [50 * MS, 70 * MS]])}
    ops = [(np.array([[10, 13], [20, 21], [24, 26], [60, 62], [62, 63]])
            * MS,
            ["%fusion.1 = hier_update", "%all-reduce-start.2 = ar",
             "%all-reduce-done.3 = ar",
             "%psum.7 = s32[4,8]{1,0:T(4,128)} all-reduce(s32[4,8]{1,0} %b)",
             "%add.5 = s32[4,8]{1,0} add(s32[4,8]{1,0} %a, s32[4,8]{1,0} "
             "%psum.7), metadata={all-reduce-done}"]),
           (np.array([[10, 14], [20, 26]]) * MS,
            ["%fusion.1 = hier_update", "%all-reduce.2 = ar"])]
    modules = [(np.array([[10, 13], [20, 26], [60, 62]]) * MS,
                ["jit_sharded_fold(1)", "jit_sharded_merge(2)",
                 "jit_sharded_merge(3)"], np.array([1, 2, 3])),
               (np.array([[10, 14], [20, 26]]) * MS,
                ["jit_sharded_fold(1)", "jit_sharded_merge(2)"],
                np.array([1, 2]))]
    launches = {1: 1 * MS, 2: 15 * MS, 3: 55 * MS}
    return TraceView(spans, ops, modules, launches)


def _run(trace, chips=2, rows=4096):
    config = json.loads((catalog.BENCH_DIR / "configs" /
                         "ipv4-zipf-cm-dp4.json").read_text())
    return types.SimpleNamespace(
        trace=trace, chips=chips, window=types.SimpleNamespace(rows=rows),
        cell=types.SimpleNamespace(config=config),
        peaks={"hbm_bytes_per_s": 819e9, "ici_bits_per_s": 1600e9})


def test_readers_on_a_hand_built_trace():
    run = _run(_hand_trace())
    # chip 0: 20-26 (start/done joined) + 60-62 = 8 ms; chip 1: 6 ms
    assert _metric("sync_collective_share.dp4")(run) == pytest.approx(7.0)
    need = 2 * 2 * (2 - 1) / 2 * 4 * (1 << 20) * 4    # two merges on chip 0
    assert _metric("psum_roofline_share.dp4")(run) == pytest.approx(
        100 * need / (0.007 * 200e9))
    # the one-chip cell's readers, unchanged, on this cell: every program
    # the ingest spans launched (chip 0 11 ms, chip 1 10 ms) ...
    assert _metric("fold_roofline_share.ingest")(run) == pytest.approx(
        100 * 4096 * 44 / (0.021 * 819e9))
    # ... busy (ops, not joined pairs): chip 0 9 ms, chip 1 10 ms
    assert _metric("device_idle_share.ingest")(run) == pytest.approx(90.5)
    # ingest spans 50 ms; some chip runs 0-30: 10-14, 20-26; 50-70: 60-63
    assert _metric("ingest_host_bound_share.ingest")(run) == pytest.approx(
        100 * (50 - 13) / 50)


def test_merge_bytes_of_the_configuration():
    mod = _metric("psum_roofline_share.dp4").__globals__
    assert mod["merge_bytes"](4, 4, [1 << 20]) == 1.5 * 4 * (1 << 20) * 4
    assert mod["merge_bytes"](2, 3, [16, 64]) == 3 * (16 + 16 * 64) * 4


def test_one_chip_reads_no_psum_share():
    assert _metric("psum_roofline_share.dp4")(_run(_hand_trace(),
                                                   chips=1)) is None


def test_recorded_four_chip_window():
    """The recorded window is small, comes from four chips, and holds a
    psum sync: both sync readers printed a share in (0, 100]."""
    assert (DATA / "ingest-dp4.xplane.pb.gz").stat().st_size < 2 << 20
    rec = json.loads((DATA / "ingest-dp4.json").read_text())
    assert rec["cell"] == "ipv4-zipf-cm-dp4.ingest"
    res = rec["result"]
    assert res["correct"] and res["device"]["count"] == 4
    for name in ("sync_collective_share.dp4", "psum_roofline_share.dp4"):
        assert 0 < res["metrics"][name]["value"] <= 100, name
