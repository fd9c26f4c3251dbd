"""The reduction from traces to per-layer metrics and ``breakdown``: on a
hand-built trace whose answers are known, and on small traces recorded on
a TPU v5e (bench/tests/data), against the numbers the chip run printed."""
import gzip
import json
import types
from pathlib import Path

import numpy as np
import pytest

from bench import catalog
from bench.trace_view import TraceView, intersect_len, union

DATA = Path(__file__).resolve().parent / "data"
MS = 1e6                                   # ns per ms


def _metric(name):
    return catalog.load_reader(catalog.Metric(
        name, "%", "lower", "device_trace",
        catalog.BENCH_DIR / "metrics" / f"{name}.py"))


def _hand_trace():
    """A 100 ms window: ingest spans 0-30 and 50-70 ms, a flush 30-50 ms.
    Device ops: 10-20 ms (fold, launched by the first ingest), 35-45 ms
    (gather, launched by the flush), an all-reduce 60-62 ms."""
    spans = {"bench.window": np.array([[0, 100 * MS]]),
             "bench.ingest": np.array([[0, 30 * MS], [50 * MS, 70 * MS]]),
             "bench.flush": np.array([[30 * MS, 50 * MS]])}
    ops = [(np.array([[10 * MS, 20 * MS], [35 * MS, 45 * MS],
                      [60 * MS, 62 * MS]]),
            ["%fusion.1 = fold", "%gather.2 = g", "%all-reduce.3 = ar"])]
    modules = [(np.array([[10 * MS, 20 * MS], [35 * MS, 45 * MS],
                          [60 * MS, 62 * MS]]),
                ["jit_fold(1)", "jit_query(2)", "jit_merge(3)"],
                np.array([7, 8, 9]))]
    launches = {7: 1 * MS, 8: 31 * MS, 9: 51 * MS}
    return TraceView(spans, ops, modules, launches)


def test_intervals():
    u = union(np.array([[5, 7], [0, 2], [1, 3], [6, 9]], float))
    assert u.tolist() == [[0, 3], [5, 9]]
    assert intersect_len(u, np.array([[2, 6]], float)) == 2.0


def test_reduction_of_a_hand_built_trace():
    t = _hand_trace()
    run = types.SimpleNamespace(trace=t, chips=2, window=types.SimpleNamespace(
        rows=4096), cell=types.SimpleNamespace(config={"sketch": {
            "domains": [1, 1], "width": 4, "partition": [[0, 1]]}}),
        peaks={"hbm_bytes_per_s": 819e9})
    assert t.window_s == pytest.approx(0.1)
    assert t.busy_s() == pytest.approx(0.022)
    assert _metric("device_idle_share.ingest")(run) == pytest.approx(78.0)
    # ingest spans last 50 ms; the device runs 12 ms of them
    assert _metric("ingest_host_bound_share.ingest")(run) == pytest.approx(
        100 * 38 / 50)
    assert _metric("flush_device_share.query")(run) == pytest.approx(50.0)
    assert _metric("flush_ms.query")(run) == pytest.approx(20.0)
    # programs launched from ingest spans: the fold and the merge, 12 ms
    assert t.program_seconds("bench.ingest") == pytest.approx(0.012)
    fold = _metric("fold_roofline_share.ingest")
    need = 4096 * (4 * 2 + 4 + 8 * 4 * 1)
    assert fold(run) == pytest.approx(100 * need / (0.012 * 819e9))
    ops = dict(map(tuple, t.top_device_ops()))
    assert ops == pytest.approx({"jit_fold/fusion": 0.010,
                                 "jit_query/gather": 0.010,
                                 "jit_merge/all-reduce": 0.002})
    gaps = t.idle_gaps(3)
    # 62-100 ms (no span open), 20-35 and 45-60 ms (inside ingest spans)
    assert [g[0] for g in gaps] == ["bench.loop", "bench.ingest",
                                    "bench.ingest"]
    assert [g[1] for g in gaps] == pytest.approx([0.038, 0.015, 0.015])


def test_fold_bytes_per_row():
    mod = _metric("fold_roofline_share.ingest").__globals__
    assert mod["fold_bytes"](4096, 2, 4, 1) == 4096 * 44
    assert mod["fold_bytes"](1, 2, 4, 2) == 8 + 4 + 64


PER_LAYER = [m["name"] for m in json.loads(
    (catalog.BENCH_DIR.parent / "BENCHMARK.json").read_text())["per_layer"]]


@pytest.mark.parametrize("name", PER_LAYER)
def test_readers_find_nothing_in_an_untraced_run(name):
    run = types.SimpleNamespace(trace=None, chips=1)
    assert _metric(name)(run) is None


RECORDED = sorted(DATA.glob("*.json"))


@pytest.mark.parametrize("record", RECORDED, ids=lambda p: p.stem)
def test_recorded_chip_trace_reduces_to_the_printed_numbers(record):
    from jax.profiler import ProfileData

    rec = json.loads(record.read_text())
    raw = gzip.decompress((DATA / f"{record.stem}.xplane.pb.gz").read_bytes())
    res = rec["result"]
    chips = res["device"]["count"]
    view = TraceView.from_profile(ProfileData.from_serialized_xspace(raw),
                                  chips)
    assert view.busy_s() == pytest.approx(res["device"]["busy_s"])
    assert view.window_s == pytest.approx(res["device"]["window_s"])
    assert 0 < view.busy_s() <= view.window_s
    config = json.loads((catalog.BENCH_DIR / "configs" / "{}.json".format(
        rec["cell"].rsplit(".", 1)[0])).read_text())
    run = types.SimpleNamespace(
        cell=types.SimpleNamespace(config=config), chips=chips, trace=view,
        peaks=catalog.Catalog(catalog.BENCH_DIR.parent).peaks(
            res["device"]["kind"]),
        window=types.SimpleNamespace(rows=rec["rows"]))
    metrics = [catalog.Metric(name, m["unit"], "", "device_trace",
                              catalog.BENCH_DIR / "metrics" / f"{name}.py")
               for name, m in res["metrics"].items()]
    got = catalog.read_metrics(metrics, run)
    assert set(got) == set(res["metrics"])
    for name, m in res["metrics"].items():
        assert got[name]["value"] == pytest.approx(m["value"]), name
        if name.endswith("_roofline_share.ingest"):
            assert 0 < m["value"] <= 100
    for key, got in (("device_ops", view.top_device_ops(10)),
                     ("idle_gaps", view.idle_gaps(10))):
        want = res["breakdown"][key]
        assert [g[0] for g in got] == [w[0] for w in want]
        assert [g[1] for g in got] == pytest.approx([w[1] for w in want])
