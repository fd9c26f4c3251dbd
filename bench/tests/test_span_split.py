"""The program's ``sketch.*`` spans beside the benchmark's: they leave every
existing reading of a trace as it was, and ``bench/span_split.py`` splits
ingest and flush time by them (on a hand-built trace whose answers are
known, and on a CPU profile of a tiny engine)."""
import types

import numpy as np
import pytest

from bench import span_split
from bench.tests.test_bench_trace import MS, _hand_trace, _metric
from bench.trace_view import TraceView

# spans the program would open inside the hand-built trace's bench spans
PROGRAM = {
    "sketch.ingest": [[0, 29], [50, 69]],
    "sketch.pool_offer": [[0, 8], [50, 55]],
    "sketch.check": [[8, 9]],
    "sketch.fold_prep": [[9, 12]],
    "sketch.fold_launch": [[12, 13]],
    "sketch.flush": [[30, 49]],
    "sketch.snapshot": [[30, 33]],
    "sketch.candidates": [[31, 32]],
    "sketch.descent_round": [[33, 48]],
    "sketch.prune": [[44, 47]],
}


def _event(name, start, end, **stats):
    return types.SimpleNamespace(name=name, start_ns=start, end_ns=end,
                                 stats=list(stats.items()))


def _profile(view, program):
    """A profile that ``TraceView.from_profile`` reduces to ``view``, with
    the host events ``program`` added (their arguments in the name)."""
    host = [_event(n, s, e) for n, iv in view.spans.items() for s, e in iv]
    host += [_event("DoEnqueueProgram", t, t + 1, run_id=r)
             for r, t in view.launches.items()]
    host += [_event(f"{n}#rows=1#", s * MS, e * MS)
             for n, iv in program.items() for s, e in iv]
    (ops, op_names), (mods, mod_names, rids) = view.ops[0], view.modules[0]
    device = types.SimpleNamespace(name="/device:TPU:0", lines=[
        types.SimpleNamespace(name="XLA Ops", events=[
            _event(n, s, e) for (s, e), n in zip(ops, op_names)]),
        types.SimpleNamespace(name="XLA Modules", events=[
            _event(n, s, e, run_id=int(r))
            for (s, e), n, r in zip(mods, mod_names, rids)])])
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name="/host:CPU", lines=[
            types.SimpleNamespace(name="python", events=host)]), device])


def _run(view):
    return types.SimpleNamespace(trace=view, chips=1, window=types.SimpleNamespace(
        rows=4096), cell=types.SimpleNamespace(config={"sketch": {
            "domains": [1, 1], "width": 4, "partition": [[0, 1]]}}),
        peaks={"hbm_bytes_per_s": 819e9})


READERS = ["device_idle_share.ingest", "ingest_host_bound_share.ingest",
           "fold_roofline_share.ingest", "flush_ms.query",
           "flush_device_share.query", "device_idle_share.query"]


def test_program_spans_leave_every_trace_reading_unchanged():
    hand = _hand_trace()
    plain = TraceView.from_profile(_profile(hand, {}), 1)
    traced = TraceView.from_profile(_profile(hand, PROGRAM), 1)
    assert set(traced.spans) == set(hand.spans)
    rids = hand.modules[0][2]
    for view in (plain, traced):
        for name in READERS:
            assert _metric(name)(_run(view)) == pytest.approx(
                _metric(name)(_run(hand))), name
        assert view.launching_span(rids) == hand.launching_span(rids)
        for got, want in ((view.idle_gaps(10), hand.idle_gaps(10)),
                          (view.top_device_ops(10), hand.top_device_ops(10))):
            assert [g[0] for g in got] == [w[0] for w in want]
            assert [g[1] for g in got] == pytest.approx([w[1] for w in want])
    got = span_split.program_spans(_profile(hand, PROGRAM))
    assert {n: (v / MS).tolist() for n, v in got.items()} == PROGRAM


def test_split_of_a_hand_built_trace():
    view = _hand_trace()
    spans = {n: np.asarray(v, float) * MS for n, v in PROGRAM.items()}
    window = types.SimpleNamespace(flush_sizes=[1], answers=[
        types.SimpleNamespace(kind="topk", k=10)])
    counters = {"sketch.blocks": 2, "sketch.pool_evicted": 7,
                "sketch.requests": 1, "sketch.descent_rounds": 5}
    out = span_split.split(view, spans, window, counters)
    # idle inside ingest: 0-10, 20-30, 50-60, 62-70 ms (38 ms)
    ing = out["ingest"]
    assert ing["blocks"] == 2 and ing["block_ms"] == pytest.approx(25.0)
    assert ing["idle_pool_share"] == pytest.approx(100 * 13 / 38)
    assert ing["idle_prep_share"] == pytest.approx(100 * 2 / 38)
    assert ing["idle_any_span_share"] == pytest.approx(100 * 36 / 38)
    assert ing["span_ms_per_block"]["sketch.pool_offer"] == pytest.approx(6.5)
    # idle inside the flush: 30-35 and 45-50 ms
    assert out["flush_idle_snapshot_share"] == pytest.approx(30.0)
    assert out["flush_idle_prune_share"] == pytest.approx(20.0)
    top = out["flush"]["top10"]
    assert top["n"] == 1 and top["flush_ms_median"] == pytest.approx(20.0)
    assert top["device_busy_ms_median"] == pytest.approx(10.0)
    assert top["span_ms_median"]["sketch.candidates"] == pytest.approx(1.0)
    assert top["span_ms_median"]["sketch.descent_round"] == pytest.approx(15)
    slow = out["slowest_flush"]
    assert slow["kind"] == "top10" and slow["ms"] == pytest.approx(20.0)
    assert slow["idle_ms_outside_spans"] == pytest.approx(1.0)
    assert out["pool_evictions_per_block"] == 3.5
    assert out["descent_rounds_per_request"] == 5.0


def test_flush_kinds_follow_the_served_order():
    a = [types.SimpleNamespace(kind=k, k=n) for k, n in
         [("heavy_hitters", 0), ("topk", 100), ("topk", 10)]]
    w = types.SimpleNamespace(flush_sizes=[2, 1], answers=a)
    assert span_split.flush_kinds(w) == ["heavy_hitters+top100", "top10"]
    w.flush_sizes = [2, 2]                   # an answer missing
    assert span_split.flush_kinds(w) == []


def test_intersect():
    a = np.array([[0, 10], [20, 30]], float)
    b = np.array([[5, 25], [28, 40]], float)
    assert span_split.intersect(a, b).tolist() == [[5, 10], [20, 25],
                                                   [28, 30]]
    assert span_split.intersect(a, np.zeros((0, 2))).shape == (0, 2)


TABLE = ["sketch.ingest", "sketch.check", "sketch.pool_offer",
         "sketch.fold_prep", "sketch.fold_launch", "sketch.flush",
         "sketch.snapshot", "sketch.candidates", "sketch.psum",
         "sketch.descent_round", "sketch.partials", "sketch.grid_readback",
         "sketch.prune"]


def test_a_cpu_profile_of_a_tiny_engine_holds_every_program_span(tmp_path):
    import jax
    from jax.profiler import ProfileData

    from bench.harness import _trace_file
    from repro.core import sketch as sk
    from repro.launch.mesh import make_mesh
    from repro.serving.sharded_topk import ShardedTopKService
    from repro.serving.sketch_engine import (SketchServeEngine,
                                             SketchTopKEndpoint)
    from repro.streams import zipf_hh_workload

    stream = zipf_hh_workload(n_src=50, n_tgt=80, n_edges=300,
                              n_occurrences=1_000, seed=2).stream
    spec = sk.mod_sketch_spec(stream.schema, [(0,), (1,)], (16, 64), 4)
    key = jax.random.PRNGKey(0)
    kernel = SketchServeEngine(SketchTopKEndpoint(
        spec, key, use_update_kernel=True), max_staleness=0)
    sharded = SketchServeEngine(ShardedTopKService(
        spec, key, make_mesh((1,), ("data",)), sync_every=None),
        shard_sync_every=1)
    items, freqs = stream.items[:200], stream.freqs[:200]
    with jax.profiler.trace(str(tmp_path)):
        kernel.ingest(items, freqs)          # the fused fold's spans
        sharded.ingest(items, freqs)         # the psum
        sharded.submit_topk(3)
        sharded.submit_heavy_hitters(5)
        sharded.flush()
    spans = span_split.program_spans(
        ProfileData.from_file(_trace_file(str(tmp_path))))
    assert set(TABLE) <= set(spans)
    for s, e in spans["sketch.candidates"]:
        assert any(a <= s and e <= b for a, b in spans["sketch.snapshot"])
