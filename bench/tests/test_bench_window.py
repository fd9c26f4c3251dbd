"""The server loop's arithmetic on a fake clock: rates over the whole
window, latency from each request's due time, unanswered requests counted
as failed."""
import contextlib

import numpy as np
import pytest

from bench import catalog, loop, stream_gen
from bench.traffic.generator import Request, Schedule

BLOCK = 100


class Clock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += max(dt, 1e-6)


class FakeServer:
    """Ingest takes ``ingest_s``, a flush ``flush_s``, the drain
    ``drain_s``; requests of kind ``lost`` are never answered."""

    def __init__(self, clock, ingest_s=0.01, flush_s=0.05, drain_s=0.2,
                 lost=None):
        self.clock, self.ingest_s, self.flush_s = clock, ingest_s, flush_s
        self.drain_s, self.lost = drain_s, lost
        self.rows, self.queue, self.done = 0, [], {}

    def ingest(self, items, freqs):
        self.rows += items.shape[0]
        self.clock.t += self.ingest_s

    def submit(self, kind, k, threshold):
        self.queue.append((len(self.done) + len(self.queue), kind))
        return self.queue[-1][0]

    def flush(self):
        self.clock.t += self.flush_s
        for h, kind in self.queue:
            if kind != self.lost:
                self.done[h] = (np.zeros((0, 2), np.uint32),
                                np.zeros(0, np.int64))
        self.queue = []

    def result(self, h):
        return self.done.get(h)

    def drain(self):
        self.clock.t += self.drain_s


def _stream():
    items = np.arange(2 * 1000, dtype=np.uint32).reshape(1000, 2)
    return stream_gen.KeyStream(items=items, freqs=np.ones(1000, np.int64),
                                block_rows=BLOCK)


def _run(server, clock, schedule, seconds):
    return loop.run_window(server, _stream(), schedule, seconds, 3,
                           lambda name: contextlib.nullcontext(),
                           clock=clock, sleep=clock.sleep)


def test_backlogged_rate_counts_every_row_and_the_drain():
    clock = Clock()
    server = FakeServer(clock)
    w = _run(server, clock, Schedule(None, BLOCK, (), 2), 1.0)
    blocks = w.rows // BLOCK
    assert w.rows == server.rows and blocks in (100, 101)
    assert w.elapsed_s == pytest.approx(blocks * 0.01 + 0.2)
    assert w.start_rows == 3 * BLOCK and w.end_rows == w.start_rows + w.rows
    run = type("Run", (), {"window": w})
    rate = catalog.load_reader(catalog.Metric(
        "ingest_rows_per_s", "rows/s", "higher", "host_clock",
        catalog.BENCH_DIR / "metrics" / "ingest_rows_per_s.py"))(run)
    assert rate == pytest.approx(w.rows / (blocks * 0.01 + 0.2))


def test_latency_runs_from_the_due_time_and_late_requests_are_served():
    clock = Clock()
    server = FakeServer(clock, ingest_s=0.3)
    reqs = (Request(0.05, "topk", k=10), Request(0.95, "topk", k=10))
    w = _run(server, clock, Schedule(None, BLOCK, reqs, 2), 1.0)
    # first: due 0.05, seen after the first block (0.3), flushed by 0.35;
    # second: due 0.95, seen after the block ending at 1.25 -- the window
    # has closed, it is served after the drain (1.25 + 0.2 + 0.05)
    assert w.latencies_s == pytest.approx([0.35 - 0.05, 1.5 - 0.95])
    assert w.attempted == 2 and w.failed == 0
    run = type("Run", (), {"window": w})
    p50 = catalog.load_reader(catalog.Metric(
        "query_p50_ms", "ms", "lower", "host_clock",
        catalog.BENCH_DIR / "metrics" / "query_p50_ms.py"))(run)
    assert p50 == pytest.approx(1e3 * (0.30 + 0.55) / 2)


def test_unanswered_requests_are_failed_and_open_loop_blocks_wait():
    clock = Clock()
    server = FakeServer(clock, ingest_s=0.001, lost="heavy_hitters")
    reqs = (Request(0.2, "topk", k=5), Request(0.2, "heavy_hitters", phi=0.1),
            Request(0.9, "topk", k=5), Request(1.5, "topk", k=5))
    w = _run(server, clock, Schedule(500.0, BLOCK, reqs, 8), 1.0)
    assert w.attempted == 3                   # the one due at 1.5 is outside
    assert w.failed == 1 and len(w.latencies_s) == 2
    assert w.rows == 5 * BLOCK                # due at 0, .2, .4, .6, .8
    assert 0 < w.mean_block_lateness_s <= w.max_block_lateness_s < 0.06
    assert [a.kind for a in w.answers] == ["topk", "topk"]


def test_percentile_is_linear_between_order_statistics():
    assert loop.percentile([4, 1, 3, 2], 50) == 2.5
    assert loop.percentile(list(range(101)), 95) == 95.0
