"""The server loop: one thread that offers blocks and requests on schedule.

Each turn of the loop hands every request that has come due (up to the
mix's ``max_flush_requests``) to one ``flush``, then offers one block if a
block is due, and sleeps until the next arrival when neither was due.  So
the loop flushes whatever is queued between blocks, and a slow system
receives no less load: every latency counts from the request's due time,
and how late each block was offered is reported.

Spans: ``bench.window`` (window open to device drained), ``bench.ingest``,
``bench.flush``, ``bench.drain`` and ``bench.idle`` -- profiler annotations,
inert unless a trace is being taken.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable, List

import numpy as np

from bench.reference import Answer
from bench.traffic.generator import Schedule

GRACE_S = 60.0          # how long past the close a due request is waited for


@dataclasses.dataclass
class WindowResult:
    seconds: float              # requested window length
    start_rows: int             # rows acknowledged before the window opened
    rows: int                   # rows ingested in the window
    elapsed_s: float            # window open -> device drained
    latencies_s: List[float]    # one per answered request due in the window
    answers: List[Answer]
    attempted: int              # requests due in the window
    failed: int                 # of those, never answered
    mean_block_lateness_s: float  # rate mode: offer time - due time
    max_block_lateness_s: float
    flush_sizes: List[int]

    @property
    def end_rows(self) -> int:
        return self.start_rows + self.rows


def percentile(values, p: float) -> float:
    """The ``p``-th percentile, linear between order statistics."""
    return float(np.percentile(np.asarray(values, np.float64), p))


def _threshold(phi: float, mass: int) -> int:
    return max(1, math.ceil(phi * mass))


def run_window(server, stream, schedule: Schedule, seconds: float,
               start_block: int, span: Callable,
               clock=time.perf_counter, sleep=time.sleep) -> WindowResult:
    reqs = schedule.requests
    n_req = len(reqs)
    block = start_block
    rows = lambda: block * stream.block_rows
    answers: List[Answer] = []
    latencies: List[float] = []
    flush_sizes: List[int] = []
    lateness: List[float] = []
    nxt = 0                               # next request not yet due
    pending: List[int] = []

    def serve(batch: List[int], t_open: float) -> None:
        at_rows = rows()
        mass = stream.rows_mass(at_rows)
        with span("bench.flush"):
            handles = [server.submit(reqs[i].kind, reqs[i].k,
                                     _threshold(reqs[i].phi, mass))
                       for i in batch]
            server.flush()
            t_done = clock()
        flush_sizes.append(len(batch))
        for i, h in zip(batch, handles):
            got = server.result(h)
            if got is None:
                continue
            latencies.append(t_done - (t_open + reqs[i].due))
            answers.append(Answer(kind=reqs[i].kind, k=reqs[i].k,
                                  threshold=_threshold(reqs[i].phi, mass),
                                  rows=at_rows, items=got[0], est=got[1]))

    with span("bench.window"):
        t_open = clock()
        t_close = t_open + seconds
        while True:
            now = clock()
            if now >= t_close:
                break
            while nxt < n_req and t_open + reqs[nxt].due <= now:
                pending.append(nxt)
                nxt += 1
            acted = False
            if pending:
                batch, pending = (pending[: schedule.max_flush],
                                  pending[schedule.max_flush:])
                serve(batch, t_open)
                acted = True
            due = t_open + schedule.block_due(block - start_block)
            now = clock()
            if due <= now and now < t_close:
                if schedule.rows_per_s is not None:
                    lateness.append(now - due)
                with span("bench.ingest"):
                    server.ingest(*stream.block(block))
                block += 1
                acted = True
            if not acted:
                wake = min(due, t_close)
                if nxt < n_req:
                    wake = min(wake, t_open + reqs[nxt].due)
                with span("bench.idle"):
                    sleep(max(0.0, wake - clock()))
        with span("bench.drain"):
            server.drain()
        t_drained = clock()
    # requests due before the close and not yet served: answered late
    while nxt < n_req and reqs[nxt].due < seconds:
        pending.append(nxt)
        nxt += 1
    while pending and clock() < t_close + GRACE_S:
        batch, pending = (pending[: schedule.max_flush],
                          pending[schedule.max_flush:])
        serve(batch, t_open)
    attempted = sum(1 for r in reqs if r.due < seconds)
    return WindowResult(
        seconds=seconds, start_rows=start_block * stream.block_rows,
        rows=(block - start_block) * stream.block_rows,
        elapsed_s=t_drained - t_open, latencies_s=latencies, answers=answers,
        attempted=attempted, failed=attempted - len(latencies),
        mean_block_lateness_s=float(np.mean(lateness)) if lateness else 0.0,
        max_block_lateness_s=max(lateness, default=0.0),
        flush_sizes=flush_sizes)
