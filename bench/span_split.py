#!/usr/bin/env python3
"""Where the host time of ingest and flush goes: the program's own spans.

The program opens ``sketch.*`` host spans at each layer boundary of the
served path and keeps counters (``repro.obs``; docs/architecture.md,
"Observability").  In a traced window the spans land on the host plane of
the profiler's trace, on the clock of the device operations and of the
benchmark's ``bench.*`` spans, so each stretch in which the chip sat idle
inside a ``bench.ingest`` or ``bench.flush`` span can be put down to what
the program was doing.  ``TraceView`` keeps the ``bench.*`` spans only;
the functions here read the program's beside it.

A diagnostic run by hand on the chip; no cell runs it:

    python3 bench/span_split.py --workload <cell> --seed <n> --seconds <s> \\
        --windows <k> [--out <file.jsonl>]

It sets the cell up as a run does, then takes ``k`` traced windows in one
process, one after another on the same stream, and prints one JSON line
per window: the window's end-to-end numbers, the counters it moved, the
milliseconds each program span takes per ingest block and per flush of
each request kind, the shares of device-idle time under each span, and
the slowest flush span by span.  Exits non-zero without a TPU.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from collections import defaultdict  # noqa: E402
from typing import Dict, Iterable, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))

from bench.trace_view import intersect_len, union  # noqa: E402

PREFIX = "sketch."


def program_spans(profile) -> Dict[str, np.ndarray]:
    """``[start, end)`` rows (ns) of every host event named ``sketch.*``,
    by name; arguments written into a name (``name#k=v#``) are dropped."""
    spans: Dict[str, list] = defaultdict(list)
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    spans[e.name.split("#")[0]].append((e.start_ns, e.end_ns))
    return {k: np.asarray(v, np.float64) for k, v in spans.items()}


def covers(a: np.ndarray, t: np.ndarray) -> np.ndarray:
    """Whether each time in ``t`` lies in the disjoint sorted set ``a``."""
    if not len(a):
        return np.zeros(np.shape(t), bool)
    i = np.searchsorted(a[:, 0], t, side="right") - 1
    return (i >= 0) & (t < a[np.maximum(i, 0), 1])


def intersect(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """The disjoint sorted set common to two disjoint sorted sets."""
    edges = np.unique(np.concatenate([a.reshape(-1), b.reshape(-1)]))
    if len(edges) < 2:
        return np.zeros((0, 2))
    mid = 0.5 * (edges[:-1] + edges[1:])
    keep = covers(a, mid) & covers(b, mid)
    return union(np.stack([edges[:-1][keep], edges[1:][keep]], axis=1))


def length(iv: np.ndarray) -> float:
    return float(np.sum(iv[:, 1] - iv[:, 0])) if len(iv) else 0.0


def under(spans: Dict[str, np.ndarray], names: Iterable[str]) -> np.ndarray:
    """The union of the program spans named ``names``."""
    rows = [spans[n] for n in names if n in spans]
    return union(np.concatenate(rows)) if rows else np.zeros((0, 2))


def idle_under(busy: np.ndarray, within: np.ndarray,
               spans: Dict[str, np.ndarray], names: Iterable[str]):
    """Nanoseconds of ``within`` (disjoint sorted intervals) outside
    ``busy`` (the chip's busy intervals), and of those, the part under the
    program spans ``names``."""
    cover = intersect(within, under(spans, names))
    return (length(within) - intersect_len(within, busy),
            length(cover) - intersect_len(cover, busy))


def idle_share(view, bench_span: str, spans: Dict[str, np.ndarray],
               names: Iterable[str]) -> Optional[float]:
    """Share (%) of chip 0's idle time inside the window's ``bench_span``
    spans that lies under the program spans ``names``; None where the chip
    never idles there."""
    idle, part = idle_under(view.busy(0), view.span_intervals(bench_span),
                            spans, names)
    return 100.0 * part / idle if idle > 0 else None


def ms_inside(iv: np.ndarray, spans: Dict[str, np.ndarray]) -> Dict[str, float]:
    """Milliseconds of each program span (a union per name) inside the
    disjoint sorted intervals ``iv``."""
    out = {n: length(intersect(iv, s)) * 1e-6 for n, s in spans.items()}
    return {n: v for n, v in out.items() if v > 0}


def _kind(answer) -> str:
    return f"top{answer.k}" if answer.kind == "topk" else "heavy_hitters"


def flush_kinds(window) -> List[str]:
    """The request kinds each flush of the window served, in order ("+"
    between kinds); empty when an answer is missing."""
    if sum(window.flush_sizes) != len(window.answers):
        return []
    kinds, i = [], 0
    for n in window.flush_sizes:
        kinds.append("+".join(sorted({_kind(a)
                                      for a in window.answers[i:i + n]})))
        i += n
    return kinds


def split(view, spans: Dict[str, np.ndarray], window, counters: dict) -> dict:
    """One window's reduction (see the module docstring)."""
    spans = {n: union(s) for n, s in sorted(spans.items())}
    busy = view.busy(0)
    out: dict = {"counters": counters}
    ingest = view.span_intervals("bench.ingest")
    n_blocks = len(view.span_durations_s("bench.ingest"))
    if n_blocks:
        out["ingest"] = {
            "blocks": n_blocks,
            "block_ms": length(ingest) * 1e-6 / n_blocks,
            "span_ms_per_block": {n: v / n_blocks for n, v in
                                  ms_inside(ingest, spans).items()},
            "idle_pool_share": idle_share(view, "bench.ingest", spans,
                                          ["sketch.pool_offer"]),
            "idle_prep_share": idle_share(view, "bench.ingest", spans,
                                          ["sketch.check",
                                           "sketch.fold_prep"]),
            "idle_any_span_share": idle_share(view, "bench.ingest", spans,
                                              list(spans))}
    if len(view.span_intervals("bench.flush")):
        out["flush_idle_snapshot_share"] = idle_share(
            view, "bench.flush", spans, ["sketch.snapshot"])
        out["flush_idle_prune_share"] = idle_share(
            view, "bench.flush", spans, ["sketch.prune"])
    # every flush, the ones served after the window closed too, in order
    flushes = view.spans.get("bench.flush", np.zeros((0, 2)))
    flushes = flushes[np.argsort(flushes[:, 0])]
    kinds = flush_kinds(window)
    if len(flushes) and len(kinds) == len(flushes):
        per = [ms_inside(iv[None], spans) for iv in flushes]
        busy_ms = [intersect_len(iv[None], busy) * 1e-6 for iv in flushes]
        out["flush"] = {}
        for kind in sorted(set(kinds)):
            sel = [i for i, k in enumerate(kinds) if k == kind]
            d = flushes[sel, 1] - flushes[sel, 0]
            out["flush"][kind] = {
                "n": len(sel),
                "flush_ms_median": float(np.median(d)) * 1e-6,
                "span_ms_median": {n: float(np.median([per[i].get(n, 0.0)
                                                       for i in sel]))
                                   for n in spans
                                   if any(n in per[i] for i in sel)},
                "device_busy_ms_median": float(np.median(
                    [busy_ms[i] for i in sel]))}
        slow = int(np.argmax(flushes[:, 1] - flushes[:, 0]))
        idle, in_spans = idle_under(busy, flushes[slow][None], spans,
                                    list(spans))
        out["slowest_flush"] = {
            "kind": kinds[slow], "requests": window.flush_sizes[slow],
            "ms": float(flushes[slow, 1] - flushes[slow, 0]) * 1e-6,
            "span_ms": per[slow], "device_busy_ms": busy_ms[slow],
            "idle_ms_outside_spans": (idle - in_spans) * 1e-6}
    blocks, reqs = (counters.get("sketch.blocks"),
                    counters.get("sketch.requests"))
    if blocks:
        out["pool_evictions_per_block"] = (
            counters.get("sketch.pool_evicted", 0) / blocks)
    if reqs:
        out["descent_rounds_per_request"] = (
            counters.get("sketch.descent_rounds", 0) / reqs)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--windows", type=int, default=1)
    ap.add_argument("--out", default=None,
                    help="also append each window's line to this file")
    args = ap.parse_args(argv)

    import jax
    from jax.profiler import ProfileData

    from bench import catalog, harness, loop, reference, stream_gen
    from bench.served import Served
    from bench.trace_view import TraceView
    from bench.traffic.generator import make_schedule
    from repro import obs

    cat = catalog.Catalog(ROOT)
    cell = cat.cell(args.workload)
    try:
        devices = harness.chips_or_fail(cell.chips)
    except harness.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 2
    cfg = cell.config
    sketch = cfg["sketch"]
    harness.use_compile_cache(cat.root)
    stream = stream_gen.make_stream(cfg["stream"], args.seed,
                                    cat.dir / "streams")
    q, r = reference.draw_hash_params(
        stream_gen.rng_for(args.seed, 2), int(sketch["width"]),
        reference.total_chunks(sketch), len(sketch["partition"]))
    server = Served(cfg, args.seed, devices, q, r)
    schedule = make_schedule(cell.traffic, stream_gen.rng_for(args.seed, 3),
                             args.seconds, stream.block_rows)
    block = harness.warm_up(server, stream, schedule,
                            int(cfg["service"]["pool_capacity"]))
    print(f"set up in {time.perf_counter() - T_START:.3f} s", flush=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    for w in range(args.windows):
        trace_dir = tempfile.mkdtemp(prefix="span-split-")
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        before = obs.counters()
        window = loop.run_window(server, stream, schedule, args.seconds,
                                 block, jax.profiler.TraceAnnotation)
        after = obs.counters()
        jax.profiler.stop_trace()
        block += window.rows // stream.block_rows
        profile = ProfileData.from_file(harness._trace_file(trace_dir))
        view = TraceView.from_profile(profile, cell.chips)
        spans = program_spans(profile)
        counters = {k: v - before.get(k, 0) for k, v in sorted(after.items())
                    if v != before.get(k, 0)}
        line = {"workload": cell.name, "seed": args.seed, "window": w,
                "rows_per_s": (window.rows / window.elapsed_s
                               if window.rows else None),
                "query_p50_ms": (loop.percentile(window.latencies_s, 50) * 1e3
                                 if window.latencies_s else None),
                "query_p95_ms": (loop.percentile(window.latencies_s, 95) * 1e3
                                 if window.latencies_s else None),
                "flush_ms_max": float(np.max(view.span_durations_s(
                    "bench.flush")) * 1e3) if len(view.span_durations_s(
                        "bench.flush")) else None,
                "device_idle_share": 100.0 * (1 - view.busy_s()
                                              / view.window_s),
                **split(view, spans, window, counters)}
        del profile, view, spans
        shutil.rmtree(trace_dir, ignore_errors=True)
        text = json.dumps(line)
        print(text, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "a") as f:
                f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
