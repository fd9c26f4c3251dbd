"""One run of one cell: set-up, the measured window, the check, the result.

Set-up builds the stream and the served system from the seed, ingests the
first blocks until every candidate pool is full (or a whole pass of the
stream is in), and warms every shape the window will meet: one flush of
each size from 1 to ``max_flush_requests`` and one flush of every request
kind in the mix.  Then the window runs (``loop.run_window``); with
``trace`` on, the profiler records it.  After the window closes the device
memory peak is read, the served tables are taken, the system is dropped,
and the reference checks the tables and every answer.
"""
from __future__ import annotations

import dataclasses
import gc
import glob
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Optional

import numpy as np

from bench import catalog, loop, reference, stream_gen
from bench.traffic.generator import make_schedule

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
HUGE_THRESHOLD = 1 << 62       # a heavy-hitter threshold nothing reaches


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Run:
    """What a metric reader reads."""
    cell: catalog.Cell
    seed: int
    chips: int
    setup_s: float
    window: loop.WindowResult
    trace: Optional[object]        # trace_view.TraceView in a traced run
    peaks: dict


class CompileCounter:
    """Counts the programs JAX compiles (or loads from its cache)."""

    def __init__(self):
        self.count = 0

    def __call__(self, event: str, duration: float, **kwargs) -> None:
        if event == COMPILE_EVENT:
            self.count += 1


def chips_or_fail(chips: int):
    """The first ``chips`` accelerator devices; raises NoChip otherwise."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devices)}")
    return devices


def use_compile_cache(root: Path) -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` when set (JAX
    reads it itself), else ``<checkout>/.jax_cache``; every program is
    cached, however quick to compile."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(root).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def warm_up(server, stream, schedule, capacity: int) -> int:
    """Fill the pools and meet every shape of the window; returns the next
    block to ingest."""
    per_pass = -(-stream.n // stream.block_rows)
    block = 0
    while block < per_pass:
        server.ingest(*stream.block(block))
        block += 1
        if block % 8 == 0 and min(server.pool_sizes()) >= capacity:
            break
    for q in range(1, schedule.max_flush + 1):
        for _ in range(q):
            server.submit("heavy_hitters", 0, HUGE_THRESHOLD)
        server.flush()
    mass = stream.rows_mass(block * stream.block_rows)
    for kind, k, phi in schedule.templates()[: schedule.max_flush]:
        server.submit(kind, k, max(1, int(np.ceil(phi * mass))))
    server.flush()
    server.ingest(*stream.block(block))
    server.drain()
    return block + 1


def _trace_file(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"expected one trace file, found {found}")
    return found[0]


def run_cell(cat: catalog.Catalog, cell: catalog.Cell, seed: int,
             seconds: float, trace: bool, devices, peaks: dict,
             t_start: float, make_server: Optional[Callable] = None,
             log: Callable = print) -> dict:
    """One run; returns the result object (the last line's JSON)."""
    import jax

    from bench.trace_view import TraceView

    cfg = cell.config
    sketch = cfg["sketch"]
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    try:
        cache = use_compile_cache(cat.root)
        t = time.perf_counter()
        stream = stream_gen.make_stream(cfg["stream"], seed,
                                        cat.dir / "streams")
        log(f"stream: {stream.n} distinct keys, mass {stream.mass}, max "
            f"weight {int(stream.freqs.max())} ({time.perf_counter() - t:.3f} s)")
        q, r = reference.draw_hash_params(
            stream_gen.rng_for(seed, 2), int(sketch["width"]),
            reference.total_chunks(sketch), len(sketch["partition"]))
        if make_server is None:
            from bench.served import Served
            make_server = Served
        t = time.perf_counter()
        server = make_server(cfg, seed, devices, q, r)
        log(f"system built ({time.perf_counter() - t:.3f} s)")
        schedule = make_schedule(cell.traffic, stream_gen.rng_for(seed, 3),
                                 seconds, stream.block_rows)
        t = time.perf_counter()
        start_block = warm_up(server, stream, schedule,
                              int(cfg["service"]["pool_capacity"]))
        log(f"warm-up: {start_block} blocks, {counter.count} programs "
            f"compiled or loaded, cache {cache} "
            f"({time.perf_counter() - t:.3f} s)")
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        setup_s = time.perf_counter() - t_start
        before = counter.count
        window = loop.run_window(server, stream, schedule, seconds,
                                 start_block, jax.profiler.TraceAnnotation)
        in_window = counter.count - before
        if trace:
            jax.profiler.stop_trace()
    finally:
        jax.monitoring.unregister_event_duration_listener(counter)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices[: cell.chips])
    log(f"compilations in the window: {in_window}")
    log(f"requests due in the window: {window.attempted}, answered "
        f"{len(window.latencies_s)}; flushes {len(window.flush_sizes)} "
        f"(largest {max(window.flush_sizes, default=0)})")
    log(f"rows in the window: {window.rows} in {window.elapsed_s:.6f} s; "
        f"blocks offered {window.mean_block_lateness_s:.6f} s after their "
        f"due time on average, {window.max_block_lateness_s:.6f} s at most")
    tables = server.tables()
    del server
    gc.collect()

    view = None
    if trace:
        t = time.perf_counter()
        view = TraceView.from_file(_trace_file(trace_dir), cell.chips)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace read ({time.perf_counter() - t:.3f} s)")
    run = Run(cell=cell, seed=seed, chips=cell.chips, setup_s=setup_s,
              window=window, trace=view, peaks=peaks)
    metrics = catalog.read_metrics(cell.per_layer if trace
                                   else cell.end_to_end, run)

    t = time.perf_counter()
    ref = reference.Reference(stream.items, stream.freqs, sketch, q, r)
    checks = reference.check_answers(ref, window.answers,
                                     int(cfg["service"]["pool_capacity"]))
    checks["table_cells_wrong"] = reference.check_tables(
        ref, window.end_rows, tables, int(cfg["guarantees"]["replicas"]))
    checks["unanswered"] = window.failed
    log(f"reference check ({time.perf_counter() - t:.3f} s)")
    numbers = {k: {"value": checks[k], "limit": reference.LIMITS[k]}
               for k in reference.LIMITS}
    correct = all(v["value"] <= v["limit"] for v in numbers.values())
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": window.attempted,
           "failed": window.failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = view.busy_s()
        device["window_s"] = view.window_s
        out["breakdown"] = {"device_ops": view.top_device_ops(10),
                            "idle_gaps": view.idle_gaps(10)}
    out["checks"] = numbers
    for k, v in numbers.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    return out
