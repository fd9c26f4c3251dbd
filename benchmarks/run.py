"""Benchmark driver: one function per paper table/figure + system benches.

Prints ``name,us_per_call,derived`` CSV (see benchmarks.common.emit) and
writes the same rows as a ``BENCH_*.json`` artifact (``--json-out``) so CI
can archive the perf trajectory run over run.
Usage: PYTHONPATH=src python -m benchmarks.run [--only substring]
"""
from __future__ import annotations

import argparse
import json
import platform
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="run benchmarks whose name contains this substring")
    ap.add_argument("--json-out", default="BENCH_RESULTS.json",
                    help="path of the JSON artifact (BENCH_*.json pattern); "
                         "'' disables")
    args = ap.parse_args()

    import jax

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import bounds_check, common, grad_compression_bench, \
        hierarchy_ingest_bench, kernel_microbench, migrate_bench, \
        paper_figs, recovery_bench, roofline_report, serve_bench, \
        sharded_topk_bench, window_bench
    benches = (paper_figs.ALL + bounds_check.ALL + kernel_microbench.ALL
               + roofline_report.ALL + sharded_topk_bench.ALL
               + hierarchy_ingest_bench.ALL + window_bench.ALL
               + migrate_bench.ALL + serve_bench.ALL
               + grad_compression_bench.ALL + recovery_bench.ALL)
    print("name,us_per_call,derived")
    t_start = time.time()
    failures = []
    for fn in benches:
        if args.only and args.only not in fn.__name__:
            continue
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:
            failures.append(fn.__name__)
            print(f"{fn.__name__},-1,ERROR", flush=True)
            traceback.print_exc()
        print(f"# {fn.__name__} finished in {time.perf_counter()-t0:.1f}s",
              file=sys.stderr, flush=True)

    if args.json_out:
        artifact = {
            "started_unix": t_start,
            "wall_s": time.time() - t_start,
            "backend": jax.default_backend(),
            "platform": platform.platform(),
            "python": platform.python_version(),
            "jax": jax.__version__,
            "only": args.only,
            "failures": failures,
            "results": common.rows_as_records(),
        }
        with open(args.json_out, "w") as f:
            json.dump(artifact, f, indent=2)
        print(f"# wrote {args.json_out} ({len(artifact['results'])} rows)",
              file=sys.stderr, flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
