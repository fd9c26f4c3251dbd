#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, configurations, traffic mixes and metrics are named in
``BENCHMARK.json`` at the root of the checkout and found as files under
``bench/`` (see bench/catalog.py).  The last line of standard output is the
result object; the numbers compared with the reference are the last lines
of standard error.  Exits non-zero, with no result, when JAX finds no TPU
or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import catalog, harness

    cat = catalog.Catalog(ROOT)
    cell = cat.cell(args.workload)
    try:
        devices = harness.chips_or_fail(cell.chips)
    except harness.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 2
    dev = devices[0]
    peaks = cat.peaks(dev.device_kind)
    print(f"workload {cell.name}, seed {args.seed}, {args.seconds} s, trace "
          f"{args.trace}; {len(devices)} x {dev.platform} {dev.device_kind}",
          flush=True)
    out = harness.run_cell(cat, cell, args.seed, args.seconds,
                           bool(args.trace), devices, peaks, T_START,
                           log=lambda m: print(m, flush=True))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
