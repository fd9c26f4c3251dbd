#!/usr/bin/env python3
"""The control: the plain reference in the program's place, in int16.

The configurations state int32 exact counts.  The control serves the same
cell with the reference's own tables one integer width lower (int16, which
wraps), the step that would tempt a later change to halve the fold's bytes.
The comparison has to find it out: ``correct`` must come out false.  The
benchmark's own runs never run it.

    python3 bench/control.py --workload <cell> --seed <n> --seconds <s>

prints the result line of each run, as bench/run.py does.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT

from bench import reference  # noqa: E402


class ReferenceServer:
    """The reference semantics served through the harness's interface:
    every seen key is a candidate, estimates are the finest level's
    Count-Min minimum, tables are kept in ``dtype``."""

    def __init__(self, config: dict, seed: int, devices, q, r,
                 dtype=np.int16):
        self.sketch, self.q, self.r = config["sketch"], q, r
        self.replicas = int(config["guarantees"]["replicas"])
        self.capacity = int(config["service"]["pool_capacity"])
        ranges = [int(x) for x in self.sketch["ranges"]]
        w = int(self.sketch["width"])
        self._tables = [np.zeros((w, int(np.prod(ranges[: L + 1]))), dtype)
                       for L in range(len(ranges))]
        self._seen = [np.zeros((0, len(self.sketch["domains"])), np.uint32)]
        self._queue = []
        self._done = {}

    def ingest(self, items, freqs) -> None:
        cells = reference.level_cells(items, self.sketch, self.q, self.r)
        for table, c in zip(self._tables, cells):
            for k in range(table.shape[0]):
                np.add.at(table[k], c[k], freqs.astype(table.dtype))
        self._seen.append(np.asarray(items, np.uint32))

    def submit(self, kind: str, k: int, threshold: int):
        handle = len(self._done) + len(self._queue)
        self._queue.append((handle, kind, k, threshold))
        return handle

    def flush(self) -> None:
        if not self._queue:
            return
        seen = np.concatenate(self._seen)
        _, first = np.unique(
            reference.pack_keys(seen, self.sketch["domains"]),
            return_index=True)
        cand = seen[first]
        self._seen = [cand]
        cells = reference.level_cells(cand, self.sketch, self.q, self.r)[-1]
        t = self._tables[-1]
        est = np.min(np.stack([t[k][cells[k]] for k in range(t.shape[0])]),
                     axis=0).astype(np.int64)
        order = np.argsort(-est, kind="stable")
        for handle, kind, k, thr in self._queue:
            if kind == "topk":
                keep = order[:k]
            else:
                keep = order[est[order] >= thr]
            self._done[handle] = (cand[keep], est[keep])
        self._queue = []

    def result(self, handle):
        return self._done.get(handle)

    def pool_sizes(self):
        return [self.capacity]      # every seen key is a candidate

    def drain(self) -> None:
        pass

    def tables(self):
        return [[t.copy() for _ in range(self.replicas)] for t in self._tables]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(1, os.path.join(ROOT, "src"))
    import jax

    from bench import catalog, harness

    cat = catalog.Catalog(ROOT)
    cell = cat.cell(args.workload)
    out = harness.run_cell(cat, cell, args.seed, args.seconds, False,
                           jax.devices(), {}, T_START,
                           make_server=ReferenceServer,
                           log=lambda m: print(m, flush=True))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
