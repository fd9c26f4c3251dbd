"""The psum sync's share of its interconnect roofline.

Least bytes each chip sends for one merge: a ring all-reduce of the
``w x sum_L h_L`` int32 tables over ``n`` chips moves ``2 (n - 1) / n`` of
them.  The window's merges are the runs of the ``sharded_merge`` program
(``jit_sharded_merge``) on chip 0; their least bytes are divided by the
window's all-reduce time (averaged over the chips, as
``sync_collective_share.dp4`` counts it) at the chip's interconnect
bandwidth, ``ici_bits_per_s / 8``.
"""
from pathlib import Path

import numpy as np

from bench.catalog import load_module

MERGE = "jit_sharded_merge"
_SYNC = load_module(Path(__file__).with_name("sync_collective_share.dp4.py"))


def merge_bytes(chips: int, width: int, ranges) -> float:
    """Least bytes one chip sends in one merge of every level's table."""
    cells = sum(int(np.prod(ranges[: L + 1], dtype=np.int64))
                for L in range(len(ranges)))
    return 2.0 * (chips - 1) / chips * width * cells * 4


def read(run):
    t = run.trace
    if t is None or run.chips < 2:
        return None
    iv, names, _ = t.modules[0]
    inside = (iv[:, 0] >= t.t0) & (iv[:, 0] < t.t1)
    merges = sum(1 for n, k in zip(names, inside)
                 if k and n.split("(")[0] == MERGE)
    secs = _SYNC.all_reduce_seconds(t)
    if not merges or secs <= 0.0:
        return None
    sk = run.cell.config["sketch"]
    need = merges * merge_bytes(run.chips, int(sk["width"]),
                                [int(r) for r in sk["ranges"]])
    return 100.0 * need / (secs * float(run.peaks["ici_bits_per_s"]) / 8)
