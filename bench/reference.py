"""The plain reference and the comparison that decides ``correct``.

The reference imports nothing of the program.  It computes, in numpy, what
the paper's composite-hash Count-Min hierarchy must hold after the first
``T`` rows of the cyclic replay: level ``L`` of row ``k`` has the cell

    sum_{j <= L} H_kj(key[g_j]) * prod(ranges[j+1..L]),
    H_kj(x) = ((r[k, j] + sum_c q[k, c] * x_c) mod (2^31 - 1)) mod ranges[j]

where ``x_c`` are the 16-bit digits (low digit first) of the modules of
group ``j`` and the columns of ``q`` follow the groups in order.  The hash
parameters are drawn by the benchmark from the seed and handed to both
sides.  Cell values are exact int64 sums of the row weights.

Every number compared is a count of faults with the limit 0:

``table_cells_wrong``  cells of the served tables (every level, every
                       replica) that differ from the reference at the end
``estimates_wrong``    answered keys whose estimate is not the reference
                       Count-Min estimate at the rows acknowledged before
                       the flush that answered it (``max_staleness=0``)
``heavy_missed``       keys an answer must hold and lacks: every key whose
                       true count reaches a heavy-hitter threshold, and
                       every key whose true count beats a top-k answer's
                       k-th estimate (both above the pools' W/m admission
                       bound)
``answers_malformed``  answers out of order, with duplicate keys, below
                       their threshold, or of the wrong length
``unanswered``         requests due in the window with no answer
"""
from __future__ import annotations

import dataclasses
from typing import List, Sequence

import numpy as np

P31 = (1 << 31) - 1
LIMITS = {"table_cells_wrong": 0, "estimates_wrong": 0, "heavy_missed": 0,
          "answers_malformed": 0, "unanswered": 0}


def draw_hash_params(rng: np.random.Generator, width: int, total_chunks: int,
                     n_groups: int):
    """Uniform multipliers ``q`` and offsets ``r`` in ``[0, 2^31 - 1)``."""
    q = rng.integers(0, P31, size=(width, total_chunks), dtype=np.int64)
    r = rng.integers(0, P31, size=(width, n_groups), dtype=np.int64)
    return q.astype(np.uint32), r.astype(np.uint32)


def _chunks(domain: int) -> int:
    return max(1, (int(domain - 1).bit_length() + 15) // 16)


def total_chunks(sketch: dict) -> int:
    return sum(_chunks(d) for d in sketch["domains"])


def level_cells(items: np.ndarray, sketch: dict, q: np.ndarray,
                r: np.ndarray) -> List[np.ndarray]:
    """Cell index of every key at every level: one int64[w, n] per level."""
    items = np.asarray(items)
    domains, ranges = sketch["domains"], [int(x) for x in sketch["ranges"]]
    w = int(sketch["width"])
    q = q.astype(np.uint64)
    r = r.astype(np.uint64)
    col = 0
    sub = []                                   # H_j: uint64[w, n] per group
    for j, group in enumerate(sketch["partition"]):
        acc = np.repeat(r[:, j][:, None], items.shape[0], axis=1)
        for m in group:
            v = items[:, m].astype(np.uint64)
            for c in range(_chunks(domains[m])):
                x = (v >> np.uint64(16 * c)) & np.uint64(0xFFFF)
                acc = acc + q[:, col][:, None] * x[None, :]
                col += 1
        sub.append((acc % np.uint64(P31)) % np.uint64(ranges[j]))
    out = []
    for level in range(len(ranges)):
        idx = np.zeros((w, items.shape[0]), np.uint64)
        for j in range(level + 1):
            stride = int(np.prod(ranges[j + 1: level + 1], dtype=np.int64))
            idx += sub[j] * np.uint64(stride)
        out.append(idx.astype(np.int64))
    return out


def pack_keys(items: np.ndarray, domains: Sequence[int]) -> np.ndarray:
    """Mixed-radix packing of whole keys into uint64 (order-preserving)."""
    if int(np.prod([int(d) for d in domains], dtype=object)) > 1 << 64:
        raise ValueError("keys wider than 64 bits")
    out = np.zeros(items.shape[0], np.uint64)
    for m, d in enumerate(domains):
        out = out * np.uint64(int(d) % (1 << 64)) if m else out
        out = out + items[:, m].astype(np.uint64)
    return out


class _RowCells:
    """One (level, row): cell values after the first ``T`` replayed rows."""

    def __init__(self, cells: np.ndarray, freqs: np.ndarray, size: int):
        n = cells.shape[0]
        self.n, self.size, self.cells, self.freqs = n, size, cells, freqs
        order = np.argsort(cells, kind="stable")      # position order kept
        self.key = cells[order] * n + order
        self.cum = np.concatenate([[0], np.cumsum(freqs[order])])
        self.full = np.bincount(cells, weights=freqs, minlength=size
                                ).round().astype(np.int64)

    def values(self, c: np.ndarray, rows: int) -> np.ndarray:
        passes, rem = divmod(int(rows), self.n)
        lo = np.searchsorted(self.key, c * self.n)
        hi = np.searchsorted(self.key, c * self.n + rem)
        return passes * self.full[c] + self.cum[hi] - self.cum[lo]

    def table(self, rows: int) -> np.ndarray:
        passes, rem = divmod(int(rows), self.n)
        part = np.bincount(self.cells[:rem], weights=self.freqs[:rem],
                           minlength=self.size).round().astype(np.int64)
        return passes * self.full + part


class Reference:
    """The reference hierarchy over one stream and one hash draw."""

    def __init__(self, items: np.ndarray, freqs: np.ndarray, sketch: dict,
                 q: np.ndarray, r: np.ndarray):
        self.sketch, self.q, self.r = sketch, q, r
        self.items, self.freqs = items, freqs.astype(np.int64)
        self.n = freqs.shape[0]
        self.mass = int(self.freqs.sum())
        ranges = [int(x) for x in sketch["ranges"]]
        self.sizes = [int(np.prod(ranges[: L + 1], dtype=np.int64))
                      for L in range(len(ranges))]
        self.rows = [[_RowCells(lv[k], self.freqs, size)
                      for k in range(lv.shape[0])]
                     for lv, size in zip(level_cells(items, sketch, q, r),
                                         self.sizes)]
        packed = pack_keys(items, sketch["domains"])
        self._order = np.argsort(packed)
        self._packed = packed[self._order]

    def mass_at(self, rows: int) -> int:
        passes, rem = divmod(int(rows), self.n)
        return passes * self.mass + int(self.freqs[:rem].sum())

    def true_counts_all(self, rows: int) -> np.ndarray:
        """Exact count of every distinct key (stream order) at ``rows``."""
        passes, rem = divmod(int(rows), self.n)
        return self.freqs * (passes + (np.arange(self.n) < rem))

    def true_counts(self, items: np.ndarray, rows: int) -> np.ndarray:
        """Exact counts of arbitrary keys (0 for keys never streamed)."""
        k = pack_keys(items, self.sketch["domains"])
        at = np.clip(np.searchsorted(self._packed, k), 0, self.n - 1)
        hit = self._packed[at] == k
        pos = self._order[at]
        return np.where(hit, self.true_counts_all(rows)[pos], 0)

    def estimates(self, items: np.ndarray, rows: int) -> np.ndarray:
        """Finest-level Count-Min estimates (min over rows) at ``rows``."""
        cells = level_cells(items, self.sketch, self.q, self.r)[-1]
        vals = [rc.values(cells[k], rows) for k, rc in enumerate(self.rows[-1])]
        return np.min(np.stack(vals), axis=0)

    def tables(self, rows: int) -> List[np.ndarray]:
        return [np.stack([rc.table(rows) for rc in lv]) for lv in self.rows]


@dataclasses.dataclass
class Answer:
    """One served request: what was asked, when, and what came back."""
    kind: str                  # "topk" | "heavy_hitters"
    k: int
    threshold: int
    rows: int                  # rows acknowledged before its flush
    items: np.ndarray
    est: np.ndarray


def check_answers(ref: Reference, answers: Sequence[Answer],
                  capacity: int) -> dict:
    out = {"estimates_wrong": 0, "heavy_missed": 0, "answers_malformed": 0}
    for a in answers:
        items = np.asarray(a.items, np.uint32).reshape(-1, ref.items.shape[1])
        est = np.asarray(a.est, np.int64).reshape(-1)
        mass = ref.mass_at(a.rows)
        admit = mass / capacity                  # W/m pool admission bound
        packed = pack_keys(items, ref.sketch["domains"])
        bad = (items.shape[0] != est.shape[0]
               or bool(np.any(np.diff(est) > 0))
               or np.unique(packed).shape[0] != packed.shape[0])
        true = ref.true_counts_all(a.rows)
        if a.kind == "heavy_hitters":
            bad |= bool(np.any(est < a.threshold))
            due = (true >= a.threshold) & (true > admit)
        else:
            floor = max(1, mass >> 17)
            bad |= est.shape[0] > a.k
            if est.shape[0] == a.k:
                due = (true > est[-1]) & (true > admit)
            else:
                due = (true >= floor) & (true > admit)
                bad |= int(due.sum()) >= a.k
        out["answers_malformed"] += int(bad)
        if est.shape[0] == items.shape[0] and items.shape[0]:
            out["estimates_wrong"] += int(np.sum(
                ref.estimates(items, a.rows) != est))
        want = pack_keys(ref.items[due], ref.sketch["domains"])
        out["heavy_missed"] += int(np.sum(~np.isin(want, packed)))
    return out


def check_tables(ref: Reference, rows: int,
                 tables: Sequence[Sequence[np.ndarray]], replicas: int) -> int:
    """Cells differing from the reference, over every level and each of the
    ``replicas`` copies the configuration keeps (a missing copy is wrong in
    every cell)."""
    want = ref.tables(rows)
    wrong = 0
    for lv, copies in zip(want, tables):
        wrong += lv.size * max(0, replicas - len(copies))
        for t in copies:
            t = np.asarray(t)
            wrong += (lv.size if t.shape != lv.shape
                      else int(np.sum(t.astype(np.int64) != lv)))
    if len(tables) != len(want):
        wrong += sum(t.size for t in want[len(tables):])
    return wrong
