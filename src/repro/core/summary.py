"""Space-saving (Misra-Gries style) summaries of weighted value streams.

The serving surfaces' candidate pools (serving/sketch_engine.py,
serving/sharded_topk.py, serving/windowed_topk.py): each partition group
keeps one bounded summary of the group values seen so far, so late-arriving
heavy values still enter the candidate sets by evicting the lightest
entries instead of being dropped by a first-come cap.

A summary is three arrays sorted by row: ``_keys`` (one key per row that
orders as the rows do lexicographically: the row packed into a uint64 when
it fits in 64 bits, its big-endian bytes otherwise), ``_counts`` and
``_errs`` (float64).  One routine, :meth:`SpaceSaving._merge`, unions
another sorted summary in by the mergeable-summaries rule (Agarwal et al.,
PODS 2012) and truncates back to capacity, dropping the lowest entries by
``(count, key)``.  :meth:`offer` merges in the block's exact summary (an
exact summary has floor 0); :meth:`merge_from` and :meth:`fold` merge
other pools.

Block rule guarantees, capacity m over total weight W, ``floor`` the
pool's minimum count when full (0 when not):

  * count(v) >= true(v): a row new to the pool takes ``floor + f``, and a
    row absent from a full pool has true(v) <= floor (it was dropped at a
    count <= the floor, and the floor never falls: every union entry is
    >= it).
  * count(v) - true(v) <= W / m: the inherited error is the floor at
    admission; every union entry is >= the floor, so the d dropped entries
    sum to >= d * floor, the m kept ones to <= W, and the floor <= W / m.
  * every value with true(v) > W / m is in the summary: a dropped row has
    true(v) <= count(v) <= the new floor <= W / m.

Counts are float64 so fractional weights (f32 gradient streams) admit
normally; float64 sums of integer weights stay exact below 2^53.  Only the
*values* feed the heavy-hitter descent (estimates come from the sketch
tables, not from these counts), so the counts' job is eviction ranking and
the W/m admission guarantee.  A block of d distinct rows costs one sort of
the block, O(d log m) searches and O(m) array copies: no per-row Python.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from repro import obs

Row = Tuple[int, ...]


class SpaceSaving:
    """Bounded weighted summary over fixed-width uint32 value rows."""

    def __init__(self, capacity: int, n_cols: int):
        if capacity < 1:
            raise ValueError("capacity >= 1 required")
        if n_cols < 1:
            raise ValueError("n_cols >= 1 required")
        self.capacity = int(capacity)
        self.n_cols = int(n_cols)
        self._keys = self._encode(np.zeros((0, self.n_cols), np.uint32))
        self._counts = np.zeros(0, np.float64)
        self._errs = np.zeros(0, np.float64)

    def __len__(self) -> int:
        return self._keys.shape[0]

    # -- keys: one per row, ordered as the rows are lexicographically --------

    def _encode(self, rows: np.ndarray) -> np.ndarray:
        if self.n_cols == 1:
            return rows[:, 0].astype(np.uint64)
        if self.n_cols == 2:
            return (rows[:, 0].astype(np.uint64) << np.uint64(32)) | rows[:, 1]
        return (np.ascontiguousarray(rows, dtype=">u4")
                .view(f"S{4 * self.n_cols}").ravel())

    def _decode(self, keys: np.ndarray) -> np.ndarray:
        if self.n_cols == 1:
            return keys.astype(np.uint32)[:, None]
        if self.n_cols == 2:
            return np.stack([(keys >> np.uint64(32)).astype(np.uint32),
                             keys.astype(np.uint32)], axis=1)
        return keys.view(">u4").reshape(-1, self.n_cols).astype(np.uint32)

    # -- the one merge routine ------------------------------------------------

    def _floor(self) -> float:
        """The most an absent row can weigh: the min count when full, else 0
        (absent from a summary under capacity means truly unseen)."""
        return float(self._counts.min()) if len(self) >= self.capacity else 0.0

    def _merge(self, keys: np.ndarray, counts: np.ndarray, errs: np.ndarray,
               floor: float) -> Tuple[int, int]:
        """Union a summary given as sorted distinct ``keys`` with their
        ``counts``/``errs`` into this one; rows absent from it weigh its
        ``floor``.  Returns (rows already resident, rows dropped)."""
        own = self._floor()
        pos = np.searchsorted(self._keys, keys)
        hit = pos < len(self)
        hit[hit] = self._keys[pos[hit]] == keys[hit]
        i = pos[hit]
        c, e = self._counts + floor, self._errs + floor
        c[i] = self._counts[i] + counts[hit]
        e[i] = self._errs[i] + errs[hit]
        new = ~hit
        at = pos[new]
        self._keys = np.insert(self._keys, at, keys[new])
        self._counts = np.insert(c, at, counts[new] + own)
        self._errs = np.insert(e, at, errs[new] + own)
        extra = len(self) - self.capacity
        if extra > 0:
            self._drop_lowest(extra)
        return int(np.count_nonzero(hit)), max(extra, 0)

    def _drop_lowest(self, extra: int) -> None:
        """Drop the ``extra`` entries lowest by ``(count, key)``: every count
        below the extra-th smallest, then its ties in key (array) order."""
        c = self._counts
        t = np.partition(c, extra - 1)[extra - 1]
        drop = c < t
        drop[np.flatnonzero(c == t)[: extra - np.count_nonzero(drop)]] = True
        keep = ~drop
        self._keys, self._counts, self._errs = (
            self._keys[keep], c[keep], self._errs[keep])

    # -- ingest ---------------------------------------------------------------

    def offer(self, values: np.ndarray, freqs: np.ndarray | None = None) -> None:
        """Fold a block of value rows with weights into the summary."""
        values = np.asarray(values, dtype=np.uint32)
        if values.ndim != 2 or values.shape[1] != self.n_cols:
            raise ValueError(f"values must be [N, {self.n_cols}]")
        if values.shape[0] == 0:
            return
        if freqs is None:
            freqs = np.ones(values.shape[0], dtype=np.int64)
        freqs = np.asarray(freqs, dtype=np.float64)
        with obs.span("sketch.pool_offer", rows=values.shape[0]):
            # the block's exact summary: one sort and a segmented sum
            keys, inv = np.unique(self._encode(values), return_inverse=True)
            tot = np.bincount(inv.reshape(-1), weights=freqs,
                              minlength=keys.shape[0])
            live = tot > 0  # zero-weight pad rows are not observations
            hits, evicted = self._merge(keys[live], tot[live],
                                        np.zeros(np.count_nonzero(live)), 0.0)
        obs.count("sketch.pool_offered", keys.shape[0])
        obs.count("sketch.pool_hits", hits)
        obs.count("sketch.pool_evicted", evicted)

    # -- readers --------------------------------------------------------------

    def values(self) -> np.ndarray:
        """All summarized rows: uint32[K, n_cols], sorted lexicographically
        and distinct.

        The canonical order makes descent answers (and their tie order)
        independent of admission order, so surfaces that hold the same
        rows -- one pool, or shard pools folded into one -- answer
        identically."""
        return self._decode(self._keys)

    def counts(self) -> Dict[Row, float]:
        return dict(zip(map(tuple, self.values().tolist()),
                        self._counts.tolist()))

    # -- durable state (serving/recovery.py snapshot currency) --------------

    def state_dict(self) -> Dict[str, np.ndarray]:
        """The summary as its three arrays: rows (sorted, as
        :meth:`values`) / counts / errs.

        Every later offer depends only on these arrays, so the round trip
        through :meth:`load_state` is bit-exact, later evictions included."""
        return {"rows": self.values(), "counts": self._counts.copy(),
                "errs": self._errs.copy()}

    def load_state(self, rows: np.ndarray, counts: np.ndarray,
                   errs: np.ndarray) -> None:
        """Restore a summary saved by :meth:`state_dict` (same capacity/width).

        Overwrites the current contents wholesale.  Rows are put in key
        order (a no-op on a saved summary) and must be distinct."""
        rows = np.asarray(rows, dtype=np.uint32)
        if rows.ndim != 2 or rows.shape[1] != self.n_cols:
            raise ValueError(f"rows must be [K, {self.n_cols}]")
        if rows.shape[0] > self.capacity:
            raise ValueError(
                f"loaded summary has {rows.shape[0]} rows but capacity is "
                f"{self.capacity}: capacity must match the saved summary")
        keys = self._encode(rows)
        order = np.argsort(keys, kind="stable")
        keys = keys[order]
        if np.any(keys[1:] == keys[:-1]):
            raise ValueError("loaded summary repeats a row")
        self._keys = keys
        self._counts = np.asarray(counts, dtype=np.float64)[order]
        self._errs = np.asarray(errs, dtype=np.float64)[order]

    # -- cross-shard merge ----------------------------------------------------

    @classmethod
    def fold(cls, summaries: List["SpaceSaving"]) -> "SpaceSaving":
        """Fold shard summaries into one fresh summary (cross-shard cascade).

        Capacity and width come from the first summary; each shard is
        folded in with :meth:`merge_from`, so the result carries the
        mergeable-summaries guarantees: counts upper-bound true weights and
        the inherited error is at most the sum of the shards' floors (each
        <= W_i / m).  When every shard is under capacity the fold is exact
        -- counts are plain sums and no row is lost -- which is what makes
        the sharded serving candidate pools shard-count invariant below
        capacity (serving/sharded_topk.py); ``fold([p])`` is a copy of p."""
        summaries = list(summaries)
        if not summaries:
            raise ValueError("fold requires at least one summary")
        out = cls(summaries[0].capacity, summaries[0].n_cols)
        for s in summaries:
            out.merge_from(s)
        return out

    def merge_from(self, other: "SpaceSaving") -> None:
        """Fold another summary in (cross-shard candidate merge).

        Mergeable-summaries rule (Agarwal et al. 2012): a row absent from
        one side contributes that side's floor -- its min count when the
        side is at capacity (the row may have been evicted with up to that
        much mass), 0 when under capacity (absent then means truly unseen).
        The union is truncated back to capacity, dropping the lowest by
        ``(count, key)``.  This preserves count(v) >= true(v) for every
        retained row, so a value heavy on either shard still out-ranks
        light entries in the merged summary; the error bound grows to the
        sum of the two floors.
        """
        if other.n_cols != self.n_cols:
            raise ValueError("cannot merge summaries of different widths")
        self._merge(other._keys, other._counts, other._errs, other._floor())
