"""Windowed heavy-hitter / top-k serving over the epoch ring.

:class:`WindowedTopKService` answers "top-k in the last W epochs" (or with
exponential time decay) by wrapping core/window.py's ring of per-epoch
hierarchies behind the same ingest/query surface as the since-boot
endpoints (serving/sketch_engine.SketchTopKEndpoint, sharded_topk):

  ingest    fold a weighted key block into the CURRENT epoch's tables via
            the shared-family hash cascade, and into that epoch's
            per-group space-saving candidate pools;
  advance   close the epoch: the oldest ring slot expires (dropped, or
            folded into the landmark accumulator) together with its
            candidate pools, and -- on the incremental tumbling path --
            its tables are SUBTRACTED from the cached window sum, exact by
            linearity and bit-identical to lazily re-summing the live
            slots (tests/test_window.py enforces the equivalence);
  query     heavy_hitters / topk run the recursive descent against the
            merged window state with candidates folded from the LIVE
            epochs' pools only, so expired keys cannot re-enter the
            candidate sets and every key of the live window is reachable
            (the no-false-negative guarantee survives expiry).

Incremental window sum (``incremental=True``, tumbling/landmark int
tables): the service keeps running per-level window tables, adds each
ingested block into them alongside the head epoch, and subtracts expiring
tables on advance -- O(1) table stacks per query instead of O(W).  Decay
mode always merges lazily (the Horner scale-then-fold re-weights every
epoch on every advance, so there is no cheap incremental form).

Everything here is linear-mode only.  Conservative tables can be neither
merged nor subtracted cell-wise, so the service refuses
``mode="conservative"`` at construction via the same
``core.distributed.require_linear`` guard as every sharded surface --
windowing composes with sharding for exactly the same reason psum does
(linearity), and ``merge_from`` below is that composition: per-slot
cell-wise adds of two aligned services' rings.

See docs/architecture.md for the layer map.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hierarchy as hh
from repro.core import sketch as sk
from repro.core import window as win
from repro.core.distributed import require_linear
from repro.core.summary import SpaceSaving
from repro.serving.sharded_topk import threshold_descent_topk


class WindowedTopKService:
    """Sliding-window / decayed heavy-hitter serving on one device.

    ``n_epochs`` fixes the ring size W; ``window_mode`` picks
    tumbling/landmark/decay (see core/window.py for the semantics);
    ``advance()`` is the epoch clock -- call it on whatever cadence the
    caller's timestamps dictate (streams/dstream.py drives it from batch
    timestamps).  Hash params are drawn once from ``key``: all epochs (and
    any merge-compatible sibling service) share them, which is what makes
    the per-epoch tables cell-wise mergeable at all.
    """

    def __init__(self, base_spec: sk.SketchSpec, key: jax.Array, *,
                 n_epochs: int, window_mode: str = "tumbling",
                 decay: float = 1.0,
                 max_candidates_per_group: int = 1 << 16,
                 use_kernel: bool = False, dtype=None,
                 incremental: bool = True, mode: str = "linear"):
        require_linear(mode, "WindowedTopKService")
        self.mode = mode
        self.wspec = win.WindowSpec(base=base_spec, n_epochs=int(n_epochs),
                                    mode=window_mode, decay=float(decay))
        self.hspec = self.wspec.hspec
        self.wstate = win.init_window(self.wspec, key, dtype=dtype)
        self.max_candidates = int(max_candidates_per_group)
        self.use_kernel = use_kernel
        # decay re-weights every live epoch on advance; only the equal-
        # weight modes admit the add/subtract running sum
        self.incremental = bool(incremental) and window_mode != "decay"
        self._window_sum: Optional[Tuple[jax.Array, ...]] = (
            tuple(jnp.zeros_like(t) for t in self.wstate.ring[0])
            if self.incremental else None)
        # ring of per-epoch per-group candidate pools, expired with their
        # epoch's tables so dead keys cannot linger in the candidate sets
        self._pools: List[List[SpaceSaving]] = [
            self._fresh_pools() for _ in range(self.wspec.n_epochs)]
        self._epoch_totals = [0] * self.wspec.n_epochs
        self._retired_total = 0

    def _fresh_pools(self) -> List[SpaceSaving]:
        return [SpaceSaving(self.max_candidates, len(g))
                for g in self.wspec.base.partition]

    # -- ingest / epoch clock ----------------------------------------------

    def ingest(self, items: np.ndarray,
               freqs: Optional[np.ndarray] = None) -> None:
        """Fold a weighted key block into the current epoch."""
        items = np.asarray(items, dtype=np.uint32)
        if items.shape[0] == 0:
            return
        if freqs is None:
            freqs = np.ones(items.shape[0], dtype=np.int64)
        freqs = np.asarray(freqs)
        self._epoch_totals[self.wstate.head] += int(freqs.sum())
        pools = self._pools[self.wstate.head]
        for j, g in enumerate(self.wspec.base.partition):
            pools[j].offer(items[:, list(g)], freqs)
        # pad to the next power of two like every other ingest surface
        # (zero-frequency pad rows are no-ops and never reach the pools)
        from repro.core.distributed import pad_block_pow2
        items, freqs, _ = pad_block_pow2(items, freqs, 1)
        self.wstate = win.window_update(self.wspec, self.wstate, items, freqs)
        if self._window_sum is not None:
            # the same block folds into the running window sum; identical
            # cascade, so sum-of-epochs and running sum stay bit-equal
            live = hh.update_jit(
                self.hspec,
                win._hier_state(self.wspec, self.wstate, self._window_sum),
                jnp.asarray(items), jnp.asarray(freqs))
            self._window_sum = tuple(st.table for st in live.states)

    def advance(self) -> None:
        """Close the current epoch and open a fresh one.

        Tumbling: the expiring slot's tables are subtracted from the
        running window sum (incremental path) or simply dropped from the
        lazy merge; its candidate pools and total expire with it.
        Landmark: tables fold into the retired accumulator and the
        expiring pools fold into a retained landmark pool seeded into the
        fresh slot, so since-boot candidates stay reachable."""
        new_head = (self.wstate.head + 1) % self.wspec.n_epochs
        expiring_tables = self.wstate.ring[new_head]
        if self._window_sum is not None and self.wspec.mode == "tumbling":
            self._window_sum = win.subtract_tables(self._window_sum,
                                                   expiring_tables)
        self.wstate = win.advance_window(self.wspec, self.wstate)
        if self.wspec.mode == "landmark":
            # nothing leaves a landmark window: fold the expiring pools
            # into the fresh slot so their values stay candidates, and
            # keep their mass in the window total
            self._retired_total += self._epoch_totals[new_head]
            carried = [SpaceSaving.fold([p]) for p in self._pools[new_head]]
            self._pools[new_head] = carried
        else:
            self._pools[new_head] = self._fresh_pools()
        self._epoch_totals[new_head] = 0

    # -- window views -------------------------------------------------------

    @property
    def epoch(self) -> int:
        return self.wstate.epoch

    @property
    def total(self) -> int:
        """Stream mass inside the current window (decay: Horner-weighted,
        rounded -- it only seeds the top-k threshold descent)."""
        live = win.live_slots(self.wspec, self.wstate)
        if self.wspec.mode == "decay":
            acc = 0.0
            for s in live:
                acc = acc * self.wspec.decay + self._epoch_totals[s]
            return max(1, int(acc))
        return self._retired_total + sum(self._epoch_totals[s] for s in live)

    def state(self) -> hh.HierarchyState:
        """The merged window hierarchy the queries run against.

        The running sum needs no retired adjustment: tumbling subtracts
        expiring epochs so it holds exactly the live window, and landmark
        never subtracts, so it already holds everything since boot (the
        ``retired`` accumulator only serves the lazy-merge path)."""
        if self._window_sum is not None:
            return win._hier_state(self.wspec, self.wstate, self._window_sum)
        return win.merged_state(self.wspec, self.wstate)

    def candidates(self) -> List[np.ndarray]:
        """Per-group candidates folded from the LIVE epochs' pools.

        Expired epochs' pools are gone, so a key seen only outside the
        window cannot re-enter the descent; a key inside the window sits in
        some live pool (under capacity: surely; at capacity: iff it carries
        > W_epoch/m of its epoch's weight).  Rows come sorted
        lexicographically from ``SpaceSaving.values`` so descent order
        never depends on the order of the live pools."""
        live = win.live_slots(self.wspec, self.wstate)
        return [SpaceSaving.fold([self._pools[s][j] for s in live]).values()
                for j in range(len(self.wspec.base.partition))]

    # -- durable state (serving/recovery.py snapshot currency) ---------------

    def _config_fingerprint(self) -> np.ndarray:
        dtype = self.wstate.ring[0][0].dtype
        desc = (f"windowed|{self.wspec!r}|dtype={dtype}"
                f"|cap={self.max_candidates}|inc={self.incremental}")
        return np.frombuffer(desc.encode(), dtype=np.uint8).copy()

    def state_dict(self) -> dict:
        """Full windowed state as a flat ``{key: ndarray}`` mapping.

        Every ring slot's tables, the retired accumulator, the shared hash
        params (finest level's arrays), the epoch clock (head + epoch
        counter), per-slot totals and pools, and -- on the incremental
        path -- the running window sum, persisted rather than recomputed
        so the round trip is bitwise-exact for any table dtype."""
        out = {
            "meta.fingerprint": self._config_fingerprint(),
            "meta.head": np.asarray(self.wstate.head, dtype=np.int64),
            "meta.epoch": np.asarray(self.wstate.epoch, dtype=np.int64),
            "meta.epoch_totals": np.asarray(self._epoch_totals,
                                            dtype=np.int64),
            "meta.retired_total": np.asarray(self._retired_total,
                                             dtype=np.int64),
            "params.q": np.asarray(self.wstate.level_params[-1].q),
            "params.r": np.asarray(self.wstate.level_params[-1].r),
        }
        for s, tables in enumerate(self.wstate.ring):
            for l, t in enumerate(tables):
                out[f"ring{s}.level{l}.table"] = np.asarray(t)
        for l, t in enumerate(self.wstate.retired):
            out[f"retired.level{l}.table"] = np.asarray(t)
        if self._window_sum is not None:
            for l, t in enumerate(self._window_sum):
                out[f"wsum.level{l}.table"] = np.asarray(t)
        for s, pools in enumerate(self._pools):
            for j, p in enumerate(pools):
                for k, v in p.state_dict().items():
                    out[f"slot{s}.pool{j}.{k}"] = v
        return out

    def load_state_dict(self, sd: dict) -> None:
        """Restore state saved by :meth:`state_dict`; bit-exact round trip."""
        fp = self._config_fingerprint()
        got = np.asarray(sd["meta.fingerprint"], dtype=np.uint8)
        if not np.array_equal(fp, got):
            raise ValueError(
                "windowed state_dict fingerprint mismatch: saved "
                f"{bytes(got).decode(errors='replace')!r}, this service is "
                f"{bytes(fp).decode(errors='replace')!r}")
        base = sk.SketchParams(q=jnp.asarray(sd["params.q"]),
                               r=jnp.asarray(sd["params.r"]))
        level_params = tuple(hh.level_params(self.hspec, base, i)
                             for i in range(self.hspec.n_levels))
        n_levels = self.hspec.n_levels
        ring = tuple(
            tuple(jnp.asarray(sd[f"ring{s}.level{l}.table"])
                  for l in range(n_levels))
            for s in range(self.wspec.n_epochs))
        retired = tuple(jnp.asarray(sd[f"retired.level{l}.table"])
                        for l in range(n_levels))
        self.wstate = self.wstate._replace(
            level_params=level_params, ring=ring, retired=retired,
            head=int(sd["meta.head"]), epoch=int(sd["meta.epoch"]))
        self._window_sum = (
            tuple(jnp.asarray(sd[f"wsum.level{l}.table"])
                  for l in range(n_levels))
            if self.incremental else None)
        self._epoch_totals = [int(x) for x in sd["meta.epoch_totals"]]
        self._retired_total = int(sd["meta.retired_total"])
        for s, pools in enumerate(self._pools):
            for j, p in enumerate(pools):
                p.load_state(sd[f"slot{s}.pool{j}.rows"],
                             sd[f"slot{s}.pool{j}.counts"],
                             sd[f"slot{s}.pool{j}.errs"])

    # -- queries ------------------------------------------------------------

    def heavy_hitters(self, threshold: int,
                      candidates: Optional[List[np.ndarray]] = None,
                      ) -> Tuple[np.ndarray, np.ndarray]:
        """Every key whose WINDOWED estimate is >= threshold."""
        if candidates is None:
            candidates = self.candidates()
        return hh.find_heavy_hitters(
            self.hspec, self.state(), threshold, candidates,
            use_kernel=self.use_kernel)

    def topk(self, k: int, min_threshold: Optional[int] = None,
             ) -> Tuple[np.ndarray, np.ndarray]:
        """The k keys with the largest windowed estimates."""
        return threshold_descent_topk(
            self.heavy_hitters, self.candidates(), k, total=self.total,
            n_modules=self.wspec.base.schema.modularity,
            min_threshold=min_threshold)

    # -- cross-shard composition (linearity, again) -------------------------

    def merge_from(self, other: "WindowedTopKService") -> None:
        """Fold a sibling service's window in, slot by slot.

        Shard a stream over N windowed services (same spec, same key, same
        advance cadence) and fold at query time: per-slot cell-wise adds
        are exact by linearity, exactly the psum contract of the sharded
        since-boot service.  Requires aligned epoch clocks and identical
        hash params -- mismatches are refused, not silently accepted."""
        if self.wspec != other.wspec:
            raise ValueError("merge_from requires identical WindowSpecs")
        if (self.wstate.head != other.wstate.head
                or self.wstate.epoch != other.wstate.epoch):
            raise ValueError(
                "merge_from requires aligned epoch clocks (same number of "
                "advance() calls on both services)")
        for pa, pb in zip(self.wstate.level_params,
                          other.wstate.level_params):
            if not (np.array_equal(np.asarray(pa.q), np.asarray(pb.q))
                    and np.array_equal(np.asarray(pa.r), np.asarray(pb.r))):
                raise ValueError(
                    "merge_from requires identical hash params on both "
                    "services (build them from the same spec and key)")
        ring = tuple(win._add_tables(a, b) for a, b
                     in zip(self.wstate.ring, other.wstate.ring))
        retired = win._add_tables(self.wstate.retired, other.wstate.retired)
        self.wstate = self.wstate._replace(ring=ring, retired=retired)
        if self._window_sum is not None:
            if other._window_sum is not None:
                other_sum = other._window_sum
            else:
                # the lazy merge has the same coverage as a running sum:
                # live window for tumbling, since-boot (incl. retired) for
                # landmark
                other_sum = tuple(
                    s.table for s in
                    win.merged_state(other.wspec, other.wstate).states)
            self._window_sum = win._add_tables(self._window_sum, other_sum)
        for s in range(self.wspec.n_epochs):
            self._epoch_totals[s] += other._epoch_totals[s]
            for mine, theirs in zip(self._pools[s], other._pools[s]):
                mine.merge_from(theirs)
        self._retired_total += other._retired_total
