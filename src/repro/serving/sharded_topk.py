"""Sharded heavy-hitter serving on multi-device meshes.

:class:`ShardedTopKService` runs the full hierarchical heavy-hitter
pipeline (core/hierarchy.py) on a data-parallel device mesh:

  ingest   the stream block is split over the mesh's data axes and every
           shard folds its slice into its *local* tables -- ONE shard_map
           over all levels (``sharded_fold``): each item is hashed once
           and every level's cell derived by the mixed-radix cascade; no
           collective on the ingest hot path, local tables donated into
           the jitted fold -- while per-shard space-saving pools
           (core/summary.py) admit candidate group values.  The fold body
           is the jnp scatter-add per level
           (core.distributed.lazy_hierarchy_update), or with
           ``use_update_kernel=True`` the fused Pallas kernel on one stack
           of concatenated padded level tables
           (core.distributed.lazy_hierarchy_update_fused, the
           kernels/ops.KernelHierarchy layout; digits split on the host
           once per block);
  sync     at explicit sync points one program (``sharded_merge``,
           core.distributed.sync_local_hierarchy) adds the psum of the
           local tables to the merged tables (exact by linearity) and
           returns the locals zeroed; the shard pools fold into global
           pools with the mergeable-summaries rule (SpaceSaving.fold) at
           the next ``candidates()``;
  query    ``heavy_hitters`` / ``topk`` run the recursive descent
           (core.hierarchy.find_heavy_hitters, optionally the Pallas
           candidate kernel kernels/hier_query.py) against the merged
           level tables.

Shard-count invariance: every level table is linear in the stream and
integer addition is exact and order-free, so the merged tables -- and with
them the query output -- are *bit-identical* for any shard count and any
split of the same stream (1, 2, 4 and 8 shards all agree; enforced by
tests/test_sharded_topk.py).  The candidate pools stay invariant as long
as they are under capacity (the fold is then an exact union); the
service's ``candidates()`` sorts rows lexicographically so the descent
order never depends on pool iteration order.

Conservative tables are non-linear and cannot psum: the service refuses
``mode="conservative"`` at construction, as do the underlying distributed
entry points (core.distributed.require_linear) and the single-shard
endpoint's :meth:`~repro.serving.sketch_engine.SketchTopKEndpoint.to_sharded`
promotion.
"""
from __future__ import annotations

from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro import obs
from repro.core import distributed as dist
from repro.core import hierarchy as hh
from repro.core import sketch as sk
from repro.core.summary import SpaceSaving
from repro.serving.migration import MigratingSurface, require_not_migrating


def threshold_descent_topk(
    heavy_hitters_fn: Callable[..., Tuple[np.ndarray, np.ndarray]],
    candidates: Sequence[np.ndarray],
    k: int,
    *,
    total: int,
    n_modules: int,
    min_threshold: Optional[int] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Top-k by estimate: geometric threshold descent until k keys found.

    Shared by SketchTopKEndpoint.topk and ShardedTopKService.topk.
    ``min_threshold`` floors the descent; the default scales with the
    stream (total / 2^17) because at threshold ~1 every candidate survives
    every level and the leaf evaluates the full candidate cross-product --
    exactly the blowup the hierarchy avoids.  Pass ``min_threshold=1``
    explicitly to force exhaustive descent on small candidate pools.
    """
    if min_threshold is None:
        min_threshold = max(1, total >> 17)
    thr = max(total, 1)
    items = np.zeros((0, n_modules), np.uint32)
    est = np.zeros((0,), np.int64)
    while thr >= min_threshold:
        items, est = heavy_hitters_fn(thr, candidates=candidates)
        if len(est) >= k or thr == min_threshold:
            break
        thr = max(min_threshold, thr // 4)
    return items[:k], est[:k]


class ShardedTopKService(MigratingSurface):
    """Heavy-hitter / top-k serving over a data-parallel device mesh.

    One service instance owns the whole mesh: ``n_shards`` is the product
    of the ``data_axes`` sizes, each shard ingesting a contiguous slice of
    every block.  Hash params are drawn once from ``key`` (all shards and
    all shard counts share them -- cell-wise sums of differently hashed
    tables would be garbage), so two services built from the same spec and
    key are merge-compatible snapshots of each other.

    ``sync_every`` controls the psum cadence: the merge all-reduce runs
    after that many ingested blocks (1 = synchronous, the sharded_build
    shape).  Pass ``sync_every=None`` for fully manual sync points; any
    query forces a sync first, so results are never stale.

    ``use_update_kernel=True`` folds through the fused Pallas kernel, as
    the one-chip endpoint's option of the same name does: every table is
    kept in the kernels/ops.KernelHierarchy layout (the merged tables as
    one replicated ``[w, padded_cols]`` stack, the locals as one
    ``[n_shards, w, padded_cols]`` stack placed shard-per-device), each
    shard's slice is padded to whole kernel launches (the
    KernelHierarchy's ``block_b``, 1,024 rows), and ``merged`` /
    ``state()`` is the per-level view, sliced and unpadded, cached until
    the tables change.  Linear mode only.

    Hot spec migration (serving/migration.py): ``begin_migration`` opens
    a double-write window onto a successor service on the same mesh;
    queries serve from the old tables until the successor has absorbed
    ``warmup`` mass, then the service cuts over wholesale.  Because the
    successor is itself shard-count invariant, a migration is
    bit-identical across shard counts end to end.
    """

    def __init__(self, base_spec: sk.SketchSpec, key: jax.Array, mesh, *,
                 data_axes: Optional[Tuple[str, ...]] = None,
                 max_candidates_per_group: int = 1 << 16,
                 sync_every: Optional[int] = 1,
                 use_kernel: bool = False, use_update_kernel: bool = False,
                 dtype=jnp.int32, mode: str = "linear"):
        dist.require_linear(mode, "ShardedTopKService")
        from repro.launch.mesh import sketch_data_axes

        if use_update_kernel and mode != "linear":
            raise ValueError(
                "the fused update kernel is linear-only: fold a signed "
                "service on the jnp path (use_update_kernel=False)")
        self.mode = mode
        self.mesh = mesh
        if data_axes is None:
            data_axes = sketch_data_axes(mesh)
        self.data_axes = tuple(data_axes)
        self.n_shards = int(np.prod([mesh.shape[a] for a in self.data_axes],
                                    dtype=np.int64))
        self.hspec = hh.HierarchySpec.from_spec(base_spec)
        state = hh.init_hierarchy(self.hspec, key, dtype=dtype)
        self._kh, self._merged = None, None
        if use_update_kernel:
            from repro.kernels.ops import KernelHierarchy

            self._kh = KernelHierarchy.from_state(self.hspec, state)
        self.merged = state
        self._local = self._zero_locals()
        self.max_candidates = int(max_candidates_per_group)
        self.use_kernel = use_kernel
        self.use_update_kernel = bool(use_update_kernel)
        self.sync_every = sync_every
        self._dtype = dtype
        self._migration = None
        self.total = 0
        self._blocks_since_sync = 0
        self._dirty = False
        self._pools_dirty = False
        self._shard_pools: List[List[SpaceSaving]] = [
            [SpaceSaving(self.max_candidates, len(g))
             for g in base_spec.partition]
            for _ in range(self.n_shards)
        ]
        self._global_pools: List[SpaceSaving] = [
            SpaceSaving(self.max_candidates, len(g))
            for g in base_spec.partition
        ]
        self._build_jit_wrappers()

    def _build_jit_wrappers(self) -> None:
        """(Re)build the jit-cached shard_map wrappers for the CURRENT mesh.

        jit wrappers cached per service: an eager shard_map re-traces on
        every call, which would dominate the ingest hot path.  Params are
        dynamic args (not closed over) so a promoted endpoint's params
        (to_sharded swaps self.merged) hit the same compiled executable.
        The local tables are DONATED: the per-shard fold (which now
        hashes each item once and cascades to every level inside one
        shard_map) accumulates in place instead of copying every level
        table per block.  ``ingest`` rebinds self._local to the result,
        which is the only live reference.

        The functions close over ``self.mesh``/``self.data_axes`` *at
        trace time*, so anything that changes the mesh (``remesh``) MUST
        call this again -- reusing the old function objects would silently
        replay executables compiled for the old device set (the same
        staleness hazard migration's ``_adopt`` documents).  They are
        named (``jit_sharded_fold``, ``jit_sharded_merge`` in a device
        trace) so their programs can be told apart.
        """
        hspec, mesh, axes, kh = self.hspec, self.mesh, self.data_axes, self._kh

        if kh is None:
            def sharded_fold(local, params, it, fr):
                return dist.lazy_hierarchy_update(hspec, mesh, axes, local,
                                                  params, it, fr)
        else:
            hplan, block, interpret = kh.hplan, kh.block_b, kh.interpret

            def sharded_fold(local, params, chunks, fr):
                return dist.lazy_hierarchy_update_fused(
                    hplan, mesh, axes, local, params.q, params.r, chunks, fr,
                    block_b=block, interpret=interpret)

        def sharded_merge(local, tables):
            return dist.sync_local_hierarchy(mesh, axes, local, tables)

        self._fold = jax.jit(sharded_fold, donate_argnums=(0,))
        self._merge = jax.jit(sharded_merge, donate_argnums=(0,))

    # -- table layout (per level, or the fused kernel's one stack) ----------

    @property
    def merged(self) -> hh.HierarchyState:
        """The merged (serving) tables as a HierarchyState; with the fused
        kernel a per-level view of the stack, cached until it changes."""
        return self._merged if self._kh is None else self._kh.state()

    @merged.setter
    def merged(self, state: hh.HierarchyState) -> None:
        """Adopt ``state`` as the merged tables, placed replicated on the
        mesh once here, so the fold and merge programs never re-send the
        params or the tables to every device."""
        rep = NamedSharding(self.mesh, PartitionSpec())
        if self._kh is None:
            self._merged = jax.device_put(state, rep)
        else:
            self._kh.load_state(state)
            self._kh.params = jax.device_put(self._kh.params, rep)
            self._kh.adopt_table(jax.device_put(self._kh.table, rep))

    def _merged_tables(self) -> Tuple[jax.Array, ...]:
        if self._kh is None:
            return tuple(st.table for st in self._merged.states)
        return (self._kh.table,)

    def _set_merged_tables(self, tables: Sequence[jax.Array]) -> None:
        if self._kh is None:
            self._merged = hh.HierarchyState(states=tuple(
                sk.SketchState(params=st.params, table=t)
                for st, t in zip(self._merged.states, tables)))
        else:
            self._kh.adopt_table(tables[0])

    def _zero_locals(self) -> Tuple[jax.Array, ...]:
        """Zeroed local stacks for the current mesh, shard-per-device."""
        tables = self._merged_tables()
        return dist.init_local_tables(self.mesh, self.data_axes,
                                      self.n_shards, [t.shape for t in tables],
                                      tables[0].dtype)

    # -- ingest (per-shard lazy fold, no collective) ------------------------

    def ingest(self, items: np.ndarray,
               freqs: Optional[np.ndarray] = None) -> None:
        """Fold a weighted key block, sharded over the mesh's data axes.

        The block is padded so every shard sees the same power-of-two row
        count (zero-frequency pad rows are no-ops in the linear update and
        are skipped by the pools), then each shard folds its contiguous
        slice into its local tables -- no collective until the next sync
        point -- and offers it to its pools while the device folds.  With
        the fused kernel each slice is further padded to whole kernel
        launches and the digits are split on the host, once for the block.
        """
        items = np.asarray(items, dtype=np.uint32)
        if items.shape[0] == 0:
            return
        if freqs is None:
            freqs = np.ones(items.shape[0], dtype=np.int64)
        freqs = np.asarray(freqs)
        if self._kh is not None:
            from repro.kernels.ops import check_fold_freqs

            # reject kernel-unrepresentable weights BEFORE touching pools
            # or totals, so a failed ingest leaves the service unchanged
            with obs.span("sketch.check"):
                check_fold_freqs(self.mode, freqs, self._kh.table.dtype)
        self.total += int(freqs.sum())
        raw_items, raw_freqs = items, freqs
        with obs.span("sketch.fold_prep"):
            items, freqs, per = dist.pad_block_pow2(items, freqs,
                                                    self.n_shards)
            args = self._fold_inputs(items, freqs, per)
        with obs.span("sketch.fold_launch"):
            self._local = self._fold(self._local, *args)
        obs.count("sketch.fold_launches")
        obs.count("sketch.fold_pad_rows",
                  args[-1].shape[0] - raw_items.shape[0])
        for s in range(self.n_shards):
            sl = slice(s * per, (s + 1) * per)
            for j, g in enumerate(self.hspec.base.partition):
                self._shard_pools[s][j].offer(items[sl][:, list(g)],
                                              freqs[sl])
        self._dirty = True
        self._pools_dirty = True
        self._blocks_since_sync += 1
        if self.sync_every and self._blocks_since_sync >= self.sync_every:
            self.sync()
        # double-write window: the successor service pads/splits the raw
        # block itself, exactly like a fresh service would -- the padded
        # copy above must NOT leak into it
        self._migration_tick(raw_items, raw_freqs)

    def _fold_inputs(self, items: np.ndarray, freqs: np.ndarray, per: int):
        """The fold's arguments after the tables, for a block padded to
        ``per`` rows a shard."""
        if self._kh is None:
            return (tuple(st.params for st in self._merged.states),
                    jnp.asarray(items), jnp.asarray(freqs))
        from repro.kernels.ops import fold_digits

        b = self._kh.block_b
        launch = -(-per // b) * b
        if launch != per:
            n = self.n_shards
            items = np.pad(items.reshape(n, per, -1),
                           ((0, 0), (0, launch - per), (0, 0))
                           ).reshape(n * launch, -1)
            freqs = np.pad(freqs.reshape(n, per),
                           ((0, 0), (0, launch - per))).reshape(-1)
        return (self._kh.params, fold_digits(self.hspec, items),
                freqs.astype(self._kh.table.dtype))

    # -- hot spec migration hooks (serving/migration.MigratingSurface) ------

    def _build_successor(self, new_spec: sk.SketchSpec,
                         key: jax.Array) -> "ShardedTopKService":
        """A fresh service on ``new_spec`` over the SAME mesh/data axes
        (same pool capacity, sync cadence, table dtype).  Shard-count
        invariance is preserved end to end: the successor is itself
        bit-identical across shard counts."""
        return ShardedTopKService(
            new_spec, key, self.mesh, data_axes=self.data_axes,
            max_candidates_per_group=self.max_candidates,
            sync_every=self.sync_every, use_kernel=self.use_kernel,
            use_update_kernel=self.use_update_kernel, dtype=self._dtype)

    def _adopt(self, inc: "ShardedTopKService") -> None:
        """Adopt the successor's state wholesale; free the old tables.

        The successor's jit-cached fold/merge wrappers come along (they
        close over the successor's static spec/mesh config, which is
        exactly this service's config from here on); the old wrappers,
        local/merged tables, and pools lose their last references.
        """
        self.hspec = inc.hspec
        self._kh = inc._kh
        self._merged = inc._merged
        self._local = inc._local
        self._dirty = inc._dirty
        self._pools_dirty = inc._pools_dirty
        self._blocks_since_sync = inc._blocks_since_sync
        self._shard_pools = inc._shard_pools
        self._global_pools = inc._global_pools
        self.total = inc.total
        self._fold = inc._fold
        self._merge = inc._merge

    # -- sync (explicit psum point) -----------------------------------------

    def sync(self) -> None:
        """psum-merge local deltas into the serving snapshot.

        Tables: one program (``sharded_merge``) all-reduces the lazily
        accumulated local tables, adds them to the merged tables (exact by
        linearity) and returns the donated locals zeroed.  The
        candidate-pool fold is deferred to the first query that reads
        ``candidates()`` -- the fold is pure host-side dict work with no
        collective, so paying it per sync (per block at sync_every=1)
        would burden the ingest hot path for nothing.
        """
        if not self._dirty:
            return
        with obs.span("sketch.merge"):
            tables, self._local = self._merge(self._local,
                                              self._merged_tables())
            self._set_merged_tables(tables)
        obs.count("sketch.merges")
        obs.count("sketch.merge_bytes",
                  sum(t.size * t.dtype.itemsize for t in tables))
        self._dirty = False
        self._blocks_since_sync = 0

    def _ensure_synced(self) -> None:
        if self._dirty:
            self.sync()

    # -- elastic N->M re-meshing --------------------------------------------

    def remesh(self, new_mesh, *,
               data_axes: Optional[Tuple[str, ...]] = None) -> None:
        """Move this service onto a different mesh (grow or shrink), live.

        Exact by linearity, no drain needed: ``sync()`` psum-merges every
        survivor shard's local deltas into the replicated serving tables,
        then the merged state is re-placed, replicated, on the new mesh
        (the ``merged`` setter) with FRESH zero locals on the new data
        axes -- merged-plus-zeros is the same sum as any
        other split, so queries before and after the remesh are
        bit-identical, at any N -> M.  Candidate pools fold into the new
        shard 0 (exact union under capacity, the same argument as
        ``to_sharded``); subsequent ingest fills all M shards' pools.

        The jit-cached shard_map wrappers are REBUILT for the new mesh:
        the old lambdas close over the old mesh at trace time, so reusing
        them would silently replay executables compiled for the old
        device set.

        Refused mid-migration (the successor would need the same remesh).
        """
        from repro.launch.mesh import sketch_data_axes

        require_not_migrating(self._migration, "ShardedTopKService.remesh")
        self.sync()
        if data_axes is None:
            data_axes = sketch_data_axes(new_mesh)
        data_axes = tuple(data_axes)
        new_n = int(np.prod([new_mesh.shape[a] for a in data_axes],
                            dtype=np.int64))
        # fold every old shard's pools before the shard list is resized
        folded = [SpaceSaving.fold([pools[j] for pools in self._shard_pools])
                  for j in range(len(self._global_pools))]
        self.mesh = new_mesh
        self.data_axes = data_axes
        self.n_shards = new_n
        # merged tables + params are logically replicated; the setter
        # re-places them on the new device set so nothing still lives on a
        # lost device
        self.merged = self.merged
        self._local = self._zero_locals()
        self._shard_pools = (
            [folded]
            + [[SpaceSaving(self.max_candidates, len(g))
                for g in self.hspec.base.partition]
               for _ in range(new_n - 1)])
        self._pools_dirty = True
        self._dirty = False
        self._blocks_since_sync = 0
        self._build_jit_wrappers()

    # -- durable state (serving/recovery.py snapshot currency) ---------------

    def _config_fingerprint(self) -> np.ndarray:
        desc = (f"sharded|{self.hspec.base!r}|mode={self.mode}"
                f"|dtype={jnp.dtype(self._dtype)}|cap={self.max_candidates}")
        return np.frombuffer(desc.encode(), dtype=np.uint8).copy()

    def state_dict(self) -> dict:
        """Full service state as a flat ``{key: ndarray}`` mapping.

        Syncs first, so the snapshot is the CANONICAL form -- merged
        tables hold everything ingested, locals are zero.  The sync is
        query-bit-neutral (any query would have forced the same psum), so
        "snapshot then crash then restore" and "never crashed" agree
        bitwise.  The fingerprint deliberately excludes the mesh/shard
        count: a 4-shard snapshot restores into a 2-shard service (pools
        fold into shard 0, same exactness argument as ``remesh``).
        """
        if self._migration is not None:
            raise ValueError(
                "cannot checkpoint a service mid-migration: the warmup "
                "successor's state is transient; call abort_migration() to "
                "roll back to the active surface (or wait for cutover), "
                "then snapshot")
        self.sync()
        out = {
            "meta.total": np.asarray(self.total, dtype=np.int64),
            "meta.n_shards": np.asarray(self.n_shards, dtype=np.int64),
            "meta.fingerprint": self._config_fingerprint(),
            "params.q": np.asarray(self.merged.states[-1].params.q),
            "params.r": np.asarray(self.merged.states[-1].params.r),
        }
        for i, st in enumerate(self.merged.states):
            out[f"level{i}.table"] = np.asarray(st.table)
        for s, pools in enumerate(self._shard_pools):
            for j, p in enumerate(pools):
                for k, v in p.state_dict().items():
                    out[f"shard{s}.pool{j}.{k}"] = v
        return out

    def load_state_dict(self, sd: dict) -> None:
        """Restore state saved by :meth:`state_dict`; bit-exact round trip.

        When the saved shard count matches, every shard's pool is restored
        in place; otherwise all saved pools fold into shard 0 (exact union
        under capacity) -- either way the merged tables, totals, and query
        output are bit-identical to the snapshotted service's.
        """
        fp = self._config_fingerprint()
        got = np.asarray(sd["meta.fingerprint"], dtype=np.uint8)
        if not np.array_equal(fp, got):
            raise ValueError(
                "sharded state_dict fingerprint mismatch: saved "
                f"{bytes(got).decode(errors='replace')!r}, this service is "
                f"{bytes(fp).decode(errors='replace')!r}")
        base = sk.SketchParams(q=jnp.asarray(sd["params.q"]),
                               r=jnp.asarray(sd["params.r"]))
        self.merged = hh.HierarchyState(states=tuple(
            sk.SketchState(params=hh.level_params(self.hspec, base, i),
                           table=jnp.asarray(sd[f"level{i}.table"]))
            for i in range(self.hspec.n_levels)))
        self._local = self._zero_locals()
        self.total = int(sd["meta.total"])
        self._dirty = False
        self._blocks_since_sync = 0
        saved_shards = int(sd["meta.n_shards"])

        def load_pool(s: int, j: int) -> SpaceSaving:
            p = SpaceSaving(self.max_candidates,
                            len(self.hspec.base.partition[j]))
            p.load_state(sd[f"shard{s}.pool{j}.rows"],
                         sd[f"shard{s}.pool{j}.counts"],
                         sd[f"shard{s}.pool{j}.errs"])
            return p

        n_groups = len(self.hspec.base.partition)
        if saved_shards == self.n_shards:
            self._shard_pools = [[load_pool(s, j) for j in range(n_groups)]
                                 for s in range(saved_shards)]
        else:
            folded = [SpaceSaving.fold([load_pool(s, j)
                                        for s in range(saved_shards)])
                      for j in range(n_groups)]
            self._shard_pools = (
                [folded]
                + [[SpaceSaving(self.max_candidates, len(g))
                    for g in self.hspec.base.partition]
                   for _ in range(self.n_shards - 1)])
        self._pools_dirty = True

    # -- queries (descent against the merged level tables) ------------------

    def state(self) -> hh.HierarchyState:
        """The merged (serving-snapshot) hierarchy state."""
        self._ensure_synced()
        return self.merged

    def candidates(self) -> List[np.ndarray]:
        """Per-group candidate arrays from the folded global pools.

        Rows come sorted lexicographically and distinct from
        ``SpaceSaving.values``, so the descent -- and hence top-k tie
        order -- never depends on the shard count.  The global pools
        are (re-)folded here from the cumulative shard pools
        with the mergeable-summaries rule when ingest has run since the
        last fold; recomputing from scratch avoids compounding fold floors
        fold over fold.
        """
        self._ensure_synced()
        if self._pools_dirty:
            with obs.span("sketch.pool_fold"):
                self._global_pools = [
                    SpaceSaving.fold([pools[j] for pools in self._shard_pools])
                    for j in range(len(self._global_pools))
                ]
            obs.count("sketch.pool_folds")
            self._pools_dirty = False
        return [p.values() for p in self._global_pools]

    def heavy_hitters(self, threshold: int,
                      candidates: Optional[List[np.ndarray]] = None,
                      ) -> Tuple[np.ndarray, np.ndarray]:
        """Every key estimated >= threshold, from the merged tables."""
        self._ensure_synced()
        if candidates is None:
            candidates = self.candidates()
        return hh.find_heavy_hitters(
            self.hspec, self.merged, threshold, candidates,
            use_kernel=self.use_kernel)

    def topk(self, k: int, min_threshold: Optional[int] = None,
             ) -> Tuple[np.ndarray, np.ndarray]:
        self._ensure_synced()
        return threshold_descent_topk(
            self.heavy_hitters, self.candidates(), k, total=self.total,
            n_modules=self.hspec.base.schema.modularity,
            min_threshold=min_threshold)
