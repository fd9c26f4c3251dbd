"""Sketch-serving stack: the streaming top-k endpoint + the async engine.

This module is the sketch half of the serving split (the model half lives
in serving/model_engine.py; both sit behind the submit/flush protocol of
serving/protocol.py).  Two layers:

:class:`SketchTopKEndpoint`
    the single-shard hierarchical heavy-hitter endpoint -- synchronous
    ingest/query, hot spec migration via the MigratingSurface mixin
    (serving/migration.py), promotion to a sharded service, cross-shard
    merge.  Unchanged semantics from before the split;
    ``repro.serving.engine`` re-exports it for old callers.

:class:`SketchServeEngine`
    the async serving engine every sketch surface (endpoint, sharded,
    windowed) can sit behind:

      * **pipelined ingest** -- on the plain linear endpoint the hash
        cascade of block k+1 is dispatched while block k's fold is still
        executing against the donated, ping-ponging table buffers
        (core.hierarchy.stage_indices / fold_indices); bit-identical to
        synchronous ingest because the split factors ``update_jit``
        exactly;
      * **snapshot queries with a staleness bound** -- queries run against
        a copied table snapshot; ``max_staleness`` bounds how much stream
        mass may have been ingested since the snapshot was taken
        (0 = always refresh first, bit-identical to the synchronous
        surfaces; None = only explicit ``sync()`` refreshes);
      * **batched multi-request descent** -- ``submit`` + ``flush`` pack
        all concurrent threshold/top-k requests into shared per-level
        launches (core.hierarchy.batched_find_heavy_hitters): Q queries
        cost one P x C x Q launch per level instead of Q separate
        descents, each request's answer bit-identical to its serial run;
      * **one integration point each** for background psum sync (sharded
        backends, cadence from the BENCH_SHARDED sweep), auto-tuning
        (AutoTuner.step on every ``sync()``), and migration (double-write
        rides inside the ingest path of the backend itself).
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving.migration import MigratingSurface, require_not_migrating


# --------------------------------------------------------------------------
# streaming top-k endpoint (hierarchical heavy-hitter sketch)
# --------------------------------------------------------------------------

class SketchTopKEndpoint(MigratingSurface):
    """Serving endpoint for streaming heavy-hitter / top-k queries.

    Ingests weighted key blocks (telemetry: routed-token pairs, request
    n-grams, edge events) into a hierarchical composite-hash sketch
    (core/hierarchy.py) and answers

      * ``heavy_hitters(threshold)`` -- every key estimated >= threshold,
      * ``topk(k)`` -- the k keys with the largest estimates,

    without storing the stream.  Memory is the hierarchy's tables plus
    bounded per-group candidate pools.  Admission is a weighted
    space-saving summary per group (core/summary.py): at capacity m, a new
    value evicts the lightest entry instead of being dropped, so any group
    value carrying more than total/m of the stream's weight is in the pool
    no matter how late it first arrives; the no-false-negative guarantee
    of the descent is conditional on that W/m admission bound.

    ``mode="conservative"`` applies the Estan-Varghese conservative update
    per level: strictly tighter estimates, but the tables are no longer
    linear in the stream, so such an endpoint refuses ``merge_from`` (both
    directions) and must stay single-shard -- conservative tables are
    excluded from the cell-wise merge and psum paths of
    core/distributed.py.

    Every ingest path hashes each item ONCE and derives all level indices
    by the mixed-radix cascade (core/hierarchy.py's shared per-group hash
    family).  ``use_update_kernel=True`` additionally folds each block into
    all level tables with the fused single-launch Pallas kernel
    (kernels/ops.KernelHierarchy); linear mode only -- a conservative
    endpoint refuses it (its jnp per-level sequential folds already share
    the cascade's one hash pass).  ``use_kernel=True`` answers the descent
    through the Pallas gather kernel (kernels/hier_query.py).

    Linear endpoints shard naturally: run one per ingest worker and fold
    with ``merge_from`` at query time (tables cell-wise, exact by
    linearity; candidate summaries via the mergeable-summaries rule).

    Hot spec migration (serving/migration.py's MigratingSurface mixin):
    ``begin_migration`` opens a double-write window onto a fresh successor
    endpoint built on a re-tuned spec; queries keep serving from the old
    tables until the successor has absorbed ``warmup`` stream mass, then
    the endpoint cuts over to the successor's state wholesale and frees
    the old tables.  Linear mode only; ``merge_from``/``to_sharded`` are
    refused mid-window (the successor would not see the same state
    change).
    """

    def __init__(self, base_spec, key, *, max_candidates_per_group: int = 1 << 16,
                 use_kernel: bool = False, use_update_kernel: bool = False,
                 dtype=jnp.int32, mode: str = "linear"):
        from repro.core import hierarchy as hh
        from repro.core.summary import SpaceSaving

        if mode not in ("linear", "conservative"):
            raise ValueError(f"mode must be 'linear' or 'conservative', got {mode!r}")
        if use_update_kernel and mode != "linear":
            raise ValueError(
                "the fused update kernel is linear-only: a conservative "
                "endpoint folds on the jnp path (use_update_kernel=False)")
        self._hh = hh
        self._kh = None
        self._migration = None
        self._use_update_kernel = bool(use_update_kernel)
        self.hspec = hh.HierarchySpec.from_spec(base_spec)
        self.state = hh.init_hierarchy(self.hspec, key, dtype=dtype)
        self.max_candidates = int(max_candidates_per_group)
        self.use_kernel = use_kernel
        self.mode = mode
        self.total = 0
        self._pools: List[SpaceSaving] = [
            SpaceSaving(self.max_candidates, len(g))
            for g in base_spec.partition
        ]
        if use_update_kernel:
            from repro.kernels.ops import KernelHierarchy

            # the endpoint's state moves into the kernel wrapper's
            # concatenated padded table; ``state`` stays visible as a
            # lazily sliced view (see the property below)
            self._kh = KernelHierarchy.from_state(self.hspec, self._state)
            self._state = None

    @property
    def state(self):
        """The hierarchy state (assembled lazily on the fused-kernel path)."""
        if self._kh is not None:
            return self._kh.state()
        return self._state

    @state.setter
    def state(self, value) -> None:
        if getattr(self, "_kh", None) is not None:
            self._kh.load_state(value)
        else:
            self._state = value

    def _ingest_active(self, items: np.ndarray, freqs: np.ndarray) -> None:
        """Fold one normalized block into the ACTIVE (serving) tables."""
        if self.mode == "conservative":
            from repro.core.sketch import check_conservative_freqs
            check_conservative_freqs(freqs, self.state.states[0].table.dtype)
        if self._kh is not None:
            # reject kernel-unrepresentable weights BEFORE touching pools
            # or totals, so a failed ingest leaves the endpoint unchanged
            from repro.kernels.ops import check_linear_kernel_freqs
            check_linear_kernel_freqs(freqs, self._kh.table.dtype)
        self.total += int(freqs.sum())
        for j, g in enumerate(self.hspec.base.partition):
            self._pools[j].offer(items[:, list(g)], freqs)
        if self._kh is not None:
            # fused single-launch path: KernelHierarchy pads blocks to its
            # own fixed block_b (zero-frequency pad rows are no-ops)
            self._kh.update(items, freqs)
            return
        # pad blocks to the next power of two so the jitted multi-level
        # update compiles O(log B) variants, not one per block length
        # (zero-frequency pad items are no-ops and stay out of the pools)
        from repro.core.distributed import pad_block_pow2
        items, freqs, _ = pad_block_pow2(items, freqs, 1)
        fold = (self._hh.update_conservative_jit
                if self.mode == "conservative" else self._hh.update_jit)
        self.state = fold(self.hspec, self.state, jnp.asarray(items),
                          jnp.asarray(freqs))

    def ingest(self, items: np.ndarray,
               freqs: Optional[np.ndarray] = None) -> None:
        items = np.asarray(items, dtype=np.uint32)
        if items.shape[0] == 0:
            return
        if freqs is None:
            freqs = np.ones(items.shape[0], dtype=np.int64)
        freqs = np.asarray(freqs)
        self._ingest_active(items, freqs)
        # double-write window: the successor sees every block verbatim
        # (unpadded -- it pads its own blocks exactly like a fresh endpoint
        # would, which keeps cutover bit-identical to a fresh build)
        self._migration_tick(items, freqs)

    # -- two-phase ingest (the serve engine's pipeline) ----------------------

    def stage_block(self, items: np.ndarray,
                    freqs: Optional[np.ndarray] = None) -> Optional["StagedBlock"]:
        """Pipeline stage A: normalize + pad the block, dispatch the cascade.

        Returns a :class:`StagedBlock` whose level indices were computed
        against the CURRENT hash params; nothing is folded and no
        endpoint state changes until :meth:`fold_staged`.  The cascade
        reads only the (never-donated) params, so it runs while a
        previous block's fold is still executing on the donated table
        buffers -- that overlap is the engine's ingest pipeline.

        Plain linear jnp path only: the fused update kernel folds inside
        one launch (nothing to split) and conservative updates read the
        tables they write (no table-free stage exists).
        """
        if self.mode != "linear" or self._kh is not None:
            raise ValueError(
                "stage_block requires the plain linear jnp update path: "
                "conservative updates read the tables during the fold and "
                "the fused update kernel is already a single launch -- use "
                "ingest() on those endpoints")
        items = np.asarray(items, dtype=np.uint32)
        if items.shape[0] == 0:
            return None
        if freqs is None:
            freqs = np.ones(items.shape[0], dtype=np.int64)
        freqs = np.asarray(freqs)
        from repro.core.distributed import pad_block_pow2
        p_items, p_freqs, _ = pad_block_pow2(items, freqs, 1)
        idxs = self._hh.stage_indices(self.hspec, self.state,
                                      jnp.asarray(p_items))
        return StagedBlock(idxs=idxs, freqs=jnp.asarray(p_freqs),
                           raw_items=items, raw_freqs=freqs,
                           mass=int(freqs.sum()))

    def fold_staged(self, staged: Optional["StagedBlock"]) -> None:
        """Pipeline stage B: fold a staged block's pre-computed indices.

        ``fold_staged(stage_block(items, freqs))`` is bit-identical to
        ``ingest(items, freqs)`` -- same totals, same pool offers, same
        tables (fold_indices == update_jit by construction), same
        migration double-write.  The caller must not swap the endpoint's
        state between stage and fold (the engine folds before staging the
        next block, so a migration cutover can never strand staged
        indices computed under the old params).
        """
        if staged is None:
            return
        self.total += staged.mass
        for j, g in enumerate(self.hspec.base.partition):
            self._pools[j].offer(staged.raw_items[:, list(g)],
                                 staged.raw_freqs)
        self._state = self._hh.fold_indices(self._state, staged.idxs,
                                            staged.freqs)
        self._migration_tick(staged.raw_items, staged.raw_freqs)

    def candidates(self) -> List[np.ndarray]:
        """Per-group candidate value arrays from the space-saving pools."""
        return [p.values() for p in self._pools]

    # -- durable state (serving/recovery.py snapshot currency) ----------------

    def _config_fingerprint(self) -> np.ndarray:
        dtype = self.state.states[0].table.dtype
        desc = (f"endpoint|{self.hspec.base!r}|mode={self.mode}"
                f"|dtype={dtype}|cap={self.max_candidates}")
        return np.frombuffer(desc.encode(), dtype=np.uint8).copy()

    def state_dict(self) -> "dict":
        """Full endpoint state as a flat ``{key: ndarray}`` mapping.

        Covers everything a bit-exact restore needs: every level table,
        the shared hash params (the finest level's arrays -- every
        coarser level's params are prefix slices of them), the stream
        total, and each group's space-saving pool in insertion order.
        A config fingerprint guards against restoring into an endpoint
        built on a different spec/mode/dtype.

        Refused mid-migration: the successor's tables are transient
        double-write state with no stable identity to restore into --
        call ``abort_migration()`` (or wait for cutover) first.
        """
        if self._migration is not None:
            raise ValueError(
                "cannot checkpoint an endpoint mid-migration: the warmup "
                "successor's state is transient; call abort_migration() to "
                "roll back to the active surface (or wait for cutover), "
                "then snapshot")
        state = self.state
        out = {
            "meta.total": np.asarray(self.total, dtype=np.int64),
            "meta.fingerprint": self._config_fingerprint(),
            # finest level's params ARE the full shared family; coarser
            # levels' params are rebuilt as prefix slices on load
            "params.q": np.asarray(state.states[-1].params.q),
            "params.r": np.asarray(state.states[-1].params.r),
        }
        for i, st in enumerate(state.states):
            out[f"level{i}.table"] = np.asarray(st.table)
        for j, p in enumerate(self._pools):
            for k, v in p.state_dict().items():
                out[f"pool{j}.{k}"] = v
        return out

    def load_state_dict(self, sd: "dict") -> None:
        """Restore state saved by :meth:`state_dict`; bit-exact round trip."""
        from repro.core import sketch as sk

        fp = self._config_fingerprint()
        got = np.asarray(sd["meta.fingerprint"], dtype=np.uint8)
        if not np.array_equal(fp, got):
            raise ValueError(
                "endpoint state_dict fingerprint mismatch: saved "
                f"{bytes(got).decode(errors='replace')!r}, this endpoint is "
                f"{bytes(fp).decode(errors='replace')!r}")
        base = sk.SketchParams(q=jnp.asarray(sd["params.q"]),
                               r=jnp.asarray(sd["params.r"]))
        states = []
        for i in range(self.hspec.n_levels):
            params = self._hh.level_params(self.hspec, base, i)
            states.append(sk.SketchState(
                params=params, table=jnp.asarray(sd[f"level{i}.table"])))
        self.state = self._hh.HierarchyState(states=tuple(states))
        self.total = int(sd["meta.total"])
        for j, p in enumerate(self._pools):
            p.load_state(sd[f"pool{j}.rows"], sd[f"pool{j}.counts"],
                         sd[f"pool{j}.errs"])

    # -- hot spec migration hooks (serving/migration.MigratingSurface) -------

    def _build_successor(self, new_spec, key) -> "SketchTopKEndpoint":
        return SketchTopKEndpoint(
            new_spec, key,
            max_candidates_per_group=self.max_candidates,
            use_kernel=self.use_kernel,
            use_update_kernel=self._use_update_kernel,
            dtype=self.state.states[0].table.dtype, mode="linear")

    def _adopt(self, inc: "SketchTopKEndpoint") -> None:
        """Adopt the successor's state wholesale; free the old tables.

        After this, the endpoint is bit-identical to a fresh endpoint
        built on the new spec (same key) and fed exactly the blocks since
        ``begin_migration`` -- the successor IS that endpoint.  ``total``
        restarts at the post-warmup-start mass: estimates and totals
        describe the same (new) stream window, which is what the top-k
        descent's threshold scaling assumes.
        """
        self.hspec = inc.hspec
        self._kh = inc._kh
        self._state = inc._state
        self._pools = inc._pools
        self.total = inc.total
        # old tables/pools: last references dropped above -> freed

    def heavy_hitters(self, threshold: int,
                      candidates: Optional[List[np.ndarray]] = None,
                      ) -> Tuple[np.ndarray, np.ndarray]:
        if candidates is None:
            candidates = self.candidates()
        return self._hh.find_heavy_hitters(
            self.hspec, self.state, threshold, candidates,
            use_kernel=self.use_kernel)

    def topk(self, k: int,
             min_threshold: Optional[int] = None) -> Tuple[np.ndarray, np.ndarray]:
        """Top-k by estimate: geometric threshold descent until k found.

        See :func:`repro.serving.sharded_topk.threshold_descent_topk` (the
        descent is shared with the sharded service) for the
        ``min_threshold`` semantics.  Candidates are hoisted: the pools
        don't change mid-descent.
        """
        from repro.serving.sharded_topk import threshold_descent_topk

        return threshold_descent_topk(
            self.heavy_hitters, self.candidates(), k, total=self.total,
            n_modules=self.hspec.base.schema.modularity,
            min_threshold=min_threshold)

    def to_sharded(self, mesh, *, data_axes=None,
                   sync_every: Optional[int] = 1,
                   ) -> "object":
        """Promote this single-shard endpoint to a ShardedTopKService.

        Carries over the hierarchy tables, hash params, candidate pools,
        and stream total; subsequent ingest runs sharded over the mesh.
        Linear endpoints only: a conservative endpoint's tables are not
        linear in the stream and must never enter the psum sync path, so
        promotion is refused (same contract as merge_from).
        """
        from repro.core.sketch import SketchState
        from repro.core.summary import SpaceSaving
        from repro.serving.sharded_topk import ShardedTopKService

        require_not_migrating(self._migration,
                              "SketchTopKEndpoint.to_sharded")
        if self.mode != "linear":
            raise ValueError(
                "to_sharded is only defined for linear endpoints: "
                "conservative tables cannot be psum-merged, so a "
                "conservative endpoint must stay single-shard")
        svc = ShardedTopKService(
            self.hspec.base, jax.random.PRNGKey(0), mesh,
            data_axes=data_axes,
            max_candidates_per_group=self.max_candidates,
            sync_every=sync_every, use_kernel=self.use_kernel,
            dtype=self.state.states[0].table.dtype)
        # the service's freshly drawn params are discarded: the promoted
        # state keeps this endpoint's params so existing tables stay valid.
        # Tables are COPIED, not aliased: the endpoint's ingest path
        # donates its table buffers (hierarchy.update_jit), so a later
        # ep.ingest() would delete buffers the service still reads.
        # Params are never donated, so sharing them is safe.
        state = self.state
        svc.merged = self._hh.HierarchyState(states=tuple(
            SketchState(params=st.params, table=jnp.array(st.table))
            for st in state.states))
        svc.total = self.total
        svc._shard_pools[0] = [SpaceSaving.fold([p]) for p in self._pools]
        svc._global_pools = [SpaceSaving.fold([p]) for p in self._pools]
        return svc

    def merge_from(self, other: "SketchTopKEndpoint") -> None:
        """Fold another endpoint's sketch + pools in (cross-shard merge).

        Only defined for linear endpoints: conservative tables are not
        linear in the stream, so a cell-wise sum of two conservatively
        built hierarchies is not the hierarchy of the union stream --
        conservative endpoints are single-shard by construction and
        rejected here (both directions).

        Shards must share the base spec and hash parameters (same spec +
        PRNG key): cell-wise sums of tables hashed with different params --
        or with the same params but permuted partition axes -- are garbage,
        so mismatches are rejected rather than silently accepted.
        """
        require_not_migrating(self._migration,
                              "SketchTopKEndpoint.merge_from")
        require_not_migrating(other._migration,
                              "SketchTopKEndpoint.merge_from (source side)")
        if self.mode != "linear" or other.mode != "linear":
            raise ValueError(
                "merge_from is only defined for linear endpoints: "
                "conservative tables cannot be merged cell-wise")
        if self.hspec.base != other.hspec.base:
            raise ValueError(
                "merge_from requires identical base specs on both endpoints")
        for sa, sb in zip(self.state.states, other.state.states):
            if not (np.array_equal(np.asarray(sa.params.q), np.asarray(sb.params.q))
                    and np.array_equal(np.asarray(sa.params.r), np.asarray(sb.params.r))):
                raise ValueError(
                    "merge_from requires identical hash params on both "
                    "endpoints (build them from the same spec and key)")
        self.state = self._hh.merge(self.state, other.state)
        self.total += other.total
        for mine, theirs in zip(self._pools, other._pools):
            mine.merge_from(theirs)


@dataclasses.dataclass
class StagedBlock:
    """One in-flight pipelined block: dispatched cascade + deferred fold."""
    idxs: Tuple[jax.Array, ...]    # per-level cell indices (async, in flight)
    freqs: jax.Array               # padded frequencies matching idxs
    raw_items: np.ndarray          # unpadded block (pools + double-write)
    raw_freqs: np.ndarray
    mass: int                      # int(raw_freqs.sum())


# --------------------------------------------------------------------------
# async serve engine: pipelined ingest, snapshots, batched descent
# --------------------------------------------------------------------------

@dataclasses.dataclass
class SketchQuery:
    """One serving request for the engine's submit/flush lifecycle.

    ``kind`` is ``"topk"`` (uses ``k``/``min_threshold``) or
    ``"heavy_hitters"`` (uses ``threshold``).  ``items``/``est`` carry the
    answer after the flush that served it, exactly what the synchronous
    ``topk``/``heavy_hitters`` call would have returned against the same
    snapshot.
    """
    rid: int
    kind: str                                  # 'topk' | 'heavy_hitters'
    k: int = 0
    threshold: int = 0
    min_threshold: Optional[int] = None
    items: Optional[np.ndarray] = None
    est: Optional[np.ndarray] = None
    done: bool = False


@dataclasses.dataclass(frozen=True)
class SketchSnapshot:
    """An immutable query view of a backend: copied tables + frozen pools.

    ``total`` is the backend's stream mass when taken (seeds the top-k
    threshold descent); ``mass`` is the ENGINE's cumulative ingested mass
    at the same instant -- the staleness watermark.  The two differ
    exactly when the backend has restarted its own total (migration
    cutover, window advance), which is why staleness is measured against
    the engine counter and never against ``backend.total``.
    """
    hspec: Any
    state: Any                                 # HierarchyState, tables copied
    candidates: List[np.ndarray]
    total: int
    mass: int


class SketchServeEngine:
    """Async serving engine over any sketch backend (endpoint/sharded/windowed).

    ``backend`` is a :class:`SketchTopKEndpoint`, a
    :class:`~repro.serving.sharded_topk.ShardedTopKService`, or a
    :class:`~repro.serving.windowed_topk.WindowedTopKService` -- anything
    with ``ingest``/``state``/``candidates``/``total``/``hspec``.  The
    engine owns three asynchrony mechanisms, all individually inert at
    their default settings:

    **Pipelined ingest.**  On a plain linear endpoint (no fused update
    kernel, not conservative), each ingested block is only *staged*: its
    hash cascade is dispatched immediately, but the fold into the donated
    table buffers is deferred until the next ingest (or a sync) -- so the
    cascade of block k+1 overlaps the fold of block k.  The fold always
    runs BEFORE the next stage, so a migration cutover triggered by a
    fold can never strand staged indices computed under the old params.
    Every other backend (kernel, conservative, sharded, windowed, or
    mid-migration) ingests synchronously through the same entry point.
    Pipelined or not, the tables after a drain are bit-identical to
    direct backend ingest.

    **Snapshot queries with a staleness bound.**  Queries never touch the
    live tables; they run against a :class:`SketchSnapshot` whose tables
    were COPIED at the last refresh (the ingest path donates its buffers,
    so aliasing them would read freed memory).  ``max_staleness`` bounds
    the stream mass ingested since the snapshot: a query whose bound is
    exceeded triggers a refresh first.  ``max_staleness=0`` refreshes on
    every post-ingest query -- bit-identical to the synchronous surfaces
    (enforced by tests/test_serve_engine.py); ``None`` means only explicit
    :meth:`sync` refreshes (unbounded staleness, maximum overlap).

    **Batched multi-request descent.**  :meth:`submit` queues
    :class:`SketchQuery` requests; :meth:`flush` serves ALL of them
    against one snapshot, packing every still-active request's per-level
    candidate grid into a single launch
    (core.hierarchy.batched_find_heavy_hitters).  Each request's descent
    trajectory -- thresholds tried, pruning, final answer -- is
    bit-identical to its own serial ``topk``/``heavy_hitters`` call.
    The engine satisfies serving/protocol.ServeEngineProtocol, same as
    the model stack's SlotScheduler.

    Background maintenance plugs in at exactly one place each: a sharded
    backend's psum merge runs every ``shard_sync_every`` ingested blocks
    (default 4, the BENCH_SHARDED sweep's knee -- amortizes the
    all-reduce without unbounded local-delta growth); an optional
    ``tuner`` (serving/autotune.AutoTuner) steps on every :meth:`sync`,
    so retune decisions and migrations happen at snapshot boundaries;
    migration double-writes ride inside the backend's own ingest/fold.

    Thread safety: one re-entrant lock around every entry point, so an
    ingest thread and query threads can share the engine (see
    examples/async_serving.py); queries serialize against ingest but
    never against device work already dispatched.
    """

    def __init__(self, backend, *, max_staleness: Optional[int] = 0,
                 shard_sync_every: Optional[int] = 4, tuner=None):
        self.backend = backend
        self.max_staleness = max_staleness
        self.shard_sync_every = shard_sync_every
        self.tuner = tuner
        self._lock = threading.RLock()
        self._staged: Optional[StagedBlock] = None
        self._mass = 0                       # engine staleness watermark
        self._blocks_since_psum = 0
        self._queue: List[SketchQuery] = []
        self._next_rid = 0
        self._is_sharded = hasattr(backend, "sync") and hasattr(backend, "n_shards")
        self._snap: Optional[SketchSnapshot] = None
        self._snap = self._take_snapshot()

    # -- ingest side ---------------------------------------------------------

    def _can_pipeline(self) -> bool:
        b = self.backend
        return (isinstance(b, SketchTopKEndpoint) and b.mode == "linear"
                and b._kh is None and not b.migrating)

    def ingest(self, items: np.ndarray,
               freqs: Optional[np.ndarray] = None) -> None:
        """Ingest one weighted block (pipelined where the backend allows)."""
        with self._lock:
            items = np.asarray(items, dtype=np.uint32)
            if items.shape[0] == 0:
                return
            if freqs is None:
                freqs = np.ones(items.shape[0], dtype=np.int64)
            freqs = np.asarray(freqs)
            self._fold_pending()             # fold k before staging k+1
            if self._can_pipeline():
                self._staged = self.backend.stage_block(items, freqs)
            else:
                self.backend.ingest(items, freqs)
            self._mass += int(freqs.sum())
            if self._is_sharded and self.shard_sync_every:
                self._blocks_since_psum += 1
                if self._blocks_since_psum >= self.shard_sync_every:
                    # background psum cadence: merge local deltas into the
                    # backend's serving tables WITHOUT refreshing the
                    # engine snapshot (that stays on the staleness clock)
                    self._fold_pending()
                    self.backend.sync()
                    self._blocks_since_psum = 0

    def _fold_pending(self) -> None:
        if self._staged is not None:
            staged, self._staged = self._staged, None
            self.backend.fold_staged(staged)

    def drain(self) -> None:
        """Fold any staged block; the backend then holds every ingested item."""
        with self._lock:
            self._fold_pending()

    def advance(self) -> None:
        """Epoch clock passthrough for windowed backends.

        Advancing changes the window tables WITHOUT moving stream mass, so
        the staleness bound alone cannot see it -- the snapshot is
        invalidated explicitly and the next query refreshes.
        """
        with self._lock:
            self._fold_pending()
            self.backend.advance()
            self._snap = None

    # -- snapshot / staleness -------------------------------------------------

    def _take_snapshot(self) -> SketchSnapshot:
        from repro.core import hierarchy as hh
        from repro.core import sketch as sk

        b = self.backend
        st = b.state
        if callable(st):                     # sharded/windowed expose a method
            st = st()
        state = hh.HierarchyState(states=tuple(
            sk.SketchState(params=s.params, table=jnp.array(s.table))
            for s in st.states))
        return SketchSnapshot(hspec=b.hspec, state=state,
                              candidates=b.candidates(),
                              total=int(b.total), mass=self._mass)

    @property
    def staleness(self) -> int:
        """Stream mass ingested since the serving snapshot was taken."""
        with self._lock:
            return self._mass - self._snap.mass if self._snap else self._mass

    @property
    def ingested_mass(self) -> int:
        """The engine's cumulative-mass watermark (staleness clock)."""
        with self._lock:
            return self._mass

    def restore_watermark(self, mass: int) -> None:
        """Reset the staleness clock after a backend restore.

        The recovery layer restores the backend's state out-of-band, so
        the engine's cumulative-mass counter must be put back to the saved
        watermark (otherwise staleness would measure against a counter
        from a different life).  Retakes the snapshot so queries see the
        restored tables immediately.
        """
        with self._lock:
            self._staged = None             # staged indices from the old life
            self._mass = int(mass)
            self._blocks_since_psum = 0
            self._snap = self._take_snapshot()

    def sync(self) -> SketchSnapshot:
        """Drain the pipeline, psum-merge (sharded), refresh the snapshot,
        and tick the auto-tuner.  The one barrier in the engine."""
        with self._lock:
            self._fold_pending()
            if self._is_sharded:
                self.backend.sync()
                self._blocks_since_psum = 0
            self._snap = self._take_snapshot()
            if self.tuner is not None:
                # retune on snapshot boundaries only: a migration decision
                # here opens the double-write window inside the backend's
                # own ingest path; queries keep serving old tables per the
                # migration contract, which this snapshot already is
                self.tuner.step()
            return self._snap

    def _fresh_snapshot(self) -> SketchSnapshot:
        if self._snap is None or (
                self.max_staleness is not None
                and self._mass - self._snap.mass > self.max_staleness):
            self.sync()
        return self._snap

    # -- synchronous query surface (one request) ------------------------------

    def heavy_hitters(self, threshold: int) -> Tuple[np.ndarray, np.ndarray]:
        """Every key estimated >= threshold, within the staleness bound."""
        from repro.core import hierarchy as hh

        with self._lock:
            snap = self._fresh_snapshot()
            return hh.find_heavy_hitters(
                snap.hspec, snap.state, threshold, snap.candidates,
                use_kernel=self.backend.use_kernel)

    def topk(self, k: int, min_threshold: Optional[int] = None,
             ) -> Tuple[np.ndarray, np.ndarray]:
        """The k keys with the largest estimates, within the staleness bound."""
        from repro.core import hierarchy as hh
        from repro.serving.sharded_topk import threshold_descent_topk

        with self._lock:
            snap = self._fresh_snapshot()

            def hh_fn(thr, candidates):
                return hh.find_heavy_hitters(
                    snap.hspec, snap.state, thr, candidates,
                    use_kernel=self.backend.use_kernel)

            return threshold_descent_topk(
                hh_fn, snap.candidates, k, total=snap.total,
                n_modules=snap.hspec.base.schema.modularity,
                min_threshold=min_threshold)

    # -- batched query surface (submit/flush protocol) -------------------------

    def submit_topk(self, k: int,
                    min_threshold: Optional[int] = None) -> SketchQuery:
        """Queue a top-k request for the next :meth:`flush`."""
        return self.submit(SketchQuery(rid=-1, kind="topk", k=int(k),
                                       min_threshold=min_threshold))

    def submit_heavy_hitters(self, threshold: int) -> SketchQuery:
        """Queue a heavy-hitters request for the next :meth:`flush`."""
        return self.submit(SketchQuery(rid=-1, kind="heavy_hitters",
                                       threshold=int(threshold)))

    def submit(self, request: SketchQuery) -> SketchQuery:
        with self._lock:
            if request.kind not in ("topk", "heavy_hitters"):
                raise ValueError(
                    f"kind must be 'topk' or 'heavy_hitters', got "
                    f"{request.kind!r}")
            request.rid = self._next_rid
            self._next_rid += 1
            self._queue.append(request)
            return request

    def flush(self) -> List[SketchQuery]:
        """Serve every queued request against ONE snapshot, batched.

        All requests see the same snapshot (mutually consistent answers);
        each individual answer is bit-identical to the serial
        ``topk``/``heavy_hitters`` call against that snapshot.  Returns
        the requests in submission order.
        """
        with self._lock:
            reqs, self._queue = self._queue, []
            if not reqs:
                return []
            snap = self._fresh_snapshot()
            self._serve_batched(snap, reqs)
            return reqs

    def _serve_batched(self, snap: SketchSnapshot,
                       reqs: List[SketchQuery]) -> None:
        """The packed threshold descent: one launch per level per round.

        Replicates :func:`~repro.serving.sharded_topk.threshold_descent_topk`
        per request -- same starting threshold ``max(total, 1)``, same
        ``max(1, total >> 17)`` floor, same geometric /4 schedule, same
        stop condition -- but evaluates every still-descending request's
        round together via core.hierarchy.batched_find_heavy_hitters.
        Requests drop out of the batch as they complete.
        """
        from repro.core import hierarchy as hh

        total = snap.total
        thr, floor = {}, {}
        for r in reqs:
            if r.kind == "heavy_hitters":
                thr[r.rid] = int(r.threshold)
                floor[r.rid] = None          # single evaluation, no descent
            else:
                m = (r.min_threshold if r.min_threshold is not None
                     else max(1, total >> 17))
                floor[r.rid] = int(m)
                thr[r.rid] = max(total, 1)

        # a floor above the starting threshold never evaluates at all in
        # the serial descent (`while thr >= min_threshold` fails upfront)
        n_mods = snap.hspec.base.schema.modularity
        pending = []
        for r in reqs:
            if r.kind == "topk" and thr[r.rid] < floor[r.rid]:
                r.items = np.zeros((0, n_mods), np.uint32)
                r.est = np.zeros((0,), np.int64)
                r.done = True
            else:
                pending.append(r)
        while pending:
            results = hh.batched_find_heavy_hitters(
                snap.hspec, snap.state, [thr[r.rid] for r in pending],
                snap.candidates, use_kernel=self.backend.use_kernel)
            nxt = []
            for r, (items, est) in zip(pending, results):
                if r.kind == "heavy_hitters":
                    r.items, r.est, r.done = items, est, True
                elif len(est) >= r.k or thr[r.rid] == floor[r.rid]:
                    r.items, r.est, r.done = items[: r.k], est[: r.k], True
                else:
                    thr[r.rid] = max(floor[r.rid], thr[r.rid] // 4)
                    nxt.append(r)
            pending = nxt
