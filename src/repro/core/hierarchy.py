"""Hierarchical heavy-hitter sketches over composite-hash prefixes.

The composite-hash family (core/sketch.py) factors a key's cell address into
per-group sub-indices with mixed-radix strides.  That factorization induces a
natural *prefix hierarchy*: level L sketches the key restricted to module
groups 0..L of the partition, coarsening one group per level.  Because the
frequency of a prefix upper-bounds the frequency of every full key extending
it, a Count-Min estimate at level L that falls below a threshold prunes the
whole subtree -- the classic hierarchical heavy-hitter recursion (Cormode's
dyadic CM / hierarchical count-sketch), lifted from bit prefixes to the
paper's module-group prefixes.

    level 0 : sketch of group g_1                (coarsest marginal)
    level L : sketch of groups g_1..g_{L+1}
    level m-1: sketch of the full composite key  (== the base SketchSpec)

``find_heavy_hitters(threshold)`` descends the hierarchy: at each level it
extends the surviving prefixes by every candidate value of the next group,
estimates all children in one batched query, and keeps those >= threshold.
Overestimation (CM) + prefix monotonicity give *no false negatives* for any
key whose group values appear in the candidate sets; false positives are
bounded by the per-level CM overestimate.

Shared per-group hash family (ingest cascade)
---------------------------------------------
All levels share ONE per-group hash family: :func:`init_hierarchy` draws the
finest level's params once and every level L uses the prefix slices
``q[:, :chunks(g_1..g_{L+1})]`` and ``r[:, :L+1]``.  Independence argument:
each level's row index is the mixed-radix combination of *independent*
per-group CW hashes, which is exactly the composite hash of the base family
restricted to groups 0..L -- two distinct level-L prefixes differ in some
group j <= L, and conditioning on the other groups' hashes leaves H_j
pairwise independent, so every level's row hash remains pairwise independent
over its own key domain and the per-level CM bounds (Thms 1-3) are
unchanged.  What IS given up is independence *between* levels, which no
per-level guarantee uses (the descent's union bound over levels never
needed cross-level independence).

What sharing buys is the ingest cascade: with shared per-group hashes the
level indices nest exactly,

    idx_L(prefix, v) = idx_{L-1}(prefix) * r_L + H_L(v)
    idx_L            = idx_{m-1} // (r_{L+1} * ... * r_{m-1})

so one hash pass over the full key yields every level's cell index by an
integer division (:func:`hierarchy_indices`).  Every ingest surface runs
this cascade; per-level hashing survives only as the bit-exactness oracle
:func:`update_reference`.  Ingest cost per item is ONE hash pass + L fused
table adds instead of the reference's ~L hash passes + L kernel launches;
the Pallas path (kernels/hier_update.py) folds a stream block into all
level tables in a single launch against the level-concatenated padded
table.  The
conservative update gets the same cascade for its index computation and then
runs the per-level sequential folds (the min couples rows, so the folds
themselves stay per level).

Every level's table is linear in the stream, so a hierarchy merges cell-wise
per level and composes with the distributed runtime (core/distributed.py)
exactly like a single sketch: see :func:`merge` and
:func:`sharded_hierarchy_build`.  The same linearity (plus the shared hash
draw) is what lets core/window.py keep a ring of per-epoch hierarchies that
merge, subtract, and decay cell-wise; docs/architecture.md has the full
layer map and the bit-exactness contracts.

The candidate-extension query is the hot path (P prefixes x C child values
per step).  The mixed radix makes it separable: within level L,

    idx(prefix, v) = idx_prefix * r_L  +  H_L(v)        (stride of g_L is 1)

so the batched query needs only P prefix partial indices and C child partial
indices per row, combined on the fly.  The Pallas path
(kernels/hier_query.py) evaluates the full P x C grid in one launch;
:func:`candidate_partials` computes the two factors for it.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sketch as sk
from repro.core.hashing import KeySchema


# --------------------------------------------------------------------------
# Spec
# --------------------------------------------------------------------------

def level_modules(base: sk.SketchSpec, level: int) -> Tuple[int, ...]:
    """Module indices (into the base schema) covered by levels 0..level,
    ordered group-major -- the column order of level items."""
    return tuple(m for g in base.partition[: level + 1] for m in g)


def level_spec(base: sk.SketchSpec, level: int) -> sk.SketchSpec:
    """The SketchSpec of one hierarchy level: groups 0..level of the base,
    with modules renumbered consecutively in group-major order."""
    mods = level_modules(base, level)
    schema = KeySchema(domains=tuple(base.schema.domains[m] for m in mods))
    part: List[Tuple[int, ...]] = []
    pos = 0
    for g in base.partition[: level + 1]:
        part.append(tuple(range(pos, pos + len(g))))
        pos += len(g)
    return sk.SketchSpec(schema, tuple(part), base.ranges[: level + 1],
                         base.width)


@dataclasses.dataclass(frozen=True)
class HierarchySpec:
    """A stack of composite-hash sketches over successive group prefixes."""
    base: sk.SketchSpec
    levels: Tuple[sk.SketchSpec, ...]

    @staticmethod
    def from_spec(base: sk.SketchSpec) -> "HierarchySpec":
        return HierarchySpec(
            base=base,
            levels=tuple(level_spec(base, l) for l in range(base.n_groups)),
        )

    @property
    def n_levels(self) -> int:
        return len(self.levels)

    @property
    def table_cells(self) -> int:
        """Total cells across all levels (memory overhead vs the base:
        sum_L prod(r_1..r_L) <= h * r/(r-1) for geometric ranges)."""
        return sum(s.width * s.table_size for s in self.levels)

    @functools.cached_property
    def _level_cols(self) -> Tuple[Tuple[int, ...], ...]:
        """Per-level column tuples for :meth:`level_items`, computed once at
        first use and cached on the (frozen) spec -- the old per-call list
        rebuild sat on the ingest hot path."""
        return tuple(tuple(level_modules(self.base, l))
                     for l in range(self.n_levels))

    @functools.cached_property
    def level_divisors(self) -> Tuple[int, ...]:
        """``idx_L = idx_finest // level_divisors[L]`` -- the suffix range
        products of the mixed radix (cascade identity; divisor of the finest
        level is 1)."""
        divs, d = [], 1
        for r in reversed(self.base.ranges):
            divs.append(d)
            d *= int(r)
        return tuple(reversed(divs))

    def level_items(self, level: int, items: np.ndarray | jax.Array):
        """Select/reorder full-key columns into level ``level``'s layout."""
        return items[:, list(self._level_cols[level])]

    def to_schema_order(self, items: np.ndarray) -> np.ndarray:
        """Group-major full-key columns -> original schema module order."""
        mods = self._level_cols[self.n_levels - 1]
        out = np.empty_like(items)
        for pos, m in enumerate(mods):
            out[:, m] = items[:, pos]
        return out


class HierarchyState(NamedTuple):
    states: Tuple[sk.SketchState, ...]   # one per level, coarse -> fine


def level_params(hspec: HierarchySpec, base_params: sk.SketchParams,
                 level: int) -> sk.SketchParams:
    """Level ``level``'s hash params as prefix slices of the finest level's.

    Group-major layout makes groups 0..level's chunk columns the FIRST
    ``total_chunks`` columns of the finest chunk matrix, so slicing q/r gives
    exactly the same per-group hash functions at every level -- the shared
    family underlying the ingest cascade."""
    nc = hspec.levels[level].schema.total_chunks
    return sk.SketchParams(q=base_params.q[:, :nc],
                           r=base_params.r[:, : level + 1])


def init_hierarchy(hspec: HierarchySpec, key: jax.Array,
                   dtype=jnp.int32) -> HierarchyState:
    """Draw ONE shared per-group hash family and zero tables for all levels.

    Every level's params are prefix slices of the finest level's draw (see
    :func:`level_params` and the module header for the independence
    argument).  All cascade entry points (:func:`update`,
    :func:`hierarchy_indices`, the fused Pallas kernel, the distributed
    folds) rely on this shared-prefix invariant; states built here always
    satisfy it."""
    base_params = sk.init_params(hspec.levels[-1], key)
    states = []
    for l, spec_l in enumerate(hspec.levels):
        states.append(sk.SketchState(
            params=level_params(hspec, base_params, l),
            table=jnp.zeros((spec_l.width, spec_l.table_size), dtype=dtype)))
    return HierarchyState(states=tuple(states))


def params_share_prefix(state: HierarchyState) -> bool:
    """Host-side check of the shared-params invariant (concrete arrays only).

    True iff every level's params are the prefix slices of the finest
    level's -- the precondition of every cascade path.  Used by the kernel
    wrappers when importing externally supplied states; the jit'd hot paths
    assume the invariant (init_hierarchy always establishes it)."""
    fine = state.states[-1].params
    fq, fr = np.asarray(fine.q), np.asarray(fine.r)
    for l, st in enumerate(state.states):
        q, r = np.asarray(st.params.q), np.asarray(st.params.r)
        if q.shape[1] > fq.shape[1] or r.shape[1] != l + 1:
            return False
        if not (np.array_equal(q, fq[:, : q.shape[1]])
                and np.array_equal(r, fr[:, : l + 1])):
            return False
    return True


import weakref

_validated_params = weakref.WeakValueDictionary()  # id(q_fine) -> q_fine


def _require_shared_params(state: HierarchyState, entry: str) -> None:
    """Refuse non-shared-params states on the cascade entry points.

    The cascade derives coarse-level cells from the finest index by
    division, which is garbage for states whose levels were drawn
    independently (the pre-cascade layout) -- silently wrong tables, lost
    no-false-negative guarantee.  Concrete states are validated host-side
    once per distinct finest-params array (params persist across blocks,
    so streaming ingest pays the tiny device read a single time and stays
    async afterwards); traced values cannot be inspected, so jit-embedded
    callers rely on the init_hierarchy invariant, same as the distributed
    folds."""
    q = state.states[-1].params.q
    if isinstance(q, jax.core.Tracer):
        return
    if _validated_params.get(id(q)) is q:
        return
    if not params_share_prefix(state):
        raise ValueError(
            f"{entry} requires the shared per-group hash family (level "
            "params must be prefix slices of the finest level's, as drawn "
            "by init_hierarchy); for independently drawn per-level params "
            "use update_reference")
    try:
        _validated_params[id(q)] = q
    except TypeError:
        pass  # non-weakrefable array type: validate again next call


# --------------------------------------------------------------------------
# Stream ops (linear => mergeable)
# --------------------------------------------------------------------------

def hierarchy_indices(hspec: HierarchySpec, fine_params: sk.SketchParams,
                      items: jax.Array) -> Tuple[jax.Array, ...]:
    """Every level's cell indices from ONE hash pass: tuple of uint32[w, B].

    Computes the finest level's composite index (one CW hash per group,
    exactly ``compute_indices`` of ``hspec.levels[-1]`` on the group-major
    columns) and derives each coarser level by the cascade identity
    ``idx_L = idx_finest // prod(r_{L+1}..r_{m-1})`` -- exact, because the
    dropped remainder is precisely the mixed-radix value of the finer
    groups' sub-indices.  Requires the shared-prefix params invariant
    (:func:`init_hierarchy`)."""
    fine = hspec.levels[-1]
    idx_fine = sk.compute_indices(
        fine, fine_params, hspec.level_items(hspec.n_levels - 1, items))
    out = []
    for div in hspec.level_divisors:
        out.append(idx_fine // jnp.uint32(div) if div > 1 else idx_fine)
    return tuple(out)


def update(hspec: HierarchySpec, state: HierarchyState,
           items: jax.Array, freqs: jax.Array) -> HierarchyState:
    """Fold a block of full keys into every level (items: uint32[B, n]).

    Cascade path: hash once per (row, item), derive all L level indices by
    integer division, then L scatter-adds -- bit-identical to
    :func:`update_reference` under the shared params drawn by
    :func:`init_hierarchy` (enforced by tests/test_hier_update_kernel.py)."""
    _require_shared_params(state, "hierarchy.update")
    items = jnp.asarray(items)
    idxs = hierarchy_indices(hspec, state.states[-1].params, items)
    new = []
    for st_l, idx in zip(state.states, idxs):
        new.append(sk.SketchState(
            params=st_l.params,
            table=sk.add_at_indices(st_l.table, idx, freqs)))
    return HierarchyState(states=tuple(new))


def update_reference(hspec: HierarchySpec, state: HierarchyState,
                     items: jax.Array, freqs: jax.Array) -> HierarchyState:
    """Per-level reference fold: L independent ``sk.update`` calls, each
    re-hashing its prefix from scratch.  The pre-cascade ingest path, kept
    as the parity oracle for :func:`update` and the fused Pallas kernel
    (and as the per-level-launch baseline in the ingest benchmark)."""
    items = jnp.asarray(items)
    new = []
    for lvl, (spec_l, st_l) in enumerate(zip(hspec.levels, state.states)):
        new.append(sk.update(spec_l, st_l, hspec.level_items(lvl, items),
                             freqs))
    return HierarchyState(states=tuple(new))


def update_conservative(hspec: HierarchySpec, state: HierarchyState,
                        items: jax.Array, freqs: jax.Array) -> HierarchyState:
    """Conservative fold into every level (freqs must be non-negative).

    The index computation shares the one-hash-pass cascade with
    :func:`update`; each level then applies the sequential Estan-Varghese
    fold independently (the row-coupling min keeps the folds per level), so
    every level still never underestimates and the heavy-hitter descent's
    no-false-negative argument is unchanged (est(prefix) >= true(prefix) >=
    true(key)).  The resulting tables are NOT linear in the stream: a
    conservatively built hierarchy must not be merged cell-wise (see
    :func:`merge`) or fed through the psum paths of core/distributed.py.
    """
    _require_shared_params(state, "hierarchy.update_conservative")
    items = jnp.asarray(items)
    idxs = hierarchy_indices(hspec, state.states[-1].params, items)
    new = []
    for st_l, idx in zip(state.states, idxs):
        new.append(sk.SketchState(
            params=st_l.params,
            table=sk.conservative_fold(st_l.table, idx, freqs)))
    return HierarchyState(states=tuple(new))


def merge(a: HierarchyState, b: HierarchyState) -> HierarchyState:
    """Cell-wise merge per level -- exact by linearity, same contract as
    core.sketch.merge, so hierarchies shard/merge like single sketches.
    Only valid for hierarchies built with the linear update: conservative
    tables (:func:`update_conservative`) are excluded from cell-wise
    merging, which is why SketchTopKEndpoint.merge_from refuses them."""
    return HierarchyState(states=tuple(
        sk.merge(sa, sb) for sa, sb in zip(a.states, b.states)))


def build_hierarchy(hspec: HierarchySpec, key: jax.Array,
                    items: np.ndarray, freqs: np.ndarray,
                    block: int = 1 << 17, dtype=jnp.int32) -> HierarchyState:
    """Build all levels over a (possibly large) weighted stream, in blocks."""
    state = init_hierarchy(hspec, key, dtype=dtype)
    for blk_items, blk_freqs in sk.stream_blocks(items, freqs, block):
        state = update_jit(hspec, state, blk_items, blk_freqs)
    return state


# The jit'd hierarchy folds donate every level TABLE (ingest folds in place
# instead of copying sum_L w*h_L cells per block) but not the params: the
# shared family is referenced by all levels and the query paths, and
# donation is effective on CPU as well as TPU.  Callers rebind the state to
# the returned value (build_hierarchy, the serving endpoints all do).

@functools.partial(jax.jit, static_argnums=0, donate_argnums=(1,))
def _update_tables_jit(hspec: HierarchySpec, tables, fine_params,
                       items, freqs):
    idxs = hierarchy_indices(hspec, fine_params, items)
    return tuple(sk.add_at_indices(t, idx, freqs)
                 for t, idx in zip(tables, idxs))


def update_jit(hspec: HierarchySpec, state: HierarchyState,
               items, freqs) -> HierarchyState:
    _require_shared_params(state, "hierarchy.update_jit")
    tables = _update_tables_jit(hspec, tuple(st.table for st in state.states),
                                state.states[-1].params, items, freqs)
    return HierarchyState(states=tuple(
        sk.SketchState(params=st.params, table=t)
        for st, t in zip(state.states, tables)))


@functools.partial(jax.jit, static_argnums=0, donate_argnums=(1,))
def _update_conservative_tables_jit(hspec: HierarchySpec, tables,
                                    fine_params, items, freqs):
    idxs = hierarchy_indices(hspec, fine_params, items)
    return tuple(sk.conservative_fold(t, idx, freqs)
                 for t, idx in zip(tables, idxs))


def update_conservative_jit(hspec: HierarchySpec, state: HierarchyState,
                            items, freqs) -> HierarchyState:
    _require_shared_params(state, "hierarchy.update_conservative_jit")
    tables = _update_conservative_tables_jit(
        hspec, tuple(st.table for st in state.states),
        state.states[-1].params, items, freqs)
    return HierarchyState(states=tuple(
        sk.SketchState(params=st.params, table=t)
        for st, t in zip(state.states, tables)))


# --------------------------------------------------------------------------
# Two-phase ingest (the serving engine's double-buffered pipeline)
# --------------------------------------------------------------------------
#
# update_jit fuses the hash cascade and the table fold into one program.
# The async serving engine (serving/sketch_engine.SketchServeEngine) wants
# them SPLIT: the cascade of block k+1 reads only the (never-donated) hash
# params and the block, so it can be dispatched while block k's fold is
# still executing against the donated table buffers -- the fold ping-pongs
# the table buffers (donation rebinds them every call) while the cascade
# runs ahead one block.  Splitting changes nothing numerically:
# stage_indices computes exactly the indices update_jit computes, and
# fold_indices applies exactly its add_at_indices -- the composition is
# bit-identical to update_jit (tests/test_serve_engine.py enforces it).

@functools.partial(jax.jit, static_argnums=0)
def _stage_indices_jit(hspec: HierarchySpec, fine_params, items):
    return hierarchy_indices(hspec, fine_params, items)


def stage_indices(hspec: HierarchySpec, state: HierarchyState,
                  items) -> Tuple[jax.Array, ...]:
    """Pipeline stage A: the hash cascade alone (all levels' cell indices).

    Depends only on the hash params and the block -- never on the tables --
    so it can run while a previous block's fold is in flight."""
    _require_shared_params(state, "hierarchy.stage_indices")
    return _stage_indices_jit(hspec, state.states[-1].params, items)


@functools.partial(jax.jit, donate_argnums=(0,))
def _fold_indices_tables_jit(tables, idxs, freqs):
    return tuple(sk.add_at_indices(t, idx, freqs)
                 for t, idx in zip(tables, idxs))


def fold_indices(state: HierarchyState, idxs: Tuple[jax.Array, ...],
                 freqs) -> HierarchyState:
    """Pipeline stage B: fold pre-computed level indices into the tables.

    Table buffers are donated (same ping-pong as :func:`update_jit`);
    callers rebind the state to the return value.  ``fold_indices(state,
    stage_indices(hspec, state, items), freqs)`` is bit-identical to
    ``update_jit(hspec, state, items, freqs)``."""
    tables = _fold_indices_tables_jit(
        tuple(st.table for st in state.states), idxs, freqs)
    return HierarchyState(states=tuple(
        sk.SketchState(params=st.params, table=t)
        for st, t in zip(state.states, tables)))


def sharded_hierarchy_build(
    hspec: HierarchySpec,
    state: HierarchyState,
    mesh,
    data_axes: Tuple[str, ...],
    items: jax.Array,
    freqs: jax.Array,
    *,
    mode: str = "linear",
) -> HierarchyState:
    """Distributed build: sharded cascade fold + per-level psum (exact).

    One shard_map over ALL levels (core.distributed.sharded_hierarchy_fold):
    each device hashes its stream slice once, derives every level's indices
    by the cascade, scatter-adds into per-level local deltas, and the psum
    merge per level is exact by linearity, just like the flat case.
    ``mode`` exists only to be refused: a conservatively built hierarchy
    (:func:`update_conservative`) has non-linear tables and must never
    enter a psum, so passing mode="conservative" raises instead of
    silently producing a wrong merged hierarchy.
    """
    from repro.core import distributed as dist

    dist.require_linear(mode, "sharded_hierarchy_build")
    items = jnp.asarray(items)
    deltas = dist.sharded_hierarchy_fold(
        hspec, state.states[-1].params, mesh, data_axes, items, freqs,
        table_dtypes=tuple(st.table.dtype for st in state.states))
    return HierarchyState(states=tuple(
        sk.SketchState(params=st.params, table=st.table + d)
        for st, d in zip(state.states, deltas)))


# --------------------------------------------------------------------------
# Separable candidate queries
# --------------------------------------------------------------------------

def candidate_partials(
    hspec: HierarchySpec,
    state: HierarchyState,
    level: int,
    prefixes: jax.Array,     # uint32[P, n_prefix_modules] (group-major)
    values: jax.Array,       # uint32[C, len(level group modules)]
) -> Tuple[jax.Array, jax.Array]:
    """The two factors of the level-``level`` child cell index.

    Returns (pp, cp): uint32[w, P] prefix partials (already scaled by the
    last group's range) and uint32[w, C] child partials, such that the cell
    index of child (p, c) at row k is ``pp[k, p] + cp[k, c]`` -- exactly
    ``compute_indices`` of the level spec on the concatenated key, by the
    mixed-radix stride identity stride_j(level) = stride_j(level-1) * r_L.
    Under the shared per-group family the sliced prefix params ARE level
    ``level - 1``'s params, so the prefix partials equal that level's own
    cell indices (the same nesting the ingest cascade exploits).
    """
    spec_l = hspec.levels[level]
    params = state.states[level].params
    w = spec_l.width
    r_last = spec_l.ranges[-1]

    if level == 0:
        pp = jnp.zeros((w, prefixes.shape[0]), jnp.uint32)
    else:
        prefix_spec = level_spec(hspec.base, level - 1)
        n_pc = prefix_spec.schema.total_chunks
        prefix_params = sk.SketchParams(q=params.q[:, :n_pc],
                                        r=params.r[:, :level])
        pp = sk.compute_indices(prefix_spec, prefix_params, prefixes)
        pp = pp * jnp.uint32(r_last)

    # child partial: the last group's sub-index, stride 1
    cp = sk.group_subindex(spec_l, params, level, values)
    return pp, cp


def _pad_rows_pow2(a: np.ndarray) -> np.ndarray:
    """Zero-pad the leading axis to the next power of two, so the jitted
    partial hashes and query launches compile O(log P) shapes rather than
    one per surviving-prefix count (prefix 0 is a valid key; pad rows are
    sliced off by the callers)."""
    n = a.shape[0]
    n_pad = 1 << max(0, (n - 1).bit_length())
    return a if n_pad == n else np.pad(a, ((0, n_pad - n), (0, 0)))


def _check_kernel_dtype(table: jax.Array) -> None:
    if table.dtype != jnp.int32:
        raise ValueError(
            f"use_kernel=True needs an int32 table (got {table.dtype}): "
            "use the jnp reference path (use_kernel=False)")


def _one_replica(state):
    """``state`` on ONE device, for the Pallas descent kernels.

    XLA cannot partition a Mosaic kernel over a mesh, so arrays replicated
    over several devices (the sharded service's psum-merged tables) are
    read from their first replica, without a copy; arrays on one device
    pass through."""
    def one(x):
        if not isinstance(x, jax.Array) or len(x.sharding.device_set) == 1:
            return x
        if not x.sharding.is_fully_replicated:
            raise ValueError(
                "use_kernel=True reads the tables on one device: merge a "
                "sharded table (psum) before the descent")
        return x.addressable_shards[0].data
    return jax.tree_util.tree_map(one, state)


def candidate_estimates(
    hspec: HierarchySpec,
    state: HierarchyState,
    level: int,
    prefixes: np.ndarray,    # uint32[P, n_prefix_modules]
    values: np.ndarray,      # uint32[C, len(level group modules)]
    *,
    use_kernel: bool = False,
    interpret: Optional[bool] = None,
    max_batch: Optional[int] = None,
) -> np.ndarray:
    """CM estimates for every (prefix x candidate-value) child: [P, C].

    ``use_kernel=True`` routes through the Pallas gather kernel
    (kernels/hier_query.py); the default is the jnp gather reference.  Both
    agree bit-for-bit.  The kernel covers int32 tables; any other dtype
    with ``use_kernel=True`` is refused rather than silently served by
    the reference.

    ``max_batch`` bounds the per-call P*C working set: the partial hashes
    are computed ONCE for all prefixes and candidates, then only the
    prefix axis is chunked (the child partials are identical across
    chunks, so rehashing them per chunk would be pure waste).
    """
    prefixes = np.asarray(prefixes, dtype=np.uint32)
    p = prefixes.shape[0]
    prefixes = jnp.asarray(_pad_rows_pow2(prefixes))
    values = jnp.asarray(np.asarray(values, dtype=np.uint32))
    if use_kernel:
        state = _one_replica(state)
    pp, cp = candidate_partials(hspec, state, level, prefixes, values)
    table = state.states[level].table
    from repro.kernels.hier_query import (
        hier_candidate_query,
        hier_candidate_query_ref,
    )
    if use_kernel:
        _check_kernel_dtype(table)

        def one(pp_chunk):
            return hier_candidate_query(table, pp_chunk, cp,
                                        interpret=interpret)
    else:
        def one(pp_chunk):
            return hier_candidate_query_ref(table, pp_chunk, cp)

    p_all, c = pp.shape[1], cp.shape[1]
    if max_batch is None or p_all * c <= max_batch:
        return np.asarray(one(pp))[:p]
    p_chunk = max(1, max_batch // max(c, 1))
    outs = []
    for s in range(0, p, p_chunk):
        pc = pp[:, s : s + p_chunk]
        if pc.shape[1] < p_chunk:
            # pad to the fixed chunk width so one compiled kernel serves
            # every chunk (pad index 0 is always a valid cell; sliced off)
            pc = jnp.pad(pc, ((0, 0), (0, p_chunk - pc.shape[1])))
        outs.append(np.asarray(one(pc)))
    return np.concatenate(outs, axis=0)[:p]


# --------------------------------------------------------------------------
# Heavy-hitter descent
# --------------------------------------------------------------------------

def find_heavy_hitters(
    hspec: HierarchySpec,
    state: HierarchyState,
    threshold: float,
    candidates: Sequence[np.ndarray],
    *,
    use_kernel: bool = False,
    interpret: Optional[bool] = None,
    max_batch: int = 1 << 16,
) -> Tuple[np.ndarray, np.ndarray]:
    """All keys whose CM estimate is >= ``threshold``.

    candidates[j]: uint32[C_j, len(g_j modules)] -- the value combos to
    consider for group j (e.g. the distinct observed values; see
    streams.heavy_hitters.group_candidates).  Guarantees, conditional on
    every true heavy hitter's group values appearing in the candidate sets:

      * no false negatives: estimates only overestimate, and a prefix's
        mass >= any extension's, so no ancestor of a heavy key is pruned;
      * false positives only from CM collisions at the leaf level, i.e.
        every reported key has true frequency >= threshold - eps*L with the
        usual (h, w) probability.

    Returns (items uint32[K, n_modules] in schema module order, estimates
    int64[K]) sorted by estimate, descending.
    """
    if len(candidates) != hspec.n_levels:
        raise ValueError(
            f"need one candidate set per level ({hspec.n_levels}), "
            f"got {len(candidates)}")
    threshold = int(threshold)

    prefixes = np.zeros((1, 0), dtype=np.uint32)
    est = np.zeros((1,), dtype=np.int64)
    for lvl in range(hspec.n_levels):
        cand = np.asarray(candidates[lvl], dtype=np.uint32)
        if cand.ndim != 2 or cand.shape[1] != len(hspec.base.partition[lvl]):
            raise ValueError(
                f"candidates[{lvl}] must be [C, {len(hspec.base.partition[lvl])}]")
        if prefixes.shape[0] == 0 or cand.shape[0] == 0:
            n_mods = len(level_modules(hspec.base, hspec.n_levels - 1))
            return (np.zeros((0, n_mods), np.uint32),
                    np.zeros((0,), np.int64))
        # batched P x C estimates; candidate_estimates hashes the partials
        # once and chunks the prefix axis to bound the one-hot working set
        grid = candidate_estimates(
            hspec, state, lvl, prefixes, cand, use_kernel=use_kernel,
            interpret=interpret, max_batch=max_batch).astype(np.int64)
        keep_p, keep_c = np.nonzero(grid >= threshold)
        prefixes = np.concatenate(
            [prefixes[keep_p], cand[keep_c]], axis=1)
        est = grid[keep_p, keep_c]

    order = np.argsort(-est, kind="stable")
    return hspec.to_schema_order(prefixes[order]), est[order]


# --------------------------------------------------------------------------
# Batched multi-request descent (Q concurrent queries, one launch per level)
# --------------------------------------------------------------------------

def batched_candidate_estimates(
    hspec: HierarchySpec,
    state: HierarchyState,
    level: int,
    prefix_sets: Sequence[np.ndarray],   # Q arrays uint32[P_q, n_prefix_mods]
    values: np.ndarray,                  # uint32[C, len(level group modules)]
    *,
    use_kernel: bool = False,
    interpret: Optional[bool] = None,
    max_batch: Optional[int] = None,
) -> List[np.ndarray]:
    """CM estimate grids for Q concurrent requests at one level: Q x [P_q, C].

    All requests share the level's candidate set but carry their own
    surviving prefix sets.  The prefix partials are hashed ONCE over the
    concatenated prefixes, padded to a common power-of-two P_max (pad
    prefix index 0 is always a valid cell; the padded rows are sliced
    off), and the whole [Q, P_max, C] request grid is evaluated in a
    single launch
    (kernels/hier_query.hier_candidate_query_batched) -- Q concurrent
    queries cost one ``pallas_call`` per level instead of Q.  Every
    returned cell is computed lane-independently, so each request's grid
    is bit-identical to its own :func:`candidate_estimates` call.

    ``max_batch`` bounds the Q*P_max*C working set by chunking the request
    axis (the per-request grids are already the unsplittable unit).
    """
    if not prefix_sets:
        return []
    counts = [int(np.asarray(p).shape[0]) for p in prefix_sets]
    if min(counts) == 0:
        raise ValueError("every request must have a non-empty prefix set "
                         "(callers retire empty requests before batching)")
    values = jnp.asarray(np.asarray(values, dtype=np.uint32))
    if use_kernel:
        state = _one_replica(state)
    cat = np.concatenate(
        [np.asarray(p, dtype=np.uint32) for p in prefix_sets], axis=0)
    pp_all, cp = candidate_partials(hspec, state, level,
                                    jnp.asarray(_pad_rows_pow2(cat)), values)
    # pad P_max to a power of two too: one compiled launch per bucket
    nq, c = len(counts), int(cp.shape[1])
    p_max = 1 << max(0, (max(counts) - 1).bit_length())

    table = state.states[level].table
    from repro.kernels.hier_query import (
        hier_candidate_query_batched,
        hier_candidate_query_batched_ref,
    )
    if use_kernel:
        _check_kernel_dtype(table)

        def one(pp3):
            return hier_candidate_query_batched(table, pp3, cp,
                                                interpret=interpret)
    else:
        def one(pp3):
            return hier_candidate_query_batched_ref(table, pp3, cp)

    # per-request column blocks, padded to the common P_max
    blocks, off = [], 0
    for n in counts:
        blk = pp_all[:, off : off + n]
        if n < p_max:
            blk = jnp.pad(blk, ((0, 0), (0, p_max - n)))
        blocks.append(blk)
        off += n
    pp3 = jnp.stack(blocks, axis=1)                  # [w, Q, P_max]

    if max_batch is None or nq * p_max * c <= max_batch:
        grids = np.asarray(one(pp3))
    else:
        q_chunk = max(1, max_batch // max(p_max * c, 1))
        outs = []
        for s in range(0, nq, q_chunk):
            qc = pp3[:, s : s + q_chunk]
            if qc.shape[1] < q_chunk:
                # pad to the fixed chunk so one compiled kernel serves
                # every chunk (pad prefix 0 is a valid cell; sliced off)
                qc = jnp.pad(qc, ((0, 0), (0, q_chunk - qc.shape[1]),
                                  (0, 0)))
            outs.append(np.asarray(one(qc)))
        grids = np.concatenate(outs, axis=0)[:nq]
    return [grids[i, : counts[i], :] for i in range(nq)]


def batched_find_heavy_hitters(
    hspec: HierarchySpec,
    state: HierarchyState,
    thresholds: Sequence[float],
    candidates: Sequence[np.ndarray],
    *,
    use_kernel: bool = False,
    interpret: Optional[bool] = None,
    max_batch: int = 1 << 16,
) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Q concurrent heavy-hitter descents sharing one set of launches.

    Request q receives exactly ``find_heavy_hitters(..., thresholds[q],
    candidates)`` -- bit-identical, enforced by tests/test_serve_engine.py
    -- but the per-level candidate grids of ALL still-active requests are
    evaluated together (:func:`batched_candidate_estimates`), so the
    device sees one P x C x Q launch per level instead of Q separate
    P x C launches.  Requests prune independently; a request whose prefix
    set empties retires early with the empty result, same as the serial
    descent.
    """
    if len(candidates) != hspec.n_levels:
        raise ValueError(
            f"need one candidate set per level ({hspec.n_levels}), "
            f"got {len(candidates)}")
    thrs = [int(t) for t in thresholds]
    nq = len(thrs)
    n_mods = len(level_modules(hspec.base, hspec.n_levels - 1))
    empty = (np.zeros((0, n_mods), np.uint32), np.zeros((0,), np.int64))

    prefixes = [np.zeros((1, 0), dtype=np.uint32) for _ in range(nq)]
    est = [np.zeros((1,), dtype=np.int64) for _ in range(nq)]
    done: List[Optional[Tuple[np.ndarray, np.ndarray]]] = [None] * nq
    for lvl in range(hspec.n_levels):
        active = [q for q in range(nq) if done[q] is None]
        if not active:
            break
        cand = np.asarray(candidates[lvl], dtype=np.uint32)
        if cand.ndim != 2 or cand.shape[1] != len(hspec.base.partition[lvl]):
            raise ValueError(
                f"candidates[{lvl}] must be [C, {len(hspec.base.partition[lvl])}]")
        for q in active:
            if prefixes[q].shape[0] == 0 or cand.shape[0] == 0:
                done[q] = empty
        active = [q for q in active if done[q] is None]
        if not active:
            break
        grids = batched_candidate_estimates(
            hspec, state, lvl, [prefixes[q] for q in active], cand,
            use_kernel=use_kernel, interpret=interpret, max_batch=max_batch)
        for q, grid in zip(active, grids):
            grid = grid.astype(np.int64)
            keep_p, keep_c = np.nonzero(grid >= thrs[q])
            prefixes[q] = np.concatenate(
                [prefixes[q][keep_p], cand[keep_c]], axis=1)
            est[q] = grid[keep_p, keep_c]

    out: List[Tuple[np.ndarray, np.ndarray]] = []
    for q in range(nq):
        if done[q] is not None:
            out.append(done[q])
            continue
        order = np.argsort(-est[q], kind="stable")
        out.append((hspec.to_schema_order(prefixes[q][order]), est[q][order]))
    return out
