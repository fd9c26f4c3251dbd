"""Distributed sketch runtime (shard_map + psum).

The sketch table is *linear* in the stream, so the cluster-scale pattern is:

  1. shard the incoming stream over the data-parallel mesh axes,
  2. every device folds its shard into a device-local table (Pallas kernel
     or jnp scatter -- contention-free either way),
  3. merge by ``psum`` over the DP axes at sync points (exact by linearity).

Queries run anywhere once merged; for row-sharded tables (w split over the
"model" axis) a ``pmin`` over row-groups completes the Count-Min min.

These helpers are mesh-generic: they work on the production (16,16) /
(2,16,16) meshes in the dry-run and on small host-platform meshes in tests.

Every path in this module assumes the *linear* update (core.sketch.update /
the one-hot-matmul kernel).  Conservative tables
(core.sketch.update_conservative, kernels/sketch_update_conservative.py)
are NOT linear in the stream and are excluded from the cell-wise merge and
psum paths here: a psum of conservatively built tables is not the table of
the union stream.  Conservative mode is single-shard only (see
kernels/ops.KernelSketch and serving.engine.SketchTopKEndpoint, which
refuse merge in that mode).
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map
from repro.core import sketch as sk


def require_linear(mode: str, entry: str) -> None:
    """Refuse conservative tables on any sharded/merge entry point.

    Every distributed path in this repo relies on the table being linear in
    the stream (psum of shard tables == table of the union stream).  That
    holds for mode="linear" and for mode="signed" (Count-Sketch cells are
    sums of +-1-weighted arrivals, so shard tables psum exactly).
    Conservative tables (Estan-Varghese) are not linear, so each sharded
    entry point calls this guard up front and fails loudly instead of
    producing a silently wrong merged table.
    """
    if mode not in ("linear", "signed"):
        raise ValueError(
            f"{entry} is only defined for linear tables (got mode="
            f"{mode!r}): conservative tables are not linear in the stream, "
            "so per-shard folds cannot be psum-merged -- conservative mode "
            "is single-shard by construction")


def pad_block_pow2(items: np.ndarray, freqs: np.ndarray, n_shards: int):
    """Pad a stream block so each of ``n_shards`` contiguous slices has the
    same power-of-two length.

    Zero-frequency pad rows are no-ops under the linear update and are
    skipped by the candidate pools, so padding never changes any table --
    which is what keeps the sharded entry points bit-exact with the serial
    build.  The power-of-two rounding bounds the jitted fold at O(log B)
    compiled variants per shard count.  One helper shared by every sharded
    ingest surface (ShardedTopKService.ingest, KernelSketch.sharded_update,
    SketchTopKEndpoint.ingest with n_shards=1): the copies must agree for
    cross-entry-point parity to hold.

    Returns (items, freqs, rows_per_shard).
    """
    n = items.shape[0]
    per = -(-n // n_shards)
    per = 1 << max(per - 1, 0).bit_length()
    m = per * n_shards
    if m != n:
        items = np.pad(items, ((0, m - n), (0, 0)))
        freqs = np.pad(freqs, (0, m - n))
    return items, freqs, per


def sharded_build(
    spec: sk.SketchSpec,
    params: sk.SketchParams,
    mesh: Mesh,
    data_axes: Tuple[str, ...],
    items: jax.Array,
    freqs: jax.Array,
    table_dtype=jnp.int32,
) -> jax.Array:
    """Build the *merged* table from a stream sharded over ``data_axes``.

    items: uint32[B, n] with B divisible by the product of data-axis sizes.
    Returns the replicated merged table [w, h].
    """

    def local_fold(items_l, freqs_l):
        state = sk.SketchState(
            params=params,
            table=jnp.zeros((spec.width, spec.table_size), dtype=table_dtype),
        )
        state = sk.update(spec, state, items_l, freqs_l)
        return jax.lax.psum(state.table, data_axes)

    fn = shard_map(
        local_fold,
        mesh=mesh,
        in_specs=(P(data_axes), P(data_axes)),
        out_specs=P(),
        check_vma=False,
    )
    return fn(items, freqs)


def sharded_signed_build(
    spec: sk.SketchSpec,
    params,                      # core.countsketch.CountSketchParams
    mesh: Mesh,
    data_axes: Tuple[str, ...],
    items: jax.Array,
    freqs: jax.Array,            # signed (turnstile) weights
    table_dtype=jnp.int32,
) -> jax.Array:
    """Signed (Count-Sketch) counterpart of :func:`sharded_build`.

    Each device hashes its stream slice once (bucket indices + composite
    sign bits), folds sign-weighted arrivals into a device-local table, and
    psum-merges over ``data_axes``.  Exact by linearity: signed cells are
    plain sums, so the merged table is bit-identical to the serial fold for
    integer dtypes.  Returns the replicated merged delta [w, h].
    """
    from repro.core import countsketch as cs

    def local_fold(items_l, freqs_l):
        idx = sk.compute_indices(spec, params.base, items_l)
        s = cs.signs(spec, params, items_l)
        tbl = jnp.zeros((spec.width, spec.table_size), dtype=table_dtype)
        sf = (s * freqs_l.astype(jnp.float32)[None, :]).astype(table_dtype)
        return jax.lax.psum(cs.add_signed(tbl, idx, sf), data_axes)

    fn = shard_map(
        local_fold,
        mesh=mesh,
        in_specs=(P(data_axes), P(data_axes)),
        out_specs=P(),
        check_vma=False,
    )
    return fn(items, freqs)


def sharded_update(
    spec: sk.SketchSpec,
    mesh: Mesh,
    data_axes: Tuple[str, ...],
    state: sk.SketchState,
    items: jax.Array,
    freqs: jax.Array,
) -> sk.SketchState:
    """One synchronous distributed update step: local fold + psum merge."""
    delta = sharded_build(spec, state.params, mesh, data_axes, items, freqs,
                          table_dtype=state.table.dtype)
    return sk.SketchState(params=state.params, table=state.table + delta)


def lazy_local_update(
    spec: sk.SketchSpec,
    mesh: Mesh,
    data_axes: Tuple[str, ...],
    local_tables: jax.Array,  # [w, h] per device, sharded "stacked" on axis 0
    params: sk.SketchParams,
    items: jax.Array,
    freqs: jax.Array,
) -> jax.Array:
    """Asynchronous variant: devices accumulate local tables; no collective.

    ``local_tables`` has a leading device axis sharded over ``data_axes``;
    call :func:`merge_local_tables` at sync intervals.  This is the
    overlap-friendly mode used by the training integration (the merge
    all-reduce is amortized over many steps and can overlap compute).
    """

    def fold(tbl_l, items_l, freqs_l):
        st = sk.SketchState(params=params, table=tbl_l[0])
        st = sk.update(spec, st, items_l, freqs_l)
        return st.table[None]

    fn = shard_map(
        fold,
        mesh=mesh,
        in_specs=(P(data_axes), P(data_axes), P(data_axes)),
        out_specs=P(data_axes),
        check_vma=False,
    )
    return fn(local_tables, items, freqs)


def merge_local_tables(
    mesh: Mesh, data_axes: Tuple[str, ...], local_tables: jax.Array
) -> jax.Array:
    """psum-merge the lazily accumulated per-device tables."""

    def m(tbl_l):
        return jax.lax.psum(tbl_l[0], data_axes)

    # the psum result is the same on every shard: a replicated output
    fn = shard_map(
        m, mesh=mesh, in_specs=(P(data_axes),), out_specs=P(),
        check_vma=False,
    )
    return fn(local_tables)


def lazy_hierarchy_update(
    hspec,                      # core.hierarchy.HierarchySpec
    mesh: Mesh,
    data_axes: Tuple[str, ...],
    local_tables: Sequence[jax.Array],  # per level: [n_shards, w, h_level]
    params: Sequence[sk.SketchParams],  # per level
    items: jax.Array,           # uint32[B, n_modules], B % n_shards == 0
    freqs: jax.Array,
    *,
    mode: str = "linear",
) -> Tuple[jax.Array, ...]:
    """Lazy local fold of ALL hierarchy levels in one shard_map: no
    collective on ingest, no per-level re-hash, no per-level dispatch.

    Every shard hashes its stream slice ONCE (the finest level's composite
    index), derives each level's cell indices by the mixed-radix cascade
    (core.hierarchy.hierarchy_indices -- exact under the shared per-group
    params every ``init_hierarchy`` state carries), and scatter-adds into
    its local copy of every level's table.  The psum merge is deferred to
    the explicit sync point (:func:`sync_local_hierarchy`).  The
    per-device fold body is the jnp scatter-add; the fused Pallas kernel's
    counterpart is :func:`lazy_hierarchy_update_fused`, and the two are
    bit-identical by the parity tests.

    ``params`` keeps the one-entry-per-level shape of ``HierarchyState``
    for compatibility; the cascade only reads the finest level's entry
    (every other level's params are prefix slices of it).

    Only valid for linear tables; the conservative update is excluded from
    every psum path (see :func:`require_linear`).
    """
    require_linear(mode, "lazy_hierarchy_update")
    from repro.core import hierarchy as hh

    items = jnp.asarray(items)
    fine_params = params[-1]
    n_levels = len(local_tables)

    def fold(tbls, items_l, freqs_l):
        idxs = hh.hierarchy_indices(hspec, fine_params, items_l)
        return tuple(sk.add_at_indices(t[0], idx, freqs_l)[None]
                     for t, idx in zip(tbls, idxs))

    fn = shard_map(
        fold,
        mesh=mesh,
        in_specs=(tuple(P(data_axes) for _ in range(n_levels)),
                  P(data_axes), P(data_axes)),
        out_specs=tuple(P(data_axes) for _ in range(n_levels)),
        check_vma=False,
    )
    return fn(tuple(local_tables), items, freqs)


def lazy_hierarchy_update_fused(
    hplan,                      # kernels.hier_update.HierPlan
    mesh: Mesh,
    data_axes: Tuple[str, ...],
    local_tables: Sequence[jax.Array],  # one [n_shards, w, padded_cols]
    q: jax.Array,               # uint32[w, C] shared-family multipliers
    r: jax.Array,               # uint32[w, m] shared-family offsets
    chunks: jax.Array,          # uint32[n_shards * per, C] key digits
    freqs: jax.Array,           # [n_shards * per], per % block_b == 0
    *,
    block_b: int,
    interpret=None,
) -> Tuple[jax.Array]:
    """Lazy local fold of ALL levels through the fused Pallas kernel, in
    one shard_map: no collective on ingest.

    The kernel-layout counterpart of :func:`lazy_hierarchy_update`: the
    local tables are ONE stack of every shard's concatenated padded level
    table (the kernels/ops.KernelHierarchy layout), and each shard runs
    kernels/hier_update.fold on its contiguous slice of the block, one
    kernel launch per ``block_b`` rows (a scan over the slice's
    sub-blocks).  The digits are split on the host
    (kernels/ops.fold_digits), so the block is this program's only input
    besides the tables and the params.  Bit-identical to the scatter-add
    body and to the per-level reference on integer tables.
    """
    from repro.kernels.hier_update import fold as kernel_fold

    (stack,) = local_tables

    def fold(tbl, q_l, r_l, ch, fr):
        k = ch.shape[0] // block_b

        def sub_block(t, x):
            return kernel_fold(hplan, t, x[0], x[1], (q_l, r_l),
                               interpret), None

        t, _ = jax.lax.scan(sub_block, tbl[0],
                            (ch.reshape(k, block_b, ch.shape[1]),
                             fr.reshape(k, block_b)))
        return (t[None],)

    fn = shard_map(
        fold,
        mesh=mesh,
        in_specs=(P(data_axes), P(), P(), P(data_axes), P(data_axes)),
        out_specs=(P(data_axes),),
        check_vma=False,
    )
    return fn(stack, q, r, chunks, freqs)


def sharded_hierarchy_fold(
    hspec,                      # core.hierarchy.HierarchySpec
    fine_params: sk.SketchParams,
    mesh: Mesh,
    data_axes: Tuple[str, ...],
    items: jax.Array,           # uint32[B, n_modules], B % n_shards == 0
    freqs: jax.Array,
    *,
    table_dtypes: Sequence = (),
) -> Tuple[jax.Array, ...]:
    """Synchronous sharded build of every level's MERGED delta in one
    shard_map: hash each stream slice once, cascade to all level indices,
    scatter-add per level, psum per level (exact by linearity).

    The hierarchy counterpart of :func:`sharded_build`; used by
    core.hierarchy.sharded_hierarchy_build.  ``fine_params`` is the finest
    level's (shared-family) params; ``table_dtypes`` gives one dtype per
    level (defaults to int32).
    """
    from repro.core import hierarchy as hh

    dtypes = (tuple(table_dtypes)
              or (jnp.int32,) * hspec.n_levels)

    def fold(items_l, freqs_l):
        idxs = hh.hierarchy_indices(hspec, fine_params, items_l)
        out = []
        for spec_l, idx, dt in zip(hspec.levels, idxs, dtypes):
            tbl = jnp.zeros((spec_l.width, spec_l.table_size), dtype=dt)
            out.append(jax.lax.psum(sk.add_at_indices(tbl, idx, freqs_l),
                                    data_axes))
        return tuple(out)

    fn = shard_map(
        fold,
        mesh=mesh,
        in_specs=(P(data_axes), P(data_axes)),
        out_specs=tuple(P() for _ in range(hspec.n_levels)),
        check_vma=False,
    )
    return fn(items, freqs)


def merge_local_hierarchy(
    mesh: Mesh, data_axes: Tuple[str, ...],
    local_tables: Sequence[jax.Array],
) -> Tuple[jax.Array, ...]:
    """psum-merge every level's lazily accumulated per-shard tables.

    The sync point of the sharded serving path: returns one replicated
    [w, h_level] table per level, exact by linearity (integer psum is exact
    addition, so the result is bit-identical for any shard count)."""
    return tuple(merge_local_tables(mesh, data_axes, t) for t in local_tables)


def sync_local_hierarchy(
    mesh: Mesh, data_axes: Tuple[str, ...],
    local_tables: Sequence[jax.Array],
    tables: Sequence[jax.Array],
) -> Tuple[Tuple[jax.Array, ...], Tuple[jax.Array, ...]]:
    """The sync point in one shard_map: every merged table plus the psum of
    its local stack (replicated), and the local stacks zeroed (sharded as
    they came).  Call inside a jit that donates ``local_tables``, so the
    zeroed stacks reuse their buffers.  Exact by linearity, like
    :func:`merge_local_hierarchy`."""

    def sync(locs, tbls):
        merged = tuple(t + jax.lax.psum(l[0], data_axes)
                       for l, t in zip(locs, tbls))
        return merged, tuple(jnp.zeros_like(l) for l in locs)

    n = len(local_tables)
    fn = shard_map(
        sync,
        mesh=mesh,
        in_specs=((P(data_axes),) * n, (P(),) * n),
        out_specs=((P(),) * n, (P(data_axes),) * n),
        check_vma=False,
    )
    return fn(tuple(local_tables), tuple(tables))


def init_local_tables(
    mesh: Mesh, data_axes: Tuple[str, ...],
    n_shards: int, level_shapes: Sequence[Tuple[int, ...]], dtype,
) -> Tuple[jax.Array, ...]:
    """Zeroed per-shard local table stacks, placed shard-per-device.

    One ``[n_shards, w, h_level]`` stack per level, sharded on axis 0 over
    the mesh's data axes (the layout ``lazy_hierarchy_update`` consumes).
    Shared by the sharded service's constructor, its N->M ``remesh`` and
    its ``load_state_dict``, so fresh locals land on the CURRENT devices
    instead of wherever the old stack happened to live.
    """
    shapes = tuple((n_shards,) + tuple(s) for s in level_shapes)
    return _zeros_program(mesh, tuple(data_axes), shapes,
                          jnp.dtype(dtype))()


@functools.lru_cache(maxsize=32)
def _zeros_program(mesh: Mesh, data_axes: Tuple[str, ...],
                   shapes: Tuple[Tuple[int, ...], ...], dtype):
    """The zeros of :func:`init_local_tables`, made in place (each device
    its own shard, no whole stack on one device first); one compiled
    program per mesh, shapes and dtype."""
    def zeros():
        return tuple(jnp.zeros(s, dtype=dtype) for s in shapes)

    return jax.jit(zeros, out_shardings=NamedSharding(mesh, P(data_axes)))


def row_sharded_query(
    spec: sk.SketchSpec,
    mesh: Mesh,
    model_axis: str,
    params: sk.SketchParams,
    table: jax.Array,     # [w, h] sharded on rows over model_axis
    items: jax.Array,     # replicated queries
) -> jax.Array:
    """Count-Min query with the w rows sharded over the model axis.

    Each shard takes the min over its local rows, then a pmin over the axis
    completes the global min.  w must be divisible by the axis size.
    """

    def q(params_l, table_l, items_l):
        w_local = table_l.shape[0]
        # local min over this shard's rows: reuse compute_indices on a
        # width-w_local view of the spec with this shard's params
        sub_spec = sk.SketchSpec(spec.schema, spec.partition, spec.ranges, w_local)
        idx = sk.compute_indices(sub_spec, params_l, items_l)
        vals = jnp.take_along_axis(table_l, idx.astype(jnp.int32), axis=1)
        return jax.lax.pmin(jnp.min(vals, axis=0), model_axis)

    fn = shard_map(
        q,
        mesh=mesh,
        in_specs=(
            sk.SketchParams(q=P(model_axis), r=P(model_axis)),
            P(model_axis),
            P(),
        ),
        out_specs=P(),
        check_vma=False,
    )
    return fn(params, table, items)
