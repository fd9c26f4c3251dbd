"""Signed Count-Sketch mode of the composite-hash family (beyond paper).

Same partitioned indexing machinery as core/sketch.py, plus a +-1 sign per
(row, item) built *compositely*: one CW parity hash per module group, exactly
like the bucket hashes, so the sign factors over the same group prefixes as
the cell address.  The level-L sign is the product (XOR of parities) of
groups 0..L, which makes the sign cascade with the hierarchy the same way
the mixed-radix index does:

    sign_L(key) = sign_{L-1}(prefix) * parity_L(g_L value)

``sign_bits`` packs all levels' signs into one integer per (row, item) --
bit L is the cumulative parity of groups 0..L -- so ingest hashes signs once
and every level reads its bit, mirroring ``hierarchy_indices``.

Median-of-rows estimates are unbiased; signed tables stay *linear* in the
stream, so merge / psum folds / table-buffer donation all apply verbatim
(unlike conservative mode, which every linear surface refuses).  This is the
right primitive for gradient sketching (training/grad_compression.py), where
values are real and cancellation matters, and it supports ``l2estimate``
(AMS-style F2 from the row norms) plus a median *threshold descent* over the
hierarchy (|estimate| thresholds; signs make over- and under-estimates
symmetric, so the descent keeps any prefix whose magnitude clears the bar).

The performance path is ``mode="signed"`` of kernels/ops.py, bit-exact
against this module on int32 tables (tests/test_signed_kernels.py).
"""
from __future__ import annotations

import functools
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hierarchy as hh
from repro.core import sketch as sk
from repro.core.hashing import addmod_p31, draw_hash_params, mulmod_p31_16


class CountSketchParams(NamedTuple):
    """Bucket hash params + one CW sign hash per (row, group)."""
    base: sk.SketchParams
    sign_q: jax.Array  # uint32[w, total_chunks]
    sign_r: jax.Array  # uint32[w, n_groups]


class CountSketchState(NamedTuple):
    params: CountSketchParams
    table: jax.Array  # [w, h], float32 or int32


def init_params(spec: sk.SketchSpec, key: jax.Array) -> CountSketchParams:
    kb, kq, kr = jax.random.split(key, 3)
    base = sk.init_params(spec, kb)
    sign_q = draw_hash_params(kq, (spec.width, spec.schema.total_chunks))
    sign_r = draw_hash_params(kr, (spec.width, spec.n_groups))
    return CountSketchParams(base, sign_q, sign_r)


def init_state(spec: sk.SketchSpec, key: jax.Array,
               dtype=jnp.float32) -> CountSketchState:
    params = init_params(spec, key)
    table = jnp.zeros((spec.width, spec.table_size), dtype=dtype)
    return CountSketchState(params, table)


# --------------------------------------------------------------------------
# Signs
# --------------------------------------------------------------------------

def sign_bits(spec: sk.SketchSpec, params: CountSketchParams,
              items: jax.Array) -> jax.Array:
    """Packed cumulative parity bits per (row, item): uint32[w, B].

    Bit L is the XOR of the per-group CW-hash parities of groups 0..L --
    i.e. the sign of the level-L prefix of the key under the shared family
    (the finest/flat sign is the top group's bit).  One pass computes every
    level's sign, the sign half of the ingest cascade.
    """
    chunks = spec.schema.module_chunks(items)  # [B, C]
    w, b = spec.width, chunks.shape[0]
    bits = jnp.zeros((w, b), dtype=jnp.uint32)
    cum = jnp.zeros((w, b), dtype=jnp.uint32)
    for j in range(spec.n_groups):
        acc = jnp.broadcast_to(params.sign_r[:, j][:, None], (w, b))
        acc = acc.astype(jnp.uint32)
        for c in spec.group_chunk_columns(j):
            acc = addmod_p31(acc, mulmod_p31_16(params.sign_q[:, c][:, None],
                                                chunks[None, :, c]))
        cum = cum ^ (acc & jnp.uint32(1))
        bits = bits | (cum << jnp.uint32(j))
    return bits


def signs_from_bits(bits: jax.Array, level: int) -> jax.Array:
    """float32 +-1 signs for one level from the packed cumulative bits."""
    par = (bits >> jnp.uint32(level)) & jnp.uint32(1)
    return 1.0 - 2.0 * par.astype(jnp.float32)


def signs(spec: sk.SketchSpec, params: CountSketchParams,
          items: jax.Array) -> jax.Array:
    """+-1 per (row, item) for the full composite key: float32[w, B]."""
    return signs_from_bits(sign_bits(spec, params, items), spec.n_groups - 1)


def group_sign_parity(spec: sk.SketchSpec, params: CountSketchParams,
                      group: int, values: jax.Array) -> jax.Array:
    """Parity bit of ONE group's sign hash: uint32[w, Q] in {0, 1}.

    ``values``: uint32[Q, len(group modules)].  The sign analogue of
    sk.group_subindex -- the separable child factor of the candidate grid:
    sign(prefix + v) = prefix_sign * (1 - 2 * parity(v)).
    """
    vcols = []
    for mi, mod in enumerate(spec.partition[group]):
        nc = spec.schema.chunk_counts[mod]
        v = values[..., mi].astype(jnp.uint32)
        for c in range(nc):
            vcols.append((v >> jnp.uint32(16 * c)) & jnp.uint32(0xFFFF))
    gchunks = jnp.stack(vcols, axis=-1)                        # [Q, Cg]

    w = spec.width
    acc = jnp.broadcast_to(params.sign_r[:, group][:, None],
                           (w, values.shape[0])).astype(jnp.uint32)
    for ci, c in enumerate(spec.group_chunk_columns(group)):
        acc = addmod_p31(acc, mulmod_p31_16(params.sign_q[:, c][:, None],
                                            gchunks[None, :, ci]))
    return acc & jnp.uint32(1)


# --------------------------------------------------------------------------
# Flat update / query / diagnostics
# --------------------------------------------------------------------------

def add_signed(table: jax.Array, idx: jax.Array,
               signed_vals: jax.Array) -> jax.Array:
    """Scatter-add per-(row, item) signed values (float32[w, B]) into the
    table -- the signed analogue of sk.add_at_indices, whose broadcast
    doesn't apply because the sign differs per row."""
    w, h = table.shape
    flat = (jnp.arange(w, dtype=jnp.uint32)[:, None] * jnp.uint32(h)
            + idx).reshape(-1)
    contrib = signed_vals.reshape(-1).astype(table.dtype)
    return table.reshape(-1).at[flat].add(contrib).reshape(w, h)


def update(spec: sk.SketchSpec, state: CountSketchState, items: jax.Array,
           values: jax.Array) -> CountSketchState:
    """Fold (item, value) pairs: cell[k, h_k(x)] += s_k(x) * v (order-free).

    Values may be real or signed integers (turnstile deletions are fine);
    int32 tables stay bit-exact for |value| < 2^24, matching the kernel."""
    idx = sk.compute_indices(spec, state.params.base, items)   # [w, B]
    s = signs(spec, state.params, items)                       # [w, B]
    table = add_signed(state.table, idx,
                       s * values[None, :].astype(jnp.float32))
    return CountSketchState(state.params, table)


def query_rows(spec: sk.SketchSpec, state: CountSketchState,
               items: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(per-row estimates [w, Q], median [Q]) -- rows enable robustness
    filters (e.g. sign agreement) on top of the median."""
    idx = sk.compute_indices(spec, state.params.base, items)
    s = signs(spec, state.params, items)
    vals = jnp.take_along_axis(state.table, idx.astype(jnp.int32),
                               axis=1).astype(jnp.float32) * s
    return vals, jnp.median(vals, axis=0)


def query(spec: sk.SketchSpec, state: CountSketchState,
          items: jax.Array) -> jax.Array:
    """Unbiased median-of-rows estimate of each item's summed value."""
    return query_rows(spec, state, items)[1]


def l2estimate(table: jax.Array) -> jax.Array:
    """AMS-style L2 estimate: sqrt(median_k sum_j table[k, j]^2).

    Each row's squared norm is an unbiased F2 estimate (cross terms cancel
    in expectation under the +-1 signs); the median over rows gives the
    usual constant-probability multiplicative bound."""
    sq = jnp.sum(jnp.square(table.astype(jnp.float32)), axis=1)
    return jnp.sqrt(jnp.median(sq))


def merge(a: CountSketchState, b: CountSketchState) -> CountSketchState:
    """Cell-wise merge -- exact by linearity (same hash params assumed)."""
    return CountSketchState(params=a.params, table=a.table + b.table)


# --------------------------------------------------------------------------
# Hierarchy: signed tables over the same group-prefix cascade
# --------------------------------------------------------------------------

class CountSketchHierarchy(NamedTuple):
    """One signed table per level, sharing ONE (bucket + sign) hash draw.

    ``params`` is the finest level's draw; level L uses the prefix slices
    (exactly core/hierarchy.py's shared family, extended to the sign hash).
    """
    params: CountSketchParams
    tables: Tuple[jax.Array, ...]   # coarse -> fine, [w, h_L] each


def level_params(hspec: hh.HierarchySpec, params: CountSketchParams,
                 level: int) -> CountSketchParams:
    """Level ``level``'s params as prefix slices of the finest draw."""
    nc = hspec.levels[level].schema.total_chunks
    return CountSketchParams(
        base=hh.level_params(hspec, params.base, level),
        sign_q=params.sign_q[:, :nc],
        sign_r=params.sign_r[:, : level + 1])


def init_hierarchy(hspec: hh.HierarchySpec, key: jax.Array,
                   dtype=jnp.float32) -> CountSketchHierarchy:
    params = init_params(hspec.levels[-1], key)
    tables = tuple(jnp.zeros((s.width, s.table_size), dtype=dtype)
                   for s in hspec.levels)
    return CountSketchHierarchy(params, tables)


def hier_fold_tables(
    hspec: hh.HierarchySpec,
    params: CountSketchParams,
    tables: Tuple[jax.Array, ...],
    items: jax.Array,
    values: jax.Array,
) -> Tuple[jax.Array, ...]:
    """Signed cascade fold: ONE hash pass (buckets + sign bits), every
    level's cells by integer division and its sign by one bit of the packed
    parities.  Jittable with static ``hspec``; shared by hier_update, the
    gradient compressor, and the DP table folds."""
    items = jnp.asarray(items)
    fine = hspec.levels[-1]
    fine_items = hspec.level_items(hspec.n_levels - 1, items)
    idxs = hh.hierarchy_indices(hspec, params.base, items)
    bits = sign_bits(fine, params, fine_items)
    vals = values[None, :].astype(jnp.float32)
    out = []
    for lvl, (table, idx) in enumerate(zip(tables, idxs)):
        s = signs_from_bits(bits, lvl)
        out.append(add_signed(table, idx, s * vals))
    return tuple(out)


def hier_update(hspec: hh.HierarchySpec, state: CountSketchHierarchy,
                items: jax.Array, values: jax.Array) -> CountSketchHierarchy:
    """Fold full keys into every level's signed table (cascade path)."""
    tables = hier_fold_tables(hspec, state.params, state.tables, items,
                              values)
    return CountSketchHierarchy(state.params, tables)


def hier_update_reference(hspec: hh.HierarchySpec,
                          state: CountSketchHierarchy, items: jax.Array,
                          values: jax.Array) -> CountSketchHierarchy:
    """Per-level oracle: L independent flat updates, each re-hashing its
    prefix (and its prefix sign) from scratch -- the parity reference for
    :func:`hier_update` and the fused signed kernel."""
    items = jnp.asarray(items)
    new = []
    for lvl, (spec_l, table) in enumerate(zip(hspec.levels, state.tables)):
        st = CountSketchState(level_params(hspec, state.params, lvl), table)
        new.append(update(spec_l, st, hspec.level_items(lvl, items),
                          values).table)
    return CountSketchHierarchy(state.params, tuple(new))


def hier_merge(a: CountSketchHierarchy,
               b: CountSketchHierarchy) -> CountSketchHierarchy:
    """Cell-wise merge per level -- exact by linearity."""
    return CountSketchHierarchy(
        a.params, tuple(ta + tb for ta, tb in zip(a.tables, b.tables)))


def hier_query(hspec: hh.HierarchySpec, state: CountSketchHierarchy,
               level: int, prefixes: jax.Array) -> jax.Array:
    """Median estimate of each level-``level`` prefix's signed mass: [Q].

    ``prefixes``: uint32[Q, n_modules(levels 0..level)] in group-major
    order.  Jittable with static (hspec, level)."""
    spec_l = hspec.levels[level]
    p = level_params(hspec, state.params, level)
    st = CountSketchState(p, state.tables[level])
    return query(spec_l, st, prefixes)


# --------------------------------------------------------------------------
# Separable signed candidate queries + threshold descent
# --------------------------------------------------------------------------

def candidate_signed_partials(
    hspec: hh.HierarchySpec,
    params: CountSketchParams,
    level: int,
    prefixes: jax.Array,     # uint32[P, n_prefix_modules] (group-major)
    values: jax.Array,       # uint32[C, len(level group modules)]
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Index AND sign factors of the level-``level`` candidate grid.

    Returns (pp, cp, sp, sc): the bucket partials of
    hierarchy.candidate_partials plus float32 +-1 sign partials such that
    child (p, c) of row k lives at cell ``pp[k,p] + cp[k,c]`` with sign
    ``sp[k,p] * sc[k,c]`` -- signs compose multiplicatively because the
    cumulative parity XORs (the separability the mixed radix gives the
    index, the group product gives the sign).  Pure jnp, jittable.
    """
    spec_l = hspec.levels[level]
    lp = level_params(hspec, params, level)
    w = spec_l.width
    r_last = spec_l.ranges[-1]

    if level == 0:
        pp = jnp.zeros((w, prefixes.shape[0]), jnp.uint32)
        sp = jnp.ones((w, prefixes.shape[0]), jnp.float32)
    else:
        prefix_spec = hspec.levels[level - 1]
        prefix_params = level_params(hspec, params, level - 1)
        pp = sk.compute_indices(prefix_spec, prefix_params.base, prefixes)
        pp = pp * jnp.uint32(r_last)
        sp = signs(prefix_spec, prefix_params, prefixes)

    cp = sk.group_subindex(spec_l, lp.base, level, values)
    sc = 1.0 - 2.0 * group_sign_parity(spec_l, lp, level,
                                       values).astype(jnp.float32)
    return pp, cp, sp, sc


def candidate_estimates(
    hspec: hh.HierarchySpec,
    state: CountSketchHierarchy,
    level: int,
    prefixes: np.ndarray,    # uint32[P, n_prefix_modules]
    values: np.ndarray,      # uint32[C, len(level group modules)]
    *,
    use_kernel: bool = False,
    interpret: Optional[bool] = None,
    max_batch: Optional[int] = None,
) -> np.ndarray:
    """Median signed estimates for every (prefix x value) child: f32[P, C].

    ``use_kernel=True`` routes the per-row gather through the Pallas gather
    kernel (kernels/hier_query.hier_candidate_query_signed); the default
    is the jnp reference.  Both agree bit-for-bit on int32 tables; the
    kernel path refuses any other dtype (use the reference).
    ``max_batch`` chunks the prefix axis only, like
    hierarchy.candidate_estimates.
    """
    prefixes = jnp.asarray(np.asarray(prefixes, dtype=np.uint32))
    values = jnp.asarray(np.asarray(values, dtype=np.uint32))
    if use_kernel:
        state = hh._one_replica(state)
    pp, cp, sp, sc = candidate_signed_partials(hspec, state.params, level,
                                               prefixes, values)
    table = state.tables[level]
    from repro.kernels.hier_query import (
        hier_candidate_query_signed,
        hier_candidate_query_signed_ref,
    )
    if use_kernel:
        hh._check_kernel_dtype(table)

        def one(pp_chunk, sp_chunk):
            per_row = hier_candidate_query_signed(
                table, pp_chunk, cp, sp_chunk, sc, interpret=interpret)
            return jnp.median(per_row, axis=0)
    else:
        def one(pp_chunk, sp_chunk):
            per_row = hier_candidate_query_signed_ref(table, pp_chunk, cp,
                                                      sp_chunk, sc)
            return jnp.median(per_row, axis=0)

    p, c = pp.shape[1], cp.shape[1]
    if max_batch is None or p * c <= max_batch:
        return np.asarray(one(pp, sp))
    p_chunk = max(1, max_batch // max(c, 1))
    outs = []
    for s in range(0, p, p_chunk):
        ppc, spc = pp[:, s : s + p_chunk], sp[:, s : s + p_chunk]
        if ppc.shape[1] < p_chunk:
            # pad to the fixed chunk width so one compiled kernel serves
            # every chunk (pad index 0 is always a valid cell; sliced off)
            pad = p_chunk - ppc.shape[1]
            ppc = jnp.pad(ppc, ((0, 0), (0, pad)))
            spc = jnp.pad(spc, ((0, 0), (0, pad)), constant_values=1.0)
        outs.append(np.asarray(one(ppc, spc)))
    return np.concatenate(outs, axis=0)[:p]


def find_heavy_hitters(
    hspec: hh.HierarchySpec,
    state: CountSketchHierarchy,
    threshold: float,
    candidates: Sequence[np.ndarray],
    *,
    use_kernel: bool = False,
    interpret: Optional[bool] = None,
    max_batch: int = 1 << 16,
) -> Tuple[np.ndarray, np.ndarray]:
    """All keys whose |median estimate| >= ``threshold`` (signed descent).

    The CM descent prunes on overestimates; the signed descent prunes on
    |median|, which is unbiased per level -- a heavy prefix survives unless
    w/2 of its rows are simultaneously pushed below threshold by collisions
    (probability bounded by the usual median argument at each level).
    Returns (items uint32[K, n_modules] in schema order, float32 estimates
    of the FINEST level) sorted by |estimate| descending.
    """
    if len(candidates) != hspec.n_levels:
        raise ValueError(
            f"need one candidate set per level ({hspec.n_levels}), "
            f"got {len(candidates)}")
    threshold = float(threshold)

    prefixes = np.zeros((1, 0), dtype=np.uint32)
    est = np.zeros((1,), dtype=np.float32)
    for lvl in range(hspec.n_levels):
        cand = np.asarray(candidates[lvl], dtype=np.uint32)
        if cand.ndim != 2 or cand.shape[1] != len(hspec.base.partition[lvl]):
            raise ValueError(
                f"candidates[{lvl}] must be "
                f"[C, {len(hspec.base.partition[lvl])}]")
        if prefixes.shape[0] == 0 or cand.shape[0] == 0:
            n_mods = len(hh.level_modules(hspec.base, hspec.n_levels - 1))
            return (np.zeros((0, n_mods), np.uint32),
                    np.zeros((0,), np.float32))
        grid = candidate_estimates(
            hspec, state, lvl, prefixes, cand, use_kernel=use_kernel,
            interpret=interpret, max_batch=max_batch)
        keep_p, keep_c = np.nonzero(np.abs(grid) >= threshold)
        prefixes = np.concatenate([prefixes[keep_p], cand[keep_c]], axis=1)
        est = grid[keep_p, keep_c]

    order = np.argsort(-np.abs(est), kind="stable")
    return hspec.to_schema_order(prefixes[order]), est[order]
