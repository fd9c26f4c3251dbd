"""Pallas TPU kernel: all candidate children of heavy prefixes in one launch.

The hierarchical heavy-hitter descent (core/hierarchy.py) expands P
surviving prefixes by C candidate values of the next module group and needs
a Count-Min estimate for every child.  The mixed-radix cell address is
separable -- ``idx(p, c) = pp[k, p] + cp[k, c]`` per row k, with the prefix
partial pre-scaled by the last group's range and the child partial's stride
equal to 1 -- so the wrapper forms the ``[w, P*C]`` child indices from the
two partial-index factors without rehashing anything, and one
kernels/sketch_query.gather_rows launch reads every child's cell in every
row (exact for any int32 count, O(P*C) work per row).  The final Count-Min
min (or, signed, the +-1 sign product) is a VPU op fused into the jitted
wrapper.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.sketch_query import gather_rows


def _child_indices(pp: jax.Array, cp: jax.Array) -> jax.Array:
    w = pp.shape[0]
    return (pp.astype(jnp.int32)[:, :, None]
            + cp.astype(jnp.int32)[:, None, :]).reshape(w, -1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def hier_candidate_query(
    table: jax.Array,   # int32[w, h]
    pp: jax.Array,      # uint32[w, P] prefix partial indices (pre-scaled)
    cp: jax.Array,      # uint32[w, C] child partial indices (stride 1)
    *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Count-Min estimates for every (prefix, candidate) child: int32[P, C].

    Other table dtypes must take :func:`hier_candidate_query_ref`.
    """
    if table.dtype != jnp.int32:
        raise ValueError(
            f"hier_candidate_query supports int32 tables only (got "
            f"{table.dtype}); use hier_candidate_query_ref")
    per_row = gather_rows(table, _child_indices(pp, cp), interpret=interpret)
    return jnp.min(per_row, axis=0).reshape(pp.shape[1], cp.shape[1])


@jax.jit
def hier_candidate_query_ref(table: jax.Array, pp: jax.Array,
                             cp: jax.Array) -> jax.Array:
    """Pure-jnp oracle: same signature, [P, C] in the table's dtype (unlike
    the kernel it is exact for int64 / float tables too)."""
    vals = jnp.take_along_axis(table, _child_indices(pp, cp), axis=1)
    return jnp.min(vals, axis=0).reshape(pp.shape[1], cp.shape[1])


# --------------------------------------------------------------------------
# Signed (Count-Sketch) candidate grid
# --------------------------------------------------------------------------
#
# The signed descent needs the same P x C gather with two extra separable
# factors: the sign of child (p, c) at row k is ``sp[k, p] * sc[k, c]``
# (cumulative parities XOR, so +-1 signs multiply), computed OUTSIDE the
# kernel by core.countsketch.candidate_signed_partials exactly like the
# bucket partials.  The kernel gathers the exact int32 cell value and the
# wrapper multiplies by the +-1 product in int32; the median over rows is
# the caller's reduce (rows are returned so the estimator stays
# bit-comparable to the jnp reference).

@functools.partial(jax.jit, static_argnames=("interpret",))
def hier_candidate_query_signed(
    table: jax.Array,   # int32[w, h]
    pp: jax.Array,      # uint32[w, P] prefix partial indices (pre-scaled)
    cp: jax.Array,      # uint32[w, C] child partial indices (stride 1)
    sp: jax.Array,      # +-1[w, P] prefix sign partials
    sc: jax.Array,      # +-1[w, C] child sign partials
    *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Per-row signed estimates for every (prefix, candidate) child:
    int32[w, P, C].  The caller takes the median over rows (float); keeping
    the rows int32 keeps the gather bit-exact vs the jnp reference."""
    if table.dtype != jnp.int32:
        raise ValueError(
            f"hier_candidate_query_signed supports int32 tables only (got "
            f"{table.dtype}); use hier_candidate_query_signed_ref")
    w, p, c = pp.shape[0], pp.shape[1], cp.shape[1]
    vals = gather_rows(table, _child_indices(pp, cp), interpret=interpret)
    sgn = (sp.astype(jnp.int32)[:, :, None]
           * sc.astype(jnp.int32)[:, None, :])
    return vals.reshape(w, p, c) * sgn


@jax.jit
def hier_candidate_query_signed_ref(table: jax.Array, pp: jax.Array,
                                    cp: jax.Array, sp: jax.Array,
                                    sc: jax.Array) -> jax.Array:
    """Pure-jnp signed oracle: float32[w, P, C] per-row estimates (exact
    for int32 tables; dtype-preserving gather, sign applied in float)."""
    w = table.shape[0]
    p, c = pp.shape[1], cp.shape[1]
    vals = jnp.take_along_axis(table, _child_indices(pp, cp), axis=1)
    vals = vals.astype(jnp.float32).reshape(w, p, c)
    return vals * sp.astype(jnp.float32)[:, :, None] \
        * sc.astype(jnp.float32)[:, None, :]


# --------------------------------------------------------------------------
# Request axis: Q concurrent queries in the one launch
# --------------------------------------------------------------------------
#
# Nothing ties a lane to "one query", so Q concurrent requests' prefix sets
# ride the lane axis: [w, Q, P] prefix partials flatten to [w, Q*P], the
# SAME launch runs once with Q*P*C lanes, and the output folds back to
# [Q, P, C].  Each lane's estimate is computed independently, so every
# request's [P, C] slab is bit-identical to its own single-request launch
# -- batching Q queries costs one launch per level instead of Q (the
# sketch serving engine's batched descent).

@functools.partial(jax.jit, static_argnames=("interpret",))
def hier_candidate_query_batched(
    table: jax.Array,   # int32[w, h]
    pp: jax.Array,      # uint32[w, Q, P] per-request prefix partials
    cp: jax.Array,      # uint32[w, C] child partials (shared by all requests)
    *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Count-Min estimates for Q requests' (prefix, candidate) grids:
    int32[Q, P, C], one kernel launch total."""
    w, q, p = pp.shape
    flat = hier_candidate_query(table, pp.reshape(w, q * p), cp,
                                interpret=interpret)
    return flat.reshape(q, p, cp.shape[1])


@jax.jit
def hier_candidate_query_batched_ref(table: jax.Array, pp: jax.Array,
                                     cp: jax.Array) -> jax.Array:
    """Request-axis jnp oracle: [w, Q, P] partials -> [Q, P, C] estimates
    in the table's dtype (the non-int32 / non-kernel batched path)."""
    w, q, p = pp.shape
    flat = hier_candidate_query_ref(table, pp.reshape(w, q * p), cp)
    return flat.reshape(q, p, cp.shape[1])
