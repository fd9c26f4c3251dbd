"""In-kernel composite index computation (shared by update/query kernels).

TPU Pallas has no 64-bit integer lanes, so all hashing is the uint32
two-limb Carter-Wegman arithmetic from ``repro.core.hashing`` -- those
functions are pure jnp and run unchanged inside Pallas kernel bodies.
This module provides the kernel-side "compute the composite cell index for
one sketch row" helper plus the static chunk-layout metadata both kernels
need.
"""
from __future__ import annotations

from typing import NamedTuple, Sequence, Tuple

import jax.numpy as jnp

from repro.core.hashing import addmod_p31, mulmod_p31_16
from repro.core.sketch import SketchSpec


class IndexPlan(NamedTuple):
    """Static (hashable) layout extracted from a SketchSpec for kernels."""
    group_cols: Tuple[Tuple[int, ...], ...]   # chunk columns per group
    ranges: Tuple[int, ...]
    strides: Tuple[int, ...]
    total_chunks: int
    width: int

    @property
    def table_size(self) -> int:
        out = 1
        for r in self.ranges:
            out *= int(r)
        return out


def make_plan(spec: SketchSpec) -> IndexPlan:
    return IndexPlan(
        group_cols=tuple(spec.group_chunk_columns(j) for j in range(spec.n_groups)),
        ranges=spec.ranges,
        strides=spec.strides,
        total_chunks=spec.schema.total_chunks,
        width=spec.width,
    )


def group_hashes(plan: IndexPlan, chunk, q_at, r_at) -> list:
    """Per-group CW hash values of ONE sketch row: uint32 vectors in [0, P31).

    ``chunk(c)`` returns the 16-bit digits of chunk column c (any vector
    shape), ``q_at(c)`` / ``r_at(j)`` the row's multiplier for column c and
    offset for group j.  The accessors let the same arithmetic read a
    ``[B, C]`` array on the jnp side and SMEM scalars / ``[C, B]`` VMEM rows
    inside a Pallas kernel, so both sides are bit-identical by construction.
    """
    out = []
    for j, cols in enumerate(plan.group_cols):
        acc = None
        for c in cols:
            x = chunk(c)
            if acc is None:
                acc = jnp.broadcast_to(
                    jnp.asarray(r_at(j)).astype(jnp.uint32), x.shape)
            acc = addmod_p31(acc, mulmod_p31_16(q_at(c), x))
        out.append(acc)
    return out


def level_indices(plan: IndexPlan, level_groups: Sequence[int], chunk, q_at,
                  r_at) -> list:
    """int32 cell indices of ONE row for every level of a composite cascade.

    Level l is indexed by groups ``0 .. level_groups[l] - 1``; its index is
    the mixed-radix (Horner) combination of those groups' sub-indices,
    which equals ``idx_finest // prod(later ranges)`` exactly (all values
    stay below the table size < 2^31).  ``level_groups=(m,)`` gives the
    flat composite index.
    """
    out, idx = [], None
    for j, acc in enumerate(group_hashes(plan, chunk, q_at, r_at)):
        g = acc % jnp.uint32(plan.ranges[j])
        idx = g if idx is None else idx * jnp.uint32(plan.ranges[j]) + g
        if j + 1 in level_groups:
            out.append(idx.astype(jnp.int32))
    return out


def row_indices(plan: IndexPlan, chunks: jnp.ndarray, q_row: jnp.ndarray,
                r_row: jnp.ndarray) -> jnp.ndarray:
    """Composite cell index for ONE sketch row.

    chunks: uint32[B, C]   16-bit key digits
    q_row:  uint32[C]      this row's multipliers
    r_row:  uint32[m]      this row's per-group offsets
    returns int32[B] cell indices in [0, h)
    """
    return level_indices(plan, (len(plan.group_cols),),
                         lambda c: chunks[:, c], lambda c: q_row[c],
                         lambda j: r_row[j])[0]


def all_indices(plan: IndexPlan, chunks: jnp.ndarray, q: jnp.ndarray,
                r: jnp.ndarray) -> jnp.ndarray:
    """int32[w, B] composite indices, one row per hash-function set."""
    return jnp.stack([row_indices(plan, chunks, q[k], r[k])
                      for k in range(plan.width)], axis=0)


def sign_bits(plan: IndexPlan, chunk, sq_at, sr_at) -> jnp.ndarray:
    """Packed cumulative sign-parity bits of ONE row (signed mode), int32.

    Bit L is the XOR of the per-group CW-hash parities of groups 0..L,
    i.e. the +-1 sign of the level-L prefix under the cascade (the flat /
    finest sign is the top group's bit) -- the kernel-side twin of
    core.countsketch.sign_bits, bit-identical per row.  Accessors as in
    :func:`group_hashes`.
    """
    bits = cum = None
    for j, acc in enumerate(group_hashes(plan, chunk, sq_at, sr_at)):
        par = acc & jnp.uint32(1)
        cum = par if cum is None else cum ^ par
        bit = cum << jnp.uint32(j)
        bits = bit if bits is None else bits | bit
    return bits.astype(jnp.int32)


def row_sign_bits(plan: IndexPlan, chunks: jnp.ndarray, sq_row: jnp.ndarray,
                  sr_row: jnp.ndarray) -> jnp.ndarray:
    """:func:`sign_bits` over ``chunks: uint32[B, C]`` with ``sq_row:
    uint32[C]`` / ``sr_row: uint32[m]``; returns int32[B]."""
    return sign_bits(plan, lambda c: chunks[:, c], lambda c: sq_row[c],
                     lambda j: sr_row[j])


def signs_from_bits(bits: jnp.ndarray, level) -> jnp.ndarray:
    """float32 +-1 signs for one level from packed cumulative parity bits.

    ``level`` may be a Python int or a traced scalar (the fused hierarchy
    kernel reads it from per-tile metadata)."""
    par = (bits >> jnp.int32(level)) & jnp.int32(1)
    return 1.0 - 2.0 * par.astype(jnp.float32)
