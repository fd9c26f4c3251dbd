"""Pallas TPU kernel: flat sketch update as one-hot x frequency MXU matmuls.

TPU adaptation of the paper's scalar update loop: scatter-add is the
canonical TPU anti-pattern, so a stream block of B items becomes, per range
tile, a dense one-hot matrix ``onehot[j, b] = (idx_b == tile_start + j)``
contracted with the frequency operand on the MXU:

    table[:, tile] += f . onehot^T        # collisions sum inside the MXU

The flat ``[w, h_pad]`` table is the one-level case of the fused hierarchy
fold, so both wrappers here launch kernels/hier_update.fold on a one-level
plan (grid ``(h_pad / tile_h,)``, hashes evaluated once per block into a
VMEM scratch).  Exactness for integer tables comes from the bf16-exact
frequency limbs described there: valid for per-arrival |f| < 2^24
(wrapper-checked); larger weights take the jnp reference path.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels.hashes import IndexPlan
from repro.kernels.hier_update import flat_plan, fold


def padded_table_size(h: int, tile_h: int) -> int:
    return ((h + tile_h - 1) // tile_h) * tile_h


@functools.partial(
    jax.jit, static_argnames=("plan", "tile_h", "interpret"),
    donate_argnums=(1,),
)
def sketch_update_pallas(
    plan: IndexPlan,
    table: jax.Array,    # [w, h_pad] int32 or float32, h_pad % tile_h == 0
    chunks: jax.Array,   # uint32[B, C]
    freqs: jax.Array,    # int32[B] or float32[B]
    q: jax.Array,        # uint32[w, C]
    r: jax.Array,        # uint32[w, m]
    *,
    tile_h: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fold one stream block into the (padded) table. Returns the new table.

    The table buffer is DONATED (effective on CPU and TPU): per-block
    ingest accumulates in place instead of copying the table every call.
    Callers must rebind to the returned table (KernelSketch.update does).
    """
    return fold(flat_plan(plan, table.shape[1], tile_h), table, chunks,
                freqs, (q, r), interpret)


@functools.partial(
    jax.jit, static_argnames=("plan", "tile_h", "interpret"),
    donate_argnums=(1,),
)
def sketch_update_signed_pallas(
    plan: IndexPlan,
    table: jax.Array,    # [w, h_pad] int32 or float32, h_pad % tile_h == 0
    chunks: jax.Array,   # uint32[B, C]
    freqs: jax.Array,    # int32[B] or float32[B], signed
    q: jax.Array,        # uint32[w, C]
    r: jax.Array,        # uint32[w, m]
    sq: jax.Array,       # uint32[w, C]   sign-hash multipliers
    sr: jax.Array,       # uint32[w, m]   sign-hash offsets
    *,
    tile_h: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Signed (Count-Sketch) fold: cell += sign(row, item) * f.

    Same donation contract as :func:`sketch_update_pallas`; freqs may be
    negative (turnstile).  Bit-exact vs core.countsketch.update on int32
    tables for |f| < 2^24."""
    return fold(flat_plan(plan, table.shape[1], tile_h), table, chunks,
                freqs, (q, r, sq, sr), interpret)
