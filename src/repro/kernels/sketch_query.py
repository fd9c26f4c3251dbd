"""Pallas TPU kernel: per-row cell gathers, and the point queries built on it.

Every sketch query reads ONE cell per (row, lane): a point query reads the
item's cell in each of the w rows, a descent candidate reads the child's
cell.  :func:`gather_rows` is that read.  Grid = (w, lane chunks): each
step keeps one table row resident in VMEM as ``[h_pad / 128, 128]`` (lane
dense; fetched once per row, the block index is constant across the lane
axis) and walks its lane chunk with the cell indices in SMEM -- one
dynamic-sublane load of the cell's 128-lane window and a masked lane
reduce per index.  The work is O(lanes) per row, independent of the table
width, and the gather is exact for any 32-bit value (no limb split).

The hash evaluation of point queries runs in the jitted wrapper
(kernels/hashes.all_indices, the same arithmetic the update kernel runs
in-kernel), and the Count-Min ``min`` over the w rows is a VPU reduce done
by the wrapper.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.hashes import IndexPlan, all_indices, row_sign_bits

_LANES = 128
_LANE_CHUNK = 2048          # indices per grid step (SMEM block)
_VMEM_CAP_BYTES = 100 * 2**20   # of v5e's 128 MiB VMEM


def _gather_kernel(n: int, idx_ref, row_ref, out_ref):
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)

    def body(i, carry):
        ix = idx_ref[i]
        window = row_ref[pl.ds(ix >> 7, 1), :]                # [1, 128]
        out_ref[i] = jnp.sum(jnp.where(lane == (ix & (_LANES - 1)),
                                       window, 0))
        return carry

    jax.lax.fori_loop(0, n, body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_rows(
    table: jax.Array,    # [w, h] 32-bit
    idx: jax.Array,      # [w, L] cell indices in [0, h)
    *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """``table[k, idx[k, i]]`` for every row k and lane i: ``[w, L]``."""
    from repro.kernels.ops import resolve_interpret

    if table.dtype.itemsize != 4:
        raise ValueError(
            f"gather_rows covers 32-bit tables only (got {table.dtype}); "
            "use the jnp reference (jnp.take_along_axis)")
    w, h = table.shape
    n_lanes = idx.shape[1]
    h_pad = -(-h // _LANES) * _LANES
    if h_pad != h:
        table = jnp.pad(table, ((0, 0), (0, h_pad - h)))
    rows = h_pad // _LANES
    chunk = min(_LANE_CHUNK, -(-max(n_lanes, 1) // _LANES) * _LANES)
    l_pad = -(-max(n_lanes, 1) // chunk) * chunk
    # pad lanes read cell 0 (always valid) and are sliced off below
    idx = jnp.pad(idx.astype(jnp.int32), ((0, 0), (0, l_pad - n_lanes)))
    n_chunks = l_pad // chunk
    row_bytes = rows * _LANES * 4
    need = 2 * row_bytes + 2 * 2**20
    if need > _VMEM_CAP_BYTES:
        raise ValueError(
            f"a table row of {h} cells does not fit VMEM for the gather "
            "kernel; use the jnp reference (jnp.take_along_axis)")
    lane_spec = pl.BlockSpec((chunk,), lambda k, j: (k * n_chunks + j,),
                             memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        functools.partial(_gather_kernel, chunk),
        grid=(w, n_chunks),
        in_specs=[lane_spec,
                  pl.BlockSpec((None, rows, _LANES), lambda k, j: (k, 0, 0))],
        out_specs=lane_spec,
        out_shape=jax.ShapeDtypeStruct((w * l_pad,), table.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(need, 16 * 2**20)),
        interpret=resolve_interpret(interpret),
        name="gather_rows",
    )(idx.reshape(-1), table.reshape(w, rows, _LANES))
    return out.reshape(w, l_pad)[:, :n_lanes]


@functools.partial(jax.jit, static_argnames=("plan", "interpret"))
def sketch_query_pallas(
    plan: IndexPlan,
    table: jax.Array,    # int32[w, h_pad]
    chunks: jax.Array,   # uint32[Q, C]
    q: jax.Array,        # uint32[w, C]
    r: jax.Array,        # uint32[w, m]
    *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Count-Min estimates for Q queries: int32[Q]."""
    per_row = gather_rows(table.astype(jnp.int32),
                          all_indices(plan, chunks, q, r),
                          interpret=interpret)
    return jnp.min(per_row, axis=0)


@functools.partial(jax.jit, static_argnames=("plan", "interpret"))
def sketch_query_signed_pallas(
    plan: IndexPlan,
    table: jax.Array,    # int32[w, h_pad]
    chunks: jax.Array,   # uint32[Q, C]
    q: jax.Array,        # uint32[w, C]
    r: jax.Array,        # uint32[w, m]
    sq: jax.Array,       # uint32[w, C]
    sr: jax.Array,       # uint32[w, m]
    *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Per-row signed estimates: int32[w, Q] (caller takes the median).

    Returning the rows rather than the median keeps the kernel output
    bit-comparable to core.countsketch.query_rows and lets callers apply
    row-level robustness filters."""
    if table.dtype != jnp.int32:
        raise ValueError("signed query kernel covers int32 tables only; "
                         "use the jnp reference for other dtypes")
    vals = gather_rows(table, all_indices(plan, chunks, q, r),
                       interpret=interpret)
    bits = jnp.stack([row_sign_bits(plan, chunks, sq[k], sr[k])
                      for k in range(plan.width)], axis=0)
    top = len(plan.group_cols) - 1
    return vals * (1 - 2 * ((bits >> top) & 1))
