"""Pallas TPU kernel: conservative (Estan-Varghese) sketch update.

A conservative step for item b with frequency f is

    cur_k = table[k, idx_k(b)]            (min-gather over the w rows)
    est   = min_k cur_k + f
    table[k, idx_k(b)] = max(cur_k, est)  (max-scatter)

Two structural facts rule out the linear kernel's (w, h/TILE_H) one-hot
matmul grid (sketch_update.py):

  * the min couples all w rows of one item, and each row's cell lands in a
    *different* h-tile, so no single (row, tile) step ever sees the values
    the min needs;
  * the update is sequential in B -- item b+1 must read item b's writes
    (duplicate keys inside one block are the common case for skewed
    streams), so the per-item work cannot be reordered or batched into one
    contraction.

The kernel therefore keeps the full w-row working set -- the (w, h_pad)
table -- resident in VMEM and makes the *stream* the grid axis: TPU Pallas
grid steps execute sequentially on a core, so grid=(B/CHUNK_B,) walks the
block in stream order while the pipeline double-buffers the next chunk's
(chunks, freqs) inputs behind the current chunk's compute.  The table
in/out blocks use a constant index map (the reduction-by-revisiting
pattern), so the table is fetched once, stays in VMEM across steps, and is
written back once at the end.  Within a step the chunk's per-item row
indices are recomputed on the VPU (kernels/hashes.row_indices -- cheap,
and it avoids an HBM round-trip for a (w, B) index tensor), then a
``fori_loop`` applies the B-sequential min-gather/max-scatter.

Unlike the linear kernel there is no MXU contraction and hence no float
accumulation: gather / integer-or-float min / add / max are exact in both
int32 and float32, so the kernel is bit-identical to
``core.sketch.update_conservative`` for both table dtypes (no limb split
needed).

VMEM budget: the resident set is ``2 * w * h_pad * itemsize`` (aliased
table in + out blocks) plus the double-buffered chunk inputs.
:func:`conservative_chunk_b` picks the largest power-of-two B-chunk that
fits beside the table -- the chunked-B variant -- and returns None when the
table itself cannot fit, in which case the caller must take the jnp
reference path (``kernels/ops.KernelSketch`` refuses with an error naming
it).

TPU lowering: the per-item ``idx[k, i]`` read and the dynamic-lane table
access in the ``fori_loop`` body have no Mosaic lowering, so this kernel
runs in interpret mode only; a compiled launch is refused up front with an
error naming the jnp path (core.sketch.update_conservative_jit).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.hashes import IndexPlan, row_indices

_VMEM_BUDGET_BYTES = 14 * 2**20   # leave ~2 MB of the ~16 MB VMEM for slack


def conservative_chunk_b(
    b: int,
    c: int,
    w: int,
    h_pad: int,
    itemsize: int,
    vmem_limit_bytes: int = _VMEM_BUDGET_BYTES,
) -> Optional[int]:
    """Largest B-chunk (a divisor of b, found by halving while even) whose
    double-buffered inputs fit next to the VMEM-resident table; None when
    even chunk=1 cannot fit (the caller must fall back to the jnp
    reference path).  Halving an even divisor of b yields a divisor of b,
    so the returned chunk always divides b; an odd over-budget chunk drops
    straight to 1."""
    table_bytes = 2 * w * h_pad * itemsize        # aliased in + out blocks

    def fits(chunk: int) -> bool:
        return table_bytes + 2 * chunk * (c * 4 + itemsize) <= vmem_limit_bytes

    chunk = b
    while chunk > 1 and not fits(chunk):
        chunk = chunk // 2 if chunk % 2 == 0 else 1
    return chunk if fits(chunk) else None


def _conservative_kernel(plan: IndexPlan,
                         chunks_ref, f_ref, q_ref, r_ref,
                         table_in_ref, table_out_ref):
    """One B-chunk step: sequential min-gather / max-scatter over the chunk."""
    s = pl.program_id(0)

    @pl.when(s == 0)
    def _init():
        table_out_ref[...] = table_in_ref[...]

    # per-item composite cell index for every row: int32[w, CHUNK_B]
    idx = jnp.stack(
        [row_indices(plan, chunks_ref[...], q_ref[k], r_ref[k])
         for k in range(plan.width)], axis=0)
    f = f_ref[...]

    def body(i, carry):
        cur = [table_out_ref[k, idx[k, i]] for k in range(plan.width)]
        est = functools.reduce(jnp.minimum, cur) + f[i]
        for k in range(plan.width):
            table_out_ref[k, idx[k, i]] = jnp.maximum(cur[k], est)
        return carry

    jax.lax.fori_loop(0, f.shape[0], body, 0)


@functools.partial(
    jax.jit,
    static_argnames=("plan", "chunk_b", "vmem_limit_bytes", "interpret"),
)
def sketch_update_conservative_pallas(
    plan: IndexPlan,
    table: jax.Array,    # [w, h_pad] int32 or float32
    chunks: jax.Array,   # uint32[B, C]
    freqs: jax.Array,    # [B], non-negative; cast to the table dtype
    q: jax.Array,        # uint32[w, C]
    r: jax.Array,        # uint32[w, m]
    *,
    chunk_b: Optional[int] = None,
    vmem_limit_bytes: int = _VMEM_BUDGET_BYTES,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Conservatively fold one stream block into the (padded) table.

    Bit-identical to ``core.sketch.update_conservative`` applied to the
    same item order (zero-frequency pad items are no-ops: est = min <= cur).
    Raises when the table working set exceeds ``vmem_limit_bytes``; use
    :func:`conservative_chunk_b` to pre-check and route to the reference
    path instead.
    """
    from repro.kernels.ops import resolve_interpret

    if not resolve_interpret(interpret):
        raise ValueError(
            "the conservative Pallas kernel runs in interpret mode only: "
            "use the jnp path (core.sketch.update_conservative_jit)")
    w, h_pad = table.shape
    b, c = chunks.shape
    if chunk_b is None:
        chunk_b = conservative_chunk_b(b, c, w, h_pad, table.dtype.itemsize,
                                       vmem_limit_bytes)
        if chunk_b is None:
            raise ValueError(
                f"conservative table working set 2*{w}*{h_pad}*"
                f"{table.dtype.itemsize}B exceeds the VMEM budget "
                f"({vmem_limit_bytes}B): take the core.sketch reference path")
    if b % chunk_b:
        raise ValueError(f"block length {b} not a multiple of chunk_b={chunk_b}")

    grid = (b // chunk_b,)
    kernel = functools.partial(_conservative_kernel, plan)
    tbl_spec = pl.BlockSpec((w, h_pad), lambda s: (0, 0))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((chunk_b, c), lambda s: (s, 0)),
            pl.BlockSpec((chunk_b,), lambda s: (s,)),
            pl.BlockSpec((w, c), lambda s: (0, 0)),
            pl.BlockSpec((w, r.shape[1]), lambda s: (0, 0)),
            tbl_spec,
        ],
        out_specs=tbl_spec,
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype),
        input_output_aliases={4: 0},
        interpret=True,
    )(chunks, freqs.astype(table.dtype), q, r, table)
