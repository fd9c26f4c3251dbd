"""Pallas TPU kernel: fold one stream block into ALL hierarchy levels in a
single launch.

Under the shared per-group hash family (core/hierarchy.py) the level
indices nest in the mixed radix,

    idx_L = idx_finest // (r_{L+1} * ... * r_{m-1}),

so one composite hash per (row, item) determines every level's cell.  This
kernel concatenates the levels into one padded table ``[w, sum_L h_L_pad]``
(each level padded to a tile multiple) and runs ONE pallas_call with grid
``(total_tiles,)``; every step updates one ``[w, tile_h]`` column tile of
all w rows:

  * at the first tile every row's per-group hashes are evaluated once
    (uint32 limb CW arithmetic on the VPU, kernels/hashes.level_indices
    with the hash parameters read as SMEM scalars) and each level's index
    is cached in a ``[n_levels, w, B]`` VMEM scratch -- the level index is
    the Horner prefix of the group sub-indices, so no division is needed;
  * every tile derives its level and base column from the grid index
    (static tile offsets per level) and reads its level's cached indices;
  * the scatter-add is a one-hot MXU contraction: ``onehot[j, b] =
    (idx_b == base + j)`` over the B lanes, contracted with an ``[8, B]``
    frequency operand.  Integer tables split f into three bf16-exact limbs
    (8, 8 and a signed top limb of the arithmetic split, each |limb| <= 256)
    so every f32 partial sum is an exact integer (per-arrival |f| < 2^24,
    wrapper-checked upstream); float32 tables contract f itself at HIGHEST
    precision.

The flat single-level sketch update (kernels/sketch_update.py) is the same
kernel on a one-level plan.  The signed (Count-Sketch) fold adds a second
scratch with the packed cumulative sign-parity bits and multiplies the
frequency operand by each level's +-1 sign before the contraction.  The
conservative update is excluded (its row-coupling min forces a sequential
B-loop; see kernels/sketch_update_conservative.py).

Bit-exactness: identical to per-level core.sketch.update on integer tables
(int32 wrap included); for f32 tables exact whenever every per-cell
partial sum is exactly representable (e.g. integer-valued weights < 2^24),
tolerance-level otherwise (MXU accumulation order differs from scatter
order).

See docs/architecture.md ("Fused Pallas ingest") for where this kernel
sits in the ingest dataflow.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.hashes import (
    IndexPlan,
    all_indices,
    level_indices,
    make_plan,
    row_sign_bits,
    sign_bits,
    signs_from_bits,
)

_LANES = 128          # stream blocks are padded to a lane multiple
_LHS_ROWS = 8         # frequency operand rows (one f32 sublane tile)


class HierPlan(NamedTuple):
    """Static layout of the fused multi-level update (hashable, jit-static).

    ``plan`` is the FINEST level's IndexPlan (group-major chunk layout).
    Level l is indexed by groups ``0 .. level_groups[l] - 1``; equivalently
    its index is the finest composite index divided by ``level_divs[l]``.
    Level l's table occupies columns ``[level_offsets[l], level_offsets[l]
    + level_sizes[l])`` of the concatenated table, zero-padded up to
    ``level_pads[l]`` (a tile_h multiple)."""
    plan: IndexPlan
    level_sizes: Tuple[int, ...]    # h_l (unpadded cells per row)
    level_pads: Tuple[int, ...]     # h_l padded to a tile_h multiple
    level_divs: Tuple[int, ...]     # idx_l = idx_finest // div_l
    tile_h: int
    level_groups: Tuple[int, ...]   # groups indexing each level

    @property
    def n_levels(self) -> int:
        return len(self.level_sizes)

    @property
    def padded_cols(self) -> int:
        return sum(self.level_pads)

    @property
    def level_offsets(self) -> Tuple[int, ...]:
        out, off = [], 0
        for p in self.level_pads:
            out.append(off)
            off += p
        return tuple(out)

    @property
    def n_tiles(self) -> int:
        return self.padded_cols // self.tile_h

    @property
    def sign_bits(self) -> Tuple[int, ...]:
        """Bit of the packed cumulative parities holding each level's sign."""
        return tuple(g - 1 for g in self.level_groups)


def make_hier_plan(hspec, tile_h: int = 512) -> HierPlan:
    """Build the fused-update plan from a core.hierarchy.HierarchySpec."""
    fine = hspec.levels[-1]
    if fine.table_size >= 1 << 31:
        raise ValueError("finest table size must fit int32 cell indices")
    pads = tuple(-(-s.table_size // tile_h) * tile_h for s in hspec.levels)
    return HierPlan(
        plan=make_plan(fine),
        level_sizes=tuple(s.table_size for s in hspec.levels),
        level_pads=pads,
        level_divs=tuple(int(d) for d in hspec.level_divisors),
        tile_h=int(tile_h),
        level_groups=tuple(range(1, hspec.n_levels + 1)),
    )


def flat_plan(plan: IndexPlan, h_pad: int, tile_h: int) -> HierPlan:
    """One-level plan: the flat composite sketch ``[w, h_pad]`` table."""
    if h_pad % tile_h:
        raise ValueError(
            f"padded table width {h_pad} not a multiple of {tile_h}")
    return HierPlan(plan=plan, level_sizes=(plan.table_size,),
                    level_pads=(int(h_pad),), level_divs=(1,),
                    tile_h=int(tile_h),
                    level_groups=(len(plan.group_cols),))


def _tile_level(hplan: HierPlan, t):
    """(level, base column within the level) of global tile ``t``, from the
    static per-level tile offsets (scalar arithmetic on the grid index)."""
    level = jnp.int32(0)
    skipped = jnp.int32(0)
    start = 0
    for l, pad in enumerate(hplan.level_pads[:-1]):
        start += pad // hplan.tile_h
        past = (t >= start).astype(jnp.int32)
        level = level + past
        skipped = skipped + past * (pad // hplan.tile_h)
    return level, (t - skipped) * hplan.tile_h


def _fold_kernel(hplan: HierPlan, signed: bool, int_table: bool, *refs):
    """One column tile of all w rows."""
    if signed:
        (chunks_ref, f_ref, q_ref, r_ref, sq_ref, sr_ref,
         table_in_ref, table_out_ref, idx_ref, bits_ref) = refs
    else:
        (chunks_ref, f_ref, q_ref, r_ref,
         table_in_ref, table_out_ref, idx_ref) = refs
    plan, tile_h = hplan.plan, hplan.tile_h
    t = pl.program_id(0)

    def chunk(c):
        return chunks_ref[c : c + 1, :]                       # uint32[1, B]

    @pl.when(t == 0)
    def _hash_once():
        for k in range(plan.width):
            levels = level_indices(plan, hplan.level_groups, chunk,
                                   lambda c: q_ref[k, c],
                                   lambda j: r_ref[k, j])
            for l, idx in enumerate(levels):
                idx_ref[l, k : k + 1, :] = idx
            if signed:
                bits_ref[k : k + 1, :] = sign_bits(
                    plan, chunk, lambda c: sq_ref[k, c],
                    lambda j: sr_ref[k, j])

    level, base = _tile_level(hplan, t)
    lhs = f_ref[...]                                          # [8, B]
    b = lhs.shape[1]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (tile_h, b), 0)
    if signed:
        bit = jnp.int32(0)
        for l, sb in enumerate(hplan.sign_bits):
            bit = bit + (level == l).astype(jnp.int32) * sb
    for k in range(plan.width):
        local = idx_ref[level, k : k + 1, :] - base           # int32[1, B]
        lhs_k = lhs
        if signed:
            par = (bits_ref[k : k + 1, :] >> bit) & 1
            lhs_k = lhs * (1 - 2 * par).astype(lhs.dtype)
        onehot = (lanes == local).astype(lhs.dtype)           # [TH, B]
        d = jax.lax.dot_general(
            lhs_k, onehot, (((1,), (1,)), ((), ())),
            precision=None if int_table else jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)               # [8, TH]
        if int_table:
            delta = (d[0:1].astype(jnp.int32)
                     + (d[1:2].astype(jnp.int32) << 8)
                     + (d[2:3].astype(jnp.int32) << 16))
        else:
            delta = d[0:1]
        table_out_ref[k : k + 1, :] = table_in_ref[k : k + 1, :] + delta


def fold(hplan: HierPlan, table: jax.Array, chunks: jax.Array,
         freqs: jax.Array, params: Tuple[jax.Array, ...],
         interpret: Optional[bool]) -> jax.Array:
    """Shared launcher of the fused fold (call inside a jit).

    ``params`` is ``(q, r)`` or, signed, ``(q, r, sign_q, sign_r)``."""
    from repro.kernels.ops import resolve_interpret

    w, cols = table.shape
    if cols != hplan.padded_cols:
        raise ValueError(
            f"concatenated table has {cols} columns, plan expects "
            f"{hplan.padded_cols}")
    signed = len(params) == 4
    int_table = jnp.issubdtype(table.dtype, jnp.integer)
    b, c = chunks.shape
    b_pad = -(-b // _LANES) * _LANES
    if b_pad != b:
        # zero-frequency pad rows are no-ops (their cells are valid)
        chunks = jnp.pad(chunks, ((0, b_pad - b), (0, 0)))
        freqs = jnp.pad(freqs, (0, b_pad - b))
    if int_table:
        fi = freqs.astype(jnp.int32)
        rows = [fi & 0xFF, (fi >> 8) & 0xFF, fi >> 16]        # exact in bf16
        lhs_dtype = jnp.bfloat16
    else:
        rows = [freqs.astype(jnp.float32)]
        lhs_dtype = jnp.float32
    lhs = jnp.zeros((_LHS_ROWS, b_pad), lhs_dtype).at[: len(rows)].set(
        jnp.stack(rows).astype(lhs_dtype))

    def whole(shape):
        return pl.BlockSpec(shape, lambda t: (0,) * len(shape))

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    tbl_spec = pl.BlockSpec((w, hplan.tile_h), lambda t: (0, t))
    scratch = [pltpu.VMEM((hplan.n_levels, w, b_pad), jnp.int32)]
    if signed:
        scratch.append(pltpu.VMEM((w, b_pad), jnp.int32))
    in_specs = ([whole((c, b_pad)), whole((_LHS_ROWS, b_pad))]
                + [smem] * len(params) + [tbl_spec])
    return pl.pallas_call(
        functools.partial(_fold_kernel, hplan, signed, int_table),
        grid=(hplan.n_tiles,),
        in_specs=in_specs,
        out_specs=tbl_spec,
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype),
        scratch_shapes=scratch,
        input_output_aliases={len(in_specs) - 1: 0},
        interpret=resolve_interpret(interpret),
        name="hier_update",
    )(chunks.T, lhs, *(p.astype(jnp.int32) for p in params), table)


@functools.partial(
    jax.jit, static_argnames=("hplan", "interpret"), donate_argnums=(1,)
)
def hier_update_pallas(
    hplan: HierPlan,
    table: jax.Array,    # [w, hplan.padded_cols] int32 or float32 concat table
    chunks: jax.Array,   # uint32[B, C] finest-layout 16-bit key digits
    freqs: jax.Array,    # int32[B] or float32[B]
    q: jax.Array,        # uint32[w, C] shared-family multipliers
    r: jax.Array,        # uint32[w, m] shared-family offsets
    *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fold one stream block into every level's table in ONE pallas_call.

    Returns the new concatenated table (input buffer donated).  Zero-pad
    rows of the block are no-ops (freq 0); level pad columns are never hit
    (indices < h_l).
    """
    return fold(hplan, table, chunks, freqs, (q, r), interpret)


@functools.partial(
    jax.jit, static_argnames=("hplan", "interpret"), donate_argnums=(1,)
)
def hier_update_signed_pallas(
    hplan: HierPlan,
    table: jax.Array,    # [w, hplan.padded_cols] int32 or float32
    chunks: jax.Array,   # uint32[B, C] finest-layout 16-bit key digits
    freqs: jax.Array,    # int32[B] or float32[B], signed
    q: jax.Array,        # uint32[w, C] bucket multipliers
    r: jax.Array,        # uint32[w, m] bucket offsets
    sq: jax.Array,       # uint32[w, C] sign multipliers
    sr: jax.Array,       # uint32[w, m] sign offsets
    *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Signed cascade fold into every level's table in ONE pallas_call.

    cell_L += sign_L(row, item) * f, where sign_L is bit L of the packed
    cumulative parities -- bit-exact vs core.countsketch.hier_update on
    int32 tables (|f| < 2^24, negatives allowed).  Same donation contract
    as :func:`hier_update_pallas`."""
    return fold(hplan, table, chunks, freqs, (q, r, sq, sr), interpret)


@functools.partial(jax.jit, static_argnames=("hplan",))
def hier_update_signed_ref(
    hplan: HierPlan,
    table: jax.Array,
    chunks: jax.Array,
    freqs: jax.Array,
    q: jax.Array,
    r: jax.Array,
    sq: jax.Array,
    sr: jax.Array,
) -> jax.Array:
    """jnp oracle for the signed fused fold over the same concatenated
    padded table: hash indices + sign bits once per row, cascade divisions,
    per-level signed scatter-adds."""
    idx_fine = all_indices(hplan.plan, chunks, q, r)            # int32[w, B]
    bits = jnp.stack([row_sign_bits(hplan.plan, chunks, sq[k], sr[k])
                      for k in range(hplan.plan.width)], axis=0)  # [w, B]
    w = idx_fine.shape[0]
    out = table
    for lvl, (off, div) in enumerate(zip(hplan.level_offsets,
                                         hplan.level_divs)):
        idx = jax.lax.div(idx_fine, jnp.int32(div)) + off
        flat = (jnp.arange(w, dtype=jnp.int32)[:, None] * table.shape[1]
                + idx).reshape(-1)
        s = signs_from_bits(bits, lvl)
        f = (s * freqs.astype(jnp.float32)[None, :]).astype(table.dtype)
        out = out.reshape(-1).at[flat].add(f.reshape(-1)).reshape(table.shape)
    return out


@functools.partial(jax.jit, static_argnames=("hplan",))
def hier_update_ref(
    hplan: HierPlan,
    table: jax.Array,
    chunks: jax.Array,
    freqs: jax.Array,
    q: jax.Array,
    r: jax.Array,
) -> jax.Array:
    """jnp oracle over the SAME concatenated padded table: per-row composite
    hash once, cascade divisions, per-level scatter-adds (bit-identical to
    per-level core.sketch.update under the shared params)."""
    idx_fine = all_indices(hplan.plan, chunks, q, r)            # int32[w, B]
    w = idx_fine.shape[0]
    out = table
    for off, div in zip(hplan.level_offsets, hplan.level_divs):
        idx = jax.lax.div(idx_fine, jnp.int32(div)) + off
        flat = (jnp.arange(w, dtype=jnp.int32)[:, None] * table.shape[1]
                + idx).reshape(-1)
        f = jnp.broadcast_to(freqs.astype(table.dtype),
                             (w, freqs.shape[0])).reshape(-1)
        out = out.reshape(-1).at[flat].add(f).reshape(table.shape)
    return out
