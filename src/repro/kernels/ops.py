"""Jit'd public wrappers around the Pallas sketch kernels.

These adapt the high-level ``SketchSpec``/``SketchState`` API (core/sketch.py)
to the kernels: chunk extraction, padding the table width to the tile size,
and padding stream blocks to a fixed block length (so one compiled kernel
serves the whole stream).  Whether a kernel runs compiled or in Pallas
interpret mode is decided in ONE place, :func:`default_interpret`: compiled
on a TPU, interpreted (the kernel body executed by the CPU backend --
bit-identical logic, which is how the kernel tests run) anywhere else.  A
caller may force either with ``interpret=True/False``.

Three update modes share the wrapper:

  * ``mode="linear"`` (default): the one-hot MXU matmul update
    (kernels/sketch_update.py).  The table stays linear in the stream, so
    sketches merge cell-wise (:meth:`KernelSketch.merge`) and compose with
    the distributed runtime.
  * ``mode="conservative"``: the Estan-Varghese conservative update
    (kernels/sketch_update_conservative.py) -- strictly tighter estimates,
    but the table is NOT linear in the stream, so ``merge``/``state()``
    (the cell-wise merge surfaces) are refused; query-side use is
    unchanged.  The kernel runs in interpret mode only: on a TPU (or when
    the table working set exceeds its VMEM budget) the update raises and
    names the jnp path (core.sketch.update_conservative_jit).
  * ``mode="signed"``: the Count-Sketch variant (core/countsketch.py) --
    the same one-hot limb matmul with the per-group composite +-1 sign
    folded into the frequency limbs, a median-of-rows estimator on the
    query side, and signed (turnstile) frequencies allowed on int tables.
    Signed tables ARE linear, so merge / sharded psum folds / table
    donation all apply exactly as in linear mode.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import countsketch as cskt
from repro.core import sketch as sk
from repro.kernels.hashes import make_plan
from repro.kernels.hier_update import (
    hier_update_pallas,
    hier_update_signed_pallas,
    make_hier_plan,
)
from repro.kernels.sketch_update import (
    padded_table_size,
    sketch_update_pallas,
    sketch_update_signed_pallas,
)
from repro.kernels.sketch_update_conservative import (
    conservative_chunk_b,
    sketch_update_conservative_pallas,
)
from repro.kernels.sketch_query import (
    sketch_query_pallas,
    sketch_query_signed_pallas,
)

_MAX_KERNEL_FREQ = 1 << 24  # two 12-bit limbs

MODES = ("linear", "conservative", "signed")


def default_interpret() -> bool:
    """The one backend test for Pallas interpret mode: compiled on a TPU,
    interpreted everywhere else."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``interpret`` as passed, or :func:`default_interpret` when None.
    Every kernel entry point resolves its ``interpret=None`` default here."""
    return default_interpret() if interpret is None else bool(interpret)


def check_linear_kernel_freqs(freqs: np.ndarray, table_dtype) -> None:
    """Reject frequencies the linear one-hot kernels cannot represent.

    The int path uses a two-12-bit-limb split whose f32 partial sums are
    exact only for magnitudes < 2^24, so that bound applies to |f|, not
    just positive f -- and negative frequencies are rejected outright
    rather than silently relying on arithmetic-shift limb behaviour.
    Float tables are unconstrained (turnstile / gradient weights).  Shared
    by KernelSketch (flat) and KernelHierarchy (fused multi-level).
    """
    if freqs.size == 0 or not jnp.issubdtype(table_dtype, jnp.integer):
        return
    if np.abs(freqs).max() >= _MAX_KERNEL_FREQ:
        raise ValueError(
            "per-arrival |frequency| >= 2^24 overflows the int-table "
            "limb split: use the core.sketch path")
    if freqs.min() < 0:
        raise ValueError(
            "negative frequencies are not supported on int tables: "
            "use the core.sketch path (or a float32 table)")


def check_signed_kernel_freqs(freqs: np.ndarray, table_dtype) -> None:
    """Signed-mode frequency guard: negatives are the point (turnstile /
    gradient deltas), so only the limb-split magnitude bound applies.  The
    signed kernels split f arithmetically -- f = (f & 0xFFF) + ((f >> 12)
    << 12) -- which is exact for |f| < 2^24 including negative f."""
    if freqs.size == 0 or not jnp.issubdtype(table_dtype, jnp.integer):
        return
    if np.abs(freqs).max() >= _MAX_KERNEL_FREQ:
        raise ValueError(
            "per-arrival |frequency| >= 2^24 overflows the int-table "
            "limb split: use the core.countsketch path")


class KernelSketch:
    """Sketch whose table lives padded for the Pallas kernels."""

    def __init__(self, spec: sk.SketchSpec, key: jax.Array, *,
                 tile_h: int = 512, block_b: int = 1024,
                 dtype=jnp.int32, interpret: Optional[bool] = None,
                 mode: str = "linear"):
        if mode not in MODES:
            raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
        self.spec = spec
        self.plan = make_plan(spec)
        if mode == "signed":
            # draw through countsketch so the jnp reference built from the
            # same key is bit-identical (bucket AND sign hashes)
            self.cs_params = cskt.init_params(spec, key)
            self.params = self.cs_params.base
        else:
            self.cs_params = None
            self.params = sk.init_params(spec, key)
        self.tile_h = int(tile_h)
        self.block_b = int(block_b)
        self.h_pad = padded_table_size(spec.table_size, tile_h)
        self.table = jnp.zeros((spec.width, self.h_pad), dtype=dtype)
        self.interpret = resolve_interpret(interpret)
        if mode == "conservative" and not self.interpret:
            raise ValueError(
                "the conservative Pallas kernel runs in interpret mode only "
                "(its per-item dynamic table access does not lower for TPU): "
                "use the jnp path (core.sketch.update_conservative_jit)")
        self.mode = mode
        self._sharded_folds: dict = {}  # (mesh, data_axes) -> jitted fold

    # -- stream ops ---------------------------------------------------------
    def _check_freqs(self, freqs: np.ndarray) -> None:
        """Reject frequencies the kernel paths cannot represent.

        The *linear* int path uses a two-12-bit-limb split whose f32
        partial sums are exact only for magnitudes < 2^24, so that bound
        applies to |f|, not just positive f -- and negative frequencies are
        rejected outright rather than silently relying on arithmetic-shift
        limb behaviour.  The conservative kernel has no limb split
        (gather/min/add/max, bit-exact at any int32 magnitude) so only the
        non-negativity requirement applies there (f < 0 would be a silent
        no-op: est = min + f <= every cell).  Turnstile streams take the
        core.sketch reference path or a float table.
        """
        if freqs.size == 0:
            return
        if self.mode == "conservative":
            sk.check_conservative_freqs(freqs, self.table.dtype)
            return
        if self.mode == "signed":
            check_signed_kernel_freqs(freqs, self.table.dtype)
            return
        check_linear_kernel_freqs(freqs, self.table.dtype)

    def update(self, items, freqs) -> None:
        items = np.asarray(items, dtype=np.uint32)
        freqs = np.asarray(freqs)
        self._check_freqs(freqs)
        b = self.block_b
        for s in range(0, items.shape[0], b):
            blk_i = items[s : s + b]
            blk_f = freqs[s : s + b]
            if blk_i.shape[0] < b:
                pad = b - blk_i.shape[0]
                blk_i = np.pad(blk_i, ((0, pad), (0, 0)))
                blk_f = np.pad(blk_f, (0, pad))
            chunks = self.spec.schema.module_chunks(jnp.asarray(blk_i))
            if self.mode == "conservative":
                self._update_block_conservative(blk_i, chunks,
                                                jnp.asarray(blk_f))
            elif self.mode == "signed":
                self.table = sketch_update_signed_pallas(
                    self.plan, self.table, chunks, jnp.asarray(blk_f),
                    self.params.q, self.params.r,
                    self.cs_params.sign_q, self.cs_params.sign_r,
                    tile_h=self.tile_h, interpret=self.interpret,
                )
            else:
                self.table = sketch_update_pallas(
                    self.plan, self.table, chunks, jnp.asarray(blk_f),
                    self.params.q, self.params.r,
                    tile_h=self.tile_h, interpret=self.interpret,
                )

    def _update_block_conservative(self, blk_i, chunks, blk_f) -> None:
        w, h_pad = self.table.shape
        chunk_b = conservative_chunk_b(
            chunks.shape[0], chunks.shape[1], w, h_pad,
            self.table.dtype.itemsize)
        if chunk_b is None:
            raise ValueError(
                f"conservative table working set 2*{w}*{h_pad}*"
                f"{self.table.dtype.itemsize}B exceeds the kernel's VMEM "
                "budget: use the jnp path (core.sketch.update_conservative_jit)")
        self.table = sketch_update_conservative_pallas(
            self.plan, self.table, chunks, blk_f,
            self.params.q, self.params.r,
            chunk_b=chunk_b, interpret=self.interpret,
        )

    def query(self, items) -> np.ndarray:
        """Point estimates: min over rows (linear/conservative) or the
        unbiased median over signed rows (signed mode, float32)."""
        items = np.asarray(items, dtype=np.uint32)
        if self.mode == "signed":
            rows = self.query_rows(items)
            return np.median(rows.astype(np.float32), axis=0)
        chunks = self.spec.schema.module_chunks(jnp.asarray(items))
        est = sketch_query_pallas(
            self.plan, self.table, chunks, self.params.q, self.params.r,
            interpret=self.interpret,
        )
        return np.asarray(est)

    def query_rows(self, items) -> np.ndarray:
        """Signed mode only: per-row signed estimates [w, Q] (the medians'
        raw material; bit-exact vs core.countsketch.query_rows on int32
        tables).  Float tables take the jnp reference gather."""
        if self.mode != "signed":
            raise ValueError("query_rows is the signed-mode estimator; "
                             "linear/conservative sketches use query()")
        items = np.asarray(items, dtype=np.uint32)
        if self.table.dtype == jnp.int32:
            chunks = self.spec.schema.module_chunks(jnp.asarray(items))
            rows = sketch_query_signed_pallas(
                self.plan, self.table, chunks, self.params.q, self.params.r,
                self.cs_params.sign_q, self.cs_params.sign_r,
                interpret=self.interpret,
            )
            return np.asarray(rows)
        rows, _ = cskt.query_rows(self.spec, self.cs_state(),
                                  jnp.asarray(items))
        return np.asarray(rows)

    def sharded_update(self, mesh, data_axes, items, freqs) -> None:
        """Distributed fold: shard the block over ``data_axes``, psum-merge
        the per-device deltas, add to the table.  Linear mode only -- the
        conservative table is not linear in the stream, so sharded folds of
        it cannot be psum-merged (core.distributed.require_linear).

        Inside shard_map each device runs the jnp reference fold, not the
        Pallas kernel; it is bit-identical to the kernel by the parity
        tests.  The jitted fold
        is cached per (mesh, data_axes) and the per-shard row count padded
        to the next power of two: an eager shard_map re-traces on every
        call, which would dominate streaming ingest (same fix as
        ShardedTopKService's cached wrappers).
        """
        from repro.core import distributed as dist

        dist.require_linear(self.mode, "KernelSketch.sharded_update")
        items = np.asarray(items, dtype=np.uint32)
        freqs = np.asarray(freqs)
        # no _check_freqs here: the limb-split bounds only constrain the
        # Pallas kernel path, and this fold runs the exact jnp reference
        # inside shard_map (turnstile / large-weight streams are fine)
        n_shards = int(np.prod([mesh.shape[a] for a in data_axes],
                               dtype=np.int64))
        items, freqs, _ = dist.pad_block_pow2(items, freqs, n_shards)
        cache_key = (mesh, tuple(data_axes))
        fold = self._sharded_folds.get(cache_key)
        if fold is None:
            if self.mode == "signed":
                fold = jax.jit(lambda it, fr: dist.sharded_signed_build(
                    self.spec, self.cs_params, mesh, tuple(data_axes),
                    it, fr, table_dtype=self.table.dtype))
            else:
                fold = jax.jit(lambda it, fr: dist.sharded_build(
                    self.spec, self.params, mesh, tuple(data_axes), it, fr,
                    table_dtype=self.table.dtype))
            self._sharded_folds[cache_key] = fold
        delta = fold(jnp.asarray(items), jnp.asarray(freqs))
        h = self.spec.table_size
        self.table = self.table.at[:, :h].add(delta)

    # -- interop ------------------------------------------------------------
    def merge(self, other: "KernelSketch") -> None:
        """Cell-wise in-place merge (cross-shard fold), linear mode only.

        Conservative tables are not linear in the stream -- the sum of two
        conservatively built tables is NOT the table of the concatenated
        stream -- so merging them is refused rather than silently wrong.
        """
        if self.mode == "conservative" or other.mode == "conservative":
            raise ValueError(
                "merge is only defined for linear-table sketches (linear "
                "or signed mode): conservative tables are not linear in "
                "the stream")
        if self.mode != other.mode:
            raise ValueError(
                "merge requires identical modes (a min-estimated and a "
                "median-estimated table are different objects even though "
                "both are linear)")
        if self.spec != other.spec or self.h_pad != other.h_pad:
            raise ValueError("merge requires identical specs and padding")
        if self.table.dtype != other.table.dtype:
            raise ValueError(
                "merge requires identical table dtypes (an int32+float32 "
                "sum would silently promote and lose exact counts)")
        if not (np.array_equal(np.asarray(self.params.q), np.asarray(other.params.q))
                and np.array_equal(np.asarray(self.params.r), np.asarray(other.params.r))):
            raise ValueError(
                "merge requires identical hash params (same spec and key)")
        if self.mode == "signed" and not (
                np.array_equal(np.asarray(self.cs_params.sign_q),
                               np.asarray(other.cs_params.sign_q))
                and np.array_equal(np.asarray(self.cs_params.sign_r),
                                   np.asarray(other.cs_params.sign_r))):
            raise ValueError(
                "merge requires identical sign-hash params (same spec "
                "and key)")
        self.table = self.table + other.table

    def state(self) -> sk.SketchState:
        """Unpadded SketchState view (for merge with the reference path).

        Refused in conservative mode: SketchState is the cell-wise-merge /
        psum currency of the distributed runtime, and conservative tables
        must not enter it.  Use :meth:`table_view` for read-only access.
        """
        if self.mode != "linear":
            raise ValueError(
                "state() feeds the min-estimated SketchState cell-wise merge "
                "path; conservative tables must not enter it and signed "
                "tables carry sign params it cannot hold -- use cs_state() "
                "(signed) or table_view()/query()")
        return sk.SketchState(params=self.params,
                              table=self.table[:, : self.spec.table_size])

    def cs_state(self) -> "cskt.CountSketchState":
        """Unpadded CountSketchState view (signed mode's merge/reference
        currency, the analogue of :meth:`state`)."""
        if self.mode != "signed":
            raise ValueError("cs_state() is the signed-mode view; "
                             "linear sketches use state()")
        return cskt.CountSketchState(
            params=self.cs_params,
            table=self.table[:, : self.spec.table_size])

    def table_view(self) -> np.ndarray:
        """Read-only unpadded table copy (inspection/tests; any mode)."""
        return np.asarray(self.table[:, : self.spec.table_size])

    # -- durable state (serving/recovery.py snapshot currency) ---------------

    def state_dict(self) -> dict:
        """Full sketch state for ALL THREE modes as ``{key: ndarray}``.

        Unlike :meth:`state` (the linear merge currency) this is the
        *recovery* currency: the padded table plus every hash param the
        mode uses (bucket q/r always, sign q/r when signed), so a restored
        sketch is bit-identical regardless of linearity -- a conservative
        table round-trips too, it just must be rebuilt by ordered WAL
        replay rather than fold when the table itself is lost.
        """
        out = {
            "meta.fingerprint": np.frombuffer(
                (f"kernel|{self.spec!r}|mode={self.mode}"
                 f"|dtype={self.table.dtype}|h_pad={self.h_pad}"
                 ).encode(), dtype=np.uint8).copy(),
            "table": np.asarray(self.table),
            "params.q": np.asarray(self.params.q),
            "params.r": np.asarray(self.params.r),
        }
        if self.mode == "signed":
            out["params.sign_q"] = np.asarray(self.cs_params.sign_q)
            out["params.sign_r"] = np.asarray(self.cs_params.sign_r)
        return out

    def load_state_dict(self, sd: dict) -> None:
        """Restore state saved by :meth:`state_dict`; bit-exact round trip."""
        fp = np.frombuffer(
            (f"kernel|{self.spec!r}|mode={self.mode}"
             f"|dtype={self.table.dtype}|h_pad={self.h_pad}").encode(),
            dtype=np.uint8)
        got = np.asarray(sd["meta.fingerprint"], dtype=np.uint8)
        if not np.array_equal(fp, got):
            raise ValueError(
                "kernel state_dict fingerprint mismatch: saved "
                f"{bytes(got).decode(errors='replace')!r}, this sketch is "
                f"{bytes(fp).decode(errors='replace')!r}")
        self.table = jnp.asarray(sd["table"])
        params = sk.SketchParams(q=jnp.asarray(sd["params.q"]),
                                 r=jnp.asarray(sd["params.r"]))
        if self.mode == "signed":
            self.cs_params = cskt.CountSketchParams(
                base=params,
                sign_q=jnp.asarray(sd["params.sign_q"]),
                sign_r=jnp.asarray(sd["params.sign_r"]))
            self.params = self.cs_params.base
        else:
            self.params = params


class KernelHierarchy:
    """Hierarchy whose level tables live concatenated + padded for the fused
    single-launch Pallas update (kernels/hier_update.py).

    The ingest counterpart of the one-launch query kernel: every stream
    block is folded into ALL levels by one pallas_call against the
    ``[w, sum_L h_L_pad]`` concatenated table, hashing each item once per
    row and deriving the level cells by the mixed-radix cascade.  Linear
    mode only -- the conservative update's row-coupling min forces a
    sequential per-level fold; conservative hierarchies take
    core.hierarchy.update_conservative (which shares the same index
    cascade) instead.

    :meth:`state` materializes the standard ``HierarchyState`` view (per
    level: unpadded table slice + prefix-sliced shared params), cached
    until the next ingest, so the descent/query stack runs unchanged on
    kernel-ingested hierarchies.
    """

    def __init__(self, hspec, key: jax.Array, *, tile_h: int = 512,
                 block_b: int = 1024, dtype=jnp.int32,
                 interpret: Optional[bool] = None, mode: str = "linear"):
        if mode not in ("linear", "signed"):
            raise ValueError(
                "KernelHierarchy modes are 'linear' and 'signed' "
                "(conservative hierarchies take "
                f"core.hierarchy.update_conservative), got {mode!r}")
        from repro.core import hierarchy as hh

        self._hh = hh
        self.hspec = hspec
        self.hplan = make_hier_plan(hspec, tile_h)
        self.mode = mode
        if mode == "signed":
            # same-key bit parity with the core.countsketch hierarchy
            self.cs_params = cskt.init_params(hspec.levels[-1], key)
            self.params = self.cs_params.base
        else:
            self.cs_params = None
            self.params = sk.init_params(hspec.levels[-1], key)  # shared family
        self.block_b = int(block_b)
        self.table = jnp.zeros((hspec.base.width, self.hplan.padded_cols),
                               dtype=dtype)
        self.interpret = resolve_interpret(interpret)
        self._state_cache: Optional[object] = None

    @classmethod
    def from_state(cls, hspec, state, *, tile_h: int = 512,
                   block_b: int = 1024,
                   interpret: Optional[bool] = None) -> "KernelHierarchy":
        """Adopt an existing (shared-params) HierarchyState's tables+params."""
        self = cls.__new__(cls)
        from repro.core import hierarchy as hh

        self._hh = hh
        self.hspec = hspec
        self.hplan = make_hier_plan(hspec, tile_h)
        self.mode = "linear"   # HierarchyState carries no sign params
        self.cs_params = None
        self.params = state.states[-1].params
        self.block_b = int(block_b)
        self.interpret = resolve_interpret(interpret)
        self._state_cache = None
        self.load_state(state)
        return self

    # -- state interop -------------------------------------------------------
    def load_state(self, state) -> None:
        """Pack a HierarchyState into the concatenated padded table.

        The state must carry the shared-prefix params of
        ``init_hierarchy`` (validated host-side): the fused kernel hashes
        with the finest params only and derives every level by division,
        which is meaningless for independently drawn per-level params.
        """
        if self.mode != "linear":
            raise ValueError(
                "load_state() takes a (sign-less) HierarchyState and is "
                "linear-mode only; signed hierarchies are built by ingest "
                "from their own key")
        if not self._hh.params_share_prefix(state):
            raise ValueError(
                "KernelHierarchy requires the shared per-group hash family "
                "(level params must be prefix slices of the finest "
                "level's, as drawn by init_hierarchy)")
        self.params = state.states[-1].params
        parts = []
        for st_l, h_l, pad_l in zip(state.states, self.hplan.level_sizes,
                                    self.hplan.level_pads):
            if st_l.table.shape[1] != h_l:
                raise ValueError("state tables do not match the spec")
            parts.append(jnp.pad(st_l.table, ((0, 0), (0, pad_l - h_l))))
        self.table = jnp.concatenate(parts, axis=1)
        self._state_cache = None

    def state(self):
        """HierarchyState view (sliced, unpadded); cached until next ingest.

        Linear mode only: HierarchyState is the min-estimated descent/merge
        currency and carries no sign params -- the signed view is
        :meth:`cs_state`."""
        if self.mode != "linear":
            raise ValueError(
                "state() is the linear (Count-Min) hierarchy view; signed "
                "hierarchies use cs_state()")
        if self._state_cache is None:
            states = []
            for l, (off, h_l) in enumerate(zip(self.hplan.level_offsets,
                                               self.hplan.level_sizes)):
                states.append(sk.SketchState(
                    params=self._hh.level_params(self.hspec, self.params, l),
                    table=self.table[:, off : off + h_l]))
            self._state_cache = self._hh.HierarchyState(states=tuple(states))
        return self._state_cache

    def cs_state(self) -> "cskt.CountSketchHierarchy":
        """CountSketchHierarchy view (sliced, unpadded); cached until next
        ingest -- feeds the signed candidate queries and threshold descent
        (core.countsketch.candidate_estimates / find_heavy_hitters)."""
        if self.mode != "signed":
            raise ValueError("cs_state() is the signed hierarchy view; "
                             "linear hierarchies use state()")
        if self._state_cache is None:
            tables = tuple(
                self.table[:, off : off + h_l]
                for off, h_l in zip(self.hplan.level_offsets,
                                    self.hplan.level_sizes))
            self._state_cache = cskt.CountSketchHierarchy(
                params=self.cs_params, tables=tables)
        return self._state_cache

    # -- ingest --------------------------------------------------------------
    def update(self, items, freqs) -> None:
        """Fold a weighted block: one fused launch per fixed-size sub-block."""
        items = np.asarray(items, dtype=np.uint32)
        freqs = np.asarray(freqs)
        if self.mode == "signed":
            check_signed_kernel_freqs(freqs, self.table.dtype)
        else:
            check_linear_kernel_freqs(freqs, self.table.dtype)
        schema = self.hspec.levels[-1].schema
        n_fine = self.hspec.n_levels - 1
        b = self.block_b
        for s in range(0, items.shape[0], b):
            blk_i = items[s : s + b]
            blk_f = freqs[s : s + b]
            if blk_i.shape[0] < b:
                pad = b - blk_i.shape[0]
                blk_i = np.pad(blk_i, ((0, pad), (0, 0)))
                blk_f = np.pad(blk_f, (0, pad))
            # group-major column order = the finest level's chunk layout
            ordered = np.asarray(self.hspec.level_items(n_fine, blk_i))
            chunks = schema.module_chunks(jnp.asarray(ordered))
            if self.mode == "signed":
                self.table = hier_update_signed_pallas(
                    self.hplan, self.table, chunks, jnp.asarray(blk_f),
                    self.params.q, self.params.r,
                    self.cs_params.sign_q, self.cs_params.sign_r,
                    interpret=self.interpret,
                )
            else:
                self.table = hier_update_pallas(
                    self.hplan, self.table, chunks, jnp.asarray(blk_f),
                    self.params.q, self.params.r, interpret=self.interpret,
                )
        self._state_cache = None
