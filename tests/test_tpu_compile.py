"""Compile the Pallas kernels for a TPU v5e at deployment widths, no chip.

Interpret mode (every other kernel test) checks the kernels' logic but not
whether Mosaic can lower them: tiling alignment, memory spaces and VMEM
limits are only checked by the TPU compiler.  These tests describe a v5e
topology (the TPU compiler ships with jaxlib) and run ``.lower().compile()``
on each kernel with ``interpret=False`` at the widths of ``chip_smoke.py``:
w = 4 rows, a 2^20-cell finest level, two IPv4 modules (4 chunk columns),
1024-row ingest blocks and the descent's 2^16 lanes per launch.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and the fixture lets
every test worker collect the same tests while only the worker running
this file loads it.  Keep these tests in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import hierarchy as hh
from repro.core import sketch as sk
from repro.core.hashing import KeySchema
from repro.kernels.hashes import make_plan
from repro.kernels.hier_query import (
    hier_candidate_query,
    hier_candidate_query_batched,
    hier_candidate_query_signed,
)
from repro.kernels.hier_update import (
    hier_update_pallas,
    hier_update_signed_pallas,
    make_hier_plan,
)
from repro.kernels.sketch_query import sketch_query_pallas
from repro.kernels.sketch_update import (
    padded_table_size,
    sketch_update_pallas,
    sketch_update_signed_pallas,
)
from repro.kernels.sketch_update_conservative import (
    sketch_update_conservative_pallas,
)

W = 4
BLOCK = 1024
LANES = 1 << 16          # core.hierarchy.find_heavy_hitters' max_batch
SCHEMA = KeySchema(domains=(1 << 32, 1 << 32))     # (src IPv4, dst IPv4)
BASE = sk.mod_sketch_spec(SCHEMA, [(0,), (1,)], (1 << 10, 1 << 10), W)
HSPEC = hh.HierarchySpec.from_spec(BASE)
C = SCHEMA.total_chunks


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the
    # persistent cache without one; keep them out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text   # the kernel, not an XLA fallback


@pytest.mark.parametrize("dtype,signed", [(jnp.int32, False),
                                          (jnp.float32, False),
                                          (jnp.int32, True)])
def test_hier_update_compiles(one_chip, dtype, signed):
    hplan = make_hier_plan(HSPEC)
    shapes = [((W, hplan.padded_cols), dtype), ((BLOCK, C), jnp.uint32),
              ((BLOCK,), dtype), ((W, C), jnp.uint32), ((W, 2), jnp.uint32)]
    if signed:
        shapes += [((W, C), jnp.uint32), ((W, 2), jnp.uint32)]

        def fn(t, ch, f, q, r, sq, sr):
            return hier_update_signed_pallas(hplan, t, ch, f, q, r, sq, sr,
                                             interpret=False)
    else:
        def fn(t, ch, f, q, r):
            return hier_update_pallas(hplan, t, ch, f, q, r, interpret=False)
    _compile(fn, *shapes, sharding=one_chip)


@pytest.mark.parametrize("form", ["single", "batched", "signed"])
def test_hier_candidate_query_compiles(one_chip, form):
    h = BASE.table_size
    if form == "single":
        def fn(t, pp, cp):
            return hier_candidate_query(t, pp, cp, interpret=False)
        shapes = [((W, h), jnp.int32), ((W, 16), jnp.uint32),
                  ((W, LANES // 16), jnp.uint32)]
    elif form == "batched":
        def fn(t, pp, cp):
            return hier_candidate_query_batched(t, pp, cp, interpret=False)
        shapes = [((W, h), jnp.int32), ((W, 4, 4), jnp.uint32),
                  ((W, LANES // 16), jnp.uint32)]
    else:
        def fn(t, pp, cp, sp, sc):
            return hier_candidate_query_signed(t, pp, cp, sp, sc,
                                               interpret=False)
        shapes = [((W, h), jnp.int32), ((W, 16), jnp.uint32),
                  ((W, LANES // 16), jnp.uint32), ((W, 16), jnp.float32),
                  ((W, LANES // 16), jnp.float32)]
    _compile(fn, *shapes, sharding=one_chip)


def test_sketch_query_compiles(one_chip):
    plan = make_plan(BASE)
    h_pad = padded_table_size(BASE.table_size, 512)

    def fn(t, ch, q, r):
        return sketch_query_pallas(plan, t, ch, q, r, interpret=False)
    _compile(fn, ((W, h_pad), jnp.int32), ((4096, C), jnp.uint32),
             ((W, C), jnp.uint32), ((W, 2), jnp.uint32), sharding=one_chip)


@pytest.mark.parametrize("signed", [False, True])
def test_sketch_update_compiles(one_chip, signed):
    plan = make_plan(BASE)
    h_pad = padded_table_size(BASE.table_size, 512)
    shapes = [((W, h_pad), jnp.int32), ((BLOCK, C), jnp.uint32),
              ((BLOCK,), jnp.int32), ((W, C), jnp.uint32),
              ((W, 2), jnp.uint32)]
    if signed:
        shapes += [((W, C), jnp.uint32), ((W, 2), jnp.uint32)]

        def fn(t, ch, f, q, r, sq, sr):
            return sketch_update_signed_pallas(plan, t, ch, f, q, r, sq, sr,
                                               interpret=False)
    else:
        def fn(t, ch, f, q, r):
            return sketch_update_pallas(plan, t, ch, f, q, r,
                                        interpret=False)
    _compile(fn, *shapes, sharding=one_chip)


def test_conservative_kernel_refuses_compiled_launch(one_chip):
    """The conservative kernel does not lower for TPU; a compiled launch is
    refused with an error that names the jnp path."""
    plan = make_plan(BASE)

    def fn(t, ch, f, q, r):
        return sketch_update_conservative_pallas(plan, t, ch, f, q, r,
                                                 interpret=False)
    with pytest.raises(ValueError, match="update_conservative_jit"):
        _compile(fn, ((W, 1024), jnp.int32), ((BLOCK, C), jnp.uint32),
                 ((BLOCK,), jnp.int32), ((W, C), jnp.uint32),
                 ((W, 2), jnp.uint32), sharding=one_chip)
