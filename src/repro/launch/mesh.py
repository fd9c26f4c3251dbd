"""Mesh construction: the one place a ``jax.sharding.Mesh`` is built.

FUNCTIONS, not module-level constants: importing this module never touches
jax device state (device count is locked at first backend init, and only
dryrun.py sets the 512-device host-platform flag).

Every mesh states its axis types.  The sketch and model code shards with
``shard_map`` specs, ``NamedSharding`` placements and
``with_sharding_constraint`` hints, which is the Auto-axis contract; since
jax 0.7 ``jax.make_mesh`` defaults to Explicit axes, under which indexing a
sharded array or constraining it needs an explicit ``out_sharding``.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """A mesh of ``shape`` over ``axes`` (Auto axis types) on the first
    ``prod(shape)`` devices, or on ``devices`` when given."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (data, model) single pod; 2x16x16 (pod, data, model) for two."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CI-scale tests (requires >= prod(shape) devices)."""
    return make_mesh(shape, axes)


def sketch_data_axes(mesh) -> tuple:
    """Data-parallel axes for sketch serving on any of the meshes above.

    Sketch ingest shards the *stream*, never the table rows, so every axis
    except "model" is a data axis: ("data",) on the single pod / test mesh,
    ("pod", "data") on the two-pod mesh.  Used by the sharded serving
    dry-run cells and ShardedTopKService's default."""
    return tuple(a for a in mesh.axis_names if a != "model")
