"""Pure-jnp oracles for the Pallas kernels (same signatures, no tiling)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.hashes import IndexPlan, all_indices


@functools.partial(jax.jit, static_argnames=("plan",))
def sketch_update_ref(
    plan: IndexPlan,
    table: jax.Array,
    chunks: jax.Array,
    freqs: jax.Array,
    q: jax.Array,
    r: jax.Array,
) -> jax.Array:
    """Scatter-add oracle over the (padded) table."""
    w, h_pad = table.shape
    idx = all_indices(plan, chunks, q, r)                        # [w, B]
    flat = (jnp.arange(w, dtype=jnp.int32)[:, None] * h_pad + idx).reshape(-1)
    f = jnp.broadcast_to(freqs.astype(table.dtype), (w, freqs.shape[0])).reshape(-1)
    return table.reshape(-1).at[flat].add(f).reshape(w, h_pad)


@functools.partial(jax.jit, static_argnames=("plan",))
def sketch_query_ref(
    plan: IndexPlan,
    table: jax.Array,
    chunks: jax.Array,
    q: jax.Array,
    r: jax.Array,
) -> jax.Array:
    """Gather + min oracle: int32[Q]."""
    idx = all_indices(plan, chunks, q, r)                        # [w, Q]
    vals = jnp.take_along_axis(table.astype(jnp.int32), idx, axis=1)
    return jnp.min(vals, axis=0)
