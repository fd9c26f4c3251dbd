"""Activation-sharding context: `constrain(x, ...)` hints inside model code.

Model code is mesh-agnostic; the launcher activates a mesh context and the
layers drop `with_sharding_constraint` pins at the few places where XLA's
propagation would otherwise lose the batch sharding (embedding lookup with a
non-divisible vocab, logits contraction, MoE dispatch buffers).  Tokens:

    DP   -- the data-parallel axes ("data" or ("pod","data"))
    MP   -- the model axis
    None -- unsharded dim

Constraints are divisibility-sanitized against the actual dim (an axis that
does not divide the dim is dropped), so the same model code lowers on any
mesh -- and is a no-op outside a context (single-device tests).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

DP = "__dp__"
MP = "__mp__"

_state = threading.local()


@contextlib.contextmanager
def activation_sharding(mesh: Mesh):
    """Enable constraint emission during tracing/lowering."""
    names = mesh.axis_names
    dp = tuple(names[:-1])
    prev = getattr(_state, "ctx", None)
    _state.ctx = (mesh, dp, names[-1])
    try:
        yield
    finally:
        _state.ctx = prev


def current_mesh() -> Optional[Mesh]:
    ctx = getattr(_state, "ctx", None)
    return ctx[0] if ctx else None


def constrain(x: jax.Array, *tokens) -> jax.Array:
    ctx = getattr(_state, "ctx", None)
    if ctx is None:
        return x
    mesh, dp, mp = ctx
    parts = []
    for dim, tok in zip(x.shape, tokens):
        if tok == DP:
            axes = list(dp)
        elif tok == MP:
            axes = [mp]
        elif tok is None:
            parts.append(None)
            continue
        else:
            axes = [tok] if isinstance(tok, str) else list(tok)
        while axes:
            prod = int(np.prod([mesh.shape[a] for a in axes]))
            if dim % prod == 0:
                break
            axes.pop()
        parts.append(tuple(axes) if len(axes) > 1 else (axes[0] if axes else None))
    parts += [None] * (x.ndim - len(parts))
    sharding = NamedSharding(mesh, P(*parts))
    if AxisType.Explicit in mesh.axis_types:
        # Explicit axes carry the sharding in the type: move the data
        return jax.sharding.reshard(x, sharding)
    return jax.lax.with_sharding_constraint(x, sharding)
