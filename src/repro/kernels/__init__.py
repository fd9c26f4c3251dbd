"""Pallas TPU kernels for the sketch hot path (linear and signed update =
fused one-hot MXU fold over every hierarchy level, query = per-row cell
gather + row reduce, conservative update = interpret-only sequential
min/max), with jnp oracles in ref.py and jitted wrappers in ops.py.
Compiled on a TPU, interpreted elsewhere (ops.default_interpret)."""
from repro.kernels.hashes import IndexPlan, make_plan  # noqa: F401
from repro.kernels.hier_query import (  # noqa: F401
    hier_candidate_query,
    hier_candidate_query_ref,
    hier_candidate_query_signed,
    hier_candidate_query_signed_ref,
)
from repro.kernels.hier_update import (  # noqa: F401
    HierPlan,
    hier_update_pallas,
    hier_update_ref,
    hier_update_signed_pallas,
    hier_update_signed_ref,
    make_hier_plan,
)
from repro.kernels.ops import (  # noqa: F401
    KernelHierarchy,
    KernelSketch,
    default_interpret,
)
from repro.kernels.sketch_update import (  # noqa: F401
    sketch_update_pallas,
    sketch_update_signed_pallas,
)
from repro.kernels.sketch_query import (  # noqa: F401
    sketch_query_pallas,
    sketch_query_signed_pallas,
)
from repro.kernels.sketch_update_conservative import (  # noqa: F401
    sketch_update_conservative_pallas,
)
