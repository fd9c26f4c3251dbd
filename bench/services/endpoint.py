"""One-chip backend: ``SketchTopKEndpoint``."""


def build(spec, key, service: dict, devices):
    import jax.numpy as jnp
    from repro.serving.sketch_engine import SketchTopKEndpoint

    return SketchTopKEndpoint(
        spec, key, max_candidates_per_group=int(service["pool_capacity"]),
        use_kernel=bool(service["use_kernel"]),
        use_update_kernel=bool(service["use_update_kernel"]),
        dtype=jnp.int32, mode=service["mode"])
