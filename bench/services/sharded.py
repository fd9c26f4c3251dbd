"""Data-parallel backend: ``ShardedTopKService`` over a one-axis mesh of the
first ``service["shards"]`` chips."""


def build(spec, key, service: dict, devices):
    import jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro.serving.sharded_topk import ShardedTopKService

    shards = int(service["shards"])
    mesh = make_mesh((shards,), ("data",), devices=devices[:shards])
    return ShardedTopKService(
        spec, key, mesh, max_candidates_per_group=int(service["pool_capacity"]),
        sync_every=int(service["sync_every"]),
        use_kernel=bool(service["use_kernel"]),
        use_update_kernel=bool(service["use_update_kernel"]),
        dtype=jnp.int32, mode=service["mode"])
