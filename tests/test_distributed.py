"""Distributed sketch + small-mesh dry-run (subprocess: own device count).

These spawn a fresh interpreter with XLA_FLAGS host-device overrides so the
main test process keeps its single-device view (per the dry-run contract).
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    return out.stdout


def test_sharded_sketch_build_equals_serial():
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import sketch as sk, distributed as dist
        from repro.core.hashing import KeySchema

        mesh = make_mesh((4, 2), ("data", "model"))
        schema = KeySchema(domains=(1 << 20, 1 << 20))
        spec = sk.mod_sketch_spec(schema, [(0,), (1,)], (32, 64), 4)
        params = sk.init_params(spec, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        items = rng.integers(0, 1 << 20, size=(4096, 2), dtype=np.int64).astype(np.uint32)
        freqs = rng.integers(1, 9, size=(4096,)).astype(np.int32)

        merged = dist.sharded_build(spec, params, mesh, ("data",),
                                    jnp.asarray(items), jnp.asarray(freqs))
        serial = sk.update_jit(spec, sk.SketchState(params=params,
            table=jnp.zeros((4, spec.table_size), jnp.int32)),
            jnp.asarray(items), jnp.asarray(freqs))
        assert (np.asarray(merged) == np.asarray(serial.table)).all(), "merge mismatch"

        # row-sharded query: w=4 rows over model axis of size 2
        tbl = jax.device_put(serial.table, NamedSharding(mesh, P("model")))
        est = dist.row_sharded_query(spec, mesh, "model", params, tbl,
                                     jnp.asarray(items[:64]))
        want = sk.query_jit(spec, serial, jnp.asarray(items[:64]))
        assert (np.asarray(est) == np.asarray(want)).all(), "query mismatch"
        print("distributed sketch OK")
    """))


def test_lazy_local_tables_merge():
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.core import sketch as sk, distributed as dist
        from repro.core.hashing import KeySchema

        mesh = make_mesh((8,), ("data",))
        schema = KeySchema(domains=(4096, 4096))
        spec = sk.mod_sketch_spec(schema, [(0,), (1,)], (16, 16), 3)
        params = sk.init_params(spec, jax.random.PRNGKey(1))
        rng = np.random.default_rng(1)
        local = jnp.zeros((8, 3, spec.table_size), jnp.int32)
        all_items, all_freqs = [], []
        for step in range(3):
            items = rng.integers(0, 4096, size=(1024, 2)).astype(np.uint32)
            freqs = np.ones(1024, np.int32)
            local = dist.lazy_local_update(spec, mesh, ("data",), local,
                params, jnp.asarray(items), jnp.asarray(freqs))
            all_items.append(items); all_freqs.append(freqs)
        merged = dist.merge_local_tables(mesh, ("data",), local)
        serial = sk.update_jit(spec, sk.SketchState(params=params,
            table=jnp.zeros((3, spec.table_size), jnp.int32)),
            jnp.asarray(np.concatenate(all_items)),
            jnp.asarray(np.concatenate(all_freqs)))
        assert (np.asarray(merged) == np.asarray(serial.table)).all()
        print("lazy merge OK")
    """))


def test_small_mesh_dryrun_train_and_decode():
    """The dry-run machinery on a small (2,2,2) pod mesh with reduced archs:
    lowering + compile + loop-aware roofline must succeed end to end."""
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        import dataclasses
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import hlo_analysis as ha
        from repro.configs import get_reduced
        from repro.launch import specs as sp
        from repro.models import sharding as shd, shard_ctx, transformer as tfm
        from repro.training import train_loop as tl

        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        for arch in ("gemma2-9b", "mixtral-8x22b", "mamba2-130m"):
            cfg = get_reduced(arch)
            tcfg = tl.TrainConfig()
            state_sds = sp.train_state_specs(cfg, tcfg)
            batch_sds = sp.batch_input_specs(cfg, 8, 64)
            pspecs = shd.param_specs(cfg, state_sds["params"], mesh)
            state_specs = {
                "params": pspecs,
                "opt": shd.opt_state_specs(cfg, state_sds["opt"], pspecs, mesh),
                "sketch_params": jax.tree.map(lambda _: P(), state_sds["sketch_params"]),
                "sketch_table": P(),
            }
            bspecs = shd.sanitize_specs(shd.batch_specs(cfg, mesh, False),
                                        batch_sds, mesh)
            fn = jax.jit(tl.make_train_step(cfg, tcfg),
                         in_shardings=(shd.to_shardings(mesh, state_specs),
                                       shd.to_shardings(mesh, bspecs)),
                         out_shardings=(shd.to_shardings(mesh, state_specs), None),
                         donate_argnums=(0,))
            with shard_ctx.activation_sharding(mesh):
                compiled = fn.lower(state_sds, batch_sds).compile()
            cost = ha.analyze(compiled.as_text())
            assert cost.flops > 0, arch
            print(arch, "train ok: flops %.2e wire %.2e" % (cost.flops, cost.coll_wire_bytes))

            # decode step
            params_sds = jax.eval_shape(lambda k: tfm.init_params(cfg, k),
                                        jax.random.PRNGKey(0))
            din = sp.decode_input_specs(cfg, 8, 128)
            cspecs = shd.cache_specs(cfg, din["cache"], mesh, 8)
            fn2 = jax.jit(lambda p, c, t, pos: tfm.decode_step(cfg, p, c, t, pos),
                          in_shardings=(shd.to_shardings(mesh, shd.param_specs(cfg, params_sds, mesh)),
                                        shd.to_shardings(mesh, cspecs),
                                        NamedSharding(mesh, P(("pod","data"), None)),
                                        NamedSharding(mesh, P())),
                          out_shardings=(None, shd.to_shardings(mesh, cspecs)),
                          donate_argnums=(1,))
            with shard_ctx.activation_sharding(mesh):
                c2 = fn2.lower(params_sds, din["cache"], din["tokens_last"], din["pos"]).compile()
            print(arch, "decode ok")
        print("small-mesh dryrun OK")
    """, devices=8))


def test_moe_local_dispatch_matches_global_when_dropless():
    print(_run("""
        import dataclasses
        from repro.launch.mesh import make_mesh
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_reduced
        from repro.models import moe as moe_mod, shard_ctx
        cfg = get_reduced("mixtral-8x22b")
        p = moe_mod.make_moe_params(cfg, jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model),
                              jnp.float32).astype(cfg.activation_dtype)
        y_global, _ = moe_mod.apply_moe(cfg, p, x)
        mesh = make_mesh((4, 2), ("data", "model"))
        cfg_l = dataclasses.replace(cfg, moe_dispatch="local")
        with shard_ctx.activation_sharding(mesh):
            y_local, aux = jax.jit(
                lambda p, x: moe_mod.apply_moe(cfg_l, p, x))(p, x)
        np.testing.assert_allclose(np.asarray(y_global, np.float32),
                                   np.asarray(y_local, np.float32),
                                   rtol=5e-2, atol=5e-2)
        assert float(aux["dropped_frac"]) == 0.0
        print("moe local dispatch numerics OK")
    """))


def test_moe_ep_shardmap_matches_global_when_dropless():
    print(_run("""
        import dataclasses
        from repro.launch.mesh import make_mesh
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_reduced
        from repro.models import moe as moe_mod, shard_ctx
        cfg = get_reduced("mixtral-8x22b")
        p = moe_mod.make_moe_params(cfg, jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model),
                              jnp.float32).astype(cfg.activation_dtype)
        y_global, _ = moe_mod.apply_moe(cfg, p, x)
        mesh = make_mesh((4, 2), ("data", "model"))
        cfg_ep = dataclasses.replace(cfg, moe_dispatch="ep_shardmap")
        with shard_ctx.activation_sharding(mesh):
            y_ep, aux = jax.jit(
                lambda p, x: moe_mod.apply_moe(cfg_ep, p, x))(p, x)
        np.testing.assert_allclose(np.asarray(y_global, np.float32),
                                   np.asarray(y_ep, np.float32),
                                   rtol=5e-2, atol=5e-2)
        assert float(aux["dropped_frac"]) == 0.0
        print("moe ep_shardmap numerics OK")
    """))


def test_elastic_remesh():
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.training.fault_tolerance import elastic_remesh

        big = make_mesh((8,), ("data",))
        small = make_mesh((4,), ("data",))
        x = jax.device_put(jnp.arange(64.0).reshape(8, 8),
                           NamedSharding(big, P("data", None)))
        y = elastic_remesh({"x": x}, small, lambda leaf: P("data", None))
        assert np.asarray(y["x"]).shape == (8, 8)
        assert len(y["x"].sharding.mesh.devices.flatten()) == 4
        print("elastic remesh OK")
    """))
