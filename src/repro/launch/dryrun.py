import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: the 512
host-platform placeholder devices let ``jax.make_mesh`` build the production
meshes -- (16,16) ("data","model") single pod and (2,16,16)
("pod","data","model") for two pods -- and XLA:CPU compiles the fully
partitioned SPMD module, surfacing sharding mismatches, compile-time OOMs,
and unsupported collectives exactly as a TPU lowering would.

Per cell we record ``memory_analysis()`` (fits-per-chip proof),
``cost_analysis()`` (FLOPs / bytes for SRoofline), and the collective mix
parsed from the optimized HLO.  Results go to JSON (one file per cell,
resumable); EXPERIMENTS.md SDry-run/SRoofline read from them.

Usage:
    python -m repro.launch.dryrun --arch gemma2-9b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod-only|--single-pod-only]
    python -m repro.launch.dryrun --all --variant <name>   # SPerf knobs
"""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import hlo_analysis as ha
from repro import roofline as rl
from repro.configs import ARCHS, SHAPES, get_config, shape_applicable
from repro.launch import specs as sp
from repro.launch.mesh import make_production_mesh, make_test_mesh, \
    sketch_data_axes
from repro.models import shard_ctx
from repro.models import sharding as shd
from repro.models import transformer as tfm
from repro.training import train_loop as tl

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results", "dryrun")


# --------------------------------------------------------------------------
# SPerf variants: config/sharding transformations exercised by hillclimbing.
# Each entry may transform the ModelConfig and/or flags read below.
# --------------------------------------------------------------------------
VARIANTS: Dict[str, Dict[str, Any]] = {
    "baseline": {},
    # hillclimb knobs (see EXPERIMENTS.md SPerf for the iteration log)
    "noremat": {"cfg": {"remat": False}},
    "attn_chunk_512": {"cfg": {"attn_chunk": 512}},
    "attn_chunk_2048": {"cfg": {"attn_chunk": 2048}},
    "ssm_chunk_256": {"cfg": {"ssm_chunk": 256}},
    "ssm_chunk_512": {"cfg": {"ssm_chunk": 512}},
    "no_sketch": {"sketch": False},
    "cap_factor_1": {"cfg": {"capacity_factor": 1.0}},
    "loss_chunk512": {"cfg": {"loss_chunk": 512}},
    "moe_local": {"cfg": {"moe_dispatch": "local"}},
    "moe_local_lc": {"cfg": {"moe_dispatch": "local", "loss_chunk": 512}},
    "mamba_opt": {"cfg": {"loss_chunk": 512, "ssm_chunk": 256}},
    "mamba_opt2": {"cfg": {"loss_chunk": 512, "ssm_chunk": 512}},
    "moe_local_v2": {"cfg": {"moe_dispatch": "local"}},
    "moe_local_v2_lc": {"cfg": {"moe_dispatch": "local", "loss_chunk": 512}},
    "moe_local_cap1": {"cfg": {"moe_dispatch": "local", "capacity_factor": 1.0}},
    "moe_local_fshard": {"cfg": {"moe_dispatch": "local",
                                 "moe_weight_shard": "f_allaxes"}},
    "moe_best": {"cfg": {"moe_dispatch": "local", "capacity_factor": 1.0,
                         "moe_weight_shard": "f_allaxes"}},
    "moe_ep": {"cfg": {"moe_dispatch": "ep_shardmap"}},
    "moe_2d_global": {"cfg": {"moe_dispatch": "global"}},  # original baseline
    "moe_ep_cap1": {"cfg": {"moe_dispatch": "ep_shardmap",
                            "capacity_factor": 1.0}},
    "vocab_pad": {"cfg": {"vocab_pad_multiple": 256}},
    "mamba_best": {"cfg": {"vocab_pad_multiple": 256, "loss_chunk": 512}},
}


def _apply_variant(cfg, variant: str):
    v = VARIANTS[variant]
    if "cfg" in v:
        cfg = dataclasses.replace(cfg, **{k: val for k, val in v["cfg"].items()
                                          if hasattr(cfg, k)})
    return cfg, v


def _replicated_like(tree):
    return jax.tree.map(lambda _: P(), tree)


def _compiled_stats(compiled):
    """cost_analysis / memory_analysis / optimized HLO of a compiled cell
    (shared by the model cells and the sketch-serving cells)."""
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    mem = compiled.memory_analysis()
    mem_d: Dict[str, float] = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes",
                 "alias_size_in_bytes"):
        if mem is not None and hasattr(mem, attr):
            mem_d[attr] = float(getattr(mem, attr))
    return cost, mem_d, compiled.as_text()


def lower_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    variant: str = "baseline",
) -> Dict[str, Any]:
    cfg = get_config(arch)
    cfg, vflags = _apply_variant(cfg, variant)
    if not vflags.get("sketch", True):
        pass  # handled through TrainConfig below
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    chips = mesh.size
    sh = SHAPES[shape_name]
    b, s = sh["global_batch"], sh["seq_len"]
    kind = sh["kind"]

    t0 = time.perf_counter()
    if kind == "train":
        tcfg = sp.default_train_config(cfg)
        if not vflags.get("sketch", True):
            tcfg = dataclasses.replace(tcfg, sketch_enabled=False)
        state_sds = sp.train_state_specs(cfg, tcfg)
        batch_sds = sp.batch_input_specs(cfg, b, s)

        pspecs = shd.param_specs(cfg, state_sds["params"], mesh)
        state_specs: Dict[str, Any] = {
            "params": pspecs,
            "opt": shd.opt_state_specs(cfg, state_sds["opt"], pspecs, mesh),
        }
        if tcfg.sketch_enabled:
            state_specs["sketch_params"] = _replicated_like(
                state_sds["sketch_params"])
            state_specs["sketch_table"] = P()
        bspecs = shd.sanitize_specs(
            shd.batch_specs(cfg, mesh, "embeds" in batch_sds), batch_sds, mesh)

        state_sh = shd.to_shardings(mesh, state_specs)
        batch_sh = shd.to_shardings(mesh, bspecs)
        step = tl.make_train_step(cfg, tcfg)
        fn = jax.jit(step, in_shardings=(state_sh, batch_sh),
                     out_shardings=(state_sh, None), donate_argnums=(0,))
        with shard_ctx.activation_sharding(mesh):
            lowered = fn.lower(state_sds, batch_sds)
    elif kind == "prefill":
        params_sds = jax.eval_shape(lambda k: tfm.init_params(cfg, k),
                                    jax.random.PRNGKey(0))
        batch_sds = sp.batch_input_specs(cfg, b, s)
        pspecs = shd.param_specs(cfg, params_sds, mesh)
        cache_sds = jax.eval_shape(
            lambda p, t, e: tfm.prefill(cfg, p, t, embeds=e, max_len=None)[1],
            params_sds, batch_sds["tokens"], batch_sds.get("embeds"))
        cspecs = shd.cache_specs(cfg, cache_sds, mesh, b)
        bspecs = shd.sanitize_specs(
            shd.batch_specs(cfg, mesh, "embeds" in batch_sds), batch_sds, mesh)
        fn = jax.jit(
            lambda p, batch: tfm.prefill(cfg, p, batch["tokens"],
                                         embeds=batch.get("embeds"),
                                         max_len=None),
            in_shardings=(shd.to_shardings(mesh, pspecs),
                          shd.to_shardings(mesh, bspecs)),
            out_shardings=(None, shd.to_shardings(mesh, cspecs)),
        )
        with shard_ctx.activation_sharding(mesh):
            lowered = fn.lower(params_sds, batch_sds)
    else:  # decode
        params_sds = jax.eval_shape(lambda k: tfm.init_params(cfg, k),
                                    jax.random.PRNGKey(0))
        din = sp.decode_input_specs(cfg, b, s)
        pspecs = shd.param_specs(cfg, params_sds, mesh)
        cspecs = shd.cache_specs(cfg, din["cache"], mesh, b)
        dp_axes, _ = shd.mesh_axes(mesh)
        dp = dp_axes if len(dp_axes) > 1 else dp_axes[0]
        tok_spec = P(dp, None) if b >= mesh.shape[dp_axes[0]] else P(None, None)
        fn = jax.jit(
            lambda p, c, t, pos: tfm.decode_step(cfg, p, c, t, pos),
            in_shardings=(shd.to_shardings(mesh, pspecs),
                          shd.to_shardings(mesh, cspecs),
                          NamedSharding(mesh, tok_spec),
                          NamedSharding(mesh, P())),
            out_shardings=(None, shd.to_shardings(mesh, cspecs)),
            donate_argnums=(1,),
        )
        with shard_ctx.activation_sharding(mesh):
            lowered = fn.lower(params_sds, din["cache"], din["tokens_last"],
                               din["pos"])
    t_lower = time.perf_counter() - t0

    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0

    cost, mem_d, hlo = _compiled_stats(compiled)
    model_flops = rl.model_flops_for(cfg, kind, b, s)
    hcost = ha.analyze(hlo)  # loop-aware: scan bodies x trip counts
    top_bytes = dict(sorted(hcost.bytes_by_op.items(),
                            key=lambda kv: -kv[1])[:10])
    roof = rl.Roofline(
        arch=arch, shape=shape_name, mesh=mesh_name, chips=chips,
        flops_per_chip=hcost.flops,
        hbm_bytes_per_chip=hcost.bytes,
        wire_bytes_per_chip=hcost.coll_wire_bytes,
        model_flops=model_flops,
        collectives={"counts": hcost.coll_counts,
                     "result_bytes": hcost.coll_bytes,
                     "wire_bytes": hcost.coll_wire_bytes},
    )

    out = {
        **roof.as_dict(),
        "variant": variant,
        "kind": kind,
        "global_batch": b,
        "seq_len": s,
        "lower_s": t_lower,
        "compile_s": t_compile,
        "memory_analysis": mem_d,
        "cost_flops": float(cost.get("flops", 0.0)),
        "cost_bytes": float(cost.get("bytes accessed", 0.0)),
        "n_params_total": cfg.param_count()["total"],
        "n_params_active": cfg.param_count()["active"],
        "bytes_by_op": top_bytes,
    }
    return out


def cell_path(arch: str, shape: str, mesh_name: str, variant: str) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    return os.path.join(RESULTS_DIR, f"{arch}__{shape}__{mesh_name}__{variant}.json")


def run_cells(archs, shapes, meshes, variant: str, skip_existing: bool = True):
    summary = []
    for multi_pod in meshes:
        mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
        for arch in archs:
            cfg = get_config(arch)
            for shape in shapes:
                if not shape_applicable(cfg, shape):
                    print(f"SKIP {arch} x {shape} (inapplicable: "
                          f"{'needs sub-quadratic decode' if shape == 'long_500k' else '?'})",
                          flush=True)
                    continue
                path = cell_path(arch, shape, mesh_name, variant)
                if skip_existing and os.path.exists(path):
                    print(f"HAVE {arch} x {shape} x {mesh_name}", flush=True)
                    continue
                print(f"CELL {arch} x {shape} x {mesh_name} ...", flush=True)
                try:
                    res = lower_cell(arch, shape, multi_pod, variant)
                    with open(path, "w") as f:
                        json.dump(res, f, indent=1)
                    print(f"  ok: compile={res['compile_s']:.1f}s "
                          f"bottleneck={res['bottleneck']} "
                          f"t=({res['t_compute_s']:.2e},{res['t_memory_s']:.2e},"
                          f"{res['t_collective_s']:.2e})s "
                          f"mem={res['memory_analysis']}", flush=True)
                    summary.append(res)
                except Exception as e:
                    err = {"arch": arch, "shape": shape, "mesh": mesh_name,
                           "variant": variant, "error": str(e),
                           "traceback": traceback.format_exc()}
                    with open(path + ".err", "w") as f:
                        json.dump(err, f, indent=1)
                    print(f"  FAIL: {type(e).__name__}: {str(e)[:300]}",
                          flush=True)
    return summary


# --------------------------------------------------------------------------
# Sketch-serving cells: the sharded heavy-hitter pipeline lowered on the
# production meshes (and the CI-scale test mesh), alongside the model cells.
# Three lowered units cover the ShardedTopKService data path
# (serving/sharded_topk.py):
#   sketch_ingest -- per-shard lazy fold of one stream block into every
#                    hierarchy level (no collective; the ingest hot path),
#   sketch_sync   -- the explicit psum sync point merging the per-shard
#                    level tables (the only collective in the pipeline),
#   sketch_build  -- synchronous fold + psum in one program
#                    (core.hierarchy.sharded_hierarchy_build).
# The descent itself is a host-driven loop over batched queries and is
# exercised by tests/benchmarks, not lowered as one XLA program.
# --------------------------------------------------------------------------

SKETCH_CELLS = ("sketch_ingest", "sketch_sync", "sketch_build")
SKETCH_MESHES = ("pod16x16", "pod2x16x16", "test2x2")
SKETCH_BATCH = 1 << 20          # rows per ingested block (global)


def _sketch_mesh(mesh_kind: str):
    if mesh_kind == "test2x2":
        return make_test_mesh()
    return make_production_mesh(multi_pod=(mesh_kind == "pod2x16x16"))


def lower_sketch_cell(cell: str, mesh_kind: str,
                      batch: int = SKETCH_BATCH) -> Dict[str, Any]:
    from repro.core import distributed as dist
    from repro.core import hierarchy as hhm
    from repro.core import sketch as sks
    from repro.core.hashing import KeySchema

    mesh = _sketch_mesh(mesh_kind)
    data_axes = sketch_data_axes(mesh)
    n_shards = 1
    for a in data_axes:
        n_shards *= mesh.shape[a]
    b = max(batch // n_shards, 1) * n_shards

    # telemetry-shaped keys: two 32-bit modules (edge / routed-token pairs)
    schema = KeySchema(domains=(1 << 32, 1 << 32))
    base = sks.mod_sketch_spec(schema, [(0,), (1,)], (512, 512), 4)
    hspec = hhm.HierarchySpec.from_spec(base)
    state = hhm.init_hierarchy(hspec, jax.random.PRNGKey(0))
    params = tuple(st.params for st in state.states)
    local_sds = tuple(
        jax.ShapeDtypeStruct((n_shards,) + st.table.shape, st.table.dtype)
        for st in state.states)
    items_sds = jax.ShapeDtypeStruct((b, 2), jnp.uint32)
    freqs_sds = jax.ShapeDtypeStruct((b,), jnp.int32)

    t0 = time.perf_counter()
    if cell == "sketch_ingest":
        fn = jax.jit(lambda local, it, fr: dist.lazy_hierarchy_update(
            hspec, mesh, data_axes, local, params, it, fr))
        lowered = fn.lower(local_sds, items_sds, freqs_sds)
    elif cell == "sketch_sync":
        fn = jax.jit(lambda local: dist.merge_local_hierarchy(
            mesh, data_axes, local))
        lowered = fn.lower(local_sds)
    elif cell == "sketch_build":
        fn = jax.jit(lambda st_, it, fr: hhm.sharded_hierarchy_build(
            hspec, st_, mesh, data_axes, it, fr))
        state_sds = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
        lowered = fn.lower(state_sds, items_sds, freqs_sds)
    else:
        raise ValueError(f"unknown sketch cell {cell!r}")
    t_lower = time.perf_counter() - t0

    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0

    cost, mem_d, hlo = _compiled_stats(compiled)
    hcost = ha.analyze(hlo)
    return {
        "cell": cell,
        "mesh": mesh_kind,
        "chips": mesh.size,
        "n_shards": n_shards,
        "data_axes": list(data_axes),
        "batch": b,
        "levels": hspec.n_levels,
        "table_cells": hspec.table_cells,
        "lower_s": t_lower,
        "compile_s": t_compile,
        "flops_per_chip": hcost.flops,
        "hbm_bytes_per_chip": hcost.bytes,
        "collectives": {"counts": hcost.coll_counts,
                        "result_bytes": hcost.coll_bytes,
                        "wire_bytes": hcost.coll_wire_bytes},
        "memory_analysis": mem_d,
        "cost_flops": float(cost.get("flops", 0.0)),
        "cost_bytes": float(cost.get("bytes accessed", 0.0)),
    }


def sketch_cell_path(cell: str, mesh_kind: str) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    return os.path.join(RESULTS_DIR, f"sketch__{cell}__{mesh_kind}.json")


def run_sketch_cells(skip_existing: bool = True):
    summary = []
    for mesh_kind in SKETCH_MESHES:
        for cell in SKETCH_CELLS:
            path = sketch_cell_path(cell, mesh_kind)
            if skip_existing and os.path.exists(path):
                print(f"HAVE {cell} x {mesh_kind}", flush=True)
                continue
            print(f"CELL {cell} x {mesh_kind} ...", flush=True)
            try:
                res = lower_sketch_cell(cell, mesh_kind)
                with open(path, "w") as f:
                    json.dump(res, f, indent=1)
                coll = res["collectives"]["counts"]
                print(f"  ok: compile={res['compile_s']:.1f}s "
                      f"shards={res['n_shards']} collectives={coll} "
                      f"mem={res['memory_analysis']}", flush=True)
                summary.append(res)
            except Exception as e:
                err = {"cell": cell, "mesh": mesh_kind, "error": str(e),
                       "traceback": traceback.format_exc()}
                with open(path + ".err", "w") as f:
                    json.dump(err, f, indent=1)
                print(f"  FAIL: {type(e).__name__}: {str(e)[:300]}",
                      flush=True)
    return summary


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--variant", default="baseline", choices=list(VARIANTS))
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--sketch-cells", action="store_true",
                    help="lower the sharded sketch-serving cells "
                         "(ingest/sync/build on every mesh) instead of the "
                         "model cells")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()  # persistent cache speeds up resumed sweeps

    if args.sketch_cells:
        run_sketch_cells(skip_existing=not args.force)
        return

    archs = ARCHS if args.all or not args.arch else [args.arch]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]
    meshes = [False, True]
    if args.multi_pod_only:
        meshes = [True]
    if args.single_pod_only:
        meshes = [False]
    run_cells(archs, shapes, meshes, args.variant,
              skip_existing=not args.force)


if __name__ == "__main__":
    main()
