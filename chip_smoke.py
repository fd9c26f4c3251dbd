#!/usr/bin/env python3
"""Smoke test of the sketch serving path on a TPU.

Drives the main serving path once, through the entry points a user calls,
at a network-telemetry deployment size: heavy-hitter detection over
(src IPv4, dst IPv4) pairs, the shape of the paper's CAIDA IP-trace
experiments, generated from ``--seed`` (streams.synthetic.ipv4_stream).

  * ``SketchServeEngine`` over ``SketchTopKEndpoint(use_kernel=True,
    use_update_kernel=True, dtype=int32, mode="linear")``: ingest through
    the fused ``hier_update`` Pallas kernel in blocks of 4096 rows, then
    ``submit_topk(100)`` / ``submit_heavy_hitters(phi * total)`` requests
    answered by ``flush()`` through the batched ``hier_query`` descent;
  * a twin endpoint on the jnp reference path, on the same device, fed the
    same blocks: tables and answers must be identical;
  * exact host-side counts: every pair above phi is reported and no
    estimate is below its true count;
  * compiled (not interpreted) kernels: the compiled ingest and descent
    programs must contain ``tpu_custom_call``, and a small parity phase
    checks every kernel against its jnp oracle first.

``--chips 4`` runs only the four-chip path: ``ShardedTopKService`` on a
4-device ``data`` mesh over the same stream, compared bit for bit with the
one-device kernel endpoint's tables and top-k, with the four shards
checked to sit on four distinct devices.

Exits non-zero, without the result line, when JAX finds no TPU or any
check fails.  The last line of standard output is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

Usage:  python chip_smoke.py [--seed N] [--chips 4]
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

PHI = 1e-4                     # heavy-hitter fraction of the stream mass
H = 1 << 20                    # finest-level cells per row
W = 4                          # rows
BLOCK_ROWS = 4096              # rows per engine.ingest call
MIN_PAIRS = 1_000_000
MIN_MASS = 1 << 26
MAX_WEIGHT = 1 << 24           # kernel limb bound (exclusive)
STREAM = dict(n_src_hosts=2_000_000, n_tgt_hosts=4_000,
              n_pairs=3_200_000, n_occurrences=MIN_MASS)


def log(msg: str) -> None:
    print(msg, flush=True)


@contextlib.contextmanager
def phase(name: str):
    t0 = time.perf_counter()
    log(f"== {name}")
    yield
    log(f"   {name}: {time.perf_counter() - t0:.3f} s")


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(f"check failed: {what}")
    log(f"   ok: {what}")


def kernel_parity(jax, jnp, np) -> None:
    """Every kernel compiled on the chip against its jnp oracle at a small
    size: linear/f32/signed fused fold, flat fold, point query, candidate
    gathers; and the conservative kernel's refusal."""
    from repro.core import countsketch as cs
    from repro.core import hierarchy as hh
    from repro.core import sketch as sk
    from repro.core.hashing import KeySchema
    from repro.kernels import ref
    from repro.kernels.hashes import make_plan
    from repro.kernels.hier_query import (
        hier_candidate_query, hier_candidate_query_batched,
        hier_candidate_query_batched_ref, hier_candidate_query_ref,
        hier_candidate_query_signed, hier_candidate_query_signed_ref)
    from repro.kernels.hier_update import (
        hier_update_pallas, hier_update_ref, hier_update_signed_pallas,
        hier_update_signed_ref, make_hier_plan)
    from repro.kernels.ops import KernelSketch
    from repro.kernels.sketch_query import sketch_query_pallas
    from repro.kernels.sketch_update import (
        padded_table_size, sketch_update_pallas)

    rng = np.random.default_rng(1)
    schema = KeySchema(domains=(1 << 32, 1 << 32))
    base = sk.mod_sketch_spec(schema, [(0,), (1,)], (100, 300), W)
    hspec = hh.HierarchySpec.from_spec(base)
    hplan = make_hier_plan(hspec)
    items = rng.integers(0, 1 << 32, size=(1000, 2), dtype=np.uint64)
    items[200:400] = items[7]                      # in-block duplicates
    items = jnp.asarray(items.astype(np.uint32))
    chunks = schema.module_chunks(items)
    big = rng.integers(0, MAX_WEIGHT, size=1000).astype(np.int32)
    signed = (big * rng.choice([-1, 1], size=1000)).astype(np.int32)
    params = sk.init_params(hspec.levels[-1], jax.random.PRNGKey(2))
    cparams = cs.init_params(hspec.levels[-1], jax.random.PRNGKey(3))
    zeros = jnp.zeros((W, hplan.padded_cols), jnp.int32)

    def same(a, b, what):
        check(bool(np.array_equal(np.asarray(a), np.asarray(b))), what)

    for f, name in ((big, "max-limb"), (big % 7, "small")):
        same(hier_update_pallas(hplan, jnp.zeros_like(zeros), chunks,
                                jnp.asarray(f), params.q, params.r),
             hier_update_ref(hplan, zeros, chunks, jnp.asarray(f),
                             params.q, params.r),
             f"hier_update int32 == jnp oracle ({name} weights)")
    f32 = (big % 1000).astype(np.float32)
    same(hier_update_pallas(hplan, jnp.zeros(zeros.shape, jnp.float32),
                            chunks, jnp.asarray(f32), params.q, params.r),
         hier_update_ref(hplan, jnp.zeros(zeros.shape, jnp.float32), chunks,
                         jnp.asarray(f32), params.q, params.r),
         "hier_update float32 == jnp oracle")
    cb = cparams.base
    same(hier_update_signed_pallas(hplan, jnp.zeros_like(zeros), chunks,
                                   jnp.asarray(signed), cb.q, cb.r,
                                   cparams.sign_q, cparams.sign_r),
         hier_update_signed_ref(hplan, zeros, chunks, jnp.asarray(signed),
                                cb.q, cb.r, cparams.sign_q, cparams.sign_r),
         "hier_update signed int32 == jnp oracle")

    plan = make_plan(base)
    h_pad = padded_table_size(base.table_size, 512)
    flat0 = jnp.zeros((W, h_pad), jnp.int32)
    flat = sketch_update_pallas(plan, jnp.zeros_like(flat0), chunks,
                                jnp.asarray(big), params.q, params.r)
    same(flat, ref.sketch_update_ref(plan, flat0, chunks, jnp.asarray(big),
                                     params.q, params.r),
         "sketch_update == jnp oracle")
    same(sketch_query_pallas(plan, flat, chunks, params.q, params.r),
         ref.sketch_query_ref(plan, flat, chunks, params.q, params.r),
         "sketch_query == jnp oracle")
    table = jnp.asarray(rng.integers(-(1 << 31), (1 << 31) - 1,
                                     size=(W, 30_000), dtype=np.int64)
                        .astype(np.int32))
    pp = jnp.asarray(rng.integers(0, 100, size=(W, 37)) * 300, jnp.uint32)
    cp = jnp.asarray(rng.integers(0, 300, size=(W, 1500)), jnp.uint32)
    same(hier_candidate_query(table, pp, cp),
         hier_candidate_query_ref(table, pp, cp),
         "hier_candidate_query == jnp oracle (full int32 range)")
    pp3 = pp[:, :36].reshape(W, 4, 9)
    same(hier_candidate_query_batched(table, pp3, cp),
         hier_candidate_query_batched_ref(table, pp3, cp),
         "hier_candidate_query_batched == jnp oracle")
    sp = jnp.asarray(rng.choice([-1.0, 1.0], size=(W, 37)), jnp.float32)
    sc = jnp.asarray(rng.choice([-1.0, 1.0], size=(W, 1500)), jnp.float32)
    same(np.asarray(hier_candidate_query_signed(table, pp, cp, sp, sc)
                    ).astype(np.float32),
         hier_candidate_query_signed_ref(table, pp, cp, sp, sc),
         "hier_candidate_query_signed == jnp oracle")
    try:
        KernelSketch(base, jax.random.PRNGKey(0), mode="conservative")
    except ValueError as e:
        check("update_conservative_jit" in str(e),
              "conservative kernel refuses on TPU and names the jnp path")
    else:
        raise AssertionError("conservative kernel accepted a TPU launch")


def make_stream(seed: int):
    from repro.streams import ipv4_stream

    stream = ipv4_stream(seed=seed, **STREAM)
    return stream.schema, stream.items, stream.freqs


def build_spec(jax, np, schema, items, freqs, seed: int):
    """Ranges and partition from the paper's pipeline on a 2 % sample."""
    from repro.core.selection import choose_sketch
    from repro.streams.synthetic import Stream

    s_items, s_freqs = Stream(schema=schema, items=items, freqs=freqs).sample(
        0.02, np.random.default_rng(seed))
    result = choose_sketch(s_items, s_freqs, schema, H, W,
                           jax.random.PRNGKey(seed))
    log(f"   strategy {result.choice}: {result.spec.describe()} "
        f"(sigma {result.sigma}; sample rows {len(s_freqs)})")
    return result.spec


def ingest_all(engine, blocks, jax):
    """Feed every block through ``engine.ingest``; returns (first block s,
    remaining blocks s) -- the first includes compilation."""
    t0 = time.perf_counter()
    engine.ingest(*blocks[0])
    jax.block_until_ready(engine.sync().state)
    t1 = time.perf_counter()
    for it, fr in blocks[1:]:
        engine.ingest(it, fr)
    jax.block_until_ready(engine.sync().state)
    return t1 - t0, time.perf_counter() - t1


def tables_of(state, np):
    return [np.asarray(st.table) for st in state.states]


def serve_queries(engine, threshold: int):
    reqs = [engine.submit_topk(100), engine.submit_topk(10),
            engine.submit_heavy_hitters(threshold),
            engine.submit_heavy_hitters(2 * threshold)]
    t0 = time.perf_counter()
    engine.flush()
    return reqs, time.perf_counter() - t0


def exact_checks(np, items, freqs, reqs, threshold: int) -> None:
    keys = (items[:, 0].astype(np.uint64) << np.uint64(32)) | items[:, 1]
    order = np.argsort(keys)
    keys, counts = keys[order], freqs[order]

    def true_counts(got):
        k = (got[:, 0].astype(np.uint64) << np.uint64(32)) | got[:, 1]
        pos = np.clip(np.searchsorted(keys, k), 0, len(keys) - 1)
        return np.where(keys[pos] == k, counts[pos], 0)

    for r in reqs:
        what = (f"top-{r.k}" if r.kind == "topk"
                else f"heavy hitters >= {r.threshold}")
        check(bool((r.est >= true_counts(r.items)).all()),
              f"{what}: no estimate below its true count "
              f"({len(r.est)} keys)")
        if r.kind == "heavy_hitters":
            heavy = set(keys[counts >= r.threshold].tolist())
            got = set(((r.items[:, 0].astype(np.uint64) << np.uint64(32))
                       | r.items[:, 1]).tolist())
            check(heavy <= got, f"{what}: all {len(heavy)} true heavy "
                  "pairs reported (no false negatives)")
    check(reqs[0].k == len(reqs[0].est), "top-100 returned 100 keys")


def compiled_path_checks(jax, jnp, np, ep) -> None:
    """The jitted ingest and descent, compiled for this chip, contain the
    Pallas kernels (``tpu_custom_call``), and the wrapper is not in
    interpret mode."""
    from repro.kernels.hier_query import hier_candidate_query_batched
    from repro.kernels.hier_update import hier_update_pallas

    kh = ep._kh
    check(kh is not None and kh.interpret is False,
          "endpoint ingests through KernelHierarchy, interpret=False")
    schema = kh.hspec.levels[-1].schema
    chunks = schema.module_chunks(jnp.zeros((kh.block_b, schema.modularity),
                                            jnp.uint32))
    text = hier_update_pallas.lower(
        kh.hplan, kh.table, chunks, jnp.zeros((kh.block_b,), jnp.int32),
        kh.params.q, kh.params.r, interpret=kh.interpret).compile().as_text()
    check("tpu_custom_call" in text, "compiled ingest holds the fused "
          "hier_update kernel (tpu_custom_call)")
    state = ep.state
    table = state.states[ep.hspec.n_levels - 1].table
    pp = jnp.zeros((W, 2, 4), jnp.uint32)
    cp = jnp.zeros((W, 1 << 13), jnp.uint32)
    text = hier_candidate_query_batched.lower(table, pp, cp).compile(
        ).as_text()
    check("tpu_custom_call" in text, "compiled descent holds the batched "
          "hier_query gather kernel (tpu_custom_call)")


def run_one_chip(args, jax, jnp, np) -> None:
    from repro.serving.sketch_engine import (SketchServeEngine,
                                             SketchTopKEndpoint)

    with phase("kernel parity (compiled vs jnp oracle, small)"):
        kernel_parity(jax, jnp, np)
    items, freqs, blocks, spec = prepare(args, jax, np)
    key = jax.random.PRNGKey(args.seed)
    total = int(freqs.sum())
    threshold = int(np.ceil(PHI * total))
    engines = {}
    for name, kernel in (("kernel", True), ("reference", False)):
        with phase(f"ingest, {name} path"):
            ep = SketchTopKEndpoint(spec, key, use_kernel=kernel,
                                    use_update_kernel=kernel,
                                    dtype=jnp.int32, mode="linear")
            eng = SketchServeEngine(ep)
            first, rest = ingest_all(eng, blocks, jax)
            log(f"   first block (compile + run) {first:.3f} s; remaining "
                f"{len(blocks) - 1} blocks {rest:.3f} s")
            engines[name] = eng
    with phase("compiled kernels on the path"):
        compiled_path_checks(jax, jnp, np, engines["kernel"].backend)
    with phase("tables: kernel path vs reference path"):
        tk = tables_of(engines["kernel"].backend.state, np)
        tr = tables_of(engines["reference"].backend.state, np)
        check(all(np.array_equal(a, b) for a, b in zip(tk, tr)),
              f"int32 tables identical ({[t.shape for t in tk]})")
        check(engines["kernel"].backend.total == total,
              f"stream mass {total} accounted")
    answers = {}
    for name, eng in engines.items():
        with phase(f"queries, {name} path"):
            reqs, dt = serve_queries(eng, threshold)
            answers[name] = reqs
            for r in reqs:
                log(f"   {r.kind} k={r.k} threshold={r.threshold}: "
                    f"{len(r.est)} keys")
            log(f"   flush of {len(reqs)} requests: {dt:.3f} s")
    with phase("answers: kernel path vs reference path"):
        for a, b in zip(answers["kernel"], answers["reference"]):
            check(np.array_equal(a.items, b.items)
                  and np.array_equal(a.est, b.est),
                  f"{a.kind} k={a.k} threshold={a.threshold} identical")
    with phase("answers vs exact counts"):
        exact_checks(np, items, freqs, answers["kernel"], threshold)


def run_four_chips(args, jax, jnp, np) -> None:
    from repro.launch.mesh import make_mesh
    from repro.serving.sharded_topk import ShardedTopKService
    from repro.serving.sketch_engine import (SketchServeEngine,
                                             SketchTopKEndpoint)

    items, freqs, blocks, spec = prepare(args, jax, np)
    key = jax.random.PRNGKey(args.seed)
    with phase("ingest, one-device kernel endpoint"):
        ep = SketchTopKEndpoint(spec, key, use_kernel=True,
                                use_update_kernel=True, dtype=jnp.int32)
        one = SketchServeEngine(ep)
        first, rest = ingest_all(one, blocks, jax)
        log(f"   first block {first:.3f} s; remaining {rest:.3f} s")
    with phase("ingest, ShardedTopKService on a 4-device data mesh"):
        mesh = make_mesh((4,), ("data",), devices=jax.devices()[:4])
        svc = ShardedTopKService(spec, key, mesh, use_kernel=True,
                                 dtype=jnp.int32)
        four = SketchServeEngine(svc)
        first, rest = ingest_all(four, blocks, jax)
        log(f"   first block {first:.3f} s; remaining {rest:.3f} s")
    with phase("placement"):
        local = svc._local[-1]
        devs = sorted({s.device.id for s in local.addressable_shards})
        log(f"   local table shards on devices {devs}, shard shape "
            f"{local.addressable_shards[0].data.shape}")
        check(len(devs) == 4, "the four shards sit on four distinct devices")
    with phase("tables and top-k: 4 shards vs one device"):
        t1 = tables_of(ep.state, np)
        t4 = tables_of(svc.state(), np)
        check(all(np.array_equal(a, b) for a, b in zip(t1, t4)),
              "int32 tables bit-identical")
        threshold = int(np.ceil(PHI * int(freqs.sum())))
        r1, dt1 = serve_queries(one, threshold)
        r4, dt4 = serve_queries(four, threshold)
        log(f"   flush: one device {dt1:.3f} s, four devices {dt4:.3f} s")
        for a, b in zip(r1, r4):
            check(np.array_equal(a.items, b.items)
                  and np.array_equal(a.est, b.est),
                  f"{a.kind} k={a.k} threshold={a.threshold} identical")


def prepare(args, jax, np):
    with phase("stream (network telemetry, src/dst IPv4 pairs)"):
        schema, items, freqs = make_stream(args.seed)
        log(f"   distinct pairs {len(freqs)}, packets {int(freqs.sum())}, "
            f"max row weight {int(freqs.max())}")
        log("   cuts: none (full stream, full table)")
        check(len(freqs) >= MIN_PAIRS, f">= {MIN_PAIRS} distinct pairs")
        check(int(freqs.sum()) >= MIN_MASS, ">= 2^26 packets")
        check(int(freqs.max()) < MAX_WEIGHT, "every row weight < 2^24")
        order = np.random.default_rng(args.seed).permutation(len(freqs))
        items, freqs = items[order], freqs[order]
        blocks = [(items[s : s + BLOCK_ROWS], freqs[s : s + BLOCK_ROWS])
                  for s in range(0, len(freqs), BLOCK_ROWS)]
        log(f"   {len(blocks)} ingest blocks of {BLOCK_ROWS} rows")
    with phase("strategy (choose_sketch on a 2% sample)"):
        spec = build_spec(jax, np, schema, items, freqs, args.seed)
        log(f"   table: {W} rows x {spec.table_size} int32 cells "
            f"({W * spec.table_size * 4 / 2**20:.1f} MiB finest level)")
    return items, freqs, blocks, spec


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    devices = jax.devices()
    dev = devices[0]
    log(f"jax {jax.__version__}; devices: {len(devices)} x {dev.platform} "
        f"{dev.device_kind}")
    if dev.platform != "tpu":
        log("no TPU found: this smoke test runs on the chip only")
        return 2
    if len(devices) < args.chips:
        log(f"--chips {args.chips} needs {args.chips} devices")
        return 2
    from repro.kernels.ops import default_interpret
    from repro.launch.compile_cache import enable_compile_cache
    check(default_interpret() is False, "Pallas interpret mode is off")
    cache = enable_compile_cache()
    warm = os.path.isdir(cache) and bool(os.listdir(cache))
    log(f"compile cache: {cache} ({'warm' if warm else 'cold'} at start)")
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(args, jax, jnp, np)
    else:
        run_one_chip(args, jax, jnp, np)
    log(f"total wall {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
