"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Boots the batched engine (prefill + decode with KV/SSM caches) on local
devices and runs a synthetic batched-request workload through the slot
scheduler, reporting decode throughput.

``--sketch-autotune`` runs the other serving stack instead: a
SketchTopKEndpoint under an online AutoTuner, fed a module-skew-flip
stream (streams.dstream.skew_flip_batches).  The tuner derives live
stats from the endpoint's own pools/tables, re-runs the greedy strategy
search, and hot-migrates the endpoint to the re-drawn spec through a
double-write warmup window -- the launcher reports every tune decision
and the final heavy-hitter error of the migrated endpoint next to a
stale (never-retuned) twin fed the same stream.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np


def run_sketch_autotune(args) -> None:
    from repro.core import sketch as sk
    from repro.core.hashing import KeySchema
    from repro.serving.autotune import AutoTuner
    from repro.serving.sketch_engine import (SketchServeEngine,
                                             SketchTopKEndpoint)
    from repro.streams import skew_flip_batches
    from repro.streams.stats import topk_point_are

    domains = (args.domain, args.domain)
    schema = KeySchema(domains=domains)
    key = jax.random.PRNGKey(args.seed)

    # Deliberately stale spec: ranges tuned for a skewed module 0 / wide
    # module 1 -- the stream flips that halfway through.
    h = args.sketch_h
    stale = sk.mod_sketch_spec(schema, [(0,), (1,)],
                               (max(2, h // 64), 64), args.sketch_w)
    live = SketchTopKEndpoint(stale, key)
    tuner = AutoTuner(live, jax.random.fold_in(key, 1),
                      retune_every=args.retune_every, warmup=args.warmup,
                      min_improvement=args.min_improvement, sample_k=256,
                      min_threshold=1, search=args.search)
    # the tuner plugs into the serving engine at exactly one place: it
    # ticks on every sync() (snapshot boundary), so retune decisions --
    # and the migrations they open -- happen between pipelined blocks,
    # never against half-folded tables
    engine = SketchServeEngine(live, max_staleness=None, tuner=tuner)

    batches = list(skew_flip_batches(domains, args.batches,
                                     args.rows_per_batch, seed=args.seed))
    window_start = 0          # first batch the CURRENT tables have seen
    t0 = time.perf_counter()
    for b, batch in enumerate(batches):
        n_prev = len(tuner.decisions)
        engine.ingest(batch.items, batch.freqs)
        engine.sync()
        d = tuner.decisions[-1] if len(tuner.decisions) > n_prev else None
        if d is not None:
            print(f"[batch {b:3d} total={d.at_total:,}] {d.reason}: "
                  f"sigma {d.sigma_current:.2f} -> {d.sigma_proposed:.2f}"
                  + (f" ranges {d.proposed_ranges}" if d.migrated else ""))
        if d is not None and d.migrated:
            # the successor starts absorbing from the NEXT ingest; after
            # cutover the endpoint's window starts here
            window_start = b + 1
        if live.migrating:
            print(f"[batch {b:3d}] warmup {live.migration_progress:.0%}")
    dt = time.perf_counter() - t0

    # Post-cutover the endpoint describes its post-migration window, so
    # score it against that window's exact counts -- and against a twin
    # endpoint on the STALE spec fed exactly the same window, isolating
    # the spec effect (same comparison as benchmarks/migrate_bench.py).
    frozen = SketchTopKEndpoint(stale, key)
    exact: dict = {}
    for batch in batches[window_start:]:
        frozen.ingest(batch.items, batch.freqs)
        for it, f in zip(batch.items.tolist(), batch.freqs.tolist()):
            exact[tuple(it)] = exact.get(tuple(it), 0) + f
    top = sorted(exact.items(), key=lambda kv: -kv[1])[:args.topk]
    q = np.array([k for k, _ in top], dtype=np.uint32)
    true = np.array([v for _, v in top], dtype=np.int64)

    def are(ep):
        # twin scoring shared with the DStream harness (streams/stats.py)
        return topk_point_are(ep.hspec, ep.state, q, true)

    print(f"\n{args.batches} batches in {dt:.2f}s; "
          f"migrations={sum(d.migrated for d in tuner.decisions)} "
          f"(spec now partition={live.hspec.base.partition} "
          f"ranges={live.hspec.base.ranges})")
    print(f"window batches [{window_start}:{len(batches)}] "
          f"top-{args.topk} ARE  auto-tuned={are(live):.4f}  "
          f"stale={are(frozen):.4f}")


def run_model_serving(args) -> None:
    from repro.configs import get_config, get_reduced
    from repro.models import transformer as tfm
    from repro.serving.model_engine import (Request, ServeConfig, ServeEngine,
                                            SlotScheduler)

    cfg = get_config(args.arch) if args.full else get_reduced(args.arch)
    params = tfm.init_params(cfg, jax.random.PRNGKey(args.seed))
    scfg = ServeConfig(max_len=args.prompt_len + args.max_new + 8,
                       temperature=args.temperature)
    engine = ServeEngine(cfg, params, scfg, seed=args.seed)
    sched = SlotScheduler(engine, n_slots=args.slots)

    rng = np.random.default_rng(args.seed)
    for rid in range(args.requests):
        prompt = rng.integers(0, cfg.vocab_size,
                              size=(args.prompt_len,)).astype(np.int32)
        sched.submit(Request(rid=rid, prompt=prompt, max_new=args.max_new))

    t0 = time.perf_counter()
    done = sched.run()
    dt = time.perf_counter() - t0
    n_tok = sum(len(r.out) for r in done)
    print(f"served {len(done)} requests, {n_tok} tokens in {dt:.2f}s "
          f"({n_tok / dt:.1f} tok/s decode incl. prefill)")
    print("sample output:", done[0].out[:8])


def main() -> None:
    from repro.configs import ARCHS

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS,
                    help="model arch to serve (omit with --sketch-autotune)")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    # sketch auto-tune mode
    ap.add_argument("--sketch-autotune", action="store_true",
                    help="serve a sketch endpoint under the online "
                         "auto-tuner over a skew-flip drift stream")
    ap.add_argument("--domain", type=int, default=1 << 16)
    ap.add_argument("--batches", type=int, default=24)
    ap.add_argument("--rows-per-batch", type=int, default=4_000)
    ap.add_argument("--sketch-h", type=int, default=4_096)
    ap.add_argument("--sketch-w", type=int, default=4)
    ap.add_argument("--retune-every", type=int, default=20_000)
    ap.add_argument("--warmup", type=int, default=8_000)
    ap.add_argument("--min-improvement", type=float, default=0.9)
    ap.add_argument("--search", choices=("greedy", "ranges"),
                    default="ranges")
    ap.add_argument("--topk", type=int, default=32)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.sketch_autotune:
        run_sketch_autotune(args)
    else:
        if args.arch is None:
            ap.error("--arch is required unless --sketch-autotune is set")
        run_model_serving(args)


if __name__ == "__main__":
    main()
