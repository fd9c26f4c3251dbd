"""A copy of the benchmark with small cells, for runs on the CPU."""
from __future__ import annotations

import json
import shutil
import time
from pathlib import Path

from bench import catalog, harness

REPO = Path(__file__).resolve().parents[2]
SMALL_STREAM = dict(n_src_hosts=20_000, n_dst_hosts=400, n_pair_draws=30_000,
                    n_packets=1 << 22, block_rows=512)


def make_root(tmp: Path, config: str = "ipv4-zipf-cm") -> Path:
    """``tmp`` holding BENCHMARK.json and bench/ with one small cell per mix
    (``small.ingest``, ``small.query``) of a cut-down ``config`` on the jnp
    path (the Pallas kernels run interpreted on the CPU), the dashboard
    refreshing every 0.5 s."""
    root = Path(tmp)
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cfg = json.loads((REPO / "bench" / "configs" / f"{config}.json")
                     .read_text())
    cfg["stream"].update(SMALL_STREAM)
    cfg["sketch"]["ranges"] = [1 << 12]
    cfg["service"].update(pool_capacity=1024, use_kernel=False)
    if "use_update_kernel" in cfg["service"]:
        cfg["service"]["use_update_kernel"] = False
    (root / "bench" / "configs" / "small.json").write_text(json.dumps(cfg))
    mix = json.loads((root / "bench" / "traffic" / "ingest.json").read_text())
    mix["requests"]["period_s"] = 0.5          # refreshes inside 1.5 s
    (root / "bench" / "traffic" / "ingest.json").write_text(json.dumps(mix))
    bench["configs"] = [{"name": "small", "source": "tests",
                         "file": "bench/configs/small.json", "reduced": [],
                         "why": "small"}]
    bench["workloads"] = [
        {"name": f"small.{mix}", "config": "small", "traffic": mix,
         "chips": 1, "why": "small"}
        for mix in ("ingest", "query")]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = sorted({"small." + w.split(".")[-1]
                                     for w in m["workloads"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def run(root: Path, cell: str, seed: int = 5, seconds: float = 1.5,
        make_server=None, devices=None) -> dict:
    """One run of a small cell on the CPU (the look for a chip skipped).

    The persistent compile cache the run turns on is turned off again, so
    later tests in the process neither read nor write it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    opts = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {o: getattr(jax.config, o) for o in opts}
    cat = catalog.Catalog(root)
    c = cat.cell(cell)
    try:
        return harness.run_cell(cat, c, seed, seconds, False,
                                devices or jax.devices(), {},
                                time.perf_counter(), make_server=make_server,
                                log=lambda m: None)
    finally:
        for o, v in saved.items():
            jax.config.update(o, v)
        compilation_cache.reset_cache()
