"""The harness is driven by files: a configuration (with a stream generator
of its own), a mix and a metric are added as new files plus BENCHMARK.json
entries, with no code changed; an unknown chip and a machine without one
are refused."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import catalog
from bench.tests import tiny

READER = '''
def read(run):
    """Flushes per second of the window."""
    return len(run.window.flush_sizes) / run.window.seconds
'''
GENERATOR = '''
import numpy as np


def generate(rng, *, n_keys, top_weight):
    """Uniform (src, dst) keys with uniform weights."""
    keys = rng.integers(0, 1 << 32, size=(n_keys, 2), dtype=np.uint64)
    items = np.unique(keys.astype(np.uint32), axis=0)
    return items, rng.integers(1, top_weight, items.shape[0]).astype(np.int64)
'''


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    root = tiny.make_root(tmp_path)
    b = root / "bench"
    cfg = json.loads((b / "configs" / "small.json").read_text())
    cfg["stream"] = {"generator": "uniform_pairs", "n_keys": 5000,
                     "top_weight": 3000, "block_rows": 256}
    (b / "streams" / "uniform_pairs.py").write_text(GENERATOR)
    (b / "configs" / "small-256.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic" / "ingest.json").read_text())
    mix["requests"]["period_s"] = 0.25
    (b / "traffic" / "dashboard-4hz.json").write_text(json.dumps(mix))
    (b / "metrics" / "flushes_per_s.py").write_text(READER)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "small-256", "source": "tests",
                             "file": "bench/configs/small-256.json",
                             "reduced": [], "why": "added as a file"})
    bench["workloads"].append({"name": "small-256.dashboard-4hz",
                               "config": "small-256",
                               "traffic": "dashboard-4hz", "chips": 1,
                               "why": "added as a file"})
    bench["end_to_end"].append({"name": "flushes_per_s", "unit": "1/s",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["small-256.dashboard-4hz"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    out = tiny.run(root, "small-256.dashboard-4hz", seconds=1.2)
    assert out["correct"], out["checks"]
    assert out["attempted"] == 2 * 4          # four refreshes of two requests
    assert out["metrics"]["flushes_per_s"]["value"] == pytest.approx(4 / 1.2)
    assert set(out["metrics"]) == {"setup_s", "flushes_per_s"}
    other = catalog.Catalog(root).cell("small.ingest")
    assert "flushes_per_s" not in [m.name for m in other.end_to_end]


def test_every_metric_of_the_benchmark_has_a_reader():
    cat = catalog.Catalog(tiny.REPO)
    names = [w["name"] for w in cat.bench["workloads"]]
    for name in names:
        cell = cat.cell(name)
        assert any(m.name == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(catalog.load_reader(m))


def test_a_device_missing_from_the_peaks_table_is_an_error():
    cat = catalog.Catalog(tiny.REPO)
    assert cat.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="peaks.json"):
        cat.peaks("TPU v9 imaginary")


def _run_bench(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ipv4-zipf-cm.ingest",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, env=env, timeout=300)


def test_a_run_without_a_tpu_fails_and_prints_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = _run_bench(tiny.REPO, env)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
    assert "no TPU" in out.stderr
    # a directory with the benchmark alone (no program) fails as well
    shutil.copytree(tiny.REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(tiny.REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    env.pop("PYTHONPATH", None)
    out = _run_bench(tmp_path, env)
    assert out.returncode != 0 and '"correct"' not in out.stdout
