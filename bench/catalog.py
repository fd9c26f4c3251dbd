"""Finds a cell's configuration, traffic mix and metric readers by name.

``BENCHMARK.json`` names them; the files live beside this module:

    configs/<config>.json     the deployment (see bench/configs/*.json)
    streams/<generator>.py    its key stream, ``generate(rng, **params)``
    services/<kind>.py        its backend, ``build(spec, key, service,
                              devices)``
    traffic/<traffic>.json    the mix, read by traffic/generator.py
    metrics/<metric>.py       one reader per metric: ``read(run)`` returns
                              the metric's value, or None where the run
                              holds nothing to read
    peaks.json                published peaks, keyed by ``device_kind``

A configuration, a mix or a metric is added by adding its file and its
entry in ``BENCHMARK.json``; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List

BENCH_DIR = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    reader: Path


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


class Catalog:
    def __init__(self, root: Path):
        """``root`` holds ``BENCHMARK.json`` and the benchmark directory."""
        self.root = Path(root)
        self.bench = json.loads((self.root / "BENCHMARK.json").read_text())
        self.dir = self.root / self.bench["paths"][0]

    def _metric(self, entry: dict) -> Metric:
        path = self.dir / "metrics" / f"{entry['name']}.py"
        if not path.is_file():
            raise FileNotFoundError(f"no reader for metric {entry['name']!r}"
                                    f" at {path}")
        return Metric(entry["name"], entry["unit"], entry["better"],
                      entry["source"], path)

    def cell(self, name: str) -> Cell:
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(have {sorted(cells)})")
        w = cells[name]
        configs = {c["name"]: c for c in self.bench["configs"]}
        config = json.loads((self.root / configs[w["config"]]["file"])
                            .read_text())
        traffic = json.loads((self.dir / "traffic" / f"{w['traffic']}.json")
                             .read_text())
        return Cell(
            name=name, chips=int(w["chips"]), config=config, traffic=traffic,
            end_to_end=[self._metric(m) for m in self.bench["end_to_end"]
                        if _applies(m, name)],
            per_layer=[self._metric(m) for m in self.bench["per_layer"]
                       if _applies(m, name)])

    def peaks(self, device_kind: str) -> dict:
        table = json.loads((self.dir / "peaks.json").read_text())
        if device_kind not in table["devices"]:
            raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                           f"(have {sorted(table['devices'])})")
        return table["devices"][device_kind]


def load_module(path: Path):
    """A benchmark file found by name (its name may hold dots)."""
    path = Path(path)
    name = "bench_{}_{}".format(path.parent.name, path.stem).replace(
        ".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: Metric) -> Callable:
    return load_module(metric.reader).read


def read_metrics(metrics: List[Metric], run) -> Dict[str, dict]:
    """Each metric's reader on ``run``; a reader that finds nothing to read
    leaves its metric out."""
    out = {}
    for m in metrics:
        value = load_reader(m)(run)
        if value is not None:
            out[m.name] = {"value": float(value), "unit": m.unit}
    return out
