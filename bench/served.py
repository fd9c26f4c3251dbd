"""The system under test, built from a configuration file.

This module and ``bench/services/<kind>.py`` (the configuration's backend,
found by its ``service.kind``) are the benchmark's only imports of the
program.  The backend runs behind ``SketchServeEngine``, driven
only through the served entry points: ``ingest``, ``submit_topk``,
``submit_heavy_hitters``, ``flush`` and ``sync``.  The hash parameters are
the benchmark's (drawn from the seed), loaded through the backend's
``state_dict`` / ``load_state_dict``, so the reference hashes alike without
reading anything the program made.
"""
from __future__ import annotations

from typing import List

import numpy as np

from bench.catalog import BENCH_DIR, load_module

SERVICES = BENCH_DIR / "services"


class Served:
    """``SketchServeEngine`` over the configured backend."""

    def __init__(self, config: dict, seed: int, devices, q: np.ndarray,
                 r: np.ndarray):
        import jax
        from repro.core import sketch as sk
        from repro.core.hashing import KeySchema
        from repro.serving.sketch_engine import SketchServeEngine

        self._jax = jax
        sketch, svc, eng = config["sketch"], config["service"], config["engine"]
        if sketch["dtype"] != "int32":
            raise ValueError("the served path keeps int32 tables")
        spec = sk.mod_sketch_spec(
            KeySchema(domains=tuple(int(d) for d in sketch["domains"])),
            sketch["partition"], sketch["ranges"], int(sketch["width"]))
        key = jax.random.PRNGKey(int(seed) % (1 << 31))
        backend = load_module(SERVICES / f"{svc['kind']}.py").build(
            spec, key, svc, devices)
        sd = backend.state_dict()
        sd["params.q"], sd["params.r"] = q, r
        backend.load_state_dict(sd)
        self.backend = backend
        self.engine = SketchServeEngine(
            backend, max_staleness=eng["max_staleness"])

    def ingest(self, items: np.ndarray, freqs: np.ndarray) -> None:
        self.engine.ingest(items, freqs)

    def submit(self, kind: str, k: int, threshold: int):
        if kind == "topk":
            return self.engine.submit_topk(k)
        return self.engine.submit_heavy_hitters(threshold)

    def flush(self) -> None:
        self.engine.flush()

    @staticmethod
    def result(handle):
        if not handle.done:
            return None
        return handle.items, handle.est

    def pool_sizes(self) -> List[int]:
        return [int(c.shape[0]) for c in self.backend.candidates()]

    def drain(self) -> None:
        """Wait until the device has finished every block ingested."""
        self.engine.drain()
        for a in self._jax.live_arrays():
            a.block_until_ready()

    def tables(self) -> List[List[np.ndarray]]:
        """Every level's served table, once per replica that holds it."""
        self.engine.sync()
        st = self.backend.state
        st = st() if callable(st) else st
        return [[np.asarray(s.data) for s in level.table.addressable_shards]
                for level in st.states]
