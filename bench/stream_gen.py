"""The benchmark's seeded key streams (generated here, never by the program).

A configuration's ``stream`` names its generator, ``bench/streams/<name>.py``,
whose ``generate(rng, **parameters)`` returns the distinct keys and their
weights.  The program receives only the generated blocks: the distinct
keys in a seeded order, replayed cyclically, ``block_rows`` at a time.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path

import numpy as np

from bench.catalog import BENCH_DIR, load_module

STREAMS = BENCH_DIR / "streams"


@dataclasses.dataclass(frozen=True)
class KeyStream:
    """Distinct keys in ingest order, each with its weight (packets)."""
    items: np.ndarray        # uint32[n, n_modules]
    freqs: np.ndarray        # int64[n]
    block_rows: int

    @property
    def n(self) -> int:
        return int(self.freqs.shape[0])

    @property
    def mass(self) -> int:
        return int(self.freqs.sum())

    def block(self, j: int):
        """Rows ``[j * block_rows, (j + 1) * block_rows)`` of the cyclic
        replay of the stream."""
        idx = (np.arange(self.block_rows, dtype=np.int64)
               + j * self.block_rows) % self.n
        return self.items[idx], self.freqs[idx]

    def rows_mass(self, rows: int) -> int:
        """Weight carried by the first ``rows`` rows of the cyclic replay."""
        passes, rem = divmod(int(rows), self.n)
        return passes * self.mass + int(self.freqs[:rem].sum())


def rng_for(seed: int, stream: int = 0) -> np.random.Generator:
    """A generator for ``seed`` (any whole number) and a sub-stream id."""
    return np.random.default_rng([int(seed) % (1 << 63), stream])


def make_stream(stream_cfg: dict, seed: int, streams=STREAMS) -> KeyStream:
    """The configuration's stream for ``seed``, in its seeded ingest order;
    ``streams`` is the directory of the generators."""
    params = dict(stream_cfg)
    kind = params.pop("generator")
    block_rows = int(params.pop("block_rows"))
    max_weight = params.pop("max_weight", None)
    generate = load_module(Path(streams) / f"{kind}.py").generate
    items, freqs = generate(rng_for(seed, 0), **params)
    if max_weight is not None and int(freqs.max()) >= int(max_weight):
        raise ValueError(f"seed {seed} draws a row weight {int(freqs.max())} "
                         f">= the configured bound {max_weight}")
    order = rng_for(seed, 1).permutation(freqs.shape[0])
    return KeyStream(items=items[order], freqs=freqs[order],
                     block_rows=block_rows)
