"""(src IPv4, dst IPv4) flow keys with packet counts.

A synthetic population, not a trace's: Zipf-skewed source and destination
hosts drawn into pairs, and a multinomial packet count over the distinct
pairs -- the draws of ``repro.streams.synthetic.ipv4_stream``,
de-duplicated on packed 64-bit keys.
"""
import numpy as np


def _zipf(n_distinct: int, n_draws: int, s: float,
          rng: np.random.Generator) -> np.ndarray:
    p = np.arange(1, n_distinct + 1, dtype=np.float64) ** (-s)
    p /= p.sum()
    return rng.choice(n_distinct, size=n_draws, p=p)


def generate(rng: np.random.Generator, *, n_src_hosts: int,
             n_dst_hosts: int, n_pair_draws: int, n_packets: int,
             zipf_src: float, zipf_dst: float):
    """(src, dst) pairs and packet counts: (uint32[n, 2], int64[n])."""
    src_hosts = rng.integers(0, 1 << 32, size=n_src_hosts,
                             dtype=np.uint64).astype(np.uint32)
    dst_hosts = rng.integers(0, 1 << 32, size=n_dst_hosts,
                             dtype=np.uint64).astype(np.uint32)
    src = src_hosts[_zipf(n_src_hosts, n_pair_draws, zipf_src, rng)]
    dst = dst_hosts[_zipf(n_dst_hosts, n_pair_draws, zipf_dst, rng)]
    packed = (src.astype(np.uint64) << np.uint64(32)) | dst.astype(np.uint64)
    uniq, mult = np.unique(packed, return_counts=True)
    freqs = rng.multinomial(n_packets, mult / mult.sum()).astype(np.int64)
    keep = freqs > 0
    uniq = uniq[keep]
    items = np.stack([(uniq >> np.uint64(32)).astype(np.uint32),
                      (uniq & np.uint64(0xFFFFFFFF)).astype(np.uint32)],
                     axis=1)
    return items, freqs[keep]
