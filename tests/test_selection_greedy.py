"""Thm 4/5 sigma-selection and Algorithm 1 greedy search."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sketch as sk
from repro.core.greedy import greedy_config
from repro.core.exhaustive import exhaustive_config, observed_error
from repro.core.selection import choose_sketch
from repro.streams import ipv4_stream, reinterpret_modularity, zipf_graph_stream


def _error_of(spec, stream, key, k=300):
    state = sk.build_sketch(spec, key, stream.items, stream.freqs)
    qi, qf = stream.top_k_queries(k)
    est = np.asarray(sk.query_jit(spec, state, jnp.asarray(qi)))
    return observed_error(est, qf)


def test_selection_picks_lower_error_sketch():
    """The sigma criterion (Thm 4/5) must agree with actual observed error."""
    stream = ipv4_stream(n_src_hosts=20_000, n_tgt_hosts=2_000, n_pairs=80_000,
                         n_occurrences=1_500_000, seed=4)
    rng = np.random.default_rng(0)
    s_items, s_freqs = stream.sample(0.03, rng)
    h, w = 4096, 5
    key = jax.random.PRNGKey(1)
    res = choose_sketch(s_items, s_freqs, stream.schema, h, w, key)
    errs = {
        "count-min": _error_of(sk.count_min_spec(stream.schema, h, w), stream, key),
        "mod-sketch": _error_of(
            sk.mod_sketch_spec(stream.schema, [(0,), (1,)],
                               res.mod_ranges, w), stream, key),
    }
    assert res.choice == min(errs, key=errs.get)


def test_selection_sigma_sample_invariance():
    """Thm 5: the sigma ordering is stable across sample rates."""
    stream = ipv4_stream(n_src_hosts=10_000, n_tgt_hosts=1_000, n_pairs=50_000,
                         n_occurrences=800_000, seed=9)
    rng = np.random.default_rng(1)
    key = jax.random.PRNGKey(7)
    choices = []
    for frac in (0.02, 0.04, 0.08):
        s_items, s_freqs = stream.sample(frac, rng)
        res = choose_sketch(s_items, s_freqs, stream.schema, 4096, 5, key)
        choices.append(res.choice)
    assert len(set(choices)) == 1


def test_greedy_candidate_count_quadratic():
    """Algorithm 1 scores at most sum_j (n-j+1) = O(n^2) candidates,
    far below T(n) (paper SV-B2)."""
    base = ipv4_stream(n_src_hosts=3000, n_tgt_hosts=400, n_pairs=20_000,
                       n_occurrences=200_000, seed=2)
    stream = reinterpret_modularity(base, 4)
    rng = np.random.default_rng(0)
    s_items, s_freqs = stream.sample(0.05, rng)
    res = greedy_config(s_items, s_freqs, stream.schema, 4096, 4,
                        jax.random.PRNGKey(0))
    n = 4
    assert res.n_candidates <= sum(n - j for j in range(n)) + n  # <= O(n^2)
    assert res.n_candidates < 15  # T(4) = 15: strictly fewer than exhaustive
    assert sum(1 for t in res.trace if t.chosen) >= 1
    # final spec covers all modules with valid ranges
    assert sorted(m for g in res.spec.partition for m in g) == list(range(n))


def test_greedy_beats_equal_sketch_mod4():
    base = ipv4_stream(n_src_hosts=8000, n_tgt_hosts=800, n_pairs=60_000,
                       n_occurrences=1_000_000, seed=0)
    stream = reinterpret_modularity(base, 4)
    rng = np.random.default_rng(0)
    s_items, s_freqs = stream.sample(0.03, rng)
    h, w = 4096, 5
    key = jax.random.PRNGKey(11)
    res = greedy_config(s_items, s_freqs, stream.schema, h, w, key)
    err_greedy = _error_of(res.spec, stream, key)
    err_equal = _error_of(sk.equal_sketch_spec(stream.schema, h, w), stream, key)
    assert err_greedy < err_equal


def test_exhaustive_refuses_large_modularity():
    stream = reinterpret_modularity(
        ipv4_stream(n_src_hosts=100, n_tgt_hosts=50, n_pairs=500,
                    n_occurrences=2000, seed=1), 8)
    with pytest.raises(ValueError, match="100 hours"):
        exhaustive_config(stream.items, stream.freqs, stream.schema, 256, 3,
                          jax.random.PRNGKey(0))


def test_exhaustive_at_least_as_good_as_greedy_mod3():
    rng = np.random.default_rng(5)
    src = rng.integers(0, 30, size=30_000).astype(np.uint32)
    mid = rng.integers(0, 300, size=30_000).astype(np.uint32)
    tgt = rng.integers(0, 3000, size=30_000).astype(np.uint32)
    items = np.stack([src, mid, tgt], axis=1)
    from repro.core.hashing import KeySchema
    from repro.streams.synthetic import Stream
    uniq, inv = np.unique(items, axis=0, return_inverse=True)
    freqs = np.bincount(inv).astype(np.int64)
    stream = Stream(schema=KeySchema(domains=(32, 512, 4096)), items=uniq,
                    freqs=freqs)
    s_items, s_freqs = stream.sample(0.1, rng)
    key = jax.random.PRNGKey(3)
    ex = exhaustive_config(s_items, s_freqs, stream.schema, 1024, 4, key)
    gr = greedy_config(s_items, s_freqs, stream.schema, 1024, 4, key)
    err_ex = _error_of(ex.spec, stream, key)
    err_gr = _error_of(gr.spec, stream, key)
    assert err_ex <= err_gr * 1.35 + 0.02   # greedy close to exhaustive


# --------------------------------------------------------------------------
# Live-stats faithfulness: the online re-search equals the offline search
# when the proxy sample is exact (streams/livestats.py contract)
# --------------------------------------------------------------------------

def _small_keyspace_endpoint(seed):
    """Keyspace engineered so the endpoint's live state is lossless: pools
    far under capacity (every group value admitted) and level tables so
    sparse that no key pair collides in all rows -- the proxy sample from
    the descent is then the exact compressed stream."""
    from repro.core.hashing import KeySchema
    from repro.serving.engine import SketchTopKEndpoint

    rng = np.random.default_rng(seed)
    n = 3000
    src = rng.integers(0, 4, size=n).astype(np.uint32)
    mid = ((src * 2 + rng.integers(0, 3, size=n)) % 8).astype(np.uint32)
    tgt = (rng.zipf(1.6, size=n) % 12).astype(np.uint32)
    uniq, inv = np.unique(np.stack([src, mid, tgt], axis=1), axis=0,
                          return_inverse=True)
    freqs = np.bincount(inv).astype(np.int64)
    schema = KeySchema(domains=(4, 8, 16))
    spec = sk.mod_sketch_spec(schema, [(0,), (1,), (2,)], (16, 16, 16), 5)
    ep = SketchTopKEndpoint(spec, jax.random.PRNGKey(0))
    ep.ingest(uniq, freqs)
    return ep, schema, uniq, freqs


@pytest.mark.parametrize("seed", [7, 11])
def test_live_stats_proxy_sample_is_exact_on_small_keyspace(seed):
    from repro.streams import collect_live_stats, exact_marginals

    ep, schema, uniq, freqs = _small_keyspace_endpoint(seed)
    stats = collect_live_stats(ep, k=len(uniq) + 32, min_threshold=1)
    exact = {tuple(r): f for r, f in zip(uniq.tolist(), freqs.tolist())}
    got = {tuple(r): f for r, f in
           zip(stats.items.tolist(), stats.freqs.tolist())}
    assert got == exact               # no phantom keys, no inflated counts
    assert stats.total == int(freqs.sum())
    assert abs(stats.coverage - 1.0) < 1e-9
    # per-group marginal mass off the level tables == exact marginals
    for j in range(schema.modularity):
        per_row = exact_marginals(uniq, freqs, [j])  # O(v_j, *) per row
        exact_m = {int(v): int(m) for v, m in
                   zip(uniq[:, j].tolist(), per_row.tolist())}
        live = {int(v): int(m) for v, m in
                zip(stats.group_values[j][:, 0].tolist(),
                    stats.group_mass[j].tolist())}
        assert live == exact_m


@pytest.mark.parametrize("seed", [7, 11])
def test_live_propose_spec_matches_offline_greedy_and_exhaustive(seed):
    """With an exact proxy sample, the online re-search IS the offline
    search: propose_spec == greedy_config bitwise (partition + ranges).
    Against exhaustive_config it is held to the objective: greedy's
    configuration is one of exhaustive's candidates, so scored under
    exhaustive's own protocol (same sample, queries and hash draw) its
    observed error is >= the exhaustive minimum, and it stays within 2x
    of that minimum."""
    from repro.core.exhaustive import _range_candidates
    from repro.core.partition import all_partitions
    from repro.core.range_opt import aggregate_sample
    from repro.streams import collect_live_stats, propose_spec

    ep, schema, uniq, freqs = _small_keyspace_endpoint(seed)
    stats = collect_live_stats(ep, k=len(uniq) + 32, min_threshold=1)
    key = jax.random.PRNGKey(3)
    for h in (64, 256):
        live = propose_spec(stats, h, 4, key)
        off = greedy_config(uniq, freqs, schema, h, 4, key)
        assert live.spec.partition == off.spec.partition
        assert live.spec.ranges == off.spec.ranges
        assert live.spec.width == off.spec.width
    ex = exhaustive_config(uniq, freqs, schema, 64, 4, key)
    live64 = propose_spec(stats, 64, 4, key)
    u, f = aggregate_sample(uniq, freqs)
    top = np.argsort(-f)[:200]
    greedy_err = None
    for pi, part in enumerate(all_partitions(range(schema.modularity))):
        for ri, ranges in enumerate(_range_candidates(
                len(part), 64.0, u, f, [list(g) for g in part], 9)):
            if (tuple(map(tuple, part)) == live64.spec.partition
                    and tuple(ranges) == live64.spec.ranges):
                state = sk.build_sketch(
                    live64.spec, jax.random.fold_in(key, 7919 * pi + ri),
                    u, f)
                est = np.asarray(sk.query_jit(
                    live64.spec, state, np.asarray(u[top], np.uint32)))
                greedy_err = observed_error(est, f[top])
    assert greedy_err is not None, "greedy config outside exhaustive's set"
    assert ex.error <= greedy_err <= 2.0 * ex.error, (ex.error, greedy_err)


def test_live_propose_spec_range_only_matches_recursive_ranges():
    from repro.core.range_opt import recursive_ranges
    from repro.streams import collect_live_stats, propose_spec

    ep, schema, uniq, freqs = _small_keyspace_endpoint(7)
    stats = collect_live_stats(ep, k=len(uniq) + 32, min_threshold=1)
    part = ((0,), (1,), (2,))
    live = propose_spec(stats, 256, 4, jax.random.PRNGKey(3), partition=part)
    assert live.spec.partition == part
    assert live.spec.ranges == recursive_ranges(uniq, freqs, part, 256.0)
