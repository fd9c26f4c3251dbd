"""The seeded inputs: streams, schedules and hash draws."""
import numpy as np
import pytest

from bench import reference, stream_gen
from bench.traffic.generator import make_schedule

CFG = dict(generator="ipv4_pairs", n_src_hosts=5000, n_dst_hosts=100,
           n_pair_draws=8000, n_packets=1 << 20, zipf_src=1.2, zipf_dst=0.8,
           block_rows=256)
POISSON = {"ingest": {"mode": "rate", "rows_per_s": 1000.0},
           "requests": {"mode": "poisson", "rate_per_s": 7.0, "mix": [
               {"kind": "heavy_hitters", "phi": 1e-4, "share": 0.25},
               {"kind": "heavy_hitters", "phi": 1e-3, "share": 0.25},
               {"kind": "topk", "k": 10, "share": 0.25},
               {"kind": "topk", "k": 100, "share": 0.25}]},
           "max_flush_requests": 4}


@pytest.mark.parametrize("seed", [0, 2**31 + 12345, 3 * 2**40])
def test_stream_is_a_function_of_the_seed(seed):
    a = stream_gen.make_stream(CFG, seed)
    b = stream_gen.make_stream(CFG, seed)
    c = stream_gen.make_stream(CFG, seed + 1)
    assert np.array_equal(a.items, b.items) and np.array_equal(a.freqs, b.freqs)
    assert not np.array_equal(a.items[:100], c.items[:100])
    assert a.mass == CFG["n_packets"]
    packed = reference.pack_keys(a.items, [1 << 32, 1 << 32])
    assert np.unique(packed).size == a.n          # distinct keys


def test_blocks_replay_the_stream_cyclically():
    s = stream_gen.make_stream(CFG, 4)
    per_pass = -(-s.n // s.block_rows)
    rows = np.concatenate([s.block(j)[0] for j in range(2 * per_pass)])
    assert np.array_equal(rows[: s.n], s.items)
    assert np.array_equal(rows[s.n: 2 * s.n], s.items)
    assert s.rows_mass(s.n) == s.mass
    assert s.rows_mass(2 * s.n + 3) == 2 * s.mass + int(s.freqs[:3].sum())


def test_a_weight_over_the_configured_bound_is_refused():
    with pytest.raises(ValueError, match="bound"):
        stream_gen.make_stream(dict(CFG, max_weight=2), 1)


def test_poisson_schedule_is_seeded_and_carries_fixed_work():
    rng = lambda s: stream_gen.rng_for(s, 3)  # noqa: E731
    a = make_schedule(POISSON, rng(1), 20.0, 256)
    b = make_schedule(POISSON, rng(1), 20.0, 256)
    c = make_schedule(POISSON, rng(2), 20.0, 256)
    assert a == b
    assert len(a.requests) == len(c.requests) == 140
    kinds = lambda s: sorted(r.template() for r in s.requests)  # noqa: E731
    assert kinds(a) == kinds(c)                   # same work, another order
    assert [r.template() for r in a.requests] != [r.template()
                                                  for r in c.requests]
    dues = [r.due for r in a.requests]
    assert dues == sorted(dues) and 0 <= dues[0] and dues[-1] < 20.0
    assert a.block_due(4) == pytest.approx(4 * 256 / 1000.0)
    gaps = lambda s: np.sort(np.diff([0.0] + [r.due for r in s.requests]))  # noqa: E731
    assert not np.allclose(gaps(a), gaps(a)[0])   # not evenly spaced
    # the same gaps in another order (the last, to the close, is left out)
    both = np.intersect1d(np.round(gaps(a), 9), np.round(gaps(c), 9))
    assert both.size >= len(a.requests) - 2
    assert [r.due for r in a.requests] != [r.due for r in c.requests]


@pytest.mark.parametrize("mode", ["poisson", "spaced"])
@pytest.mark.parametrize("seed", [5, 2**33 + 7])
def test_each_kind_is_spread_over_the_window(mode, seed):
    mix = dict(POISSON, requests=dict(POISSON["requests"], mode=mode))
    s = make_schedule(mix, stream_gen.rng_for(seed, 3), 20.0, 256)
    kinds = [r.template() for r in s.requests]
    for t in set(kinds):
        at = [i for i, k in enumerate(kinds) if k == t]
        stretch = len(kinds) / len(at)
        # one request of the kind in each stretch of 1/count of the sequence
        assert [int(i // stretch) for i in at] == list(range(len(at)))


def test_spaced_schedule_is_evenly_spaced_and_the_same_for_every_seed():
    mix = dict(POISSON, requests=dict(POISSON["requests"], mode="spaced"))
    a = make_schedule(mix, stream_gen.rng_for(1, 3), 20.0, 256)
    c = make_schedule(mix, stream_gen.rng_for(2, 3), 20.0, 256)
    dues = np.array([r.due for r in a.requests])
    assert len(dues) == 140
    assert np.allclose(dues, 20.0 * np.arange(1, 141) / 141)
    assert np.array_equal(dues, [r.due for r in c.requests])
    order = [(m["kind"], int(m.get("k", 0)), float(m.get("phi", 0.0)))
             for m in mix["requests"]["mix"]]
    leads = set()
    for seed in range(8):
        kinds = [r.template() for r in make_schedule(
            mix, stream_gen.rng_for(seed, 3), 20.0, 256).requests]
        lead = order.index(kinds[0])
        # rounds of one each, in the mix's order from a seeded first kind
        assert kinds == [order[(lead + i) % 4] for i in range(140)]
        leads.add(lead)
    assert len(leads) > 1


def test_periodic_schedule_is_due_every_period_inside_the_window():
    mix = {"ingest": {"mode": "backlog"},
           "requests": {"mode": "periodic", "period_s": 1.0, "batch": [
               {"kind": "topk", "k": 100},
               {"kind": "heavy_hitters", "phi": 1e-4}]},
           "max_flush_requests": 2}
    s = make_schedule(mix, stream_gen.rng_for(0, 3), 3.5, 64)
    assert [r.due for r in s.requests] == [1.0, 1.0, 2.0, 2.0, 3.0, 3.0]
    assert s.rows_per_s is None and s.block_due(10) == 0.0


def test_hash_draw_is_seeded_and_in_range():
    q, r = reference.draw_hash_params(stream_gen.rng_for(9, 2), 4, 4, 1)
    q2, _ = reference.draw_hash_params(stream_gen.rng_for(9, 2), 4, 4, 1)
    assert q.shape == (4, 4) and r.shape == (4, 1)
    assert np.array_equal(q, q2)
    assert int(q.max()) < reference.P31 and int(r.max()) < reference.P31
