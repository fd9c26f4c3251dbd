"""Space-saving summary unit tests (core/summary.py)."""
import numpy as np
import pytest

from repro.core.summary import SpaceSaving


def _rows(*vals):
    return np.asarray(vals, dtype=np.uint32).reshape(-1, 1)


def test_late_heavy_value_evicts_lightest():
    s = SpaceSaving(capacity=3, n_cols=1)
    s.offer(_rows(1, 2, 3), np.array([5, 1, 4]))
    s.offer(_rows(9), np.array([100]))
    got = set(s.values()[:, 0].tolist())
    assert got == {1, 3, 9}           # 2 (count 1) evicted
    # inherited floor keeps the overestimate property
    assert s.counts()[(9,)] == 101


def test_counts_only_overestimate_and_wm_bound():
    rng = np.random.default_rng(0)
    vals = rng.integers(0, 50, size=2000).astype(np.uint32).reshape(-1, 1)
    freqs = rng.integers(1, 10, size=2000)
    s = SpaceSaving(capacity=16, n_cols=1)
    for i in range(0, 2000, 100):
        s.offer(vals[i : i + 100], freqs[i : i + 100])
    true = {}
    for v, f in zip(vals[:, 0].tolist(), freqs.tolist()):
        true[v] = true.get(v, 0) + int(f)
    w = sum(true.values())
    for row, c in s.counts().items():
        assert c >= true.get(row[0], 0)               # overestimate only
        assert c - true.get(row[0], 0) <= w / 16 + 1  # inherited error bound


def test_fractional_weights_admit():
    """Regression: int64-truncated totals dropped every sub-1.0 weight, so
    f32 gradient streams never populated the candidate pools."""
    s = SpaceSaving(capacity=4, n_cols=1)
    s.offer(_rows(1, 2, 3), np.array([0.5, 0.9, 0.4], np.float32))
    assert len(s) == 3
    assert s.counts()[(2,)] == pytest.approx(0.9)
    # zero-weight pad rows still stay out
    s.offer(_rows(7), np.array([0.0]))
    assert (7,) not in s.counts()


def test_merge_absent_rows_get_min_count_floor():
    """Regression: merge must substitute a full side's min count for absent
    rows (the mergeable-summaries rule) -- contributing 0 instead broke
    count(v) >= true(v) for rows evicted on one shard, so a globally heavy
    value could be out-ranked by light survivors after merge_from."""
    a = SpaceSaving(capacity=2, n_cols=1)
    b = SpaceSaving(capacity=2, n_cols=1)
    # v=7 (weight 10 per shard) is evicted on both shards by weight-12 rows
    a.offer(_rows(7), np.array([10]))
    a.offer(_rows(1, 2), np.array([12, 12]))
    b.offer(_rows(7), np.array([10]))
    b.offer(_rows(3, 4), np.array([12, 12]))
    m_a = min(a.counts().values())
    m_b = min(b.counts().values())
    a.merge_from(b)
    # every retained count includes the other side's floor, so it still
    # upper-bounds the true weight of ANY row, including evicted v=7
    for row, c in a.counts().items():
        assert c >= m_a + m_b >= 20  # true(7) = 20 stays dominated
    # under-capacity sides add no floor (absent there means truly unseen)
    c2 = SpaceSaving(capacity=4, n_cols=1)
    c2.offer(_rows(1), np.array([12]))
    d = SpaceSaving(capacity=3, n_cols=1)
    d.offer(_rows(8, 9), np.array([5, 6]))
    d.merge_from(c2)
    assert d.counts()[(8,)] == 5 and d.counts()[(1,)] == 12
    e = SpaceSaving(capacity=1, n_cols=1)
    e.offer(_rows(5), np.array([9]))
    d2 = SpaceSaving(capacity=3, n_cols=1)
    d2.offer(_rows(8, 9), np.array([5, 6]))
    d2.merge_from(e)  # e is full with min 9: rows absent from e get +9
    assert d2.counts()[(8,)] == 5 + 9 and d2.counts()[(9,)] == 6 + 9
    assert d2.counts()[(5,)] == 9  # d2 under capacity: no floor from d2


def test_merge_keeps_heavy_from_both_shards():
    a = SpaceSaving(capacity=3, n_cols=1)
    b = SpaceSaving(capacity=3, n_cols=1)
    a.offer(_rows(1, 2, 3), np.array([50, 1, 2]))
    b.offer(_rows(4, 5, 2), np.array([60, 1, 1]))
    a.merge_from(b)
    got = set(a.values()[:, 0].tolist())
    assert {1, 4} <= got and len(a) == 3
    # eviction after a merge still works (heap rebuilt over merged counts)
    a.offer(_rows(8), np.array([500]))
    assert 8 in set(a.values()[:, 0].tolist())
    with pytest.raises(ValueError, match="widths"):
        a.merge_from(SpaceSaving(capacity=3, n_cols=2))


def test_multiway_fold_overestimates_and_admits_heavy():
    """SpaceSaving.fold across shards (the sharded serving candidate-pool
    sync): counts keep upper-bounding true weights and any value past the
    W/m admission bound survives, however the stream was split."""
    rng = np.random.default_rng(7)
    n = 400
    vals = rng.integers(0, 60, size=n).astype(np.uint32).reshape(-1, 1)
    freqs = rng.integers(1, 6, size=n).astype(np.int64)
    # one globally heavy value spread evenly across shards
    vals[::8] = 99
    freqs[::8] = 10
    true = {}
    for v, f in zip(vals[:, 0].tolist(), freqs.tolist()):
        true[v] = true.get(v, 0) + int(f)
    w_total = sum(true.values())
    m = 16
    assert true[99] > w_total / m  # past the admission bound

    for n_shards in (2, 4):
        bounds = np.linspace(0, n, n_shards + 1).astype(int)
        shards = []
        for s, e in zip(bounds[:-1], bounds[1:]):
            p = SpaceSaving(capacity=m, n_cols=1)
            p.offer(vals[s:e], freqs[s:e])
            shards.append(p)
        folded = SpaceSaving.fold(shards)
        assert len(folded) <= m
        assert (99,) in folded.counts()                # admitted
        for row, c in folded.counts().items():
            assert c >= true.get(row[0], 0)            # overestimate only
        # fold == iterative merge_from (same cascade)
        it = SpaceSaving(capacity=m, n_cols=1)
        for p in shards:
            it.merge_from(p)
        assert folded.counts() == it.counts()


def test_fold_min_count_floor_accumulates_across_shards():
    """Rows absent from a full shard inherit that shard's min-count floor,
    and the floors add up across a multi-way fold -- so a value evicted on
    every shard still cannot out-rank the retained overestimates."""
    shards = []
    for base in (0, 10, 20):
        p = SpaceSaving(capacity=2, n_cols=1)
        p.offer(_rows(7), np.array([4]))               # evicted below
        p.offer(_rows(base + 1, base + 2), np.array([6, 5]))
        shards.append(p)
    floors = [min(p.counts().values()) for p in shards]
    folded = SpaceSaving.fold(shards)
    # every retained count >= the sum of the other shards' floors + its own
    # observed mass; in particular >= true(7) = 12 for any retained row
    for row, c in folded.counts().items():
        assert c >= sum(floors) - max(floors) + 5
    # under-capacity folds are exact unions: no floors, no truncation
    a = SpaceSaving(capacity=8, n_cols=1)
    b = SpaceSaving(capacity=8, n_cols=1)
    a.offer(_rows(1, 2), np.array([3, 4]))
    b.offer(_rows(2, 3), np.array([5, 6]))
    u = SpaceSaving.fold([a, b])
    assert u.counts() == {(1,): 3, (2,): 9, (3,): 6}


def test_fold_validation():
    with pytest.raises(ValueError, match="at least one"):
        SpaceSaving.fold([])
    with pytest.raises(ValueError, match="widths"):
        SpaceSaving.fold([SpaceSaving(capacity=2, n_cols=1),
                          SpaceSaving(capacity=2, n_cols=2)])


def test_lazy_heap_stays_bounded():
    """Regression: repeated increments of resident rows once grew the lazy
    heap without bound.  Storage is now the resident rows alone, and
    eviction still finds the minimum."""
    s = SpaceSaving(capacity=8, n_cols=1)
    hot = _rows(1, 2, 3)
    for _ in range(200):
        s.offer(hot, np.array([1, 1, 1]))
    assert len(s) == 3
    assert all(v.shape[0] == 3 for v in s.state_dict().values())
    assert s.counts()[(1,)] == 200
    s.offer(_rows(4, 5, 6, 7, 8), np.ones(5))
    assert len(s) == s.capacity
    s.offer(_rows(9), np.array([50]))
    # the minimum (count 1) goes, its tie broken by the lowest key
    assert set(s.values()[:, 0].tolist()) == {1, 2, 3, 5, 6, 7, 8, 9}
    assert s.counts()[(9,)] == 1 + 50


def _zipf_rows(rng, n, n_cols):
    """n rows of width n_cols, Zipf-distributed, weights 1-4."""
    z = rng.zipf(1.1, size=n).astype(np.uint64) & np.uint64(0xFFFFFFFF)
    cols = [(z * np.uint64(2654435761 * j + 1)) & np.uint64(0xFFFFFFFF)
            for j in range(n_cols)]
    rows = np.stack(cols, axis=1).astype(np.uint32)
    return rows, rng.integers(1, 5, size=n)


@pytest.mark.parametrize("n_cols", [1, 2, 3])
@pytest.mark.parametrize("capacity", [8, 1 << 10, 1 << 16])
def test_block_rule_keeps_the_space_saving_guarantees(capacity, n_cols):
    """count >= true, count - true <= W/m, count - err <= true, and every
    row with true > W/m kept, over blocks that evict."""
    rng = np.random.default_rng(capacity + n_cols)
    n = max(5 * capacity, 4096)
    rows, freqs = _zipf_rows(rng, n, n_cols)
    rows[::5] = rows[0]  # one row past W/m even at capacity 8
    s = SpaceSaving(capacity=capacity, n_cols=n_cols)
    for i in range(0, n, 4096):
        s.offer(rows[i:i + 4096], freqs[i:i + 4096])
    uniq, inv = np.unique(rows, axis=0, return_inverse=True)
    true = np.bincount(inv.reshape(-1), weights=freqs)
    assert uniq.shape[0] > capacity  # the pool had to evict
    w_over_m = freqs.sum() / capacity
    truth = dict(zip(map(tuple, uniq.tolist()), true.tolist()))
    sd = s.state_dict()
    assert len(s) == capacity
    t = np.asarray([truth[r] for r in map(tuple, sd["rows"].tolist())])
    assert np.all(sd["counts"] >= t)
    assert np.all(sd["counts"] - t <= w_over_m)
    assert np.all(sd["counts"] - sd["errs"] <= t)
    heavy = {r for r, c in truth.items() if c > w_over_m}
    assert heavy and heavy <= set(map(tuple, sd["rows"].tolist()))


@pytest.mark.parametrize("n_cols", [1, 2, 3])
def test_offer_is_merge_of_the_blocks_exact_summary(n_cols):
    rng = np.random.default_rng(5)
    rows, freqs = _zipf_rows(rng, 2000, n_cols)
    freqs[::9] = 0  # zero-weight pad rows stay out on both paths
    pool = SpaceSaving(capacity=64, n_cols=n_cols)
    pool.offer(rows[:1500], freqs[:1500])
    assert len(pool) == pool.capacity
    block, bfreqs = rows[1500:], freqs[1500:]
    # under capacity, so exact: floor 0, no row dropped
    exact = SpaceSaving(capacity=block.shape[0] + 1, n_cols=n_cols)
    exact.offer(block, bfreqs)
    merged = SpaceSaving.fold([pool])
    merged.merge_from(exact)
    pool.offer(block, bfreqs)
    for k, v in pool.state_dict().items():
        np.testing.assert_array_equal(v, merged.state_dict()[k])


def test_state_round_trip_is_bit_exact_after_evictions():
    rng = np.random.default_rng(9)
    rows, freqs = _zipf_rows(rng, 6000, 2)
    freqs = freqs * 0.37  # fractional counts: bits, not just values
    a = SpaceSaving(capacity=48, n_cols=2)
    for i in range(0, 3000, 500):
        a.offer(rows[i:i + 500], freqs[i:i + 500])
    sd = a.state_dict()
    b = SpaceSaving(capacity=48, n_cols=2)
    b.load_state(**sd)
    for s in (a, b):
        for i in range(3000, 6000, 500):
            s.offer(rows[i:i + 500], freqs[i:i + 500])
    sa, sb = a.state_dict(), b.state_dict()
    for k in sa:
        assert sa[k].dtype == sb[k].dtype
        np.testing.assert_array_equal(sa[k], sb[k])
    # a restore in any row order lands in key order; repeats are refused
    perm = rng.permutation(len(a))
    c = SpaceSaving(capacity=48, n_cols=2)
    c.load_state(sa["rows"][perm], sa["counts"][perm], sa["errs"][perm])
    assert c.counts() == a.counts()
    np.testing.assert_array_equal(c.values(), sa["rows"])
    with pytest.raises(ValueError, match="repeats"):
        c.load_state(sa["rows"][[0, 0]], sa["counts"][:2], sa["errs"][:2])


def test_validation():
    with pytest.raises(ValueError):
        SpaceSaving(capacity=0, n_cols=1)
    with pytest.raises(ValueError, match="n_cols"):
        SpaceSaving(capacity=2, n_cols=0)
    s = SpaceSaving(capacity=2, n_cols=2)
    with pytest.raises(ValueError, match="\\[N, 2\\]"):
        s.offer(np.zeros((3, 1), np.uint32))
