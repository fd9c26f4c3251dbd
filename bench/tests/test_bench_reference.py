"""The plain reference: its hash and tables agree with the program's own jnp
path on a small stream, its cell values at any row count agree with a
fresh count, and the answer check finds each kind of wrong answer."""
import numpy as np
import pytest

from bench import reference, stream_gen

SKETCHES = [
    {"domains": [1 << 32, 1 << 32], "partition": [[0, 1]], "ranges": [4096],
     "width": 4, "dtype": "int32"},
    {"domains": [1 << 32, 1000, 1 << 16], "partition": [[1], [0, 2]],
     "ranges": [37, 300], "width": 3, "dtype": "int32"},
]


def _stream(n_mod, seed=3, n=3000):
    rng = np.random.default_rng(seed)
    items = np.stack([rng.integers(0, 1 << 32, n, dtype=np.uint64),
                      rng.integers(0, 1000, n, dtype=np.uint64),
                      rng.integers(0, 1 << 16, n, dtype=np.uint64)],
                     axis=1)[:, :n_mod].astype(np.uint32)
    items = np.unique(items, axis=0)
    freqs = rng.integers(1, 5000, items.shape[0]).astype(np.int64)
    return items, freqs


@pytest.mark.parametrize("sketch", SKETCHES)
def test_reference_tables_match_the_program_jnp_hierarchy(sketch):
    import jax.numpy as jnp
    from repro.core import hierarchy as hh
    from repro.core import sketch as sk
    from repro.core.hashing import KeySchema

    items, freqs = _stream(len(sketch["domains"]))
    q, r = reference.draw_hash_params(
        stream_gen.rng_for(1, 2), sketch["width"],
        reference.total_chunks(sketch), len(sketch["partition"]))
    spec = sk.mod_sketch_spec(KeySchema(domains=tuple(sketch["domains"])),
                              sketch["partition"], sketch["ranges"],
                              sketch["width"])
    hspec = hh.HierarchySpec.from_spec(spec)
    base = sk.SketchParams(q=jnp.asarray(q), r=jnp.asarray(r))
    state = hh.HierarchyState(states=tuple(
        sk.SketchState(params=hh.level_params(hspec, base, L),
                       table=jnp.zeros((lv.width, lv.table_size), jnp.int32))
        for L, lv in enumerate(hspec.levels)))
    state = hh.update(hspec, state, jnp.asarray(items), jnp.asarray(freqs))
    ref = reference.Reference(items, freqs, sketch, q, r)
    for got, want in zip(state.states, ref.tables(items.shape[0])):
        assert np.array_equal(np.asarray(got.table), want)


def test_cell_values_at_any_row_count_match_a_fresh_count():
    sketch = SKETCHES[1]
    items, freqs = _stream(3, n=500)
    q, r = reference.draw_hash_params(stream_gen.rng_for(2, 2), 3, 5, 2)
    ref = reference.Reference(items, freqs, sketch, q, r)
    n = items.shape[0]
    assert all(not t.any() for t in ref.tables(0))
    probe = items[:50]
    cells = reference.level_cells(probe, sketch, q, r)[-1]
    for rows in (1, 17, n, n + 5, 3 * n + 11):
        reps = np.arange(rows) % n
        fresh = reference.Reference(items[reps], freqs[reps], sketch, q, r)
        tables = ref.tables(rows)
        for lv, want in zip(tables, fresh.tables(rows)):
            assert np.array_equal(lv, want)
        assert ref.mass_at(rows) == int(freqs[reps].sum())
        want = np.min(tables[-1][np.arange(3)[:, None], cells], axis=0)
        assert np.array_equal(ref.estimates(probe, rows), want)


def _ref():
    sketch = SKETCHES[0]
    items, freqs = _stream(2, n=4000)
    freqs[:5] = [900_000, 800_000, 700_000, 600_000, 500_000]
    q, r = reference.draw_hash_params(stream_gen.rng_for(4, 2), 4, 4, 1)
    return reference.Reference(items, freqs, sketch, q, r)


def _answer(ref, kind, rows, k=0, threshold=0):
    est = ref.estimates(ref.items, rows)
    order = np.argsort(-est, kind="stable")
    keep = order[:k] if kind == "topk" else order[est[order] >= threshold]
    return reference.Answer(kind=kind, k=k, threshold=threshold, rows=rows,
                            items=ref.items[keep], est=est[keep])


def test_the_answer_check_passes_true_answers_and_finds_wrong_ones():
    ref = _ref()
    rows = ref.n + 100
    hh = _answer(ref, "heavy_hitters", rows, threshold=1_100_000)
    top = _answer(ref, "topk", rows, k=3)
    assert len(hh.est) == 4 and len(top.est) == 3
    ok = reference.check_answers(ref, [hh, top], capacity=1024)
    assert ok == {"estimates_wrong": 0, "heavy_missed": 0,
                  "answers_malformed": 0}

    missing = reference.Answer(**{**hh.__dict__, "items": hh.items[1:],
                                  "est": hh.est[1:]})
    assert reference.check_answers(ref, [missing], 1024)["heavy_missed"] == 1
    off = reference.Answer(**{**top.__dict__, "est": top.est + 1})
    assert reference.check_answers(ref, [off], 1024)["estimates_wrong"] == 3
    stale = reference.Answer(**{**top.__dict__, "rows": 10})
    assert reference.check_answers(ref, [stale], 1024)["estimates_wrong"] > 0
    flipped = reference.Answer(**{**top.__dict__, "items": top.items[::-1],
                                  "est": top.est[::-1]})
    assert reference.check_answers(ref, [flipped], 1024)[
        "answers_malformed"] == 1
    short = reference.Answer(**{**top.__dict__, "items": top.items[:2],
                                "est": top.est[:2]})
    got = reference.check_answers(ref, [short], 1024)
    assert got["answers_malformed"] == 1 and got["heavy_missed"] >= 1


def test_the_table_check_counts_wrong_cells_and_missing_replicas():
    ref = _ref()
    want = ref.tables(ref.n)
    assert reference.check_tables(ref, ref.n, [[want[0]] * 2], 2) == 0
    bad = want[0].copy()
    bad[1, 7] += 1
    assert reference.check_tables(ref, ref.n, [[want[0], bad]], 2) == 1
    assert reference.check_tables(ref, ref.n, [[want[0]]], 2) == want[0].size
