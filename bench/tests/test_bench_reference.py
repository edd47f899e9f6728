"""The copied SCI/CUR generator, the plain reference and the clients'
seeded draws, at a small size against fixed counts."""
import numpy as np
import pytest

from bench import scigen
from bench.reference import HostReference, Ranks, edit_table


@pytest.mark.parametrize("kind,records,edges,total,tips", [
    ("SCI", 2685, 30360, 1404102568, [59, 58, 57, 52, 45]),
    ("CUR", 2495, 41021, 1343413113, [59, 58]),
])
def test_generator_fixed_counts(kind, records, edges, total, tips):
    h = scigen.generate(kind, n_versions=60, inserts=30, n_branches=4,
                        n_attrs=6, seed=3)
    assert (h.n_versions, h.n_records, h.n_edges) == (60, records, edges)
    assert int(h.data.astype(np.int64).sum()) == total
    assert h.tips == tips
    assert all(np.all(np.diff(r) > 0) for r in h.rlists)
    assert h.parents[0] == () and all(p for p in h.parents[1:])
    assert np.array_equal(h.data[:, 0], np.arange(records))


def test_generator_refuses_unknown_kinds():
    with pytest.raises(ValueError):
        scigen.generate("XYZ")


def test_reference_checkout_and_commit():
    data = np.arange(40, dtype=np.int32).reshape(10, 4)
    ref = HostReference([np.array([1, 3, 5]), np.array([0, 9])], data)
    assert np.array_equal(ref.checkout(0), data[[1, 3, 5]])
    new = np.full((2, 4), -1, np.int32)
    vid = ref.commit(0, np.array([0, 2]), new)
    assert vid == 2 and ref.size(2) == 4
    assert np.array_equal(ref.checkout(2),
                          np.concatenate([data[[1, 5]], new]))
    assert ref.rlists[2].tolist() == [1, 5, 10, 11]
    ref.commit(2, np.array([3]), new[:1])
    assert ref.rlists[3].tolist() == [11, 12] and ref.n_records == 13


def test_edit_table_rates_and_fresh_rows():
    rng = np.random.default_rng(0)
    table = rng.integers(0, 1000, size=(500, 6), dtype=np.int32)
    table[:, 0] = np.arange(500)
    keep, new_rows, new_table = edit_table(
        rng, table, delete_frac=0.02, updates=20, inserts=30, next_pk=10**6)
    assert len(keep) == 500 - 10 - 20 and len(new_rows) == 50
    assert np.array_equal(new_table, np.concatenate([table[keep], new_rows]))
    old = {r.tobytes() for r in table}
    assert not any(r.tobytes() in old for r in new_rows)
    assert np.array_equal(new_rows[20:, 0], np.arange(10**6, 10**6 + 30))
    upd_keys = set(new_rows[:20, 0].tolist())
    assert upd_keys <= set(table[:, 0].tolist()) - set(table[keep, 0].tolist())


def test_zipf_ranks_skew_toward_the_newest():
    ranks = Ranks({"dist": "zipf", "a": 1.2}, 1000)
    rng = np.random.default_rng(1)
    r = np.array([ranks.draw(rng) for _ in range(20000)])
    assert r.min() >= 0 and r.max() < 1000
    assert 0.21 < np.mean(r == 0) < 0.25
    assert 0.77 < np.mean(r < 64) < 0.81


def test_uniform_ranks_cover_every_version_alike():
    ranks, rng = Ranks({"dist": "uniform"}, 10), np.random.default_rng(2)
    counts = np.bincount([ranks.draw(rng) for _ in range(20000)],
                         minlength=10)
    assert len(counts) == 10 and counts.min() > 1800 and counts.max() < 2200
