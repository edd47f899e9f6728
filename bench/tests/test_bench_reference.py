"""The copied SCI/CUR generator, the plain reference and the clients'
seeded draws, at a small size against fixed counts."""
import numpy as np
import pytest

from bench import scigen
from bench.reference import HostReference, Ranks, edit_table, minted_ids


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


def _merge_fixture():
    """Two parents: record 1 in both; records 2 and 7 value-equal."""
    data = np.arange(40, dtype=np.int32).reshape(10, 4)
    data[7] = data[2]
    return data, HostReference([np.array([1, 2, 5]), np.array([1, 7, 8])],
                               data)


def test_a_merge_keeps_value_equal_records_and_a_shared_record_once():
    data, ref = _merge_fixture()
    new = np.full((2, 4), -1, np.int32)
    vid = ref.merge([0, 1], np.array([1, 2, 5, 7, 8]), new)
    assert vid == 2 and ref.rlists[2].tolist() == [1, 2, 5, 7, 8, 10, 11]
    assert np.array_equal(ref.checkout(2),
                          np.concatenate([data[[1, 2, 5, 7, 8]], new]))
    assert np.array_equal(ref.checkout(2)[1], ref.checkout(2)[3])


def test_a_merge_comes_out_in_rid_order_and_names_only_its_parents():
    _, ref = _merge_fixture()
    ref.commit(1, np.array([0, 2]), np.full((1, 4), -1, np.int32))  # vid 2
    assert ref.rlists[2].tolist() == [1, 8, 10]
    # the new record of vid 2 sorts after every pool record
    vid = ref.merge([0, 2], np.concatenate([[1, 2, 8], minted_ids(2, 1)]),
                    np.full((1, 4), -2, np.int32))
    assert ref.rlists[vid].tolist() == [1, 2, 8, 10, 11]
    assert np.all(np.diff(ref.rlists[vid]) > 0)
    with pytest.raises(ValueError):          # record 7 is in neither parent
        ref.merge([0, 2], np.array([1, 7]), np.zeros((0, 4), np.int32))
    with pytest.raises(ValueError):          # not in checkout order
        ref.merge([0, 2], np.array([8, 1]), np.zeros((0, 4), np.int32))
    with pytest.raises(KeyError):            # a commit not replayed
        ref.merge([0, 2], minted_ids(9, 1), np.zeros((0, 4), np.int32))


def test_a_single_parent_edit_after_a_merge_indexes_its_checkout_order():
    data, ref = _merge_fixture()
    new = np.full((1, 4), -1, np.int32)
    m = ref.merge([0, 1], np.array([1, 2, 5, 7, 8]), new)   # rids 1..8, 10
    child = ref.commit(m, np.array([1, 3, 5]), new + 5)
    assert ref.rlists[child].tolist() == [2, 7, 10, 11]
    assert np.array_equal(ref.checkout(child),
                          np.concatenate([data[[2, 7]], new, new + 5]))
    assert ref.rids_of(minted_ids(m, 1)).tolist() == [10]
    assert ref.rids_of(minted_ids(child, 1)).tolist() == [11]


def test_edit_table_on_value_equal_rows_keeps_the_first_and_adds_fresh():
    """After a merge a table may hold a key twice, even two equal rows:
    the kept copies of equal rows are the first in checkout order, and no
    updated row equals any row of the table."""
    table = np.zeros((40, 4), np.int32)
    table[:, 0] = np.arange(40) // 2          # every key twice
    table[:, 2] = np.arange(40) % 2           # rows 2i, 2i+1 differ by 1
    table[20:, 2] = 7                          # rows 2i, 2i+1 equal, i >= 10
    old = {r.tobytes() for r in table}
    for seed in range(30):
        keep, new_rows, new_table = edit_table(
            np.random.default_rng(seed), table, delete_frac=0.25,
            updates=6, inserts=2, next_pk=10**6)
        assert not any(r.tobytes() in old for r in new_rows)
        kept = set(keep.tolist())
        for i in range(20, 40, 2):            # an equal pair keeps its first
            assert (i + 1 not in kept) or (i in kept)
        assert len(keep) == 40 - 10 - 6
        assert np.array_equal(new_table,
                              np.concatenate([table[keep], new_rows]))


def test_edit_table_moves_an_update_past_every_row_under_its_key():
    table = np.zeros((999, 4), np.int32)      # key 0, third attribute 0..998
    table[:, 2] = np.arange(999)
    for seed in range(5):
        _, new_rows, _ = edit_table(np.random.default_rng(seed), table,
                                    delete_frac=0.0, updates=1, inserts=0,
                                    next_pk=10**6)
        assert new_rows.tolist() == [[0, 0, 999, 0]]


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
