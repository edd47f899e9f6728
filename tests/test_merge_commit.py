"""Merge commits through ``PartitionedCVD.commit_many`` and the served
path, against a plain reference.

A table-form commit names ``parent``, or ``parents`` for a merge, and its
table is matched against the union of its parents' records as a multiset
(``datamodels.match_records``): each record is used at most once, value-
equal records (twins) give their lowest rids first, and unmatched rows
become new records in table order.  The reference below writes that out
as a loop over plain Python lists; it imports nothing of the program.
The store is held to it at a tiny CUR size: merges across partitions,
merges naming a parent staged in the same wave, twins, single-parent
edits of a merged version; every version checked out through
``BatchedCheckoutServer`` on the host tier and on the kernel tier, with
the pinned superblock (whole store, and partition groups) extended in
place and compared byte for byte with a fresh build.  The journal replays
a merge bit-identically, the commit log is ``to_tree``'s tree over the
journaled parents, and a malformed ``parents`` fails in staging with the
store and the journal untouched."""
import numpy as np
import pytest

from repro.core import generate
from repro.core.checkout import (build_superblock, checkout_partitioned,
                                 estimate_superblock_bytes,
                                 get_superblock_groups, peek_superblock)
from repro.core.datamodels import diff_against_parents, match_records
from repro.core.journal import (Journal, attach_journal, read_records,
                                replay_into)
from repro.core.online import RepartitionTrigger
from repro.core.partition import PartitionedCVD
from repro.core.version_graph import VersionGraph, to_tree
from repro.serve.checkout import BatchedCheckoutServer

N_ATTRS = 6
PARTS = 3


# ------------------------------------------------------- plain reference --
def loop_match(table, rows, rids):
    """Each table row, in table order, takes the lowest unused rid whose
    row equals it; the rows no record is left for are new."""
    pool = sorted(zip((int(r) for r in rids), (tuple(x) for x in rows)))
    used, matched, new = set(), [], []
    for row in table:
        row = tuple(row)
        rid = next((r for r, x in pool if r not in used and x == row), None)
        if rid is None:
            new.append(row)
        else:
            used.add(rid)
            matched.append(rid)
    return matched, new


class PlainStore:
    """version v = ``pool[rlists[v]]``, rows in rid order; a commit matches
    its table against the union of its parents' records (``loop_match``)
    and appends its new rows to the pool, in table order."""

    def __init__(self, rlists, data):
        self.rlists = [sorted(int(r) for r in rl) for rl in rlists]
        self.pool = [tuple(int(x) for x in row) for row in data]

    def commit(self, parents, table):
        union = sorted(set().union(*(self.rlists[p] for p in parents)))
        matched, new = loop_match(table, [self.pool[r] for r in union],
                                  union)
        fresh = list(range(len(self.pool), len(self.pool) + len(new)))
        self.pool.extend(new)
        self.rlists.append(sorted(matched + fresh))
        return len(self.rlists) - 1

    def checkout(self, vid):
        return [self.pool[r] for r in self.rlists[vid]]


# ------------------------------------------------------------ the store --
def cur_store(seed, twins=False):
    """A tiny CUR history (merges in its own DAG) in ``PARTS`` partitions,
    v -> v % PARTS, so most pairs of versions live apart; the base
    version graph; and the reference over the same records.  ``twins``
    copies records only one of two versions holds onto records only the
    other holds: value-equal rows under different rids."""
    w = generate("CUR", n_versions=24, inserts=6, n_branches=4,
                 n_attrs=N_ATTRS, seed=seed)
    data = np.array(w.data)
    rlists = w.graph.rlists()
    if twins:
        a, b = set(rlists[-1]), set(rlists[-2])
        only_a, only_b = sorted(a - b), sorted(b - a)
        n = min(len(only_a), len(only_b), 4)
        assert n >= 2
        data[only_b[:n]] = data[only_a[:n]]
    from repro.core.graph import BipartiteGraph
    graph = BipartiteGraph.from_rlists(rlists, n_records=len(data))
    store = PartitionedCVD(graph, data,
                           np.arange(graph.n_versions) % PARTS)
    return store, w.vgraph, PlainStore(rlists, data)


def edit(rng, rows):
    """A curator's edit of ``rows`` (a version's checkout, or a union of
    several): some rows deleted, two updated, one repeated, three new,
    shuffled, so table order differs from rid order."""
    rows = np.asarray(rows, np.int32).reshape(-1, N_ATTRS)
    keep = rows[rng.random(len(rows)) > 0.2]
    upd = keep[:2].copy()
    upd[:, 2] += 1000 + rng.integers(0, 1000, len(upd)).astype(np.int32)
    new = rng.integers(5000, 6000, (3, N_ATTRS)).astype(np.int32)
    table = np.concatenate([keep[2:], upd, keep[-1:], new])
    return table[rng.permutation(len(table))]


def union_rows(ref, parents):
    union = sorted(set().union(*(ref.rlists[p] for p in parents)))
    return [ref.pool[r] for r in union]


def land(store, ref, rng, wave, submit=None):
    """Commit one wave to the store (``commit_many``, or ``submit``) and to
    the reference: each entry of ``wave`` is the parents of one commit, a
    parent ``("staged", j)`` being the wave's j-th commit, and its table
    an edit of the union of its parents' rows.  Returns the commits."""
    vid0 = store.graph.n_versions
    out = []
    for parents in wave:
        named = [vid0 + p[1] if isinstance(p, tuple) else p for p in parents]
        table = edit(rng, union_rows(ref, named))
        out.append(({"parent": named[0]} if len(named) == 1
                    else {"parents": named}) | {"table": table})
        ref.commit(named, table)
    vids = (store.commit_many(out) if submit is None else submit(out))
    assert list(vids) == list(range(vid0, vid0 + len(wave)))
    return out


def assert_matches_reference(store, ref):
    assert store.graph.n_versions == len(ref.rlists)
    np.testing.assert_array_equal(store.data, np.array(ref.pool, np.int32))
    for v in range(store.graph.n_versions):
        np.testing.assert_array_equal(store.graph.rlist(v), ref.rlists[v])
        np.testing.assert_array_equal(store.global_rlist(v), ref.rlists[v])
        np.testing.assert_array_equal(
            store.checkout(v), np.array(ref.checkout(v), np.int32)
            .reshape(-1, N_ATTRS))


def apart(store, n=2):
    """``n`` versions in different partitions, newest first."""
    seen, out = set(), []
    for v in range(store.graph.n_versions - 1, -1, -1):
        pid = int(store.assignment[v])
        if pid not in seen:
            seen.add(pid)
            out.append(v)
        if len(out) == n:
            return out
    raise AssertionError("not enough partitions")


def interleaved(store, start) -> bool:
    """Whether a partition now holds an old rid below the largest rid it
    held at ``start`` (its grids then): a merge's partner brought it."""
    return any((np.setdiff1d(p.grids, g) < g.max()).any()
               for p, g in zip(store.partitions, start))


def waves_for(case, store):
    a, b, c = apart(store, 3)
    n = store.graph.n_versions
    if case == "cross_partition":
        return [[(a, b), (b, c, a)], [(c, a)]]
    if case == "staged_parent":
        return [[(a,), (("staged", 0), b), (c, ("staged", 1))]]
    if case == "twins":
        return [[(n - 1, n - 2), (n - 2, n - 1)], [(n - 1, n - 2, n - 4)]]
    if case == "edit_of_merge":          # single-parent edits of merges
        return [[(a, b)], [(n,), (n, c)], [(n + 1,), (n + 2,)]]
    raise AssertionError(case)


# ------------------------------------------------------------ the match --
def _random_case(seed, repeats):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 30))
    vals = rng.integers(0, 3 if repeats else 1 << 20, (n + 20, 4))
    vals = vals.astype(np.int32)
    rows, extra = vals[:n], vals[n:]
    picked = rows[rng.integers(0, n, int(rng.integers(0, 2 * n)))]
    table = np.concatenate([picked, extra[:int(rng.integers(0, 20))]])
    table = table[rng.permutation(len(table))]
    rids = rng.choice(10 * n, n, replace=False).astype(np.int64)
    return table, rows, rids


@pytest.mark.parametrize("seed", range(6))
def test_match_records_is_the_loop_reference(seed):
    table, rows, rids = _random_case(seed, repeats=True)
    matched, new = match_records(table, rows, rids)
    want_m, want_new = loop_match(table, rows, rids)
    assert matched.tolist() == want_m
    assert [tuple(r) for r in new] == want_new
    assert len(matched) + len(new) == len(table)


@pytest.mark.parametrize("seed", range(4))
def test_match_records_is_diff_against_parents_on_distinct_rows(seed):
    table, rows, rids = _random_case(seed, repeats=False)
    table = np.unique(table, axis=0)
    table = table[np.random.default_rng(seed).permutation(len(table))]
    assert len(np.unique(rows, axis=0)) == len(rows)
    got, want = match_records(table, rows, rids), \
        diff_against_parents(table, rows, rids)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])


def test_match_records_keeps_twins_where_last_wins_collapses_them():
    rows = np.array([[1, 1], [2, 2], [1, 1]], np.int32)
    rids = np.array([9, 4, 7], np.int64)
    table = np.array([[1, 1], [2, 2], [1, 1], [1, 1]], np.int32)
    matched, new = match_records(table, rows, rids)
    assert matched.tolist() == [7, 4, 9]
    assert new.tolist() == [[1, 1]]
    assert sorted(diff_against_parents(table, rows, rids)[0].tolist()) \
        == [4, 7, 7, 7]


# ---------------------------------------------------------- commit_many --
@pytest.mark.parametrize("case", ["cross_partition", "staged_parent",
                                  "twins", "edit_of_merge"])
def test_commit_many_lands_merges_as_the_plain_reference(case):
    store, _, ref = cur_store(11, twins=case == "twins")
    rng = np.random.default_rng(3)
    n0, start = store.graph.n_records, [p.grids for p in store.partitions]
    for wave in waves_for(case, store):
        commits = land(store, ref, rng, wave)
        assert_matches_reference(store, ref)
        report = store.last_ingest
        merges = sum("parents" in c for c in commits)
        assert report.merges == merges
        assert (report.merge_s > 0) == (merges > 0)
        assert report.merge_s <= report.stage_s
    if case == "cross_partition":
        # a merge's partner brought OLD rids into the kept partition,
        # interleaved below rids it already held
        assert interleaved(store, start)
        assert all(np.isin(g, p.grids).all()
                   for p, g in zip(store.partitions, start))
        assert store.graph.n_records > n0
    if case == "twins":
        merged = store.graph.rlist(24)
        rows = store.data[merged]
        assert len(np.unique(rows, axis=0)) < len(rows)


def test_a_merge_lands_in_the_partition_of_the_parent_it_shares_most_with():
    store, _, ref = cur_store(5)
    a, b = apart(store)
    rng = np.random.default_rng(0)
    base = np.array(ref.checkout(b), np.int32)        # b's rows, kept whole
    table = np.concatenate([base, np.array(ref.checkout(a), np.int32)[:1]])
    (vid,) = store.commit_many([{"parents": [a, b], "table": table}])
    assert store.assignment[vid] == store.assignment[b]
    assert store._commit_log[vid][0] == b
    assert store._commit_parents[vid] == (a, b)
    # a tie keeps the first parent named
    (tie,) = store.commit_many([{"parents": [b, vid],
                                 "table": base[rng.permutation(len(base))]}])
    assert store._commit_log[tie][0] == b


# ------------------------------------------------------ the served path --
@pytest.mark.parametrize("use_kernel", [False, True])
def test_every_version_serves_exactly_off_the_extended_superblock(
        use_kernel):
    store, _, ref = cur_store(7, twins=True)
    srv = BatchedCheckoutServer(store, use_kernel=use_kernel)
    srv.warmup()                       # the whole superblock pinned

    def submit(commits):
        tickets = srv.submit_commit(commits)
        srv.flush()
        return [int(srv.result(t)) for t in tickets]
    rng = np.random.default_rng(9)
    start = [p.grids for p in store.partitions]
    for wave in waves_for("cross_partition", store) + [[(24, 25)]]:
        land(store, ref, rng, wave, submit=submit)
    assert interleaved(store, start)
    assert srv.stats.merges_ingested == 4 and srv.stats.commit_waves == 3
    assert srv.stats.merge_s > 0
    vids = list(range(store.graph.n_versions))
    for v, block in zip(vids, srv.serve(vids)):
        np.testing.assert_array_equal(
            np.asarray(block),
            np.array(ref.checkout(v), np.int32).reshape(-1, N_ATTRS))
    sb, fresh = peek_superblock(store), build_superblock(store)
    assert sb.epoch == store.epoch
    np.testing.assert_array_equal(sb.host, fresh.host)
    np.testing.assert_array_equal(sb.bounds, fresh.bounds)
    if use_kernel:
        assert srv.stats.refresh_h2d_bytes > 0
        np.testing.assert_array_equal(
            np.asarray(sb.device()).reshape(-1), fresh.host.reshape(-1))
    srv.close()


@pytest.mark.parametrize("device", [False, True])
def test_pinned_groups_extend_to_a_fresh_build_after_cross_partition_merges(
        device):
    store, _, ref = cur_store(13)
    mgr = get_superblock_groups(
        store, budget=estimate_superblock_bytes(store), create=True)
    mgr.warm(device=device)
    assert len(mgr.groups) >= 2
    rng = np.random.default_rng(4)
    start = [p.grids for p in store.partitions]
    for wave in waves_for("cross_partition", store):
        land(store, ref, rng, wave)
    assert interleaved(store, start)
    assert mgr.append_evictions == 0 and mgr.groups
    for key, sb in mgr.groups.items():
        fresh = build_superblock(store, pids=key)
        np.testing.assert_array_equal(sb.host, fresh.host)
        if device:
            np.testing.assert_array_equal(
                np.asarray(sb.device()).reshape(-1), fresh.host.reshape(-1))
    vids = list(range(store.graph.n_versions))
    for v, got in zip(vids, checkout_partitioned(store, vids,
                                                 use_kernel=device)):
        np.testing.assert_array_equal(
            np.asarray(got),
            np.array(ref.checkout(v), np.int32).reshape(-1, N_ATTRS))


# ------------------------------------------------- journal and lineage --
def _journaled(tmp_path, seed=17):
    store, vg, ref = cur_store(seed, twins=True)
    journal = Journal(str(tmp_path / "merge.wal"), owner=store)
    attach_journal(store, journal)
    rng = np.random.default_rng(seed)
    landed = []
    for case in ("staged_parent", "edit_of_merge"):
        for wave in waves_for(case, store):
            landed += land(store, ref, rng, wave)
    attach_journal(store, None)
    journal.close()
    recs, bad = read_records(journal.path)
    assert bad is None
    return store, vg, landed, recs


def test_the_journal_names_a_merges_parents_in_order_and_replays_it(
        tmp_path):
    store, _, landed, recs = _journaled(tmp_path)
    entries = [c for r in recs if r.kind == "commit.batch"
               for c in r.payload["commits"]]
    assert len(entries) == len(landed)
    for c, sent in zip(entries, landed):
        if "parents" in sent:
            assert c["parents"] == sent["parents"] and "parent" not in c
        else:
            assert c["parent"] == sent["parent"] and "parents" not in c
    fresh, _, _ = cur_store(17, twins=True)
    assert replay_into(fresh, recs)["applied"] == len(recs)
    for name in ("indptr", "indices"):
        np.testing.assert_array_equal(getattr(fresh.graph, name),
                                      getattr(store.graph, name))
    np.testing.assert_array_equal(fresh.data, store.data)
    np.testing.assert_array_equal(fresh.assignment, store.assignment)
    np.testing.assert_array_equal(fresh.vid_to_pid, store.vid_to_pid)
    assert len(fresh.partitions) == len(store.partitions)
    for pa, pb in zip(fresh.partitions, store.partitions):
        for name in ("vids", "grids", "block", "indptr", "indices"):
            np.testing.assert_array_equal(getattr(pa, name),
                                          getattr(pb, name))
    assert fresh._commit_log == store._commit_log
    assert fresh._commit_parents == store._commit_parents


def test_the_commit_log_is_to_tree_over_the_journaled_parents(tmp_path):
    store, vg, _, recs = _journaled(tmp_path)
    n0 = vg.n_versions
    base_tree, _ = to_tree(_base_graph(store, n0), vg)
    full = VersionGraph()
    for v in range(n0):
        full.add_version(parents=vg.parents(v))
    for r in recs:
        for c in r.payload["commits"]:
            named = c.get("parents") or [c["parent"]]
            assert full.add_version(parents=named) == c["vid"]
    tree, _ = to_tree(store.graph, full)
    for v in range(n0, store.graph.n_versions):
        assert store._commit_log[v] == (tree.parent[v], tree.edge_w[v],
                                        tree.n_records[v])
        assert list(store._commit_parents[v]) == list(full.parents(v))
    trig = RepartitionTrigger(store, base_tree, min_waves=3)
    np.testing.assert_array_equal(trig.tree.parent, tree.parent)
    np.testing.assert_array_equal(trig.tree.edge_w, tree.edge_w)
    np.testing.assert_array_equal(trig.tree.n_records, tree.n_records)


def _base_graph(store, n0):
    from repro.core.graph import BipartiteGraph
    g = store.graph
    return BipartiteGraph(indptr=g.indptr[:n0 + 1].copy(),
                          indices=g.indices[:g.indptr[n0]].copy(),
                          n_records=g.n_records)


# ----------------------------------------------------- malformed parents --
@pytest.mark.parametrize("named", [
    {"parents": [3]},                    # one vid
    {"parents": [3, 3]},                 # a repeat
    {"parents": [3, 99]},                # out of range
    {"parents": [-1, 3]},
    {"parents": [3, 4], "parent": 3},    # both
])
@pytest.mark.parametrize("form", ["table", "rlist"])
def test_a_malformed_merge_fails_in_staging_and_touches_nothing(
        tmp_path, named, form):
    store, _, ref = cur_store(2)
    journal = Journal(str(tmp_path / "bad.wal"), owner=store)
    attach_journal(store, journal)
    good = {"parent": 0, "table": np.array(ref.checkout(0), np.int32)}
    bad = dict(named)
    if form == "table":
        bad["table"] = np.array(ref.checkout(3), np.int32)
    else:
        bad["rlist"] = store.graph.rlist(3)
    state = (store.graph.n_versions, store.graph.n_records, store.epoch,
             store.data, store.graph.indices, store.assignment)
    with pytest.raises(ValueError, match="parent"):
        store.commit_many([good, bad])
    assert (store.graph.n_versions, store.graph.n_records, store.epoch) \
        == state[:3]
    assert all(a is b for a, b in zip(
        (store.data, store.graph.indices, store.assignment), state[3:]))
    assert journal.appended == 0
    assert read_records(journal.path)[0] == []
    attach_journal(store, None)
    journal.close()
