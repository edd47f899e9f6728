"""Fused commit ingestion waves (the write-side twin of the checkout
wave engine): ``commit_many`` bit-identity to the serial
``commit_version`` loop (example-based AND hypothesis-random batches),
the ``segment_append`` kernel's three tile modes, targeted superblock
refresh (cold pinned groups stay pinned; uploads bounded by the new
BN-aligned tiles), the three ingest fault sites swept single-fault
bit-identical, journal group commit (ONE fsync per wave; all-or-nothing
replay at EVERY kill boundary), the trigger-resync and mid-rebuild
regressions, and the serve-layer write tickets (single-server and
multi-tenant)."""
import contextlib
import functools
import os

import numpy as np
import pytest

import repro.core.checkout as checkout_mod
import repro.core.partition as partition_mod
from repro.core.checkout import (build_superblock,
                                 estimate_superblock_bytes,
                                 get_superblock, get_superblock_groups,
                                 checkout_partitioned, peek_superblock)
from repro.core.datamodels import diff_against_parents
from repro.core.faults import FaultPlan, InjectedFault, read_leases
from repro.core.graph import BipartiteGraph, intersect_size
from repro.core.journal import (Journal, attach_journal, get_journal,
                                read_records, replay_into)
from repro.core.online import RepartitionTrigger
from repro.core.partition import PartitionedCVD
from repro.core.version_graph import WeightedTree
from repro.serve.checkout import BatchedCheckoutServer
from repro.serve.tenancy import MultiTenantServer, TenantQuota

SEED = int(os.environ.get("REPRO_FAULT_SEED", "0"))

INGEST_SITES = ("ingest.extract", "ingest.append", "ingest.commit")


# ------------------------------------------------------------ scaffolding --
def _mkstore(seed=7, n_versions=8, n_records=256, size=24, n_attrs=8,
             parts=4):
    rng = np.random.default_rng(seed)
    rls = [np.sort(rng.choice(n_records, size,
                              replace=False)).astype(np.int64)
           for _ in range(n_versions)]
    graph = BipartiteGraph.from_rlists(rls, n_records=n_records)
    data = rng.integers(0, 1 << 20, (n_records, n_attrs)).astype(np.int32)
    store = PartitionedCVD(graph, data, np.zeros(n_versions, np.int64))
    if parts > 1:
        store.repartition(np.arange(n_versions) % parts)
    return store


def _mkbatch(rng, store, k, *, fresh_pid_every=0):
    """k random commit dicts mixing the rlist / rlist+new_rows / table
    forms, with same-wave parent chaining.  Deterministic in ``rng``."""
    n0 = int(store.graph.n_records)
    v0 = int(store.graph.n_versions)
    n_attrs = store.data.shape[1]
    n_cur = n0
    commits = []
    for i in range(k):
        parent = int(rng.integers(0, v0 + i))     # may chain into the wave
        form = int(rng.integers(0, 3))
        c = {"parent": parent}
        if form == 0:                              # rlist over existing rids
            m = int(rng.integers(1, 20))
            c["rlist"] = np.sort(rng.choice(n0, m, replace=False))
        elif form == 1:                            # rlist + new rows
            m = int(rng.integers(0, 12))
            nn = int(rng.integers(1, 6))
            new = rng.integers(0, 1 << 20, (nn, n_attrs)).astype(np.int32)
            c["rlist"] = np.concatenate(
                [np.sort(rng.choice(n0, m, replace=False)),
                 np.arange(n_cur, n_cur + nn)]).astype(np.int64)
            c["new_rows"] = new
            n_cur += nn
        else:                                      # full table vs parent
            keep = int(rng.integers(1, 10))
            nn = int(rng.integers(0, 5))
            base = store.data[np.sort(rng.choice(n0, keep, replace=False))]
            new = rng.integers(1 << 20, 1 << 21,
                               (nn, n_attrs)).astype(np.int32)
            c["table"] = np.concatenate([base, new])
            n_cur += nn      # upper bound (dup rows in base never shrink it)
        if fresh_pid_every and i % fresh_pid_every == fresh_pid_every - 1:
            c["pid"] = int(store.assignment.max()) + 1 + i
        commits.append(c)
    return commits


def _apply_serial(store, commits):
    """The serial oracle: the same batch through K ``commit_version``
    calls (table-form diffs extracted exactly as the batched path does,
    against the by-now-committed parent)."""
    vids = []
    for c in commits:
        parent = c.get("parent")
        pid = c.get("pid")
        if c.get("table") is not None:
            n = int(store.graph.n_records)
            p_rids = store.graph.rlist(int(parent))
            matched, new = diff_against_parents(
                np.ascontiguousarray(np.asarray(c["table"],
                                                store.data.dtype)),
                store.data[p_rids], p_rids)
            rlist = np.unique(np.concatenate(
                [matched, n + np.arange(len(new), dtype=np.int64)]))
            vids.append(store.commit_version(
                rlist, parent=parent, pid=pid,
                new_rows=new if len(new) else None))
        else:
            vids.append(store.commit_version(
                np.unique(np.asarray(c["rlist"], np.int64)),
                parent=parent, pid=pid, new_rows=c.get("new_rows")))
    return vids


def _assert_stores_equal(a, b):
    """Bit-identity on everything the batch/serial paths must agree on
    (the epoch COUNT is excluded by design: one wave = one bump, the
    serial loop bumps K times)."""
    np.testing.assert_array_equal(a.graph.indptr, b.graph.indptr)
    np.testing.assert_array_equal(a.graph.indices, b.graph.indices)
    np.testing.assert_array_equal(np.asarray(a.data), np.asarray(b.data))
    np.testing.assert_array_equal(a.assignment, b.assignment)
    np.testing.assert_array_equal(a.vid_to_pid, b.vid_to_pid)
    assert len(a.partitions) == len(b.partitions)
    for pa, pb in zip(a.partitions, b.partitions):
        assert pa.pid == pb.pid
        np.testing.assert_array_equal(pa.vids, pb.vids)
        np.testing.assert_array_equal(pa.grids, pb.grids)
        np.testing.assert_array_equal(pa.block, pb.block)
        np.testing.assert_array_equal(pa.indptr, pb.indptr)
        np.testing.assert_array_equal(pa.indices, pb.indices)
    vids = list(range(a.graph.n_versions))
    for x, y in zip(checkout_partitioned(a, vids, use_kernel=False),
                    checkout_partitioned(b, vids, use_kernel=False)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _snap(store):
    return (store.graph.indptr.copy(), store.graph.indices.copy(),
            np.asarray(store.data).copy(), store.assignment.copy(),
            store.vid_to_pid.copy(), int(store.epoch))


def _snap_equal(s, store):
    indptr, indices, data, assignment, v2p, epoch = s
    return (np.array_equal(store.graph.indptr, indptr)
            and np.array_equal(store.graph.indices, indices)
            and np.array_equal(np.asarray(store.data), data)
            and np.array_equal(store.assignment, assignment)
            and np.array_equal(store.vid_to_pid, v2p)
            and int(store.epoch) == epoch)


# ------------------------------------------------- batch == serial oracle --
def test_commit_many_matches_serial_oracle():
    rng = np.random.default_rng(3)
    batched, serial = _mkstore(), _mkstore()
    commits = _mkbatch(rng, batched, 8, fresh_pid_every=4)
    vids = batched.commit_many(commits)
    svids = _apply_serial(serial, commits)
    assert vids == svids == list(range(8, 16))
    _assert_stores_equal(batched, serial)
    # one wave = one epoch bump; lineage memo matches the serial loop's
    assert batched.epoch == _mkstore().epoch + 1
    assert batched._commit_log == serial._commit_log


def test_commit_many_empty_and_single():
    store = _mkstore()
    snap = _snap(store)
    assert store.commit_many([]) == []
    assert _snap_equal(snap, store)         # empty wave: not even an epoch
    serial = _mkstore()
    c = {"rlist": np.arange(10, dtype=np.int64), "parent": 2}
    assert store.commit_many([c]) == [serial.commit_version(
        np.arange(10, dtype=np.int64), parent=2)]
    _assert_stores_equal(store, serial)


def test_commit_many_rejects_bad_parent_and_stages_nothing():
    store = _mkstore()
    snap = _snap(store)
    with pytest.raises(ValueError, match="parent"):
        store.commit_many([{"rlist": np.arange(4, dtype=np.int64),
                            "parent": 99}])
    with pytest.raises(ValueError):
        store.commit_many([{"table": np.zeros((3, 8), np.int32)}])  # no parent
    assert _snap_equal(snap, store)


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5, 6, 7, 8])
def test_commit_many_random_batches(seed):
    """The hypothesis property's always-on twin (hypothesis is an
    optional dependency): random mixed-form batches with same-wave
    chaining stay bit-identical to the serial loop."""
    rng = np.random.default_rng(seed)
    k = int(rng.integers(1, 10))
    batched, serial = _mkstore(seed=seed % 5), _mkstore(seed=seed % 5)
    commits = _mkbatch(rng, batched, k,
                       fresh_pid_every=int(rng.integers(0, 4)))
    assert batched.commit_many(commits) == _apply_serial(serial, commits)
    _assert_stores_equal(batched, serial)


def test_commit_many_hypothesis_property():
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 2 ** 16), k=st.integers(1, 10))
    def prop(seed, k):
        rng = np.random.default_rng(seed)
        batched, serial = _mkstore(seed=seed % 5), _mkstore(seed=seed % 5)
        commits = _mkbatch(rng, batched, k,
                           fresh_pid_every=int(rng.integers(0, 4)))
        assert batched.commit_many(commits) == _apply_serial(serial,
                                                             commits)
        _assert_stores_equal(batched, serial)

    prop()


# ------------------------------------------------------ the append kernel --
def test_segment_append_kernel_modes():
    from repro.kernels import ops as K
    rng = np.random.default_rng(0)
    bn, d = 8, 256
    src = rng.standard_normal((5 * bn, d)).astype(np.float32)
    delta = rng.standard_normal((3 * bn, d)).astype(np.float32)
    #        reuse0  delta0  pad   reuse3  delta2  pad
    sel = np.array([0, 1, 2, 0, 1, 2], np.int32)
    starts = np.array([0, 0, 0, 3 * bn, 2 * bn, 0], np.int32)
    # lane-row layout: a 256-wide row is two consecutive 128-lane rows
    out = np.asarray(K.segment_append(src.reshape(-1, 128),
                                      delta.reshape(-1, 128), sel, starts,
                                      block_n=bn, row_lanes=d // 128,
                                      interpret=True)).reshape(-1, d)
    expect = np.concatenate([
        src[:bn], delta[:bn], np.zeros((bn, d), np.float32),
        src[3 * bn:4 * bn], delta[2 * bn:3 * bn],
        np.zeros((bn, d), np.float32)])
    np.testing.assert_array_equal(out, expect)


@pytest.mark.parametrize("kernel", ["segment_append", "segment_move"])
@pytest.mark.parametrize("tiles_per_launch", [1, 4])
def test_segment_kernels_split_launches_bit_identical(kernel, tiles_per_launch,
                                                      capped_launches):
    """Plans bigger than one launch's SMEM run as several launches into one
    output — bit-identical to a single launch."""
    import importlib
    mod = importlib.import_module(f"repro.kernels.{kernel}")
    rng = np.random.default_rng(3)
    bn, t = 8, 11
    src = rng.integers(0, 1 << 20, (6 * bn, 128)).astype(np.int32)
    delta = rng.integers(0, 1 << 20, (4 * bn, 128)).astype(np.int32)
    sel = rng.integers(0, 3 if kernel == "segment_append" else 2,
                       t).astype(np.int32)
    starts = np.where(sel == 0, rng.integers(0, 5 * bn, t),
                      rng.integers(0, 3 * bn, t)).astype(np.int32)
    run = functools.partial(getattr(mod, kernel), src, delta, sel, starts,
                            block_n=bn, interpret=True)
    one = np.asarray(run())
    # 8 B of plan per tile: sel + start, int32 each
    split, launches = capped_launches(getattr(mod, kernel), run,
                                      8 * tiles_per_launch)
    assert launches == -(-t // tiles_per_launch) > 1
    np.testing.assert_array_equal(np.asarray(split), one)


def test_segment_append_rejects_ragged_width():
    from repro.kernels import ops as K
    with pytest.raises(ValueError, match="lane tile"):
        K.segment_append(np.zeros((8, 100), np.float32),
                         np.zeros((8, 100), np.float32),
                         np.zeros(1, np.int32), np.zeros(1, np.int32),
                         interpret=True)


# ------------------------------------------- targeted superblock refresh --
def test_whole_store_superblock_extends_to_fresh_build():
    store = _mkstore()
    sb0, _ = get_superblock(store)
    assert sb0 is not None and sb0.epoch == store.epoch
    rng = np.random.default_rng(1)
    store.commit_many(_mkbatch(rng, store, 5, fresh_pid_every=3))
    sb1 = peek_superblock(store)
    assert sb1 is not None and sb1.epoch == store.epoch
    fresh = build_superblock(store)
    np.testing.assert_array_equal(sb1.host, fresh.host)
    np.testing.assert_array_equal(sb1.row_offsets, fresh.row_offsets)
    np.testing.assert_array_equal(sb1.bounds, fresh.bounds)


def test_commit_upload_bounded_by_new_tiles():
    """The device-resident whole-store superblock is extended in place:
    bytes over the link are bounded by the wave's BN-aligned new tiles,
    never a whole re-upload."""
    store = _mkstore()
    sb0, _ = get_superblock(store)
    sb0.device()                      # pin the device copy (cpu jax array)
    captured = {}
    orig = checkout_mod.refresh_superblocks_after_commit

    def spy(*a, **kw):
        captured["stats"] = out = orig(*a, **kw)
        return out

    checkout_mod.refresh_superblocks_after_commit = spy
    try:
        # a tail-append commit: 24 fresh rows into vid 0's partition —
        # every untouched partition segment and every full old tile of
        # the touched one reuses on device
        rng = np.random.default_rng(2)
        n0 = store.graph.n_records
        new = rng.integers(0, 1 << 20, (24, 8)).astype(np.int32)
        store.commit_many([{"rlist": np.concatenate(
            [store.graph.rlist(0), np.arange(n0, n0 + 24)]),
            "parent": 0, "new_rows": new}])
    finally:
        checkout_mod.refresh_superblocks_after_commit = orig
    st = captured["stats"]
    assert st["extended"] == 1 and st["evicted"] == 0
    sb = peek_superblock(store)
    row_bytes = sb.host.shape[1] * sb.host.dtype.itemsize  # lane-padded D
    assert st["bytes_uploaded"] == st["delta_tiles"] * sb.block_n * row_bytes
    # bounded by the new BN-aligned tiles: 24 new rows + the re-packed
    # boundary tile of the touched segment — nowhere near a re-upload
    assert st["delta_tiles"] <= 24 // sb.block_n + 2
    assert st["bytes_uploaded"] < sb.host.nbytes / 4
    # ... and the extension is bit-faithful to a fresh build
    np.testing.assert_array_equal(sb.host, build_superblock(store).host)


def test_cold_pinned_groups_stay_pinned():
    """Satellite 3: a commit touches ONE partition group — every other
    pinned group revalidates in place (same object, new epoch) instead of
    being nuked, and the pins/evictions invariant holds throughout."""
    store = _mkstore(n_versions=12, n_records=512, parts=6)
    budget = estimate_superblock_bytes(store)
    mgr = get_superblock_groups(store, budget=budget, create=True)
    mgr.warm(device=False)
    assert len(mgr.groups) >= 2
    before = dict(mgr.groups)
    # a commit into vid 0's partition touches exactly that slot's group
    parent = 0
    slot = int(store.vid_to_pid[parent])
    touched_keys = {k for k in before if slot in k}
    store.commit_many([{"rlist": store.graph.rlist(parent)[:10],
                        "parent": parent}])
    assert set(mgr.groups) == set(before)        # nothing evicted
    for key, sb in mgr.groups.items():
        assert sb.epoch == store.epoch
        if key not in touched_keys:
            assert sb is before[key]             # cold: revalidated in place
        else:
            assert sb is not before[key]         # hot: extended in place
    assert mgr.pins - mgr.evictions == len(mgr.groups)
    # served rows off the refreshed groups match the plain gather
    for v in (0, store.graph.n_versions - 1):
        got = checkout_partitioned(store, [v], use_kernel=False)[0]
        np.testing.assert_array_equal(np.asarray(got),
                                      store.data[store.graph.rlist(v)])


def test_full_budget_growth_evicts_a_cold_group():
    """With the budget full, a touched group that grows makes room by
    LRU-evicting a COLD group and is extended in place on the device — it
    is never evicted itself (an append eviction)."""
    store = _mkstore(n_versions=12, n_records=512, parts=6)
    mgr = get_superblock_groups(
        store, budget=estimate_superblock_bytes(store), create=True)
    mgr.warm(device=True)
    assert mgr.pinned_bytes == mgr.budget and len(mgr.groups) >= 2
    before = dict(mgr.groups)
    slot = int(store.vid_to_pid[0])
    (hot,) = [k for k in before if slot in k]
    n0 = store.graph.n_records
    new = np.random.default_rng(5).integers(0, 1 << 20, (24, 8)) \
        .astype(np.int32)
    store.commit_many([{"rlist": np.concatenate(
        [store.graph.rlist(0), np.arange(n0, n0 + 24)]),
        "parent": 0, "new_rows": new}])
    assert mgr.append_evictions == 0 and mgr.extended == 1
    grown = mgr.groups[hot]
    assert grown is not before[hot] and grown.epoch == store.epoch
    assert 0 < grown.bytes_uploaded < grown.host.nbytes    # delta only
    evicted = set(before) - set(mgr.groups)
    assert evicted and hot not in evicted
    assert mgr.pinned_bytes <= mgr.budget
    assert mgr.pins - mgr.evictions == len(mgr.groups)
    np.testing.assert_array_equal(
        grown.host, build_superblock(store, pids=hot).host)
    for v in (0, store.graph.n_versions - 1):
        got = checkout_partitioned(store, [v], use_kernel=True)[0]
        np.testing.assert_array_equal(np.asarray(got),
                                      store.data[store.graph.rlist(v)])


# ------------------------------------------------------- ingest fault sweep --
@pytest.mark.parametrize("nth", [0, 1])
@pytest.mark.parametrize("site", INGEST_SITES)
def test_ingest_single_fault_bit_identical(site, nth):
    """A single injected fault at each ingest site: either absorbed
    in-place (ingest.append — the touched group is evicted, results
    unchanged) or surfaced with NOTHING mutated and clean on one retry;
    the final store is bit-identical to the fault-free oracle either
    way, with balanced group counters."""
    def run(plan):
        store = _mkstore(n_versions=12, n_records=512, parts=6)
        mgr = get_superblock_groups(
            store, budget=estimate_superblock_bytes(store), create=True)
        mgr.warm(device=False)
        rng = np.random.default_rng(9)
        ctx = plan.armed() if plan is not None else contextlib.nullcontext()
        with ctx:
            for k in (3, 2):
                batch = _mkbatch(rng, store, k)
                snap = _snap(store)
                try:
                    store.commit_many(batch)
                except InjectedFault:
                    # recovery contract: the fault surfaced with nothing
                    # mutated — one bare retry lands the identical wave
                    assert _snap_equal(snap, store)
                    store.commit_many(batch)
        return store, mgr

    oracle, _ = run(None)
    store, mgr = run(FaultPlan.single(site, nth=nth))
    _assert_stores_equal(store, oracle)
    assert mgr.pins - mgr.evictions == len(mgr.groups)
    assert int(getattr(store, "_inflight_waves", 0) or 0) == 0


def test_seeded_plan_ingest_sites():
    """The CI fault-matrix entry: a seeded schedule restricted to the
    ingest sites keeps the batch path bit-identical to the oracle."""
    plan = FaultPlan.seeded(SEED, sites=INGEST_SITES)
    oracle = _mkstore()
    rng = np.random.default_rng(4)
    batches = [_mkbatch(rng, oracle, 3), ]
    oracle.commit_many(batches[0])
    store = _mkstore()
    with plan.armed():
        snap = _snap(store)
        try:
            store.commit_many(batches[0])
        except InjectedFault:
            assert _snap_equal(snap, store)
            store.commit_many(batches[0])
    _assert_stores_equal(store, oracle)


def test_commit_version_fault_mid_rebuild_leaves_store_intact():
    """Satellite 1 regression: a failure anywhere in the STAGE half of
    ``commit_version`` — here the partition rebuild itself — must leave
    the live store bit-identical to its pre-commit state."""
    store = _mkstore()
    snap = _snap(store)
    orig = partition_mod.build_partition
    calls = {"n": 0}

    def boom(*a, **kw):
        calls["n"] += 1
        raise RuntimeError("mid-rebuild crash")

    partition_mod.build_partition = boom
    try:
        with pytest.raises(RuntimeError, match="mid-rebuild"):
            store.commit_version(np.arange(10, dtype=np.int64), parent=0)
    finally:
        partition_mod.build_partition = orig
    assert calls["n"] == 1
    assert _snap_equal(snap, store)
    # and the clean retry commits normally
    v = store.commit_version(np.arange(10, dtype=np.int64), parent=0)
    assert v == store.graph.n_versions - 1


# ------------------------------------------------------ journal group commit --
def _tree_for(store):
    n = store.graph.n_versions
    return WeightedTree(
        parent=np.concatenate([[-1], np.zeros(n - 1, np.int64)]),
        n_records=np.array([len(store.graph.rlist(v)) for v in range(n)],
                           np.int64),
        edge_w=np.zeros(n, np.int64))


def test_one_fsync_per_wave_and_replay(tmp_path):
    store = _mkstore()
    j = Journal(str(tmp_path / "j.owj"), owner=store)
    attach_journal(store, j)
    rng = np.random.default_rng(6)
    batch = _mkbatch(rng, store, 5)
    synced0, appended0 = j.synced, j.appended
    vids = store.commit_many(batch)
    assert j.synced - synced0 == 1          # the whole wave: ONE fsync
    assert j.appended - appended0 == 1      # ... and ONE record
    recs, bad = read_records(j.path)
    assert bad is None
    assert [r.kind for r in recs] == ["commit.batch"]
    # replay into a fresh store reproduces the wave bit-identically
    fresh = _mkstore()
    out = replay_into(fresh, recs)
    assert out["applied"] == 1
    _assert_stores_equal(fresh, store)
    # ... and is idempotent
    assert replay_into(fresh, recs)["applied"] == 0
    _assert_stores_equal(fresh, store)
    assert vids == list(range(8, 13))


def test_kill_matrix_inside_group_committed_window(tmp_path):
    """Truncate the journal at EVERY byte boundary inside a group-commit
    window (record boundaries AND torn mid-frame cuts): replay restores
    either the full wave or none of it — never a partial batch."""
    store = _mkstore()
    j = Journal(str(tmp_path / "j.owj"), owner=store)
    attach_journal(store, j)
    rng = np.random.default_rng(8)
    pre = _snap(store)
    store.commit_version(np.arange(6, dtype=np.int64), parent=0)
    mid = _snap(store)
    store.commit_many(_mkbatch(rng, store, 4))
    post = _snap(store)
    recs, bad = read_records(j.path)
    assert bad is None and len(recs) == 2
    marks = [pre, mid, post]
    boundaries = [0] + [r.end for r in recs]
    raw = open(j.path, "rb").read()
    for i, b in enumerate(boundaries):
        for tag, cut in ((f"cut{i}", b), (f"tear{i}", b + 7)):
            p = tmp_path / f"{tag}.owj"
            p.write_bytes(raw[:cut])
            got, _ = read_records(str(p))
            fresh = _mkstore()
            replay_into(fresh, got)
            # all-or-nothing: every cut lands on a marked state
            assert _snap_equal(
                (*marks[min(i, len(got))][:5],
                 int(fresh.epoch)), fresh), f"partial batch at {tag}"


# --------------------------------------------------------- trigger resync --
def test_trigger_resyncs_after_interleaved_commits():
    """Satellite 2 regression: a commit landing between observations must
    RESYNC the trigger's tree from the commit log, not hard-raise the
    serving flush that armed it."""
    store = _mkstore()
    trig = RepartitionTrigger(store, _tree_for(store), min_waves=3)
    srv = BatchedCheckoutServer(store, use_kernel=False, trigger=trig,
                                pipeline=False)
    for i, vids in enumerate(([0, 3], [1, 4], [2, 5], [6, 7], [0, 2])):
        outs = srv.serve(vids)
        for v, m in zip(vids, outs):
            np.testing.assert_array_equal(
                np.asarray(m), store.data[store.graph.rlist(v)])
        if i in (1, 3):      # the interleaved writer
            store.commit_version(store.graph.rlist(i)[:8], parent=i)
    srv.close()
    assert trig.tree.n == store.graph.n_versions
    # resynced lineage came from the commit log, not a degraded guess
    assert trig.tree.parent[-1] == 3
    assert trig.tree.edge_w[-1] == intersect_size(
        store.graph.rlist(3), store.graph.rlist(store.graph.n_versions - 1))


def test_trigger_constructor_resyncs_stale_tree():
    store = _mkstore()
    tree = _tree_for(store)
    store.commit_many([{"rlist": np.arange(5, dtype=np.int64),
                        "parent": 1}])
    trig = RepartitionTrigger(store, tree, min_waves=3)   # must not raise
    assert trig.tree.n == store.graph.n_versions
    # a tree AHEAD of the store stays unrepairable
    bad = WeightedTree(parent=np.full(99, -1, np.int64),
                       n_records=np.ones(99, np.int64),
                       edge_w=np.zeros(99, np.int64))
    with pytest.raises(ValueError, match="ahead"):
        RepartitionTrigger(store, bad)


# ------------------------------------------------------ serve write plane --
def test_server_write_tickets_reads_after_write():
    store = _mkstore()
    srv = BatchedCheckoutServer(store, use_kernel=False)   # pipelined
    rt = srv.submit(0)
    wt = srv.submit_commit([
        {"rlist": np.arange(12, dtype=np.int64), "parent": 0},
        {"rlist": np.arange(20, dtype=np.int64), "parent": 8},  # same wave
    ])
    srv.flush()
    assert [int(srv.result(t)) for t in wt] == [8, 9]
    # a read submitted after the write observes the committed version
    rt2 = srv.submit(9)
    srv.flush()
    srv.deliver()
    np.testing.assert_array_equal(np.asarray(srv.result(rt2)),
                                  store.data[store.graph.rlist(9)])
    np.testing.assert_array_equal(np.asarray(srv.result(rt)),
                                  store.data[store.graph.rlist(0)])
    assert srv.stats.commit_waves == 1
    assert srv.stats.commits_ingested == 2
    srv.close()
    assert read_leases(store).held() == 0


def test_server_write_defers_until_leases_drain():
    """The migration-protocol mirror: an out-of-band epoch lease defers
    the write wave (re-queued, counted) instead of racing it; the commit
    lands once the lease is released."""
    store = _mkstore()
    srv = BatchedCheckoutServer(store, use_kernel=False, pipeline=False,
                                write_drain_timeout_s=0.01)
    outsider = read_leases(store).acquire(store)
    wt = srv.submit_commit([{"rlist": np.arange(5, dtype=np.int64),
                             "parent": 0}])
    srv.flush()
    assert srv.stats.commit_deferrals == 1
    assert store.graph.n_versions == 8          # nothing committed
    with pytest.raises(KeyError):
        srv._results[wt[0]]
    outsider.release()
    srv.flush()
    assert int(srv.result(wt[0])) == 8
    assert srv.stats.commit_waves == 1
    srv.close()


def test_multi_tenant_write_waves():
    store = _mkstore()
    mt = MultiTenantServer(
        store, threads=False, use_kernel=False,
        quotas={"a": TenantQuota(wave_share=2.0), "b": TenantQuota()})
    ra = mt.submit("a", 0)
    wa = mt.submit_commit("a", [
        {"rlist": np.arange(16, dtype=np.int64), "parent": 0},
        {"rlist": np.arange(24, dtype=np.int64), "parent": 8},
    ])
    rb = mt.submit("b", 1)
    mt.pump()
    assert [int(v) for v in mt.results("a", wa)] == [8, 9]
    np.testing.assert_array_equal(np.asarray(mt.result("a", ra)),
                                  store.data[store.graph.rlist(0)])
    np.testing.assert_array_equal(np.asarray(mt.result("b", rb)),
                                  store.data[store.graph.rlist(1)])
    # the committed versions are now servable by the OTHER tenant
    rb2 = mt.submit("b", 9)
    mt.pump()
    assert len(mt.result("b", rb2)) == 24
    acct = mt.accounting()
    assert acct["backlog"] == 0 and acct["leases_held"] == 0
    mt.close()
    acct = mt.accounting()
    assert all(v["queued"] == 0 and v["inflight"] == 0
               for v in acct["tenants"].values())
    assert mt.stats("a").delivered == 3 and mt.stats("b").delivered == 2


def test_multi_tenant_writes_threaded():
    store = _mkstore()
    with MultiTenantServer(store, threads=True, use_kernel=False,
                           quotas={"a": TenantQuota(),
                                   "b": TenantQuota()}) as mt:
        wa = mt.submit_commit("a", [{"rlist": np.arange(10,
                                                        dtype=np.int64),
                                     "parent": 0}])
        rb = [mt.submit("b", v) for v in (0, 1, 2)]
        assert int(mt.result("a", wa[0], timeout=10.0)) == 8
        for v, t in zip((0, 1, 2), rb):
            np.testing.assert_array_equal(
                np.asarray(mt.result("b", t, timeout=10.0)),
                store.data[store.graph.rlist(v)])
        assert mt.drain(timeout=10.0)
    assert read_leases(store).held() == 0


def test_write_commits_count_against_quota():
    store = _mkstore()
    mt = MultiTenantServer(
        store, threads=False, use_kernel=False,
        quotas={"a": TenantQuota(max_inflight=2)})
    from repro.serve.tenancy import QuotaExceeded
    mt.submit_commit("a", [{"rlist": np.arange(3, dtype=np.int64),
                            "parent": 0}] * 2)
    with pytest.raises(QuotaExceeded):
        mt.submit_commit("a", [{"rlist": np.arange(3, dtype=np.int64),
                                "parent": 0}])
    mt.pump()
    mt.close()


# --------------------------------------------------------- edge-w memo ----
def test_edge_weight_memo_matches_recompute():
    """Satellite 4: commit-time seeded edge weights (the ``_edge_w``
    memo) agree with a brute-force ``intersect_size`` recompute."""
    store = _mkstore()
    rng = np.random.default_rng(5)
    store.commit_many(_mkbatch(rng, store, 6))
    for v, (p, w, size) in store._commit_log.items():
        assert size == len(store.graph.rlist(v))
        if p >= 0:
            assert w == intersect_size(store.graph.rlist(p),
                                       store.graph.rlist(v))
