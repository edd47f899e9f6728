"""Density-triggered online repartitioning with incremental superblock
migration: MigrationPlan correctness + paper cost model, in-place
``apply_migration`` vs rebuild-from-scratch equivalence, the
``segment_move`` device path (reused tiles never re-cross the host link),
eager superblock eviction, the memory budget, and the telemetry ->
trigger -> migration loop through the serve layer."""
import logging

import numpy as np
import pytest

from repro.core import generate, to_tree
from repro.core.checkout import (build_superblock, checkout_wave,
                                 estimate_superblock_bytes, evict_superblocks,
                                 get_density_stats, get_superblock,
                                 measure_density, migrate_superblock,
                                 peek_superblock, take_superblock)
from repro.core.graph import BipartiteGraph
from repro.core.lyresplit import lyresplit_for_budget
from repro.core.online import RepartitionTrigger, _same_partitioning
from repro.core.partition import PartitionedCVD, plan_migration
from repro.core.version_graph import WeightedTree
from repro.serve.checkout import BatchedCheckoutServer


def _store(rng, n_versions=24, n_partitions=4, seed=3, n_attrs=12):
    w = generate("SCI", n_versions=n_versions, inserts=100, n_branches=4,
                 n_attrs=n_attrs, seed=seed)
    assignment = rng.permutation(np.arange(w.n_versions) % n_partitions)
    return PartitionedCVD(w.graph, w.data, assignment), w


def _scattered_store(rng, n_versions=16, n_records=1024, size=48, n_attrs=8):
    """Versions sharing nothing, records scattered: the row-DMA-dominated
    workload the density trigger exists for.  Tree = star rooted at v0."""
    rls = [np.sort(rng.choice(n_records, size, replace=False)).astype(np.int64)
           for _ in range(n_versions)]
    graph = BipartiteGraph.from_rlists(rls, n_records=n_records)
    data = rng.integers(0, 1 << 20, (n_records, n_attrs)).astype(np.int32)
    store = PartitionedCVD(graph, data, np.zeros(n_versions, np.int64))
    tree = WeightedTree(
        parent=np.concatenate([[-1], np.zeros(n_versions - 1, np.int64)]),
        n_records=np.array([len(r) for r in rls], np.int64),
        edge_w=np.zeros(n_versions, np.int64))
    return store, tree, graph, data


# ---------------------------------------------------------- plan_migration --
def test_plan_covers_every_row_and_names_true_sources(rng):
    store, w = _store(rng, n_partitions=3, seed=11)
    target = rng.integers(0, 5, w.n_versions).astype(np.int64)
    plan = plan_migration(store, target)
    assert plan.n_partitions == len(np.unique(target))
    for i, (grids, ops) in enumerate(zip(plan.new_grids, plan.ops)):
        # ops tile the new block exactly, in order, without gaps
        covered = 0
        for op in ops:
            assert op.dst_start == covered and op.n_rows > 0
            covered += op.n_rows
            rows = slice(op.dst_start, op.dst_start + op.n_rows)
            if op.kind == "move":
                src = store.partitions[op.src_pid]
                sl = slice(op.src_start, op.src_start + op.n_rows)
                # the named old rows really hold these records
                np.testing.assert_array_equal(src.grids[sl], grids[rows])
            else:
                assert op.src_pid == -1
        assert covered == len(grids)
        # row-level arrays agree with the segment form
        assert (plan.src_pid_rows[i] >= 0).sum() + \
            (plan.src_pid_rows[i] < 0).sum() == len(grids)
    assert plan.rows_moved + plan.rows_loaded == sum(
        len(g) for g in plan.new_grids)


def test_plan_cost_model_intelligent_le_naive(rng):
    store, w = _store(rng, n_partitions=4, seed=5)
    for seed in range(4):
        target = np.random.default_rng(seed).integers(
            0, 6, w.n_versions).astype(np.int64)
        plan = plan_migration(store, target)
        assert 0 <= plan.cost_intelligent <= plan.cost_naive
        assert plan.cost_naive == sum(len(g) for g in plan.new_grids)


def test_plan_identity_migration_costs_nothing_to_morph(rng):
    """Migrating to the CURRENT assignment: every partition matches itself,
    zero inserts + zero deletes, every row moves (device-copyable)."""
    store, w = _store(rng, n_partitions=4, seed=9)
    plan = plan_migration(store, store.assignment)
    assert plan.cost_intelligent == 0
    assert plan.rows_loaded == 0
    assert np.all(plan.matched_old >= 0)


def test_plan_rejects_wrong_length(rng):
    store, w = _store(rng)
    with pytest.raises(ValueError, match="versions"):
        plan_migration(store, np.zeros(w.n_versions + 1, np.int64))


# --------------------------------------------------------- apply_migration --
def test_apply_migration_equals_rebuild_from_scratch(rng):
    store, w = _store(rng, n_partitions=3, seed=21)
    target = rng.integers(0, 5, w.n_versions).astype(np.int64)
    plan = plan_migration(store, target)
    store.apply_migration(plan)
    fresh = PartitionedCVD(w.graph, w.data, target)
    assert len(store.partitions) == len(fresh.partitions)
    np.testing.assert_array_equal(store.vid_to_pid, fresh.vid_to_pid)
    np.testing.assert_array_equal(store.assignment, fresh.assignment)
    for a, b in zip(store.partitions, fresh.partitions):
        assert a.pid == b.pid
        np.testing.assert_array_equal(a.vids, b.vids)
        np.testing.assert_array_equal(a.grids, b.grids)
        np.testing.assert_array_equal(a.block, b.block)
        np.testing.assert_array_equal(a.indptr, b.indptr)
        np.testing.assert_array_equal(a.indices, b.indices)
        assert a.vid_to_slot == b.vid_to_slot
    # every version still checks out bit-identically to the oracle
    for v in range(w.n_versions):
        np.testing.assert_array_equal(store.checkout(v),
                                      w.data[w.graph.rlist(v)])


def test_apply_migration_bumps_epoch_and_rejects_wrong_plan(rng):
    store, w = _store(rng)
    other, _ = _store(rng, n_versions=30, seed=99)
    epoch = store.epoch
    with pytest.raises(ValueError, match="versions"):
        store.apply_migration(plan_migration(other, other.assignment))
    plan = plan_migration(store, np.arange(w.n_versions, dtype=np.int64) % 2)
    store.apply_migration(plan)
    assert store.epoch == epoch + 1


# ------------------------------------------------------ migrate_superblock --
def test_migrate_superblock_bit_identical_and_reuses_device(rng):
    """Kernel path: the migrated superblock (assembled by segment_move off
    the OLD device buffer + a delta upload) is bit-identical
    to a from-scratch rebuild on every valid row, and uploads strictly fewer
    bytes."""
    store, w = _store(rng, n_partitions=3, seed=13)
    sb, _ = get_superblock(store)
    sb.device()
    tree, _ = to_tree(w.graph, w.vgraph)
    target = lyresplit_for_budget(
        tree, 2.0 * w.graph.n_records, max_iters=8).best.assignment
    plan = plan_migration(store, target)
    old_sb = take_superblock(store)
    assert old_sb is sb
    store.apply_migration(plan)
    new_sb, stats = migrate_superblock(store, old_sb, plan, use_kernel=True)
    assert stats.used_device
    assert stats.reused_tiles + stats.delta_tiles == stats.n_tiles
    assert stats.reused_tiles > 0
    assert stats.bytes_uploaded < stats.bytes_total
    # device copy == host copy == what build_superblock would produce
    dev = np.asarray(new_sb._device)
    np.testing.assert_array_equal(dev, new_sb.host)
    fresh = build_superblock(store)
    np.testing.assert_array_equal(new_sb.row_offsets, fresh.row_offsets)
    np.testing.assert_array_equal(new_sb.bounds, fresh.bounds)
    for i, p in enumerate(store.partitions):
        r = p.block.shape[0]
        off = int(fresh.row_offsets[i])
        np.testing.assert_array_equal(new_sb.host[off:off + r, :new_sb.d],
                                      fresh.host[off:off + r, :fresh.d])
    # the migrated superblock is installed: the next wave hits the cache
    cached, hit = get_superblock(store)
    assert hit and cached is new_sb
    outs = checkout_wave(store, list(range(8)), use_kernel=True)
    for v, m in zip(range(8), outs):
        np.testing.assert_array_equal(np.asarray(m), store.checkout(v))


def test_migrate_superblock_host_only_store(rng):
    """No device copy pinned: migration still assembles the host superblock
    incrementally (no upload at all) and stays correct."""
    store, w = _store(rng, n_partitions=4, seed=17)
    get_superblock(store)                        # host copy only, no device()
    target = np.asarray(rng.integers(0, 3, w.n_versions), np.int64)
    plan = plan_migration(store, target)
    old_sb = take_superblock(store)
    store.apply_migration(plan)
    new_sb, stats = migrate_superblock(store, old_sb, plan, use_kernel=False)
    assert not stats.used_device and stats.bytes_uploaded == 0
    outs = checkout_wave(store, [0, 5, 9], use_kernel=False)
    for v, m in zip([0, 5, 9], outs):
        np.testing.assert_array_equal(m, store.checkout(v))


def test_identity_migration_reuses_everything(rng):
    """Migrating to the same assignment re-uploads (near) nothing: every
    tile is a device-to-device copy."""
    store, w = _store(rng, n_partitions=4, seed=19)
    sb, _ = get_superblock(store)
    sb.device()
    plan = plan_migration(store, store.assignment)
    old_sb = take_superblock(store)
    store.apply_migration(plan)
    new_sb, stats = migrate_superblock(store, old_sb, plan, use_kernel=True)
    assert stats.delta_tiles == 0 and stats.bytes_uploaded == 0
    np.testing.assert_array_equal(np.asarray(new_sb._device), old_sb.host)


# ------------------------------------------------- eviction + upload counts --
def test_repartition_evicts_superblock_eagerly(rng):
    store, w = _store(rng)
    sb, _ = get_superblock(store)
    sb.device()
    assert sb.uploads == 1
    store.repartition(np.arange(w.n_versions, dtype=np.int64) % 2)
    # the stale pinned device copy is dropped at the bump, not at next build
    assert sb._device is None
    assert peek_superblock(store) is None
    assert getattr(store, "_superblock_evictions") == 1
    evict_superblocks(store)                     # idempotent on empty cache
    assert store._superblock_evictions == 1


def test_apply_migration_evicts_untaken_superblock(rng):
    store, w = _store(rng)
    sb, _ = get_superblock(store)
    sb.device()
    plan = plan_migration(store, np.asarray(w.graph.version_sizes() > 0,
                                            np.int64) * 0)
    store.apply_migration(plan)                  # nobody took the old sb
    assert sb._device is None and peek_superblock(store) is None
    assert store._superblock_evictions == 1


def test_take_superblock_keeps_device_and_clears_cache(rng):
    store, w = _store(rng)
    sb, _ = get_superblock(store)
    sb.device()
    taken = take_superblock(store)
    assert taken is sb and taken._device is not None
    assert peek_superblock(store) is None
    assert take_superblock(store) is None


# ----------------------------------------------------------- memory budget --
def test_superblock_budget_refuses_and_routes_perpart(rng, caplog):
    store, w = _store(rng, n_partitions=4, seed=23)
    need = estimate_superblock_bytes(store)
    assert need == build_superblock(store).host.nbytes
    store.superblock_max_bytes = need - 1
    with caplog.at_level(logging.WARNING, logger="repro.core.checkout"):
        sb, hit = get_superblock(store, max_bytes=store.superblock_max_bytes)
        assert sb is None and not hit
        # multi-partition kernel wave: refused the pin, still correct
        vids = [0, 5, 9, 13]
        outs = checkout_wave(store, vids, use_kernel=True)
        for v, m in zip(vids, outs):
            np.testing.assert_array_equal(np.asarray(m), store.checkout(v))
        assert peek_superblock(store) is None    # never built one
        get_superblock(store, max_bytes=store.superblock_max_bytes)
    # the refusal is logged ONCE per store, not per wave
    msgs = [r for r in caplog.records if "max_bytes" in r.getMessage()]
    assert len(msgs) == 1
    # raising the budget un-refuses
    store.superblock_max_bytes = need
    sb, _ = get_superblock(store, max_bytes=store.superblock_max_bytes)
    assert sb is not None
    # an already-cached copy is served even over budget (memory already paid)
    sb2, hit = get_superblock(store, max_bytes=1)
    assert hit and sb2 is sb


def test_serve_warmup_respects_budget(rng):
    store, w = _store(rng)
    store.superblock_max_bytes = 1
    srv = BatchedCheckoutServer(store, use_kernel=False)
    srv.warmup()                                 # must not build or raise
    assert peek_superblock(store) is None
    outs = srv.serve([1, 2])
    for v, m in zip([1, 2], outs):
        np.testing.assert_array_equal(m, store.checkout(v))


# -------------------------------------------------------- density telemetry --
def test_density_recorded_on_all_paths(rng):
    store, w = _store(rng, n_partitions=3, seed=29)
    vids = [0, 4, 9]
    # telemetry is OPT-IN: an unmonitored store records nothing (query-only
    # users must not pay the measurement)
    checkout_wave(store, vids, use_kernel=False)
    assert get_density_stats(store) is None
    stats = get_density_stats(store, create=True)
    checkout_wave(store, vids, use_kernel=False)          # perpart host path
    assert stats.waves == 1
    assert set(stats.per_vid) == set(vids)
    get_superblock(store)
    checkout_wave(store, vids, use_kernel=False)          # fused host path
    checkout_wave(store, vids, use_kernel=True)           # kernel wave path
    assert stats.waves == 3
    checkout_wave(store, vids, use_kernel=False, record_density=False)
    assert stats.waves == 3                               # opt-out honored
    # the three paths measure the SAME density for the same wave
    d_local = measure_density(
        [store.partitions[int(store.vid_to_pid[v])].local_rlist(v)
         for v in vids], build_superblock(store).block_n)[0]
    for v, d in zip(vids, d_local):
        assert stats.per_vid[v] == pytest.approx(float(d))


def test_short_dense_versions_measure_full_density(rng):
    """Regression: a consecutive rlist shorter than BN goes out as ONE
    promoted tail-run DMA — telemetry must measure it 1.0, not 0.0, on
    every path (a 0.0 here would spuriously fire the repartition trigger
    on already-optimal traffic)."""
    dens, tiles = measure_density([np.arange(3, dtype=np.int64),
                                   np.array([0, 5, 9], np.int64)], 8)
    assert dens[0] == 1.0 and tiles[0] == 1
    assert dens[1] == 0.0
    # end-to-end through the planned kernel wave: two dense ragged versions
    n = 3 * 8 + 3
    data = np.arange(n * 4, dtype=np.int32).reshape(n, 4)
    rls = [np.arange(0, n, dtype=np.int64),
           np.arange(n - 2, n, dtype=np.int64)]          # 2 rows: tail-only
    graph = BipartiteGraph.from_rlists(rls, n_records=n)
    store = PartitionedCVD(graph, data, np.zeros(2, np.int64))
    stats = get_density_stats(store, create=True)
    get_superblock(store)
    checkout_wave(store, [0, 1], use_kernel=True)
    assert stats.per_vid[0] == 1.0 and stats.per_vid[1] == 1.0
    assert stats.low_streak == 0


def test_trigger_default_reuses_live_device_buffer(rng):
    """Regression: with ``use_kernel`` left at None the migration must
    still consume a LIVE old device buffer (backend probe must not demote
    it to a full re-upload off-TPU)."""
    store, tree, graph, data = _scattered_store(
        rng, n_versions=8, n_records=256, size=16)
    get_superblock(store)[0].device()
    trig = RepartitionTrigger(store, tree, min_waves=1)   # use_kernel=None
    checkout_wave(store, [0, 1, 2], use_kernel=True)
    rep = trig.observe()
    assert rep is not None
    assert rep.superblock is not None and rep.superblock.used_device
    for v in range(graph.n_versions):
        np.testing.assert_array_equal(store.checkout(v), data[graph.rlist(v)])


def test_migrated_superblock_installs_under_original_cache_key(rng):
    """Regression: a superblock cached under non-default get_superblock
    args must migrate back into the SAME cache slot, or the next same-args
    wave rebuilds (and double-pins) from scratch."""
    store, w = _store(rng, n_partitions=3, seed=27)
    sb, _ = get_superblock(store, block_n=16)
    assert sb.block_n == 16
    plan = plan_migration(store, np.asarray(
        np.arange(w.n_versions) % 2, np.int64))
    old_sb = take_superblock(store)
    store.apply_migration(plan)
    new_sb, _ = migrate_superblock(store, old_sb, plan, use_kernel=False)
    cached, hit = get_superblock(store, block_n=16)
    assert hit and cached is new_sb and cached.block_n == 16


def test_low_density_streak_and_reset(rng):
    store, tree, graph, data = _scattered_store(rng)
    stats = get_density_stats(store, create=True)
    for i in range(3):
        checkout_wave(store, [0, 1, 2], use_kernel=False)
        assert stats.low_streak == i + 1
    stats.reset()
    assert stats.low_streak == 0 and stats.per_vid == {}
    assert stats.waves == 3                               # all-time survives


def test_empty_wave_does_not_break_the_streak():
    """A wave of zero-tile gathers is no evidence of density either way —
    it must neither grow nor reset a low streak."""
    from repro.core.checkout import DensityStats
    s = DensityStats()
    s.record([0], np.array([0.0]), np.array([4]))          # low wave
    assert s.low_streak == 1
    s.record([1], np.array([1.0]), np.array([0]))          # empty wave
    assert s.low_streak == 1 and s.waves == 2
    s.record([0], np.array([0.0]), np.array([4]))          # low again
    assert s.low_streak == 2


def test_serve_rejects_trigger_on_perpart_engine(rng):
    """engine='perpart' never records density, so a trigger there would be
    silently inert — reject the combination loudly."""
    store, tree, graph, data = _scattered_store(rng)
    trig = RepartitionTrigger(store, tree)
    with pytest.raises(ValueError, match="wave"):
        BatchedCheckoutServer(store, engine="perpart", trigger=trig)


# --------------------------------------------------------- trigger + serve --
def test_trigger_fires_and_improves_density(rng):
    store, tree, graph, data = _scattered_store(rng)
    trig = RepartitionTrigger(store, tree, min_waves=3, low_density=0.5,
                              use_kernel=False)
    assert trig.observe() is None                         # no streak yet
    for _ in range(3):
        checkout_wave(store, [0, 3, 7, 11], use_kernel=False)
    assert trig.should_fire()
    rep = trig.observe()
    assert rep is not None and rep.n_partitions_after > 1
    assert rep.cost_intelligent <= rep.cost_naive
    assert rep.c_avg_after < rep.c_avg_before
    # post-migration: every version still bit-identical to the oracle
    for v in range(graph.n_versions):
        np.testing.assert_array_equal(store.checkout(v), data[graph.rlist(v)])
    # and the re-clustered layout measures dense
    checkout_wave(store, [0, 3, 7, 11], use_kernel=False)
    assert get_density_stats(store).last_wave_density == 1.0


def test_trigger_noop_when_already_optimal(rng):
    """Dense store already at the LYRESPLIT partitioning: even a forced
    low-density streak must not churn the layout (same-partitioning and
    min-gain guards)."""
    store, tree, graph, data = _scattered_store(rng)
    trig = RepartitionTrigger(store, tree, min_waves=1, use_kernel=False)
    for _ in range(2):
        checkout_wave(store, [0, 1], use_kernel=False)
    assert trig.observe() is not None                     # first fire adopts
    epoch = store.epoch
    stats = get_density_stats(store)
    stats.low_streak = 5                                  # fake a streak
    assert trig.observe() is None                         # guards hold
    assert store.epoch == epoch
    assert stats.low_streak == 0                          # signal consumed


def test_serve_trigger_between_flushes_kernel_path(rng):
    """The full loop through the serve layer on the KERNEL tier: scattered
    waves -> trigger -> apply_migration + migrate_superblock -> later waves
    run off the migrated device superblock, results bit-identical."""
    store, tree, graph, data = _scattered_store(
        rng, n_versions=12, n_records=512, size=24)
    trig = RepartitionTrigger(store, tree, min_waves=2, use_kernel=True)
    srv = BatchedCheckoutServer(store, use_kernel=True, trigger=trig)
    srv.warmup()
    served = []
    for _ in range(4):
        vids = [int(v) for v in rng.integers(0, graph.n_versions, 4)]
        served.append((vids, srv.serve(vids)))
    assert srv.stats.repartitions == 1
    rep = trig.reports[0]
    assert rep.superblock is not None and rep.superblock.used_device
    for vids, outs in served:
        for v, m in zip(vids, outs):
            np.testing.assert_array_equal(np.asarray(m),
                                          data[graph.rlist(v)])


def test_same_partitioning_is_label_invariant():
    a = np.array([0, 0, 1, 2, 1])
    b = np.array([7, 7, 3, 0, 3])                         # same cells
    c = np.array([0, 1, 1, 2, 1])
    assert _same_partitioning(a, b)
    assert not _same_partitioning(a, c)
    assert not _same_partitioning(a, np.array([0, 0, 1]))


# ------------------------------------------------- Fig-14 workload property --
def test_fig14_stream_intelligent_cheaper_and_upload_small(rng):
    """The paper's headline (Figs 14-15) on an SCI commit stream: migrating
    a drifted online assignment to the fresh LYRESPLIT one costs less than
    rebuilding (record-row unit) AND re-uploads a small fraction of the
    superblock bytes."""
    w = generate("SCI", n_versions=120, inserts=40, n_branches=10, n_attrs=4,
                 seed=7)
    tree, _ = to_tree(w.graph, w.vgraph)
    sr = lyresplit_for_budget(tree, 2.0 * w.graph.n_records, max_iters=12)
    base = sr.best.assignment.copy()
    # drift: a handful of versions re-homed to their parent's partition
    drifted = base.copy()
    for v in rng.choice(np.flatnonzero(tree.parent >= 0), 8, replace=False):
        drifted[v] = drifted[int(tree.parent[v])]
    store = PartitionedCVD(w.graph, w.data, drifted)
    sb, _ = get_superblock(store)
    sb.device()
    plan = plan_migration(store, base)
    assert plan.cost_intelligent <= plan.cost_naive
    assert plan.cost_intelligent < plan.cost_naive      # strictly: overlap
    old_sb = take_superblock(store)
    store.apply_migration(plan)
    new_sb, stats = migrate_superblock(store, old_sb, plan, use_kernel=True)
    assert stats.bytes_uploaded < 0.25 * stats.bytes_total
    for v in range(0, w.n_versions, 7):
        np.testing.assert_array_equal(store.checkout(v),
                                      w.data[w.graph.rlist(v)])


# ------------------------------------------------------- property (streams) --
def _check_stream(rls, n_records, start, target):
    """THE migration property, for one random commit stream and an ARBITRARY
    re-assignment: after apply_migration + migrate_superblock every
    version's checkout is bit-identical to the NumPy oracle, the migrated
    superblock equals a from-scratch rebuild on every valid row, and the
    plan's intelligent cost never exceeds naive."""
    graph = BipartiteGraph.from_rlists(rls, n_records=n_records)
    data = np.arange(n_records * 3, dtype=np.int32).reshape(n_records, 3)
    store = PartitionedCVD(graph, data, start)
    get_superblock(store)                       # host copy to migrate
    plan = plan_migration(store, target)
    assert plan.cost_intelligent <= plan.cost_naive
    old_sb = take_superblock(store)
    store.apply_migration(plan)
    new_sb, stats = migrate_superblock(store, old_sb, plan, use_kernel=False)
    fresh = build_superblock(store)
    for i, p in enumerate(store.partitions):
        r = p.block.shape[0]
        off = int(fresh.row_offsets[i])
        np.testing.assert_array_equal(new_sb.host[off:off + r, :new_sb.d],
                                      fresh.host[off:off + r, :fresh.d])
    for v in range(graph.n_versions):
        np.testing.assert_array_equal(store.checkout(v), data[graph.rlist(v)])
    outs = checkout_wave(store, list(range(graph.n_versions)),
                         use_kernel=False)
    for v, m in zip(range(graph.n_versions), outs):
        np.testing.assert_array_equal(m, data[graph.rlist(v)])


def _random_stream(rng):
    """A random version tree + rlists grown commit-by-commit: each version
    keeps a random subset of its parent's records and allocates fresh
    ones."""
    n = int(rng.integers(2, 11))
    rls = [np.arange(int(rng.integers(1, 13)), dtype=np.int64)]
    next_rid = len(rls[0])
    for v in range(1, n):
        p = int(rng.integers(0, v))
        keep_n = int(rng.integers(0, len(rls[p]) + 1))
        keep = np.sort(rng.choice(rls[p], keep_n, replace=False)) if keep_n \
            else np.zeros(0, np.int64)
        fresh_n = int(rng.integers(1, 11))
        fresh = np.arange(next_rid, next_rid + fresh_n, dtype=np.int64)
        next_rid += fresh_n
        rls.append(np.sort(np.concatenate([keep, fresh])))
    start = rng.integers(0, int(rng.integers(1, 4)), n).astype(np.int64)
    target = rng.integers(0, int(rng.integers(1, 5)), n).astype(np.int64)
    return rls, next_rid, start, target


def test_property_migration_preserves_every_checkout_seeded():
    """Deterministic sweep of the stream property (always runs, even where
    hypothesis is absent)."""
    rng = np.random.default_rng(1234)
    for _ in range(20):
        _check_stream(*_random_stream(rng))


try:
    from hypothesis import given, settings, strategies as st
except ImportError:                              # pragma: no cover
    st = None

if st is not None:
    @st.composite
    def commit_streams(draw):
        """The same stream family, hypothesis-driven (shrinks on failure)."""
        n = draw(st.integers(min_value=2, max_value=10))
        rls = [np.arange(draw(st.integers(min_value=1, max_value=12)),
                         dtype=np.int64)]
        next_rid = len(rls[0])
        for v in range(1, n):
            p = draw(st.integers(min_value=0, max_value=v - 1))
            keep_n = draw(st.integers(min_value=0, max_value=len(rls[p])))
            keep = rls[p][:keep_n] if keep_n else np.zeros(0, np.int64)
            fresh_n = draw(st.integers(min_value=1, max_value=10))
            fresh = np.arange(next_rid, next_rid + fresh_n, dtype=np.int64)
            next_rid += fresh_n
            rls.append(np.sort(np.concatenate([keep, fresh])))
        p_old = draw(st.integers(min_value=1, max_value=3))
        p_new = draw(st.integers(min_value=1, max_value=4))
        start = np.asarray([draw(st.integers(0, p_old - 1))
                            for _ in range(n)], np.int64)
        target = np.asarray([draw(st.integers(0, p_new - 1))
                             for _ in range(n)], np.int64)
        return rls, next_rid, start, target

    @given(commit_streams())
    @settings(max_examples=25, deadline=None)
    def test_property_migration_preserves_every_checkout(stream):
        _check_stream(*stream)
