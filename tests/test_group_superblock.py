"""Partition-group superblocks: budget-aware partial fusion.

Covers the group former (hot-set packing under the byte budget), wave
routing/splitting (one fused launch per touched pinned group, perpart only
for genuine stragglers), LRU eviction + the pinned-bytes invariant, the
re-armable budget refusal log, per-group epoch-bump migration, the
HotSetPolicy ranking, the serve-layer group stats, and the leak regression
(50 epochs of trigger->migrate->evict keep counters balanced and release
every device buffer).
"""
import importlib
import logging

import numpy as np
import pytest

from repro.core import generate
from repro.core.checkout import (build_superblock,
                                 checkout_partitioned_perpart, checkout_wave,
                                 estimate_superblock_bytes, get_density_stats,
                                 get_superblock, get_superblock_groups,
                                 migrate_superblock, partition_segment_bytes,
                                 peek_superblock)
from repro.core.graph import BipartiteGraph
from repro.core.online import (HotSetPolicy, RepartitionTrigger,
                               get_hot_set_policy)
from repro.core.partition import PartitionedCVD, plan_migration
from repro.core.version_graph import WeightedTree
from repro.serve.checkout import BatchedCheckoutServer

_ops = importlib.import_module("repro.kernels.ops")


def _sci_store(rng, n_versions=24, n_partitions=6, seed=3, n_attrs=12):
    w = generate("SCI", n_versions=n_versions, inserts=100, n_branches=4,
                 n_attrs=n_attrs, seed=seed)
    assignment = rng.permutation(np.arange(w.n_versions) % n_partitions)
    return PartitionedCVD(w.graph, w.data, assignment), w


def _uniform_store(rng, p=8, n_versions=32, r=1024, rows=24, d=12):
    """Uniform partitions (v -> v%p), half dense-run / half scattered
    versions — group byte sizes come out near-equal, so budget fractions
    translate predictably into co-pinnable group counts."""
    rls = []
    for v in range(n_versions):
        if v % 2 == 0:
            s = int(rng.integers(0, r - rows))
            rls.append(np.arange(s, s + rows, dtype=np.int64))
        else:
            rls.append(np.sort(rng.choice(r, rows, replace=False))
                       .astype(np.int64))
    graph = BipartiteGraph.from_rlists(rls, n_records=r)
    data = rng.integers(0, 1 << 20, (r, d)).astype(np.int32)
    return PartitionedCVD(graph, data, np.arange(n_versions) % p)


def _assert_wave_equal(store, vids, **kw):
    base = checkout_partitioned_perpart(store, vids, use_kernel=False)
    got = checkout_wave(store, vids, **kw)
    for g, b in zip(got, base):
        np.testing.assert_array_equal(np.asarray(g), b)
        assert np.asarray(g).dtype == b.dtype


def _count_ops_launches(monkeypatch, calls):
    real = _ops.checkout_wave

    def counted(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(_ops, "checkout_wave", counted)


# ------------------------------------------------------------- correctness --
@pytest.mark.parametrize("budget_kind", ["zero", "tiny", "quarter", "half",
                                         "exact", "unlimited"])
def test_grouped_wave_matches_perpart(rng, budget_kind):
    """Grouped-wave checkout is bit-identical to the perpart oracle across
    the budget spectrum (0 / partial / exact-fit / unlimited), on both
    tiers, with duplicate and unsorted vids."""
    store, w = _sci_store(rng, seed=11)
    need = estimate_superblock_bytes(store)
    budget = {"zero": 0, "tiny": 1, "quarter": need // 4, "half": need // 2,
              "exact": need, "unlimited": None}[budget_kind]
    store.superblock_max_bytes = budget
    vids = list(rng.integers(0, w.n_versions, 9)) + [3, 3, 0]  # dups, unsorted
    _assert_wave_equal(store, vids, use_kernel=False)   # no groups pinned yet
    _assert_wave_equal(store, vids, use_kernel=True)    # pins groups (kernel)
    _assert_wave_equal(store, vids, use_kernel=True)    # pinned-group replay
    _assert_wave_equal(store, vids, use_kernel=False)   # host free fusion
    mgr = get_superblock_groups(store)
    if budget_kind in ("exact", "unlimited"):
        # the whole-store fast path: the group layer never engages
        assert mgr is None
        assert peek_superblock(store) is not None
    else:
        assert mgr is not None
        assert mgr.pinned_bytes <= mgr.budget
        assert mgr.pinned_bytes == sum(
            int(sb.host.nbytes) for sb in mgr.groups.values())
        assert mgr.pins - mgr.evictions == len(mgr.groups)


def test_grouped_wave_empty_and_single_vid(rng):
    store, w = _sci_store(rng, seed=13)
    store.superblock_max_bytes = estimate_superblock_bytes(store) // 4
    assert checkout_wave(store, [], use_kernel=True) == []
    _assert_wave_equal(store, [7], use_kernel=True)
    with pytest.raises(ValueError, match="unknown version"):
        checkout_wave(store, [w.n_versions + 1], use_kernel=True)


def test_perpart_kernel_on_tiny_partition_block(rng):
    """Regression (found by the grouped-wave property sweep): a partition
    block SHORTER than one row tile (R < BN) used to fail the kernel path
    at trace time — the run-DMA dynamic_slice is statically (BN, BD) and
    the data operand was only padded along D.  Stragglers route such
    partitions through checkout_batched, so the tiny-block case must
    work."""
    rls = [np.array([0, 1, 2], np.int64), np.array([2, 0], np.int64)]
    graph = BipartiteGraph.from_rlists(rls, n_records=3)
    data = rng.integers(0, 1 << 20, (3, 5)).astype(np.int32)
    store = PartitionedCVD(graph, data, np.array([0, 1], np.int64))
    _assert_wave_equal(store, [0, 1, 1], use_kernel=True)
    store.superblock_max_bytes = 0            # every partition a straggler
    _assert_wave_equal(store, [0, 1, 1], use_kernel=True)


# ------------------------------------------------- launch-count accounting --
def test_launches_equal_touched_pinned_groups(rng, monkeypatch):
    """Acceptance: with the budget at a fraction of the full superblock, a
    wave executes ONE fused kernel launch per touched pinned group — no
    more (no per-partition launches), no stragglers when the touched
    groups co-fit."""
    store = _uniform_store(rng, p=8)
    need = estimate_superblock_bytes(store)
    store.superblock_max_bytes = need - 1     # over budget; cap ~= need/4
    # touch partitions 0..3 only: their groups co-fit in the budget
    vids = [v for v in range(16)]             # v%8 -> partitions 0..7... trim
    vids = [v for v in vids if v % 8 < 4]
    calls: list[int] = []
    _count_ops_launches(monkeypatch, calls)
    _assert_wave_equal(store, vids, use_kernel=True)    # cold: pins + fuses
    mgr = get_superblock_groups(store)
    assert mgr is not None and mgr.last_wave is not None
    touched_pinned = len({mgr.pid_to_group[int(store.vid_to_pid[v])]
                          for v in vids
                          if mgr.pid_to_group.get(int(store.vid_to_pid[v]))
                          in mgr.groups})
    assert mgr.last_wave.straggler_vids == 0
    assert mgr.last_wave.launches == touched_pinned == len(calls)
    assert mgr.last_wave.groups_touched >= touched_pinned
    # warm replay: same groups, same launch count, no new pins
    calls.clear()
    _assert_wave_equal(store, vids, use_kernel=True)
    assert mgr.last_wave.launches == touched_pinned == len(calls)
    assert mgr.last_wave.pinned == 0 and mgr.last_wave.evictions == 0


def test_single_fused_pallas_call_per_group(rng, monkeypatch):
    """Each touched pinned group is exactly ONE pallas_call (trace-time
    count; the odd store dims force fresh traces)."""
    _cb = importlib.import_module("repro.kernels.checkout_batched")
    store = _uniform_store(rng, p=4, n_versions=20, r=651, rows=19, d=13)
    need = estimate_superblock_bytes(store)
    store.superblock_max_bytes = need - 1
    calls = []
    real = _cb.pl.pallas_call

    def spy(*a, **kw):
        calls.append(kw.get("grid"))
        return real(*a, **kw)

    monkeypatch.setattr(_cb.pl, "pallas_call", spy)
    _cb.checkout_wave.clear_cache()    # force a fresh trace: count is exact
    # partitions 0 (three vids) and 1 (one vid): the two groups' plan
    # shapes differ, so each launch is a fresh trace (same-shape launches
    # would share one compiled trace and hide the second pallas_call)
    vids = [0, 4, 8, 1]
    _assert_wave_equal(store, vids, use_kernel=True)
    mgr = get_superblock_groups(store)
    assert mgr.last_wave.straggler_vids == 0
    assert len(calls) == mgr.last_wave.launches


# ------------------------------------------------------------ LRU eviction --
def test_group_lru_eviction_keeps_pinned_bytes_under_budget(rng):
    """Disjoint traffic phases bigger than the budget force LRU eviction of
    the cold phase's groups; pinned bytes never exceed the budget and the
    pin/eviction counters stay balanced."""
    store = _uniform_store(rng, p=8)
    need = estimate_superblock_bytes(store)
    store.superblock_max_bytes = need // 3    # roomy enough for one phase
    phase_a = [v for v in range(32) if v % 8 in (0, 1)]
    phase_b = [v for v in range(32) if v % 8 in (4, 5)]
    mgr = None
    for _ in range(3):
        for vids in (phase_a, phase_b):
            _assert_wave_equal(store, vids, use_kernel=True)
            mgr = get_superblock_groups(store)
            assert mgr.pinned_bytes <= mgr.budget
            assert mgr.pinned_bytes == sum(
                int(sb.host.nbytes) for sb in mgr.groups.values())
            assert mgr.pins - mgr.evictions == len(mgr.groups)
    assert mgr.evictions > 0                  # phases actually displaced
    # intra-wave protection: a wave never evicts a group it still needs —
    # groups it could not co-pin route perpart instead
    both = phase_a + phase_b
    _assert_wave_equal(store, both, use_kernel=True)
    assert mgr.pinned_bytes <= mgr.budget


def test_per_call_max_bytes_does_not_thrash_shared_groups(rng):
    """A caller passing its own max_bytes override must not mutate the
    store-shared group manager's budget (that would evict every other
    caller's pinned groups); only a store-level budget change re-forms."""
    store, w = _sci_store(rng, seed=41)
    need = estimate_superblock_bytes(store)
    store.superblock_max_bytes = need // 4
    vids = [0, 5, 9, 13]
    checkout_wave(store, vids, use_kernel=True)
    mgr = get_superblock_groups(store)
    assert len(mgr.groups) > 0
    ev0, budget0 = mgr.evictions, mgr.budget
    base = checkout_partitioned_perpart(store, vids, use_kernel=False)
    got = checkout_wave(store, vids, use_kernel=True, max_bytes=need // 3)
    for g, b in zip(got, base):
        np.testing.assert_array_equal(np.asarray(g), b)
    assert mgr.budget == budget0                  # override didn't mutate
    assert mgr.evictions == ev0                   # pins survived
    # a store-LEVEL budget change does re-form the groups
    store.superblock_max_bytes = need // 2
    checkout_wave(store, vids, use_kernel=True)
    assert mgr.budget == need // 2
    assert mgr.evictions > ev0


def test_full_superblock_build_releases_group_pins(rng):
    store, w = _sci_store(rng, seed=17)
    need = estimate_superblock_bytes(store)
    store.superblock_max_bytes = need // 4
    checkout_wave(store, [0, 5, 9, 13], use_kernel=True)
    mgr = get_superblock_groups(store)
    assert mgr is not None and len(mgr.groups) > 0
    store.superblock_max_bytes = need         # budget raised: full sb wins
    sb, _ = get_superblock(store, max_bytes=need)
    assert sb is not None
    assert len(mgr.groups) == 0 and mgr.pinned_bytes == 0


# --------------------------------------------------------- budget log re-arm --
def test_budget_log_rearmed_on_budget_or_epoch_change(rng, caplog):
    """The refusal log is once-per-state, not once-per-store: changing the
    budget value or bumping the epoch re-arms it."""
    store, w = _sci_store(rng, seed=19)
    need = estimate_superblock_bytes(store)
    with caplog.at_level(logging.WARNING, logger="repro.core.checkout"):
        get_superblock(store, max_bytes=need - 1)
        get_superblock(store, max_bytes=need - 1)     # same state: silent
        assert len([r for r in caplog.records
                    if "max_bytes" in r.getMessage()]) == 1
        get_superblock(store, max_bytes=need // 2)    # budget changed
        assert len([r for r in caplog.records
                    if "max_bytes" in r.getMessage()]) == 2
        get_superblock(store, max_bytes=need // 2)
        assert len([r for r in caplog.records
                    if "max_bytes" in r.getMessage()]) == 2
        store.repartition(store.assignment.copy())    # epoch bumped
        get_superblock(store, max_bytes=need // 2)
        assert len([r for r in caplog.records
                    if "max_bytes" in r.getMessage()]) == 3


# ------------------------------------------------------------ hot-set policy --
def test_hot_set_policy_touch_ewma_and_rank(rng):
    pol = HotSetPolicy(alpha=0.2)
    for _ in range(4):
        pol.touch([0, 2])
    pol.touch([1])
    # 0 and 2 carry history; 1 was only just touched once — and the lazy
    # decay must match the eager semantics: w(0) = 0.2*Σ(0.8^k), k=1..4
    assert pol.weight(0) > pol.weight(1)
    assert pol.weight(0) == pytest.approx(
        0.2 * sum(0.8 ** k for k in range(1, 5)))
    assert pol.weight(1) == pytest.approx(0.2)
    assert pol.weight(3) == 0.0
    store, _ = _sci_store(rng, n_partitions=4, seed=23)
    order = [int(q) for q in pol.rank(store, 4)]
    assert set(order) == {0, 1, 2, 3}
    assert order.index(0) < order.index(1) < order.index(3)
    # density EWMA breaks ties between equally-touched partitions
    stats = get_density_stats(store, create=True)
    cold = [p for p in order if p == 3]
    assert cold  # partition 3 untouched -> ranked last
    pol2 = HotSetPolicy()
    dense_vid = int(np.flatnonzero(store.vid_to_pid == 2)[0])
    stats.per_vid = {dense_vid: 1.0}
    order2 = [int(q) for q in pol2.rank(store, 4)]
    assert order2[0] == 2                    # untouched everywhere: density wins
    # remap carries heat through a morph map; reset drops it
    w2 = pol.weight(2)
    pol.remap([2, -1, 0])                     # new 0 <- old 2, new 2 <- old 0
    assert pol.weight(0) == pytest.approx(w2)
    assert pol.weight(1) == 0.0               # from-scratch: starts cold
    pol.reset()
    assert not pol.touch_ewma


def test_group_former_packs_hot_partitions_first(rng):
    store = _uniform_store(rng, p=8)
    need = estimate_superblock_bytes(store)
    store.superblock_max_bytes = need - 1
    pol = get_hot_set_policy(store, create=True)
    for _ in range(5):
        pol.touch([6, 7])                     # partitions 6,7 are the hot set
    checkout_wave(store, [6, 7, 14, 15], use_kernel=True)   # vids -> pids 6,7
    mgr = get_superblock_groups(store)
    first_group = mgr.planned[0]
    assert 6 in first_group or 7 in first_group
    # the hot pair lands in one co-resident group and is pinned
    assert mgr.pid_to_group[6] in mgr.groups or mgr.pid_to_group[7] in mgr.groups


def test_regroup_consolidates_hot_partitions(rng):
    """regroup() re-forms groups from the current heat: hot partitions that
    the initial (cold) plan scattered across pid-order groups consolidate
    into the leading co-resident groups."""
    store = _uniform_store(rng, p=8)
    need = estimate_superblock_bytes(store)
    store.superblock_max_bytes = need - 1
    hot = [2, 5, 7]
    hot_vids = [v for v in range(32) if v % 8 in hot]
    for _ in range(4):
        checkout_wave(store, hot_vids, use_kernel=True)
    mgr = get_superblock_groups(store)
    mgr.regroup()
    lead = [q for key in mgr.planned for q in key][:len(hot)]
    assert set(lead) == set(hot)
    # next wave re-pins the consolidated hot groups and still matches
    _assert_wave_equal(store, hot_vids, use_kernel=True)
    assert mgr.pinned_bytes <= mgr.budget


def test_auto_regroup_fires_on_hot_set_drift(rng):
    """The heat-driven automatic regroup: once the LIVE hot ranking
    drifts past ``drift_threshold`` from the prefix the plan packed
    around, the periodic ``maybe_regroup`` checkpoint re-forms the
    groups from current heat — hot partitions consolidate without an
    explicit ``regroup()`` call, and serving stays bit-identical."""
    store = _uniform_store(rng, p=8)
    store.superblock_max_bytes = estimate_superblock_bytes(store) - 1
    pol = get_hot_set_policy(store, create=True)
    for _ in range(6):
        pol.touch([0, 1])                         # initial hot set {0, 1}
    phase_a = [v for v in range(32) if v % 8 in (0, 1)]
    _assert_wave_equal(store, phase_a, use_kernel=True)
    mgr = get_superblock_groups(store)
    assert mgr.regroup_drift() == 0.0             # plan matches live heat
    mgr.auto_regroup_every = 2                    # tighten for the test
    # traffic shifts wholesale to partitions {6, 7}: the EWMA re-ranks,
    # drift crosses the threshold, and a periodic wave checkpoint fires
    # the regroup on its own
    for _ in range(40):
        pol.touch([6, 7])
    assert mgr.regroup_drift() >= mgr.drift_threshold
    phase_b = [v for v in range(32) if v % 8 in (6, 7)]
    for _ in range(4):
        _assert_wave_equal(store, phase_b, use_kernel=True)
    assert mgr.auto_regroups >= 1
    assert mgr.regroup_drift() < mgr.drift_threshold
    lead = [q for key in mgr.planned for q in key][:2]
    assert set(lead) == {6, 7}                    # hot pair consolidated
    assert mgr.pinned_bytes <= mgr.budget
    assert mgr.pins - mgr.evictions == len(mgr.groups)


def test_oversize_partition_is_permanent_straggler(rng):
    store = _uniform_store(rng, p=4)
    seg = partition_segment_bytes(store)
    store.superblock_max_bytes = int(seg.max()) - 1   # biggest can't ever pin
    vids = list(range(8))
    _assert_wave_equal(store, vids, use_kernel=True)
    mgr = get_superblock_groups(store)
    big = int(np.argmax(seg))
    assert big in mgr.straggler_pids
    assert mgr.last_wave.straggler_vids > 0


def _assert_valid_rows_equal(store, got_sb, want_sb):
    """Migrated superblocks are compared on VALID rows only: BN-alignment
    pad rows are never addressed by any rlist (runs reading into them land
    in the sliced-off output region), and the incremental path deliberately
    reuses whole old tiles, stale pad content included."""
    pids = want_sb.pids if want_sb.pids is not None \
        else np.arange(len(want_sb.row_offsets))
    for s, pid in enumerate(pids):
        r = store.partitions[int(pid)].block.shape[0]
        off_g, off_w = int(got_sb.row_offsets[s]), int(want_sb.row_offsets[s])
        np.testing.assert_array_equal(
            got_sb.host[off_g:off_g + r, :got_sb.d],
            want_sb.host[off_w:off_w + r, :want_sb.d])


def dataclasses_replace_host(sb, host):
    import dataclasses as _dc
    return _dc.replace(sb, host=host, _slot_of=None)


# -------------------------------------------------- per-group epoch migration --
def test_epoch_bump_migrates_groups_instead_of_nuking(rng):
    """apply_migration detaches pinned group superblocks and re-pins them
    migrated (bit-identical to a fresh group build) instead of evicting;
    waves after the bump still match the oracle."""
    store, w = _sci_store(rng, n_partitions=5, seed=29)
    need = estimate_superblock_bytes(store)
    store.superblock_max_bytes = need - 1
    vids = [int(v) for v in rng.integers(0, w.n_versions, 10)]
    checkout_wave(store, vids, use_kernel=True)       # pin some groups
    mgr = get_superblock_groups(store)
    assert len(mgr.groups) > 0
    pins_before = mgr.pins
    # a mild re-homing migration (most partitions morph in place)
    new_assignment = store.assignment.copy()
    new_assignment[w.n_versions - 1] = new_assignment[0]
    plan = plan_migration(store, new_assignment)
    store.apply_migration(plan)
    assert mgr.pins > pins_before             # at least one group re-pinned
    for key, sb in mgr.groups.items():
        assert sb.epoch == store.epoch
        fresh = build_superblock(store, pids=list(key))
        _assert_valid_rows_equal(store, sb, fresh)
        if sb._device is not None:            # device path migrated too
            dev = dataclasses_replace_host(sb, np.asarray(sb._device))
            _assert_valid_rows_equal(store, dev, fresh)
    _assert_wave_equal(store, vids, use_kernel=True)
    assert mgr.pinned_bytes <= mgr.budget


def test_migrate_superblock_group_pids_matches_rebuild(rng):
    """Direct per-group migrate_superblock(pids=...): host mirror and device
    result equal a from-scratch group build after the morph."""
    store, w = _sci_store(rng, n_partitions=4, seed=31)
    sb0 = build_superblock(store, pids=[1, 2])
    sb0.device()
    new_assignment = store.assignment.copy()
    new_assignment[0] = new_assignment[1]
    plan = plan_migration(store, new_assignment)
    store.apply_migration(plan)
    matched = np.asarray(plan.matched_old)
    new_pids = sorted(int(i) for i in np.flatnonzero(matched >= 0)
                      if int(matched[i]) in (1, 2))
    if not new_pids:
        pytest.skip("morph dissolved both partitions (degenerate draw)")
    new_sb, mstats = migrate_superblock(store, sb0, plan, pids=new_pids,
                                        use_kernel=True, install=False)
    fresh = build_superblock(store, pids=new_pids)
    _assert_valid_rows_equal(store, new_sb, fresh)
    dev = dataclasses_replace_host(new_sb, np.asarray(new_sb._device))
    _assert_valid_rows_equal(store, dev, fresh)
    assert [int(q) for q in new_sb.pids] == new_pids
    assert mstats.n_tiles > 0
    assert peek_superblock(store) is None     # install=False: nothing cached


# ------------------------------------------------------------- serve layer --
def test_serve_stats_and_group_warmup(rng):
    store = _uniform_store(rng, p=8)
    need = estimate_superblock_bytes(store)
    store.superblock_max_bytes = need // 3
    srv = BatchedCheckoutServer(store, use_kernel=True)
    srv.warmup()
    mgr = get_superblock_groups(store)
    assert mgr is not None and len(mgr.groups) > 0    # hot groups pre-pinned
    assert mgr.pinned_bytes <= mgr.budget
    for sb in mgr.groups.values():
        assert sb._device is not None                 # kernel tier: uploaded
    outs = srv.serve(list(range(12)))
    for v, m in zip(range(12), outs):
        np.testing.assert_array_equal(np.asarray(m), store.checkout(v))
    s = srv.stats
    assert s.group_waves == 1
    assert s.group_launches >= 1
    assert s.groups_touched >= s.group_launches
    assert s.group_launches == mgr.last_wave.launches
    # host-tier warmup pins but does not upload
    store2 = _uniform_store(rng, p=8)
    store2.superblock_max_bytes = need // 3
    srv2 = BatchedCheckoutServer(store2, use_kernel=False)
    srv2.warmup()
    mgr2 = get_superblock_groups(store2)
    assert mgr2 is not None and len(mgr2.groups) > 0
    assert all(sb._device is None for sb in mgr2.groups.values())
    outs = srv2.serve([0, 9, 18])
    for v, m in zip([0, 9, 18], outs):
        np.testing.assert_array_equal(m, store2.checkout(v))
    assert srv2.stats.group_waves == 1                # host free fusion


def test_trigger_with_groups_resets_per_vid_ewma(rng):
    """The telemetry->trigger->migration loop on an over-budget store: the
    fired trigger clears the per-vid density EWMA (stale layout), the
    group layer survives the epoch bump, and serving continues correct."""
    r, n_versions, size = 256, 12, 16
    rls = [np.sort(rng.choice(r, size, replace=False)).astype(np.int64)
           for _ in range(n_versions)]
    graph = BipartiteGraph.from_rlists(rls, n_records=r)
    data = rng.integers(0, 1 << 20, (r, 4)).astype(np.int32)
    store = PartitionedCVD(graph, data, np.arange(n_versions) % 4)
    tree = WeightedTree(
        parent=np.concatenate([[-1], np.zeros(n_versions - 1, np.int64)]),
        n_records=np.array([len(x) for x in rls], np.int64),
        edge_w=np.zeros(n_versions, np.int64))
    store.superblock_max_bytes = estimate_superblock_bytes(store) - 1
    srv = BatchedCheckoutServer(
        store, use_kernel=True,
        trigger=RepartitionTrigger(store, tree, min_waves=2,
                                   low_density=0.5, use_kernel=True))
    stats = get_density_stats(store)
    waves = [[int(v) for v in rng.choice(n_versions, 4, replace=False)]
             for _ in range(6)]
    fired = False
    for vids in waves:
        outs = srv.serve(vids)
        for v, m in zip(vids, outs):
            np.testing.assert_array_equal(np.asarray(m), data[graph.rlist(v)])
        if srv.stats.repartitions and not fired:
            fired = True
            # reset-on-migration: the per-vid EWMA described the OLD layout
            assert stats.per_vid == {} or set(stats.per_vid) <= set(vids)
    assert fired, "trigger never fired on scattered over-budget traffic"
    assert stats.waves > 0


# ---------------------------------------------------------- leak regression --
def test_leak_50_epochs_counters_balanced(rng):
    """50 alternating migrate cycles with grouped waves in between: pinned
    bytes stay <= budget, pin/eviction counters stay balanced, and every
    superblock that ever left the group cache has its device copy
    released (no stale device buffers)."""
    store, w = _sci_store(rng, n_partitions=4, seed=37, n_attrs=6)
    need = estimate_superblock_bytes(store)
    store.superblock_max_bytes = need - 1
    a = store.assignment.copy()
    b = a.copy()
    b[:4] = a[4:8]                            # a mild A<->B morph
    vids = [int(v) for v in rng.integers(0, w.n_versions, 6)]
    seen: set[int] = set()
    by_id: dict[int, object] = {}
    mgr = None
    for epoch in range(50):
        checkout_wave(store, vids, use_kernel=True)
        mgr = get_superblock_groups(store)
        for sb in mgr.groups.values():
            seen.add(id(sb))
            by_id[id(sb)] = sb
        assert mgr.pinned_bytes <= mgr.budget
        assert mgr.pinned_bytes == sum(
            int(sb.host.nbytes) for sb in mgr.groups.values())
        assert mgr.pins - mgr.evictions == len(mgr.groups)
        target = b if epoch % 2 == 0 else a
        plan = plan_migration(store, target)
        store.apply_migration(plan)
    live = {id(sb) for sb in mgr.groups.values()}
    stale = [by_id[i] for i in seen - live]
    assert stale, "cycles never displaced a group (test is vacuous)"
    assert all(sb._device is None for sb in stale)
    assert mgr.pins - mgr.evictions == len(mgr.groups)
    # the store-level whole-superblock cache never engaged (over budget)
    assert peek_superblock(store) is None
    _assert_wave_equal(store, vids, use_kernel=True)
