"""Batched checkout engine: fused multi-version kernel vs the NumPy oracle,
single-launch accounting, vectorized host paths byte-identical to the seed
loop implementations, and serve-layer wave coalescing."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import generate
from repro.core.checkout import (WaveStages, _gather_off_superblock,
                                 build_superblock, checkout_partitioned,
                                 checkout_rlists, checkout_versions,
                                 checkout_versions_loop, checkout_wave)
from repro.core.graph import BipartiteGraph
from repro.core.datamodels import SplitByRlist
from repro.core.partition import PartitionedCVD, single_partition
from repro.core import query as Q
import importlib

_cb = importlib.import_module("repro.kernels.checkout_batched")
from repro.kernels import ops, ref
from repro.serve.checkout import BatchedCheckoutServer


def _random_rlists(rng, r, k, dense_frac=0.5):
    """Mix of dense runs (post-LYRESPLIT shape) and scattered rlists."""
    rls = []
    for i in range(k):
        if rng.random() < dense_frac:
            n = int(rng.integers(1, r // 2))
            s = int(rng.integers(0, r - n))
            rls.append(np.arange(s, s + n, dtype=np.int64))
        else:
            n = int(rng.integers(0, r // 2))
            rls.append(np.sort(rng.choice(r, size=n, replace=False)).astype(np.int64))
    return rls


# ------------------------------------------------------------------ kernel --
@pytest.mark.parametrize("r,d,k,dtype", [
    (256, 16, 4, np.int32),
    (1000, 40, 16, np.int32),
    (512, 128, 8, np.float32),
    (333, 100, 7, np.int32),          # non-aligned rows/cols
])
def test_checkout_batched_vs_oracle(r, d, k, dtype, rng):
    data = (rng.standard_normal((r, d)) * 10).astype(dtype)
    rls = _random_rlists(rng, r, k)
    outs, plan = ops.checkout_batched(data, rls, interpret=True)
    oracle = ref.gather_batched_ref(data, rls)
    assert len(outs) == k
    for got, want in zip(outs, oracle):
        np.testing.assert_array_equal(np.asarray(got), want)
    assert plan.n_tiles == int(plan.tile_offsets[-1])


def test_checkout_batched_single_pallas_call(rng, monkeypatch):
    """K=16 versions -> exactly ONE pallas_call in the traced program (the
    fused-launch claim).  Counted at trace time: unique shapes force a fresh
    trace, and every pl.pallas_call in the jaxpr is one kernel launch per
    execution."""
    calls = []
    real = _cb.pl.pallas_call

    def counting(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(_cb.pl, "pallas_call", counting)
    _cb.checkout_wave.clear_cache()    # force a fresh trace: count is exact
    data = rng.integers(0, 100, (611, 23)).astype(np.int32)
    rls = _random_rlists(rng, 611, 16)
    outs, _ = ops.checkout_batched(data, rls, interpret=True)
    for got, want in zip(outs, ref.gather_batched_ref(data, rls)):
        np.testing.assert_array_equal(np.asarray(got), want)
    assert sum(calls) == 1


@pytest.mark.parametrize("row_lanes", [1, 2])
@pytest.mark.parametrize("tiles_per_launch", [1, 3, 7])
def test_checkout_wave_split_launches_bit_identical(rng, capped_launches,
                                                   row_lanes,
                                                   tiles_per_launch):
    """A plan too big for one launch's SMEM runs as several launches into
    one aligned output: bit-identical to a single launch and to the oracle,
    for one- and two-lane-row (D=128/256) superblocks."""
    r, d = 300, 128 * row_lanes
    data = rng.integers(0, 1000, (r, d)).astype(np.int32)
    rls = _random_rlists(rng, r, 6)
    plan = _cb.plan_batched(rls)
    args = (jnp.asarray(data.reshape(-1, 128)), jnp.asarray(plan.starts),
            jnp.asarray(plan.mode), jnp.full(plan.n_tiles, r, jnp.int32))
    run = functools.partial(_cb.checkout_wave, *args, row_lanes=row_lanes,
                            interpret=True)
    one = np.asarray(run())
    # 40 B of plan per tile: BN=8 starts + mode + hi, int32 each
    split, launches = capped_launches(_cb.checkout_wave, run,
                                      40 * tiles_per_launch)
    assert launches == -(-plan.n_tiles // tiles_per_launch) > 1
    np.testing.assert_array_equal(np.asarray(split), one)
    packed = np.asarray(split).reshape(-1, d)
    for k, want in enumerate(ref.gather_batched_ref(data, rls)):
        np.testing.assert_array_equal(packed[plan.segment(k, 8)], want)


# ----------------------------------------------------------- launch ladder --
@pytest.mark.parametrize("n", list(range(0, 18)) + [
    31, 32, 33, 255, 256, 257, 4095, 4097, 13107, 30001, 47311, 65537,
    123457, 1 << 20])
def test_launch_tiles_ladder(n):
    """The launch ladder: exact up to 8 (and to 16), never below the plan,
    at most 1/8 more above that, monotone, and at most 8 rungs in any
    doubling."""
    got = ops.launch_tiles(n)
    assert got >= n
    assert ops.launch_tiles(n + 1) >= got
    if n <= 8:
        assert got == n
    else:
        assert got <= -(-n * 9 // 8)
    if 16 <= n <= 1 << 17:
        rungs = {ops.launch_tiles(m) for m in range(n + 1, 2 * n + 1)}
        assert len(rungs) <= 9      # the rungs of (n, 2n], ends included
    assert ops.launch_tiles(got) == got


def _ladder_store(rng, p=4, r=640, d=12):
    """16 versions over ``p`` partitions (v -> v % p); version v spans
    1 + v // p BN-row tiles with a ragged tail, even versions one dense
    run, odd ones scattered rows."""
    rls = []
    for v in range(16):
        n = 8 * (1 + v // p) - v % 3
        if v % 2 == 0:
            s = int(rng.integers(0, r - n))
            rls.append(np.arange(s, s + n, dtype=np.int64))
        else:
            rls.append(np.sort(rng.choice(r, n, replace=False))
                       .astype(np.int64))
    graph = BipartiteGraph.from_rlists(rls, n_records=r)
    data = rng.integers(0, 1 << 20, (r, d)).astype(np.int32)
    return PartitionedCVD(graph, data, np.arange(16) % p)


def _pick_tiles(tiles, target):
    """Keys of ``tiles`` (key -> BN-row tiles) whose tiles sum to
    ``target``: most tiles first, any shortfall filled with repeats of the
    smallest."""
    pick, t = [], 0
    for k in sorted(tiles, key=lambda k: -tiles[k]):
        if t + tiles[k] <= target:
            pick.append(k)
            t += tiles[k]
    small = min(tiles, key=tiles.get)
    while t < target:
        pick.append(small)
        t += tiles[small]
    assert t == target
    return pick


def _wave_of_tiles(store, target, vids):
    return _pick_tiles({v: -(-len(store.checkout(v)) // 8) for v in vids},
                       target)


@pytest.mark.parametrize("offset", [-1, 0, 1])
@pytest.mark.parametrize("path,rung", [("whole", 32), ("group", 24),
                                       ("straggler", 36)])
def test_gather_at_a_ladder_rung_is_bit_identical(rng, path, rung, offset):
    """Waves whose plan ends just below, on and just above a rung of the
    launch ladder, through a whole-store superblock, a group superblock
    and a straggler batch: every block equals the host oracle, and the
    wave's stages count the planned and the pad tiles."""
    store = _ladder_store(rng)
    target = rung + offset
    pad = ops.launch_tiles(target) - target
    assert (pad == 0) == (offset == 0)
    stages = WaveStages()
    if path == "straggler":
        p = store.partitions[1]
        rls = [p.local_rlist(int(v)) for v in p.vids]
        pick = [rls[k] for k in _pick_tiles(
            {k: -(-len(rl) // 8) for k, rl in enumerate(rls)}, target)]
        outs, _ = ops.checkout_batched(p.block, pick, stages=stages)
        wants = ref.gather_batched_ref(p.block, pick)
    else:
        if path == "whole":
            vids = _wave_of_tiles(store, target, range(16))
            res = checkout_wave(store, vids, use_kernel=True,
                                device_out=True)
            outs, stages = res.materialize(), res.stages
        else:
            sb = build_superblock(store, pids=(0, 1, 2))
            vids = _wave_of_tiles(store, target,
                                  [v for v in range(16) if v % 4 < 3])
            part, launched, _ = _gather_off_superblock(
                store, vids, sb, stages, use_kernel=True,
                density_threshold=0.05)
            assert launched
            outs = part.split()
        wants = [store.checkout(v) for v in vids]
    assert (stages.tiles, stages.pad_tiles) == (target, pad)
    assert len(outs) == len(wants)
    for got, want in zip(outs, wants):
        np.testing.assert_array_equal(np.asarray(got), want)


def test_waves_of_one_rung_share_one_compiled_gather(rng):
    """Two served waves of different tile counts on one rung compile the
    gather once, and the server counts their planned and pad tiles."""
    store = _ladder_store(rng)
    srv = BatchedCheckoutServer(store, use_kernel=True)
    waves = [_wave_of_tiles(store, t, range(16)) for t in (33, 35)]
    assert ops.launch_tiles(33) == ops.launch_tiles(35) == 36
    _cb.checkout_wave.clear_cache()
    for vids in waves:
        for v, m in zip(vids, srv.serve(vids)):
            np.testing.assert_array_equal(m, store.checkout(v))
    assert _cb.checkout_wave._cache_size() == 1
    st = srv.stats
    assert (st.tiles, st.pad_tiles) == (33 + 35, 3 + 1)
    assert st.pad_tiles / (st.tiles + st.pad_tiles) <= 1 / 8


def test_plan_batched_modes(rng):
    """Dense rlists plan as run DMAs, scattered ones as row DMAs."""
    bn = 8
    dense = np.arange(100, 500, dtype=np.int64)
    sparse = np.sort(rng.choice(10_000, 200, replace=False)).astype(np.int64)
    plan = _cb.plan_batched([dense, sparse], block_n=bn)
    t_dense = int(plan.tile_offsets[1])
    assert plan.density[0] > 0.9 and plan.mode[:t_dense].sum() >= t_dense - 1
    assert plan.density[1] < 0.1 and plan.mode[t_dense:].sum() == 0


def test_single_version_kernels_vs_oracle(rng):
    """checkout_gather / gather_row_tiles interpret=True vs the jnp oracle
    (the per-version building blocks the batched engine replaces)."""
    r, d = 512, 64
    data = rng.integers(0, 1000, (r, d)).astype(np.int32)
    rids = np.sort(rng.choice(r, 100, replace=False)).astype(np.int32)
    out = ops.checkout_gather(data, rids)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(ref.gather_rows_ref(jnp.asarray(data),
                                                        jnp.asarray(rids))))
    packed, perm, _ = ops.checkout_gather_tiled(data, rids)
    np.testing.assert_array_equal(np.asarray(packed)[perm], data[rids])


def test_checkout_gather_tiled_sorts_unsorted_rlists(rng):
    """Satellite: unsorted rlists are valid at the entry point now."""
    r, d = 256, 16
    data = rng.integers(0, 1000, (r, d)).astype(np.int32)
    rids = rng.permutation(rng.choice(r, 64, replace=False)).astype(np.int64)
    packed, perm, _ = ops.checkout_gather_tiled(data, rids)
    np.testing.assert_array_equal(np.asarray(packed)[perm], data[rids])


def test_duplicate_rids_raise_clear_error(rng):
    data = np.zeros((16, 8), np.int32)
    with pytest.raises(ValueError, match="duplicate"):
        ops.checkout_gather_tiled(data, np.array([1, 1, 3]))
    with pytest.raises(ValueError, match="sorted"):
        ops.plan_tiles(np.array([5, 3, 1]))


def test_checkout_batched_honors_rids_as_given(rng):
    """Engine contract: kernel and host paths agree with data[rl] for
    unsorted and duplicate rids alike (rids honored AS GIVEN)."""
    data = rng.integers(0, 1000, (64, 16)).astype(np.int32)
    rls = [np.array([9, 3, 3, 50]), rng.permutation(40).astype(np.int64)]
    outs, _ = ops.checkout_batched(data, rls, interpret=True)
    host = checkout_rlists(data, rls, use_kernel=False)
    for got, h, rl in zip(outs, host, rls):
        np.testing.assert_array_equal(np.asarray(got), data[rl])
        np.testing.assert_array_equal(h, data[rl])


def test_checkout_batched_empty_wave(rng):
    """All-empty waves return empty blocks instead of crashing."""
    data = rng.integers(0, 9, (8, 4)).astype(np.int32)
    outs, plan = ops.checkout_batched(
        data, [np.zeros(0, np.int64), np.zeros(0, np.int64)])
    assert plan.n_tiles == 0 and len(outs) == 2
    for o in outs:
        assert o.shape == (0, 4) and o.dtype == data.dtype


# ------------------------------------------------------------------ engine --
def test_engine_fused_vs_loop(rng):
    w = generate("SCI", n_versions=24, inserts=100, n_branches=4,
                 n_attrs=12, seed=3)
    vids = list(rng.integers(0, w.n_versions, size=16))
    host = checkout_versions(w.graph, w.data, vids, use_kernel=False)
    loop = checkout_versions_loop(w.graph, w.data, vids)
    kern = checkout_versions(w.graph, w.data, vids, use_kernel=True)
    for h, l, k in zip(host, loop, kern):
        np.testing.assert_array_equal(h, l)
        np.testing.assert_array_equal(np.asarray(k), l)


def test_engine_partitioned_matches_store_checkout(rng):
    w = generate("CUR", n_versions=12, inserts=80, n_branches=3,
                 n_attrs=10, seed=1)
    assignment = np.arange(w.n_versions) % 3        # 3 partitions
    store = PartitionedCVD(w.graph, w.data, assignment)
    vids = list(range(w.n_versions)) + [0, 5]       # duplicates welcome
    outs = checkout_partitioned(store, vids, use_kernel=False)
    for v, m in zip(vids, outs):
        np.testing.assert_array_equal(m, store.checkout(v))
    outs_k = store.checkout_many(vids, use_kernel=True)
    for v, m in zip(vids, outs_k):
        np.testing.assert_array_equal(np.asarray(m), store.checkout(v))


def test_serve_wave_coalescing(rng):
    w = generate("SCI", n_versions=10, inserts=60, n_branches=2,
                 n_attrs=8, seed=2)
    store = single_partition(w.graph, w.data)
    srv = BatchedCheckoutServer(store, use_kernel=False)
    reqs = [3, 7, 3, 1, 7, 7]                       # duplicate-heavy wave
    outs = srv.serve(reqs)
    assert len(outs) == len(reqs)
    for v, m in zip(reqs, outs):
        np.testing.assert_array_equal(m, store.checkout(v))
    assert srv.stats.waves == 1
    assert srv.stats.requests == 6
    assert srv.stats.unique_versions == 3           # dedup before the gather


# ------------------------------------------------- vectorized host paths ----
def test_diff_against_parents_byte_identical(rng):
    m = SplitByRlist(n_attrs=5)
    for trial in range(20):
        n_parent = int(rng.integers(0, 60))
        parent_rows = rng.integers(-50, 50, (n_parent, 5)).astype(np.int32)
        parent_rids = rng.integers(0, 1000, n_parent).astype(np.int64)
        # table: mix of parent rows (hits) and fresh rows (misses)
        take = rng.integers(0, max(n_parent, 1), int(rng.integers(0, 40)))
        fresh = rng.integers(-50, 50, (int(rng.integers(0, 40)), 5)).astype(np.int32)
        table = np.concatenate([parent_rows[take] if n_parent else fresh[:0],
                                fresh])
        table = table[rng.permutation(len(table))]
        got = m._diff_against_parents(table, parent_rows, parent_rids)
        want = m._diff_against_parents_loop(table, parent_rows, parent_rids)
        np.testing.assert_array_equal(got[0], want[0])
        assert got[1].tobytes() == want[1].tobytes()
        assert got[1].dtype == want[1].dtype and got[1].shape == want[1].shape


def test_checkout_multi_pk_precedence(rng):
    m = SplitByRlist(n_attrs=6)
    t0 = rng.integers(0, 100, (50, 6)).astype(np.int32)
    t0[:, 0] = np.arange(50)          # PK col 0 unique
    t0[:, 1] = 7
    v0 = m.commit(t0)
    t1 = t0.copy()
    t1[:25, 2:] += 1                  # 25 rows changed under the same PK
    v1 = m.commit(t1, parents=(v0,))
    merged = m.checkout_multi([v1, v0])
    # earlier vid wins every PK collision: v1's rows verbatim, v0-only rest
    np.testing.assert_array_equal(
        merged, m.checkout_multi_loop([v1, v0]))
    v1_rows = {r.tobytes() for r in m.checkout(v1)}
    for r in merged[:25]:
        assert r.tobytes() in v1_rows
    pks = merged[:, :2]
    assert len(np.unique(pks.view([("", pks.dtype)] * 2))) == len(merged)


def test_checkout_multi_byte_identical_randomized(rng):
    for seed in range(5):
        w = generate("SCI", n_versions=8, inserts=40, n_branches=2,
                     n_attrs=6, seed=seed)
        m = SplitByRlist(n_attrs=6)
        vids = {}
        for v in range(w.n_versions):
            parents = tuple(vids[p] for p in w.vgraph.parents(v))
            vids[v] = m.commit(w.data[w.graph.rlist(v)], parents=parents)
        sel = list(rng.integers(0, w.n_versions, 4))
        got = m.checkout_multi(sel)
        want = m.checkout_multi_loop(sel)
        np.testing.assert_array_equal(got, want)
        assert got.dtype == want.dtype


def test_join_versions_byte_identical(rng):
    for seed in range(5):
        w = generate("SCI", n_versions=10, inserts=60, n_branches=3,
                     n_attrs=6, seed=seed)
        v1, v2 = 4, 9
        got = Q.join_versions(w.graph, w.data, v1, v2, on=0, use_kernel=False)
        want = Q.join_versions_loop(w.graph, w.data, v1, v2, on=0)
        np.testing.assert_array_equal(got, want)
        assert got.dtype == want.dtype


def test_join_versions_empty_join(rng):
    w = generate("SCI", n_versions=4, inserts=10, n_branches=1,
                 n_attrs=4, seed=0)
    data = w.data.copy()
    out = Q.join_versions(w.graph, data, 0, 1, on=0, use_kernel=False)
    want = Q.join_versions_loop(w.graph, data, 0, 1, on=0)
    np.testing.assert_array_equal(out, want)


def test_vlist_models_incremental_index(rng):
    """CombinedTable/SplitByVlist rlist()/vlists agree with the CSR-free
    definition: rid in rlist(v) iff v in vlists[rid]."""
    from repro.core.datamodels import CombinedTable, SplitByVlist
    for cls in (CombinedTable, SplitByVlist):
        m = cls(n_attrs=4)
        t0 = rng.integers(0, 50, (30, 4)).astype(np.int32)
        v0 = m.commit(t0)
        t1 = np.concatenate([t0[:20], rng.integers(50, 99, (10, 4)).astype(np.int32)])
        v1 = m.commit(t1, parents=(v0,))
        vl = m.vlists
        for vid in (v0, v1):
            rl = m.rlist(vid)
            member = np.array([vid in vl[r] for r in range(m._n_rows)])
            np.testing.assert_array_equal(np.flatnonzero(member), rl)
