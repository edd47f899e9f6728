"""Cross-partition fused checkout: the wave engine vs the per-partition
engine (byte-for-byte), ONE-pallas_call accounting for multi-partition
waves, superblock epoch caching, tail-run promotion bounds, and the serve
layer's deadline/size flusher + ticketing."""
import importlib

import numpy as np
import pytest

from repro.core import generate
from repro.core import query as Q
from repro.core.checkout import (build_superblock, checkout_partitioned,
                                 checkout_partitioned_perpart, checkout_wave,
                                 get_superblock, plan_wave)
from repro.core.partition import PartitionedCVD
from repro.serve.checkout import BatchedCheckoutServer

_cb = importlib.import_module("repro.kernels.checkout_batched")


def _store(rng, n_versions=24, n_partitions=4, seed=3, n_attrs=12):
    w = generate("SCI", n_versions=n_versions, inserts=100, n_branches=4,
                 n_attrs=n_attrs, seed=seed)
    assignment = rng.permutation(np.arange(w.n_versions) % n_partitions)
    return PartitionedCVD(w.graph, w.data, assignment), w


# ------------------------------------------------------------------ engine --
@pytest.mark.parametrize("n_partitions,k", [(1, 4), (4, 9), (7, 16)])
def test_wave_matches_perpart_randomized(rng, n_partitions, k):
    """The fused cross-partition wave is byte-for-byte the per-partition
    engine on randomized stores (host and kernel paths)."""
    store, w = _store(rng, n_partitions=n_partitions, seed=n_partitions)
    vids = list(rng.integers(0, w.n_versions, k)) + [0, 0]   # dups welcome
    base = checkout_partitioned_perpart(store, vids, use_kernel=False)
    for path in (False, True):
        got = checkout_wave(store, vids, use_kernel=path)
        for g, b in zip(got, base):
            np.testing.assert_array_equal(np.asarray(g), b)
            assert np.asarray(g).dtype == b.dtype


def test_checkout_partitioned_defaults_to_wave(rng):
    store, w = _store(rng)
    vids = [0, 5, 11, 3]
    got = checkout_partitioned(store, vids, use_kernel=False)
    for v, m in zip(vids, got):
        np.testing.assert_array_equal(m, store.checkout(v))
    with pytest.raises(ValueError, match="unknown engine"):
        checkout_partitioned(store, vids, engine="nope")
    with pytest.raises(ValueError, match="unknown version"):
        checkout_partitioned(store, [w.n_versions + 3])


def test_multipartition_wave_single_pallas_call(rng, monkeypatch):
    """Acceptance: a wave spanning P>=4 partitions executes exactly ONE
    pallas_call (counted at trace time — unique dims force a fresh trace)."""
    calls = []
    real = _cb.pl.pallas_call

    def counting(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(_cb.pl, "pallas_call", counting)
    _cb.checkout_wave.clear_cache()    # force a fresh trace: count is exact
    w = generate("SCI", n_versions=24, inserts=100, n_branches=4,
                 n_attrs=29, seed=17)
    store = PartitionedCVD(w.graph, w.data, np.arange(w.n_versions) % 6)
    vids = list(rng.integers(0, w.n_versions, 16))
    touched = {int(store.vid_to_pid[v]) for v in vids}
    assert len(touched) >= 4
    outs = checkout_wave(store, vids, use_kernel=True)
    for v, m in zip(vids, outs):
        np.testing.assert_array_equal(np.asarray(m), store.checkout(v))
    assert sum(calls) == 1


def test_empty_and_all_empty_waves(rng):
    store, w = _store(rng)
    assert checkout_wave(store, []) == []
    # a version with zero rows (if any) still slots in correctly
    outs = checkout_wave(store, [2, 2, 2], use_kernel=False)
    assert len(outs) == 3


# -------------------------------------------------------------- superblock --
def test_superblock_layout_and_bounds(rng):
    store, _ = _store(rng, n_partitions=5)
    sb = build_superblock(store)
    assert sb.host.shape[1] % 128 == 0          # lane-row layout
    for p, off, hi in zip(store.partitions, sb.row_offsets, sb.bounds):
        r, d = p.block.shape
        np.testing.assert_array_equal(sb.host[off:off + r, :d], p.block)
        assert hi - off >= r and (hi - off) % sb.block_n == 0
        # padding rows inside the segment are zero
        assert not sb.host[off + r:hi].any()


def test_superblock_epoch_cache_hit_and_invalidation(rng):
    store, w = _store(rng)
    sb1, hit1 = get_superblock(store)
    assert not hit1
    sb2, hit2 = get_superblock(store)
    assert hit2 and sb2 is sb1
    # device copy is pinned: repeated waves perform zero new uploads
    sb1.device()
    uploads = sb1.uploads
    checkout_wave(store, [0, 1, 2], use_kernel=True)
    checkout_wave(store, [3, 4, 5], use_kernel=True)
    sb3, hit3 = get_superblock(store)
    assert hit3 and sb3 is sb1 and sb1.uploads == uploads == 1
    # epoch bump (repartition) invalidates the cache
    store.repartition(np.arange(w.n_versions) % 2)
    sb4, hit4 = get_superblock(store)
    assert not hit4 and sb4 is not sb1 and sb4.epoch == store.epoch
    outs = checkout_wave(store, [0, 7], use_kernel=False)
    for v, m in zip([0, 7], outs):
        np.testing.assert_array_equal(m, store.checkout(v))


def test_plan_wave_rebases_and_bounds(rng):
    store, w = _store(rng, n_partitions=3)
    sb = build_superblock(store)
    vids = [0, 9, 4]
    wp = plan_wave(store, vids, sb)
    for k, v in enumerate(vids):
        pid = int(store.vid_to_pid[v])
        np.testing.assert_array_equal(
            wp.rebased[k],
            np.asarray(store.partitions[pid].local_rlist(v))
            + int(sb.row_offsets[pid]))
        t0, t1 = int(wp.plan.tile_offsets[k]), int(wp.plan.tile_offsets[k + 1])
        assert np.all(wp.hi[t0:t1] == int(sb.bounds[pid]))
        # every rebased rid lives inside its partition's segment
        if len(wp.rebased[k]):
            assert wp.rebased[k].min() >= int(sb.row_offsets[pid])
            assert wp.rebased[k].max() < int(sb.bounds[pid])


def test_tail_run_promotion_and_bound_fallback(rng):
    """Dense non-BN-multiple versions promote their tail chunk to a run DMA;
    the kernel's per-tile bound check keeps a promoted tail at the very end
    of a partition segment correct (row-DMA fallback on device)."""
    bn = _cb.DEFAULT_BN
    n = 3 * bn + 3                                     # dense, ragged tail
    data = np.arange(n * 4, dtype=np.int32).reshape(n, 4)
    from repro.core.graph import BipartiteGraph
    rls = [np.arange(0, n, dtype=np.int64),            # whole partition
           np.arange(n - 2, n, dtype=np.int64)]        # last 2 rows
    graph = BipartiteGraph.from_rlists(rls, n_records=n)
    store = PartitionedCVD(graph, data, np.zeros(2, np.int64))
    # cache the superblock so the single-partition wave still takes the
    # superblock kernel path (uncached one-partition waves go perpart)
    sb, _ = get_superblock(store)
    wp = plan_wave(store, [0, 1], sb)
    # both ragged tails promoted to run candidates
    t_a = int(wp.plan.tile_offsets[1])
    assert wp.plan.mode[t_a - 1] == 1 and wp.plan.mode[-1] == 1
    # version 0's tail run fits inside the aligned segment (reads padding
    # rows only); version 1 starts 2 rows before the segment end, so the
    # device bound check (start + BN <= hi) must reject the run and fall
    # back to row DMAs
    assert int(wp.plan.starts[(t_a - 1) * bn]) + bn <= int(wp.hi[t_a - 1])
    assert int(wp.plan.starts[(len(wp.hi) - 1) * bn]) + bn > int(wp.hi[-1])
    outs = checkout_wave(store, [0, 1], use_kernel=True)
    for v, m in zip([0, 1], outs):
        np.testing.assert_array_equal(np.asarray(m), store.checkout(v))


# ------------------------------------------------------------------- query --
def test_query_join_and_diff_store_path(rng):
    store, w = _store(rng, n_partitions=4, seed=11)
    for v1, v2 in [(3, 9), (0, 17), (5, 5)]:
        want = Q.join_versions(w.graph, w.data, v1, v2, on=0,
                               use_kernel=False)
        got = Q.join_versions(store, None, v1, v2, on=0, use_kernel=False)
        np.testing.assert_array_equal(got, want)
        da, db = Q.diff(w.graph, w.data, v1, v2)
        sa, sb_ = Q.diff(store, None, v1, v2, use_kernel=False)
        np.testing.assert_array_equal(sa, da)
        np.testing.assert_array_equal(sb_, db)


# ------------------------------------------------------------------- serve --
def test_serve_size_flusher_and_ticket_order(rng):
    """Regression: duplicate vids across an auto-flush boundary still come
    back in insertion-ticket order (collected per ticket, not per wave)."""
    store, w = _store(rng)
    srv = BatchedCheckoutServer(store, use_kernel=False, max_wave=4)
    reqs = [3, 7, 3, 1, 7, 7, 2, 3, 3]
    outs = srv.serve(reqs)
    assert srv.stats.waves == 3                        # 4 + 4 + 1
    assert len(outs) == len(reqs)
    for v, m in zip(reqs, outs):
        np.testing.assert_array_equal(m, store.checkout(v))
    assert srv.stats.requests == len(reqs)
    assert len(srv.stats.ticket_latency_s) == len(reqs)
    assert srv.stats.p50_latency_s >= 0.0
    assert srv.stats.max_latency_s >= srv.stats.p50_latency_s


def test_serve_deadline_flusher(rng):
    store, w = _store(rng)
    now = [0.0]
    srv = BatchedCheckoutServer(store, use_kernel=False, deadline_s=0.05,
                                clock=lambda: now[0])
    t1 = srv.submit(4)
    now[0] = 0.02
    assert not srv.poll()                              # deadline not reached
    t2 = srv.submit(9)
    now[0] = 0.06                                      # oldest waited 60ms
    assert srv.poll()
    np.testing.assert_array_equal(srv.result(t1), store.checkout(4))
    np.testing.assert_array_equal(srv.result(t2), store.checkout(9))
    assert srv.stats.waves == 1
    # per-ticket latency measured from each submit, not from the flush
    lat = srv.stats.ticket_latency_s
    assert lat[0] == pytest.approx(0.06) and lat[1] == pytest.approx(0.04)


def test_single_partition_wave_skips_superblock(rng):
    """A kernel wave confined to one partition is already a single launch:
    it must not build+pin a whole-store superblock."""
    from repro.core.checkout import peek_superblock
    store, w = _store(rng, n_partitions=4, seed=31)
    pid = int(store.vid_to_pid[5])
    peers = [v for v in range(w.n_versions)
             if int(store.vid_to_pid[v]) == pid][:3]
    outs = checkout_wave(store, peers, use_kernel=True)
    assert peek_superblock(store) is None
    for v, m in zip(peers, outs):
        np.testing.assert_array_equal(np.asarray(m), store.checkout(v))


def test_serve_bad_vid_does_not_poison_wave(rng):
    """An unknown vid raises in the OFFENDING client's submit() — before it
    is queued, before any auto-flush — leaving other tickets serviceable."""
    store, w = _store(rng)
    srv = BatchedCheckoutServer(store, use_kernel=False, max_wave=2)
    t1 = srv.submit(3)
    with pytest.raises(ValueError, match="unknown version"):
        srv.submit(w.n_versions + 5)
    t2 = srv.submit(4)                                 # size flush fires
    assert srv.stats.waves == 1
    np.testing.assert_array_equal(srv.result(t1), store.checkout(3))
    np.testing.assert_array_equal(srv.result(t2), store.checkout(4))
    # a failing serve() must not leak reservations nor mis-reserve the
    # NEXT ticket id (which was speculatively reserved but never assigned)
    with pytest.raises(ValueError, match="unknown version"):
        srv.serve([1, w.n_versions + 1, 2])
    assert srv._reserved == set()
    t3 = srv.submit(5)                                 # gets the spec'd id
    srv.flush()
    assert t3 not in srv._reserved
    np.testing.assert_array_equal(srv.result(t3), store.checkout(5))


def test_serve_flush_requeues_wave_on_failure(rng, monkeypatch):
    """A failed gather re-queues the whole coalesced wave: tickets survive
    and the next flush serves them."""
    import repro.serve.checkout as sc
    store, w = _store(rng)
    srv = BatchedCheckoutServer(store, use_kernel=False)
    t = srv.submit(2)
    real = sc.checkout_partitioned
    boom = {"armed": True}

    def flaky(*a, **kw):
        if boom.pop("armed", False):
            raise RuntimeError("transient gather failure")
        return real(*a, **kw)

    monkeypatch.setattr(sc, "checkout_partitioned", flaky)
    with pytest.raises(RuntimeError, match="transient"):
        srv.flush()
    assert srv.stats.waves == 0
    srv.flush()                                        # re-queued wave
    np.testing.assert_array_equal(srv.result(t), store.checkout(2))
    assert srv.stats.waves == 1


def test_host_path_never_builds_a_superblock(rng):
    """Pure-host processes must not pay the superblock memory copy: the
    host tier only reuses an ALREADY-cached superblock (free fusion) and
    otherwise gathers per partition."""
    from repro.core.checkout import peek_superblock
    store, w = _store(rng, seed=23)
    assert peek_superblock(store) is None
    outs = checkout_wave(store, [0, 3, 9], use_kernel=False)
    assert peek_superblock(store) is None              # still no copy
    for v, m in zip([0, 3, 9], outs):
        np.testing.assert_array_equal(m, store.checkout(v))
    get_superblock(store)                              # kernel path built one
    assert peek_superblock(store) is not None
    outs2 = checkout_wave(store, [0, 3, 9], use_kernel=False)
    for a, b in zip(outs, outs2):
        np.testing.assert_array_equal(a, b)


def test_serve_result_retention_is_bounded(rng, monkeypatch):
    """Unclaimed ticket results are FIFO-evicted beyond the retention cap,
    so flush()-only consumers cannot leak a long-running server — but
    serve()'s own in-flight tickets are reserved and never self-evict."""
    import repro.serve.checkout as sc
    monkeypatch.setattr(sc, "RETAIN_RESULTS", 2)
    store, w = _store(rng)
    srv = BatchedCheckoutServer(store, use_kernel=False)
    t1 = srv.submit(1)
    t2 = srv.submit(2)
    t3 = srv.submit(3)
    srv.flush()
    with pytest.raises(KeyError):
        srv.result(t1)                                 # evicted (oldest)
    np.testing.assert_array_equal(srv.result(t2), store.checkout(2))
    np.testing.assert_array_equal(srv.result(t3), store.checkout(3))
    # a serve() wave larger than the cap must not evict its own results
    reqs = [int(v) for v in rng.integers(0, w.n_versions, 7)]
    outs = srv.serve(reqs)
    for v, m in zip(reqs, outs):
        np.testing.assert_array_equal(m, store.checkout(v))
    assert len(srv._results) == 0 and len(srv._reserved) == 0


def test_serve_warmup_pins_superblock(rng):
    store, w = _store(rng)
    srv = BatchedCheckoutServer(store, use_kernel=True)
    srv.warmup()
    sb, hit = get_superblock(store)
    assert hit and sb.uploads == 1
    srv.serve([1, 2, 3])
    assert sb.uploads == 1                             # no re-upload
