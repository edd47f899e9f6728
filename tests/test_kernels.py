"""Per-kernel shape/dtype sweeps: Pallas (interpret mode on CPU) vs the
pure-jnp oracles in kernels/ref.py."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.checkout_gather import plan_tiles


@pytest.mark.parametrize("r,d,n,dtype", [
    (64, 8, 16, np.int32),
    (1000, 20, 137, np.int32),
    (512, 128, 512, np.float32),
    (257, 100, 31, np.int32),        # non-aligned rows/cols
    (2048, 256, 1, np.float32),      # single-row gather
])
def test_checkout_gather_sweep(r, d, n, dtype, rng):
    data = (rng.standard_normal((r, d)) * 10).astype(dtype)
    rids = np.sort(rng.choice(r, size=n, replace=False)).astype(np.int32)
    out = ops.checkout_gather(data, rids)
    oracle = np.asarray(ref.gather_rows_ref(jnp.asarray(data), jnp.asarray(rids)))
    np.testing.assert_allclose(np.asarray(out), oracle)


@pytest.mark.parametrize("r,d,n,block_n", [
    (128, 16, 50, 8),
    (1024, 64, 600, 8),
    (1024, 64, 600, 16),
    (333, 24, 100, 8),
])
def test_checkout_gather_tiled_sweep(r, d, n, block_n, rng):
    data = rng.integers(0, 1000, size=(r, d)).astype(np.int32)
    rids = np.sort(rng.choice(r, size=n, replace=False)).astype(np.int64)
    packed, perm, waste = ops.checkout_gather_tiled(data, rids, block_n=block_n)
    np.testing.assert_array_equal(np.asarray(packed)[perm], data[rids])
    assert 0.0 <= waste < 1.0


def test_tiled_waste_drops_for_dense_runs(rng):
    """The planner's efficiency claim: dense rid runs (what LYRESPLIT
    partitions produce) waste ~nothing; random rids waste a lot."""
    r = 4096
    dense = np.arange(1000, 3000)
    rand = np.sort(rng.choice(r, size=2000, replace=False))
    _, _, w_dense = plan_tiles(dense, block_n=8)
    _, _, w_rand = plan_tiles(rand, block_n=8)
    assert w_dense < 0.01
    assert w_rand > w_dense


@pytest.mark.parametrize("r,n_versions,block_r", [
    (256, 33, 128),
    (1000, 70, 256),
    (513, 100, 128),
])
def test_membership_scan_sweep(r, n_versions, block_r, rng):
    rlists = [np.sort(rng.choice(r, size=int(rng.integers(5, r // 2)),
                                 replace=False)) for _ in range(n_versions)]
    bm = ops.build_bitmap(rlists, r)
    for vid in (0, n_versions // 2, n_versions - 1):
        mask, cnt = ops.membership_scan(bm, vid=vid, block_r=block_r)
        m_ref, c_ref = ref.membership_scan_ref(
            jnp.asarray(np.pad(bm, ((0, 0), (0, (-r) % block_r)))),
            vid, block_r)
        expect = np.zeros(r, np.int32)
        expect[rlists[vid]] = 1
        np.testing.assert_array_equal(np.asarray(mask), expect)
        np.testing.assert_array_equal(np.asarray(mask), np.asarray(m_ref)[:r])
        np.testing.assert_array_equal(np.asarray(cnt), np.asarray(c_ref))
        assert int(np.asarray(cnt).sum()) == len(rlists[vid])


@pytest.mark.parametrize("r,n_versions,block_r", [
    (256, 16, 128),
    (1024, 64, 256),
    (777, 40, 128),
])
def test_version_aggregate_sweep(r, n_versions, block_r, rng):
    rlists = [np.sort(rng.choice(r, size=int(rng.integers(5, r // 2)),
                                 replace=False)) for _ in range(n_versions)]
    bm = ops.build_bitmap(rlists, r)
    vals = rng.standard_normal(r).astype(np.float32)
    agg = np.asarray(ops.version_aggregate(bm, vals, block_r=block_r))
    for v in range(n_versions):
        np.testing.assert_allclose(agg[v], vals[rlists[v]].sum(),
                                   rtol=1e-4, atol=1e-4)
    oracle = np.asarray(ref.version_aggregate_ref(jnp.asarray(bm),
                                                  jnp.asarray(vals)))
    np.testing.assert_allclose(agg[:len(oracle)], oracle, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("kernel", ["membership_scan", "version_aggregate"])
def test_bitmap_kernels_reject_unaligned_block_r(kernel):
    """Records run along the 128-lane axis: a block that is not a whole
    number of lane tiles is refused, not silently rounded."""
    bm = ops.build_bitmap([np.arange(10), np.arange(5, 50)], 256)
    args = {"membership_scan": dict(vid=1),
            "version_aggregate": dict(values=np.ones(256, np.float32))}
    with pytest.raises(ValueError, match="multiple of 128"):
        getattr(ops, kernel)(bm, block_r=64, **args[kernel])


def test_version_aggregate_count_mode(rng):
    r, nv = 512, 20
    rlists = [np.sort(rng.choice(r, size=int(rng.integers(5, 100)),
                                 replace=False)) for _ in range(nv)]
    bm = ops.build_bitmap(rlists, r)
    counts = np.asarray(ops.version_aggregate(bm, np.ones(r, np.float32)))
    for v in range(nv):
        assert counts[v] == len(rlists[v])


def test_fresh_datastack_passes_the_call_through_on_a_chunk_of_its_own():
    """The launch wrapper returns ``fn``'s result unchanged, and its frame
    declares a stack far deeper than the interpreter's 16 KB data-stack
    chunk, so every call opens a chunk of its own for the trace and
    lowering below it."""
    assert ops._fresh_datastack(lambda a, *, b: (a, b), 2, b=3) == (2, 3)
    assert ops._fresh_datastack.__code__.co_stacksize * 8 >= 32 * 16 * 1024
