"""Compile the store's kernels for a described TPU v5e, at SCI_1M sizes.

Nothing runs: each test lowers and compiles a kernel for a chip that is
described, not attached (``jax.experimental.topologies``), and checks that
the Mosaic kernel is in the program.  What the TPU compiler refuses — more
SMEM than a launch may hold, a slice not aligned to the (8, 128) tiling —
shows up here without a chip.  Sizes follow the SCI_1M store the chip smoke
run serves (``chip_smoke.py``): ~30K rows per version, a ~2M-row
superblock at LyreSplit's gamma = 2.

The topology is described inside a module fixture (never at import): only
one process at a time may load the TPU library, and under pytest-xdist
every worker imports this file.
"""
import importlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import segment_append as sa
from repro.kernels import segment_move as sm
from repro.kernels import version_agg as va
from repro.kernels import vlist_membership as vm
from repro.kernels.plan_launch import LANES, PLAN_SMEM_BYTES

# the package re-exports ops.checkout_batched and ops.checkout_gather
# under the modules' names
cb = importlib.import_module("repro.kernels.checkout_batched")
cg = importlib.import_module("repro.kernels.checkout_gather")

BN = 8
SB_ROWS = 2_000_000            # whole-store superblock rows at gamma = 2
WAVE_TILES = 60_000            # a 16-version SCI_1M wave (~30K rows each)
SB_TILES = SB_ROWS // BN


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import compilation_cache
    from jax.experimental import topologies
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, *args, **static):
    text = fn.lower(*args, **static).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _shape(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_plan_of_big_wave_exceeds_one_launch():
    # the sizes below are only meaningful if they split: one launch of a
    # 60K-tile wave plan (40 B per tile) would not fit SMEM
    assert WAVE_TILES * (BN + 2) * 4 > PLAN_SMEM_BYTES
    assert SB_TILES * 2 * 4 > PLAN_SMEM_BYTES


@pytest.mark.parametrize("d", [128, 256])
def test_checkout_wave_compiles_for_a_16_version_wave(one_chip, d):
    w = d // LANES
    _compile(cb.checkout_wave,
             _shape(one_chip, (SB_ROWS * w, LANES)),
             _shape(one_chip, (WAVE_TILES * BN,)),
             _shape(one_chip, (WAVE_TILES,)),
             _shape(one_chip, (WAVE_TILES,)),
             block_n=BN, row_lanes=w)


@pytest.mark.parametrize("d", [128, 256])
@pytest.mark.parametrize("kernel", [sa.segment_append, sm.segment_move],
                         ids=["segment_append", "segment_move"])
def test_segment_kernels_compile_over_a_2m_row_superblock(one_chip, kernel,
                                                          d):
    w = d // LANES
    _compile(kernel,
             _shape(one_chip, (SB_ROWS * w, LANES)),
             _shape(one_chip, (64 * BN * w, LANES)),
             _shape(one_chip, (SB_TILES + 64,)),
             _shape(one_chip, (SB_TILES + 64,)),
             block_n=BN, row_lanes=w)


def test_gather_row_tiles_compiles(one_chip):
    _compile(cg.gather_row_tiles,
             _shape(one_chip, (SB_ROWS, LANES)),
             _shape(one_chip, (WAVE_TILES,)),
             block_n=BN, block_d=LANES)


def test_membership_scan_compiles(one_chip):
    # 1,000 versions = 32 bitmap words, ~1M records
    _compile(vm.membership_scan,
             _shape(one_chip, (32, 1 << 20), jnp.uint32),
             vid=777, block_r=1024)


def test_version_aggregate_compiles(one_chip):
    _compile(va.version_aggregate,
             _shape(one_chip, (32, 1 << 20), jnp.uint32),
             _shape(one_chip, (1 << 20,), jnp.float32),
             block_r=1024)
