"""Checkout gather kernel — the TPU realization of the paper's hash-join
probe (Table 1, split-by-rlist checkout).

``checkout v`` = gather the rows named by v's rlist out of the partition's
data block.  On Postgres this is a hash join whose cost is linear in the
partition size (App. D.1); on TPU it is an HBM->VMEM row gather whose cost is
linear in bytes touched — same cost model, different constant.

``gather_row_tiles`` — beyond-paper optimization: rlists are SORTED, so after
LYRESPLIT partitioning a checkout touches long dense runs of the block.
``plan_tiles`` RLEs the rlist into BN-row-aligned tile indices and each grid
step DMAs a (BN, BD) tile.  Checkout has SET semantics (a version is a set
of records), so the packed tile output needs no reordering; the planner's
``perm`` exists for oracle comparison.  The feature dimension is tiled at BD
(a multiple of 128 lanes) so the VMEM working set stays bounded regardless
of table width.

A per-row gather in request order (``kernels.ops.checkout_gather``) is the
all-row-DMA case of the wave kernel in ``checkout_batched``.

Multi-version retrieval (K versions, one launch) lives in the sibling module
``checkout_batched`` — it fuses run and row gathers into a single adaptive
(starts, mode) plan; see its module docstring for the engine data-flow map.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BD = 512   # feature-tile width (lanes); multiple of 128
DEFAULT_BN = 8     # rows per tile for the ranged variant (sublane multiple)


def _copy_kernel(idx_ref, x_ref, o_ref):
    # x_ref is the row tile selected by the index_map; copy through.
    del idx_ref
    o_ref[...] = x_ref[...]


@functools.partial(jax.jit, static_argnames=("block_n", "block_d", "interpret"))
def gather_row_tiles(data: jax.Array, tile_idx: jax.Array, *,
                     block_n: int = DEFAULT_BN, block_d: int = DEFAULT_BD,
                     interpret: bool = False) -> jax.Array:
    """out tile t = data rows [tile_idx[t]*BN, (tile_idx[t]+1)*BN).

    data: (R, D) with R a multiple of BN (pad upstream).
    tile_idx: (T,) int32 BN-row tile indices from ``plan_tiles``.
    Returns (T*BN, D) packed tiles.
    """
    r, d = data.shape
    t = tile_idx.shape[0]
    bd = min(block_d, d)
    assert d % bd == 0 and r % block_n == 0, (r, d, block_n, bd)
    grid = (t, d // bd)
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[pl.BlockSpec((block_n, bd), lambda i, j, ti: (ti[i], j))],
        out_specs=pl.BlockSpec((block_n, bd), lambda i, j, ti: (i, j)),
    )
    return pl.pallas_call(
        _copy_kernel, grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((t * block_n, d), data.dtype),
        interpret=interpret,
    )(tile_idx.astype(jnp.int32), data)


def plan_tiles(rids, block_n: int = DEFAULT_BN):
    """Host-side planner: the set of BN-row tiles covering a sorted rlist.

    Returns (tile_idx, perm, waste):
      * tile_idx — sorted unique tiles (row // BN) the rlist touches;
      * perm     — rlist position -> packed-output row, so
                   packed[perm] == data[rids] (oracle comparison only;
                   production checkout keeps set semantics);
      * waste    — fraction of gathered rows that are not in the rlist
                   (the price of tiling; low after LYRESPLIT because
                   partitions hold dense rid runs).
    """
    rids = np.asarray(rids)
    if len(rids) and np.any(np.diff(rids) < 1):
        raise ValueError(
            "plan_tiles requires a sorted, duplicate-free rlist (a version "
            "is a SET of records); sort/validate at the checkout_gather "
            "entry point — see kernels.ops.checkout_gather_tiled")
    tile_of = rids // block_n
    tiles = np.unique(tile_of).astype(np.int32)
    perm = np.searchsorted(tiles, tile_of) * block_n + rids % block_n
    waste = 1.0 - len(rids) / max(len(tiles) * block_n, 1)
    return tiles, perm.astype(np.int64), waste
