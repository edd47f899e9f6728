"""Device-side superblock migration kernel — incremental, not rebuild.

``PartitionedCVD.apply_migration`` changes the partition layout, which
changes the superblock row layout.  Rebuilding the superblock from scratch
pays a full ΣR×D host concatenation plus a full host→device re-upload —
exactly the naive-migration cost the paper's intelligent migration avoids
(§4.3, Figs 14-15).  But most BN-row segments of the post-migration
superblock are byte-identical to segments of the PRE-migration superblock,
which is *already resident on device*: only rows that migration actually
moved across partition boundaries (or freshly materialized) need to travel
over the host→device link.

This kernel executes that copy plan as one ``pallas_call`` per SMEM-sized
slice of the plan, all writing one output: every BN-row
output tile of the new superblock is produced by a single run DMA from one
of two sources, chosen by a prefetched per-tile selector:

    sel[t] == 0  ->  reuse: copy rows [start[t], start[t]+BN) of the OLD
                     device-resident superblock (device-to-device; never
                     crosses the host link)
    sel[t] != 0  ->  delta: copy rows [start[t], start[t]+BN) of the small
                     host-uploaded delta block (only the changed tiles)

``core.checkout.migrate_superblock`` builds (sel, start, delta) from a
``MigrationPlan`` and reports bytes_uploaded = delta.nbytes vs the rebuild
cost of the whole superblock.  The plan rides in scalar prefetch (SMEM) so
the DMA engine sees every source address ahead of the body, same as the
checkout kernels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .checkout_gather import DEFAULT_BN
from .plan_launch import planned_tile_call


def _make_kernel(block_n: int, row_lanes: int):
    rows = block_n * row_lanes

    def kernel(sel_ref, start_ref, src_ref, delta_ref, o_ref, sems):
        t = pl.program_id(0)
        s0 = start_ref[t] * row_lanes

        @pl.when(sel_ref[t] == 0)
        def _reuse():
            cp = pltpu.make_async_copy(
                src_ref.at[pl.ds(s0, rows)], o_ref, sems.at[0])
            cp.start()
            cp.wait()

        @pl.when(sel_ref[t] != 0)
        def _delta():
            cp = pltpu.make_async_copy(
                delta_ref.at[pl.ds(s0, rows)], o_ref, sems.at[0])
            cp.start()
            cp.wait()

    return kernel


@functools.partial(jax.jit, static_argnames=("block_n", "row_lanes",
                                             "interpret"))
def segment_move(src: jax.Array, delta: jax.Array, sel: jax.Array,
                 starts: jax.Array, *, block_n: int = DEFAULT_BN,
                 row_lanes: int = 1, interpret: bool = False) -> jax.Array:
    """Assemble a migrated superblock: T output tiles, in as few launches
    as SMEM allows (see ``plan_launch``).

    src:    (R_old*row_lanes, 128) the pre-migration lane-row superblock
            (device-resident).
    delta:  (R_delta*row_lanes, 128) host-uploaded changed rows, BN-tile
            packed, same lane-row layout.
    sel:    (T,) int32 per-tile source — 0 = src (reuse), 1 = delta.
    starts: (T,) int32 first source row (logical) of the tile in its
            chosen source.
    Returns (T*block_n*row_lanes, 128): the post-migration superblock.

    Every run [starts[t], starts[t]+block_n) must be in-bounds for its
    source — ``core.checkout.migrate_superblock`` guarantees it by
    construction (tiles whose source run would cross an aligned segment
    end are routed to the delta instead).
    """
    return planned_tile_call(
        _make_kernel(block_n, row_lanes),
        [sel.astype(jnp.int32), starts.astype(jnp.int32)], [src, delta],
        name="segment_move",
        n_tiles=sel.shape[0], block_rows=block_n * row_lanes,
        dtype=src.dtype, scratch_shapes=[pltpu.SemaphoreType.DMA((1,))],
        interpret=interpret)
