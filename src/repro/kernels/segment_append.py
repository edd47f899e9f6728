"""Device-side superblock APPEND kernel — in-place commit ingestion.

``PartitionedCVD.commit_many`` grows the touched partitions of a pinned
group superblock: existing rows keep their bytes, new rows land at the
tail of each partition segment.  ``segment_move`` already assembles an
output whose tile count is independent of the source's row count, but a
commit wave adds one tile kind migration never produces: an ALL-PAD tile
(a freshly BN-aligned segment tail no real row maps into yet).  Routing
those through the host delta would upload garbage bytes just to own them;
this kernel zero-fills them on device instead.

Every BN-row output tile of the post-ingest superblock is produced by one
of three per-tile selector modes (prefetched to SMEM like the rest of the
wave-engine plans, one SMEM-sized launch at a time):

    sel[t] == 0  ->  reuse: copy rows [start[t], start[t]+BN) of the OLD
                     device-resident superblock (device-to-device; never
                     crosses the host link)
    sel[t] == 1  ->  delta: copy rows [start[t], start[t]+BN) of the small
                     host-uploaded delta block (the new BN-aligned tiles —
                     the ONLY bytes a commit wave sends over the link)
    sel[t] == 2  ->  pad: zero-fill the tile on device (alignment slack;
                     no source read at all)

``core.checkout._extend_group_superblock`` builds (sel, start, delta)
from the pre/post-commit partition grids; bytes_uploaded = delta.nbytes
vs re-deriving the whole group through eviction + rebuild.
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

        @pl.when(sel_ref[t] == 1)
        def _delta():
            cp = pltpu.make_async_copy(
                delta_ref.at[pl.ds(s0, rows)], o_ref, sems.at[0])
            cp.start()
            cp.wait()

        @pl.when(sel_ref[t] == 2)
        def _pad():
            o_ref[...] = jnp.zeros_like(o_ref)

    return kernel


@functools.partial(jax.jit, static_argnames=("block_n", "row_lanes",
                                             "interpret"))
def segment_append(src: jax.Array, delta: jax.Array, sel: jax.Array,
                   starts: jax.Array, *, block_n: int = DEFAULT_BN,
                   row_lanes: int = 1, interpret: bool = False) -> jax.Array:
    """Extend a superblock in place: T output tiles, in as few launches as
    SMEM allows (see ``plan_launch``).

    src:    (R_old*row_lanes, 128) the pre-commit lane-row superblock
            (device-resident).
    delta:  (R_delta*row_lanes, 128) host-uploaded new/changed rows,
            BN-tile packed, same lane-row layout.
    sel:    (T,) int32 per-tile source — 0 = src, 1 = delta, 2 = zero pad.
    starts: (T,) int32 first source row (logical) of the tile in its
            chosen source (ignored for sel == 2).
    Returns (T*block_n*row_lanes, 128): the post-commit superblock.
    Growth is the norm: T*block_n exceeds R_old by the wave's BN-aligned
    new tiles.

    Every sel 0/1 run [starts[t], starts[t]+block_n) must be in-bounds for
    its source — ``core.checkout.extend_superblock_after_commit``
    guarantees it by construction (runs that would cross an old aligned
    segment end are routed to the delta; all-pad tiles never read a
    source).
    """
    return planned_tile_call(
        _make_kernel(block_n, row_lanes),
        [sel.astype(jnp.int32), starts.astype(jnp.int32)], [src, delta],
        name="segment_append",
        n_tiles=sel.shape[0], block_rows=block_n * row_lanes,
        dtype=src.dtype, scratch_shapes=[pltpu.SemaphoreType.DMA((1,))],
        interpret=interpret)
