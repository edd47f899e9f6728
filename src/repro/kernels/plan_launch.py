"""Launch a planned per-tile kernel in pieces whose plan fits SMEM.

The store's device kernels (``checkout_wave``, ``segment_move``,
``segment_append``) are driven by a per-tile plan that rides scalar
prefetch, i.e. lives in SMEM for the whole launch.  SMEM is small (1 MiB
on a TPU v5e), so one launch over a whole SCI_1M wave (~30K-60K tiles at
40 B each) or a whole-store superblock (~250K tiles at 8 B each) does not
compile.  ``planned_tile_call`` splits the tile range into launches of at
most ``PLAN_SMEM_BYTES`` of plan each; every launch writes its tiles into
the SAME output buffer (aliased through ``input_output_aliases``), so the
result is one array, bit-identical to a single launch, with no copy.

Every array these kernels move is in the LANE-ROW layout: a logical
``(R, D)`` row array with D a multiple of ``LANES`` is stored as its
row-major view ``(R * D/LANES, LANES)``.  A logical row is ``row_lanes``
consecutive lane-rows, so a run of BN logical rows is one contiguous DMA
whatever the width.  (With the logical ``(R, D > 128)`` shape the TPU
tiles rows in groups of 8 across several lane tiles, and Mosaic refuses
a run DMA whose first row is not provably a multiple of 8.)
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128                   # lane width of a TPU vreg / HBM tile
PLAN_SMEM_BYTES = 512 * 1024  # prefetched plan bytes per launch: half of a
                              # v5e's 1 MiB SMEM, the rest is headroom for
                              # the compiler's own scalars and semaphores


def launch_ranges(n_tiles: int, bytes_per_tile: int) -> list[tuple[int, int]]:
    """(first tile, tile count) of each launch covering ``n_tiles``."""
    per = max(1, PLAN_SMEM_BYTES // max(1, bytes_per_tile))
    return [(t0, min(per, n_tiles - t0)) for t0 in range(0, n_tiles, per)]


def planned_tile_call(kernel, scalars, operands, *, name: str, n_tiles: int,
                      block_rows: int, dtype, scratch_shapes,
                      interpret: bool):
    """Run ``kernel`` over tiles ``0..n_tiles-1`` in SMEM-sized launches.

    kernel:   the Pallas body, called as ``kernel(*scalar_refs,
              *operand_refs, o_ref, *scratch)`` with ``pl.program_id(0)``
              the tile index LOCAL to its launch (the scalar refs hold
              only that launch's slice of the plan).
    scalars:  int32 per-tile plan arrays, each with a whole number of
              entries per tile (prefetched to SMEM per launch).
    operands: HBM inputs (``memory_space=ANY``), read by manual DMA.
    name:     the kernel's name in the compiled program (every launch of
              the split carries it), so a trace tells the kernels apart.
    Returns the ``(n_tiles * block_rows, LANES)`` output; tile t is rows
    ``[t * block_rows, (t + 1) * block_rows)``."""
    per_tile = [int(s.shape[0]) // n_tiles for s in scalars]
    out_shape = jax.ShapeDtypeStruct((n_tiles * block_rows, LANES), dtype)
    n_fixed = len(scalars) + len(operands)
    out = None
    for t0, n in launch_ranges(n_tiles, 4 * sum(per_tile)):
        chunk = [s[t0 * k:(t0 + n) * k] for s, k in zip(scalars, per_tile)]
        args = [*chunk, *operands] + ([] if out is None else [out])
        spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(n,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)]
            * (len(args) - len(scalars)),
            out_specs=pl.BlockSpec(
                (block_rows, LANES), lambda i, *_, t0=t0: (i + t0, 0)),
            scratch_shapes=scratch_shapes,
        )
        out = pl.pallas_call(
            functools.partial(_drop_alias, kernel, n_fixed, out is not None),
            grid_spec=spec, out_shape=out_shape,
            input_output_aliases={} if out is None else {len(args) - 1: 0},
            interpret=interpret, name=name,
        )(*args)
    return out


def _drop_alias(kernel, n_fixed: int, aliased: bool, *refs):
    # the previous launch's output rides in as an aliased input only so
    # the tiles it wrote survive; the body never reads it
    if aliased:
        refs = refs[:n_fixed] + refs[n_fixed + 1:]
    kernel(*refs)
