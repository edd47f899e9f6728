"""Batched multi-version checkout kernel — K versions, ONE ``pallas_call``.

``checkout_gather`` retrieves one version per kernel launch; serving heavy
multi-user traffic means retrieving MANY versions per request wave (RStore's
batched retrieval; Bhattacherjee et al.'s recreation/storage tradeoff).  K
launches pay K pipeline spin-ups and K stalls between DMA streams.  This
kernel fuses the whole wave into one scalar-prefetched plan executed by a
single launch — one pipelined DMA stream for the concatenation of K rlists.

Data flow::

    rlists (K versions, sorted rids each)
      └─ plan_batched                       [host, vectorized numpy]
           chunks each rlist into BN-row output tiles and classifies every
           tile by measured run density:
             mode 1 — the BN rids are consecutive -> ONE (BN, BD) run DMA
                      (the tile-gather path; LYRESPLIT partitions make this
                      the common case)
             mode 0 — scattered rids           -> BN (1, BD) row DMAs
                      (the row-gather path)
           emits (starts, mode, tile_offsets): a flat tile plan whose
           concatenation covers every requested version back to back
      └─ checkout_wave                      [device, pallas_call]
           grid = (total_tiles,); the plan rides in scalar-prefetch (SMEM)
           so the DMA engine sees every source address ahead of the body —
           the K-version wave streams as one pipeline (split into a few
           launches into one output when the plan outgrows SMEM; see
           ``plan_launch``)
      └─ split per version                  [host, zero-copy slices]
           out[k] = packed[tile_offsets[k]*BN : tile_offsets[k]*BN + n_k]

Rows come back in rlist order per version (no perm needed); per-version
padding to the BN-row tile boundary re-reads that version's last row and is
sliced off on the host.

Cross-partition waves (``checkout_wave``) add a THIRD prefetched scalar:
``core.checkout.plan_wave`` rebases every version's local rlist by its
partition's row offset inside a device-resident superblock, so one flat
(starts, mode) plan covers versions from *different* partitions back to
back.  The rebase lets the planner promote consecutive tail chunks to run
DMAs (the padded rows land in the sliced-off region), which makes a run DMA
read past a version's last valid row — ``hi`` carries the per-tile exclusive
row bound (the tile's partition segment end) and the kernel only issues the
run DMA when ``start + BN <= hi[t]``, falling back to row DMAs otherwise.
The bounds check runs on device, so a stale plan degrades to correct row
gathers instead of reading out of bounds.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .checkout_gather import DEFAULT_BN
from .plan_launch import planned_tile_call


@dataclasses.dataclass(frozen=True)
class BatchedPlan:
    """Host-side gather plan for one fused multi-version checkout."""

    starts: np.ndarray        # (T*BN,) int32 — source rid per packed output row
    mode: np.ndarray          # (T,) int32 — 1 = run DMA, 0 = per-row DMAs
    tile_offsets: np.ndarray  # (K+1,) int64 — version k owns tiles [k, k+1)
    n_rows: np.ndarray        # (K,) int64 — valid rows per version
    density: np.ndarray       # (K,) float — fraction of full-run tiles

    @property
    def n_tiles(self) -> int:
        return len(self.mode)

    def segment(self, k: int, block_n: int) -> slice:
        s = int(self.tile_offsets[k]) * block_n
        return slice(s, s + int(self.n_rows[k]))


def plan_batched(rlists, block_n: int = DEFAULT_BN,
                 density_threshold: float = 0.05) -> BatchedPlan:
    """Chunk K rlists into a flat adaptive tile plan.

    Rids are planned AS GIVEN (output row i of version k is
    data[rlists[k][i]]); run DMAs only fire on exactly-consecutive chunks,
    so unsorted or duplicate rids simply fall back to row DMAs.

    Per version, the measured run density (fraction of BN-row chunks whose
    rids are consecutive) picks the gather mode: above ``density_threshold``
    the consecutive chunks go out as single run DMAs (tile-gather); below it
    every chunk uses row DMAs — mixed-mode bookkeeping isn't worth it when
    runs almost never happen.

    Vectorized across versions: one flat padded rid array, one diff pass,
    one segment reduction — no per-version python work.  On the serve
    pipeline the plan runs on the host thread UNDER the previous wave's
    in-flight kernel, so python-loop churn here would convoy the kernel's
    runtime; ``plan_batched_loop`` keeps the per-version original as the
    oracle."""
    k_total = len(rlists)
    rls = [np.asarray(rl, dtype=np.int64) for rl in rlists]
    n_rows = np.fromiter((len(rl) for rl in rls), np.int64, k_total)
    t_per = -(-n_rows // block_n)
    tile_offsets = np.zeros(k_total + 1, np.int64)
    np.cumsum(t_per, out=tile_offsets[1:])
    total = int(tile_offsets[-1]) * block_n
    if total == 0:
        return BatchedPlan(starts=np.zeros(0, np.int32),
                           mode=np.zeros(0, np.int32),
                           tile_offsets=tile_offsets, n_rows=n_rows,
                           density=np.zeros(k_total, np.float64))
    # flat padded rids: init every slot to its version's LAST rid (padding
    # repeats it, so a padded tail can never appear consecutive), then
    # scatter the valid rids over the prefix of each version's segment
    last = np.fromiter((rl[-1] if len(rl) else 0 for rl in rls),
                       np.int64, k_total)
    flat = np.repeat(last, t_per * block_n)
    valid = np.concatenate([rl for rl in rls if len(rl)]) if n_rows.any() \
        else np.zeros(0, np.int64)
    row0 = np.concatenate([[0], np.cumsum(n_rows)[:-1]])
    flat_idx = np.repeat(tile_offsets[:-1] * block_n - row0, n_rows) \
        + np.arange(len(valid))
    flat[flat_idx] = valid
    chunks = flat.reshape(-1, block_n)
    # a chunk is a run iff its rids are consecutive
    runs = np.all(np.diff(chunks, axis=1) == 1, axis=1) if block_n > 1 \
        else np.ones(len(chunks), bool)
    rsum = np.concatenate([[0], np.cumsum(runs)])
    per_version = (rsum[tile_offsets[1:]]
                   - rsum[tile_offsets[:-1]]).astype(np.float64)
    density = np.divide(per_version, t_per, out=np.zeros(k_total, np.float64),
                        where=t_per > 0)
    # below-threshold versions demote every chunk to row DMAs
    runs &= np.repeat(density >= density_threshold, t_per)
    return BatchedPlan(starts=flat.astype(np.int32),
                       mode=runs.astype(np.int32),
                       tile_offsets=tile_offsets, n_rows=n_rows,
                       density=density)


def plan_batched_loop(rlists, block_n: int = DEFAULT_BN,
                      density_threshold: float = 0.05) -> BatchedPlan:
    """The original per-version planning loop — the oracle
    ``plan_batched``'s vectorization is property-tested against."""
    starts_parts: list[np.ndarray] = []
    mode_parts: list[np.ndarray] = []
    tile_offsets = np.zeros(len(rlists) + 1, np.int64)
    n_rows = np.zeros(len(rlists), np.int64)
    density = np.zeros(len(rlists), np.float64)
    for k, rl in enumerate(rlists):
        rl = np.asarray(rl, dtype=np.int64)
        n = len(rl)
        n_rows[k] = n
        t = -(-n // block_n) if n else 0
        tile_offsets[k + 1] = tile_offsets[k] + t
        if n == 0:
            continue
        pad = t * block_n - n
        padded = np.concatenate([rl, np.full(pad, rl[-1], np.int64)]) if pad \
            else rl
        chunks = padded.reshape(t, block_n)
        runs = np.all(np.diff(chunks, axis=1) == 1, axis=1) if block_n > 1 \
            else np.ones(t, bool)
        density[k] = float(runs.mean())
        if density[k] < density_threshold:
            runs = np.zeros(t, bool)
        starts_parts.append(padded.astype(np.int32))
        mode_parts.append(runs.astype(np.int32))
    starts = np.concatenate(starts_parts) if starts_parts \
        else np.zeros(0, np.int32)
    mode = np.concatenate(mode_parts) if mode_parts else np.zeros(0, np.int32)
    return BatchedPlan(starts=starts, mode=mode, tile_offsets=tile_offsets,
                       n_rows=n_rows, density=density)


def _make_wave_kernel(block_n: int, row_lanes: int):
    """The wave gather body over lane-row arrays: a logical row is
    ``row_lanes`` consecutive lane-rows.  Run DMAs fire only when the whole
    BN-row read stays inside the tile's partition segment of the
    superblock (``hi`` is the exclusive bound, in logical rows)."""
    w = row_lanes

    def kernel(starts_ref, mode_ref, hi_ref, data_ref, o_ref, sems):
        t = pl.program_id(0)
        s0 = starts_ref[t * block_n]
        run_ok = jnp.logical_and(mode_ref[t] == 1,
                                 s0 + block_n <= hi_ref[t])

        @pl.when(run_ok)
        def _run():
            cp = pltpu.make_async_copy(
                data_ref.at[pl.ds(s0 * w, block_n * w)], o_ref, sems.at[0])
            cp.start()
            cp.wait()

        @pl.when(jnp.logical_not(run_ok))
        def _rows():
            def row(i):
                return pltpu.make_async_copy(
                    data_ref.at[pl.ds(starts_ref[t * block_n + i] * w, w)],
                    o_ref.at[pl.ds(i * w, w)], sems.at[i])
            for i in range(block_n):
                row(i).start()
            for i in range(block_n):
                row(i).wait()

    return kernel


@functools.partial(jax.jit, static_argnames=("block_n", "row_lanes",
                                             "interpret"))
def checkout_wave(data: jax.Array, starts: jax.Array, mode: jax.Array,
                  hi: jax.Array, *, block_n: int = DEFAULT_BN,
                  row_lanes: int = 1, interpret: bool = False) -> jax.Array:
    """Execute a cross-partition ``plan_wave`` plan over a lane-row
    superblock, in as few launches as SMEM allows (see ``plan_launch``).

    data:   (R*row_lanes, 128) lane-row superblock — every partition's
            rows concatenated, each logical row ``row_lanes`` lane-rows.
    starts: (T*block_n,) int32 superblock rids (rebased by partition offset).
    mode:   (T,) int32 per-tile gather mode (1 = run candidate).
    hi:     (T,) int32 per-tile exclusive row bound for run DMAs.
    Returns (T*block_n*row_lanes, 128) packed lane-rows: logical row i is
    lane-rows [i*row_lanes, (i+1)*row_lanes); slice per version with the
    plan's segments after the host reshape to (T*block_n, row_lanes*128).
    """
    t = mode.shape[0]
    return planned_tile_call(
        _make_wave_kernel(block_n, row_lanes),
        [starts.astype(jnp.int32), mode.astype(jnp.int32),
         hi.astype(jnp.int32)], [data],
        name="checkout_wave",
        n_tiles=t, block_rows=block_n * row_lanes, dtype=data.dtype,
        scratch_shapes=[pltpu.SemaphoreType.DMA((block_n,))],
        interpret=interpret)
