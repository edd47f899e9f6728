"""Version-membership bitmap kernel — the TPU realization of the paper's
``ARRAY[v] <@ vlist`` containment scan (combined-table / split-by-vlist
checkout, Table 1) and of version-predicate queries.

Representation: the vlist column is a *bitset*: ``bitmap`` is (W, R) uint32
with W = ceil(n_versions / 32); bit v of ``bitmap[v // 32, r]`` is set iff
record r ∈ version v.  Records run along the 128-lane axis, so the kernels
read the bitmap as built, with no transpose.  This is the range/bitmap-encoded vlist the paper cites as a
further compression ([14], §3.2) — a beyond-paper feature we make first-class
because TPUs vectorize bit ops over 32-lane words natively.

Kernel: one pass over the bitmap — the scanned word row and the mask are
lane-dense — BR records per grid step; emits a per-record 0/1 membership mask and a
per-block popcount (so the host can size the compacted result without a
second scan).  Bandwidth-bound by design: W words/row vs D attrs/row means
the scan touches W/D of the data a full-table scan would — the
quantitative reason combined-table checkout loses to split-by-rlist only by
a small factor (paper Fig 3c) while commit loses by orders of magnitude.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .plan_launch import LANES

DEFAULT_BR = 1024   # records per grid step (a multiple of 128 lanes)


def _membership_kernel(bm_ref, mask_ref, cnt_ref, *, word: int, bit: int):
    w = bm_ref[word:word + 1, :]                  # (1, BR) uint32
    m = ((w >> jnp.uint32(bit)) & jnp.uint32(1)).astype(jnp.int32)
    mask_ref[...] = m
    cnt_ref[...] = jnp.broadcast_to(jnp.sum(m, axis=1, keepdims=True),
                                    cnt_ref.shape)


@functools.partial(jax.jit, static_argnames=("vid", "block_r", "interpret"))
def membership_scan(bitmap: jax.Array, *, vid: int,
                    block_r: int = DEFAULT_BR, interpret: bool = False
                    ) -> tuple[jax.Array, jax.Array]:
    """Return (mask (R,) int32, per-block counts (R/BR,) int32) for vid.

    bitmap: (W, R) uint32 bitset (``build_bitmap``), R a multiple of
    block_r and block_r a multiple of 128 (pad with zero records).
    """
    w, r = bitmap.shape
    br = min(block_r, r)
    assert r % br == 0 and br % LANES == 0, (r, br)
    n_blocks = r // br
    kernel = functools.partial(_membership_kernel, word=vid // 32,
                               bit=vid % 32)
    mask, cnt = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec((w, br), lambda i: (0, i))],
        out_specs=[pl.BlockSpec((1, br), lambda i: (0, i)),
                   pl.BlockSpec((1, LANES), lambda i: (0, i))],
        out_shape=[jax.ShapeDtypeStruct((1, r), jnp.int32),
                   jax.ShapeDtypeStruct((1, n_blocks * LANES), jnp.int32)],
        interpret=interpret,
    )(bitmap)
    return mask[0], cnt[0, ::LANES]


def build_bitmap(rlists, n_records: int) -> jax.Array:
    """Host-side: CSR rlists -> (W, R) uint32 bitset (numpy), the layout
    the kernels read."""
    import numpy as np
    n_versions = len(rlists)
    w = (n_versions + 31) // 32
    bm = np.zeros((w, n_records), dtype=np.uint32)
    for v, rl in enumerate(rlists):
        bm[v // 32, np.asarray(rl)] |= np.uint32(1 << (v % 32))
    return bm
