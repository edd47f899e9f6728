"""Per-version aggregate kernel — the cross-version analytics class of
paper §2.2 ("aggregate count of protein-protein tuples with confidence > 0.9,
for each version") as a TPU-native bitmap matvec.

Insight: with the bitset vlist (see vlist_membership.py), the per-version
aggregate over a value column is

    out[v] = Σ_r  bit(r, v) · val[r]

i.e. a {0,1}-matrix × vector product.  Unpacking 32 versions from one uint32
word turns the CSR segment-sum (scatter-heavy, TPU-hostile) into a dense
(32, BR) × (BR,) reduction per word row — VPU-friendly, no scatters,
sequential HBM traffic.  The bitmap is (W, R) as ``build_bitmap`` lays it
out, so records run along the 128-lane axis; the grid walks record blocks and
accumulates into the one (W*32, 1) output block every step revisits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .plan_launch import LANES

DEFAULT_BR = 1024   # records per grid step (a multiple of 128 lanes)


def _agg_kernel(bm_ref, val_ref, o_ref, *, n_words: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    vals = val_ref[...]                                       # (1, BR) f32
    br = vals.shape[1]
    shifts = jax.lax.broadcasted_iota(jnp.int32, (32, br), 0) \
        .astype(jnp.uint32)
    for k in range(n_words):
        word = jnp.broadcast_to(bm_ref[k:k + 1, :], (32, br))  # (32, BR)
        bits = ((word >> shifts) & jnp.uint32(1)).astype(jnp.int32) \
            .astype(jnp.float32)
        part = jnp.sum(bits * vals, axis=1, keepdims=True)     # (32, 1)
        o_ref[k * 32:(k + 1) * 32, :] += part


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def version_aggregate(bitmap: jax.Array, values: jax.Array, *,
                      block_r: int = DEFAULT_BR, interpret: bool = False
                      ) -> jax.Array:
    """out: (W*32,) float32 — per-version sums of ``values`` (masked upstream
    for predicates; use values=1.0 for COUNT).

    bitmap: (W, R) uint32 bitset (``build_bitmap``); values: (R,) float32;
    R a multiple of block_r, block_r a multiple of 128.
    """
    w, r = bitmap.shape
    br = min(block_r, r)
    assert r % br == 0 and br % LANES == 0, (r, br)
    out = pl.pallas_call(
        functools.partial(_agg_kernel, n_words=w),
        grid=(r // br,),
        in_specs=[pl.BlockSpec((w, br), lambda rb: (0, rb)),
                  pl.BlockSpec((1, br), lambda rb: (0, rb))],
        out_specs=pl.BlockSpec((w * 32, 1), lambda rb: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((w * 32, 1), jnp.float32),
        interpret=interpret,
    )(bitmap, values.astype(jnp.float32).reshape(1, r))
    return out[:, 0]
