"""Pure-jnp oracles for every kernel in this package.

Tests sweep shapes/dtypes and assert_allclose kernel-vs-oracle; the oracles
are also the CPU/GPU fallback paths in ops.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def gather_rows_ref(data: jax.Array, rids: jax.Array) -> jax.Array:
    return jnp.take(data, rids, axis=0)


def gather_batched_ref(data, rlists):
    """NumPy oracle for checkout_batched: per-version gather loop."""
    import numpy as np
    data = np.asarray(data)
    return [data[np.asarray(rl, dtype=np.int64)] for rl in rlists]


def gather_row_tiles_ref(data: jax.Array, tile_idx: jax.Array, block_n: int) -> jax.Array:
    r, d = data.shape
    tiles = data.reshape(r // block_n, block_n, d)
    return jnp.take(tiles, tile_idx, axis=0).reshape(-1, d)


def membership_scan_ref(bitmap: jax.Array, vid: int, block_r: int
                        ) -> tuple[jax.Array, jax.Array]:
    """bitmap: (W, R) uint32, as ``build_bitmap`` lays it out."""
    word, bit = vid // 32, vid % 32
    mask = ((bitmap[word] >> jnp.uint32(bit)) & jnp.uint32(1)).astype(jnp.int32)
    cnt = mask.reshape(-1, block_r).sum(axis=1).astype(jnp.int32)
    return mask, cnt


def version_aggregate_ref(bitmap: jax.Array, values: jax.Array) -> jax.Array:
    """bitmap: (W, R) uint32, as ``build_bitmap`` lays it out."""
    w, r = bitmap.shape
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = ((bitmap[:, :, None] >> shifts[None, None, :]) & jnp.uint32(1))  # (W, R, 32)
    vals = values.astype(jnp.float32)
    out = jnp.einsum("wrb,r->wb", bits.astype(jnp.float32), vals)
    return out.reshape(w * 32)


def mha_ref(q, k, v, causal: bool = True):
    """Materialized-softmax GQA attention oracle for flash_attention.
    q: (B,Sq,H,Dh); k/v: (B,Sk,Hkv,Dh)."""
    import jax
    import jax.numpy as jnp
    b, sq, h, dh = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qg = q.reshape(b, sq, hkv, g, dh)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32)
    logits = logits * dh ** -0.5
    if causal:
        m = jnp.arange(k.shape[1])[None, :] <= jnp.arange(sq)[:, None]
        logits = jnp.where(m[None, None, None], logits, -1e30)
    p = jax.nn.softmax(logits, -1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v)
    return out.reshape(b, sq, h, dh)


def ssd_chunk_ref(xs, bmat, cmat, dt, a, chunk: int = 256):
    """Chunked-SSD oracle mirroring models/ssd.ssd_forward's scan math.
    xs: (B,L,H,P); bmat/cmat: (B,L,N); dt: (B,L,H) post-softplus; a: (H,)."""
    import jax
    import jax.numpy as jnp
    b, l, h, p = xs.shape
    n = bmat.shape[-1]
    q = min(chunk, l)
    nc = l // q
    xs_c = xs.reshape(b, nc, q, h, p).swapaxes(0, 1).astype(jnp.float32)
    b_c = bmat.reshape(b, nc, q, n).swapaxes(0, 1).astype(jnp.float32)
    c_c = cmat.reshape(b, nc, q, n).swapaxes(0, 1).astype(jnp.float32)
    dt_c = dt.reshape(b, nc, q, h).swapaxes(0, 1).astype(jnp.float32)
    mask = jnp.tril(jnp.ones((q, q), bool))

    def step(h_prev, inp):
        x1, b1, c1, d1 = inp
        cum = jnp.cumsum(d1 * a, axis=1)
        lmat = jnp.where(mask[None, :, :, None],
                         jnp.exp(cum[:, :, None, :] - cum[:, None, :, :]), 0.0)
        cb = jnp.einsum("bin,bjn->bij", c1, b1)
        att = cb[..., None] * lmat * d1[:, None, :, :]
        y = jnp.einsum("bijh,bjhp->bihp", att, x1)
        y += jnp.einsum("bin,bih,bhpn->bihp", c1, jnp.exp(cum), h_prev)
        dec = jnp.exp(cum[:, -1:, :] - cum)
        s = jnp.einsum("bjh,bjn,bjhp->bhpn", dec * d1, b1, x1)
        h_new = h_prev * jnp.exp(cum[:, -1])[..., None, None] + s
        return h_new, y

    h0 = jnp.zeros((b, h, p, n), jnp.float32)
    _, y = jax.lax.scan(step, h0, (xs_c, b_c, c_c, dt_c))
    return y.swapaxes(0, 1).reshape(b, l, h, p).astype(xs.dtype)
