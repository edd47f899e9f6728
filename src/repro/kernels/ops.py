"""Jit'd public wrappers for the kernels: pick the Pallas TPU path on TPU,
interpret=True (Python-executed kernel body) elsewhere, with pure-jnp oracles
available for oracle comparison (ref.py).

Handles padding to hardware tile multiples so callers can pass ragged CVD
shapes straight from the store.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import checkout_batched as _cb
from . import checkout_gather as _cg
from . import ref as _ref
from . import segment_append as _sa
from . import segment_move as _sm
from . import version_agg as _va
from . import vlist_membership as _vm
from .plan_launch import LANES


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _fresh_datastack(fn, *args, **kwargs):
    """``fn(*args, **kwargs)``, run on an interpreter data stack of its own.

    CPython 3.11+ keeps Python frames in 16 KB "data stack" chunks and
    frees a chunk the moment the frame at its base returns.  Tracing and
    lowering a kernel of a new shape is a deep recursion of small calls:
    where a hot call lands on a chunk boundary, every such call maps and
    unmaps a chunk, so the lowering's cost depends on how deep the caller
    happens to be (one gather lowered in 150 ms from one serving call
    path and in 267 ms from a path a few frames deeper, TPU v5e host).
    This function's frame declares a 64 K-slot stack it never uses, so
    every call opens one 1 MB chunk and the whole trace, lowering and
    compile run inside it, at the same cost from any caller."""
    return fn(*args, **kwargs)


_fresh_datastack.__code__ = _fresh_datastack.__code__.replace(
    co_stacksize=1 << 16)


def _pad_axis(x: jax.Array, mult: int, axis: int) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _lane_rows(data, min_rows: int = 1) -> tuple[jax.Array, int]:
    """(device lane-row array, row_lanes) of a logical (R, D) row array:
    columns zero-padded to a multiple of 128 lanes, rows to at least
    ``min_rows``, then viewed as (R * row_lanes, 128) — free for host
    (numpy) input, which is then uploaded once; see ``plan_launch``."""
    xp = jnp if isinstance(data, jax.Array) else np
    data = xp.asarray(data)
    r, d = data.shape
    d_pad = -(-max(d, 1) // LANES) * LANES
    if d_pad != d or r < min_rows:
        data = xp.pad(data, ((0, max(min_rows - r, 0)), (0, d_pad - d)))
    return jnp.asarray(data.reshape(-1, LANES)), d_pad // LANES


def _check_lane_rows(arr, what: str) -> None:
    if arr.ndim != 2 or arr.shape[1] != LANES:
        raise ValueError(
            f"{what} shape {tuple(arr.shape)} is not the lane-row layout "
            f"(rows, {LANES}) of the {LANES}-lane tile — build superblocks "
            "with core.checkout.build_superblock (which pads and reshapes)")


def checkout_gather(data, rids, *, block_n: int = _cg.DEFAULT_BN,
                    use_kernel: bool | None = None) -> jax.Array:
    """Materialize a version: rows of ``data`` named by ``rids``, in the
    order given — the all-row-DMA case of the wave kernel."""
    if use_kernel is None:
        use_kernel = True
    if not use_kernel:
        return _ref.gather_rows_ref(jnp.asarray(data), jnp.asarray(rids))
    rids = np.asarray(rids, np.int64)
    n, d = len(rids), np.shape(data)[1]
    if n == 0:
        return jnp.zeros((0, d), jnp.asarray(data).dtype)
    lane_data, w = _lane_rows(data)
    t = -(-n // block_n)
    starts = np.full(t * block_n, rids[-1], np.int32)
    starts[:n] = rids
    out = _cb.checkout_wave(
        lane_data, jnp.asarray(starts), jnp.zeros(t, jnp.int32),
        jnp.full(t, np.shape(data)[0], jnp.int32), block_n=block_n,
        row_lanes=w, interpret=not _on_tpu())
    return out.reshape(-1, w * LANES)[:n, :d]


def _validate_rlist(rids, *, sort: bool = True) -> tuple[np.ndarray, np.ndarray | None]:
    """Entry-point rlist validation for the tiled/batched checkout paths.

    ``plan_tiles``/``plan_batched`` require sorted, duplicate-free rlists;
    callers (DeltaBased replay, ad-hoc queries) don't always guarantee order.
    Returns (sorted_rids, order) where ``order`` is the stable argsort applied
    (None when already sorted).  Duplicates are a caller bug — a version is a
    SET of records — and raise a clear error instead of a planner assert.
    """
    rids = np.asarray(rids)
    if rids.ndim != 1:
        raise ValueError(f"rlist must be 1-D, got shape {rids.shape}")
    order = None
    if len(rids) > 1 and np.any(np.diff(rids) < 0):
        if not sort:
            raise ValueError("rlist must be sorted")
        order = np.argsort(rids, kind="stable")
        rids = rids[order]
    if len(rids) > 1 and np.any(np.diff(rids) == 0):
        raise ValueError(
            "rlist contains duplicate rids — a version is a set of records; "
            "deduplicate (np.unique) before checkout")
    return rids, order


def checkout_gather_tiled(data, rids, *, block_n: int = _cg.DEFAULT_BN,
                          block_d: int = _cg.DEFAULT_BD):
    """Ranged/tiled checkout (beyond-paper fast path for sorted rlists).

    Accepts unsorted (but duplicate-free) rlists: sorted here, and ``perm``
    is composed so packed_rows[perm] == data[rids] for the rids AS GIVEN.

    Returns (packed_rows, perm, waste) — packed_rows[perm] == data[rids]."""
    data = jnp.asarray(data)
    rids_sorted, order = _validate_rlist(rids)
    tiles, perm, waste = _cg.plan_tiles(rids_sorted, block_n=block_n)
    if order is not None:   # packed[perm][i] == data[rids_sorted[i]]
        unsorted_perm = np.empty_like(perm)
        unsorted_perm[order] = perm
        perm = unsorted_perm
    d = data.shape[1]
    bd = min(block_d, -(-max(d, 1) // LANES) * LANES)
    padded = _pad_axis(_pad_axis(data, bd, axis=1), block_n, axis=0)
    out = _cg.gather_row_tiles(padded, jnp.asarray(tiles), block_n=block_n,
                               block_d=bd, interpret=not _on_tpu())
    return out[:, :d], perm, waste


def checkout_batched(data, rlists, *, block_n: int = _cg.DEFAULT_BN,
                     density_threshold: float = 0.05,
                     interpret: bool | None = None, stages=None):
    """Fused multi-version checkout: K rlists, one wave-kernel gather.

    Plans the concatenation of the rlists with ``plan_batched`` — per-tile
    run DMAs where the rlist is dense, row DMAs where it is scattered —
    executes the whole wave as ``checkout_batched.checkout_wave`` (the
    per-tile bound degenerates to the block's row count: ``plan_batched``
    only marks exactly-consecutive chunks as runs), and splits the packed
    output back into per-version row blocks.

    Row k's block is data[rlists[k]] exactly — rids are honored AS GIVEN
    (unsorted/duplicate rids gather in request order via row DMAs; run DMAs
    only fire on exactly-consecutive chunks), matching the host fallback and
    the NumPy oracle.  Canonical sorted-unique rlists get the dense fast
    path.

    ``stages`` (a ``core.checkout.WaveStages``), when given, has the bytes
    of a host block's upload added to its ``h2d_bytes``, and the planned
    tiles and the ladder's pad tiles (``launch_tiles``) to ``tiles`` and
    ``pad_tiles``.

    Returns (list of (n_k, D) arrays in request order, BatchedPlan).
    """
    rls = []
    for rl in rlists:
        rl = np.asarray(rl)
        if rl.ndim != 1:
            raise ValueError(f"rlist must be 1-D, got shape {rl.shape}")
        rls.append(rl)
    plan = _cb.plan_batched(rls, block_n=block_n,
                            density_threshold=density_threshold)
    r, d = np.shape(data)
    if plan.n_tiles == 0:
        empty = np.zeros((0, d), dtype=np.asarray(data[:0]).dtype)
        return [empty for _ in rls], plan
    # a block shorter than one row tile cannot even TRACE the kernel (the
    # run DMA is statically block_n rows); pad rows up to the tile — runs
    # only fire on consecutive REAL rids, so the pad rows are never read
    lane_data, w = _lane_rows(data, min_rows=block_n)
    if stages is not None:
        if not isinstance(data, jax.Array):
            stages.h2d_bytes += int(lane_data.nbytes)
        stages.tiles += plan.n_tiles
        stages.pad_tiles += launch_tiles(plan.n_tiles) - plan.n_tiles
    packed = _launch(lane_data, plan.starts, plan.mode,
                     np.full(plan.n_tiles, r, np.int32), block_n=block_n,
                     row_lanes=w, interpret=interpret)
    packed = np.asarray(packed).reshape(-1, w * LANES)[:, :d]
    return [packed[plan.segment(k, block_n)] for k in range(len(rls))], plan


def checkout_wave(data, starts, mode, hi, *, block_n: int = _cg.DEFAULT_BN,
                  row_lanes: int = 1,
                  interpret: bool | None = None) -> jax.Array:
    """Cross-partition fused checkout: a whole multi-partition wave over a
    lane-row superblock ``(R * row_lanes, 128)`` — a few launches into one
    output at most (``plan_launch``).

    The superblock (``core.checkout.build_superblock``) is already padded
    and in the lane-row layout; only the plan is padded here, to
    ``launch_tiles(T)`` tiles.  Returns packed lane-rows: reshape on the
    host to ``(-1, row_lanes * 128)`` for logical rows, of which the first
    ``T * block_n`` are the plan's (the pad tiles' rows follow them).
    """
    data = jnp.asarray(data)
    _check_lane_rows(data, "superblock")
    return _launch(data, starts, mode, hi, block_n=block_n,
                   row_lanes=row_lanes, interpret=interpret)


def launch_tiles(n: int) -> int:
    """The tile count a serving gather of ``n`` planned tiles launches at.

    The jitted gather compiles once per (array shape, tile count), and a
    wave's planned count changes from wave to wave, so the launch rounds
    it up to the next rung of a fixed ladder: every count up to 16, then
    eight rungs per doubling (``m * 2**k``, 8 <= m < 16).  A launch pads
    fewer than n/8 tiles, and the waves of one rung share one compile."""
    step = 1 << max(0, (n - 1).bit_length() - 4)
    return -(-n // step) * step


def _launch(data, starts, mode, hi, *, block_n: int, row_lanes: int,
            interpret: bool | None) -> jax.Array:
    """``checkout_batched.checkout_wave`` over a lane-row ``data`` of at
    least ``block_n`` rows, with the host plan padded to ``launch_tiles``
    tiles.  A pad tile is a run DMA of rows ``[0, block_n)`` (start 0,
    mode 1, bound ``block_n``) into output rows no plan segment reaches."""
    t = len(mode)
    pad = launch_tiles(t) - t
    if pad:
        starts = np.pad(np.asarray(starts, np.int32), (0, pad * block_n))
        mode = np.pad(np.asarray(mode, np.int32), (0, pad),
                      constant_values=1)
        hi = np.pad(np.asarray(hi, np.int32), (0, pad),
                    constant_values=block_n)
    return _fresh_datastack(
        _cb.checkout_wave, data, jnp.asarray(starts), jnp.asarray(mode),
        jnp.asarray(hi), block_n=block_n, row_lanes=row_lanes,
        interpret=not _on_tpu() if interpret is None else interpret)


def segment_move(src, delta, sel, starts, *, block_n: int = _cg.DEFAULT_BN,
                 row_lanes: int = 1,
                 interpret: bool | None = None) -> jax.Array:
    """Incremental superblock migration: assemble the post-migration
    superblock reusing BN-aligned tiles of the OLD device-resident
    superblock (sel 0) and pulling only changed tiles from a small
    host-uploaded delta (sel 1).  Both sources are lane-row arrays
    (``core.checkout`` builds them that way)."""
    src = jnp.asarray(src)
    delta = jnp.asarray(delta)
    _check_lane_rows(src, "superblock")
    _check_lane_rows(delta, "delta")
    return _fresh_datastack(
        _sm.segment_move, src, delta, jnp.asarray(sel), jnp.asarray(starts),
        block_n=block_n, row_lanes=row_lanes,
        interpret=not _on_tpu() if interpret is None else interpret)


def segment_append(src, delta, sel, starts, *,
                   block_n: int = _cg.DEFAULT_BN, row_lanes: int = 1,
                   interpret: bool | None = None) -> jax.Array:
    """In-place superblock append for a commit ingest wave: assemble the
    grown superblock reusing BN-aligned tiles of the OLD device-resident
    superblock (sel 0), uploading only the new BN-aligned tiles from a
    small host delta (sel 1), and zero-filling alignment-slack tiles on
    device (sel 2).  Both sources are lane-row arrays (``core.checkout``
    builds them that way)."""
    src = jnp.asarray(src)
    delta = jnp.asarray(delta)
    _check_lane_rows(src, "superblock")
    _check_lane_rows(delta, "delta")
    return _fresh_datastack(
        _sa.segment_append, src, delta, jnp.asarray(sel), jnp.asarray(starts),
        block_n=block_n, row_lanes=row_lanes,
        interpret=not _on_tpu() if interpret is None else interpret)


def _record_block(block_r: int, r: int) -> int:
    """Records per grid step of the bitmap kernels: ``block_r``, clamped to
    the lane-padded table for tables smaller than one block."""
    if block_r <= 0 or block_r % LANES:
        raise ValueError(f"block_r={block_r} is not a positive multiple of "
                         f"{LANES} (records run along the lane axis)")
    return min(block_r, -(-max(r, 1) // LANES) * LANES)


def membership_scan(bitmap, vid: int, *, block_r: int = _vm.DEFAULT_BR):
    """(mask, per-``block_r`` counts) for version ``vid`` over the (W, R)
    bitset vlists of ``build_bitmap``."""
    bitmap = jnp.asarray(bitmap)
    r = bitmap.shape[1]
    br = _record_block(block_r, r)
    mask, cnt = _vm.membership_scan(_pad_axis(bitmap, br, axis=1), vid=vid,
                                    block_r=br, interpret=not _on_tpu())
    return mask[:r], cnt


def version_aggregate(bitmap, values, *, block_r: int = _va.DEFAULT_BR):
    """Per-version sums of ``values`` over the (W, R) bitset vlists of
    ``build_bitmap``; the (n_versions,) prefix of the (W*32,) kernel
    output is the meaningful part."""
    bitmap = jnp.asarray(bitmap)
    values = jnp.asarray(values)
    br = _record_block(block_r, bitmap.shape[1])
    return _va.version_aggregate(_pad_axis(bitmap, br, axis=1),
                                 _pad_axis(values, br, axis=0), block_r=br,
                                 interpret=not _on_tpu())


build_bitmap = _vm.build_bitmap
plan_tiles = _cg.plan_tiles
plan_batched = _cb.plan_batched


# ------------------------------------------------------------------------
# flash attention: Pallas kernel forward + blockwise custom-vjp backward
# (never materializes the SxS logits in either direction)
# ------------------------------------------------------------------------
from . import flash_attention as _fa          # noqa: E402


def _expand_kv(k, group):
    import jax.numpy as jnp
    b, sk, hkv, dh = k.shape
    return jnp.repeat(k, group, axis=2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal: bool = True, interpret: bool = True):
    """Differentiable flash attention.  Forward = Pallas kernel
    (interpret=True executes the kernel body on CPU; on a TPU runtime pass
    interpret=False for the Mosaic build).  Backward = blockwise lax.scan
    recomputation — per-tile probabilities only, O(S·BK) live memory."""
    return _fa.flash_attention_fwd(q, k, v, causal=causal,
                                   interpret=interpret)


def _flash_fwd_rule(q, k, v, causal, interpret):
    o = _fa.flash_attention_fwd(q, k, v, causal=causal, interpret=interpret)
    lse = _row_lse(q, k, causal)               # (B, Sq, H) f32
    return o, (q, k, v, o, lse)


def _blocks(s, bk):
    bk = min(bk, s)
    while s % bk:
        bk //= 2
    return max(bk, 1)


def _row_lse(q, k, causal, block_k: int = 512):
    """logsumexp of the scaled causal logits rows, streamed over K blocks."""
    import jax.numpy as jnp
    b, sq, h, dh = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    bk = _blocks(sk, block_k)
    scale = dh ** -0.5
    kb = k.reshape(b, sk // bk, bk, hkv, dh).transpose(1, 0, 2, 3, 4)
    kpos = jnp.arange(sk).reshape(sk // bk, bk)
    qpos = jnp.arange(sq)

    def step(carry, xs):
        m_run, l_run = carry
        k_c, kp = xs
        qg = q.reshape(b, sq, hkv, g, dh)
        s_c = jnp.einsum("bqhgd,bkhd->bqhgk", qg, k_c).astype(jnp.float32)
        s_c = s_c * scale                       # (B,Sq,Hkv,G,BK)
        if causal:
            mask = kp[None, :] <= qpos[:, None]           # (Sq, BK)
            s_c = jnp.where(mask[None, :, None, None, :], s_c, -1e30)
        m_c = jnp.max(s_c, axis=-1)
        m_new = jnp.maximum(m_run, m_c)
        l_new = l_run * jnp.exp(m_run - m_new) + \
            jnp.sum(jnp.exp(s_c - m_new[..., None]), axis=-1)
        return (m_new, l_new), None

    m0 = jnp.full((b, sq, hkv, g), -1e30, jnp.float32)
    l0 = jnp.zeros((b, sq, hkv, g), jnp.float32)
    (m, l), _ = jax.lax.scan(step, (m0, l0), (kb, kpos))
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    return lse.reshape(b, sq, h)


def _flash_bwd_rule(causal, interpret, res, do):
    """Blockwise backward: for each K block, rebuild P from (q, k, lse) and
    accumulate dq; dk/dv accumulate per block.  Live memory O(Sq·BK)."""
    import jax.numpy as jnp
    q, k, v, o, lse = res
    b, sq, h, dh = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    bk = _blocks(sk, 512)
    scale = dh ** -0.5
    qg = q.reshape(b, sq, hkv, g, dh)
    dog = do.reshape(b, sq, hkv, g, dh)
    lseg = lse.reshape(b, sq, hkv, g)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(b, sq, hkv, g)        # (B,Sq,Hkv,G)
    kb = k.reshape(b, sk // bk, bk, hkv, dh).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, sk // bk, bk, hkv, dh).transpose(1, 0, 2, 3, 4)
    kpos = jnp.arange(sk).reshape(sk // bk, bk)
    qpos = jnp.arange(sq)

    def step(dq_acc, xs):
        k_c, v_c, kp = xs
        s_c = jnp.einsum("bqhgd,bkhd->bqhgk", qg, k_c).astype(jnp.float32)
        s_c = s_c * scale
        if causal:
            mask = kp[None, :] <= qpos[:, None]
            s_c = jnp.where(mask[None, :, None, None, :], s_c, -1e30)
        p = jnp.exp(s_c - lseg[..., None])                  # (B,Sq,Hkv,G,BK)
        dov = jnp.einsum("bqhgd,bkhd->bqhgk", dog.astype(jnp.float32),
                         v_c.astype(jnp.float32))
        ds = p * (dov - delta[..., None]) * scale
        dq_c = jnp.einsum("bqhgk,bkhd->bqhgd", ds, k_c.astype(jnp.float32))
        dk_c = jnp.einsum("bqhgk,bqhgd->bkhd", ds, qg.astype(jnp.float32))
        dv_c = jnp.einsum("bqhgk,bqhgd->bkhd", p, dog.astype(jnp.float32))
        return dq_acc + dq_c, (dk_c, dv_c)

    dq0 = jnp.zeros((b, sq, hkv, g, dh), jnp.float32)
    dq, (dk_b, dv_b) = jax.lax.scan(step, dq0, (kb, vb, kpos))
    dk = dk_b.transpose(1, 0, 2, 3, 4).reshape(b, sk, hkv, dh)
    dv = dv_b.transpose(1, 0, 2, 3, 4).reshape(b, sk, hkv, dh)
    return (dq.reshape(b, sq, h, dh).astype(q.dtype),
            dk.astype(k.dtype), dv.astype(v.dtype))


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)
