"""Training step: microbatched grad accumulation + AdamW, with optional
int8 error-feedback gradient compression on the cross-pod reduction.

The engine is versioning-UNAWARE (DESIGN.md §2): batches arrive as plain
(tokens, labels); the paper's machinery lives entirely in repro.data.

Compression design: with the plain step, autodiff's gradient all-reduce spans
("pod","data") at full width.  With ``grad_compress=True`` the step runs the
loss/grad computation inside ``shard_map`` MANUAL over "pod" only (data/model
stay auto-sharded), so autodiff reduces gradients within the pod at full
precision, and the scarce cross-pod hop carries int8 (accumulated in int32)
with a per-tensor scale and per-pod error-feedback residual — 4× less
inter-pod traffic for <1e-2 relative gradient error (tests/test_train.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..models.transformer import ArchConfig, loss_fn, param_specs
from ..sharding import MeshContext, dp_spec, mesh_context, shard
from .optimizer import AdamW, AdamWState


def _drop_fsdp(spec: P) -> P:
    """Replace the FSDP ("data") factor of a PartitionSpec with None."""
    def fix(e):
        if e == "data":
            return None
        if isinstance(e, (tuple, list)):
            kept = tuple(a for a in e if a != "data")
            return kept if kept else None
        return e
    return P(*(fix(e) for e in spec))


def cast_and_gather_params(params, specs):
    """ZeRO-1: bf16 working copy of the f32 master params, gathered over the
    FSDP axis ONCE PER STEP (kept TP-sharded).  Without this the weight
    all-gathers re-run inside every microbatch of the grad-accumulation scan
    and in the remat recompute — measured 5x the necessary weight traffic on
    llava train_4k (§Perf iteration B3)."""
    def one(p, s):
        if p.dtype == jnp.float32:
            return shard(p.astype(jnp.bfloat16), _drop_fsdp(s))
        return p
    out = jax.tree.map(one, params, specs,
                       is_leaf=lambda x: hasattr(x, "dtype"))
    # NOTE: attempted as §Perf iteration B3a and REVERTED — XLA:CPU re-sinks
    # the hoisted gathers into the microbatch/layer scans even behind an
    # optimization_barrier, so this only added a full bf16 param copy
    # (+4.3 GB peak on llava-34B) for zero traffic win.  Kept for the
    # hypothesis record; make_train_step no longer calls it.
    return jax.lax.optimization_barrier(out)


def _split_microbatches(batch: dict, n: int) -> dict:
    def re(x):
        b = x.shape[0]
        assert b % n == 0, (b, n)
        return x.reshape(n, b // n, *x.shape[1:])
    return jax.tree.map(re, batch)


def accumulate_grads(params, batch: dict, cfg: ArchConfig):
    """Mean loss + grads over cfg.microbatches sequential microbatches."""
    n = cfg.microbatches
    if n <= 1:
        loss, grads = jax.value_and_grad(loss_fn)(params, batch, cfg)
        return loss, grads

    mb = _split_microbatches(batch, n)

    def body(carry, mbatch):
        acc, loss_acc = carry
        loss, grads = jax.value_and_grad(loss_fn)(params, mbatch, cfg)
        acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32), acc, grads)
        return (acc, loss_acc + loss), None

    zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (gsum, lsum), _ = jax.lax.scan(body, (zero, jnp.zeros((), jnp.float32)), mb)
    inv = 1.0 / n
    return lsum * inv, jax.tree.map(lambda g: g * inv, gsum)


# ------------------------------------------------ int8 EF compression ------
def quantize_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def ef_init(params, npods: int):
    """Per-pod error-feedback residuals, stacked on a leading pod axis."""
    return jax.tree.map(
        lambda p: jnp.zeros((npods, *p.shape), jnp.float32), params)


def ef_init_abstract(abstract_params, npods: int):
    return jax.tree.map(
        lambda p: jax.ShapeDtypeStruct((npods, *p.shape), jnp.float32),
        abstract_params)


def ef_specs(param_specs):
    return jax.tree.map(lambda s: P("pod", *s), param_specs,
                        is_leaf=lambda s: isinstance(s, P))


# ----------------------------------------------------------- train step ----
def make_train_step(cfg: ArchConfig, ctx: MeshContext, opt: Optional[AdamW] = None,
                    grad_compress: bool = False):
    """Returns ``step(params, opt_state, batch) -> (params, opt_state, metrics)``
    (plain) or ``step(params, opt_state, ef, batch) -> (..., ef, metrics)``
    (compressed; requires a "pod" mesh axis)."""
    opt = opt or AdamW()
    mesh = ctx.mesh

    try:
        specs = param_specs(cfg)
    except Exception:        # non-ArchConfig cfgs in unit tests
        specs = None

    if not (grad_compress and "pod" in mesh.axis_names):
        def train_step(params, opt_state: AdamWState, batch: dict):
            with mesh_context(ctx):
                batch = jax.tree.map(
                    lambda x: shard(x, dp_spec(*([None] * (x.ndim - 1)))), batch)
                loss, grads = accumulate_grads(params, batch, cfg)
                new_params, new_state, gnorm = opt.update(grads, opt_state, params)
                metrics = {"loss": loss.astype(jnp.float32),
                           "grad_norm": gnorm.astype(jnp.float32),
                           "step": new_state.step}
                return new_params, new_state, metrics
        return train_step

    npods = mesh.shape["pod"]
    inner_ctx = dataclasses.replace(ctx, dp=("data",))

    def per_pod(params, ef, batch):
        # manual over "pod": batch and ef arrive pod-local; data/model auto.
        ef = jax.tree.map(lambda e: e[0], ef)         # drop leading pod dim
        with mesh_context(None):                      # constraints off inside
            loss, grads = accumulate_grads(params, batch, cfg)

        flat_g, tdef = jax.tree.flatten(grads)
        flat_e = tdef.flatten_up_to(ef)
        out_g, out_e = [], []
        for g, e in zip(flat_g, flat_e):
            target = g.astype(jnp.float32) + e
            q, scale = quantize_int8(target)
            s = jax.lax.psum(q.astype(jnp.int32), "pod")          # int8 wire
            sc = jax.lax.pmax(scale, "pod")                       # shared scale
            deq = s.astype(jnp.float32) * sc / npods              # pod mean
            out_g.append(deq.astype(g.dtype))
            out_e.append(target - q.astype(jnp.float32) * scale)  # residual
        grads_hat = tdef.unflatten(out_g)
        new_ef = tdef.unflatten([e[None] for e in out_e])
        loss_avg = jax.lax.pmean(loss, "pod")
        return loss_avg, grads_hat, new_ef

    mapped = jax.shard_map(
        per_pod, mesh=mesh,
        in_specs=(P(), P("pod"), P("pod")),
        out_specs=(P(), P(), P("pod")),
        axis_names={"pod"}, check_vma=False)

    def train_step_c(params, opt_state: AdamWState, ef, batch: dict):
        with mesh_context(ctx):
            batch = jax.tree.map(
                lambda x: shard(x, dp_spec(*([None] * (x.ndim - 1)))), batch)
            loss, grads, new_ef = mapped(params, ef, batch)
            new_params, new_state, gnorm = opt.update(grads, opt_state, params)
            metrics = {"loss": loss.astype(jnp.float32),
                       "grad_norm": gnorm.astype(jnp.float32),
                       "step": new_state.step}
            return new_params, new_state, new_ef, metrics

    return train_step_c


def make_eval_step(cfg: ArchConfig, ctx: MeshContext):
    def eval_step(params, batch: dict):
        with mesh_context(ctx):
            return loss_fn(params, batch, cfg)
    return eval_step
