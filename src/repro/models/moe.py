"""Mixture-of-Experts with expert parallelism (OLMoE, DeepSeek-V2 geometry).

Dispatch is sort-based (capacity-bounded, drop-on-overflow) and runs inside a
``shard_map`` over the mesh so the expert exchange is an EXPLICIT
``jax.lax.all_to_all`` pair on the "model" axis — the communication pattern
the roofline analysis needs to see, not an XLA-inferred scatter.

Data layout per (pod, data) shard:
    tokens (T_loc, d) --route/sort--> buf (E, C, d)
      --all_to_all(model: split E, concat C)--> (E_loc, C*m, d)
      --expert FFN (E_loc local experts)--> (E_loc, C*m, d)
      --reverse all_to_all--> (E, C, d) --combine--> (T_loc, d)
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import Params, dense_abstract, dense_init, swiglu_abstract, swiglu_init


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    n_experts: int
    top_k: int
    d_ff_expert: int
    n_shared: int = 0          # shared (always-on) experts
    d_ff_shared: int = 0       # width of the fused shared expert
    capacity_factor: float = 1.25
    router_dtype: str = "float32"


def moe_init(key, cfg: MoEConfig) -> Params:
    ks = jax.random.split(key, 5)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff_expert
    p = {
        "router": dense_init(ks[0], d, e),
        "wi": jax.random.normal(ks[1], (e, d, f), jnp.float32) * d ** -0.5,
        "wg": jax.random.normal(ks[2], (e, d, f), jnp.float32) * d ** -0.5,
        "wo": jax.random.normal(ks[3], (e, f, d), jnp.float32) * f ** -0.5,
    }
    if cfg.n_shared:
        p["shared"] = swiglu_init(ks[4], d, cfg.d_ff_shared or cfg.d_ff_expert * cfg.n_shared)
    return p


def moe_abstract(cfg: MoEConfig) -> Params:
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff_expert
    p = {
        "router": dense_abstract(d, e),
        "wi": jax.ShapeDtypeStruct((e, d, f), jnp.float32),
        "wg": jax.ShapeDtypeStruct((e, d, f), jnp.float32),
        "wo": jax.ShapeDtypeStruct((e, f, d), jnp.float32),
    }
    if cfg.n_shared:
        p["shared"] = swiglu_abstract(d, cfg.d_ff_shared or cfg.d_ff_expert * cfg.n_shared)
    return p


def moe_param_specs(cfg: MoEConfig) -> Params:
    """PartitionSpecs: experts sharded over the model axis (EP)."""
    p = {
        "router": {"w": P(None, None)},
        "wi": P("model", None, None),
        "wg": P("model", None, None),
        "wo": P("model", None, None),
    }
    if cfg.n_shared:
        p["shared"] = {"wi": {"w": P(None, "model")},
                       "wg": {"w": P(None, "model")},
                       "wo": {"w": P("model", None)}}
    return p


def _capacity(t_loc: int, cfg: MoEConfig) -> int:
    c = math.ceil(t_loc * cfg.top_k / cfg.n_experts * cfg.capacity_factor)
    return max(8, -(-c // 8) * 8)   # round up to sublane multiple


def _dispatch_combine(x, router_w, wi, wg, wo, *, cfg: MoEConfig, model_axis: str):
    """Runs PER (pod,data)-SHARD inside shard_map.  x: (T_loc, d)."""
    t_loc, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    m = jax.lax.axis_size(model_axis)
    e_loc = e // m
    c = _capacity(t_loc, cfg)

    # --- route -------------------------------------------------------------
    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)                  # (T, k)
    top_w = top_w / jnp.clip(top_w.sum(-1, keepdims=True), 1e-9)

    # --- sort-based slotting --------------------------------------------------
    flat_e = top_e.reshape(-1)                              # (T*k,)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    sorted_t = (jnp.arange(t_loc * k) // k)[order]
    counts = jnp.bincount(flat_e, length=e)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(t_loc * k) - starts[sorted_e]
    keep = rank < c
    dest_e = jnp.where(keep, sorted_e, e)                   # e = drop row
    dest_c = jnp.clip(rank, 0, c - 1)

    buf = jnp.zeros((e + 1, c, d), x.dtype)
    buf = buf.at[dest_e, dest_c].set(x[sorted_t], mode="drop")
    buf = buf[:e]

    # --- expert exchange (EP all-to-all) -------------------------------------
    buf = jax.lax.all_to_all(buf, model_axis, split_axis=0, concat_axis=1,
                             tiled=True)                    # (E_loc, C*m, d)
    h = jnp.einsum("ecd,edf->ecf", buf, wg.astype(x.dtype))
    h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", buf, wi.astype(x.dtype))
    out = jnp.einsum("ecf,efd->ecd", h, wo.astype(x.dtype))
    out = jax.lax.all_to_all(out, model_axis, split_axis=1, concat_axis=0,
                             tiled=True)                    # (E, C, d)

    # --- combine ----------------------------------------------------------------
    y_sorted = out[dest_e.clip(0, e - 1), dest_c] * keep[:, None].astype(x.dtype)
    y_flat = jnp.zeros((t_loc * k, d), x.dtype).at[order].set(y_sorted)
    y = (y_flat.reshape(t_loc, k, d) * top_w[..., None].astype(x.dtype)).sum(axis=1)
    return y


def moe_ffn(p: Params, x: jax.Array, cfg: MoEConfig, mesh: jax.sharding.Mesh,
            dp_axes: tuple[str, ...] = ("data",), model_axis: str = "model"):
    """x: (B, S, d) batch sharded over dp_axes.  Routed + shared experts.

    Tokens are sharded over the EP ("model") axis too (§Perf iteration C1):
    each rank routes its own S/m sequence slice, so the all-to-all exchanges
    distinct tokens and the expert FFN does 1/m of the work.  The replicated
    variant (every rank dispatching identical tokens) costs m× redundant
    expert FLOPs and m× all-to-all bytes — measured 16× on olmoe train_4k.
    Decode (S=1, or S not divisible by m) falls back to replicated dispatch.
    """
    from .layers import swiglu
    b, s, d = x.shape
    m = mesh.shape[model_axis]
    token_parallel = s > 1 and s % m == 0
    seq_spec = "model" if token_parallel else None

    def per_shard(xs, rw, wi, wg, wo):
        t = xs.shape[0] * xs.shape[1]
        y = _dispatch_combine(xs.reshape(t, d), rw, wi, wg, wo,
                              cfg=cfg, model_axis=model_axis)
        return y.reshape(xs.shape)

    mapped = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(dp_axes, seq_spec, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=P(dp_axes, seq_spec, None),
        check_vma=False,
    )
    y = mapped(x, p["router"]["w"], p["wi"], p["wg"], p["wo"])
    if cfg.n_shared:
        y = y + swiglu(p["shared"], x)
    return y
