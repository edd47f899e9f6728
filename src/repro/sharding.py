"""Mesh context + sharding helpers shared by models, train, serve, launch.

Axis roles (DESIGN.md §5):
  pod    — data parallelism across pods (multi-pod mesh only)
  data   — data parallelism + FSDP parameter sharding within a pod
  model  — tensor / expert / sequence parallelism

Models never touch jax.sharding directly; they call ``shard(x, spec)`` with a
PartitionSpec, which resolves against the active MeshContext (no-op when no
mesh is set — e.g. single-device smoke tests).
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class MeshContext:
    mesh: Mesh
    dp: tuple[str, ...] = ("data",)     # batch axes ("pod","data") multi-pod
    tp: str = "model"

    @property
    def dp_size(self) -> int:
        return int(jax.numpy.prod(jax.numpy.asarray(
            [self.mesh.shape[a] for a in self.dp])))

    @property
    def tp_size(self) -> int:
        return self.mesh.shape[self.tp]


_state = threading.local()


def current_ctx() -> Optional[MeshContext]:
    return getattr(_state, "ctx", None)


@contextlib.contextmanager
def mesh_context(ctx: Optional[MeshContext]):
    prev = current_ctx()
    _state.ctx = ctx
    try:
        if ctx is not None:
            with ctx.mesh:
                yield ctx
        else:
            yield None
    finally:
        _state.ctx = prev


def make_ctx(mesh: Mesh) -> MeshContext:
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    return MeshContext(mesh=mesh, dp=dp or ("data",), tp="model")


def shard(x, spec: P):
    """with_sharding_constraint against the active mesh (no-op without one).

    Axis names in ``spec`` that the active mesh lacks (e.g. "pod" on the
    single-pod mesh) are dropped."""
    ctx = current_ctx()
    if ctx is None:
        return x
    names = set(ctx.mesh.axis_names)

    def fix(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in names)
            return kept if kept else None
        return entry if entry in names else None

    spec = P(*(fix(e) for e in spec))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, spec))


def dp_spec(*rest) -> P:
    """P over the batch dim using the active context's dp axes."""
    ctx = current_ctx()
    dp = ctx.dp if ctx else ("data",)
    return P(dp, *rest)


def residual_spec(x) -> P:
    """Sharding for the (B, S, D) residual stream between blocks.

    Megatron-style sequence parallelism (§Perf iteration A3): sharding the
    residual's SEQ dim over the TP axis lets SPMD lower the per-layer TP
    boundary as reduce-scatter + all-gather (2·B·S·D/m bytes) instead of a
    full all-reduce (2·B·S·D), and norms/residual adds run on 1/m of the
    rows.  Falls back to replicated-seq when S doesn't divide the TP axis
    (decode, odd shapes).
    """
    ctx = current_ctx()
    dp = ctx.dp if ctx else ("data",)
    s = x.shape[1] if x.ndim >= 3 else 0
    if ctx is not None and s > 1 and s % ctx.tp_size == 0:
        return P(dp, "model", None)
    return P(dp, None, None)


def logical_to_sharding(tree_specs, mesh: Mesh):
    """Map a pytree of PartitionSpecs to NamedShardings on ``mesh``, dropping
    axis names the mesh lacks."""
    names = set(mesh.axis_names)

    def fix_spec(spec: P) -> NamedSharding:
        def fix(entry):
            if entry is None:
                return None
            if isinstance(entry, (tuple, list)):
                kept = tuple(a for a in entry if a in names)
                return kept if kept else None
            return entry if entry in names else None
        return NamedSharding(mesh, P(*(fix(e) for e in spec)))

    return jax.tree.map(fix_spec, tree_specs,
                        is_leaf=lambda s: isinstance(s, P))
