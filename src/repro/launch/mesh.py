"""Production mesh construction.

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import;
everything else sees the real device count).
"""
from __future__ import annotations

import jax

from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the models place
    activations with ``with_sharding_constraint``, which the default
    Explicit axes refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever this host actually has (smoke tests, examples): 1 device ->
    (1, 1) so the same model code paths run unchanged."""
    n = len(jax.devices())
    model = 1
    data = n // model
    return make_mesh((data, model), ("data", "model"))
