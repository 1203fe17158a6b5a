"""Production mesh construction.

Defined as functions (not module constants) so importing never touches
jax device state — the dry-run must set XLA_FLAGS before first jax init.
Every axis is ``AxisType.Auto``: the sharding rules place arrays, and XLA
propagates the rest."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = 256 chips ("data", "model").
    Multi-pod: (2, 16, 16) = 512 chips ("pod", "data", "model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1):
    """Whatever this host actually has (tests/examples): (n//model, model)."""
    n = len(jax.devices())
    model = max(1, min(model, n))
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
