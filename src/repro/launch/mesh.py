"""Production mesh construction.

Kept as functions (never module-level constants) so importing this module
never touches jax device state — required because the dry-run must set
XLA_FLAGS before any device initialization.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; multi_pod adds a 2-pod leading axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Whatever this host actually has (smoke tests / examples)."""
    n = len(jax.devices())
    return jax.make_mesh((n,), ("data",), axis_types=(AxisType.Auto,))
