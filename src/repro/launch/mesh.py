"""Production mesh builders.

Kept as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — required by the dry-run contract.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips).

    Axes: ``data`` (batch / gradient reduce-scatter), ``model`` (tensor /
    expert / sequence parallel), plus ``pod`` for the cross-pod axis — the
    hierarchy MLfabric's aggregation tree maps onto (DESIGN.md §3).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over the real local devices (smoke tests / examples)."""
    n = len(jax.devices())
    data = min(data, n)
    return jax.make_mesh((data, max(n // data, 1)), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))


def batch_axes(mesh) -> tuple:
    """Mesh axes the global batch is sharded over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
