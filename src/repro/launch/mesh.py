"""Production mesh definitions.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets its 512-placeholder-device
XLA flag before any jax initialization, and tests/benches see 1 device.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 = one v5e pod-slice; 2x16x16 = two pods over DCN."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(
    shape: tuple[int, ...],
    axes: tuple[str, ...],
    devices: Optional[Sequence[jax.Device]] = None,
) -> jax.sharding.Mesh:
    """Mesh with classic (auto) axis semantics over ``devices`` (default:
    every device of the process)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)
