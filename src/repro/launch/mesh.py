"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before first init.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """A mesh whose axes are all ``Auto``: GSPMD propagates shardings and
    ``shard_hint``'s ``with_sharding_constraint`` may name any axis
    (``jax.make_mesh`` defaults to ``Explicit`` axes, which refuse it)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2x16x16 = 512 chips (pod, data, model) — the pod axis is the
    DCN/ICI-superpod data-parallel dimension."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Small mesh over whatever devices exist (tests / local runs)."""
    n = len(jax.devices())
    model_axis = min(model_axis, n)
    return _auto_mesh((n // model_axis, model_axis), ("data", "model"))