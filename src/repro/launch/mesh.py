"""Production meshes. A FUNCTION, not a module constant — importing this
module never touches jax device state (the dry-run sets
``--xla_force_host_platform_device_count`` before first jax init)."""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 = one 256-chip v5e pod; (2,16,16) = two pods over DCN.

    Axes: 'data' = data parallel (fast ICI), 'model' = tensor/expert/sequence
    parallel (fast ICI), 'pod' = the DCN-connected slow axis (data-parallel
    across pods; gradients cross it once per step via the hierarchical
    monoid reduction).
    """
    shape: Tuple[int, ...] = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...],
              devices: Optional[Sequence[jax.Device]] = None
              ) -> jax.sharding.Mesh:
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=devices)


def make_host_mesh(model: Optional[int] = None,
                   devices: Optional[Sequence[jax.Device]] = None
                   ) -> jax.sharding.Mesh:
    """(data, model) mesh over ``devices`` — every visible device when None
    (tests / single host); pass ``jax.devices()[:1]`` to pin one chip."""
    n = len(jax.devices() if devices is None else devices)
    model = model or 1
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"), devices)
