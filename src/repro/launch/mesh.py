"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — required for the dry-run's
``XLA_FLAGS`` ordering and for smoke tests that must see 1 device.
"""

from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis Auto (sharding by propagation)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips across DCN."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int | None = None):
    """Small mesh over whatever devices the host actually has (tests,
    examples).  Uses (data, model) with model defaulting to 1."""
    n = len(jax.devices())
    model = model or 1
    assert n % model == 0, (n, model)
    return make_mesh((n // model, model), ("data", "model"))


def mesh_axis_names(mesh) -> tuple[str, ...]:
    return tuple(mesh.axis_names)


def data_axes(mesh) -> tuple[str, ...]:
    """Axes that shard the batch: ('pod','data') when pod exists."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
