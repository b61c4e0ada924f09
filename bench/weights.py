"""Seeded weights, made on the device in one jitted call and in the dtype the
configuration serves, and where the benchmark keeps them.

The benchmark owns the draw: the engine under test and the plain reference
are both handed what :func:`make` returns for a seed, so the reference takes
no weights from the program.  The architecture's module
(``bench/arch/<name>.py``) gives the draw itself, what each leaf looks like
to the engine, and the axis of each leaf that a cell of several chips
splits.

A cell on one chip draws every leaf whole on its device.  A cell on several
draws each leaf already split over its chips along the architecture's
``SPLIT_AXIS`` (whole where that axis does not divide by the chip count),
so that no chip ever holds more than its share; with JAX's partitionable
threefry (on by default) the values are those of the one-device draw, bit
for bit.  The reference then takes one layer at a time whole onto every
chip (:meth:`Place.layer`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec


def prng_key(seed: int) -> jax.Array:
    """A JAX key from any non-negative seed, also one wider than 32 bits."""
    word = int(np.random.SeedSequence(int(seed)).generate_state(1)[0])
    return jax.random.PRNGKey(word & 0x7FFFFFFF)


class Place:
    """The devices of one cell: one (``mesh`` None; arrays stay where they
    are made) or several, as a one-axis mesh ``("chips",)``."""

    def __init__(self, devices=()):
        self.mesh = Mesh(np.asarray(devices), ("chips",)) if len(devices) > 1 else None
        if self.mesh is not None:
            self._whole = NamedSharding(self.mesh, PartitionSpec())
            self._take = jax.jit(
                lambda w, i: {k: jax.lax.dynamic_slice_in_dim(a, i, 1) for k, a in w.items()},
                out_shardings=self._whole)

    @property
    def chips(self) -> int:
        return 1 if self.mesh is None else self.mesh.size

    def split(self, shape: tuple[int, ...], axis: int) -> NamedSharding:
        """A leaf of ``shape`` split over the chips along ``axis``, or whole
        on each where that axis does not divide."""
        if shape[axis] % self.chips:
            return self._whole
        spec = [None] * len(shape)
        spec[axis] = "chips"
        return NamedSharding(self.mesh, PartitionSpec(*spec))

    def whole(self, a: jax.Array) -> jax.Array:
        """``a`` whole on every chip."""
        return a if self.mesh is None else jax.device_put(a, self._whole)

    def layer(self, stacked: dict, i: int) -> tuple[dict, int]:
        """Leaves stacked on a leading layer axis and the index of layer
        ``i`` among what is returned: on one chip the leaves as they are,
        on several layer ``i`` alone, whole on every chip."""
        if self.mesh is None:
            return stacked, i
        return self._take(stacked, jnp.int32(i)), 0


def make(arch, m, seed: int, embed_std: float, dtype: str = "bfloat16",
         place: Place | None = None) -> dict:
    """Every weight of the configuration, drawn from ``seed`` by
    ``arch.draw``, on ``place`` (one device where None)."""
    place = place or Place()
    key, std = prng_key(seed), float(embed_std)
    split = {}
    if place.mesh is not None:
        abstract = jax.eval_shape(lambda k: arch.draw(m, k, std, dtype), key)
        split["out_shardings"] = {k: place.split(a.shape, arch.SPLIT_AXIS[k])
                                  for k, a in abstract.items()}
    fn = jax.jit(arch.draw, static_argnums=(0, 2, 3), **split)
    return jax.block_until_ready(fn(m, key, std, dtype))
