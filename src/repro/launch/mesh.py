"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  Single-pod: 16x16 = 256 chips (data x model).
Multi-pod: 2x16x16 = 512 chips (pod x data x model); the `pod` axis carries
pure data parallelism across the cross-pod (DCN-class) links.

Every axis is ``AxisType.Auto``: the models place their shardings with
``NamedSharding`` constraints and let the partitioner propagate the rest,
which ``jax.make_mesh``'s default of ``Explicit`` axes refuses.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """The census mesh, built from host placeholder devices
    (``jax.devices("cpu")``): the production chips are simulated, so the
    census builds the same way on a machine that also holds a chip."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape),
                         devices=jax.devices("cpu"))


def make_mesh(shape, axes):
    """Arbitrary mesh for tests / elastic restarts / DSE sweeps."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(tuple(shape)))
