"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state.  Single pod: 16x16 = 256 chips (v5e pod),
axes (data, model).  Multi-pod: 2 pods = 512 chips, axes (pod, data, model)
— the "pod" axis is the slow DCI dimension; batch shards over (pod, data).

Every axis is `AxisType.Auto`: the compiler propagates shardings from the
annotated arrays, which is the behaviour the model code relies on.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """1-device mesh for CPU smoke paths (same axis names, all size 1)."""
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
