"""Production mesh builders.

Defined as functions (never module-level constants) so importing this
module never touches jax device state. Every axis is `Auto`: the model
code places tensors with `with_sharding_constraint`
(`dist/sharding.shard`), which accepts only `Auto` axes, while
`jax.make_mesh` defaults to `Explicit` ones.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(data: int = 2, tp: int = 2, pod: int = 1, devices=None):
    """Small mesh for subprocess integration tests (8 host devices);
    `devices` picks a subset, as a shrunken cluster would."""
    if pod > 1:
        return _mesh((pod, data, tp), ("pod", "data", "model"), devices)
    return _mesh((data, tp), ("data", "model"), devices)
