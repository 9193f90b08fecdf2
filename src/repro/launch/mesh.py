"""Production meshes. Importing this module never touches jax device
state — meshes are built only inside the factory functions.

Every mesh in the repo is built by :func:`auto_mesh`: `jax.make_mesh`
defaults to Explicit axes, while the models and the sync path shard
through `with_sharding_constraint` and need Auto axes.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def auto_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              devices=None):
    """`jax.make_mesh` with every axis Auto (optionally over `devices`)."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi-pod adds the 2-pod WAN axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_mesh(pods: int = 1, data: int = 16, model: int = 16):
    """General mesh factory (elastic scaling: any pod count)."""
    if pods > 1:
        return auto_mesh((pods, data, model), ("pod", "data", "model"))
    return auto_mesh((data, model), ("data", "model"))
