"""Mesh construction (functions only — importing this module never
touches jax device state).

Every mesh in this repo has `Auto` axes: the models place work with
GSPMD sharding constraints (`models/common.wsc`) and `shard_map`, and
leave layouts in between to the partitioner.  `jax.make_mesh` defaults
to `Explicit` axes, under which un-annotated ops such as the embedding
gather raise a `ShardingTypeError`, so meshes are built here and the
entry points that take one check it with `require_auto_axes`.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """`jax.make_mesh` with every axis `Auto`."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def require_auto_axes(mesh) -> None:
    """Raise for a mesh with non-`Auto` axes (see the module docstring)."""
    bad = [n for n, t in zip(mesh.axis_names, mesh.axis_types)
           if t != AxisType.Auto]
    if bad:
        raise ValueError(
            f"mesh axes {bad} are not Auto ({mesh.axis_types}); build the "
            "mesh with repro.launch.mesh.make_mesh")


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    mp = min(model_parallel, n)
    while n % mp:
        mp -= 1
    return make_mesh((n // mp, mp), ("data", "model"))
