"""The one mesh constructor.

Every mesh in the repo is built here, with Auto axes: GSPMD propagates
shardings and `with_sharding_constraint` takes bare `PartitionSpec`s
(`distributed.autoshard.constrain`), which an Explicit-axis mesh refuses.

Defined as FUNCTIONS so importing this module never touches jax device
state; `dryrun.py` sets `--xla_force_host_platform_device_count=512`
before any jax import, everything else sees the real device count.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.core.topology import MeshSpec


def make_mesh(shape, axes, *, devices=None):
    """Auto-axis `jax.sharding.Mesh` of `shape` named `axes`.

    `devices` defaults to `jax.devices()`; pass a described topology's
    devices to compile for a chip that is not attached.
    """
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh_spec(*, multi_pod: bool = False) -> MeshSpec:
    return MeshSpec.multi_pod() if multi_pod else MeshSpec.single_pod()


def make_host_mesh(shape=(2, 4), axes=("data", "model")):
    """Mesh plus its `MeshSpec` (tests, examples, `--mesh DxM`)."""
    return make_mesh(shape, axes), MeshSpec(tuple(shape), tuple(axes))
