"""Mesh construction: the one place a ``jax.sharding.Mesh`` is built.

Every mesh has ``AxisType.Auto`` axes: the model code places arrays with
``with_sharding_constraint`` and sharded indexing, which explicit axes
(``jax.make_mesh``'s default) refuse. ``make_production_mesh`` is a
function (not a module constant) so importing this module never touches
jax device state; the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], *,
              devices=None):
    """Mesh of ``shape`` named ``axes`` over the first ``prod(shape)`` of
    ``devices`` (default ``jax.devices()``), e.g. (1, 4) ('data', 'model')
    on four chips or on four fake CPU devices."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 chips (data, model). Multi-pod: 2 pods x 256 chips
    (pod, data, model); the 'pod' axis rides DCN, 'data'/'model' ride ICI."""
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


def mesh_axis_sizes(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
