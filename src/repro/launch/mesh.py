"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state.  The dry-run launcher
sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; everything else sees the real single CPU device.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    """The assigned production mesh: 16x16 per pod, 2 pods when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Mesh with *Auto* axes: the sharding rules in ``distributed`` leave
    propagation to the compiler, which ``jax.make_mesh``'s default
    Explicit axes refuse (e.g. the vocab-sharded embedding gather)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """A mesh over whatever devices exist (CPU tests: usually 1x1)."""
    n = len(jax.devices())
    if data * model > n:
        data, model = n, 1
    devs = np.asarray(jax.devices()[: data * model]).reshape(data, model)
    return jax.sharding.Mesh(devs, ("data", "model"))


def dp_axes(mesh) -> Tuple[str, ...]:
    """The data-parallel axes of a mesh (pod+data when multi-pod)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def mesh_axis_size(mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1
