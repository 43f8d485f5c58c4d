"""Production meshes.

``make_production_mesh`` is a FUNCTION (never evaluated at import) so that
importing this module touches no jax device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import so that the 512-chip multi-pod mesh can be built from CPU devices.

Every mesh here has ``Auto`` axes: the model code places arrays through
``jax.jit`` shardings and ``with_sharding_constraint`` and leaves the rest
to the partitioner.  ``jax.make_mesh`` would otherwise make ``Explicit``
axes, under which an embedding gather needs an explicit ``out_sharding``.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...], devices):
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes), devices=devices
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes, jax.devices()[: math.prod(shape)])


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh over the first prod(shape) devices (tests, elastic)."""
    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) < n:
        raise ValueError(f"need {n} devices, have {len(devs)}")
    return _auto_mesh(shape, axes, devs[:n])


def best_mesh_for(n_devices: int, model_parallel: int = 1):
    """Elastic restart helper: the largest (data, model) grid that fits
    ``n_devices`` with the requested model-parallel degree."""
    model = model_parallel
    while model > 1 and (n_devices % model or n_devices // model < 1):
        model //= 2
    data = n_devices // model
    return make_mesh((data, model), ("data", "model"))
