"""Production mesh construction.

``make_production_mesh`` is a function (not a module-level constant) so
importing this module never touches jax device state.

Every mesh here has ``Auto`` axes: the model code places arrays through
``with_sharding_constraint`` and leaves the rest to GSPMD propagation.
``jax.make_mesh`` defaults to ``Explicit`` axes, under which each op must
state its output sharding (the embedding gather raises
``ShardingTypeError`` there).
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: Tuple[int, ...],
               axes: Tuple[str, ...]) -> jax.sharding.Mesh:
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """Single pod: 256 chips as (data=16, model=16). Multi-pod: 2 pods,
    512 chips as (pod=2, data=16, model=16); the pod axis is an outer
    data-parallel axis (gradient reduction spans pod x data)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over however many (host) devices exist — used by the
    multi-device subprocess tests and the elastic-restore path."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return _auto_mesh((data, model), ("data", "model"))


def batch_axes_for(mesh: jax.sharding.Mesh) -> Tuple[str, ...]:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
