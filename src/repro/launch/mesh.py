"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state):

* single-pod: ``(16, 16)`` over ``("data", "model")`` — 256 chips,
* multi-pod:  ``(2, 16, 16)`` over ``("pod", "data", "model")`` — 512 chips.

Axis roles (DESIGN.md §4): ``("pod","data")`` = DP; ``"data"`` also carries
FSDP parameter sharding and long-context sequence parallelism; ``"model"``
= TP/EP.  ``make_tiny_mesh`` builds the same role structure at toy sizes for
CPU tests.  The shape/axis-name vocabulary itself lives in the jax-free
:mod:`repro.launch.mesh_shapes`, shared with :mod:`repro.sim.topology`.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax

from .mesh_shapes import production_shape, tiny_shape

__all__ = ["make_production_mesh", "make_tiny_mesh", "mesh_axis_sizes", "dp_axes"]


def _mk(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    return jax.make_mesh(shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    return _mk(*production_shape(multi_pod=multi_pod))


def make_tiny_mesh(*, multi_pod: bool = False, data: int = 2, model: int = 2):
    return _mk(*tiny_shape(multi_pod=multi_pod, data=data, model=model))


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def dp_axes(mesh) -> tuple:
    """The data-parallel axes present on this mesh, outermost first."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
