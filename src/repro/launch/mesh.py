"""Meshes.

``make_mesh`` lays out the attached chips (one chip or a 4-chip host) as
axes ``("data", "model")``; by default every chip is on ``data``, one
federated client per chip.  ``make_production_mesh`` describes the pods
(axes ``("data", "model")`` or ``("pod", "data", "model")``) for compile-only
rehearsals.

``data`` (x ``pod``) carries the federated clients: one data-parallel group
per client cohort.  ``model`` is tensor parallelism inside a client replica.
Defined as functions so importing this module never touches jax device state.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh"]


def _mesh(shape, axes, devices=None):
    # Auto axis types: GSPMD propagates the "model" axis; shard_map takes the
    # client axes manual.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_mesh(data: int | None = None, model: int = 1):
    """``(data, model)`` mesh over ``jax.devices()``.

    ``data=None`` puts every device not used by ``model`` on ``data``.  An
    explicit ``data * model`` takes the first that many devices.
    """
    devices = jax.devices()
    if data is None:
        data = len(devices) // model
    if data < 1 or data * model > len(devices):
        raise ValueError(f"mesh data={data} x model={model} does not fit the "
                         f"{len(devices)} attached devices")
    return _mesh((data, model), ("data", "model"), devices[:data * model])


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)
