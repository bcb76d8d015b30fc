"""Mesh construction (functions only — importing this module never touches
jax device state; the dry-run driver sets the host-device count before any
jax initialization).

Every mesh the program builds goes through :func:`make_mesh`, which gives
each axis ``AxisType.Auto``: the model code pins tensors with GSPMD
sharding constraints (:func:`repro.dist.constrain.constrain`) and places
parameters with ``NamedSharding``s, which is the ``Auto`` contract.
``jax.make_mesh``'s default of ``Explicit`` axes would turn every such
constraint into an error and make gathers on sharded operands refuse to
trace.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

__all__ = ["make_mesh", "make_production_mesh", "make_local_mesh",
           "dp_axes"]


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> Mesh:
    """A mesh of ``shape`` over ``axes``, every axis ``Auto``.

    ``devices`` (default: all of them) pins the mesh to a given device
    list, laid out in order; without it ``jax.make_mesh`` picks a
    topology-aware order.
    """
    types = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(tuple(shape), tuple(axes), axis_types=types)
    devs = np.asarray(list(devices), dtype=object).reshape(tuple(shape))
    return Mesh(devs, tuple(axes), axis_types=types)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16×16 = 256 chips per pod; multi-pod stacks 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model: int = 1, devices: Optional[Sequence] = None
                    ) -> Mesh:
    """(data, model) mesh over ``devices`` (default: every device)."""
    devs = list(jax.devices()) if devices is None else list(devices)
    n = len(devs)
    assert n % model == 0, (n, model)
    return make_mesh((n // model, model), ("data", "model"),
                     devices=devs if devices is not None else None)


def dp_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
