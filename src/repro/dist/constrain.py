"""Mesh context + logical-axis sharding constraints.

Model code never names mesh axes directly: it pins tensors with the
*logical* labels ``"dp"`` (data parallel) and ``"tp"`` (tensor/model
parallel), which resolve against whatever mesh is ambiently active —
``("pod", "data")`` and ``"model"`` on a multi-pod mesh, ``("data",)``
and ``"model"`` on a single-pod mesh, and to nothing at all when no mesh
is active (single-host tests), in which case :func:`constrain` is the
identity.  This is the de-specialized version of hard-coding a layout:
the same forward function lowers correctly under every mesh shape.

Pallas kernels are the exception to "GSPMD partitions everything": a
Mosaic custom call cannot be partitioned automatically.
:func:`kernel_map` runs such a call inside ``jax.shard_map`` over the
active mesh, with per-operand logical specs, so each device runs the
kernel on its own shard.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["use_mesh", "current_mesh", "constrain", "splits",
           "kernel_map"]

_state = threading.local()

#: logical label -> candidate mesh axis names, in precedence order.
_LOGICAL_AXES = {
    "dp": ("pod", "data"),
    "tp": ("model",),
}


def current_mesh() -> Optional[jax.sharding.Mesh]:
    """The ambiently active mesh, or None (single-host / no context)."""
    stack = getattr(_state, "meshes", None)
    return stack[-1] if stack else None


@contextlib.contextmanager
def use_mesh(mesh: jax.sharding.Mesh):
    """Activate ``mesh`` for every :func:`constrain` call in scope."""
    stack = getattr(_state, "meshes", None)
    if stack is None:
        stack = _state.meshes = []
    stack.append(mesh)
    try:
        yield mesh
    finally:
        stack.pop()


def _resolve_axis(label, mesh):
    """Map a logical label to the mesh axes it spans (possibly a tuple)."""
    if label is None:
        return None
    if label in _LOGICAL_AXES:
        axes = tuple(a for a in _LOGICAL_AXES[label]
                     if a in mesh.axis_names)
        if not axes:
            return None
        return axes if len(axes) > 1 else axes[0]
    return label if label in mesh.axis_names else None


def constrain(t: jax.Array, *labels) -> jax.Array:
    """Pin ``t`` to the sharding described by per-axis logical ``labels``.

    ``labels`` align with ``t``'s leading axes (missing trailing labels =
    replicated).  Axes whose size does not divide the resolved mesh-axis
    size are silently dropped to replicated (the divisibility guard), so
    smoke-scale shapes never fail to lower.  Identity when no mesh is
    active.
    """
    mesh = current_mesh()
    if mesh is None:
        return t
    from .sharding import guard_spec
    resolved = [_resolve_axis(lb, mesh) for lb in labels[:t.ndim]]
    spec = guard_spec(P(*resolved), t.shape, mesh)
    if all(a is None for a in spec):
        return t
    return jax.lax.with_sharding_constraint(t, NamedSharding(mesh, spec))


def _label_size(label, mesh) -> int:
    axis = _resolve_axis(label, mesh)
    if axis is None:
        return 1
    axes = axis if isinstance(axis, tuple) else (axis,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def splits(label, dim: int) -> bool:
    """Whether a ``dim``-sized axis splits evenly over ``label``'s mesh
    axes (so a :func:`kernel_map` spec may name it).  False when no
    mesh is active or the label spans one device — nothing to split."""
    mesh = current_mesh()
    if mesh is None:
        return False
    n = _label_size(label, mesh)
    return n > 1 and dim % n == 0


def kernel_map(fn, *args, in_specs, out_specs):
    """``fn(*args)`` inside ``shard_map`` over the active mesh.

    ``in_specs`` (one per arg) and ``out_specs`` are ``PartitionSpec``s
    of *logical* labels (``"dp"``, ``"tp"``, None), resolved against the
    mesh like :func:`constrain`; the caller checks divisibility with
    :func:`splits` (a GQA kernel must split query and kv heads together,
    which no per-operand guard can know).  Mesh axes an operand is not
    split on see it replicated.  Without a mesh, or on a one-device
    mesh, ``fn`` runs as is.
    """
    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return fn(*args)

    def res(spec):
        return P(*(_resolve_axis(lb, mesh) for lb in spec))

    outs = (tuple(res(s) for s in out_specs)
            if isinstance(out_specs, (tuple, list)) else res(out_specs))
    return jax.shard_map(fn, mesh=mesh,
                         in_specs=tuple(res(s) for s in in_specs),
                         out_specs=outs, check_vma=False)(*args)
