"""Activation sharding constraints (mesh-context based).

Model code calls ``constrain(x, *logical_axes)``; outside an
``activation_mesh`` context this is a no-op, so CPU unit tests and
single-device runs are unaffected.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

__all__ = ["activation_mesh", "auto_axes", "constrain", "BATCH", "TP",
           "batch_axes", "pick_tp_dim"]

# logical activation axes used by model code (resolved against the live mesh)
BATCH = ("pod", "data")
TP = "model"

_ACT_MESH: Optional[Mesh] = None


def auto_axes(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis ``Auto``.  The model shards by GSPMD
    propagation steered by ``constrain``, not by sharding in types, while
    ``jax.make_mesh`` makes ``Explicit`` axes by default."""
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


class activation_mesh:
    """Context: model-internal ``constrain`` calls target this mesh.
    No-op (constraints vanish) when not entered — CPU unit tests unaffected."""

    def __init__(self, mesh: Optional[Mesh]):
        self.mesh = None if mesh is None else auto_axes(mesh)

    def __enter__(self):
        global _ACT_MESH
        self._old = _ACT_MESH
        _ACT_MESH = self.mesh
        return self.mesh

    def __exit__(self, *exc):
        global _ACT_MESH
        _ACT_MESH = self._old
        return False


def constrain(x, *spec):
    """with_sharding_constraint against the context mesh; silently drops axes
    that are absent from the mesh or do not divide the dimension."""
    mesh = _ACT_MESH
    if mesh is None or x is None:
        return x
    clean = []
    for dim, entry in zip(x.shape, spec):
        if entry is None:
            clean.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        axes = tuple(a for a in axes if a in mesh.axis_names)
        size = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
        if axes and dim % size == 0 and size > 1:
            clean.append(axes if len(axes) > 1 else axes[0])
        elif len(axes) == 1 or not axes:
            clean.append(None)
        else:
            # try prefixes (e.g. ('pod','data') -> 'pod' alone won't help batch
            # locality; just drop)
            clean.append(None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*clean)))


def batch_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


def _div(n: int, mesh: Mesh, axis: str) -> bool:
    return n % _axis_size(mesh, axis) == 0


def pick_tp_dim(mesh: Mesh, *dims: int) -> int:
    """Index (into dims) of the first dim divisible by the model axis, else -1."""
    for i, d in enumerate(dims):
        if d and _div(d, mesh, "model"):
            return i
    return -1


