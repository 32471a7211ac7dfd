"""Production meshes.  A function (not a module constant) so importing never
touches jax device state."""
from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

__all__ = ["make_production_mesh", "make_local_mesh", "batch_mesh",
           "shard_batch", "over_batch"]


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 v5e pod (256 chips), or 2 pods = 512 chips with a 'pod' axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU runs)."""
    return jax.make_mesh((data, model), ("data", "model"))


def batch_mesh(B: int, devices: Optional[Sequence] = None) -> Optional[Mesh]:
    """1-D ``('data',)`` mesh that splits ``B`` independent per-sample
    solves over ``devices`` (default: every local device).

    None when there is one device or ``B`` does not divide evenly (the tail
    tile of a chunked solve): sharding must never change results, only
    placement.
    """
    devs = list(jax.local_devices() if devices is None else devices)
    if len(devs) > 1 and B % len(devs) == 0:
        return Mesh(np.array(devs), ("data",), axis_types=(AxisType.Auto,))
    return None


def shard_batch(mesh: Optional[Mesh], *arrays):
    """Place the leading (batch) axis of each array across ``mesh``.

    The batched Lanczos solvers call this on every (B, ...) operand tile, so
    a multi-device host holds B / devices samples on each device.  Identity
    when ``mesh`` is None.  Returns the arrays in order (a single array
    unwrapped).
    """
    if mesh is not None:
        spec = NamedSharding(mesh, P("data"))
        arrays = tuple(jax.device_put(a, spec) for a in arrays)
    return arrays if len(arrays) > 1 else arrays[0]


def over_batch(fn: Callable, mesh: Optional[Mesh], shared: int = 0
               ) -> Callable:
    """``fn`` applied to each device's slice of the batch (``shard_map``).

    The first ``shared`` arguments are replicated and the rest split on
    their leading axis; outputs are concatenated along it.  Each sample's
    solve is independent, so no data crosses devices.  The split is spelled
    out because jit cannot partition the Pallas spmv inside ``fn`` ("Mosaic
    kernels cannot be automatically partitioned").  ``fn`` itself when
    ``mesh`` is None.
    """
    if mesh is None:
        return fn

    def call(*args):
        specs = (P(),) * shared + (P("data"),) * (len(args) - shared)
        return jax.shard_map(fn, mesh=mesh, in_specs=specs,
                             out_specs=P("data"), check_vma=False)(*args)

    return call
