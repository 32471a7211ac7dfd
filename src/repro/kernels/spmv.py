"""Universal padded gather-table spmv: one matvec, every engine, two backends.

Every hot path in the repo applies the same operator family through the
padded gather-table contract (``graphs.Topology.gather_operands``):

    (A x)[i] = sum_j  signs[i, j] * x[table[i, j]]  +  loops[i] * x[i]

with ``signs`` defaulting to all-ones (plain adjacency; the signed form is
the Bilu–Linial operator of the synthesis subsystem) and ``loops`` to zero.
``signs`` is any per-slot weight: the batched Laplacian Lanczos of a fault
sweep passes one (n, k) table shared by the whole batch, each vertex's
neighbours over every sample, with per-sample slot counts as ``signs``
(:func:`repro.core.spectral._shared_table`).  Vmapped over the batch with
the table unbatched, :func:`spmv_ref`'s gather then takes n·k rows of B
values, the batch on lanes, where B (n, k) tables took B·n·k scalars.
This module is the single dispatch point for that operator:

* :func:`spmv_ref`    — pure-jnp reference (XLA gather + sum), any backend;
* :func:`spmv_padded` — the Pallas kernel: x in VMEM as rows of 128 lanes,
  the transposed table (and optional per-slot signs) streamed in row
  blocks, each gather done as in-vreg lane gathers over every row of x
  (the only gather Mosaic compiles).  float32 accumulation only;
* :func:`spmv`        — backend dispatcher.

The default is ``ref`` on every platform.  On the CPU, Mosaic only
interprets.  On a TPU v5e the kernel compiles and wins one matvec — 6.4 ms
against 16.0 ms at (n, k) = (65536, 32) — but its cost grows as n^2 and
with the vmapped batch, while XLA's gather shares its index work across the
batch; the engines' batched solves dominate, and the ``xpander(65536,32)``
survey row took 71.3 s with the kernel and 26.3 s with ``ref`` (one v5e,
``chip_smoke.py`` phases, compilation included).  ``backend="pallas"``
runs the compiled kernel wherever Mosaic compiles and fails loudly
elsewhere; ``"pallas_interpret"`` runs it under the interpreter (tests).

Backend resolution order: explicit ``backend=`` argument >
:func:`use_backend` context override > ``REPRO_SPMV_BACKEND`` env var >
``"ref"``.  The engines thread the
resolved backend through their jitted solvers as a static argument, so a
:func:`use_backend` override retraces them (the context manager clears the
jit caches on entry and exit for exactly this reason).

Dispatch is observable through :mod:`repro.obs` counters (the call-counting
tests read these instead of monkey-patching): ``spmv/pallas_trace`` counts
Pallas-kernel *traces* (clear the jit caches first; a cache hit never
re-traces), ``spmv/dispatch/<backend>`` counts dispatcher decisions, and
``spmv/matvec/<backend>`` counts matvec closures per resolved backend.
"""
from __future__ import annotations

import contextlib
import functools
import os
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import obs

__all__ = [
    "BACKENDS", "spmv", "spmv_ref", "spmv_padded", "spmv_matvec",
    "default_backend", "resolve_backend", "use_backend", "pallas_supported",
    "kernel_backend",
]

#: "ref" = pure jnp gather+sum; "pallas" = compiled kernel (TPU/GPU);
#: "pallas_interpret" = the kernel under the Pallas interpreter (any backend,
#: slow — parity tests and CPU smoke only).
BACKENDS = ("ref", "pallas", "pallas_interpret")

_OVERRIDE: Optional[str] = None


def pallas_supported() -> bool:
    """True where Mosaic can *compile* the kernel (CPU only interprets)."""
    return jax.default_backend() != "cpu"


def kernel_backend() -> str:
    """The strongest kernel-exercising backend available here: compiled
    Pallas off-CPU, interpret mode on CPU (slow but faithful)."""
    return "pallas" if pallas_supported() else "pallas_interpret"


def _validate(backend: str) -> str:
    if backend not in BACKENDS:
        raise ValueError(f"unknown spmv backend {backend!r} "
                         f"(known: {BACKENDS})")
    return backend


def default_backend() -> str:
    """Ambient default: env ``REPRO_SPMV_BACKEND`` if set, else ``"ref"``
    on every platform (the module docstring says why)."""
    env = os.environ.get("REPRO_SPMV_BACKEND")
    if env:
        return _validate(env)
    return "ref"


def resolve_backend(backend: Optional[str] = None) -> str:
    """Explicit argument > :func:`use_backend` override > ambient default."""
    if backend is not None:
        return _validate(backend)
    if _OVERRIDE is not None:
        return _OVERRIDE
    return default_backend()


@contextlib.contextmanager
def use_backend(backend: str):
    """Force every default-resolved spmv onto ``backend`` inside the block.

    Clears the jit caches on entry AND exit: the engines bake the resolved
    backend into their traces as a static argument, so cached traces from
    another backend must not be replayed under this one.
    """
    global _OVERRIDE
    _validate(backend)
    prev = _OVERRIDE
    _OVERRIDE = backend
    jax.clear_caches()
    try:
        yield
    finally:
        _OVERRIDE = prev
        jax.clear_caches()


# --------------------------------------------------------------------------
# reference path
# --------------------------------------------------------------------------

def spmv_ref(x: jnp.ndarray, table: jnp.ndarray,
             loops: Optional[jnp.ndarray] = None,
             signs: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Pure-jnp reference: ``sum_j signs[i,j] * x[table[i,j]] + loops[i]*x[i]``."""
    g = x[table]
    if signs is not None:
        g = g * signs
    y = jnp.sum(g, axis=1)
    if loops is not None:
        y = y + loops * x
    return y


# --------------------------------------------------------------------------
# Pallas kernel: float32 accumulation, gathers as in-vreg lane gathers
# --------------------------------------------------------------------------

_LANES, _SUBLANES = 128, 8
_TILE = _LANES * _SUBLANES           # rows per float32 (8, 128) vreg


def _lane_gather(x_ref, idx, nchunks: int):
    """``x[idx]`` for an (R, 128) block of indices, in Mosaic's only gather.

    Mosaic gathers within one vreg along one axis (``take_along_axis`` over
    at most 128 lanes).  So ``x`` sits in VMEM as (C, 8, 128) vregs, and the
    block scans all ``8 * nchunks`` rows of 128: each row is broadcast over
    the block, lane-gathered by ``idx % 128`` and kept where ``idx // 128``
    names that row.  The gather is exact (selects only); its cost grows as
    n/128 per output row, not as the k gathers of an ideal spmv.
    """
    hi = jnp.right_shift(idx, 7)
    lo = jnp.bitwise_and(idx, _LANES - 1)

    def body(c, acc):
        xc = x_ref[c]                                # (8, 128) one vreg
        for s in range(_SUBLANES):
            row = jnp.broadcast_to(xc[s:s + 1, :], idx.shape)
            g = jnp.take_along_axis(row, lo, axis=1)
            acc = jnp.where(hi == c * _SUBLANES + s, g, acc)
        return acc

    return jax.lax.fori_loop(0, nchunks, body,
                             jnp.zeros(idx.shape, jnp.float32))


def _spmv_kernel(x_ref, xb_ref, tab_ref, *rest, nchunks: int, signed: bool):
    if signed:
        sg_ref, loops_ref, o_ref = rest
    else:
        (loops_ref, o_ref), sg_ref = rest, None
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    for j in range(tab_ref.shape[0]):                # k unrolled slots
        g = _lane_gather(x_ref, tab_ref[j], nchunks)
        acc = acc + (g if sg_ref is None else sg_ref[j] * g)
    o_ref[...] = acc + loops_ref[...] * xb_ref[...]


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def spmv_padded(x: jnp.ndarray, table: jnp.ndarray,
                loops: Optional[jnp.ndarray] = None,
                signs: Optional[jnp.ndarray] = None, *,
                block_rows: int = 8192,
                interpret: bool = True) -> jnp.ndarray:
    """The Pallas padded gather-table spmv.

    ``x``: (n,) float32 or bfloat16; ``table``: (n, k) int32 self-padded
    neighbor table; ``loops``: optional (n,) self-loop weights (padding
    compensation); ``signs``: optional (n, k) per-slot ±1 signs (signed
    adjacency).  Accumulates in float32 and returns ``x.dtype``; float64
    operands raise ``TypeError`` (Mosaic has no float64 — the exact-count
    DPs use :func:`spmv_ref`).  Rows are laid out 128 to a lane row and
    streamed in blocks of ``block_rows``, rounded up to whole (8, 128)
    tiles; the table and signs go in transposed, (k, rows/128, 128), so
    that each slot is one lane-dense block.  Ragged ``n`` is zero-padded and
    sliced off the output.
    """
    for name, a in (("x", x), ("loops", loops), ("signs", signs)):
        if a is not None and a.dtype == jnp.float64:
            raise TypeError(f"spmv kernel: {name} is float64, but the kernel "
                            "accumulates in float32 only (Mosaic has no "
                            "float64); use backend='ref'")
    obs.count("spmv/pallas_trace")               # trace-time: counts kernel traces
    n, k = table.shape
    block_rows = -(-min(block_rows, n) // _TILE) * _TILE
    nb = -(-n // block_rows)
    rows = nb * block_rows
    rb = block_rows // _LANES

    def lanes(v, dtype):                         # (n,) -> (rows/128, 128)
        return jnp.pad(v.astype(dtype), (0, rows - n)).reshape(-1, _LANES)

    def slots(t, dtype):                         # (n, k) -> (k, rows/128, 128)
        t = jnp.pad(t.astype(dtype), ((0, rows - n), (0, 0)))
        return t.T.reshape(k, -1, _LANES)

    xl = lanes(x, jnp.float32)
    ops = [xl.reshape(-1, _SUBLANES, _LANES), xl, slots(table, jnp.int32)]
    slot_spec = pl.BlockSpec((k, rb, _LANES), lambda i: (0, i, 0))
    row_spec = pl.BlockSpec((rb, _LANES), lambda i: (i, 0))
    in_specs = [pl.BlockSpec((rows // _TILE, _SUBLANES, _LANES),
                             lambda i: (0, 0, 0)), row_spec, slot_spec]
    if signs is not None:
        ops.append(slots(signs, jnp.float32))
        in_specs.append(slot_spec)
    ops.append(lanes(jnp.zeros((n,), jnp.float32) if loops is None else loops,
                     jnp.float32))
    in_specs.append(row_spec)
    kernel = functools.partial(_spmv_kernel, nchunks=-(-n // _TILE),
                               signed=signs is not None)
    out = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=in_specs,
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((rows // _LANES, _LANES), jnp.float32),
        interpret=interpret,
        name="spmv_lane_gather",
    )(*ops)
    return out.reshape(-1)[:n].astype(x.dtype)


# --------------------------------------------------------------------------
# dispatch
# --------------------------------------------------------------------------

def spmv(x: jnp.ndarray, table: jnp.ndarray,
         loops: Optional[jnp.ndarray] = None,
         signs: Optional[jnp.ndarray] = None, *,
         backend: Optional[str] = None) -> jnp.ndarray:
    """Apply the padded gather-table operator through the resolved backend."""
    b = resolve_backend(backend)
    obs.count("spmv/dispatch/" + b)
    if b == "ref":
        return spmv_ref(x, table, loops, signs)
    return spmv_padded(x, table, loops, signs,
                       interpret=(b == "pallas_interpret"))


def spmv_matvec(table, loops=None, *, backend: Optional[str] = None
                ) -> Callable[[jnp.ndarray], jnp.ndarray]:
    """Adjacency-operator closure over one (n, k) table — the drop-in matvec
    for :func:`repro.core.spectral.lanczos_tridiag` and friends.  The backend
    is resolved once, at closure creation."""
    b = resolve_backend(backend)
    obs.count("spmv/matvec/" + b)
    tab = jnp.asarray(table, dtype=jnp.int32)
    lw = None if loops is None else jnp.asarray(loops, dtype=jnp.float32)

    def mv(x: jnp.ndarray) -> jnp.ndarray:
        return spmv(x, tab, lw, backend=b)

    return mv
