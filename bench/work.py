"""Bytes and operations that an m-step Lanczos solve needs, from its shape.

The count is the algorithm's, not any implementation's: B independent
graphs of order n, each applied through a k-slot gather table (plus k slot
signs when ``signed``), m steps of the three-term recurrence with two-pass
full re-orthogonalisation against the growing basis, everything in float32.
An implementation that skips basis rows, fuses passes or replaces the gather
with a kernel is held to the same yardstick, so its roofline share can only
rise by doing the same work faster.

Per graph and step j (0-based):

* re-orthogonalisation: two passes, each a product with the j + 1 stored
  basis rows and a product with their transpose, so the basis is read four
  times: ``4 * 4 * n * (j + 1)`` bytes and ``4 * 2 * n * (j + 1)`` operations;
* the operator: the table (``4 * n * k`` bytes), the signs where signed
  (``4 * n * k``), and ``OPERATOR_VECTORS`` n-vectors (input, output, the
  per-vertex weight); ``n * k`` additions, and ``n * k`` multiplications
  where signed;
* the recurrence: ``RECURRENCE_VECTORS`` n-vector reads and writes (the
  previous vector, the update, alpha's and beta's reductions, the
  normalisation and the store into the basis), ``RECURRENCE_OPS * n``
  operations.
"""
from __future__ import annotations

#: n-vectors read or written by one operator application besides the table
OPERATOR_VECTORS = 3
#: n-vectors read or written by one step's recurrence outside the basis
RECURRENCE_VECTORS = 6
#: floating-point operations per vertex in one step's recurrence
RECURRENCE_OPS = 10
#: bytes per float32 or int32 element
WORD = 4


def lanczos_work(B: int, n: int, k: int, m: int, signed: bool) -> dict:
    """``{"bytes": ..., "ops": ...}`` of B m-step solves of order n, width k."""
    basis_rows = m * (m + 1) // 2          # sum over steps of (j + 1)
    reorth_bytes = 4 * WORD * n * basis_rows
    reorth_ops = 4 * 2 * n * basis_rows
    table = WORD * n * k * (2 if signed else 1)
    step_bytes = table + WORD * n * (OPERATOR_VECTORS + RECURRENCE_VECTORS)
    step_ops = n * k * (2 if signed else 1) + RECURRENCE_OPS * n
    return dict(bytes=B * (reorth_bytes + m * step_bytes),
                ops=B * (reorth_ops + m * step_ops))


def least_seconds(work: dict, peak: dict) -> tuple:
    """(seconds, bound) the chip needs at least for ``work``: the larger of
    bytes over HBM bandwidth and operations over peak, and which one it is."""
    t_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    t_ops = work["ops"] / peak["flops_per_s"]
    return (t_bytes, "hbm") if t_bytes >= t_ops else (t_ops, "compute")
