"""Plain references for the benchmark's checks: numpy, float64, no program code.

Everything here is a straightforward implementation of what the program
documents, written from the edge list of the input graph alone:

* :func:`lanczos_ritz` — m steps of the Lanczos recurrence with two-pass full
  re-orthogonalisation on an operator, from a given start vector, and the
  extreme Ritz values of the resulting tridiagonal matrix;
* :func:`adjacency_op` / :func:`laplacian_op` — the operators the program's
  rho_2 solves use (the ones-deflated adjacency of a regular graph, and the
  Laplacian shifted by ``(max degree + 2) * ones ones^T / n``);
* :func:`bfs_paths_loads` — BFS distances, minimal-path counts and
  minimal-path (ECMP) link loads from a set of sources.

``rnd`` rounds every stored vector and every product to a lower precision
(the bfloat16 control); the default keeps float64 throughout.
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import numpy as np

Rounder = Optional[Callable[[np.ndarray], np.ndarray]]


def bf16(x: np.ndarray) -> np.ndarray:
    """x rounded to bfloat16 and held in float32 (the control's precision:
    bfloat16 operands, float32 accumulation, as the chip's matrix unit)."""
    import ml_dtypes

    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def _keep(x):
    return x


def neighbor_table(n: int, edges: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(table, deg): row v lists v's neighbours with multiplicity, padded
    with -1 to the largest degree."""
    edges = np.asarray(edges, dtype=np.int64)
    # slots in edge-scan order: u's slot before v's within each edge
    src = edges.reshape(-1)
    dst = edges[:, ::-1].reshape(-1)
    order = np.argsort(src, kind="stable")
    src, dst = src[order], dst[order]
    deg = np.bincount(src, minlength=n)
    width = max(int(deg.max()) if deg.size else 0, 1)
    start = np.concatenate([[0], np.cumsum(deg)[:-1]])
    table = np.full((n, width), -1, dtype=np.int64)
    table[src, np.arange(src.size) - start[src]] = dst
    return table, deg


def _gather_sum(table: np.ndarray, x: np.ndarray) -> np.ndarray:
    """(A x)[v] = sum of x over v's neighbours (padding -1 adds 0)."""
    xp = np.append(x, x.dtype.type(0))
    return xp[table].sum(axis=1)


def adjacency_op(table: np.ndarray, rnd: Rounder = None) -> Callable:
    """x -> P A P x with P the projection off the all-ones vector."""
    r = rnd or _keep

    def op(x):
        x = r(x - x.mean())
        y = r(_gather_sum(table, x))
        return r(y - y.mean())

    return op


def laplacian_op(table: np.ndarray, deg: np.ndarray,
                 rnd: Rounder = None) -> Callable:
    """x -> (D - A) x + c * mean(x) * ones, c = max degree + 2: the constant
    vector moves to eigenvalue c, every other eigenpair of L stays."""
    r = rnd or _keep
    c = float(deg.max()) + 2.0
    d = deg.astype(np.float64)

    def op(x):
        return r(d * x - r(_gather_sum(table, x)) + c * x.mean())

    return op


def lanczos_ritz(op: Callable, v0: np.ndarray, m: int,
                 rnd: Rounder = None) -> Tuple[float, float]:
    """(smallest, largest) Ritz value of m Lanczos steps of ``op`` from v0.

    Two passes of full re-orthogonalisation against every stored basis
    vector at each step; a step whose residual vanishes ends the recurrence
    (the Krylov space is exhausted) and the Ritz values are those of the
    steps taken.
    """
    r = rnd or _keep
    dtype = np.float32 if rnd is not None else np.float64
    n = v0.size
    V = np.zeros((m + 1, n), dtype=dtype)
    v = np.asarray(v0, dtype=dtype)
    v = r(v / np.linalg.norm(v))
    V[0] = v
    v_prev = np.zeros(n, dtype=dtype)
    beta_prev = 0.0
    alphas, betas = [], []
    for j in range(m):
        w = r(op(v) - beta_prev * v_prev)
        alpha = float(r(np.asarray(v @ w)))
        w = r(w - alpha * v)
        for _ in range(2):
            coeff = r(V[:j + 1] @ w)
            w = r(w - V[:j + 1].T @ coeff)
        beta = float(r(np.asarray(np.linalg.norm(w))))
        alphas.append(alpha)
        if beta <= 1e-10 * max(1.0, abs(alpha)):
            break
        betas.append(beta)
        v_prev, v = v, r(w / beta)
        V[j + 1] = v
        beta_prev = beta
    a = np.asarray(alphas)
    b = np.asarray(betas[:a.size - 1])
    T = np.diag(a) + np.diag(b, 1) + np.diag(b, -1)
    ev = np.linalg.eigvalsh(T)
    return float(ev[0]), float(ev[-1])


def bfs_paths_loads(table: np.ndarray, sources: np.ndarray,
                    demand: Callable[[int], np.ndarray],
                    rnd: Rounder = None):
    """Per source s: BFS distances, minimal-path counts sigma(s, .), and the
    minimal-path (ECMP) loads of s's demand row: each unit s -> t splits
    equally over the shortest s-t paths, so the directed slot u -> v one
    layer further out carries ``sigma(s, u) * g(v) / sigma(s, v)``, where
    g(v) is the demand routed through v.

    Returns (dist (S, n) int64 with -1 unreachable, sigma (S, n) float64,
    ratio (S, n) float64 = g / sigma, loads (n, width) summed over the
    sources).  ``demand(s)`` is the (n,) demand row of source s.
    """
    r = rnd or _keep
    n, width = table.shape
    pad = table < 0
    nbr = np.where(pad, np.arange(n)[:, None], table)
    S = sources.size
    dist = np.full((S, n), -1, dtype=np.int64)
    sigma = np.zeros((S, n), dtype=np.float64)
    ratio = np.zeros((S, n), dtype=np.float64)
    loads = np.zeros((n, width), dtype=np.float64)
    for i, s in enumerate(sources):
        d = dist[i]
        sg = sigma[i]
        d[s] = 0
        sg[s] = 1.0
        layers = [np.array([s])]
        while True:
            seen = np.zeros(n, dtype=bool)
            seen[nbr[layers[-1]].ravel()] = True
            new = np.flatnonzero(seen & (d < 0))
            if new.size == 0:
                break
            d[new] = len(layers)
            nb = nbr[new]
            prev = (d[nb] == len(layers) - 1) & ~pad[new]
            sg[new] = np.where(prev, sg[nb], 0.0).sum(axis=1)
            layers.append(new)
        w = np.where(d > 0, demand(int(s)), 0.0)
        g = r(w.copy())
        safe = np.where(sg > 0, sg, 1.0)
        for layer in layers[-2:0:-1]:          # far layers first
            nb = nbr[layer]
            succ = (d[nb] == d[layer][:, None] + 1) & ~pad[layer]
            inc = r(np.where(succ, g[nb] / safe[nb], 0.0).sum(axis=1))
            g[layer] = r(w[layer] + sg[layer] * inc)
        ratio[i] = np.where(d > 0, g / safe, 0.0)
        loads += r(slot_loads(nbr, pad, d, sg, ratio[i]))
    return dist, sigma, ratio, loads


def slot_loads(nbr: np.ndarray, pad: np.ndarray, d: np.ndarray,
               sg: np.ndarray, ratio: np.ndarray,
               rows: Optional[np.ndarray] = None) -> np.ndarray:
    """One source's load on the directed slots of ``rows`` (default all)."""
    if rows is None:
        rows = np.arange(nbr.shape[0])
    nb = nbr[rows]
    succ = (d[nb] == d[rows][:, None] + 1) & ~pad[rows] & \
        (d[rows][:, None] >= 0)
    return np.where(succ, sg[rows][:, None] * ratio[nb], 0.0)
