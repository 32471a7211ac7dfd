"""Spectral solvers: dense oracles (numpy, float64) + device-scale Lanczos (JAX).

The dense path is the test oracle and handles n <= ~4096.  The Lanczos path is
the production solver: it never materializes the n x n matrix — the adjacency
operator of a regular (multi)graph is applied through the (n, k) neighbor
table, ``(A x)[i] = sum_j x[table[i, j]] + loops[i] * x[i]``, routed through
the universal spmv dispatcher (:mod:`repro.kernels.spmv`).

The batched solvers stream their (B, n, k) operand stacks through Lanczos in
memory-bounded batch tiles (:data:`DEFAULT_BATCH_TILE_BYTES`), so a fault
sweep or synthesis scoring pass at n ~ 10^5 never materializes B Lanczos
bases at once; on a multi-device host each tile is split over the devices
(:func:`repro.launch.mesh.shard_batch` places it,
:func:`repro.launch.mesh.over_batch` runs each device's slice).

Relations used throughout (k-regular G):  rho_2 = k * mu_2 = k - lambda_2.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels import spmv as KS
from repro.launch import mesh as _mesh

from .graphs import Topology

__all__ = [
    "adjacency_spectrum", "laplacian_spectrum", "normalized_laplacian_spectrum",
    "algebraic_connectivity", "spectral_gap", "lambda_nontrivial",
    "fiedler_vector", "canonical_fiedler", "table_matvec", "lanczos_tridiag",
    "lanczos_extremes", "lanczos_top_ritz", "rho2_lanczos",
    "rho2_lanczos_batched", "rho2_laplacian_batched", "signed_extremes_batched",
    "fiedler_lanczos", "DENSE_THRESHOLD", "DEFAULT_BATCH_TILE_BYTES",
]

#: graphs at or below this order use the dense float64 oracle; larger ones go
#: through the matrix-free JAX Lanczos path.  The Analysis/survey API reads
#: this as its default auto-selection cutover.
DENSE_THRESHOLD = 4096

#: memory budget per batched-Lanczos tile: the batch axis of a (B, n, k)
#: operand stack is chunked so one tile's working set (per-sample Lanczos
#: basis (m+1, n) f32 + gather operands) stays under this many bytes.
#: Tier-1 sizes (n <= 2184, B <= 48) always fit one tile, so chunking is
#: invisible there; at n = 65536 a 24-candidate signing batch streams in
#: a few tiles instead of 7 GB at once.
DEFAULT_BATCH_TILE_BYTES = 256 << 20


def _batch_tile(B: int, n: int, k: int, m: int,
                batch_chunk: Optional[int]) -> int:
    """Samples per batched-Lanczos tile (explicit override or byte budget)."""
    if batch_chunk is not None:
        return max(1, min(int(batch_chunk), B))
    per_sample = 4 * n * (m + 2 * k + 16)   # V basis + operands + workspace
    return max(1, min(B, DEFAULT_BATCH_TILE_BYTES // max(per_sample, 1)))


# --------------------------------------------------------------------------
# dense oracles (host, float64)
# --------------------------------------------------------------------------

def adjacency_spectrum(topo: Topology) -> np.ndarray:
    return np.linalg.eigvalsh(topo.adjacency())


def laplacian_spectrum(topo: Topology) -> np.ndarray:
    return np.linalg.eigvalsh(topo.laplacian())


def normalized_laplacian_spectrum(topo: Topology) -> np.ndarray:
    return np.linalg.eigvalsh(topo.normalized_laplacian())


def algebraic_connectivity(topo: Topology, method: str = "auto",
                           iters: int = 200, seed: int = 0) -> float:
    """rho_2: second-smallest Laplacian eigenvalue."""
    if method == "dense" or (method == "auto" and topo.n <= DENSE_THRESHOLD):
        return float(laplacian_spectrum(topo)[1])
    return rho2_lanczos(topo, iters=iters, seed=seed)


def spectral_gap(topo: Topology) -> float:
    """lambda_1 - lambda_2 of the adjacency matrix."""
    s = adjacency_spectrum(topo)
    return float(s[-1] - s[-2])


def lambda_nontrivial(topo: Topology) -> float:
    """lambda(G): largest |eigenvalue| != ±k (Definition 1)."""
    k = topo.radix
    s = adjacency_spectrum(topo)
    nontriv = s[np.abs(np.abs(s) - k) > 1e-6]
    return float(np.max(np.abs(nontriv)))


def fiedler_vector(topo: Topology) -> np.ndarray:
    """Eigenvector of L for rho_2 (dense path) — the bisection sweep witness."""
    w, v = np.linalg.eigh(topo.laplacian())
    return v[:, 1]


def _sign_canonical(vec: np.ndarray, tol: float = 1e-12) -> np.ndarray:
    """Flip ``vec`` so its first entry with |value| > tol is positive."""
    nz = np.flatnonzero(np.abs(vec) > tol)
    if nz.size and vec[nz[0]] < 0:
        return -vec
    return vec


def canonical_fiedler(topo: Topology, vector: Optional[np.ndarray] = None,
                      *, tol: float = 1e-6) -> np.ndarray:
    """A *deterministic* representative of the rho_2 Laplacian eigenspace.

    Symmetric families (butterfly, torus, hypercube, ...) have degenerate
    Fiedler eigenspaces, so ``eigh``'s second column is an arbitrary rotation
    within that eigenspace — it differs across BLAS builds and across
    dense-vs-Lanczos solver paths, which made the tie-sensitive adversarial
    traffic pattern drift between backends (butterfly ``thpt_adversarial``
    moved 0.3143 -> 0.3004 purely from an eigensolver path change).

    Dense path (``n <= DENSE_THRESHOLD``): recompute the full eigensystem,
    select every eigenvector with ``|w - rho_2| <= tol * max(1, |rho_2|)``
    (excluding the constant mode), and return the normalized projection of a
    fixed deterministic probe onto that eigenspace.  The projection is
    basis-invariant, so any eigensolver producing the same eigenspace yields
    the same vector — the input ``vector`` is ignored here by design.

    Above the dense threshold an exact eigenspace is unavailable; the provided
    Lanczos ``vector`` is returned sign-canonicalized (approximate invariance:
    deterministic up to the Lanczos solver's own reproducibility).
    """
    n = topo.n
    if n > DENSE_THRESHOLD:
        if vector is None:
            raise ValueError("canonical_fiedler above DENSE_THRESHOLD needs "
                             "an explicit (Lanczos) vector")
        vec = np.asarray(vector, dtype=np.float64)
        nrm = np.linalg.norm(vec)
        if nrm > 0:
            vec = vec / nrm
        return _sign_canonical(vec)
    w, v = np.linalg.eigh(topo.laplacian())
    rho2 = w[1]
    member = np.abs(w - rho2) <= tol * max(1.0, abs(rho2))
    member[0] = False                      # never the constant mode
    basis = v[:, member]                   # (n, m) orthonormal eigenspace
    idx = np.arange(n, dtype=np.float64)
    probes = [idx / n, np.cos(idx), idx * idx / (n * n)]
    for probe in probes:
        rep = basis @ (basis.T @ probe)
        nrm = np.linalg.norm(rep)
        if nrm > tol:
            return _sign_canonical(rep / nrm)
    return _sign_canonical(v[:, 1])        # probes all orthogonal: fall back


# --------------------------------------------------------------------------
# device-scale Lanczos (JAX)
# --------------------------------------------------------------------------

def table_matvec(table: np.ndarray, loops: Optional[np.ndarray] = None,
                 backend: Optional[str] = None
                 ) -> Callable[[jnp.ndarray], jnp.ndarray]:
    """Adjacency operator from an (n, k) neighbor table.

    Routed through the universal spmv dispatcher.  ``backend``
    (``"ref"`` / ``"pallas"`` / ``"pallas_interpret"``) is resolved once at
    closure creation; ``None`` follows :func:`repro.kernels.spmv.resolve_backend`.
    """
    return KS.spmv_matvec(table, loops, backend=backend)


def _lanczos_scan(op: Callable, v0: jnp.ndarray, m: int
                  ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """m-step Lanczos recurrence with full (two-pass) reorthogonalization.

    Traceable building block shared by the single-graph, batched (vmap), and
    Ritz-vector entry points.  Returns (alpha[m], beta[m], V[(m+1), n]).
    """
    # trace-time: one increment per XLA (re)trace of any Lanczos entry point
    # — the observable behind the survey's no-retrace regression gate
    obs.count("jit_trace/lanczos_scan")
    n = v0.shape[0]
    v = v0.astype(jnp.float32)
    v = v / jnp.linalg.norm(v)
    V0 = jnp.zeros((m + 1, n), dtype=jnp.float32).at[0].set(v)

    # f32 products at default precision run as bf16 passes on the TPU, which
    # the orthogonality of the basis (and so rho_2) cannot afford
    dot = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST)

    def body(carry, j):
        V, v, v_prev, beta_prev = carry
        w = op(v) - beta_prev * v_prev
        alpha = dot(w, v)
        w = w - alpha * v
        mask = (jnp.arange(m + 1) <= j).astype(jnp.float32)
        for _ in range(2):  # two-pass full reorthogonalization
            coeff = dot(V, w) * mask
            w = w - dot(V.T, coeff)
        beta = jnp.linalg.norm(w)
        ok = beta > 1e-7
        v_next = jnp.where(ok, w / jnp.where(ok, beta, 1.0), jnp.zeros_like(w))
        beta = jnp.where(ok, beta, 0.0)
        V = V.at[j + 1].set(v_next)
        return (V, v_next, v, beta), (alpha, beta)

    (V, _, _, _), (alphas, betas) = jax.lax.scan(
        body, (V0, v, jnp.zeros_like(v), jnp.float32(0.0)), jnp.arange(m))
    return alphas, betas, V


@functools.partial(jax.jit, static_argnames=("matvec", "m"))
def lanczos_tridiag(matvec: Callable, v0: jnp.ndarray, m: int,
                    deflate: Optional[jnp.ndarray] = None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """m-step Lanczos with full (two-pass) reorthogonalization.

    ``deflate``: optional (d, n) orthonormal rows projected out of the operator
    (P A P with P = I - D^T D), used to remove the trivial ±k eigenpairs.
    Returns (alpha[m], beta[m-1]) of the symmetric tridiagonal T.
    """
    alphas, betas, _ = _lanczos_with_basis(matvec, v0, m, deflate)
    return alphas, betas[:-1]


@functools.partial(jax.jit, static_argnames=("matvec", "m"))
def _lanczos_with_basis(matvec: Callable, v0: jnp.ndarray, m: int,
                        deflate: Optional[jnp.ndarray] = None
                        ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    def project(x):
        if deflate is not None:
            x = x - deflate.T @ (deflate @ x)
        return x

    def op(x):
        return project(matvec(project(x)))

    v = project(v0.astype(jnp.float32))
    return _lanczos_scan(op, v, m)


def _tridiag_eigvals(alphas: np.ndarray, betas: np.ndarray) -> np.ndarray:
    m = len(alphas)
    T = np.zeros((m, m))
    T[np.arange(m), np.arange(m)] = np.asarray(alphas, dtype=np.float64)
    T[np.arange(m - 1), np.arange(1, m)] = np.asarray(betas, dtype=np.float64)
    T[np.arange(1, m), np.arange(m - 1)] = np.asarray(betas, dtype=np.float64)
    return np.linalg.eigvalsh(T)


def lanczos_extremes(matvec: Callable, n: int, m: int = 200, seed: int = 0,
                     deflate_vectors: Optional[Sequence[np.ndarray]] = None
                     ) -> Tuple[float, float]:
    """(lambda_max, lambda_min) of the (deflated) operator."""
    obs.count("lanczos/solves")
    obs.count("lanczos/iters", m)
    key = jax.random.PRNGKey(seed)
    v0 = jax.random.normal(key, (n,), dtype=jnp.float32)
    deflate = None
    if deflate_vectors:
        D = np.stack([d / np.linalg.norm(d) for d in deflate_vectors])
        # orthonormalize (tiny d x d Gram-Schmidt)
        Q, _ = np.linalg.qr(D.T)
        deflate = jnp.asarray(Q.T, dtype=jnp.float32)
    alphas, betas = lanczos_tridiag(matvec, v0, m, deflate)
    ev = _tridiag_eigvals(np.asarray(alphas), np.asarray(betas))
    return float(ev[-1]), float(ev[0])


def lanczos_top_ritz(matvec: Callable, n: int, m: int = 200, seed: int = 0,
                     deflate_vectors: Optional[Sequence[np.ndarray]] = None
                     ) -> Tuple[float, np.ndarray]:
    """Top eigenpair (lambda_max, Ritz vector) of the (deflated) operator.

    The Ritz vector is V^T y for the top eigenvector y of the tridiagonal T —
    the matrix-free analogue of the dense ``fiedler_vector`` when the operator
    is the ones-deflated adjacency of a regular graph.
    """
    obs.count("lanczos/solves")
    obs.count("lanczos/iters", m)
    key = jax.random.PRNGKey(seed)
    v0 = jax.random.normal(key, (n,), dtype=jnp.float32)
    deflate = None
    if deflate_vectors:
        D = np.stack([d / np.linalg.norm(d) for d in deflate_vectors])
        Q, _ = np.linalg.qr(D.T)
        deflate = jnp.asarray(Q.T, dtype=jnp.float32)
    alphas, betas, V = _lanczos_with_basis(matvec, v0, m, deflate)
    alphas = np.asarray(alphas, dtype=np.float64)
    betas = np.asarray(betas, dtype=np.float64)[:-1]
    T = np.diag(alphas) + np.diag(betas, 1) + np.diag(betas, -1)
    w, y = np.linalg.eigh(T)
    ritz = np.asarray(V)[:m].T @ y[:, -1]
    nrm = np.linalg.norm(ritz)
    if nrm > 0:
        ritz = ritz / nrm
    return float(w[-1]), ritz


@obs.traced("spectral/rho2_lanczos", phase="execute")
def rho2_lanczos(topo: Topology, iters: int = 200, seed: int = 0,
                 matvec: Optional[Callable] = None) -> float:
    """rho_2 = k - lambda_2 for regular graphs, via ones-deflated Lanczos.

    For bipartite graphs the -k eigenpair is also deflated (sign vector from
    the 2-coloring) so the reported lambda_2 is the top *nontrivial* one.
    Note: assumes lambda_2 >= 0 (true for all surveyed topologies; dense path
    covers near-complete graphs where lambda_2 < 0).

    ``matvec``: optional replacement adjacency operator obeying the same
    padded gather-table contract (e.g. the ``cayley_spmv`` Pallas kernel via
    ``kernel_matvec``); defaults to the pure-jnp :func:`table_matvec`.
    """
    k = topo.radix
    if matvec is None:
        tab, w = topo.gather_operands()  # valid for any multigraph (loops folded)
        mv = table_matvec(tab, w)
    else:
        mv = matvec
    defl = [np.ones(topo.n)]
    if topo.meta.get("bipartite"):
        defl.append(_bipartite_sign(topo))
    lmax, _ = lanczos_extremes(mv, topo.n, m=iters, seed=seed,
                               deflate_vectors=defl)
    return float(k - lmax)


def _bipartite_sign(topo: Topology) -> np.ndarray:
    import networkx as nx

    color = nx.bipartite.color(topo.to_networkx())
    return np.array([1.0 if color[i] == 0 else -1.0 for i in range(topo.n)])


def trivial_deflation(topo: Topology) -> list:
    """Deflation basis removing the trivial adjacency eigenpairs: the all-ones
    (+k) vector, plus the 2-coloring sign vector (-k) for bipartite graphs.

    Bipartiteness is detected (O(m) 2-coloring) rather than read from meta —
    even-k tori, hypercubes, etc. are bipartite without declaring it.
    """
    defl = [np.ones(topo.n)]
    if topo.meta.get("bipartite") or _is_bipartite(topo):
        defl.append(_bipartite_sign(topo))
    return defl


def _is_bipartite(topo: Topology) -> bool:
    import networkx as nx

    return bool(nx.is_bipartite(topo.to_networkx()))


@obs.traced("spectral/fiedler_lanczos", phase="execute")
def fiedler_lanczos(topo: Topology, iters: int = 200, seed: int = 0) -> np.ndarray:
    """Approximate Fiedler vector, matrix-free (device-scale graphs).

    For k-regular G the Laplacian eigenvector of rho_2 equals the adjacency
    eigenvector of lambda_2, which is the top Ritz vector of the ones-deflated
    adjacency operator.  Used by the Analysis/survey layer to witness
    bisections when n is too large for the dense eigendecomposition.
    """
    tab, w = topo.gather_operands()
    mv = table_matvec(tab, w)
    _, ritz = lanczos_top_ritz(mv, topo.n, m=iters, seed=seed,
                               deflate_vectors=[np.ones(topo.n)])
    return ritz


@functools.partial(jax.jit, static_argnames=("m", "backend"))
def _lanczos_tridiag_batched(tables: jnp.ndarray, weights: jnp.ndarray,
                             v0s: jnp.ndarray, m: int,
                             backend: Optional[str] = None
                             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """vmapped ones-deflated Lanczos over B same-shape neighbor tables.

    ``tables``: (B, n, k) int32, ``weights``: (B, n) float32 per-vertex loop
    weights, ``v0s``: (B, n) float32 start vectors.  Returns stacked
    (alphas (B, m), betas (B, m)).  ``backend`` is static — the resolved
    spmv route is baked into the trace.
    """
    bk = KS.resolve_backend(backend)

    def run(tab, lw, v0):
        def op(x):
            x = x - jnp.mean(x)                      # project out ones
            y = KS.spmv(x, tab, lw, backend=bk)
            return y - jnp.mean(y)

        alphas, betas, _ = _lanczos_scan(op, v0 - jnp.mean(v0), m)
        return alphas, betas

    return jax.vmap(run)(tables, weights, v0s)


def _truncate_at_breakdown(alphas: np.ndarray, betas: np.ndarray
                           ) -> Tuple[np.ndarray, np.ndarray]:
    """Cut (alpha, beta) at the first Lanczos breakdown (beta zeroed by the
    scan).  Steps past a breakdown contribute spurious zero rows to T, which
    are harmless when reading the *largest* Ritz value but poison the
    *smallest* one (the quantity the Laplacian path reports)."""
    zero = np.nonzero(betas == 0.0)[0]
    if zero.size:
        obs.count("lanczos/breakdown_truncations")
        keep = int(zero[0]) + 1
        return alphas[:keep], betas[:max(keep - 1, 0)]
    return alphas, betas[:-1]


def _batched_ritz_extremes(alphas: jnp.ndarray, betas: jnp.ndarray
                           ) -> Tuple[np.ndarray, np.ndarray]:
    """(lambda_min, lambda_max) Ritz values per batch row, each row
    breakdown-truncated (:func:`_truncate_at_breakdown`) before the tridiag
    solve.  Shared readout for every batched-Lanczos path so breakdown
    handling cannot drift between them."""
    alphas = np.asarray(alphas, dtype=np.float64)
    betas = np.asarray(betas, dtype=np.float64)
    B = alphas.shape[0]
    lmin = np.empty(B, dtype=np.float64)
    lmax = np.empty(B, dtype=np.float64)
    for i in range(B):
        a_i, b_i = _truncate_at_breakdown(alphas[i], betas[i])
        ev = _tridiag_eigvals(a_i, b_i)
        lmin[i], lmax[i] = float(ev[0]), float(ev[-1])
    return lmin, lmax


@functools.partial(jax.jit, static_argnames=("m", "backend", "mesh"))
def _lap_lanczos_batched(tables: jnp.ndarray, weights: jnp.ndarray,
                         degs: jnp.ndarray, v0s: jnp.ndarray, m: int,
                         backend: Optional[str] = None, mesh=None
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """vmapped ones-deflated *Laplacian* Lanczos over B same-shape tables.

    The adjacency batch (:func:`_lanczos_tridiag_batched`) needs regular
    graphs; this one applies L = D - A through the padded gather form, so it
    is valid for the irregular graphs produced by fault injection.  ``degs``
    holds per-vertex degrees *including* signed self-loop weights, which makes
    ``deg * x - (gather + w * x)`` exactly L x (loops cancel).

    Deflation of the trivial 0 eigenpair (ones) is done by a rank-one SHIFT,
    not a projection: ``L + c * ones ones^T / n`` moves the ones eigenvalue to
    ``c = max_deg + 2 > rho2`` (Fiedler: rho2 <= vertex connectivity <=
    min degree, and rho2 = n = max_deg + 1 for K_n) and leaves every
    ones-orthogonal eigenpair untouched.  A projection would let float32
    roundoff reintroduce the ones component, whose ghost 0 Ritz value poisons
    the *smallest* eigenvalue — exactly the one this path reports.
    ``mesh`` (static) splits the batch over its devices
    (:func:`repro.launch.mesh.over_batch`).
    """
    bk = KS.resolve_backend(backend)

    def run(tab, lw, deg, v0):
        c = jnp.max(deg) + 2.0

        def op(x):
            lx = deg * x - KS.spmv(x, tab, lw, backend=bk)
            return lx + c * jnp.mean(x)

        alphas, betas, _ = _lanczos_scan(op, v0, m)
        return alphas, betas

    return _mesh.over_batch(jax.vmap(run), mesh)(tables, weights, degs, v0s)


def _tile_indices(lo: int, hi: int, tile: int) -> Tuple[np.ndarray, int]:
    """Index vector for one batch tile, padded to ``tile`` samples by
    repeating sample ``lo`` so every tile replays one compiled solve (the
    padded rows are recomputed garbage, sliced off by the caller)."""
    idx = np.arange(lo, hi, dtype=np.int64)
    if idx.size < tile:
        idx = np.concatenate([idx, np.full(tile - idx.size, lo, np.int64)])
    return idx, hi - lo


@obs.traced("spectral/rho2_laplacian_batched", phase="execute")
def rho2_laplacian_batched(tables: np.ndarray, weights: np.ndarray,
                           degs: np.ndarray, iters: int = 160,
                           seed: int = 0, *,
                           batch_chunk: Optional[int] = None,
                           backend: Optional[str] = None,
                           devices: Optional[Sequence] = None) -> np.ndarray:
    """rho_2 for B (possibly irregular) graphs in one *streamed* Lanczos solve.

    Operands are stacked padded gather forms — ``tables`` (B, n, k) int32,
    ``weights`` (B, n) per-vertex self weights (loop + padding compensation),
    ``degs`` (B, n) degrees including loop weights — exactly what
    :func:`repro.core.faults.stacked_operands` builds for a batch of fault
    samples.  Returns the second-smallest Laplacian eigenvalue per graph
    (~0 for disconnected samples: the extra kernel vector survives the ones
    deflation).  This is the fault-sweep engine: B degraded instances never
    cost B Python-level solves.

    The batch axis streams through the vmapped solve in memory-bounded tiles
    (``batch_chunk`` samples each; default from
    :data:`DEFAULT_BATCH_TILE_BYTES` — tier-1 sizes always fit one tile, so
    results are identical to the unchunked solve).  Each tile is split over
    ``devices`` (default: every local device) when its size divides evenly.
    ``backend`` picks the spmv route (default: the dispatcher's).
    """
    tables = np.asarray(tables)
    weights, degs = np.asarray(weights), np.asarray(degs)
    B, n, k = tables.shape
    obs.count("lanczos/solves", B)
    obs.count("lanczos/iters", B * iters)
    key = jax.random.PRNGKey(seed)
    v0s = np.asarray(jax.random.normal(key, (B, n), dtype=jnp.float32))
    tile = _batch_tile(B, n, k, iters, batch_chunk)
    bk = KS.resolve_backend(backend)
    mesh = _mesh.batch_mesh(tile, devices)
    alphas = np.empty((B, iters), dtype=np.float64)
    betas = np.empty((B, iters), dtype=np.float64)
    for lo in range(0, B, tile):
        idx, keep = _tile_indices(lo, min(lo + tile, B), tile)
        ops = _mesh.shard_batch(
            mesh, jnp.asarray(tables[idx], dtype=jnp.int32),
            jnp.asarray(weights[idx], dtype=jnp.float32),
            jnp.asarray(degs[idx], dtype=jnp.float32),
            jnp.asarray(v0s[idx]))
        a, b = _lap_lanczos_batched(*ops, iters, backend=bk, mesh=mesh)
        alphas[lo:lo + keep] = np.asarray(a, dtype=np.float64)[:keep]
        betas[lo:lo + keep] = np.asarray(b, dtype=np.float64)[:keep]
    lmin, _ = _batched_ritz_extremes(alphas, betas)
    return np.maximum(lmin, 0.0)


@functools.partial(jax.jit, static_argnames=("m", "backend", "mesh"))
def _signed_lanczos_batched(table: jnp.ndarray, slot_signs: jnp.ndarray,
                            v0s: jnp.ndarray, m: int,
                            backend: Optional[str] = None, mesh=None
                            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """vmapped Lanczos on B *signed* adjacency operators sharing one table.

    ``table``: (n, k) int32 neighbor table of the base graph, shared across
    the batch; ``slot_signs``: (B, n, k) float32 per-slot ±1 signs (the
    signing of edge e written into both of e's table slots); ``v0s``: (B, n)
    start vectors.  The operator is ``(A_s x)[i] = sum_j s[i,j] x[table[i,j]]``
    — the Bilu–Linial signed adjacency in the padded gather-table contract,
    applied through the spmv dispatcher's ``signs=`` form.
    No deflation: a signing destroys the trivial ±k eigenpairs.  ``mesh``
    (static) splits the batch over its devices, the table replicated.
    """
    bk = KS.resolve_backend(backend)

    def solve(tab, sgs, vs):
        def run(sg, v0):
            def op(x):
                return KS.spmv(x, tab, signs=sg, backend=bk)

            alphas, betas, _ = _lanczos_scan(op, v0, m)
            return alphas, betas

        return jax.vmap(run)(sgs, vs)

    return _mesh.over_batch(solve, mesh, shared=1)(table, slot_signs, v0s)


@obs.traced("spectral/signed_extremes_batched", phase="execute")
def signed_extremes_batched(table: np.ndarray, slot_signs: np.ndarray,
                            iters: int = 90, seed: int = 0, *,
                            batch_chunk: Optional[int] = None,
                            backend: Optional[str] = None
                            ) -> Tuple[np.ndarray, np.ndarray]:
    """(lambda_max, lambda_min) of B signed adjacencies in one streamed solve.

    This is the synthesis subsystem's objective oracle: by Bilu–Linial the
    eigenvalues of the signed adjacency A_s are exactly the NEW eigenvalues a
    2-lift introduces, so ``lambda_max`` bounds the lift's lambda_2 and
    ``max(|lambda_min|, lambda_max)`` is the signed spectral radius (the
    Ramanujan criterion).  Operands follow :func:`_signed_lanczos_batched`;
    returns float64 arrays (lmax (B,), lmin (B,)), breakdown-truncated so
    spurious zero Ritz rows never contaminate either end.

    Like :func:`rho2_laplacian_batched`, the batch axis streams through the
    vmapped solve in memory-bounded tiles (``batch_chunk`` /
    :data:`DEFAULT_BATCH_TILE_BYTES`); tier-1 sizes fit one tile and are
    bit-identical to the unchunked solve.
    """
    slot_signs = np.asarray(slot_signs)
    B, n, k = slot_signs.shape
    obs.count("lanczos/solves", B)
    obs.count("lanczos/iters", B * iters)
    v0s = np.asarray(jax.random.normal(jax.random.PRNGKey(seed), (B, n),
                                       dtype=jnp.float32))
    tab = jnp.asarray(table, dtype=jnp.int32)
    tile = _batch_tile(B, n, k, iters, batch_chunk)
    bk = KS.resolve_backend(backend)
    mesh = _mesh.batch_mesh(tile)
    alphas = np.empty((B, iters), dtype=np.float64)
    betas = np.empty((B, iters), dtype=np.float64)
    for lo in range(0, B, tile):
        idx, keep = _tile_indices(lo, min(lo + tile, B), tile)
        sg, v0 = _mesh.shard_batch(
            mesh, jnp.asarray(slot_signs[idx], dtype=jnp.float32),
            jnp.asarray(v0s[idx]))
        a, b = _signed_lanczos_batched(tab, sg, v0, iters, backend=bk,
                                       mesh=mesh)
        alphas[lo:lo + keep] = np.asarray(a, dtype=np.float64)[:keep]
        betas[lo:lo + keep] = np.asarray(b, dtype=np.float64)[:keep]
    lmin, lmax = _batched_ritz_extremes(alphas, betas)
    return lmax, lmin


def rho2_lanczos_batched(topos: Sequence[Topology], iters: int = 200,
                         seed: int = 0) -> list:
    """rho_2 for a batch of same-shape regular graphs in ONE vmapped solve.

    All topologies must share (n, table-width) so their neighbor tables stack;
    bipartite graphs are rejected (their -k pair needs per-graph deflation) —
    the survey layer routes those through :func:`rho2_lanczos` one by one.
    """
    if not topos:
        return []
    shapes = set()
    tabs, lws = [], []
    for t in topos:
        if t.meta.get("bipartite"):
            raise ValueError(f"{t.name}: bipartite graphs cannot be batched")
        tab, w = t.gather_operands()
        shapes.add(tab.shape)
        tabs.append(tab)
        lws.append(w)
    if len(shapes) != 1:
        raise ValueError(f"neighbor tables must share one shape, got {shapes}")
    obs.count("lanczos/solves", len(topos))
    obs.count("lanczos/iters", len(topos) * iters)
    key = jax.random.PRNGKey(seed)
    n = topos[0].n
    v0s = jax.random.normal(key, (len(topos), n), dtype=jnp.float32)
    alphas, betas = _lanczos_tridiag_batched(
        jnp.asarray(np.stack(tabs), dtype=jnp.int32),
        jnp.asarray(np.stack(lws), dtype=jnp.float32), v0s, iters)
    alphas = np.asarray(alphas, dtype=np.float64)
    betas = np.asarray(betas, dtype=np.float64)
    out = []
    for i, t in enumerate(topos):
        ev = _tridiag_eigvals(alphas[i], betas[i][:-1])
        out.append(float(t.radix - ev[-1]))
    return out
