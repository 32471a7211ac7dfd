"""Synthetic traffic patterns + routing-scheme link-load accounting.

The routing layer (:mod:`repro.core.routing`) measures where shortest paths
*are*; this module loads them.  Each traffic pattern is a demand matrix
``D[s, t]`` normalized so every node injects at most 1 unit of traffic
(``sum_t D[s, t] <= 1``).  Four routing schemes (:data:`ROUTING_SCHEMES`)
turn demands into directed link loads:

* ``minimal`` — all minimal paths, equal weight per path (ECMP, the
  SpectralFly evaluation model): the flow from s to t crossing edge (u, v)
  on a shortest-path DAG is ``D[s,t] * sigma(s,u) * sigma(v,t) / sigma(s,t)``,
  computed by a Brandes-style backward accumulation over BFS layers — one
  vectorized gather per layer, batched over sources;
* ``valiant`` — Valiant load balancing: every unit s → t detours through a
  uniformly random intermediate w (two minimal-ECMP legs s → w, w → t),
  evaluated in expectation over all intermediates;
* ``ugal`` — UGAL-style adaptive selection: each pair routes minimally
  unless the estimated minimal-channel load exceeds the Valiant
  alternative's (``d_min * q_min > h_val * q_val``), in which case it
  diverts to Valiant;
* ``ksp`` — k-shortest-path non-minimal ECMP: equal splitting over every
  path of length at most ``dist(s, t) + slack`` (near-minimal layers of the
  same frontier-BFS DP).

:func:`mcf_throughput_ub` bounds all of them from above with a
multi-commodity-flow LP on the directed link-capacity polytope (scipy
linprog; optional dependency).

Units
-----
* demands and link loads are in *injection units*: load 1.0 on a directed
  link means it carries exactly one node's full injection rate;
* ``saturation_throughput`` = 1 / max link load: the factor every node can
  scale its injection by before the hottest link saturates (unit link
  capacity), dimensionless;
* conservation: the sum of all directed link loads equals
  ``sum_{s,t} D[s,t] * hops(s,t)`` exactly — each unit of flow occupies one
  unit of load per hop traversed.

Patterns (:data:`TRAFFIC_PATTERNS`)
-----------------------------------
* ``uniform``        — all-to-all, ``D[s, t] = 1/(n-1)``
* ``bit_complement`` — permutation ``t = (n-1) - s`` (bitwise complement when
  n is a power of two)
* ``transpose``      — permutation ``(a, b) → (b, a)`` for n = m*m (matrix
  transpose); raises for non-square n
* ``neighbor``       — nearest-neighbor stencil: half a unit to each of
  ``s ± 1 (mod n)``
* ``adversarial``    — spectrally adversarial permutation: vertices sorted by
  Fiedler value are matched first-to-last, forcing every flow across the
  sparsest (Fiedler) cut
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

from .graphs import Topology
from .routing import (DEFAULT_SOURCE_CHUNK, RoutingResult, analyze_routing,
                      exact_counts, reverse_slot_index)
from repro.kernels import spmv as KS

try:                                   # optional: only the MCF LP bound
    from scipy import sparse as _scipy_sparse
    from scipy.optimize import linprog as _scipy_linprog
except ImportError:                    # pragma: no cover - scipy-less CI
    _scipy_sparse = None
    _scipy_linprog = None

__all__ = [
    "TRAFFIC_PATTERNS", "ROUTING_SCHEMES", "TrafficResult", "demand_matrix",
    "demand_rows", "EcmpRouter", "ecmp_link_loads", "scheme_link_loads",
    "valiant_link_loads", "ugal_link_loads", "ksp_link_loads",
    "mcf_throughput_ub", "evaluate_traffic", "spectral_throughput_estimate",
]

TRAFFIC_PATTERNS = ("uniform", "bit_complement", "transpose", "neighbor",
                    "adversarial")

#: routing schemes understood by :func:`evaluate_traffic` /
#: :func:`scheme_link_loads` (and, through them, the simulator's schedule
#: compiler and the survey's thpt_* columns).
ROUTING_SCHEMES = ("minimal", "valiant", "ugal", "ksp")


# --------------------------------------------------------------------------
# demand matrices
# --------------------------------------------------------------------------

def _permutation_demands(perm: np.ndarray) -> np.ndarray:
    """Demand matrix of a permutation: one unit from s to perm[s] (fixed
    points send nothing — a node never loads the network talking to itself)."""
    n = perm.size
    D = np.zeros((n, n))
    s = np.arange(n)
    keep = perm != s
    D[s[keep], perm[keep]] = 1.0
    return D


def _pattern_permutation(pattern: str, n: int, *,
                         fiedler: Optional[np.ndarray] = None) -> np.ndarray:
    """The permutation behind a permutation-type pattern (O(n log n), no
    (n, n) matrix — the scalable core shared by matrix and row builders)."""
    if pattern == "bit_complement":
        return n - 1 - np.arange(n)
    if pattern == "transpose":
        m = math.isqrt(n)
        if m * m != n:
            raise ValueError(f"transpose traffic needs square n, got {n}")
        s = np.arange(n)
        return (s % m) * m + s // m
    if pattern == "adversarial":
        if fiedler is None:
            raise ValueError("adversarial traffic needs the Fiedler vector")
        f = np.asarray(fiedler, dtype=np.float64)
        # Canonicalize before pairing: on degenerate Fiedler eigenspaces the
        # raw eigenvector differs across eigensolver paths / BLAS builds, and
        # argsort ties make the permutation (hence thpt_adversarial) drift.
        # Quantizing to 6 decimals of the max-normalized vector collapses
        # cross-backend jitter (~1e-13) into identical keys; the index
        # tie-break then makes the ordering fully deterministic, and the
        # leading-sign flip removes the eigenvector's sign ambiguity.
        amax = np.max(np.abs(f)) if f.size else 0.0
        q = np.round(f / amax, 6) if amax > 0 else np.zeros_like(f)
        nz = np.flatnonzero(q)
        if nz.size and q[nz[0]] < 0:
            q = -q
        order = np.lexsort((np.arange(n), q))
        perm = np.empty(n, dtype=np.int64)
        perm[order] = order[::-1]
        return perm
    raise ValueError(f"unknown traffic pattern {pattern!r} "
                     f"(known: {TRAFFIC_PATTERNS})")


def demand_rows(pattern: str, n: int, sources: Sequence[int], *,
                fiedler: Optional[np.ndarray] = None) -> np.ndarray:
    """The ``sources`` rows of :func:`demand_matrix` without materializing it.

    This is the datacenter-scale entry point: an (n, n) float64 demand matrix
    at n = 65536 is 32 GiB, but a sampled traffic evaluation only ever routes
    the S sampled source rows.  Row order follows ``sources``.  Exactly equal
    to ``demand_matrix(pattern, n)[sources]`` (tested), so the sampled path
    inherits every pattern's semantics.
    """
    srcs = np.asarray(list(sources), dtype=np.int64)
    S = srcs.size
    rows = np.arange(S)
    if pattern == "uniform":
        if n < 2:
            raise ValueError("uniform traffic needs n >= 2")
        D = np.full((S, n), 1.0 / (n - 1))
        D[rows, srcs] = 0.0
        return D
    if pattern == "neighbor":
        D = np.zeros((S, n))
        np.add.at(D, (rows, (srcs + 1) % n), 0.5)
        np.add.at(D, (rows, (srcs - 1) % n), 0.5)
        D[rows, srcs] = 0.0
        return D
    perm = _pattern_permutation(pattern, n, fiedler=fiedler)
    D = np.zeros((S, n))
    keep = perm[srcs] != srcs
    D[rows[keep], perm[srcs[keep]]] = 1.0
    return D


def demand_matrix(pattern: str, n: int, *,
                  fiedler: Optional[np.ndarray] = None) -> np.ndarray:
    """Build the (n, n) demand matrix of a named synthetic pattern.

    Args:
        pattern: one of :data:`TRAFFIC_PATTERNS`.
        n: number of nodes.
        fiedler: (n,) Fiedler vector, required by ``adversarial`` (it defines
            the cut the permutation stresses).

    Returns:
        (n, n) float64 demands in injection units; row sums are <= 1 and the
        diagonal is 0.
    """
    if pattern == "uniform":
        if n < 2:
            raise ValueError("uniform traffic needs n >= 2")
        D = np.full((n, n), 1.0 / (n - 1))
        np.fill_diagonal(D, 0.0)
        return D
    if pattern == "neighbor":
        D = np.zeros((n, n))
        s = np.arange(n)
        D[s, (s + 1) % n] += 0.5
        D[s, (s - 1) % n] += 0.5
        np.fill_diagonal(D, 0.0)   # n <= 2 degenerates to self-traffic
        return D
    return _permutation_demands(_pattern_permutation(pattern, n,
                                                     fiedler=fiedler))


# --------------------------------------------------------------------------
# ECMP link loads (Brandes-style backward accumulation, batched over sources)
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("backend",))
def _ecmp_loads_chunk(table: jnp.ndarray, dist: jnp.ndarray,
                      sigma: jnp.ndarray, w: jnp.ndarray,
                      backend: Optional[str] = None) -> jnp.ndarray:
    """Summed per-edge ECMP loads for a (S, n) block of sources.

    For each source: backward accumulation over BFS layers d = dmax..1 of
    ``g(v) = w(v) + sigma(v) * sum_{v' in succ(v)} g(v')/sigma(v')`` (the
    demand subtree routed through v) — the per-layer neighbor sum is one spmv
    through the :mod:`repro.kernels.spmv` dispatcher — then the per-slot
    directed edge loads ``load[u, j] = sigma(u) * g(v)/sigma(v)`` for
    ``v = table[u, j]`` one hop further out.  Self-padded slots have equal
    dist and drop out of the mask.  Returns the (n, k) load table summed over
    the block's sources.
    """
    obs.count("jit_trace/ecmp")                  # trace-time increment
    bk = KS.resolve_backend(backend)
    dmax = jnp.maximum(dist.max(), 0)

    def one(dist_s, sigma_s, w_s):
        sigma_safe = jnp.where(sigma_s > 0, sigma_s, 1.0)

        def back(i, g):
            d = dmax - i
            h = jnp.where(dist_s == d, g / sigma_safe, 0.0)
            inc = KS.spmv(h, table, backend=bk)
            return jnp.where(dist_s == d - 1, g + sigma_s * inc, g)

        g = jax.lax.fori_loop(0, dmax, back, w_s)
        ratio = jnp.where(dist_s > 0, g / sigma_safe, 0.0)
        succ = dist_s[table] == (dist_s[:, None] + 1)
        return sigma_s[:, None] * jnp.where(succ, ratio[table], 0.0)

    return jax.vmap(one)(dist, sigma, w).sum(axis=0)


@functools.partial(jax.jit, static_argnames=("backend",))
def _ecmp_pair_loads_chunk(table: jnp.ndarray, dist: jnp.ndarray,
                           sigma: jnp.ndarray, src: jnp.ndarray,
                           dst: jnp.ndarray, nbytes: jnp.ndarray,
                           backend: Optional[str] = None) -> jnp.ndarray:
    """:func:`_ecmp_loads_chunk` of a block of sources whose demand rows are
    built here from node pairs: row ``src`` gets ``nbytes`` at column
    ``dst``."""
    obs.count("jit_trace/ecmp_pairs")            # trace-time increment
    rows = jnp.zeros(dist.shape, jnp.float32).at[src, dst].add(nbytes)
    return _ecmp_loads_chunk(table, dist, sigma, rows, backend=backend)


class EcmpRouter:
    """Minimal-ECMP link loads of any number of demands over one routing.

    The padded table, and each chunk of sources' BFS distances and float32
    path counts, go to the device on first use and stay there, so every
    demand routed after reuses them.  Demand comes as (S, n) rows aligned
    with the sources (:meth:`row_loads`) or, where the routing covers every
    source, as node pairs (:meth:`pair_loads`); both give the same loads
    for the same demand.

    Args:
        table: (n, k) padded neighbor table (``gather_operands()[0]``).
        dist: (S, n) BFS distances from :func:`repro.core.routing.bfs_distances`.
        sigma: (S, n) minimal-path counts matching ``dist``.
        chunk: sources per jitted call.
    """

    def __init__(self, table: np.ndarray, dist: np.ndarray,
                 sigma: np.ndarray, chunk: int = DEFAULT_SOURCE_CHUNK,
                 backend: Optional[str] = None):
        table = np.asarray(table)
        self.shape = table.shape
        self.table = jnp.asarray(table, dtype=jnp.int32)
        self.dist = np.asarray(dist)
        self.sigma = sigma
        self.backend = backend
        S = self.dist.shape[0]
        self.bounds = [(lo, min(lo + chunk, S)) for lo in range(0, S, chunk)]
        self._chunks: Dict[int, Tuple[jnp.ndarray, jnp.ndarray]] = {}

    def _chunk(self, i: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
        if i not in self._chunks:
            lo, hi = self.bounds[i]
            self._chunks[i] = (
                jnp.asarray(self.dist[lo:hi]),
                jnp.asarray(self.sigma[lo:hi], dtype=jnp.float32))
        return self._chunks[i]

    def row_loads(self, demands: np.ndarray) -> np.ndarray:
        """(n, k) float64 directed loads of (S, n) demand rows in injection
        units, one per BFS source (row s holds D[s, :]): entry ``[u, j]`` is
        the load on directed link u → table[u, j] (padding slots stay 0;
        parallel edges each get their ECMP share).  Demands to unreachable
        targets are ignored (dropped, reported by :func:`evaluate_traffic`).
        """
        # a demand to an unreachable target would otherwise sit in g forever
        demands = np.where(self.dist >= 0, demands, 0.0)
        loads = np.zeros(self.shape, dtype=np.float64)
        for i, (lo, hi) in enumerate(self.bounds):
            dist, sigma = self._chunk(i)
            loads += np.asarray(_ecmp_loads_chunk(
                self.table, dist, sigma,
                jnp.asarray(demands[lo:hi], dtype=jnp.float32),
                backend=self.backend), dtype=np.float64)
        return loads

    def pair_loads(self, u: np.ndarray, v: np.ndarray, b: np.ndarray
                   ) -> np.ndarray:
        """(n, k) float64 directed loads of ``b[i]`` on node pair
        ``u[i] → v[i]``, as :meth:`row_loads` lays them out.  Needs the
        routing of every source (row ``s`` of ``dist`` is node ``s``).
        Pairs to unreachable targets are ignored.  Each chunk's pairs go to
        the device, bytes in float32, padded to a power of two so that a
        chunk compiles once for pair counts up to it; the device builds the
        demand rows.  A pair listed once gives the float32 value of its
        bytes, as :meth:`row_loads`'s cast does; a pair listed twice adds
        its two payloads in float32."""
        if self.dist.shape[0] != self.shape[0]:
            raise ValueError("pair demand needs an all-sources routing "
                             f"(got {self.dist.shape[0]}/{self.shape[0]} "
                             "sources)")
        served = np.where(self.dist[u, v] >= 0, b, 0.0)
        order = np.argsort(u, kind="stable")
        u, v = u[order], v[order]
        served = served[order].astype(np.float32)
        loads = np.zeros(self.shape, dtype=np.float64)
        for i, (lo, hi) in enumerate(self.bounds):
            a, z = np.searchsorted(u, [lo, hi])
            if z == a:
                continue
            cap = 1 << int(z - a - 1).bit_length()
            src = np.zeros(cap, dtype=np.int32)
            dst = np.zeros(cap, dtype=np.int32)
            nbytes = np.zeros(cap, dtype=np.float32)
            src[:z - a], dst[:z - a] = u[a:z] - lo, v[a:z]
            nbytes[:z - a] = served[a:z]
            dist, sigma = self._chunk(i)
            loads += np.asarray(_ecmp_pair_loads_chunk(
                self.table, dist, sigma, src, dst, nbytes,
                backend=self.backend), dtype=np.float64)
        return loads


def ecmp_link_loads(table: np.ndarray, dist: np.ndarray, sigma: np.ndarray,
                    demands: np.ndarray,
                    chunk: int = DEFAULT_SOURCE_CHUNK,
                    backend: Optional[str] = None) -> np.ndarray:
    """Directed link loads under minimal-path ECMP routing of ``demands``:
    :meth:`EcmpRouter.row_loads` of a router used once.

    Args:
        table: (n, k) padded neighbor table (``gather_operands()[0]``).
        dist: (S, n) BFS distances from :func:`repro.core.routing.bfs_distances`.
        sigma: (S, n) minimal-path counts matching ``dist``.
        demands: (S, n) demand rows in injection units, one per BFS source
            (row s holds D[s, :]).  Demands to unreachable targets are ignored
            (dropped, reported by :func:`evaluate_traffic`).
        chunk: sources per jitted call.

    Returns:
        (n, k) float64 directed loads aligned with the table slots: entry
        ``[u, j]`` is the load on directed link u → table[u, j] (padding slots
        stay 0; parallel edges each get their ECMP share).
    """
    return EcmpRouter(table, dist, sigma, chunk=chunk,
                      backend=backend).row_loads(demands)


@functools.partial(jax.jit, static_argnames=("backend",))
def _ecmp_loads_cand_chunk(table: jnp.ndarray, dist: jnp.ndarray,
                           sigma: jnp.ndarray, w: jnp.ndarray,
                           cand: jnp.ndarray,
                           backend: Optional[str] = None) -> jnp.ndarray:
    """*Per-source* ECMP loads at M candidate flat slots — (S, M).

    Same backward accumulation as :func:`_ecmp_loads_chunk`, but instead of
    summing over the block it gathers each source's contribution to the M
    candidate ``(u, j)`` slots (flat indices into the (n, k) load table).
    This is the second pass of the sampled max-load bootstrap: resampling
    source rows of the (S, M) matrix rebuilds the max statistic's sampling
    distribution without ever storing (S, n, k).
    """
    obs.count("jit_trace/ecmp_candidates")       # trace-time increment
    bk = KS.resolve_backend(backend)
    dmax = jnp.maximum(dist.max(), 0)

    def one(dist_s, sigma_s, w_s):
        sigma_safe = jnp.where(sigma_s > 0, sigma_s, 1.0)

        def back(i, g):
            d = dmax - i
            h = jnp.where(dist_s == d, g / sigma_safe, 0.0)
            inc = KS.spmv(h, table, backend=bk)
            return jnp.where(dist_s == d - 1, g + sigma_s * inc, g)

        g = jax.lax.fori_loop(0, dmax, back, w_s)
        ratio = jnp.where(dist_s > 0, g / sigma_safe, 0.0)
        succ = dist_s[table] == (dist_s[:, None] + 1)
        full = sigma_s[:, None] * jnp.where(succ, ratio[table], 0.0)
        return full.ravel()[cand]

    return jax.vmap(one)(dist, sigma, w)


def _max_link_load_ucb(table: np.ndarray, routing: RoutingResult,
                       served: np.ndarray, loads_scaled: np.ndarray, *,
                       chunk: int, backend: Optional[str],
                       bootstrap: int = 200, confidence: float = 0.95,
                       candidates: int = 256) -> float:
    """One-sided bootstrap upper confidence bound for the full-census max
    directed-link load under sampled-source routing.

    The n/S correction is unbiased per-slot, but ``max`` over slots of an
    estimate is biased low (unsampled sources contribute nothing to the true
    hottest link).  This reruns the load accumulation restricted to the
    ``candidates`` hottest slots of the point estimate, keeping *per-source*
    contributions, then bootstrap-resamples source rows and takes the
    ``confidence`` quantile of the replicate maxima.  Caveat: links outside
    the candidate set are invisible to the bound; with the default 256 slots
    the true argmax is overwhelmingly among them for the smooth load
    profiles ECMP produces (documented in docs/scale.md).
    """
    n, k = table.shape
    S = routing.dist.shape[0]
    flat = loads_scaled.ravel()
    M = int(min(candidates, flat.size))
    cand = np.argsort(flat)[-M:]
    tab = jnp.asarray(table, dtype=jnp.int32)
    cand_j = jnp.asarray(cand, dtype=jnp.int32)
    demands = np.where(routing.dist >= 0, served, 0.0)
    # the (inner, n, k) per-source intermediate is the footprint here
    inner = max(1, min(chunk, (64 << 20) // max(4 * n * k, 1)))
    C = np.zeros((S, M), dtype=np.float64)
    for lo in range(0, S, inner):
        hi = min(lo + inner, S)
        C[lo:hi] = np.asarray(_ecmp_loads_cand_chunk(
            tab, jnp.asarray(routing.dist[lo:hi]),
            jnp.asarray(routing.sigma[lo:hi], dtype=jnp.float32),
            jnp.asarray(demands[lo:hi], dtype=jnp.float32),
            cand_j, backend=backend), dtype=np.float64)
    rng = np.random.default_rng((routing.seed or 0) + 0x10AD)
    idx = rng.integers(0, S, size=(bootstrap, S))
    rep_max = (n / S) * C[idx].sum(axis=1).max(axis=1)
    ucb = float(np.quantile(rep_max, confidence))
    return max(ucb, float(loads_scaled.max()))


# --------------------------------------------------------------------------
# non-minimal & adaptive schemes: Valiant, UGAL, k-shortest-path ECMP
# --------------------------------------------------------------------------

def valiant_link_loads(table: np.ndarray, routing: RoutingResult,
                       served: np.ndarray, *,
                       chunk: int = DEFAULT_SOURCE_CHUNK,
                       backend: Optional[str] = None
                       ) -> Tuple[np.ndarray, float, int]:
    """Valiant load balancing in expectation over all intermediates.

    Every unit s → t is routed s → w → t for a uniformly random intermediate
    w, each leg minimal-ECMP.  Rather than sampling w, both legs are routed
    in expectation: leg 1 sends ``out(s)/n`` from s to every w; leg 2 sends
    ``in(t)/S`` from every *sampled* source row (the intermediate pool under
    sampling — all n rows when exact, so both legs reduce to the exact
    ``/n`` split) to every t.  The caller's single n/S correction then makes
    both legs unbiased estimators of the full-census Valiant loads.

    Returns ``(loads (n, k) float64 — unscaled, hops_weighted, max_hops)``
    where ``hops_weighted`` counts both legs (conservation: equals the load
    sum) and ``max_hops`` = worst leg-1 distance + worst leg-2 distance (the
    simulator's round-latency bound).
    """
    dist = routing.dist
    S, n = served.shape
    out_s = served.sum(axis=1)
    in_t = served.sum(axis=0)
    D1 = np.broadcast_to(out_s[:, None] / n, (S, n)).copy()
    D2 = np.broadcast_to(in_t[None, :] / S, (S, n)).copy()
    loads = ecmp_link_loads(table, dist, routing.sigma, D1,
                            chunk=chunk, backend=backend)
    loads += ecmp_link_loads(table, dist, routing.sigma, D2,
                             chunk=chunk, backend=backend)
    reach = dist >= 0
    dpos = np.where(reach, dist, 0)
    hops = float((np.where(reach, D1, 0.0) * dpos).sum()
                 + (np.where(reach, D2, 0.0) * dpos).sum())
    h1 = int(dpos[out_s > 0].max()) if bool((out_s > 0).any()) else 0
    h2 = int(dpos[:, in_t > 0].max()) if bool((in_t > 0).any()) else 0
    return loads, hops, h1 + h2


@jax.jit
def _ugal_qmin_chunk(table: jnp.ndarray, load_in: jnp.ndarray,
                     dist: jnp.ndarray) -> jnp.ndarray:
    """Peak minimal-DAG link load q_min(s, t) for a (S, n) block of sources.

    Layered max-DP over the BFS DAG: ``M(v)`` at layer d is the max over
    predecessor slots (neighbors one layer closer) of
    ``max(M(pred), load(pred → v))`` — the largest link load anywhere on the
    union of minimal paths s → v.  ``load_in[v, j]`` is the load of the
    incoming directed link ``table[v, j] → v`` (gathered host-side through
    :func:`repro.core.routing.reverse_slot_index`).  Self-padded slots never
    qualify as predecessors (their dist equals the row's own).
    """
    obs.count("jit_trace/ugal_qmin")             # trace-time increment
    dmax = jnp.maximum(dist.max(), 0)

    def one(dist_s):
        def body(d, M):
            pred = dist_s[table] == (d - 1)
            cand = jnp.where(pred, jnp.maximum(M[table], load_in), 0.0)
            return jnp.where(dist_s == d, cand.max(axis=1), M)

        return jax.lax.fori_loop(1, dmax + 1, body,
                                 jnp.zeros(dist_s.shape, load_in.dtype))

    return jax.vmap(one)(dist)


def _ugal_decision(table: np.ndarray, routing: RoutingResult,
                   served: np.ndarray, *, chunk: int,
                   backend: Optional[str]
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """UGAL's per-pair choice: ``(minimal_mask (S, n) bool, L_min (n, k))``.

    One-shot UGAL-L-style estimate: channel loads are estimated from routing
    the *entire* offered demand all-minimal (q_min = peak load on the pair's
    minimal DAG) vs all-Valiant (q_val = global peak).  A pair stays minimal
    iff ``d_min * q_min <= h_val * q_val`` with ``h_val = E_w[d(s,w)] +
    E_w[d(w,t)]`` the expected Valiant path length; ties route minimal.
    Both sides scale identically under the sampled n/S correction, so the
    decision is taken on unscaled loads.
    """
    dist = routing.dist
    S, n = served.shape
    L_min = ecmp_link_loads(table, dist, routing.sigma, served,
                            chunk=chunk, backend=backend)
    rev = reverse_slot_index(table)
    load_in = L_min[table, rev]        # (n, k): load on link table[v,j] -> v
    L_val, _, _ = valiant_link_loads(table, routing, served,
                                     chunk=chunk, backend=backend)
    q_val = float(L_val.max())
    reach = dist >= 0
    dpos = np.where(reach, dist, 0)
    n_reach_row = np.maximum(reach.sum(axis=1), 1)
    n_reach_col = np.maximum(reach.sum(axis=0), 1)
    a_s = (dpos * reach).sum(axis=1) / n_reach_row   # E_w d(s, w)
    b_t = (dpos * reach).sum(axis=0) / n_reach_col   # E_w d(w, t)
    tab = jnp.asarray(table, dtype=jnp.int32)
    lin = jnp.asarray(load_in, dtype=jnp.float32)
    qmin = np.zeros((S, n), dtype=np.float64)
    for lo in range(0, S, chunk):
        hi = min(lo + chunk, S)
        qmin[lo:hi] = np.asarray(_ugal_qmin_chunk(
            tab, lin, jnp.asarray(dist[lo:hi])), dtype=np.float64)
    lhs = dpos * qmin
    rhs = (a_s[:, None] + b_t[None, :]) * q_val
    return (lhs <= rhs) | ~reach, L_min


def ugal_link_loads(table: np.ndarray, routing: RoutingResult,
                    served: np.ndarray, *,
                    chunk: int = DEFAULT_SOURCE_CHUNK,
                    backend: Optional[str] = None
                    ) -> Tuple[np.ndarray, float, int]:
    """UGAL adaptive routing: per-pair minimal vs Valiant by estimated load.

    Splits the served demand by :func:`_ugal_decision`, routes the minimal
    share ECMP and the diverted share Valiant, and sums the loads.  When
    nothing diverts (e.g. uniform traffic on every symmetric family — the
    minimal channel estimate never exceeds the doubled-hop Valiant one) the
    all-minimal loads computed for the decision are reused as-is, making
    UGAL degenerate to ``minimal`` exactly.

    Returns ``(loads, hops_weighted, max_hops)`` as
    :func:`valiant_link_loads`.
    """
    dist = routing.dist
    minimal_mask, L_min = _ugal_decision(table, routing, served,
                                         chunk=chunk, backend=backend)
    D_min = np.where(minimal_mask, served, 0.0)
    D_val = served - D_min
    reach = dist >= 0
    dpos = np.where(reach, dist, 0)
    sm = np.where(reach, D_min, 0.0)
    hops_min = float((sm * dpos).sum())
    mh_min = int(dpos[sm > 0].max()) if bool((sm > 0).any()) else 0
    if not D_val.any():
        return L_min, hops_min, mh_min
    loads = ecmp_link_loads(table, dist, routing.sigma, D_min,
                            chunk=chunk, backend=backend)
    lv, hv, mhv = valiant_link_loads(table, routing, D_val,
                                     chunk=chunk, backend=backend)
    return loads + lv, hops_min + hv, max(mh_min, mhv)


@functools.partial(jax.jit, static_argnames=("Lmax", "slack"))
def _ksp_loads_chunk(table: jnp.ndarray, nopad: jnp.ndarray,
                     dist: jnp.ndarray, demand: jnp.ndarray,
                     Lmax: int, slack: int
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Near-minimal path ECMP loads for a (S, n) block — forward/backward
    walk DP over length layers.

    Forward: ``W[h]`` = walks of length h from the source (one spmv per
    layer, pad slots masked by ``nopad``), stacked to (Lmax+1, n).  Every
    walk to t of length in ``[dist(t), dist(t)+slack]`` is an admitted path
    with equal weight ``D[t] / P(t)`` (``P`` = total admitted walks).  For
    ``slack <= 1`` every admitted walk is a simple path (a repeated vertex
    implies a closed subwalk of length >= 2, i.e. total length >=
    dist + 2); larger slacks admit backtracking walks — a derouting model.
    Backward: ``G[h](v)`` = downstream credit of being at v at step h;
    the load on slot (u, j) accumulates ``W[h][u] * G[h+1][table[u,j]]``.
    ``slack=0`` reproduces minimal ECMP exactly (equal weight per minimal
    path — the same model as :func:`_ecmp_loads_chunk`).  The walks are
    float64 (traced inside :func:`repro.core.routing.exact_counts`), so every
    spmv takes the ``ref`` backend: the Pallas kernel is float32-only.
    """
    obs.count("jit_trace/ksp")                   # trace-time increment
    n, k = table.shape

    def one(dist_s, d_s):
        src = (dist_s == 0).astype(nopad.dtype)

        def fwd(W, _):
            return KS.spmv(W, table, None, nopad, backend="ref"), W

        _, Ws = jax.lax.scan(fwd, src, None, length=Lmax + 1)
        dpos = jnp.maximum(dist_s, 0)
        P = jnp.zeros_like(d_s)
        wsum = jnp.zeros_like(d_s)     # sum_e (d+e) * W[d+e]
        for e in range(slack + 1):
            idx = jnp.minimum(dpos + e, Lmax)
            cnt = jnp.where((dist_s >= 0) & (dpos + e <= Lmax),
                            jnp.take_along_axis(Ws, idx[None, :], axis=0)[0],
                            0.0)
            P = P + cnt
            wsum = wsum + (dpos + e) * cnt
        credit = d_s / jnp.where(P > 0, P, 1.0)
        hops_s = jnp.sum(credit * wsum)

        def bwd(carry, xs):
            g_next, loads = carry      # G at h+1, running (n, k) loads
            wh, h = xs
            loads = loads + nopad * wh[:, None] * g_next[table]
            admit = (dist_s >= 0) & (h >= dist_s) & (h <= dist_s + slack)
            g = jnp.where(admit, credit, 0.0) + \
                KS.spmv(g_next, table, None, nopad, backend="ref")
            return (g, loads), None

        (_, loads_s), _ = jax.lax.scan(
            bwd, (jnp.zeros(n, d_s.dtype), jnp.zeros((n, k), d_s.dtype)),
            (Ws, jnp.arange(Lmax + 1)), reverse=True)
        return loads_s, hops_s

    loads, hops = jax.vmap(one)(dist, demand)
    return loads.sum(axis=0), hops.sum()


def ksp_link_loads(table: np.ndarray, routing: RoutingResult,
                   served: np.ndarray, *, slack: int = 1,
                   chunk: int = DEFAULT_SOURCE_CHUNK
                   ) -> Tuple[np.ndarray, float, int]:
    """k-shortest-path non-minimal ECMP: equal split over every path of
    length <= ``dist(s, t) + slack``.

    Returns ``(loads (n, k) float64 — unscaled, hops_weighted, max_hops)``.
    The DP runs in float64 (:func:`repro.core.routing.exact_counts` — walk
    counts overflow float32 fast) with the source chunk re-sized so the per-source
    (Lmax+1, n) walk stacks stay within a fixed byte budget.
    """
    if slack < 0:
        raise ValueError(f"slack must be >= 0 (got {slack})")
    table = np.asarray(table)
    n, k = table.shape
    dist = routing.dist
    served = np.where(dist >= 0, served, 0.0)
    if not served.any():
        return np.zeros((n, k), dtype=np.float64), 0.0, 0
    Lmax = int(dist[served > 0].max()) + int(slack)
    nopad = table != np.arange(n)[:, None]
    per_src = 8 * n * (Lmax + 2 + k)   # walk stack + load table, f64
    inner = max(1, min(chunk, (256 << 20) // max(per_src, 1)))
    tab = jnp.asarray(table, dtype=jnp.int32)
    loads = np.zeros((n, k), dtype=np.float64)
    hops = 0.0
    with exact_counts():
        npd = jnp.asarray(nopad, dtype=jnp.float64)
        for lo in range(0, dist.shape[0], inner):
            hi = min(lo + inner, dist.shape[0])
            lc, hc = _ksp_loads_chunk(
                tab, npd, jnp.asarray(dist[lo:hi]),
                jnp.asarray(served[lo:hi], dtype=jnp.float64),
                Lmax=Lmax, slack=int(slack))
            loads += np.asarray(lc, dtype=np.float64)
            hops += float(hc)
    return loads, hops, Lmax


def scheme_link_loads(table: np.ndarray, routing: RoutingResult,
                      served: np.ndarray, scheme: str = "minimal", *,
                      slack: int = 1, chunk: int = DEFAULT_SOURCE_CHUNK,
                      backend: Optional[str] = None,
                      router: Optional[EcmpRouter] = None
                      ) -> Tuple[np.ndarray, float, int]:
    """Route served demand rows under one of :data:`ROUTING_SCHEMES`.

    The shared dispatch used by :func:`evaluate_traffic` and the simulator's
    schedule compiler.  ``served`` is (S, n) demand rows aligned with
    ``routing.sources`` (diagonal zeroed, unreachable targets dropped).
    ``router``: an :class:`EcmpRouter` over ``routing`` whose operands are
    already on the device, for the minimal scheme (a caller routing many
    demands over one graph); one is made here when absent.

    Returns ``(loads, hops_weighted, max_hops)``: (n, k) float64 directed
    slot loads *before* any n/S sampling correction, the demand-weighted hop
    total (equals the load sum — conservation), and the worst per-flow hop
    count (the simulator's round-latency bound).
    """
    table = np.asarray(table)
    dist = routing.dist
    if scheme == "minimal":
        router = router or EcmpRouter(table, dist, routing.sigma,
                                      chunk=chunk, backend=backend)
        loads = router.row_loads(served)
        reach = dist >= 0
        dpos = np.where(reach, dist, 0)
        sm = np.where(reach, served, 0.0)
        hops = float((sm * dpos).sum())
        mh = int(dpos[sm > 0].max()) if bool((sm > 0).any()) else 0
        return loads, hops, mh
    if scheme == "valiant":
        return valiant_link_loads(table, routing, served,
                                  chunk=chunk, backend=backend)
    if scheme == "ugal":
        return ugal_link_loads(table, routing, served,
                               chunk=chunk, backend=backend)
    if scheme == "ksp":
        return ksp_link_loads(table, routing, served, slack=slack,
                              chunk=chunk)
    raise ValueError(f"unknown routing scheme {scheme!r} "
                     f"(known: {ROUTING_SCHEMES})")


# --------------------------------------------------------------------------
# multi-commodity-flow LP throughput ceiling
# --------------------------------------------------------------------------

@obs.traced("traffic/mcf_throughput_ub", phase="execute")
def mcf_throughput_ub(topo: Union[Topology, Tuple[np.ndarray, int]],
                      pattern: str = "uniform", *,
                      fiedler: Optional[np.ndarray] = None,
                      demands: Optional[np.ndarray] = None,
                      groups: Optional[int] = None) -> float:
    """LP upper bound on saturation throughput over *all* routings.

    Maximize theta s.t. theta-scaled demands admit a fractional
    multi-commodity flow respecting unit capacity on every directed link
    (one capacity unit per non-padding gather-table slot — parallel edges
    each count, matching the ECMP slot semantics).  Commodities are grouped
    by source into ``groups`` buckets (contiguous in Fiedler order when
    ``fiedler`` is given, index order otherwise): merging commodities only
    *relaxes* the flow polytope, so the grouped optimum is a valid upper
    bound on the true per-commodity MCF optimum — which in turn dominates
    every realizable routing scheme — for any group count.  ``groups >= n``
    is the exact per-commodity LP.

    The LP has ``1 + groups * E`` variables (scipy sparse + HiGHS); the
    default caps at 8 groups (~25k variables on the largest bench
    instances) — HiGHS wall time grows super-linearly with the group count
    on these highly-degenerate instances while the bound barely tightens,
    and a coarse grouping is still a certified (just looser) ceiling.
    Tiny instances (``n <= 8``) get the exact per-commodity LP under the
    same cap.  Assumes a connected
    topology (demand between disconnected components makes the LP
    infeasible).  Raises ``RuntimeError`` with a clear message when scipy is
    unavailable — callers (survey, benches) catch it and skip the column.

    Returns theta* (``inf`` when there is no demand).
    """
    if _scipy_linprog is None:
        raise RuntimeError(
            "mcf_throughput_ub needs scipy (scipy.optimize.linprog) which is "
            "not installed — the MCF LP bound is skipped; install scipy to "
            "enable it")
    if isinstance(topo, Topology):
        n = topo.n
        table = topo.gather_operands()[0]
    else:
        table, n = np.asarray(topo[0]), int(topo[1])
    if demands is None:
        D = demand_rows(pattern, n, np.arange(n), fiedler=fiedler)
    else:
        D = np.asarray(demands, dtype=np.float64).copy()
        if D.shape != (n, n):
            raise ValueError(f"demands must be ({n}, {n}), got {D.shape}")
        D[np.arange(n), np.arange(n)] = 0.0
    if D.sum() <= 0:
        return float("inf")
    mask = (table != np.arange(n)[:, None]).ravel()
    tail = np.repeat(np.arange(n), table.shape[1])[mask]
    head = table.ravel()[mask]
    E = tail.size
    if groups is None:
        # HiGHS wall time grows super-linearly in the group count while the
        # bound barely tightens past a handful of groups (hypercube(8):
        # identical UB at 2..12 groups, 0.1s vs minutes) — cap at 8
        groups = max(2, min(n, 25_000 // max(E, 1), 8))
    G = max(1, min(int(groups), n))
    order = np.arange(n)
    if fiedler is not None and G < n:
        f = np.asarray(fiedler, dtype=np.float64)
        amax = np.max(np.abs(f))
        q = np.round(f / amax, 6) if amax > 0 else np.zeros_like(f)
        order = np.lexsort((order, q))
    buckets = np.array_split(order, G)
    out = D.sum(axis=1)
    sup = np.zeros((G, n))
    for g, b in enumerate(buckets):
        sup[g, b] += out[b]
        sup[g] -= D[b].sum(axis=0)
    e_idx = np.arange(E)
    inc = _scipy_sparse.coo_matrix(
        (np.r_[np.ones(E), -np.ones(E)],
         (np.r_[tail, head], np.r_[e_idx, e_idx])), shape=(n, E)).tocsr()
    A_eq = _scipy_sparse.hstack(
        [_scipy_sparse.csr_matrix(-sup.reshape(G * n, 1)),
         _scipy_sparse.block_diag([inc] * G, format="csr")], format="csr")
    eye = _scipy_sparse.eye(E, format="csr")
    A_ub = _scipy_sparse.hstack(
        [_scipy_sparse.csr_matrix((E, 1))] + [eye] * G, format="csr")
    c = np.zeros(1 + G * E)
    c[0] = -1.0
    res = _scipy_linprog(c, A_ub=A_ub, b_ub=np.ones(E),
                         A_eq=A_eq, b_eq=np.zeros(G * n), method="highs")
    if res.status == 3:                # unbounded: no capacity ever binds
        return float("inf")
    if not res.success:
        raise RuntimeError(f"MCF LP failed (status {res.status}): "
                           f"{res.message}")
    return float(-res.fun)


# --------------------------------------------------------------------------
# evaluation driver
# --------------------------------------------------------------------------

@dataclasses.dataclass
class TrafficResult:
    """Link-load accounting of one pattern on one topology.

    ``max_link_load``/``mean_link_load`` are per *directed* link in injection
    units (each undirected edge is two directed links, loaded independently);
    ``saturation_throughput`` = 1/max load; ``conservation_error`` is the
    relative gap between the load sum and the demand-weighted hop count
    (should be float32-roundoff small).
    """
    name: str
    pattern: str
    n: int
    total_demand: float            # injection units offered (reachable pairs)
    dropped_demand: float          # injection units to unreachable targets
    avg_hops: float                # demand-weighted mean shortest-path hops
    link_loads: np.ndarray         # (n, k) directed loads (gather-table slots)
    max_link_load: float
    mean_link_load: float          # over loaded (non-padding) directed slots
    saturation_throughput: float   # 1 / max_link_load (inf if no load)
    conservation_error: float
    seconds: float
    exact: bool = True             # False = sampled-source estimate
    sample_correction: float = 1.0  # n/S factor applied to loads and totals
    scheme: str = "minimal"        # routing scheme the loads were routed by
    max_link_load_ucb: float = 0.0  # bootstrap UCB (== max when exact)

    def to_dict(self) -> Dict:
        """JSON-ready summary (drops the (n, k) load table)."""
        return dict(
            name=self.name, pattern=self.pattern, scheme=self.scheme,
            n=self.n, exact=self.exact,
            total_demand=round(self.total_demand, 6),
            dropped_demand=round(self.dropped_demand, 6),
            avg_hops=round(self.avg_hops, 6),
            max_link_load=round(self.max_link_load, 6),
            max_link_load_ucb=round(self.max_link_load_ucb, 6),
            mean_link_load=round(self.mean_link_load, 6),
            saturation_throughput=round(self.saturation_throughput, 6),
            conservation_error=self.conservation_error,
            seconds=round(self.seconds, 3))

    def report(self) -> str:
        """Compact text block for CLI reports."""
        return "\n".join([
            f"traffic         : {self.pattern} via {self.scheme} "
            f"({self.total_demand:.1f} units offered, "
            f"{self.avg_hops:.3f} avg hops)",
            f"max link load   : {self.max_link_load:.4f} "
            f"(mean {self.mean_link_load:.4f}) injection units",
            f"saturation thpt : {self.saturation_throughput:.4f} "
            f"injection fraction/node",
        ])


@obs.traced("traffic/evaluate", phase="execute")
def evaluate_traffic(topo: Union[Topology, Tuple[np.ndarray, int]],
                     pattern: str = "uniform", *,
                     scheme: str = "minimal",
                     slack: int = 1,
                     routing: Optional[RoutingResult] = None,
                     fiedler: Optional[np.ndarray] = None,
                     demands: Optional[np.ndarray] = None,
                     chunk: int = DEFAULT_SOURCE_CHUNK,
                     backend: Optional[str] = None) -> TrafficResult:
    """Route one synthetic pattern over a topology and account link loads.

    Args:
        topo: a :class:`Topology` or ``(table, n)`` padded-table pair.
        pattern: name from :data:`TRAFFIC_PATTERNS` (ignored when ``demands``
            is given, which then also names the result's pattern ``custom``).
        scheme: routing scheme from :data:`ROUTING_SCHEMES` (default
            ``minimal`` — the historical ECMP behaviour).
        slack: extra hops the ``ksp`` scheme admits beyond minimal
            (``dist + slack`` path budget); ignored by the other schemes.
        routing: reuse a :class:`RoutingResult` (e.g. the one a lazy Analysis
            session already computed); computed here if absent.  A *sampled*
            routing result (``exact=False``) is accepted: only its S source
            rows are routed and every extensive figure (loads, totals) is
            scaled by the unbiasedness correction n/S — uniform sources make
            the scaled per-link loads and totals unbiased estimators of the
            full-census figures.  ``max_link_load`` is then a noisy order
            statistic (biased low: unsampled sources contribute nothing);
            for the ``minimal`` scheme a bootstrap upper confidence bound
            ``max_link_load_ucb`` is computed over candidate hot slots and
            ``saturation_throughput`` uses *it*, so the sampled figure errs
            conservative rather than optimistic (other schemes keep the
            point estimate as the bound — see docs/scale.md).
        fiedler: Fiedler vector for the ``adversarial`` pattern.
        demands: explicit (n, n) demand matrix in injection units, overriding
            ``pattern`` (sampled routing uses its S source rows).
        chunk: sources per jitted call.
        backend: spmv backend for the load accumulation (default:
            dispatcher's).

    Returns:
        :class:`TrafficResult` with per-directed-link loads and the
        max-load / saturation-throughput summary.
    """
    t0 = time.time()
    if scheme not in ROUTING_SCHEMES:
        raise ValueError(f"unknown routing scheme {scheme!r} "
                         f"(known: {ROUTING_SCHEMES})")
    if isinstance(topo, Topology):
        name, n = topo.name, topo.n
        table = topo.gather_operands()[0]
    else:
        table, n = np.asarray(topo[0]), int(topo[1])
        name = f"table(n={n})"
    if routing is None:
        routing = analyze_routing((table, n), chunk=chunk)
    srcs = routing.sources
    S = srcs.size
    scale = 1.0 if routing.exact else n / S
    if demands is None:
        D = demand_rows(pattern, n, srcs, fiedler=fiedler)
    else:
        D = np.asarray(demands, dtype=np.float64)
        if D.shape != (n, n):
            raise ValueError(f"demands must be ({n}, {n}), got {D.shape}")
        D = D[srcs]
        pattern = "custom"
    reachable = routing.dist >= 0
    served = np.where(reachable, D, 0.0)
    served[np.arange(S), srcs] = 0.0
    total = float(served.sum())
    dropped = float(D.sum() - D[np.arange(S), srcs].sum() - total)
    with obs.span("traffic/loads", scheme=scheme, sources=S):
        loads, hops_weighted, _ = scheme_link_loads(
            table, routing, served, scheme, slack=slack, chunk=chunk,
            backend=backend)
    load_sum = float(loads.sum())
    # conservation holds per source row, so check it *before* the n/S scale
    conservation = abs(load_sum - hops_weighted) / max(hops_weighted, 1e-12)
    loads = loads * scale
    max_load = float(loads.max()) if loads.size else 0.0
    ucb = max_load
    if not routing.exact and scheme == "minimal" and max_load > 0:
        with obs.span("traffic/ucb", sources=S):
            ucb = _max_link_load_ucb(table, routing, served, loads,
                                     chunk=chunk, backend=backend)
    sat_denom = max_load if routing.exact else ucb
    loaded = loads[loads > 0]
    return TrafficResult(
        name=name, pattern=pattern, n=n, total_demand=total * scale,
        dropped_demand=dropped * scale,
        avg_hops=hops_weighted / total if total > 0 else 0.0,
        link_loads=loads, max_link_load=max_load,
        mean_link_load=float(loaded.mean()) if loaded.size else 0.0,
        saturation_throughput=1.0 / sat_denom if sat_denom > 0
        else float("inf"),
        conservation_error=conservation,
        seconds=time.time() - t0,
        exact=routing.exact, sample_correction=scale,
        scheme=scheme, max_link_load_ucb=ucb)


def spectral_throughput_estimate(n: int, rho2: float) -> float:
    """Uniform-traffic saturation throughput predicted from the spectral gap.

    Uniform all-to-all pushes ``|X| * |Y| / (n-1)`` injection units across any
    (X, Y) cut per direction; supporting that over the Fiedler bisection floor
    (Theorem 2, ``rho2 * n / 4`` links at unit capacity) needs
    ``theta = BW * (n-1) / (n/2)^2 ≈ rho2`` — the spectral prediction the
    measured ECMP figure is compared against.  Deliberately uncapped, exactly
    like :attr:`TrafficResult.saturation_throughput` (both can exceed 1: a
    node injects over all ``radix`` links at once).  Dimensionless, same
    units as the measured figure.
    """
    lo, hi = n // 2, n - n // 2
    bw = rho2 * n / 4.0
    return bw * (n - 1) / float(lo * hi)
