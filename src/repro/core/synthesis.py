"""Topology synthesis: batched search for maximum-spectral-gap graphs.

The paper's conclusion — every surveyed topology sits well below the
Ramanujan spectral-gap optimum — "suggests the potential utility of adopting
Ramanujan graphs as interconnection networks."  This module *designs* such
networks at a target (n, k) instead of only analyzing given ones, along the
two constructive paths of the literature:

* **Bilu–Linial lifts** (the Xpander line): repeatedly 2-lift a small seed,
  choosing each edge signing to minimize the top eigenvalue of the signed
  adjacency A_s — by the Bilu–Linial identity, spec(2-lift) = spec(A) ∪
  spec(A_s), so the signing alone controls the new eigenvalues.  The signed
  objective runs in the padded gather-table operand contract (one shared
  (n, k) neighbor table + per-candidate (n, k) slot signs) so B candidate
  signings cost ONE vmapped Lanczos solve
  (:func:`repro.core.spectral.signed_extremes_batched`), and a simulated-
  annealing single-flip refinement loop runs fully jitted under
  ``jax.lax.fori_loop`` with a warm-started small-Lanczos objective estimate.

* **Degree-preserving rewiring** (Markov-chain double-edge swaps): for sizes
  a lift tower cannot reach, hill-climb over the connected double-edge-swap
  chain from a random regular graph, scoring every candidate batch with the
  PR-2 batched Laplacian Lanczos (one vmapped solve per round via
  :func:`repro.core.spectral.rho2_laplacian_batched` over
  :func:`repro.core.faults.stacked_operands`).

:func:`synthesize` wraps both behind one call and returns a
:class:`SynthesisResult` (best topology, rho2 trajectory, fraction of the
Ramanujan-bound gap achieved).  The products register as first-class
families — ``build("xpander(512,6)")``, ``build("rewired(360,5)")`` — so
``Analysis``, ``survey()``, ``fault_sweep()`` and ``routing()/traffic()``
consume designed topologies exactly like surveyed ones.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

from ..api.registry import register
from ..kernels import spmv as KS
from . import bounds as B
from . import spectral as S
from .graphs import Topology
from .lifts import two_lift

__all__ = [
    "SynthesisResult", "synthesize", "lift_search", "rewire_search",
    "best_signing_batched", "signed_slot_operands", "double_edge_swaps",
    "xpander", "rewired",
]

#: candidate signings / graphs evaluated per batched solve
DEFAULT_BATCH = 24
#: default refinement budgets (see ``synthesize``'s ``budget`` docs)
DEFAULT_LIFT_BUDGET = 2400
DEFAULT_REWIRE_BUDGET = 288


# --------------------------------------------------------------------------
# signed-adjacency operands: the lifts.py objective in gather-table form
# --------------------------------------------------------------------------

def signed_slot_operands(topo: Topology) -> Tuple[np.ndarray, np.ndarray]:
    """(table (n, k) int32, edge_slot (n, k) int32) for an edge-regular graph.

    ``table`` is the standard neighbor table; ``edge_slot[i, j]`` is the row
    index into ``topo.edges`` that produced slot (i, j), so a batch of
    signings (B, m) expands to per-slot signs with ONE gather —
    ``signings[:, edge_slot]`` — placing each edge's sign into both of its
    table slots.  This is the port of ``lifts._signed_adjacency`` to the
    operand contract shared with the ``cayley_spmv`` kernel.
    """
    if topo.loops is not None and np.any(topo.loops):
        raise ValueError(f"{topo.name}: signed lifts need a loop-free graph")
    src = np.concatenate([topo.edges[:, 0], topo.edges[:, 1]])
    dst = np.concatenate([topo.edges[:, 1], topo.edges[:, 0]])
    eid = np.tile(np.arange(topo.m, dtype=np.int32), 2)
    order = np.argsort(src, kind="stable")
    src, dst, eid = src[order], dst[order], eid[order]
    deg = np.bincount(src, minlength=topo.n)
    k = int(deg.max())
    if not np.all(deg == k):
        raise ValueError(f"{topo.name}: signed lifts need an edge-regular graph")
    starts = np.concatenate([[0], np.cumsum(deg)])
    slot = np.arange(src.size) - starts[src]
    table = np.empty((topo.n, k), dtype=np.int32)
    edge_slot = np.empty((topo.n, k), dtype=np.int32)
    table[src, slot] = dst.astype(np.int32)
    edge_slot[src, slot] = eid
    return table, edge_slot


# --------------------------------------------------------------------------
# jitted simulated-annealing flip refinement
# --------------------------------------------------------------------------

def _lam_estimator(table, shift: float, est_iters: int, objective: str,
                   backend: Optional[str] = None):
    """Traceable objective estimate: a small warm-started Lanczos solve.

    For ``objective="gap"`` the operator is A_s + shift·I (PSD for
    shift >= k) and the estimate is its top Ritz value − shift, i.e.
    lambda_max(A_s) — the eigenvalue binding the lift's rho2.  For
    ``"radius"`` the raw A_s tridiagonal is read at both ends,
    max(|lambda_min|, lambda_max) — the Ramanujan criterion.  The signed
    matvec routes through the :mod:`repro.kernels.spmv` dispatcher.  Returns
    (estimate, next warm vector).
    """
    bk = KS.resolve_backend(backend)

    def est(sg, v0):
        def op(x):
            y = KS.spmv(x, table, signs=sg, backend=bk)
            if objective == "gap":
                y = y + shift * x
            return y

        a, b, V = S._lanczos_scan(op, v0, est_iters)
        T = jnp.diag(a) + jnp.diag(b[:-1], 1) + jnp.diag(b[:-1], -1)
        w, y = jnp.linalg.eigh(T)
        if objective == "gap":
            lam = w[-1] - shift
            top = y[:, -1]
        else:
            idx = jnp.argmax(jnp.abs(w))
            lam = jnp.abs(w)[idx]
            top = jnp.take(y, idx, axis=1)
        ritz = V[:est_iters].T @ top
        nrm = jnp.linalg.norm(ritz)
        ritz = jnp.where(nrm > 1e-6, ritz / jnp.where(nrm > 1e-6, nrm, 1.0), v0)
        return lam, ritz

    return est


@functools.partial(jax.jit, static_argnames=("steps", "est_iters", "objective",
                                             "backend"))
def _anneal_signings(table, edge_slot, signings, key, shift, temp0, *,
                     steps: int, est_iters: int, objective: str,
                     backend: Optional[str] = None):
    """SA single-flip refinement of B signings, fully on-device.

    Each ``fori_loop`` step flips one random edge sign per candidate,
    re-estimates the objective with a warm-started ``est_iters``-step Lanczos
    solve, and accepts downhill moves always / uphill moves with probability
    exp(-delta / T_t) under geometric cooling from ``temp0``.  Estimates are
    noisy by design — the caller re-scores refined AND original candidates
    with the exact batched solve and keeps the per-candidate winner
    (elitism), so estimator bias can never lose ground.
    """
    obs.count("jit_trace/anneal_signings")       # trace-time increment
    Bc, m = signings.shape
    n = table.shape[0]
    est = _lam_estimator(table, shift, est_iters, objective,
                         backend=KS.resolve_backend(backend))

    key, k0 = jax.random.split(key)
    v0s = jax.random.normal(k0, (Bc, n), dtype=jnp.float32)
    obj, vecs = jax.vmap(est)(signings[:, edge_slot], v0s)

    def step(t, carry):
        signings, obj, vecs, key = carry
        key, k1, k2 = jax.random.split(key, 3)
        e = jax.random.randint(k1, (Bc,), 0, m)
        flipped = jax.vmap(lambda s, ei: s.at[ei].multiply(-1.0))(signings, e)
        new_obj, new_vecs = jax.vmap(est)(flipped[:, edge_slot], vecs)
        temp = temp0 * jnp.exp(-3.0 * t / steps)
        u = jax.random.uniform(k2, (Bc,))
        accept = (new_obj < obj) | \
            (u < jnp.exp(-(new_obj - obj) / jnp.maximum(temp, 1e-9)))
        signings = jnp.where(accept[:, None], flipped, signings)
        obj = jnp.where(accept, new_obj, obj)
        vecs = jnp.where(accept[:, None], new_vecs, vecs)
        return signings, obj, vecs, key

    signings, obj, _, _ = jax.lax.fori_loop(0, steps, step,
                                            (signings, obj, vecs, key))
    return signings, obj


def best_signing_batched(topo: Topology, batch: int = DEFAULT_BATCH,
                         steps: int = 400, est_iters: int = 10,
                         iters: int = 90, seed: int = 0,
                         temp0: float = 0.05, objective: str = "gap"
                         ) -> Tuple[np.ndarray, float, float]:
    """Best of ``batch`` random signings after jitted SA flip refinement.

    The batched successor of ``lifts.best_random_signing``: all candidates
    are drawn, refined, and finally scored together (the exact scoring is one
    :func:`repro.core.spectral.signed_extremes_batched` call over refined ∪
    initial candidates, so refinement can only help).  Deterministic in
    ``seed``.  Returns (signing (m,) float ±1, lambda_max(A_s), signed
    spectral radius) of the winner under ``objective`` ("gap" minimizes
    lambda_max — the lift-rho2 criterion; "radius" minimizes
    max|eig| — the Ramanujan criterion).
    """
    if objective not in ("gap", "radius"):
        raise ValueError(f"unknown signing objective {objective!r}")
    table, edge_slot = signed_slot_operands(topo)
    rng = np.random.default_rng(seed)
    init = rng.choice([-1.0, 1.0], size=(batch, topo.m)).astype(np.float32)
    refined = init
    if steps > 0:
        refined, _ = _anneal_signings(
            jnp.asarray(table), jnp.asarray(edge_slot), jnp.asarray(init),
            jax.random.PRNGKey(seed), jnp.float32(topo.radix),
            jnp.float32(temp0), steps=steps, est_iters=est_iters,
            objective=objective)
        refined = np.sign(np.asarray(refined, dtype=np.float64))
        cands = np.concatenate([refined, init], axis=0)
    else:
        cands = init
    slot_signs = cands[:, edge_slot]
    lmax, lmin = S.signed_extremes_batched(table, slot_signs, iters=iters,
                                           seed=seed + 1)
    radius = np.maximum(np.abs(lmin), lmax)
    score = lmax if objective == "gap" else radius
    best = int(np.argmin(score))
    return cands[best].astype(np.float64), float(lmax[best]), float(radius[best])


# --------------------------------------------------------------------------
# degree-preserving double-edge-swap rewiring
# --------------------------------------------------------------------------

def double_edge_swaps(edges: np.ndarray, swaps: int,
                      rng: np.random.Generator) -> np.ndarray:
    """Apply ``swaps`` random degree-preserving double-edge swaps.

    The classic Markov-chain move on simple graphs: edges {a,b}, {c,d} become
    {a,c}, {b,d} (orientation randomized), rejected when it would create a
    self-loop or parallel edge, so the result is again simple with the exact
    same degree sequence.  Caps proposals at 20x ``swaps``.
    """
    e = np.array(edges, dtype=np.int64, copy=True)
    m = e.shape[0]
    eset = {tuple(sorted(row)) for row in e.tolist()}
    if len(eset) != m:
        raise ValueError("double_edge_swaps needs a simple graph")
    done = attempts = 0
    while done < swaps and attempts < 20 * swaps:
        attempts += 1
        i, j = rng.integers(0, m, size=2)
        if i == j:
            continue
        a, b = e[i]
        c, d = e[j]
        if rng.random() < 0.5:
            c, d = d, c
        if a == c or b == d:
            continue
        n1, n2 = tuple(sorted((int(a), int(c)))), tuple(sorted((int(b), int(d))))
        if n1 in eset or n2 in eset:
            continue
        eset.discard(tuple(sorted((int(a), int(b)))))
        eset.discard(tuple(sorted((int(c), int(d)))))
        eset.add(n1)
        eset.add(n2)
        e[i] = n1
        e[j] = n2
        done += 1
    return e


def _batched_rho2_edges(n: int, edge_sets: List[np.ndarray], iters: int,
                        seed: int) -> np.ndarray:
    """rho2 of B same-order graphs given as edge arrays, one vmapped solve."""
    from .faults import stacked_operands

    topos = [Topology("cand", n, e) for e in edge_sets]
    tabs, ws, degs = stacked_operands(topos)
    return S.rho2_laplacian_batched(tabs, ws, degs, iters=iters, seed=seed)


# --------------------------------------------------------------------------
# the two search drivers + synthesize()
# --------------------------------------------------------------------------

@dataclasses.dataclass
class SynthesisResult:
    """Outcome of one topology-design search."""
    topo: Topology              # the best graph found (regular, simple)
    method: str                 # "lift" or "rewire"
    n: int
    k: int
    rho2: float                 # measured on topo (dense or Lanczos verified)
    ramanujan_rho2: float       # k - 2 sqrt(k-1), the design optimum
    gap_fraction: float         # rho2 / ramanujan_rho2
    trajectory: List[float]     # predicted rho2 after each search stage
    evaluations: int            # candidate signings/graphs scored exactly
    seconds: float

    def to_dict(self) -> Dict:
        """JSON-ready summary (the topology itself is not serialized)."""
        return dict(name=self.topo.name, method=self.method, n=self.n,
                    k=self.k, rho2=round(self.rho2, 6),
                    ramanujan_rho2=round(self.ramanujan_rho2, 6),
                    gap_fraction=round(self.gap_fraction, 6),
                    trajectory=[round(x, 6) for x in self.trajectory],
                    evaluations=self.evaluations,
                    seconds=round(self.seconds, 3))

    def report(self) -> str:
        """Compact text block for CLI reports."""
        return "\n".join([
            f"synthesized     : {self.topo.name} (method={self.method})",
            f"nodes / radix   : {self.n} / {self.k}",
            f"rho2 (measured) : {self.rho2:.5f}",
            f"Ramanujan rho2  : {self.ramanujan_rho2:.5f} "
            f"({100 * self.gap_fraction:.1f}% achieved)",
            f"search          : {self.evaluations} exact evaluations, "
            f"{len(self.trajectory)} stages, {self.seconds:.1f}s",
        ])


def _lift_seed(n: int, k: int, seed: int) -> Tuple[Topology, int]:
    """Smallest valid 2-lift tower base: n = n0 * 2^t with n0 >= k+1 and
    n0*k even.  Returns (seed topology, t)."""
    from .topologies import complete, random_regular

    n0, t = n, 0
    while n0 % 2 == 0 and n0 // 2 >= k + 1 and ((n0 // 2) * k) % 2 == 0:
        n0 //= 2
        t += 1
    if t == 0:
        raise ValueError(
            f"lift synthesis cannot reach n={n} at k={k} (need n = n0 * 2^t "
            f"with n0 >= {k + 1} and n0*k even); use method='rewire'")
    g = complete(k + 1) if n0 == k + 1 else random_regular(n0, k, seed=seed)
    return g, t


@obs.traced("synthesis/lift_search", phase="execute")
def lift_search(n: int, k: int, budget: int = DEFAULT_LIFT_BUDGET,
                batch: int = DEFAULT_BATCH, seed: int = 0,
                iters: int = 90) -> Tuple[Topology, List[float], int]:
    """Grow an (n, k) expander by a tower of best-signed 2-lifts.

    ``budget`` is the total SA flip-refinement steps, split evenly across the
    tower's levels; each level additionally spends ``2 * batch`` exact signed
    Lanczos evaluations (one vmapped solve).  The rho2 trajectory uses the
    Bilu–Linial identity — lambda_2(lift) = max(lambda_2(base),
    lambda_max(A_s)) — so no intermediate full solves are needed.  Returns
    (topology, trajectory, exact evaluations).
    """
    g, t = _lift_seed(n, k, seed)
    lam2 = float(np.sort(S.adjacency_spectrum(g))[-2])
    traj = [k - lam2]
    lams, evals = [], 0
    steps = max(budget // t, 0)
    for lvl in range(t):
        s, top, _radius = best_signing_batched(
            g, batch=batch, steps=steps, iters=iters, seed=seed + 7 * lvl,
            objective="gap")
        evals += 2 * batch if steps > 0 else batch
        g = two_lift(g, s)
        lams.append(top)
        lam2 = max(lam2, top)
        traj.append(k - lam2)
    g.name = f"xpander({n},{k})"
    g.meta["lift_lams"] = lams
    g.meta["k"] = k
    g.meta["seed"] = seed
    return g, traj, evals


@obs.traced("synthesis/rewire_search", phase="execute")
def rewire_search(n: int, k: int, budget: int = DEFAULT_REWIRE_BUDGET,
                  batch: int = DEFAULT_BATCH, seed: int = 0,
                  iters: int = 160, swap_fraction: float = 0.05
                  ) -> Tuple[Topology, List[float], int]:
    """Hill-climb the double-edge-swap Markov chain toward maximum rho2.

    Starts from a random k-regular graph; each round proposes ``batch``
    candidates (each ``swap_fraction * m`` swaps away from the incumbent) and
    scores incumbent + candidates in ONE batched Laplacian Lanczos solve,
    moving to the best.  ``budget`` is the total candidate evaluations
    (rounds = budget // (batch + 1)).  Reaches any (n, k) with n*k even —
    the sizes a power-of-two lift tower cannot hit.  Returns (topology,
    rho2 trajectory, exact evaluations).
    """
    from .topologies import random_regular

    if (n * k) % 2 or n <= k:
        raise ValueError(f"no {k}-regular graph on {n} vertices")
    rng = np.random.default_rng(seed)
    g = random_regular(n, k, seed=seed)
    edges = g.edges
    swaps = max(1, int(round(swap_fraction * edges.shape[0])))
    rounds = max(budget // (batch + 1), 1)
    rho2_cur = float(_batched_rho2_edges(n, [edges], iters, seed)[0])
    traj = [rho2_cur]
    evals = 1
    for rnd in range(rounds):
        cands = [double_edge_swaps(edges, swaps, rng) for _ in range(batch)]
        vals = _batched_rho2_edges(n, [edges] + cands, iters, seed + 1 + rnd)
        evals += batch + 1
        best = int(np.argmax(vals))
        if best > 0:
            edges = cands[best - 1]
        rho2_cur = float(vals[best])
        traj.append(rho2_cur)
    topo = Topology(f"rewired({n},{k})", n, edges,
                    meta=dict(k=k, seed=seed, swaps_per_candidate=swaps))
    return topo, traj, evals


def synthesize(n: int, k: int, method: str = "lift",
               budget: Optional[int] = None, batch: int = DEFAULT_BATCH,
               seed: int = 0, iters: Optional[int] = None) -> SynthesisResult:
    """Design a k-regular n-vertex topology with maximum spectral gap.

    ``method="lift"`` grows a Bilu–Linial 2-lift tower (needs n = n0 * 2^t);
    ``method="rewire"`` runs the degree-preserving double-edge-swap search
    (any n*k even).  ``budget`` scales search effort: total SA flip steps
    (lift, default 2400) or total candidate evaluations (rewire, default
    288).  Deterministic in ``seed``.  The returned
    :class:`SynthesisResult` carries the measured rho2 (re-verified on the
    final graph), the per-stage rho2 trajectory, and the achieved fraction
    of the Ramanujan-bound gap ``k - 2 sqrt(k-1)``.
    """
    if k < 3:
        raise ValueError("synthesis needs radix k >= 3")
    t0 = time.time()
    if method == "lift":
        topo, traj, evals = lift_search(
            n, k, budget=DEFAULT_LIFT_BUDGET if budget is None else budget,
            batch=batch, seed=seed, iters=iters or 90)
    elif method == "rewire":
        topo, traj, evals = rewire_search(
            n, k, budget=DEFAULT_REWIRE_BUDGET if budget is None else budget,
            batch=batch, seed=seed, iters=iters or 160)
    else:
        raise ValueError(f"unknown synthesis method {method!r} "
                         "(known: 'lift', 'rewire')")
    rho2 = S.algebraic_connectivity(topo, seed=seed)
    opt = B.ramanujan_rho2(k)
    return SynthesisResult(
        topo=topo, method=method, n=topo.n, k=k, rho2=rho2,
        ramanujan_rho2=opt, gap_fraction=rho2 / opt, trajectory=traj,
        evaluations=evals, seconds=time.time() - t0)


# --------------------------------------------------------------------------
# first-class registry families: designed topologies survey like built ones
# --------------------------------------------------------------------------

def _cf_xpander(n: int, k: int, seed: int = 0,
                budget: int = DEFAULT_LIFT_BUDGET) -> dict:
    return dict(nodes=n, radix=k)


def _cf_rewired(n: int, k: int, seed: int = 0,
                budget: int = DEFAULT_REWIRE_BUDGET) -> dict:
    return dict(nodes=n, radix=k)


@register("xpander", params=dict(n=int, k=int, seed=int, budget=int),
          defaults=dict(seed=0, budget=DEFAULT_LIFT_BUDGET),
          closed_forms=_cf_xpander, default_instance="xpander(32,4,0,160)")
def xpander(n: int, k: int, seed: int = 0,
            budget: int = DEFAULT_LIFT_BUDGET) -> Topology:
    """Lift-synthesized expander: best-signed Bilu–Linial 2-lift tower at (n, k)."""
    res = synthesize(n, k, method="lift", budget=budget, seed=seed)
    res.topo.meta["synthesis"] = res.to_dict()
    return res.topo


@register("rewired", params=dict(n=int, k=int, seed=int, budget=int),
          defaults=dict(seed=0, budget=DEFAULT_REWIRE_BUDGET),
          closed_forms=_cf_rewired, default_instance="rewired(40,4,0,80)")
def rewired(n: int, k: int, seed: int = 0,
            budget: int = DEFAULT_REWIRE_BUDGET) -> Topology:
    """Rewire-synthesized expander: double-edge-swap rho2 hill-climb at (n, k)."""
    res = synthesize(n, k, method="rewire", budget=budget, seed=seed)
    res.topo.meta["synthesis"] = res.to_dict()
    return res.topo
