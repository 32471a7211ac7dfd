"""Query kind ``fault_sweep``: one link-fault sweep through ``Analysis.fault_sweep``.

Cell parameters: ``rates`` (link-fault rates), ``samples`` (Monte-Carlo
samples per rate), ``lanczos_iters`` (m).  Set-up builds the configuration's
topology and one ``Analysis``, reads its healthy rho_2 (as the first sweep
of a user's session would), and runs a one-rate sweep, which compiles the
batched solve the window replays.  Each query is one user call::

    Analysis(topo).fault_sweep(rates, model="link", samples=B, seed=s,
                               iters=m)

Every rate is one batched Laplacian Lanczos solve of B degraded samples.

The check recomputes one query drawn from the run's seed from the edge list
alone: per rate, the same iid link failures (``numpy.random.default_rng(s +
7919 i).choice(m_edges, round(rate * m_edges), replace=False)`` for sample
i, as the program documents its Monte-Carlo draw), each degraded graph's
rho_2 by a float64 Lanczos run of the same m steps from the same start
vector on the shifted Laplacian (``bench/plain.py``), and its connected
components by scipy:

* ``rho2_gap`` — the largest gap, over rates, of the mean, least and
  largest rho_2 of the rate's samples;
* ``connectivity_mismatch`` — samples whose connectedness differs.
"""
from __future__ import annotations

import pathlib
import sys

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
import plain  # noqa: E402

#: the sample-seed stride of the program's Monte-Carlo draw
SAMPLE_STRIDE = 7919


def setup(config: dict, cell: dict, seed: int) -> dict:
    from repro.api import Analysis, build

    topo = build(config["spec"])
    a = Analysis(topo, lanczos_iters=cell["lanczos_iters"])
    a.rho2                               # the session's healthy rho_2
    width = int(np.bincount(topo.edges.reshape(-1), minlength=topo.n).max())
    state = dict(n=topo.n, k=width, edges=np.array(topo.edges), cell=cell,
                 program=dict(analysis=a))
    a.fault_sweep(rates=cell["rates"][:1], model="link",
                  samples=cell["samples"], seed=2 ** 30 + 1,
                  iters=cell["lanczos_iters"])     # warm-up: the solve's shape
    return state


def query(state: dict, qseed: int) -> dict:
    cell = state["cell"]
    res = state["program"]["analysis"].fault_sweep(
        rates=cell["rates"], model="link", samples=cell["samples"],
        seed=qseed, iters=cell["lanczos_iters"])
    return dict(graphs=sum(r["samples"] for r in res.rows), seed=int(qseed),
                rows=[dict(rate=r["rate"], samples=r["samples"],
                           rho2_mean=r["rho2_mean"], rho2_min=r["rho2_min"],
                           rho2_max=r["rho2_max"],
                           connected=round(r["connectivity_prob"]
                                           * r["samples"]))
                      for r in res.rows])


def work(state: dict) -> list:
    """(B, n, k, m, signed) of each Lanczos solve one query makes."""
    cell = state["cell"]
    return [(cell["samples"], state["n"], state["k"], cell["lanczos_iters"],
             False) for _ in cell["rates"]]


def _sample(n: int, edges: np.ndarray, rate: float, seed: int, v0, m: int,
            rnd):
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components

    t = int(round(rate * len(edges)))
    keep = np.ones(len(edges), dtype=bool)
    if t:
        keep[np.random.default_rng(seed).choice(len(edges), size=t,
                                                replace=False)] = False
    e = edges[keep]
    table, deg = plain.neighbor_table(n, e)
    lmin, _ = plain.lanczos_ritz(plain.laplacian_op(table, deg, rnd), v0, m,
                                 rnd)
    adj = coo_matrix((np.ones(len(e)), (e[:, 0], e[:, 1])), shape=(n, n))
    comps, _ = connected_components(adj, directed=False)
    return max(lmin, 0.0), comps == 1


def reference(state: dict, qseed: int, rnd=None) -> dict:
    """The answer of query ``qseed`` from the plain references alone."""
    import jax
    import jax.numpy as jnp

    cell, n, edges = state["cell"], state["n"], state["edges"]
    B, m = cell["samples"], cell["lanczos_iters"]
    v0s = np.asarray(jax.random.normal(jax.random.PRNGKey(qseed), (B, n),
                                       dtype=jnp.float32), dtype=np.float64)
    rows = []
    for rate in cell["rates"]:
        out = [_sample(n, edges, rate, qseed + SAMPLE_STRIDE * i, v0s[i], m,
                       rnd) for i in range(B)]
        rho2 = np.array([o[0] for o in out])
        rows.append(dict(rate=rate, samples=B, rho2_mean=float(rho2.mean()),
                         rho2_min=float(rho2.min()),
                         rho2_max=float(rho2.max()),
                         connected=int(sum(o[1] for o in out))))
    return dict(seed=int(qseed), rows=rows)


def compare(got: dict, want: dict) -> dict:
    """The numbers the check holds against the cell's limits."""
    gap, mismatch = 0.0, 0
    if len(got["rows"]) != len(want["rows"]):
        return dict(rho2_gap=float("inf"), connectivity_mismatch=float("inf"))
    for g, w in zip(got["rows"], want["rows"]):
        for key in ("rho2_mean", "rho2_min", "rho2_max"):
            gap = max(gap, abs(g[key] - w[key]))
        mismatch += abs(g["connected"] - w["connected"])
    return dict(rho2_gap=gap, connectivity_mismatch=mismatch)


def check(state: dict, answers: list, rng: np.random.Generator) -> dict:
    """Compare one answer of the window, drawn from ``rng``."""
    if not answers:
        return {}
    got = answers[int(rng.integers(len(answers)))]
    return compare(got, reference(state, got["seed"]))
