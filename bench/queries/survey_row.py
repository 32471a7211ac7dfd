"""Query kind ``survey_row``: one datacenter survey row through ``repro.api.survey``.

Cell parameters: ``columns`` (the survey's column set), ``sources`` (sampled
BFS sources, so ``sample_fraction = sources / n``), ``pattern`` (the traffic
pattern) and ``lanczos_iters`` (m).  Each query is one user call on the
cell's prebuilt topology, with a fresh ``Analysis`` as every instance of a
user's survey gets::

    survey([Analysis(topo, seed=s, lanczos_iters=m)], columns,
           routing=dict(pattern=..., sample_fraction=sources / n, seed=s))

The answer kept for the check is read from that ``Analysis``'s own caches
(unrounded): rho_2, the sampled routing result and the traffic result.

The check recomputes one query drawn from the run's seed with the plain
references of ``bench/plain.py`` from the topology's edge list alone:

* ``rho2_gap`` — |rho_2 - rho_2 of a float64 Lanczos run of the same m steps
  from the same start vector on the ones-deflated adjacency|;
* ``hops_mismatch`` — ordered (source, target) pairs whose hop count differs
  (the hop histogram: it carries ``diameter_lb`` and ``avg_hops``);
* ``paths_mismatch`` — |total minimal-path count over the sampled pairs -
  the reference's|, an integer below 2^53 on both sides;
* ``load_gap`` — relative gap of the max directed-link ECMP load;
* ``thpt_gap`` — relative gap of the saturation throughput (1 / the
  bootstrap upper bound of the max load).
"""
from __future__ import annotations

import concurrent.futures
import os
import pathlib
import sys

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
import plain  # noqa: E402

#: candidate hot slots and replicates of the max-load bootstrap bound, and
#: the offsets of its seed and of the source-sampling seed, as the program
#: documents them for sampled uniform traffic
UCB_CANDIDATES, UCB_REPLICATES, UCB_CONFIDENCE, UCB_SEED_OFFSET = \
    256, 200, 0.95, 0x10AD


def spec_for(config: dict, seed: int) -> str:
    """The registry spec of this run's topology (``{seed}`` filled)."""
    ss = np.random.SeedSequence([seed % 2 ** 64, 0x5EC])
    return config["spec"].format(seed=int(ss.generate_state(1)[0] % 2 ** 20))


def setup(config: dict, cell: dict, seed: int) -> dict:
    from repro.api import build

    topo = build(spec_for(config, seed))
    state = dict(n=topo.n, k=int(topo.radix), edges=np.array(topo.edges),
                 cell=cell, program=dict(topo=topo))
    query(state, 2 ** 30 + 1)            # warm-up: every shape of the query
    return state


def query(state: dict, qseed: int) -> dict:
    from repro.api import Analysis, survey

    cell, topo = state["cell"], state["program"]["topo"]
    a = Analysis(topo, seed=qseed, lanczos_iters=cell["lanczos_iters"])
    frac = cell["sources"] / state["n"]
    survey([a], cell["columns"], routing=dict(
        pattern=cell["pattern"], sample_fraction=frac, seed=qseed))
    r = a.routing(sample_fraction=frac, seed=qseed)
    t = a.traffic(cell["pattern"], sample_fraction=frac, seed=qseed)
    return dict(graphs=1, **_summary(qseed, a.rho2, r.dist, r.sources,
                                     r.sigma, t.max_link_load,
                                     t.saturation_throughput))


def _summary(qseed, rho2, dist, sources, sigma, max_load, thpt) -> dict:
    dist = np.asarray(dist)
    off = dist >= 0
    off[np.arange(len(sources)), sources] = False
    return dict(seed=int(qseed), rho2=float(rho2),
                hist=np.bincount(dist[off]).tolist(),
                paths=float(np.asarray(sigma)[off].sum()),
                max_load=float(max_load), thpt=float(thpt))


def work(state: dict) -> list:
    """(B, n, k, m, signed) of each Lanczos solve one query makes."""
    return [(1, state["n"], state["k"], state["cell"]["lanczos_iters"],
             False)]


def reference(state: dict, qseed: int, rnd=None) -> dict:
    """The answer of query ``qseed`` from the plain references alone."""
    import jax
    import jax.numpy as jnp

    cell, n, k = state["cell"], state["n"], state["k"]
    table, _ = plain.neighbor_table(n, state["edges"])
    v0 = np.asarray(jax.random.normal(jax.random.PRNGKey(qseed), (n,),
                                      dtype=jnp.float32), dtype=np.float64)
    _, lmax = plain.lanczos_ritz(plain.adjacency_op(table, rnd),
                                 v0 - v0.mean(), cell["lanczos_iters"], rnd)
    S = cell["sources"]
    srcs = np.sort(np.random.default_rng(qseed).choice(n, size=S,
                                                       replace=False))

    def demand(s):
        row = np.full(n, 1.0 / (n - 1))
        row[s] = 0.0
        return row

    # numpy releases the interpreter lock in these gathers: split the
    # sources over a few threads
    chunks = np.array_split(srcs, min(8, os.cpu_count() or 1))
    with concurrent.futures.ThreadPoolExecutor(len(chunks)) as pool:
        parts = list(pool.map(
            lambda c: plain.bfs_paths_loads(table, c, demand, rnd), chunks))
    dist, sigma, ratio = (np.concatenate([p[i] for p in parts])
                          for i in range(3))
    loads = sum(p[3] for p in parts)
    scale = n / S
    scaled = loads * scale
    max_load = float(scaled.max())
    width = table.shape[1]
    cand = np.argsort(scaled.ravel())[-UCB_CANDIDATES:]
    rows, slots = cand // width, cand % width
    pad = table < 0
    nbr = np.where(pad, np.arange(n)[:, None], table)
    C = np.stack([plain.slot_loads(nbr, pad, dist[i], sigma[i], ratio[i],
                                   rows)[np.arange(cand.size), slots]
                  for i in range(S)])
    rng = np.random.default_rng(qseed + UCB_SEED_OFFSET)
    idx = rng.integers(0, S, size=(UCB_REPLICATES, S))
    rep_max = scale * C[idx].sum(axis=1).max(axis=1)
    ucb = max(float(np.quantile(rep_max, UCB_CONFIDENCE)), max_load)
    return _summary(qseed, k - lmax, dist, srcs, sigma, max_load, 1.0 / ucb)


def compare(got: dict, want: dict) -> dict:
    """The numbers the check holds against the cell's limits."""
    h1, h2 = np.asarray(got["hist"]), np.asarray(want["hist"])
    size = max(h1.size, h2.size)
    h1 = np.pad(h1, (0, size - h1.size))
    h2 = np.pad(h2, (0, size - h2.size))
    return dict(
        rho2_gap=abs(got["rho2"] - want["rho2"]),
        hops_mismatch=int(np.abs(h1 - h2).sum()),
        paths_mismatch=abs(got["paths"] - want["paths"]),
        load_gap=abs(got["max_load"] - want["max_load"]) / want["max_load"],
        thpt_gap=abs(got["thpt"] - want["thpt"]) / want["thpt"])


def check(state: dict, answers: list, rng: np.random.Generator) -> dict:
    """Compare one answer of the window, drawn from ``rng``."""
    if not answers:
        return {}
    got = answers[int(rng.integers(len(answers)))]
    return compare(got, reference(state, got["seed"]))
