"""Fault models + batched degraded-spectral sweeps (repro.core.faults)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro import obs
from repro.api import Analysis, survey
from repro.core import faults as F
from repro.core import spectral as S
from repro.core import topologies as T
from repro.core.graphs import Topology


# --------------------------------------------------------------------------
# fault models
# --------------------------------------------------------------------------

def test_random_link_faults_seed_deterministic():
    g = T.torus(8, 2)
    a = F.random_link_faults(g, 0.1, seed=7)
    b = F.random_link_faults(g, 0.1, seed=7)
    c = F.random_link_faults(g, 0.1, seed=8)
    assert np.array_equal(a.failed_links, b.failed_links)
    assert not np.array_equal(a.failed_links, c.failed_links)
    assert a.n_failed_links == round(0.1 * g.m)


def test_random_node_faults_include_incident_links():
    g = T.hypercube(5)
    sc = F.random_node_faults(g, 0.2, seed=1)
    assert sc.n_failed_nodes == round(0.2 * g.n)
    dead = set(sc.failed_nodes.tolist())
    expect = {i for i, (u, v) in enumerate(g.edges)
              if u in dead or v in dead}
    assert set(sc.failed_links.tolist()) == expect


def test_adversarial_degree_attack_removes_claimed_nodes():
    """The degree adversary kills exactly the highest-degree routers, and the
    degraded graph contains none of their links."""
    g = T.fat_tree(3, 2)                      # genuinely irregular degrees
    deg = g.degrees(include_loops=False)
    sc = F.adversarial_degree_attack(g, 0.1)
    f = sc.n_failed_nodes
    assert f == round(0.1 * g.n)
    # every failed node's degree >= every survivor's degree
    alive = np.setdiff1d(np.arange(g.n), sc.failed_nodes)
    assert deg[sc.failed_nodes].min() >= deg[alive].max() - 1e-9
    d = F.apply_faults(g, sc)
    assert d.n == g.n - f
    # survivors' induced edge count matches the claimed removal exactly
    dead = np.zeros(g.n, dtype=bool)
    dead[sc.failed_nodes] = True
    kept = (~dead[g.edges[:, 0]]) & (~dead[g.edges[:, 1]])
    assert d.m == int(kept.sum()) == g.m - sc.n_failed_links


def test_adversarial_spectral_attack_removes_top_fiedler_edges():
    g = T.torus(8, 2)
    f = S.fiedler_vector(g)
    sc = F.adversarial_spectral_attack(g, 0.1, fiedler=f)
    energy = (f[g.edges[:, 0]] - f[g.edges[:, 1]]) ** 2
    t = sc.n_failed_links
    assert t == round(0.1 * g.m)
    # the claimed edge set carries at least as much Fiedler energy as any
    # other t-subset (i.e. it is the top-t set, modulo ties)
    claimed = np.sort(energy[sc.failed_links])
    top = np.sort(energy)[-t:]
    assert np.allclose(claimed, top)
    d = F.apply_faults(g, sc)
    assert d.m == g.m - t
    # and it is spectrally more damaging than a random cut of the same size
    rand = F.apply_faults(g, F.random_link_faults(g, 0.1, seed=0))
    assert S.laplacian_spectrum(d)[1] <= S.laplacian_spectrum(rand)[1] + 1e-9


def test_apply_faults_strips_healthy_only_meta():
    from repro.api import build

    g = build("torus(8,2)")                   # registry sets the tags
    assert g.meta.get("vertex_transitive")
    d = F.apply_faults(g, F.random_link_faults(g, 0.1, seed=0))
    assert "vertex_transitive" not in d.meta and "spec" not in d.meta
    assert d.meta["fault"]["kind"] == "link"


# --------------------------------------------------------------------------
# batched degraded solve vs dense oracle
# --------------------------------------------------------------------------

def test_stacked_operands_apply_exact_laplacian():
    g = T.fat_tree(3, 2)                      # irregular + loop-free
    scen = [F.random_link_faults(g, 0.15, seed=i) for i in range(4)]
    degraded = [F.apply_faults(g, s) for s in scen]
    tabs, ws, degs = F.stacked_operands(degraded)
    rng = np.random.default_rng(0)
    for i, d in enumerate(degraded):
        x = rng.normal(size=d.n)
        lx = degs[i] * x - (x[tabs[i]].sum(axis=1) + ws[i] * x)
        assert np.abs(lx - d.laplacian() @ x).max() < 1e-9


def test_batched_rho2_matches_dense_oracle():
    g = T.torus(8, 2)
    degraded = [F.apply_faults(g, F.random_link_faults(g, 0.12, seed=i))
                for i in range(8)]
    tabs, ws, degs = F.stacked_operands(degraded)
    got = S.rho2_laplacian_batched(tabs, ws, degs, iters=120, seed=0)
    want = np.array([S.laplacian_spectrum(d)[1] for d in degraded])
    assert np.abs(got - want).max() < 1e-3


def test_batched_rho2_flags_disconnection():
    """A sample cut into two components must report rho2 ~ 0."""
    g = T.cycle(32)
    sc = F.FaultScenario(kind="link", rate=2 / 32, seed=0,
                         failed_links=np.array([0, 16]),
                         failed_nodes=np.empty(0, dtype=np.int64))
    d = F.apply_faults(g, sc)
    assert F.connected_component_count(d.n, d.edges) == 2
    tabs, ws, degs = F.stacked_operands([d])
    got = S.rho2_laplacian_batched(tabs, ws, degs, iters=64, seed=0)
    assert got[0] < 1e-4


# --------------------------------------------------------------------------
# one shared neighbour table for a stack: the row-gather path
# --------------------------------------------------------------------------

def _link_stack():
    g = T.torus(8, 2)
    return [F.apply_faults(g, F.random_link_faults(g, 0.15, seed=i))
            for i in range(6)]


def _looped_cycle_stack():
    """A cycle with a loop-weighted vertex, links cut at random."""
    n = 12
    loops = np.zeros(n)
    loops[3] = 2.0
    g = Topology("looped-cycle", n, np.array([(i, (i + 1) % n)
                                              for i in range(n)]), loops=loops)
    return [F.apply_faults(g, F.random_link_faults(g, 0.2, seed=i))
            for i in range(5)]


def _multigraph_stack():
    """Repeated neighbours: one edge doubled and one tripled, so a table row
    holds one vertex two or three times."""
    n = 10
    edges = [(i, (i + 1) % n) for i in range(n)] + [(0, 1), (4, 5), (4, 5)]
    g = Topology("multi-cycle", n, np.array(edges))
    return [F.apply_faults(g, F.random_link_faults(g, 0.2, seed=i))
            for i in range(5)]


def _node_stack():
    g = T.torus(8, 2)
    return [F.apply_faults(g, F.random_node_faults(g, 0.1, seed=i))
            for i in range(4)]


def _wide_stack():
    """Samples of one order with unrelated neighbourhoods: the union of a
    vertex's neighbours over the stack outgrows the table's width."""
    g = T.cycle(16)
    perms = [np.random.default_rng(i).permutation(16) for i in range(4)]
    return [Topology(f"relabelled-{i}", 16, p[g.edges])
            for i, p in enumerate(perms)]


SHARED_STACKS = {"torus-links": _link_stack,
                 "looped-cycle": _looped_cycle_stack,
                 "multigraph": _multigraph_stack}


@pytest.mark.parametrize("stack", sorted(SHARED_STACKS))
def test_shared_table_applies_the_per_sample_operator(stack):
    degraded = SHARED_STACKS[stack]()
    tabs, ws, degs = F.stacked_operands(degraded)
    width, shared = S._shared_table(tabs, ws)
    assert shared is not None and width <= tabs.shape[2]
    U, C, w2 = shared
    rng = np.random.default_rng(1)
    for b, d in enumerate(degraded):
        x = rng.normal(size=d.n)
        per_sample = x[tabs[b]].sum(axis=1) + ws[b] * x
        one_table = (C[b] * x[U]).sum(axis=1) + w2[b] * x
        assert np.abs(one_table - per_sample).max() < 1e-6
        lx = degs[b] * x - one_table
        assert np.abs(lx - d.laplacian() @ x).max() < 1e-6


@pytest.mark.parametrize("stack", sorted(SHARED_STACKS))
def test_shared_table_solve_matches_dense_oracle(stack):
    degraded = SHARED_STACKS[stack]()
    tabs, ws, degs = F.stacked_operands(degraded)
    got = S.rho2_laplacian_batched(tabs, ws, degs, iters=40, seed=0,
                                   backend="ref")
    want = np.array([max(S.laplacian_spectrum(d)[1], 0.0) for d in degraded])
    assert np.abs(got - want).max() < 1e-3


@pytest.mark.parametrize("stack,shared", [("torus-links", True),
                                          ("node-faults", False),
                                          ("wider-than-k", False)])
def test_batched_solve_counts_its_gather(stack, shared):
    make = dict(SHARED_STACKS, **{"node-faults": _node_stack,
                                  "wider-than-k": _wide_stack})[stack]
    tabs, ws, degs = F.stacked_operands(make())
    before = obs.counters("lanczos/")
    S.rho2_laplacian_batched(tabs, ws, degs, iters=20, seed=0, backend="ref")
    delta = obs.counter_delta(before, "lanczos/")
    assert delta.get("lanczos/shared_table_tiles", 0) == int(shared)
    assert delta.get("lanczos/per_sample_tiles", 0) == int(not shared)


def _cut_cycle_stack():
    """A cycle cut into two paths in three samples and into one path in a
    fourth: Lanczos on a 32-vertex graph breaks down before m = 40."""
    g = T.cycle(32)
    return [F.apply_faults(g, F.FaultScenario(
        kind="link", rate=len(cut) / 32, seed=0, failed_links=np.array(cut),
        failed_nodes=np.empty(0, dtype=np.int64)))
        for cut in ([0, 16], [3, 20], [5, 6], [7])]


@pytest.mark.parametrize("stack,shared", [("torus-links", True),
                                          ("node-faults", False),
                                          ("cut-cycle", True)])
def test_batched_scan_matches_single_vector_scan(stack, shared):
    """The block recurrence gives each sample the single-vector scan's
    (alpha, beta) on the same operator, breakdown zeros included, and the
    batched rho_2 still matches the dense oracle."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import spmv as KS

    degraded = dict(SHARED_STACKS, **{"node-faults": _node_stack,
                                      "cut-cycle": _cut_cycle_stack})[stack]()
    tabs, ws, degs = F.stacked_operands(degraded)
    B, n, _ = tabs.shape
    m = 40
    v0s = jax.random.normal(jax.random.PRNGKey(3), (B, n), dtype=jnp.float32)
    degs32 = jnp.asarray(degs, dtype=jnp.float32)
    if shared:
        U, C, w2 = S._shared_table(tabs, ws)[1]
        alphas, betas = S._lap_lanczos_batched(
            jnp.asarray(U), jnp.asarray(w2), degs32, v0s, m, backend="ref",
            counts=jnp.asarray(C))
        operands = [(U, w2[b], C[b]) for b in range(B)]
    else:
        alphas, betas = S._lap_lanczos_batched(
            jnp.asarray(tabs), jnp.asarray(ws, dtype=jnp.float32), degs32,
            v0s, m, backend="ref")
        operands = [(tabs[b], ws[b], None) for b in range(B)]
    assert alphas.shape == betas.shape == (B, m)
    for b, (tab, lw, cnt) in enumerate(operands):
        deg = degs32[b]

        def op(x):
            y = KS.spmv(x, jnp.asarray(tab), jnp.asarray(lw, jnp.float32),
                        signs=None if cnt is None else jnp.asarray(cnt),
                        backend="ref")
            return deg * x - y + (jnp.max(deg) + 2.0) * jnp.mean(x)

        a1, b1, _ = S._lanczos_scan(op, v0s[b], m)
        a1, b1 = np.asarray(a1), np.asarray(b1)
        assert np.abs(np.asarray(alphas[b]) - a1).max() \
            <= 1e-5 * np.abs(a1).max()
        assert np.abs(np.asarray(betas[b]) - b1).max() \
            <= 1e-5 * np.abs(b1).max()
        assert np.array_equal(np.asarray(betas[b]) == 0.0, b1 == 0.0)

    before = obs.counters("lanczos/")
    got = S.rho2_laplacian_batched(tabs, ws, degs, iters=m, seed=0,
                                   backend="ref")
    delta = obs.counter_delta(before, "lanczos/")
    assert (delta.get("lanczos/breakdown_truncations", 0) > 0) == \
        (stack == "cut-cycle")
    want = np.array([max(S.laplacian_spectrum(d)[1], 0.0) for d in degraded])
    assert np.abs(got - want).max() < 1e-3


def test_link_sweep_second_rate_does_not_retrace():
    g = T.torus(8, 2)
    F.fault_sweep(g, rates=(0.05,), samples=8, seed=0, iters=30)
    before = obs.counters()
    F.fault_sweep(g, rates=(0.25,), samples=8, seed=1, iters=30)
    delta = obs.counter_delta(before)
    assert "jit_trace/lanczos_scan" not in delta
    assert delta.get("lanczos/shared_table_tiles", 0) == 1


SHARDED = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax
import numpy as np
from repro import obs
from repro.core import faults as F, spectral as S, topologies as T

g = T.torus(8, 2)
degraded = [F.apply_faults(g, F.random_link_faults(g, 0.15, seed=i))
            for i in range(8)]
tabs, ws, degs = F.stacked_operands(degraded)
before = obs.counters("lanczos/")
sharded = S.rho2_laplacian_batched(tabs, ws, degs, iters=60, backend="ref")
single = S.rho2_laplacian_batched(tabs, ws, degs, iters=60, backend="ref",
                                  devices=jax.devices()[:1])
print(json.dumps(dict(devices=len(jax.devices()),
                      diff=float(np.abs(sharded - single).max()),
                      counters=obs.counter_delta(before, "lanczos/"))))
"""


def test_sharded_shared_table_solve_matches_one_device():
    """Four host devices: the replicated table and the batch split 2 a
    device give the one-device answer."""
    env = dict(os.environ, PYTHONPATH=os.path.abspath("src"),
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", SHARDED], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["devices"] == 4
    assert res["counters"].get("lanczos/shared_table_tiles") == 2
    assert res["diff"] <= 1e-5, res


def test_connected_component_count_matches_networkx():
    import networkx as nx

    g = T.torus(6, 2)
    d = F.apply_faults(g, F.random_link_faults(g, 0.4, seed=5))
    want = nx.number_connected_components(d.to_networkx())
    assert F.connected_component_count(d.n, d.edges) == want


# --------------------------------------------------------------------------
# sweeps: determinism + analytic bounds
# --------------------------------------------------------------------------

def test_fault_sweep_seed_deterministic():
    g = T.hypercube(6)
    a = F.fault_sweep(g, rates=(0.05, 0.15), samples=8, seed=3, iters=80)
    b = F.fault_sweep(g, rates=(0.05, 0.15), samples=8, seed=3, iters=80)
    c = F.fault_sweep(g, rates=(0.05, 0.15), samples=8, seed=4, iters=80)
    for ra, rb in zip(a.rows, b.rows):
        assert ra["rho2_mean"] == rb["rho2_mean"]
        assert ra["connectivity_prob"] == rb["connectivity_prob"]
    assert any(ra["rho2_mean"] != rc["rho2_mean"]
               for ra, rc in zip(a.rows, c.rows))


def test_interlacing_bound_upper_bounds_sampled_gap():
    """Link removal only subtracts PSD terms from L, so every sampled
    degraded rho2 must sit at or below the healthy value."""
    for g in (T.torus(8, 2), T.slimfly(5)):
        sweep = F.fault_sweep(g, rates=(0.02, 0.1, 0.25), model="link",
                              samples=16, seed=0, iters=100)
        for row in sweep.rows:
            assert row["interlacing_rho2_ub"] == pytest.approx(
                sweep.rho2_healthy)
            assert row["rho2_max"] <= row["interlacing_rho2_ub"] + 1e-3
            assert row["rho2_min"] >= row["weyl_rho2_lb"] - 1e-3


def test_fault_sweep_single_batched_solve_per_rate():
    g = T.torus(8, 2)
    sweep = F.fault_sweep(g, rates=(0.05, 0.1, 0.2), samples=32, seed=0,
                          iters=60)
    assert sweep.batched_solves == 3          # one vmapped call per rate
    assert all(r["samples"] == 32 for r in sweep.rows)


def test_fault_sweep_rejects_unknown_model():
    with pytest.raises(ValueError, match="unknown fault model"):
        F.fault_sweep(T.petersen(), model="meteor")


# --------------------------------------------------------------------------
# api surface
# --------------------------------------------------------------------------

def test_analysis_fault_sweep_uses_cached_healthy_rho2():
    a = Analysis("torus(8,2)")
    sweep = a.fault_sweep(rates=(0.1,), samples=4)
    assert sweep.rho2_healthy == pytest.approx(a.rho2)
    assert "rate" in sweep.rows[0] and "fault model" in sweep.report()


def test_survey_faults_appends_resilience_columns():
    res = survey(["torus(6,2)", "petersen"], faults=dict(rate=0.1, samples=4))
    for col in ("fault_rate", "rho2_degraded", "rho2_retention",
                "connectivity_prob", "bw_fiedler_lb_degraded"):
        assert col in res.columns
        assert all(col in r for r in res.rows)
    assert all(r["fault_rate"] == 0.1 for r in res.rows)
    assert all(r["rho2_degraded"] <= r["rho2"] + 1e-3 for r in res.rows)
