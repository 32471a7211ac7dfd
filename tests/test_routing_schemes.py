"""Non-minimal & adaptive routing schemes: Valiant/UGAL/ksp closed forms,
the MCF throughput ceiling, spmv-backend invariance of the canonical
adversarial demand, and the sampled-estimator bias fixes."""
import numpy as np
import pytest

from repro.core import topologies as T
from repro.core.ramanujan import lps
from repro.core.routing import analyze_routing, reverse_slot_index
from repro.core.synthesis import xpander
from repro.core.spectral import canonical_fiedler
from repro.core.traffic import (ROUTING_SCHEMES, demand_matrix,
                                evaluate_traffic, ksp_link_loads,
                                mcf_throughput_ub, scheme_link_loads)

HAVE_SCIPY = True
try:                                    # mirrors the traffic-module guard
    import scipy  # noqa: F401
except ImportError:                     # pragma: no cover - scipy-less CI
    HAVE_SCIPY = False

needs_scipy = pytest.mark.skipif(
    not HAVE_SCIPY, reason="scipy not installed: MCF LP ceiling unavailable")


def _uniform_served(g, routing):
    D = demand_matrix("uniform", g.n)
    return np.where(routing.dist >= 0, D, 0.0)


# --------------------------------------------------------------------------
# Valiant closed forms
# --------------------------------------------------------------------------

def test_valiant_complete_graph_closed_form():
    """K_n: every link carries exactly 2/n under uniform Valiant (one
    leg in, one leg out through every intermediate), so saturation
    throughput is n/2 — below minimal ECMP's (n-1)/2... Valiant pays its
    2x tax even where it buys nothing."""
    n = 12
    g = T.complete(n)
    r = analyze_routing(g)
    t = evaluate_traffic(g, "uniform", scheme="valiant", routing=r)
    live = g.gather_operands()[0] >= 0
    np.testing.assert_allclose(t.link_loads[live], 2.0 / n, rtol=1e-5)
    assert t.saturation_throughput == pytest.approx(n / 2.0, rel=1e-5)


def test_valiant_cycle_loads_all_equal():
    """C_n is edge-transitive: uniform Valiant load is identical on every
    directed link."""
    g = T.cycle(10)
    t = evaluate_traffic(g, "uniform", scheme="valiant")
    table = g.gather_operands()[0]
    lv = t.link_loads[table >= 0]
    np.testing.assert_allclose(lv, lv[0], rtol=1e-5)


# --------------------------------------------------------------------------
# UGAL
# --------------------------------------------------------------------------

def test_ugal_reduces_to_minimal_under_uniform():
    """Uniform traffic spreads minimal load evenly, so UGAL's load
    comparison keeps every pair minimal and the loads are bit-identical
    to minimal ECMP."""
    for g in (T.hypercube(4), T.petersen(), T.slimfly(5)):
        r = analyze_routing(g)
        t_min = evaluate_traffic(g, "uniform", scheme="minimal", routing=r)
        t_ugal = evaluate_traffic(g, "uniform", scheme="ugal", routing=r)
        np.testing.assert_array_equal(t_min.link_loads, t_ugal.link_loads)
        assert t_min.saturation_throughput == t_ugal.saturation_throughput


def test_nonminimal_adversarial_no_worse_than_minimal_on_expanders():
    """The acceptance invariant at test scale: non-minimal routing recovers
    adversarial throughput on expander families.  (UGAL needs enough scale
    for its load estimate to pay off — lps(5,13) at n=120 is below that, so
    its UGAL leg is only asserted at bench scale on lps(13,5).)"""
    for g, check_ugal in ((lps(5, 13), False), (T.slimfly(5), True),
                          (xpander(64, 6, 0, 0), True)):
        r = analyze_routing(g)
        fiedler = canonical_fiedler(g)
        kw = dict(routing=r, fiedler=fiedler)
        t_min = evaluate_traffic(g, "adversarial", scheme="minimal", **kw)
        t_val = evaluate_traffic(g, "adversarial", scheme="valiant", **kw)
        assert t_val.saturation_throughput >= \
            t_min.saturation_throughput - 1e-9
        if check_ugal:
            t_ugal = evaluate_traffic(g, "adversarial", scheme="ugal", **kw)
            assert t_ugal.saturation_throughput >= \
                t_min.saturation_throughput - 1e-9


# --------------------------------------------------------------------------
# k-shortest-path ECMP
# --------------------------------------------------------------------------

@pytest.mark.parametrize("build", [T.petersen, lambda: T.hypercube(4),
                                   lambda: T.slimfly(5)],
                         ids=["petersen", "hypercube4", "slimfly5"])
@pytest.mark.parametrize("pattern", ["uniform", "bit_complement"])
def test_ksp_slack_zero_is_minimal(build, pattern):
    """slack=0 admits exactly the shortest paths with walk-count weights =
    ECMP's path-count weights (every shortest walk is a path)."""
    g = build()
    r = analyze_routing(g)
    t_min = evaluate_traffic(g, pattern, scheme="minimal", routing=r)
    t_ksp = evaluate_traffic(g, pattern, scheme="ksp", slack=0, routing=r)
    # minimal accumulates in f32, ksp in f64: equal to f32 roundoff
    np.testing.assert_allclose(t_min.link_loads, t_ksp.link_loads,
                               rtol=1e-5, atol=1e-6)
    assert t_min.saturation_throughput == pytest.approx(
        t_ksp.saturation_throughput, rel=1e-5)


def test_ksp_conserves_demand_and_spreads_load():
    """slack=1 serves the full demand (conservation) and cannot raise the
    peak load above minimal by more than the extra hops admit."""
    g = T.petersen()
    r = analyze_routing(g)
    t = evaluate_traffic(g, "adversarial", scheme="ksp", slack=1, routing=r,
                         fiedler=canonical_fiedler(g))
    assert t.conservation_error < 1e-6
    # detours can only lengthen the demand-weighted mean path
    t_min = evaluate_traffic(g, "adversarial", scheme="minimal", routing=r,
                             fiedler=canonical_fiedler(g))
    assert t.avg_hops >= t_min.avg_hops - 1e-9
    assert t.saturation_throughput > 0


def test_ksp_rejects_negative_slack():
    g = T.petersen()
    r = analyze_routing(g)
    served = _uniform_served(g, r)
    with pytest.raises(ValueError):
        ksp_link_loads(g.gather_operands()[0], r, served, slack=-1)


# --------------------------------------------------------------------------
# MCF throughput ceiling
# --------------------------------------------------------------------------

@needs_scipy
def test_mcf_complete_graph_exact():
    """K_n uniform: direct single-hop routing saturates every link at
    1/(n-1) per unit injection, so theta* = n-1 exactly."""
    n = 12
    ub = mcf_throughput_ub(T.complete(n))
    assert ub == pytest.approx(n - 1, rel=1e-6)


@needs_scipy
@pytest.mark.parametrize("build", [
    T.petersen, lambda: T.hypercube(4), lambda: T.cycle(10),
    lambda: T.torus(4, 2), lambda: T.slimfly(5),
    lambda: T.cube_connected_cycles(3), lambda: T.butterfly(2, 3),
    lambda: T.random_regular(48, 4, seed=0),
], ids=["petersen", "hypercube4", "cycle10", "torus4x2", "slimfly5",
        "ccc3", "butterfly2x3", "rr48"])
@pytest.mark.parametrize("pattern", ["uniform", "adversarial"])
def test_mcf_ub_dominates_every_scheme(build, pattern):
    """No routing scheme may beat the optimal-routing LP ceiling."""
    g = build()
    r = analyze_routing(g)
    fiedler = canonical_fiedler(g) if pattern == "adversarial" else None
    ub = mcf_throughput_ub(g, pattern, fiedler=fiedler)
    assert np.isfinite(ub) and ub > 0
    for scheme in ROUTING_SCHEMES:
        t = evaluate_traffic(g, pattern, scheme=scheme, routing=r,
                             fiedler=fiedler)
        assert t.saturation_throughput <= ub * (1 + 1e-6) + 1e-9, \
            (scheme, t.saturation_throughput, ub)


@needs_scipy
def test_mcf_grouping_only_loosens():
    """Merging commodities relaxes the LP: fewer groups => UB no smaller."""
    g = T.petersen()
    fine = mcf_throughput_ub(g, groups=g.n)
    coarse = mcf_throughput_ub(g, groups=2)
    assert coarse >= fine - 1e-9


def test_mcf_raises_without_scipy(monkeypatch):
    from repro.core import traffic as TR

    monkeypatch.setattr(TR, "_scipy_linprog", None)
    with pytest.raises(RuntimeError, match="scipy"):
        TR.mcf_throughput_ub(T.petersen())


# --------------------------------------------------------------------------
# backend invariance of the canonical adversarial demand (the PR-8 bugfix)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("build", [lambda: T.butterfly(2, 3),
                                   lambda: T.hypercube(4)],
                         ids=["butterfly", "hypercube"])
def test_adversarial_demand_backend_invariant(build):
    """Degenerate Fiedler eigenspaces (butterfly, hypercube) must yield the
    SAME canonical vector — hence bit-identical demand matrices and
    throughputs — whatever spmv backend or eigensolver produced rho2."""
    g = build()
    f = canonical_fiedler(g)
    D = demand_matrix("adversarial", g.n, fiedler=f)
    r = analyze_routing(g)          # float64 sigma DP: always on ref
    results = {}
    for backend in ("ref", "pallas_interpret"):
        D_b = demand_matrix("adversarial", g.n, fiedler=canonical_fiedler(g))
        np.testing.assert_array_equal(D, D_b)
        t = evaluate_traffic(g, "adversarial", routing=r, fiedler=f,
                             backend=backend)
        results[backend] = t.saturation_throughput
    assert results["ref"] == results["pallas_interpret"]


def test_canonical_fiedler_matches_lanczos_path():
    """Dense recompute and the Lanczos-vector entry point agree (dense
    canonicalization ignores the provided vector below the threshold)."""
    from repro.core.spectral import fiedler_lanczos

    g = T.butterfly(2, 3)
    dense = canonical_fiedler(g)
    via_lanczos = canonical_fiedler(g, fiedler_lanczos(g, iters=120, seed=0))
    np.testing.assert_array_equal(dense, via_lanczos)


# --------------------------------------------------------------------------
# sampled-source parity and the UCB fix
# --------------------------------------------------------------------------

@pytest.mark.parametrize("scheme", list(ROUTING_SCHEMES))
def test_sampled_fraction_one_matches_exact(scheme):
    """sample_fraction=1.0 must reproduce the exact evaluation bitwise for
    every scheme (the degenerate-limit contract of the scale subsystem)."""
    g = T.slimfly(5)
    r_exact = analyze_routing(g)
    r_full = analyze_routing(g, sample_fraction=1.0, seed=0)
    t_exact = evaluate_traffic(g, "uniform", scheme=scheme, routing=r_exact)
    t_full = evaluate_traffic(g, "uniform", scheme=scheme, routing=r_full)
    np.testing.assert_array_equal(t_exact.link_loads, t_full.link_loads)
    assert t_exact.saturation_throughput == t_full.saturation_throughput


def test_sampled_ucb_bounds_point_estimate():
    """The bootstrap UCB is never below the sampled point estimate, and the
    sampled saturation throughput is computed from the UCB (conservative),
    so it never exceeds the point-estimate throughput."""
    g = T.random_regular(128, 4, seed=1)
    r = analyze_routing(g, sample_fraction=0.25, seed=3)
    t = evaluate_traffic(g, "uniform", routing=r)
    assert not t.exact
    assert t.max_link_load_ucb >= t.max_link_load - 1e-12
    assert t.saturation_throughput == pytest.approx(
        1.0 / t.max_link_load_ucb)


def test_sampled_ucb_covers_true_max():
    """On a healthy sample the 95% UCB should cover the exact max link
    load (checked across seeds; statistically near-certain margin)."""
    g = T.random_regular(128, 4, seed=1)
    exact = evaluate_traffic(g, "uniform", routing=analyze_routing(g))
    covered = 0
    for seed in range(5):
        r = analyze_routing(g, sample_fraction=0.3, seed=seed)
        t = evaluate_traffic(g, "uniform", routing=r)
        covered += t.max_link_load_ucb >= exact.max_link_load
    assert covered >= 4


def test_exact_run_has_ucb_equal_max():
    g = T.petersen()
    t = evaluate_traffic(g, "uniform", routing=analyze_routing(g))
    assert t.max_link_load_ucb == t.max_link_load


# --------------------------------------------------------------------------
# reverse_slot_index
# --------------------------------------------------------------------------

def test_reverse_slot_index_involutive():
    for g in (T.petersen(), T.hypercube(4), T.cycle(3), T.slimfly(5)):
        table = g.gather_operands()[0]
        rev = reverse_slot_index(table)
        u, j = np.where(table >= 0)
        v = table[u, j]
        # (u --slot j--> v) reversed points back at u ...
        assert np.array_equal(table[v, rev[u, j]], u)
        # ... through the partner slot (involution), pads self-mapping
        assert np.array_equal(rev[v, rev[u, j]], j)
        pu, pj = np.where(table < 0)
        assert np.array_equal(rev[pu, pj], pj)


def test_reverse_slot_index_rejects_asymmetric():
    table = T.petersen().gather_operands()[0].copy()
    table[0, 0] = 5 if table[0, 0] != 5 else 6   # break symmetry
    with pytest.raises(ValueError):
        reverse_slot_index(table)


# --------------------------------------------------------------------------
# scheme wiring: dispatcher, simulator, survey
# --------------------------------------------------------------------------

def test_scheme_link_loads_rejects_unknown():
    g = T.petersen()
    r = analyze_routing(g)
    with pytest.raises(ValueError, match="scheme"):
        scheme_link_loads(g.gather_operands()[0], r,
                          _uniform_served(g, r), "compass")


def test_simulator_rides_nonminimal_paths():
    """simulate_traffic(scheme=) must agree with the static traffic layer's
    saturation throughput for every scheme."""
    from repro.core.simulate import simulate_traffic

    g = T.hypercube(4)
    r = analyze_routing(g)
    for scheme in ROUTING_SCHEMES:
        sim = simulate_traffic(g, "uniform", payloads=1 << 20, routing=r,
                               scheme=scheme)
        static = evaluate_traffic(g, "uniform", scheme=scheme, routing=r)
        assert sim.saturation_throughput == pytest.approx(
            static.saturation_throughput, rel=2e-5)


def test_analysis_traffic_scheme_cache_keys():
    from repro.api import Analysis

    a = Analysis("petersen")
    t1 = a.traffic("uniform")
    t2 = a.traffic("uniform", scheme="valiant")
    t3 = a.traffic("uniform", scheme="ksp", slack=2)
    assert t1 is a.traffic("uniform")
    assert t2 is not t1 and t3 is not t2
    assert t2.scheme == "valiant" and t3.scheme == "ksp"


@needs_scipy
def test_survey_scheme_columns():
    from repro.api.survey import ROUTING_COLUMNS, survey

    res = survey(["petersen"], routing=dict(pattern="adversarial",
                                            schemes=True))
    row = res.rows[0]
    for col in ("thpt_valiant", "thpt_ugal", "thpt_ksp", "thpt_mcf_ub",
                "thpt_gap_to_opt"):
        assert col in ROUTING_COLUMNS
        assert row[col] is not None
    assert 0 < row["thpt_gap_to_opt"] <= 1 + 1e-6


def test_survey_without_schemes_leaves_columns_none():
    from repro.api.survey import survey

    row = survey(["petersen"], routing=True).rows[0]
    assert row["thpt_valiant"] is None and row["thpt_mcf_ub"] is None
