"""Non-minimal & adaptive routing schemes vs the MCF optimal-routing ceiling.

Minimal-path ECMP (what ``routing_eval`` measures) collapses on adversarial
permutation traffic: every flow insists on shortest paths, so a Fiedler-
matched permutation can focus all of them across one sparse cut.  This bench
measures what the alternative schemes recover on every family of the routing
set — Valiant load balancing (two minimal-ECMP legs through a random
intermediate), UGAL-style adaptive selection (per-pair minimal vs Valiant by
estimated channel load) and k-shortest-path non-minimal ECMP (paths up to
``dist+slack``) — and reports each against the linear-programming
multi-commodity-flow throughput ceiling ``thpt_mcf_ub``: the best any routing
scheme could do on that topology, so ``gap_to_opt`` separates routing loss
from the topological limit the spectral gap predicts.

Acceptance invariants (``required_true`` in CI):

* on every expander family (lps / slimfly / xpander) the non-minimal schemes
  beat minimal ECMP on adversarial traffic — Valiant's 2x average-load tax is
  worth paying when the adversary saturates the minimal paths;
* no scheme ever exceeds the MCF ceiling, on any family or pattern;
* the butterfly adversarial throughput is bit-identical across spmv backends
  (ref vs pallas_interpret) for all four schemes — the tie-sensitive
  degenerate-eigenspace regression this PR fixes.

Emits ``benchmarks/out/BENCH_routing_schemes.json`` (gated in CI) and
``benchmarks/out/routing_schemes.csv``.

    PYTHONPATH=src python -m benchmarks.routing_schemes
"""
from __future__ import annotations

import json
import pathlib
import time
from typing import List

# the routing_eval coverage with the xpander expander swapped in for the
# random-regular baseline: the three expander families carry the acceptance
# invariant, the rest exercise the schemes on structured topologies
SPECS = [
    "lps(13,5)",                  # Ramanujan reference (n=2184, k=6)
    "slimfly(13)",                # n=338
    "xpander(256,6,0,0)",         # expander by construction (n=1792)
    "torus(16,2)",                # n=256
    "hypercube(8)",               # n=256
    "ccc(6)",                     # n=384
    "butterfly(3,4)",             # n=324
    "petersen_torus(5,4)",        # n=200
    "dragonfly",                  # n=42
]

#: the expander families whose adversarial traffic must be recovered by the
#: non-minimal schemes (the paper's thesis: spectral gap = routable bandwidth,
#: but only if the routing scheme can actually spread the load)
EXPANDERS = ("lps(13,5)", "slimfly(13)", "xpander(256,6,0,0)")

#: measured throughput may exceed the LP ceiling only by solver roundoff
MCF_TOL_REL = 1e-6
MCF_TOL_ABS = 1e-9

#: the backend-invariance probe: the family whose adversarial demand was
#: tie-sensitive before Fiedler canonicalization (degenerate rho2 eigenspace)
BACKEND_PROBE = "butterfly(3,4)"

#: large instances route rho2/Fiedler through Lanczos (same as routing_eval);
#: canonical_fiedler still recomputes the dense eigenspace below this size
DENSE_THRESHOLD = 1024

SCHEMES = ("minimal", "valiant", "ugal", "ksp")


def _thpts(a, pattern: str) -> dict:
    return {s: a.traffic(pattern, scheme=s).saturation_throughput
            for s in SCHEMES}


def _backend_invariance() -> dict:
    """Adversarial throughput of every scheme on the probe family, per spmv
    backend — returned as repr'd floats so bit-identity is visible in the
    payload."""
    from repro.api import Analysis
    from repro.core.routing import analyze_routing
    from repro.core.traffic import evaluate_traffic

    a = Analysis(BACKEND_PROBE, dense_threshold=DENSE_THRESHOLD)
    fiedler = a.fiedler                 # canonical: backend-independent
    routing = analyze_routing(a.topo)   # float64 sigma DP: always on ref
    out = {}
    for backend in ("ref", "pallas_interpret"):
        out[backend] = {
            s: evaluate_traffic(a.topo, "adversarial", scheme=s,
                                routing=routing, fiedler=fiedler,
                                backend=backend).saturation_throughput
            for s in SCHEMES}
    return out


def run(out_json: str = "benchmarks/out/BENCH_routing_schemes.json",
        out_csv: str = "benchmarks/out/routing_schemes.csv") -> List[dict]:
    from repro.api import Analysis
    from repro.api.survey import csv_field

    from .calibrate import measure_calibration

    calibration = measure_calibration()
    t_all = time.time()
    table: List[dict] = []
    adversarial_wins = True
    mcf_ceiling_ok = True
    mcf_available = True
    for spec in SPECS:
        a = Analysis(spec, dense_threshold=DENSE_THRESHOLD)
        t0 = time.time()
        row = dict(family=a.family or a.name, spec=spec, nodes=a.n,
                   radix=a.radix, rho2=round(a.rho2, 5))
        for pattern in ("uniform", "adversarial"):
            meas = _thpts(a, pattern)
            try:
                ub = a.mcf_throughput_ub(pattern)
            except RuntimeError:          # scipy-less environment
                ub, mcf_available = None, False
            tag = "" if pattern == "uniform" else "_adv"
            for s in SCHEMES:
                row[f"thpt_{s}{tag}"] = round(meas[s], 4)
            row[f"thpt_mcf_ub{tag}"] = None if ub is None else round(ub, 4)
            if ub is not None:
                best = max(meas.values())
                row[f"gap_to_opt{tag}"] = round(best / ub, 4)
                mcf_ceiling_ok &= all(
                    v <= ub * (1 + MCF_TOL_REL) + MCF_TOL_ABS
                    for v in meas.values())
            else:
                row[f"gap_to_opt{tag}"] = None
            if pattern == "adversarial" and spec in EXPANDERS:
                adversarial_wins &= (meas["valiant"] >= meas["minimal"]
                                     and meas["ugal"] >= meas["minimal"])
        row["seconds"] = round(time.time() - t0, 2)
        table.append(row)
    probe = _backend_invariance()
    backends_bitwise = all(
        probe["ref"][s] == probe["pallas_interpret"][s] for s in SCHEMES)
    payload = dict(
        bench="routing_schemes",
        total_seconds=round(time.time() - t_all, 3),
        calibration_seconds=round(calibration, 4),
        families=SPECS,
        schemes=list(SCHEMES),
        correctness=dict(
            cases=len(SPECS),
            mcf_available=bool(mcf_available),
            nonminimal_wins_adversarial_on_expanders=bool(adversarial_wins),
            all_schemes_leq_mcf_ub=bool(mcf_ceiling_ok and mcf_available),
            adversarial_backend_bitwise=bool(backends_bitwise),
            backend_probe={b: {s: repr(v) for s, v in d.items()}
                           for b, d in probe.items()},
        ),
        scheme_table=table,
    )
    p = pathlib.Path(out_json)
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(json.dumps(payload, indent=2))
    cols = list(table[0])
    pathlib.Path(out_csv).write_text("\n".join(
        [",".join(cols)]
        + [",".join(csv_field(row[c]) for c in cols) for row in table]))
    return table


if __name__ == "__main__":
    for row in run():
        print(row)
