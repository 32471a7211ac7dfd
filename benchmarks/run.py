"""Benchmark aggregator. One function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (scaffold contract); detailed CSVs go
to benchmarks/out/.  Every gated bench also emits a ``BENCH_*.json`` payload
(compared against ``benchmarks/baselines/`` by ``check_regression.py``), so
successive PRs accumulate a perf trajectory per subsystem.

Selectors::

    python -m benchmarks.run                 # full suite
    python -m benchmarks.run --list          # names only
    python -m benchmarks.run --only routing_eval --only table1

``--only`` accepts the registry names printed by ``--list`` (repeatable),
so one bench can be iterated — or one CI matrix entry gated — without paying
for the rest of the suite.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time
from typing import Callable, Dict, List, Tuple


def _timed(name, fn, derive):
    t0 = time.time()
    rows = fn()
    us = (time.time() - t0) * 1e6
    print(f"{name},{us:.0f},{derive(rows)}")
    return rows


def _emit_survey_bench(rows, total_us,
                       out_json: str = "benchmarks/out/BENCH_survey.json") -> None:
    from .calibrate import measure_calibration

    payload = dict(
        bench="table1_survey",
        total_seconds=round(total_us / 1e6, 3),
        calibration_seconds=round(measure_calibration(), 4),
        cases=len(rows),
        all_rho2_bounds_hold=all(r["rho2_ok"] for r in rows),
        per_row=[dict(spec=r.get("instance"), nodes=r.get("nodes"),
                      seconds=r.get("seconds")) for r in rows],
    )
    p = pathlib.Path(out_json)
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(json.dumps(payload, indent=2))


def _run_table1():
    from . import table1

    t0 = time.time()
    rows = _timed("table1_rho2_bw_bounds", table1.run,
                  lambda rows: f"all_rho2_bounds_hold="
                               f"{all(r['rho2_ok'] for r in rows)}")
    _emit_survey_bench(rows, (time.time() - t0) * 1e6)


def _run_fault_sweep():
    from . import fault_sweep

    _timed("fault_sweep_resilience", fault_sweep.run,
           lambda rows: "min_retention_at_10pct=%.2f"
           % min(r["retention_at_010"] or 0.0 for r in rows))


def _run_routing_eval():
    from . import routing_eval

    _timed("routing_eval_path_traffic", routing_eval.run,
           lambda rows: "all_diameters_match=%s"
           % all(r["diameter_ok"] is not False for r in rows))


def _run_routing_schemes():
    from . import routing_schemes

    _timed("routing_schemes_vs_mcf_ceiling", routing_schemes.run,
           lambda rows: "min_gap_to_opt=%.3f"
           % min(r["gap_to_opt_adv"] for r in rows
                 if r["gap_to_opt_adv"] is not None))


def _run_synthesis_frontier():
    from . import synthesis_frontier

    _timed("synthesis_frontier_ramanujan_gap", synthesis_frontier.run,
           lambda rows: "max_gap_fraction=%.3f"
           % max(r["gap_fraction"] for r in rows))


def _run_collective_sim():
    from . import collective_sim

    _timed("collective_sim_measured_vs_model", collective_sim.run,
           lambda rows: "all_ring_geq_model=%s"
           % all(r["ring_geq_model"] for r in rows))


def _run_workload_sim():
    from . import workload_sim

    _timed("workload_sim_step_time", workload_sim.run,
           lambda rows: "max_dropped_frac=%.4f"
           % max(r["dropped_frac"] for r in rows))


def _run_fig5():
    from . import fig5

    _timed("fig5_proportional_bw", fig5.run,
           lambda rows: f"curve_points={len(rows)}")


def _run_lps_bench():
    from . import lps_bench

    _timed("lps_ramanujan_cert", lps_bench.run,
           lambda rows: f"all_ramanujan={all(r['ramanujan'] for r in rows)}")


def _run_collective_model():
    from . import collective_model

    _timed("collective_model_torus_vs_lps", collective_model.run,
           lambda rows: "max_speedup=%.1fx"
           % max(r["speedup_vs_torus"] for r in rows))


def _run_roofline():
    from . import roofline

    _timed("roofline_dryrun_table", roofline.run,
           lambda rows: f"cells={len(rows)}")


def _run_scale_bench():
    from . import scale_bench

    _timed("scale_survey_row_65536", scale_bench.run,
           lambda rows: "within_budget=%s"
           % (rows[0]["correctness"]["within_wall_budget"]
              and rows[0]["correctness"]["within_rss_budget"]))


def _run_obs_overhead():
    from . import obs_overhead

    _timed("obs_overhead_span_tax", obs_overhead.run,
           lambda rows: "overhead_frac=%.4f"
           % rows[0]["correctness"]["overhead_frac"])


#: name -> (runner, BENCH json this bench emits — None for ungated benches).
#: Declaration order is execution order for the full suite.
BENCHES: Dict[str, Tuple[Callable[[], None], str]] = {
    "table1": (_run_table1, "BENCH_survey.json"),
    "fault_sweep": (_run_fault_sweep, "BENCH_faults.json"),
    "routing_eval": (_run_routing_eval, "BENCH_routing.json"),
    "routing_schemes": (_run_routing_schemes, "BENCH_routing_schemes.json"),
    "synthesis_frontier": (_run_synthesis_frontier, "BENCH_synthesis.json"),
    "collective_sim": (_run_collective_sim, "BENCH_simulate.json"),
    "workloads": (_run_workload_sim, "BENCH_workloads.json"),
    "fig5": (_run_fig5, None),
    "lps_bench": (_run_lps_bench, None),
    "collective_model": (_run_collective_model, "BENCH_collective_model.json"),
    "roofline": (_run_roofline, "BENCH_roofline.json"),
    "scale": (_run_scale_bench, "BENCH_scale.json"),
    "obs": (_run_obs_overhead, "BENCH_obs.json"),
}


def _run_instrumented(name: str) -> list:
    """Run one bench under :mod:`repro.obs`: spans enabled, counters
    snapshotted — and inject the observability ``meta`` block (peak RSS,
    build/compile/execute phase breakdown, jit-trace count, span count) into
    the BENCH json the bench just emitted.  Returns the bench's trace events
    so the aggregator can write one merged ``benchmarks/out/trace.json``."""
    from repro import obs

    runner, bench_json = BENCHES[name]
    rss0 = obs.peak_rss_kb()
    before = obs.counters()
    t0 = time.time()
    with obs.tracing():
        # no phase= tag: the wrapper must not swallow the per-phase rollup
        with obs.span("bench/" + name, bench=name):
            runner()
        rep = obs.metrics_report()
        events = list(obs.trace_events())
    wall = time.time() - t0
    if bench_json:
        p = pathlib.Path("benchmarks/out") / bench_json
        if p.exists():
            payload = json.loads(p.read_text())
            payload["meta"] = dict(
                wall_seconds=round(wall, 3),
                peak_rss_gb=round(rep.peak_rss_kb / 1e6, 3),
                rss_growth_gb=round(max(0, rep.peak_rss_kb - rss0) / 1e6, 3),
                phases={k: round(v, 3)
                        for k, v in sorted(rep.phases.items())},
                jit_traces=sum(
                    obs.counter_delta(before, "jit_trace/").values()),
                spans=len(events),
            )
            p.write_text(json.dumps(payload, indent=2))
    return events


def main(argv: List[str] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--only", action="append", default=None, metavar="NAME",
                    help="run only the named bench (repeatable; see --list)")
    ap.add_argument("--list", action="store_true",
                    help="print bench names (+ emitted BENCH file) and exit")
    args = ap.parse_args(argv)
    if args.list:
        for name, (_, bench_json) in BENCHES.items():
            print(f"{name}\t{bench_json or '-'}")
        return 0
    names = list(BENCHES) if args.only is None else args.only
    unknown = [n for n in names if n not in BENCHES]
    if unknown:
        ap.error(f"unknown bench name(s) {unknown}; known: {list(BENCHES)}")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    events: List[dict] = []
    for name in names:
        events += _run_instrumented(name)
    out = pathlib.Path("benchmarks/out/trace.json")
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(
        dict(traceEvents=events, displayTimeUnit="ms"), indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
