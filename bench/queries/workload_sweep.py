"""Query kind ``workload_sweep``: one link-fault sweep that executes a training
step's communication on each degraded sample, through ``Analysis.fault_sweep``.

Cell parameters: ``rates`` (link-fault rates), ``samples`` (Monte-Carlo
samples a rate), ``lanczos_iters`` (m), ``workload_samples`` (samples a rate
whose step is executed).  The configuration names the fabric (``spec``),
the workload spec, the rank ``placement`` and the chip's ``peak_flops``.

Set-up parses and plans the workload first, so that a program without the
workload's grammar fails at once, then builds the topology and one
``Analysis``, reads its healthy rho_2 and runs one warm-up query.  Each
query is one user call::

    Analysis(topo).fault_sweep(rates, model="link", samples=B, seed=s,
                               iters=m, workload=plan, workload_samples=W)

The check recomputes one query drawn from the run's seed: rho_2 and
connectivity by the fault-sweep kind's reference (the same link draws,
``bench/queries/fault_sweep.py``), and each executed step by
``bench/plain_workload.py`` on the same degraded edge lists:

* ``rho2_gap``, ``connectivity_mismatch`` — as the fault-sweep kind;
* ``phase_gap`` — the largest relative gap of a phase's mean seconds (a
  phase missing or extra on either side is an infinite gap);
* ``step_gap`` — the larger relative gap of the mean and the largest step;
* ``dropped_gap`` — the gap of the mean dropped share of the demand.
"""
from __future__ import annotations

import pathlib
import sys

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
import plain_workload  # noqa: E402

_sweep_path = pathlib.Path(__file__).resolve().parent / "fault_sweep.py"


def _sweep_kind():
    """The fault-sweep query kind, whose link draws and reference this one
    shares."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_queries_fault_sweep",
                                                  _sweep_path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _layout(config: dict) -> dict:
    """(pp, dp, ep, batch) of the configuration's workload spec, read from
    the spec string alone."""
    _, _, params = config["workload"].partition("@")
    kv = dict(p.split("=") for p in params.split(","))
    if int(kv.get("tp", 1)) != 1:
        raise ValueError("the plain reference covers tp = 1 only")
    return dict(pp=int(kv.get("pp", 1)), dp=int(kv.get("dp", 1)),
                ep=int(kv.get("ep", 1)), batch=int(config["global_batch"]))


def setup(config: dict, cell: dict, seed: int) -> dict:
    from repro.api import Analysis, build
    from repro.core.workloads import parse_workload, plan_workload

    spec = parse_workload(config["workload"])
    plan = plan_workload(spec, peak_flops=config["peak_flops"])
    topo = build(config["spec"])
    a = Analysis(topo, lanczos_iters=cell["lanczos_iters"])
    a.rho2                               # the session's healthy rho_2
    width = int(np.bincount(topo.edges.reshape(-1), minlength=topo.n).max())
    state = dict(n=topo.n, k=width, edges=np.array(topo.edges), cell=cell,
                 config=config, program=dict(analysis=a, plan=plan))
    query(state, 2 ** 30 + 1)            # warm-up: every shape of a query
    return state


def query(state: dict, qseed: int) -> dict:
    cell, prog = state["cell"], state["program"]
    res = prog["analysis"].fault_sweep(
        rates=cell["rates"], model="link", samples=cell["samples"],
        seed=qseed, iters=cell["lanczos_iters"], workload=prog["plan"],
        workload_samples=cell["workload_samples"])
    return dict(graphs=sum(r["samples"] for r in res.rows), seed=int(qseed),
                rows=[dict(rate=r["rate"], samples=r["samples"],
                           rho2_mean=r["rho2_mean"], rho2_min=r["rho2_min"],
                           rho2_max=r["rho2_max"],
                           connected=round(r["connectivity_prob"]
                                           * r["samples"]),
                           step_mean=r["workload_step_mean"],
                           step_max=r["workload_step_max"],
                           dropped=r["workload_dropped_frac_mean"],
                           phases=dict(r["workload_phase_seconds_mean"]))
                      for r in res.rows])


def work(state: dict) -> list:
    """(B, n, k, m, signed) of each Lanczos solve one query makes."""
    cell = state["cell"]
    return [(cell["samples"], state["n"], state["k"], cell["lanczos_iters"],
             False) for _ in cell["rates"]]


def _degraded(edges: np.ndarray, rate: float, seed: int) -> np.ndarray:
    """The edge list left by the sweep's link draw for ``seed``."""
    t = int(round(rate * len(edges)))
    keep = np.ones(len(edges), dtype=bool)
    if t:
        keep[np.random.default_rng(seed).choice(len(edges), size=t,
                                                replace=False)] = False
    return edges[keep]


def reference(state: dict, qseed: int, rnd=None) -> dict:
    """The answer of query ``qseed`` from the plain references alone."""
    sweep = _sweep_kind()
    cell, n, edges = state["cell"], state["n"], state["edges"]
    lay = _layout(state["config"])
    want = sweep.reference(state, qseed, rnd)
    for row in want["rows"]:
        graphs = [_degraded(edges, row["rate"], qseed + sweep.SAMPLE_STRIDE * i)
                  for i in range(min(cell["workload_samples"],
                                     row["samples"]))]
        steps = plain_workload.steps(
            n, graphs, peak_flops=state["config"]["peak_flops"], rnd=rnd,
            **lay)
        row.update(
            step_mean=float(np.mean([s["step"] for s in steps])),
            step_max=float(np.max([s["step"] for s in steps])),
            dropped=float(np.mean([s["dropped_frac"] for s in steps])),
            phases={k: float(np.mean([s["phase_seconds"][k] for s in steps]))
                    for k in steps[0]["phase_seconds"]})
    return want


def _rel(a: float, b: float) -> float:
    return abs(a - b) / abs(b) if b else abs(a - b)


def compare(got: dict, want: dict) -> dict:
    """The numbers the check holds against the cell's limits."""
    out = _sweep_kind().compare(got, want)
    phase = step = dropped = 0.0
    if len(got["rows"]) != len(want["rows"]):
        return dict(out, phase_gap=float("inf"), step_gap=float("inf"),
                    dropped_gap=float("inf"))
    for g, w in zip(got["rows"], want["rows"]):
        if set(g["phases"]) != set(w["phases"]):
            phase = float("inf")
        else:
            for k in w["phases"]:
                phase = max(phase, _rel(g["phases"][k], w["phases"][k]))
        step = max(step, _rel(g["step_mean"], w["step_mean"]),
                   _rel(g["step_max"], w["step_max"]))
        dropped = max(dropped, abs(g["dropped"] - w["dropped"]))
    return dict(out, phase_gap=phase, step_gap=step, dropped_gap=dropped)


def check(state: dict, answers: list, rng: np.random.Generator) -> dict:
    """Compare one answer of the window, drawn from ``rng``."""
    if not answers:
        return {}
    got = answers[int(rng.integers(len(answers)))]
    return compare(got, reference(state, got["seed"]))
