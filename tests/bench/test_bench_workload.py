"""The ``workload_sweep`` query kind and its plain reference on the CPU: a
stand-in of the DeepSeek-V3 cell on a 64-chip torus reads correct, the
program equals ``bench/plain_workload.py`` phase by phase, and a run whose
plan leaves a phase out, or sends the expert gradient over the whole DP
group, reads not correct."""
from __future__ import annotations

import dataclasses
import json
import sys

import jax
import numpy as np
import pytest

import benchtiny

sys.path.insert(0, str(benchtiny.REPO / "bench"))
import plain_workload  # noqa: E402
import run  # noqa: E402

from repro.api import build  # noqa: E402
from repro.core import faults as F  # noqa: E402
from repro.core import workloads as W  # noqa: E402

CELL = "tiny-dsv3.degraded_step"
REAL = "dsv3-pp16-dp256-ep64-v4pod.degraded_step"
SPEC = "deepseek_v3@pp=4,dp=16,ep=4"          # 64 ranks, expert replicas 4
PEAK = 275e12


def _with_cell(root):
    """Add the stand-in: torus(4,3), 64 ranks, the real cell's rate and
    limits, a short Lanczos run."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    (root / "bench" / "configs" / "tiny-dsv3.json").write_text(json.dumps(
        dict(name="tiny-dsv3", spec="torus(4,3)", workload=SPEC,
             placement="linear", peak_flops=PEAK, global_batch=256,
             seq_len=4096)))
    real = json.loads((root / "bench" / "cells" / f"{REAL}.json").read_text())
    (root / "bench" / "cells" / f"{CELL}.json").write_text(json.dumps(
        dict(real, config="tiny-dsv3", lanczos_iters=20)))
    spec["configs"].append(dict(name="tiny-dsv3", source="test stand-in",
                                file="bench/configs/tiny-dsv3.json",
                                reduced=[], why="stand-in"))
    spec["workloads"].append(dict(name=CELL, config="tiny-dsv3",
                                  traffic="degraded_step", chips=1,
                                  why="stand-in"))
    for m in spec["per_layer"]:
        if m["name"].startswith(("workload_", "lanczos_solve_")):
            m["workloads"] = m["workloads"] + [CELL]
    (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return _with_cell(benchtiny.make_root(tmp_path_factory.mktemp("bench")))


@pytest.fixture
def fresh_traces():
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("rate", [0.0, 0.1, 0.7],
                         ids=["healthy", "degraded", "shattered"])
def test_program_equals_plain_reference_per_phase(rate):
    g = build("torus(4,3)")
    if rate:
        g = F.apply_faults(g, F.random_link_faults(g, rate, seed=5))
    got = W.simulate_workload(g, W.plan_workload(SPEC, peak_flops=PEAK))
    want, = plain_workload.steps(g.n, [g.edges], pp=4, dp=16, ep=4,
                                 batch=256, peak_flops=PEAK)
    secs = got.phase_seconds()
    assert set(secs) == set(want["phase_seconds"]) == {
        "pp_sendrecv", "moe_dispatch", "moe_combine", "dp_allreduce",
        "dp_allreduce_experts"}
    for name, w in want["phase_seconds"].items():
        np.testing.assert_allclose(secs[name], w, rtol=1e-5, err_msg=name)
    np.testing.assert_allclose(got.step_seconds, want["step"], rtol=1e-5)
    assert got.dropped_frac == pytest.approx(want["dropped_frac"], abs=1e-9)
    assert (got.dropped_frac > 0) == (rate > 0.5)


def test_sound_run_is_correct_and_reads_the_workload_metrics(root,
                                                            fresh_traces):
    res = run.run_cell(CELL, 2 ** 33 + 11, 0.1, True, root=root)
    assert res["correct"], res["checks"]
    for name in ("workload_ms_per_graph", "workload_routing_ms_per_graph",
                 "workload_lower_ms_per_graph", "lanczos_solve_ms_per_graph"):
        assert res["metrics"][name]["value"] > 0, name
    assert res["metrics"]["workload_lower_ms_per_graph"]["value"] < \
        res["metrics"]["workload_ms_per_graph"]["value"]


def _phase_left_out(monkeypatch):
    """The plan loses its pipeline phase."""
    orig = W.plan_workload

    def plan(*a, **kw):
        p = orig(*a, **kw)
        return dataclasses.replace(p, phases=tuple(
            ph for ph in p.phases if ph.name != "pp_sendrecv"))

    monkeypatch.setattr(W, "plan_workload", plan)


def _experts_over_whole_dp(monkeypatch):
    """The expert gradient all-reduced over the stage's whole DP group, as
    if every rank held every expert's replica."""
    orig = W.plan_workload

    def plan(*a, **kw):
        p = orig(*a, **kw)
        dp = p.spec.dp
        return dataclasses.replace(p, phases=tuple(
            dataclasses.replace(ph, group_axis="dp", group_size=dp,
                                n_groups=p.spec.pp)
            if ph.name == "dp_allreduce_experts" else ph for ph in p.phases))

    monkeypatch.setattr(W, "plan_workload", plan)


@pytest.mark.parametrize("fault", [_phase_left_out, _experts_over_whole_dp],
                         ids=lambda f: f.__name__)
def test_broken_run_is_not_correct(root, fault, monkeypatch, fresh_traces):
    fault(monkeypatch)
    res = run.run_cell(CELL, 2 ** 33 + 11, 0.1, False, root=root)
    assert not res["correct"], res["checks"]
