"""The program's step spans in a traced run of each stand-in cell: the metrics
that read them are reported where ``BENCHMARK.json`` lists them, every older
per-layer metric reads what it read without them, and an idle gap inside a
layer is named by the step the host was in."""
from __future__ import annotations

import sys

import pytest

import benchtiny

sys.path.insert(0, str(benchtiny.REPO / "bench"))
import devtrace  # noqa: E402
import run  # noqa: E402

#: per-layer metrics that read the step spans and JAX's compile spans
NEW = {"retrace_ms_per_graph", "sigma_ms_per_graph",
       "lanczos_host_ms_per_graph"}
#: the spans at the layer entry points, which the older metrics read
LAYER_SPANS = {"survey/build", "survey/batched_lanczos", "survey/row",
               "spectral/rho2_lanczos", "spectral/rho2_laplacian_batched",
               "routing/analyze", "traffic/evaluate", "faults/sweep"}
#: name prefixes of the spans inside the layer spans
STEP_PREFIXES = ("routing/bfs", "routing/sigma", "routing/summary",
                 "routing/bootstrap",
                 "traffic/loads", "traffic/ucb", "lanczos/", "faults/scenarios",
                 "faults/stack", "faults/components", "jax/")
CELLS = (benchtiny.SURVEY, benchtiny.SWEEP)


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """Each stand-in traced once: its result and the spans of its window."""
    from repro import obs

    root = benchtiny.make_root(tmp_path_factory.mktemp("bench"))
    out = {}
    for name in CELLS:
        res = run.run_cell(name, 2 ** 31 + 11, 0.2, True, root=root)
        spans = [e for e in obs.trace_events() if e["name"] != "bench/clock"]
        out[name] = (res, spans, run.load_cell(name, root))
    return out


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reports_the_new_metrics(runs, name):
    res, _, cell = runs[name]
    assert res["correct"], res["checks"]
    listed = {m["name"] for m in cell["per_layer"]} & NEW
    expected = NEW if name == benchtiny.SURVEY else \
        {"lanczos_host_ms_per_graph"}
    assert listed == expected
    for metric in expected:
        assert res["metrics"][metric]["value"] > 0
        assert res["metrics"][metric]["unit"] == "ms/graph"


@pytest.mark.parametrize("name", CELLS)
def test_older_metrics_read_the_same_without_the_step_spans(runs, name):
    res, spans, cell = runs[name]
    layers = [e for e in spans if e["name"] in LAYER_SPANS]
    steps = {e["name"] for e in spans if e["name"] not in LAYER_SPANS}
    assert "lanczos/solve" in steps
    assert all(name.startswith(STEP_PREFIXES) for name in steps), steps
    base = dict(queries=res["attempted"], graphs=res["attempted"],
                compile_s=0.5, jit_traces={"jit_trace/lanczos_scan": 1},
                work=[], peak=None, trace=None)
    older = [m["name"] for m in cell["per_layer"] if m["name"] not in NEW]
    assert older
    for metric in older:
        reader = run.load_module(benchtiny.REPO / "bench" / "metrics" /
                                 f"{metric}.py")
        assert reader.read(dict(base, spans=spans)) == \
            reader.read(dict(base, spans=layers)), metric
    for metric in NEW:      # the new ones read nothing from the layers alone
        reader = run.load_module(benchtiny.REPO / "bench" / "metrics" /
                                 f"{metric}.py")
        assert reader.read(dict(base, spans=layers)) is None


def test_idle_gap_in_a_layer_is_named_by_its_step(runs):
    _, spans, _ = runs[benchtiny.SURVEY]
    ivs = [(e["ts"], e["ts"] + e["dur"], int(e["args"]["depth"]), e["name"])
           for e in spans]
    by_name = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append(e)
    for step, layer in (("routing/bfs", "routing/analyze"),
                        ("routing/sigma", "routing/analyze"),
                        ("routing/bootstrap", "routing/analyze"),
                        ("traffic/loads", "traffic/evaluate"),
                        ("traffic/ucb", "traffic/evaluate"),
                        ("lanczos/ritz", "spectral/rho2_lanczos")):
        for e in by_name[step]:
            t = e["ts"] + e["dur"] / 2
            inner = [s for s in spans if s["name"].startswith("jax/")
                     and s["ts"] <= t < s["ts"] + s["dur"]]
            got = devtrace._innermost(ivs, t)
            assert got == (inner[0]["name"] if inner else step), (step, got)
            assert got != layer
    # the retrace is named by JAX's own step, inside the solve
    solve = by_name["lanczos/solve"][0]
    compile_ = next(e for e in by_name["jax/compile"]
                    if solve["ts"] <= e["ts"] <= solve["ts"] + solve["dur"])
    assert compile_["args"]["depth"] == solve["args"]["depth"] + 1
    assert devtrace._innermost(
        ivs, compile_["ts"] + compile_["dur"] / 2) == "jax/compile"

