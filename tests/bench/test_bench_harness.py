"""The benchmark harness on the CPU: it finds everything by name, a new
metric is one new file, and it refuses to report from anything but a TPU."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

import benchtiny

REPO = benchtiny.REPO
sys.path.insert(0, str(REPO / "bench"))
import run  # noqa: E402


def test_every_cell_config_kind_and_metric_is_a_file():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for wl in spec["workloads"]:
        c = run.load_cell(wl["name"])
        assert c["chips"] == wl["chips"]
        assert (REPO / "bench" / "queries" /
                f"{c['cell']['query']}.py").is_file()
        assert c["config"]["name"] == wl["config"]
        assert c["end_to_end"] and c["per_layer"]
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert hasattr(run.load_module(
            REPO / "bench" / "metrics" / f"{m['name']}.py"), "read")
    for c in spec["configs"]:
        assert (REPO / c["file"]).is_file()


def test_a_metric_file_in_a_copy_is_picked_up_without_edits(tmp_path):
    root = benchtiny.make_root(tmp_path)
    (root / "bench" / "metrics" / "queries_seen.py").write_text(
        "def read(rec):\n    return float(rec['queries'])\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["end_to_end"].append(dict(name="queries_seen", unit="queries",
                                   better="higher", bound=0.01,
                                   source="host_clock"))
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    res = run.run_cell(benchtiny.SWEEP, 3, 0.2, False, root=root)
    assert res["correct"]
    assert res["metrics"]["queries_seen"]["value"] == res["attempted"]
    assert list(res)[-1] == "checks"


def test_peaks_refuse_an_unknown_device():
    with pytest.raises(KeyError, match="not in peaks.json"):
        run.device_peak("TPU v99", REPO / "bench" / "peaks.json")
    assert run.device_peak("TPU v5 lite", REPO / "bench" / "peaks.json")[
        "hbm_bytes_per_s"] == 819e9


def test_query_seeds_take_large_seeds_and_differ_by_index():
    seeds = {run.query_seed(2 ** 33 + 7, i) for i in range(50)}
    assert len(seeds) == 50 and all(0 <= s < 2 ** 30 for s in seeds)
    assert run.query_seed(5, 0) == run.query_seed(5, 0)


def test_no_result_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench" / "run.py"), "--workload",
         "torus-16x16x16.fault_sweep", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_traced_run_reads_spans_and_counters_but_no_cpu_device_metric(
        tmp_path):
    root = benchtiny.make_root(tmp_path)
    res = run.run_cell(benchtiny.SWEEP, 4, 0.2, True, root=root)
    assert res["correct"]
    got = set(res["metrics"])
    assert {"spectral_ms_per_graph", "faults_host_ms_per_graph",
            "jit_traces_per_query", "compile_s_per_query"} <= got
    # the CPU trace has no TPU plane: no device figure is reported from it
    assert not got & {"device_idle_pct", "lanczos_roofline"}
    assert res["device"]["busy_s"] == 0.0
    assert not (root / "bench" / "out" / f"trace-{benchtiny.SWEEP}").exists()
