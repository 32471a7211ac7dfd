"""The reduction from a profiler trace to device figures, on a small trace
recorded on one TPU v5e: a two-rate fault sweep of ``torus(4,3)`` (8
samples, m = 20) with the benchmark's clock marker and the program's spans
(``data/small_sweep.meta.json``)."""
from __future__ import annotations

import json
import pathlib
import sys

import pytest

import benchtiny

sys.path.insert(0, str(benchtiny.REPO / "bench"))
import devtrace  # noqa: E402
import run  # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def reduced():
    meta = json.loads((DATA / "small_sweep.meta.json").read_text())
    return devtrace.reduce_trace(str(DATA / "small_sweep.xplane.pb"),
                                 meta["marker_perf"], meta["window"],
                                 meta["spans"], meta["span_offset"])


def test_device_time_of_the_recorded_window(reduced):
    assert reduced["devices"] == 1
    assert reduced["window_s"] == pytest.approx(0.019444768, rel=1e-6)
    assert reduced["busy_s"] == pytest.approx(0.000944582, rel=1e-9)
    assert reduced["module_s"]["jit__lap_lanczos_batched"] == pytest.approx(
        0.000938241, rel=1e-9)
    # exclusive op times tile the busy time: nested ops are not counted twice
    assert sum(reduced["op_self_s"].values()) == pytest.approx(
        reduced["busy_s"], rel=1e-9)


def test_idle_gaps_are_named_by_the_span_the_host_was_in(reduced):
    names = {name for name, _ in reduced["idle_gaps"]}
    assert names <= {"spectral/rho2_laplacian_batched", "faults/sweep"}
    secs = [s for _, s in reduced["idle_gaps"]]
    assert secs == sorted(secs, reverse=True)
    assert secs[0] == pytest.approx(0.004853802, rel=1e-6)
    bd = devtrace.breakdown(reduced)
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) == 10
    assert bd["device_ops"][0][0] == "jit__lap_lanczos_batched:fusion.11"


def test_readers_on_the_recorded_window(reduced):
    rec = dict(trace=reduced, work=[(8, 64, 6, 20, False)] * 2,
               peak=run.device_peak("TPU v5 lite",
                                    benchtiny.REPO / "bench" / "peaks.json"))
    metrics = benchtiny.REPO / "bench" / "metrics"
    idle = run.load_module(metrics / "device_idle_pct.py").read(rec)
    assert idle == pytest.approx(100 * (1 - 0.000944582 / 0.019444768),
                                 rel=1e-6)
    roof = run.load_module(metrics / "lanczos_roofline.py")
    assert 0 < roof.read(rec) < 100
    assert roof.bound(rec) == "hbm"
    assert roof.read(dict(rec, trace=dict(reduced, module_s={}))) is None


def test_nested_ops_are_charged_exclusive_time():
    ops = [(0, 10, "loop"), (1, 3, "a"), (4, 8, "b"), (5, 6, "c"),
           (12, 13, "d")]
    assert devtrace._self_times(ops) == {"loop": 4, "a": 2, "b": 3, "c": 1,
                                         "d": 1}
    assert devtrace._union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
