"""A copy of the benchmark with small stand-in cells, for tests on the CPU.

The copy holds the benchmark's own files unchanged; only ``BENCHMARK.json``,
the configuration and cell files are its own: ``xpander(4608,8,{seed},0)``
for the survey row (K_9 lifted 9 times: above the ``Analysis`` dense
threshold of 4,096, so rho_2 goes through Lanczos as at full size) and ``torus(4,3)`` for the
fault sweep, each with the limits of the real cell of its kind.  A ``cpu``
row in its peak table lets a traced run finish on the CPU; no CPU number is
reported under a device metric's name, since the CPU trace has no device.
"""
from __future__ import annotations

import json
import pathlib
import shutil

REPO = pathlib.Path(__file__).resolve().parents[2]
SURVEY = "tiny-xp.survey_row"
SWEEP = "tiny-torus.fault_sweep"


def make_root(tmp: pathlib.Path) -> pathlib.Path:
    """Write the copy under ``tmp`` and return its root."""
    root = tmp / "checkout"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__",
                                                  ".jax_cache"))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    cells = root / "bench" / "cells"
    real = {json.loads(p.read_text())["query"]: json.loads(p.read_text())
            for p in sorted(cells.glob("*.json"))}
    configs = {"tiny-xp": "xpander(4608,8,{seed},0)", "tiny-torus": "torus(4,3)"}
    for name, s in configs.items():
        (root / "bench" / "configs" / f"{name}.json").write_text(
            json.dumps(dict(name=name, spec=s)))
    survey = dict(real["survey_row"], config="tiny-xp", sources=16,
                  lanczos_iters=30)
    sweep = dict(real["fault_sweep"], config="tiny-torus", samples=8,
                 rates=[0.1, 0.3], lanczos_iters=20)
    (cells / f"{SURVEY}.json").write_text(json.dumps(survey))
    (cells / f"{SWEEP}.json").write_text(json.dumps(sweep))
    spec["configs"] = [dict(name=n, source="test stand-in",
                            file=f"bench/configs/{n}.json", reduced=[],
                            why="stand-in")
                       for n in configs]
    spec["workloads"] = [
        dict(name=SURVEY, config="tiny-xp", traffic="survey_row", chips=1,
             why="stand-in"),
        dict(name=SWEEP, config="tiny-torus", traffic="fault_sweep", chips=1,
             why="stand-in")]
    kinds = {"xpander-67584-k32.survey_row": SURVEY,
             "torus-16x16x16.fault_sweep": SWEEP}
    for m in spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = sorted({kinds[w] for w in m["workloads"]
                                     if w in kinds})
    (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    peaks = json.loads((root / "bench" / "peaks.json").read_text())
    peaks["devices"]["cpu"] = dict(flops_per_s=1e12, hbm_bytes_per_s=1e11)
    (root / "bench" / "peaks.json").write_text(json.dumps(peaks))
    return root
