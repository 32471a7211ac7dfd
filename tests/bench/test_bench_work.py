"""The Lanczos byte and operation counts: a hand count at a tiny shape, and
a count that depends on the shape alone, not on the spmv backend."""
from __future__ import annotations

import sys

import numpy as np

import benchtiny

sys.path.insert(0, str(benchtiny.REPO / "bench"))
import run  # noqa: E402
import work  # noqa: E402


def test_hand_count_at_a_tiny_shape():
    # B=2, n=8, k=3, m=2, unsigned; steps j = 0, 1 read 1 and 2 basis rows
    # four times each: 4 * 4 * 8 * (1 + 2) = 384 bytes, 4 * 2 * 8 * 3 = 192 ops
    # per step: table 4 * 8 * 3 = 96, vectors 4 * 8 * (3 + 6) = 288 bytes;
    # ops 8 * 3 + 10 * 8 = 104
    w = work.lanczos_work(2, 8, 3, 2, signed=False)
    assert w == dict(bytes=2 * (384 + 2 * (96 + 288)),
                     ops=2 * (192 + 2 * 104))
    s = work.lanczos_work(2, 8, 3, 2, signed=True)
    assert s["bytes"] - w["bytes"] == 2 * 2 * 96
    assert s["ops"] - w["ops"] == 2 * 2 * 8 * 3


def test_counts_at_the_cells_shapes():
    big = work.lanczos_work(1, 67584, 32, 200, False)["bytes"]
    small = work.lanczos_work(1, 4096, 6, 200, False)["bytes"]
    assert abs(big / 1e9 - 23.95) < 0.1
    assert abs(small / 1e9 - 1.37) < 0.05


def test_least_time_names_its_bound():
    peak = dict(flops_per_s=197e12, hbm_bytes_per_s=819e9)
    secs, bound = work.least_seconds(work.lanczos_work(32, 4096, 6, 200,
                                                       False), peak)
    assert bound == "hbm"
    assert abs(secs - 32 * 1.37e9 / 819e9) / secs < 0.05
    assert work.least_seconds(dict(bytes=1, ops=1e15), peak)[1] == "compute"


def test_same_count_on_the_ref_and_kernel_backends(tmp_path):
    from repro.kernels import spmv as KS

    root = benchtiny.make_root(tmp_path)
    c = run.load_cell(benchtiny.SWEEP, root)
    kind = run.load_module(root / "bench" / "queries" / "fault_sweep.py")
    counts, rho2 = [], []
    for backend in ("ref", "pallas_interpret"):
        with KS.use_backend(backend):
            state = kind.setup(c["config"], c["cell"], 1)
            got = kind.query(state, 77)
        counts.append([work.lanczos_work(*w) for w in kind.work(state)])
        rho2.append([r["rho2_mean"] for r in got["rows"]])
    assert counts[0] == counts[1]
    np.testing.assert_allclose(rho2[0], rho2[1], atol=1e-4)
