"""Each query kind against its plain reference at a small stand-in size, and
the control (the reference in bfloat16 in the program's place) failing the
cell's limits."""
from __future__ import annotations

import sys

import pytest

import benchtiny

sys.path.insert(0, str(benchtiny.REPO / "bench"))
import plain  # noqa: E402
import run  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchtiny.make_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture(scope="module")
def cases(root):
    out = {}
    for name in (benchtiny.SURVEY, benchtiny.SWEEP):
        c = run.load_cell(name, root)
        kind = run.load_module(root / "bench" / "queries" /
                               f"{c['cell']['query']}.py")
        state = kind.setup(c["config"], c["cell"], 5)
        got = kind.query(state, 12345)
        state.pop("program")
        out[name] = (kind, state, got, c["cell"]["limits"])
    return out


@pytest.mark.parametrize("name", [benchtiny.SURVEY, benchtiny.SWEEP])
def test_query_agrees_with_its_reference(cases, name):
    kind, state, got, limits = cases[name]
    numbers = kind.compare(got, kind.reference(state, got["seed"]))
    assert set(numbers) == set(limits)
    for key, value in numbers.items():
        assert value <= limits[key], (key, value, limits[key])


@pytest.mark.parametrize("name", [benchtiny.SURVEY, benchtiny.SWEEP])
def test_control_in_bfloat16_fails_a_limit(cases, name):
    kind, state, got, limits = cases[name]
    want = kind.reference(state, got["seed"])
    control = kind.compare(kind.reference(state, got["seed"], rnd=plain.bf16),
                           want)
    assert any(control[k] > limits[k] for k in limits), control


def test_reference_lanczos_matches_a_dense_eigensolve():
    import numpy as np

    rng = np.random.default_rng(0)
    n = 40
    edges = np.array([(i, (i + d) % n) for i in range(n) for d in (1, 7)])
    table, deg = plain.neighbor_table(n, edges)
    A = np.zeros((n, n))
    np.add.at(A, (edges[:, 0], edges[:, 1]), 1.0)
    np.add.at(A, (edges[:, 1], edges[:, 0]), 1.0)
    L = np.diag(A.sum(1)) - A
    v0 = rng.standard_normal(n)
    lmin, _ = plain.lanczos_ritz(plain.laplacian_op(table, deg), v0, n - 1)
    assert abs(lmin - np.linalg.eigvalsh(L)[1]) < 1e-9
    _, lmax = plain.lanczos_ritz(plain.adjacency_op(table), v0 - v0.mean(),
                                 n - 1)
    assert abs(lmax - np.sort(np.linalg.eigvalsh(A))[-2]) < 1e-9
