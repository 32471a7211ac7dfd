"""A whole run (no chip check) with the timed path broken underneath: the
check has to come out not correct, once for each fault a cell can have."""
from __future__ import annotations

import sys

import jax
import numpy as np
import pytest

import benchtiny

sys.path.insert(0, str(benchtiny.REPO / "bench"))
import run  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchtiny.make_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture
def fresh_traces():
    """Jitted programs trace the patched code only after a cache clear."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def _identity_operator(monkeypatch):
    """A step that returns its state unchanged: the operator is the identity."""
    from repro.kernels import spmv as KS

    monkeypatch.setattr(KS, "spmv_matvec",
                        lambda table, loops=None, backend=None: (lambda x: x))
    monkeypatch.setattr(KS, "spmv", lambda x, table, loops=None, **kw: x)


def _half_sources(monkeypatch):
    """Half the batch of BFS sources left out, the other half counted twice."""
    from repro.core import routing as R

    orig = R.bfs_distances

    def half(table, sources=None, chunk=R.DEFAULT_SOURCE_CHUNK):
        src = np.asarray(sources)
        d = orig(table, src[: max(1, src.size // 2)], chunk)
        return np.concatenate([d, d])[: src.size]

    monkeypatch.setattr(R, "bfs_distances", half)


def _hop_altered(monkeypatch):
    """One answer altered where it is produced: a BFS distance off by one."""
    from repro.core import routing as R

    orig = R.bfs_distances

    def altered(*a, **kw):
        d = orig(*a, **kw)
        d[0, np.flatnonzero(d[0] > 0)[0]] += 1
        return d

    monkeypatch.setattr(R, "bfs_distances", altered)


def _rho2_altered(monkeypatch):
    """One answer altered where it is produced: lambda_max off by 1e-3."""
    from repro.core import spectral as S

    orig = S.lanczos_extremes
    monkeypatch.setattr(S, "lanczos_extremes",
                        lambda *a, **kw: (orig(*a, **kw)[0] + 1e-3,
                                          orig(*a, **kw)[1]))


def _half_batch(monkeypatch):
    """Half of the degraded samples solved, the mean taken over them."""
    from repro.core import spectral as S

    orig = S.rho2_laplacian_batched

    def half(tables, weights, degs, *a, **kw):
        h = max(1, len(tables) // 2)
        r = orig(tables[:h], weights[:h], degs[:h], *a, **kw)
        return np.full(len(tables), r.mean())

    monkeypatch.setattr(S, "rho2_laplacian_batched", half)


def _no_exchange(monkeypatch):
    """The exchange between chips left out: every chip's slice of the batch
    is replaced by the first chip's, as if no result crossed between them."""
    from repro.launch import mesh as M

    def local_only(fn, mesh, shared=0):
        def call(*args):
            quarter = [a if i < shared else a[: max(1, a.shape[0] // 4)]
                       for i, a in enumerate(args)]
            out = fn(*quarter)
            reps = -(-args[shared].shape[0] // quarter[shared].shape[0])
            return jax.tree.map(
                lambda o: jax.numpy.concatenate([o] * reps)[
                    : args[shared].shape[0]], out)
        return call

    monkeypatch.setattr(M, "over_batch", local_only)


def _connectivity_altered(monkeypatch):
    """One answer altered where it is produced: a component count off by one."""
    from repro.core import faults as F

    orig = F.connected_component_count
    monkeypatch.setattr(F, "connected_component_count",
                        lambda n, edges: orig(n, edges) + 1)


FAULTS = {
    benchtiny.SURVEY: [_identity_operator, _half_sources, _hop_altered,
                       _rho2_altered],
    benchtiny.SWEEP: [_identity_operator, _half_batch, _no_exchange,
                      _connectivity_altered],
}


@pytest.mark.parametrize("name", sorted(FAULTS))
def test_sound_run_is_correct(root, name, fresh_traces):
    res = run.run_cell(name, 21, 0.1, False, root=root)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("name,fault", [(n, f) for n in sorted(FAULTS)
                                        for f in FAULTS[n]],
                         ids=lambda v: getattr(v, "__name__", v))
def test_broken_run_is_not_correct(root, name, fault, monkeypatch,
                                   fresh_traces):
    fault(monkeypatch)
    res = run.run_cell(name, 21, 0.1, False, root=root)
    assert not res["correct"], res["checks"]
