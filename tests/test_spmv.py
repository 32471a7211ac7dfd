"""The universal padded gather-table spmv: parity, dispatch, kernel routing.

Parity: ``spmv_padded`` (interpret-mode Pallas on CPU) vs ``spmv_ref`` vs the
dense adjacency oracle across dtypes, ragged block_rows, signed operands, and
loop-regularized irregular graphs.  Dispatch: backend resolution order and the
``use_backend`` override.  Routing: trace-count proofs — read from the
``spmv/pallas_trace`` counter of :mod:`repro.obs` — that the spectral /
faults / synthesis / simulate engines actually apply their matvecs through
the kernel under the kernel backend, and stay on the reference path under
``ref`` (the default).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import spectral as S
from repro.core import topologies as T
from repro.kernels import spmv as KS

RNG = np.random.default_rng(7)


def _random_regular(n, k, seed=0):
    return T.random_regular(n, k, seed=seed)


# --------------------------------------------------------------------------
# parity: kernel vs reference vs dense
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n,k,block", [(30, 4, 8), (64, 6, 64), (50, 3, 16),
                                       (128, 8, 33)])
def test_spmv_padded_matches_ref_and_dense(n, k, block):
    g = _random_regular(n, k)
    tab, w = g.gather_operands()
    x = RNG.standard_normal(n).astype(np.float32)
    want = g.adjacency() @ x
    ref = KS.spmv_ref(jnp.asarray(x), jnp.asarray(tab, jnp.int32),
                      jnp.asarray(w, jnp.float32))
    ker = KS.spmv_padded(jnp.asarray(x), jnp.asarray(tab, jnp.int32),
                         jnp.asarray(w, jnp.float32), block_rows=block,
                         interpret=True)
    np.testing.assert_allclose(np.asarray(ref), want, atol=1e-4)
    np.testing.assert_allclose(np.asarray(ker), want, atol=1e-4)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("block", [7, 16, 40])
def test_spmv_padded_ragged_blocks(block):
    """n not divisible by block_rows: padded rows must be sliced off."""
    g = _random_regular(40, 4, seed=3)
    tab, w = g.gather_operands()
    x = RNG.standard_normal(40).astype(np.float32)
    ref = np.asarray(KS.spmv_ref(jnp.asarray(x), jnp.asarray(tab, jnp.int32),
                                 jnp.asarray(w, jnp.float32)))
    ker = np.asarray(KS.spmv_padded(
        jnp.asarray(x), jnp.asarray(tab, jnp.int32),
        jnp.asarray(w, jnp.float32), block_rows=block, interpret=True))
    assert ker.shape == (40,)
    np.testing.assert_allclose(ker, ref, atol=1e-5)


def test_spmv_padded_bfloat16():
    g = _random_regular(32, 4, seed=1)
    tab, _ = g.gather_operands()
    x = jnp.asarray(RNG.standard_normal(32), jnp.bfloat16)
    ref = KS.spmv_ref(x.astype(jnp.float32), jnp.asarray(tab, jnp.int32))
    ker = KS.spmv_padded(x, jnp.asarray(tab, jnp.int32), block_rows=16,
                         interpret=True)
    assert ker.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(ker, dtype=np.float32),
                               np.asarray(ref), atol=0.15)


def test_spmv_padded_loop_regularized_irregular_graph():
    """Self-padded table + negative compensation weights: exact adjacency on
    an irregular graph (the gather_operands contract)."""
    g = T.data_vortex(4, 3)            # irregular, loop-regularized family
    tab, w = g.gather_operands()
    x = RNG.standard_normal(g.n).astype(np.float32)
    want = g.adjacency() @ x
    ker = np.asarray(KS.spmv_padded(
        jnp.asarray(x), jnp.asarray(tab, jnp.int32),
        jnp.asarray(w, jnp.float32), block_rows=16, interpret=True))
    np.testing.assert_allclose(ker, want, atol=1e-4)


def test_spmv_signed_matches_ref_and_dense():
    """Per-slot signs: the Bilu–Linial signed adjacency through both paths."""
    from repro.core.synthesis import signed_slot_operands

    g = _random_regular(24, 4, seed=5)
    table, edge_slot = signed_slot_operands(g)
    signing = RNG.choice([-1.0, 1.0], size=g.m)
    sg = signing[edge_slot].astype(np.float32)
    # dense signed adjacency oracle
    A = np.zeros((g.n, g.n))
    for (u, v), s in zip(g.edges, signing):
        A[u, v] += s
        A[v, u] += s
    x = RNG.standard_normal(g.n).astype(np.float32)
    want = A @ x
    ref = np.asarray(KS.spmv_ref(jnp.asarray(x), jnp.asarray(table, jnp.int32),
                                 signs=jnp.asarray(sg)))
    ker = np.asarray(KS.spmv_padded(
        jnp.asarray(x), jnp.asarray(table, jnp.int32), None,
        jnp.asarray(sg), block_rows=8, interpret=True))
    np.testing.assert_allclose(ref, want, atol=1e-4)
    np.testing.assert_allclose(ker, want, atol=1e-4)


def test_spmv_dispatcher_and_matvec_agree():
    g = _random_regular(48, 5, seed=2)
    tab, w = g.gather_operands()
    x = jnp.asarray(RNG.standard_normal(48), jnp.float32)
    a = KS.spmv(x, jnp.asarray(tab, jnp.int32), jnp.asarray(w, jnp.float32),
                backend="ref")
    b = KS.spmv(x, jnp.asarray(tab, jnp.int32), jnp.asarray(w, jnp.float32),
                backend="pallas_interpret")
    mv = KS.spmv_matvec(tab, w, backend="ref")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    np.testing.assert_allclose(np.asarray(mv(x)), np.asarray(a), atol=1e-6)


# --------------------------------------------------------------------------
# backend resolution
# --------------------------------------------------------------------------

def test_backend_resolution_order(monkeypatch):
    monkeypatch.delenv("REPRO_SPMV_BACKEND", raising=False)
    # auto: ref on every platform (the kernel is opt-in; see spmv docstring)
    assert KS.default_backend() == "ref"
    assert KS.resolve_backend() == "ref"
    # env overrides auto
    monkeypatch.setenv("REPRO_SPMV_BACKEND", "pallas_interpret")
    assert KS.resolve_backend() == "pallas_interpret"
    # context override beats env
    with KS.use_backend("ref"):
        assert KS.resolve_backend() == "ref"
        # explicit argument beats everything
        assert KS.resolve_backend("pallas_interpret") == "pallas_interpret"
    assert KS.resolve_backend() == "pallas_interpret"   # env restored


def test_backend_validation():
    with pytest.raises(ValueError):
        KS.resolve_backend("nope")
    with pytest.raises(ValueError):
        with KS.use_backend("nope"):
            pass


def test_kernel_backend_is_interpret_on_cpu():
    if jax.default_backend() == "cpu":
        assert not KS.pallas_supported()
        assert KS.kernel_backend() == "pallas_interpret"
        assert KS.default_backend() in ("ref", "pallas_interpret")
    else:                                          # pragma: no cover
        assert KS.kernel_backend() == "pallas"


# --------------------------------------------------------------------------
# engines route through the kernel (trace-count proofs) and fall back to ref
# --------------------------------------------------------------------------

def _pallas_traces(fn, backend):
    """Kernel traces caused by fn() under ``backend``, from cold caches (a
    cache hit replays a compiled trace without re-tracing), read from the
    ``spmv/pallas_trace`` counter of :mod:`repro.obs`."""
    with KS.use_backend(backend):               # clears jit caches on entry
        before = obs.counters()
        fn()
        return obs.counter_delta(before).get("spmv/pallas_trace", 0)


def _count_traces(fn):
    return _pallas_traces(fn, KS.kernel_backend())


def _count_ref(fn):
    return _pallas_traces(fn, "ref")


def test_spectral_routes_through_kernel():
    g = T.hypercube(5)
    assert _count_traces(lambda: S.rho2_lanczos(g, iters=20, seed=0)) > 0
    assert _count_ref(lambda: S.rho2_lanczos(g, iters=20, seed=0)) == 0


def test_batched_spectral_routes_through_kernel():
    g = T.hypercube(4)
    tab = g.neighbor_table()
    tabs = np.stack([tab] * 3)
    ws = np.zeros((3, g.n), np.float32)
    degs = np.full((3, g.n), 4.0, np.float32)

    def run():
        S.rho2_laplacian_batched(tabs, ws, degs, iters=12, seed=0)

    assert _count_traces(run) > 0
    assert _count_ref(run) == 0


def test_faults_route_through_kernel():
    from repro.core.faults import fault_sweep

    g = T.hypercube(4)

    def run():
        fault_sweep(g, rates=[0.05], model="link", samples=2, seed=0,
                    iters=12)

    assert _count_traces(run) > 0
    assert _count_ref(run) == 0


def test_synthesis_routes_through_kernel():
    from repro.core.synthesis import best_signing_batched

    g = T.petersen()

    def run():
        best_signing_batched(g, batch=3, steps=2, est_iters=4, iters=10,
                             seed=0)

    assert _count_traces(run) > 0
    assert _count_ref(run) == 0


def test_simulate_routes_through_kernel():
    from repro.core.simulate import simulate_collective

    g = T.torus(3, 2)

    def run():
        simulate_collective(g, "all_reduce", "ring", payloads=(1 << 16,))

    assert _count_traces(run) > 0
    assert _count_ref(run) == 0


def test_routing_sigma_routes_through_kernel():
    """The sigma DP is float64, which the float32-only kernel refuses: it
    goes through the spmv dispatcher on ``ref`` whatever the ambient
    backend, chosen by dtype in code."""
    from repro.core.routing import analyze_routing

    g = T.torus(3, 2)
    with KS.use_backend(KS.kernel_backend()):
        before = obs.counters()
        analyze_routing(g)
        delta = obs.counter_delta(before)
    assert delta.get("spmv/pallas_trace", 0) == 0
    assert delta.get("spmv/dispatch/ref", 0) > 0
    assert _count_ref(lambda: analyze_routing(g)) == 0


def test_kernel_refuses_float64():
    g = _random_regular(16, 4)
    tab = jnp.asarray(g.gather_operands()[0], jnp.int32)
    with jax.enable_x64(True):
        with pytest.raises(TypeError, match="float64"):
            KS.spmv_padded(jnp.zeros(16, jnp.float64), tab)


def test_traffic_routes_through_kernel():
    from repro.core.routing import analyze_routing
    from repro.core.traffic import evaluate_traffic

    g = T.torus(3, 2)

    def run():
        evaluate_traffic(g, "uniform", routing=analyze_routing(g))

    assert _count_traces(run) > 0
    assert _count_ref(run) == 0


def test_kernel_and_ref_agree_on_rho2():
    g = T.petersen_torus(3, 3)
    with KS.use_backend(KS.kernel_backend()):
        a = S.rho2_lanczos(g, iters=60, seed=0)
    with KS.use_backend("ref"):
        b = S.rho2_lanczos(g, iters=60, seed=0)
    assert a == pytest.approx(b, abs=1e-4)
