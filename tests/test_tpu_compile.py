"""Compile the main path's kernels for a TPU v5e that is described, not
attached: what Mosaic or XLA:TPU would refuse fails here, at no chip time.

Nothing runs, so these tests say nothing about results or times; the chip
run (``chip_smoke.py``) checks those.  The topology is described inside a
fixture, never at import: only one process at a time may load the TPU
library, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import spectral as S
from repro.kernels import spmv as KS


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs to /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep the cache out of these tests."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _plain(x, t):
    return KS.spmv_padded(x, t, interpret=False)


def _with_loops(x, t, w):
    return KS.spmv_padded(x, t, w, interpret=False)


def _signed(x, t, s):
    return KS.spmv_padded(x, t, None, s, interpret=False)


def _vmapped_signed(xs, t, ss):
    """The form ``_signed_lanczos_batched`` applies: one shared table,
    per-sample signs and vectors."""
    return jax.vmap(lambda x, s: _signed(x, t, s))(xs, ss)


F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32

KERNEL_CASES = {
    "plain-2184x6": (_with_loops, [((2184,), F32), ((2184, 6), I32),
                                   ((2184,), F32)]),
    "plain-65536x32": (_with_loops, [((65536,), F32), ((65536, 32), I32),
                                     ((65536,), F32)]),
    "signed-4096x16": (_signed, [((4096,), F32), ((4096, 16), I32),
                                 ((4096, 16), F32)]),
    "bf16-2184x6": (_plain, [((2184,), BF16), ((2184, 6), I32)]),
    "vmapped-signed-4x65536x32": (_vmapped_signed, [
        ((4, 65536), F32), ((65536, 32), I32), ((4, 65536, 32), F32)]),
}


@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_spmv_kernel_compiles_for_v5e(case, one_chip):
    fn, shapes = KERNEL_CASES[case]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("which", ["tpu-default", "pallas"])
def test_lap_lanczos_tile_compiles_on_tpu_default_backend(which, one_chip,
                                                          monkeypatch):
    """One batched Laplacian Lanczos tile at n = 65,536, k = 32, B = 4 on
    the backend the dispatcher picks on a TPU, and on the kernel."""
    monkeypatch.delenv("REPRO_SPMV_BACKEND", raising=False)
    backend = KS.default_backend() if which == "tpu-default" else which
    B, n, k = 4, 65536, 32

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = S._lap_lanczos_batched.lower(
        sds((B, n, k), I32), sds((B, n), F32), sds((B, n), F32),
        sds((B, n), F32), 160, backend=backend).compile()
    if backend == "pallas":
        assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("B,n,k,m", [(32, 4096, 6, 200), (4, 65536, 32, 160)])
def test_lap_lanczos_shared_table_compiles(B, n, k, m, one_chip):
    """The batched Laplacian Lanczos through one (n, k) table shared by the
    batch, with per-sample slot counts, gathers rows of the batch: the fault
    sweep's tile, and a datacenter-size one."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = S._lap_lanczos_batched.lower(
        sds((n, k), I32), sds((B, n), F32), sds((B, n), F32),
        sds((B, n), F32), m, backend="ref",
        counts=sds((B, n, k), F32)).compile()
    assert f"slice_sizes={{{B},1}}" in compiled.as_text()   # rows of B


def test_pod_pair_lowering_compiles_at_every_pair_count(one_chip):
    """The ECMP pass over node pairs at the DeepSeek-V3 pod cell's size: a
    chunk of 512 sources of torus(16,3), at each power-of-two pair count
    that a chunk of one of its phases pads to."""
    import numpy as np

    from repro.core import traffic as T
    from repro.core import workloads as W
    from repro.core.placement import place_ranks

    n, k, S = 4096, 6, 512
    plan = W.plan_workload("deepseek_v3@pp=16,dp=256,ep=64,"
                           "shape=train_4k_gb15360")
    node_of = place_ranks(n, plan.world, strategy="linear")
    caps = set()
    for phase in plan.phases:
        u, _, _, _ = W._phase_pairs(
            phase, W._phase_groups(plan, phase.group_axis), node_of)
        per_chunk = np.bincount(u // S, minlength=n // S)
        caps |= {1 << int(c - 1).bit_length() for c in per_chunk if c}
    assert caps

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    for cap in sorted(caps):
        T._ecmp_pair_loads_chunk.lower(
            sds((n, k), I32), sds((S, n), I32), sds((S, n), F32),
            sds((cap,), I32), sds((cap,), I32), sds((cap,), F32)).compile()
