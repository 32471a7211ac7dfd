"""On-chip smoke test: the survey engines' main path on a TPU, checked
against references.

    python chip_smoke.py              # every phase, on one chip
    python chip_smoke.py --chips 4    # only the sharded batched Lanczos,
                                      # against the same stack on one device

Phases (one chip): the spmv kernel against ``spmv_ref`` at n = 65,536;
Lanczos rho_2 against the dense oracle and a Table-1 closed form; BFS
diameters, ECMP throughput and exact path counts against closed forms and
``benchmarks/baselines/BENCH_routing.json``; the round engine's telemetry
identities; a fault sweep's interlacing bound; and the ``BENCH_scale``
survey row, ``xpander(65536,32,0,0)``.

Each phase prints one line: its result, host-clock seconds (every result is
on the host, so the device work has finished), backend compile seconds and
the ``jit_trace/*`` counters it moved.  The last line of stdout is one JSON
object, ``{"ok": true, "device": {"platform", "kind", "count"}}``, printed
only when every phase met its reference.  Without a TPU, or on any failure,
the script exits non-zero and prints no such line.  Everything runs in this
one process, which holds the chip.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import obs  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_seconds = [0.0]


def _on_duration(event: str, seconds: float, **_) -> None:
    if event == _COMPILE_EVENT:
        _compile_seconds[0] += seconds


class PhaseFailed(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise PhaseFailed(what)


def run_phase(name: str, fn, *args) -> dict:
    """Run one phase; print its result line; re-raise its failure."""
    before = obs.counters("jit_trace/")
    c0, t0 = _compile_seconds[0], time.perf_counter()
    result = fn(*args)
    seconds = time.perf_counter() - t0
    line = dict(phase=name, result=result, seconds=seconds,
                compile_seconds=_compile_seconds[0] - c0,
                jit_trace=obs.counter_delta(before, prefix="jit_trace/"))
    print(json.dumps(line, default=float), flush=True)
    return result


def _random_operands(n: int, k: int, seed: int):
    rng = np.random.default_rng(seed)
    table = rng.integers(0, n, size=(n, k), dtype=np.int32)
    x = rng.standard_normal(n).astype(np.float32)
    loops = rng.integers(-1, 2, size=n).astype(np.float32)
    signs = rng.choice([-1.0, 1.0], size=(n, k)).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(table), jnp.asarray(loops), \
        jnp.asarray(signs)


def _timed(fn, *args, reps: int = 5) -> float:
    """Median host seconds of ``fn(*args)`` after one warm-up call."""
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def phase_kernel(n: int = 65536, k: int = 32, backend: str = "pallas") -> dict:
    """The Pallas spmv against ``spmv_ref`` on the device: plain, signed,
    bfloat16, with the median seconds of each.  The float32 sums of the same
    k terms, in different orders, agree to 1e-4 of the largest output;
    bfloat16 output is one rounding of the float32 sum, held to 2^-7."""
    from repro.kernels import spmv as KS

    x, tab, loops, signs = _random_operands(n, k, seed=0)
    plain = jax.jit(lambda x, t, w: KS.spmv(x, t, w, backend=backend))
    signed = jax.jit(lambda x, t, s: KS.spmv(x, t, signs=s, backend=backend))
    ref_plain = jax.jit(KS.spmv_ref)
    ref_signed = jax.jit(lambda x, t, s: KS.spmv_ref(x, t, signs=s))
    out = {}
    for name, ker, ref, args in (
            ("plain", plain, ref_plain, (x, tab, loops)),
            ("signed", signed, ref_signed, (x, tab, signs))):
        got, want = np.asarray(ker(*args)), np.asarray(ref(*args))
        err = float(np.max(np.abs(got - want)))
        check(err <= 1e-4 * max(1.0, float(np.max(np.abs(want)))),
              f"{name} kernel vs ref: max abs err {err}")
        out[name] = dict(max_abs_err=err, kernel_s=_timed(ker, *args),
                         ref_s=_timed(ref, *args))
    xb = x.astype(jnp.bfloat16)
    got = np.asarray(plain(xb, tab, loops).astype(jnp.float32))
    want = np.asarray(ref_plain(xb.astype(jnp.float32), tab, loops))
    err = float(np.max(np.abs(got - want)))
    check(err <= 2.0 ** -7 * float(np.max(np.abs(want))),
          f"bf16 kernel vs ref: max abs err {err}")
    out["bf16"] = dict(max_abs_err=err)
    out.update(n=n, k=k)
    return out


def phase_spectral() -> dict:
    """Lanczos rho_2 (dense path switched off) against the dense float64
    oracle on the host, and slimfly(13) against its Table-1 rho_2 = 13."""
    from repro.api import Analysis

    lanczos = Analysis("lps(13,5)", dense_threshold=100).rho2
    dense = Analysis("lps(13,5)").rho2
    check(abs(lanczos - dense) <= 1e-3,
          f"lps(13,5) rho2 lanczos {lanczos} vs dense {dense}")
    sf = Analysis("slimfly(13)", dense_threshold=100)
    want = sf.closed_forms["rho2_ub"]
    check(abs(sf.rho2 - want) <= 1e-3, f"slimfly(13) rho2 {sf.rho2} != {want}")
    return dict(lps_lanczos=lanczos, lps_dense=dense, slimfly=sf.rho2)


def phase_routing(specs=("slimfly(13)", "hypercube(8)", "torus(16,2)"),
                  torus_side: int = 32) -> dict:
    """Exact BFS diameters against the closed forms, uniform minimal-ECMP
    saturation throughput against the committed routing baseline, and the
    torus(32,2) antipodal path count, which float32 cannot hold."""
    from repro.api import Analysis, build
    from repro.core import routing as R

    base = json.loads((ROOT / "benchmarks/baselines/BENCH_routing.json")
                      .read_text())
    thpt_base = {r["spec"]: r["thpt_uniform"] for r in base["routing_table"]}
    out = {}
    for spec in specs:
        a = Analysis(spec)
        diam = a.routing().diameter
        check(diam == a.closed_forms["diameter"],
              f"{spec} diameter {diam} != {a.closed_forms['diameter']}")
        thpt = a.traffic("uniform").saturation_throughput
        check(round(thpt, 4) == thpt_base[spec],
              f"{spec} uniform throughput {thpt} != {thpt_base[spec]}")
        out[spec] = dict(diameter=diam, thpt_uniform=thpt)
    tab, _ = build(f"torus({torus_side},2)").gather_operands()
    sigma = R.shortest_path_counts(tab, R.bfs_distances(tab, sources=[0]))
    half = torus_side // 2
    want = 4 * math.comb(torus_side, half)
    got = sigma[0, half * torus_side + half]
    check(got == want, f"torus({torus_side},2) sigma {got} != {want}")
    out["torus_sigma"] = float(got)
    return out


def phase_simulate(spec: str = "slimfly(13)") -> dict:
    """Ring all-reduce with telemetry (round times reproduce the engine
    total) and uniform traffic with telemetry (peak round load equals the
    static ECMP maximum)."""
    from repro.api import Analysis

    a = Analysis(spec)
    ring = a.simulate("all_reduce", "ring", payload=[2 ** 20, 2 ** 26],
                      telemetry=True)
    total, engine = ring.telemetry.total_seconds(), float(ring.time_seconds[-1])
    check(math.isclose(total, engine, rel_tol=1e-4),
          f"sum(counts * round_seconds) {total} != engine {engine}")
    traffic = a.simulate("traffic", pattern="uniform", telemetry=True)
    peak = float(traffic.telemetry.round_max_link_load.max())
    static = a.traffic("uniform").max_link_load
    check(math.isclose(peak, static, rel_tol=1e-6),
          f"peak round load {peak} != static ECMP {static}")
    return dict(ring_seconds=[float(t) for t in ring.time_seconds],
                telemetry_total=total, peak_round_load=peak,
                static_max_load=static)


def phase_faults(spec: str = "slimfly(13)", samples: int = 32) -> dict:
    """Link-fault sweep: removing links never raises rho_2 (interlacing)."""
    from repro.api import Analysis

    res = Analysis(spec).fault_sweep(rates=(0.05, 0.1), samples=samples)
    worst = max(r["rho2_max"] for r in res.rows)
    check(worst <= res.rho2_healthy + 1e-3,
          f"degraded rho2 {worst} > healthy {res.rho2_healthy}")
    return dict(rho2_healthy=res.rho2_healthy,
                rho2_mean=[r["rho2_mean"] for r in res.rows],
                rho2_max=[r["rho2_max"] for r in res.rows])


def phase_scale(spec: str = None, nodes: int = None,
                sources: int = None) -> dict:
    """The ``BENCH_scale`` survey row: build the xpander (signed batched
    Lanczos), chunked-Lanczos rho_2, sampled routing with its bootstrap CI,
    corrected uniform traffic.  rho_2 must sit within 1% of the Ramanujan
    value k - 2 sqrt(k - 1)."""
    from benchmarks import scale_bench as SB
    from repro.api import survey

    spec = spec or SB.SCALE_SPEC
    nodes = nodes or SB.SCALE_NODES
    sources = sources or SB.SCALE_SOURCES
    row = survey([spec], SB.COLUMNS,
                 routing=dict(pattern="uniform",
                              sample_fraction=sources / nodes, seed=0)).rows[0]
    check(row["nodes"] == nodes, f"row has {row['nodes']} nodes")
    check(SB.DIAMETER_LB_FLOOR <= row["diameter_lb"] <= row["diameter_bfs"],
          f"diameter_lb {row['diameter_lb']} outside "
          f"[{SB.DIAMETER_LB_FLOOR}, {row['diameter_bfs']}]")
    lo, hi = row["avg_hops_ci"]
    check(lo <= row["avg_hops"] <= hi,
          f"avg_hops {row['avg_hops']} outside CI {row['avg_hops_ci']}")
    k = row["radix"]
    ramanujan = k - 2 * math.sqrt(k - 1)
    check(abs(row["rho2"] - ramanujan) <= 0.01 * ramanujan,
          f"rho2 {row['rho2']} not within 1% of {ramanujan}")
    stats = jax.devices()[0].memory_stats() or {}
    return dict(rho2=row["rho2"], ramanujan_rho2=ramanujan,
                diameter_lb=row["diameter_lb"],
                diameter_bfs=row["diameter_bfs"], avg_hops=row["avg_hops"],
                avg_hops_ci=row["avg_hops_ci"],
                saturation_throughput=row["saturation_throughput"],
                peak_bytes_in_use=stats.get("peak_bytes_in_use"))


def phase_sharded(spec: str = "lps(13,5)", B: int = 64, rate: float = 0.05,
                  iters: int = 160) -> dict:
    """``rho2_laplacian_batched`` on a B-sample link-fault stack, split over
    every device by ``shard_batch``, against the same stack with every
    operand on ``jax.devices()[0]`` — on the default spmv backend and on the
    Pallas kernel, which jit cannot partition without ``over_batch``."""
    from repro.api import build
    from repro.core import faults as F
    from repro.core import spectral as S
    from repro.kernels import spmv as KS
    from repro.launch import mesh as M

    topo = build(spec)
    degraded = [F.apply_faults(topo, F.make_scenario(topo, "link", rate,
                                                     seed=7919 * i))
                for i in range(B)]
    tabs, ws, degs = F.stacked_operands(degraded)
    ndev = len(jax.devices())
    mesh = M.batch_mesh(B)
    check(mesh is not None, f"B={B} does not split over {ndev} devices")
    placed = M.shard_batch(mesh, jnp.asarray(tabs))
    spans = len(placed.sharding.device_set)
    check(spans == ndev, f"operands span {spans} of {ndev} devices")
    out = dict(B=B, devices=ndev, operand_devices=spans)
    for backend in dict.fromkeys((KS.default_backend(), "pallas")):
        sharded = S.rho2_laplacian_batched(tabs, ws, degs, iters=iters,
                                           backend=backend)
        single = S.rho2_laplacian_batched(tabs, ws, degs, iters=iters,
                                          backend=backend,
                                          devices=jax.devices()[:1])
        err = float(np.max(np.abs(sharded - single)))
        check(err <= 1e-5, f"{backend}: sharded vs one-device rho2 differ "
                           f"by {err}")
        out[backend] = dict(max_abs_diff=err,
                            rho2_mean=float(np.mean(sharded)))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded batched-Lanczos phase")
    args = ap.parse_args(argv)
    cache = enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    if len(jax.devices()) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(jax.devices())} devices", file=sys.stderr)
        return 2
    print(json.dumps(dict(compile_cache=cache)), flush=True)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    phases = [("sharded_lanczos", phase_sharded)] if args.chips == 4 else [
        ("kernel_parity", phase_kernel), ("spectral", phase_spectral),
        ("routing", phase_routing), ("simulate", phase_simulate),
        ("faults", phase_faults), ("scale_row", phase_scale)]
    try:
        for name, fn in phases:
            run_phase(name, fn)
    except PhaseFailed as e:
        print(f"chip_smoke: phase failed: {e}", file=sys.stderr)
        return 1
    print(json.dumps(dict(ok=True, device=dict(
        platform=dev.platform, kind=dev.device_kind,
        count=len(jax.devices())))))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
