"""The observability substrate: spans, counters, telemetry, recompile hygiene.

Four layers of coverage:

* ``repro.obs`` primitives — counters + deltas, span nesting and the
  disabled-path no-op, ``tracing()`` buffer semantics, Chrome-trace JSON,
  ``render_tree``, and the phase interval-union of ``metrics_report``; spans
  on a ``jax.profiler`` trace's host plane, JAX's trace / lower / compile
  steps recorded as spans, and an import with no ``jax``.
* Per-round simulator telemetry — ``RoundTelemetry`` arrays from
  ``run_schedule(telemetry=True)`` / ``Analysis.simulate(telemetry=True)``:
  the max over rounds of the per-unit-payload link load must equal the
  static ECMP ``max_link_load`` on uniform traffic (the ISSUE-10 acceptance
  identity, checked on 3+ families), and ``sum(counts * round_seconds)``
  must reproduce the engine's measured completion time.
* Recompile hygiene — a survey over small instances of the nine bench
  families must trigger exactly one batched solve per same-shape engine
  group (pins the PR-1 batching), and re-running an identical survey must
  add NO jit traces beyond the per-instance fresh-closure Lanczos solves
  (pins the PR-8 trace-time backend resolution via counters, not probes).
* Backend-dispatch counters — ``spmv/matvec/<backend>`` replaces the old
  monkey-patch call counting.
"""
import json
import os
import pathlib
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.api import survey
from repro.api.analysis import Analysis
from repro.api.registry import build
from repro.core import topologies as T
from repro.core.simulate import RoundTelemetry, compile_schedule, run_schedule

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"


# --------------------------------------------------------------------------
# counters
# --------------------------------------------------------------------------

def test_count_and_delta():
    before = obs.counters()
    obs.count("test/x")
    obs.count("test/x", 4)
    obs.count("test/y")
    d = obs.counter_delta(before)
    assert d["test/x"] == 5 and d["test/y"] == 1
    assert obs.counter_delta(before, prefix="test/x") == {"test/x": 5}
    # unchanged counters never appear in a delta
    assert "test/x" not in obs.counter_delta(obs.counters())


def test_counters_prefix_filter():
    obs.count("pfx/a")
    obs.count("other/b")
    snap = obs.counters("pfx/")
    assert "pfx/a" in snap and all(k.startswith("pfx/") for k in snap)


# --------------------------------------------------------------------------
# spans
# --------------------------------------------------------------------------

def test_span_disabled_is_shared_noop():
    obs.disable()
    s1 = obs.span("a")
    s2 = obs.span("b", phase="execute")
    assert s1 is s2                      # the shared null object
    with s1:
        pass
    assert obs.trace_events() == [] or all(
        e["name"] not in ("a", "b") for e in obs.trace_events())


def test_span_nesting_depth_and_tags():
    with obs.tracing():
        obs.reset_spans()
        with obs.span("outer", phase="build", family="petersen"):
            with obs.span("inner", phase="build"):
                pass
        evs = obs.trace_events()
    names = {e["name"]: e for e in evs}
    assert set(names) == {"outer", "inner"}
    assert names["inner"]["args"]["depth"] == 1
    assert names["outer"]["args"]["depth"] == 0
    assert names["outer"]["args"]["family"] == "petersen"
    # the inner interval lies within the outer one
    o, i = names["outer"], names["inner"]
    assert o["ts"] <= i["ts"]
    assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1e-3


def test_traced_decorator_and_enable_toggle():
    @obs.traced("test/fn", phase="execute", kind="unit")
    def fn(x):
        return x + 1

    obs.disable()
    obs.reset_spans()
    assert fn(1) == 2
    assert obs.trace_events() == []      # disabled: no recording
    with obs.tracing():
        assert fn(2) == 3
        evs = obs.trace_events()
    assert [e["name"] for e in evs] == ["test/fn"]
    assert evs[0]["args"]["kind"] == "unit"
    assert evs[0]["cat"] == "execute"


def test_tracing_writes_chrome_trace_json(tmp_path):
    path = tmp_path / "trace.json"
    with obs.tracing(path):
        with obs.span("root", phase="build"):
            with obs.span("child"):
                pass
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert {e["name"] for e in evs} == {"root", "child"}
    for e in evs:                        # Chrome trace-event "X" schema
        assert e["ph"] == "X"
        assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
        assert e["pid"] == 1 and "tid" in e and "args" in e


def test_tracing_nesting_outermost_owns_buffer():
    with obs.tracing():
        with obs.span("before"):
            pass
        with obs.tracing():              # inner: must NOT clear the buffer
            with obs.span("within"):
                pass
        assert {e["name"] for e in obs.trace_events()} >= {"before", "within"}
        assert obs.enabled()             # inner exit must not disable
    assert not obs.enabled()


def test_render_tree_indents_by_depth():
    with obs.tracing():
        obs.reset_spans()
        with obs.span("parent", phase="execute"):
            with obs.span("child", instance="petersen"):
                pass
    txt = obs.render_tree()
    lines = txt.splitlines()
    assert lines[0].startswith("parent")
    assert lines[1].startswith("  child")
    assert "instance=petersen" in lines[1]


def test_metrics_report_phases_interval_union():
    """Nested same-phase spans must not double-count phase seconds."""
    with obs.tracing():
        obs.reset_spans()
        with obs.span("outer", phase="execute"):
            with obs.span("inner", phase="execute"):
                pass
    rep = obs.metrics_report()
    outer = rep.spans["outer"].total_seconds
    inner = rep.spans["inner"].total_seconds
    assert rep.phases["execute"] <= outer + 1e-9     # union, not sum
    assert rep.phases["execute"] >= inner
    d = rep.to_dict()
    assert set(d) == {"spans", "phases", "counters", "peak_rss_kb"}
    json.dumps(d)                        # JSON-clean
    assert "peak RSS" in rep.report()


def test_peak_rss_is_positive_high_water():
    assert obs.peak_rss_kb() > 0


# --------------------------------------------------------------------------
# spans on the device trace's clock, and JAX's compile pipeline as spans
# --------------------------------------------------------------------------

def test_span_appears_on_the_profiler_host_plane(tmp_path):
    from jax.profiler import ProfileData

    name = "test/annotated_span"
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.tracing():
            with obs.span(name, step=1):
                time.sleep(0.02)
            ev = [e for e in obs.trace_events() if e["name"] == name]
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    host = [e for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host") for line in plane.lines
            for e in line.events if e.name == name]
    assert len(ev) == 1 and len(host) == 1
    assert abs(host[0].duration_ns / 1e6 - ev[0]["dur"] / 1e3) < 1.0


def test_fresh_jit_records_trace_lower_and_compile_spans():
    x = jnp.arange(7.0)
    with obs.tracing():
        obs.reset_spans()
        with obs.span("outer"):
            jax.jit(lambda v: v * 3.0 + 2.0)(x).block_until_ready()
        evs = obs.trace_events()
    jax_evs = [e for e in evs if e["name"].startswith("jax/")]
    assert {"jax/trace", "jax/lower", "jax/compile"} <= {
        e["name"] for e in jax_evs}
    outer = next(e for e in evs if e["name"] == "outer")
    for e in jax_evs:
        assert e["args"]["depth"] == 1 and e["args"]["fun"]
        # nested in the span open at the time, on its clock
        assert outer["ts"] - 1e3 <= e["ts"]
        assert e["ts"] + e["dur"] <= outer["ts"] + outer["dur"] + 1e3
    assert any(e["args"]["fun"] == "<lambda>" for e in jax_evs
               if e["name"] == "jax/trace")
    obs.disable()
    obs.reset_spans()
    jax.jit(lambda v: v * 5.0 - 1.0)(x).block_until_ready()
    assert obs.trace_events() == []        # disabled: the listener is idle


def test_jit_cache_hit_records_no_trace_span():
    f = jax.jit(lambda v: v * 7.0 + 1.0)
    x = jnp.arange(5.0)
    f(x).block_until_ready()               # traced and compiled here
    with obs.tracing():
        obs.reset_spans()
        with obs.span("hit"):
            f(x).block_until_ready()
        names = [e["name"] for e in obs.trace_events()]
    assert names == ["hit"]


def test_obs_imports_and_traces_without_jax():
    code = ("import sys; sys.modules['jax'] = None\n"
            "from repro import obs\n"
            "with obs.tracing():\n"
            "    with obs.span('a', n=1):\n"
            "        pass\n"
            "assert [e['name'] for e in obs.trace_events()] == ['a']\n"
            "assert 'jax' not in [m for m in sys.modules if sys.modules[m]]\n")
    env = dict(os.environ, PYTHONPATH=str(SRC))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr


# --------------------------------------------------------------------------
# per-round telemetry (the tentpole acceptance identity)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("spec", ["petersen", "hypercube(5)", "torus(6,2)"])
def test_telemetry_max_round_load_matches_static_ecmp(spec):
    """max over rounds of the per-unit-payload link load == the static ECMP
    ``max_link_load`` on uniform traffic (same demand, same lowering)."""
    a = Analysis(build(spec))
    sim = a.simulate("traffic", pattern="uniform", telemetry=True)
    tel = sim.telemetry
    assert isinstance(tel, RoundTelemetry)
    static = a.traffic("uniform").max_link_load
    assert np.isclose(tel.round_max_link_load.max(), static, rtol=1e-6)
    # 1 / max load is the saturation throughput both layers report
    assert np.isclose(1.0 / tel.round_max_link_load.max(),
                      sim.saturation_throughput, rtol=1e-6)


def test_telemetry_round_times_reproduce_engine_total():
    """sum(counts * round_seconds) == the engine's measured completion time
    at the telemetry payload (the straggler-hop breakdown is exact)."""
    g = T.torus(4, 2)
    sched = compile_schedule(g, "all_reduce", "ring")
    res = run_schedule(sched, payloads=(1 << 16, 1 << 24), telemetry=True)
    tel = res.telemetry
    assert tel.payload_bytes == float(1 << 24)       # largest of the sweep
    assert np.isclose(tel.total_seconds(), res.time_seconds[-1], rtol=1e-4)
    assert tel.unique_rounds == sched.unique_rounds
    assert np.array_equal(tel.counts, sched.counts)
    assert np.array_equal(tel.hops, sched.hops)
    # breakdown: round = bandwidth term + latency term, utilization in (0, 1]
    np.testing.assert_allclose(
        tel.round_seconds, tel.round_bw_seconds + tel.round_latency_seconds)
    assert ((tel.round_util_max > 0) & (tel.round_util_max <= 1.0)).all()
    assert (tel.round_util_mean <= tel.round_util_max + 1e-12).all()


def test_telemetry_argmax_link_is_a_real_link():
    g = T.petersen()
    sched = compile_schedule(g, "broadcast", "bfs_tree")
    res = run_schedule(sched, telemetry=True)
    node, slot = res.telemetry.argmax_link()
    tab, _ = g.gather_operands()
    assert 0 <= node < g.n and 0 <= slot < tab.shape[1]
    u = int(res.telemetry.round_max_link_load.argmax())
    assert sched.round_bytes[u, node, slot] == sched.round_bytes[u].max()


def test_telemetry_off_by_default_and_cached_separately():
    a = Analysis(build("petersen"))
    plain = a.simulate("traffic", pattern="uniform")
    assert plain.telemetry is None
    teled = a.simulate("traffic", pattern="uniform", telemetry=True)
    assert teled.telemetry is not None
    assert plain is not teled            # cache keys on the telemetry flag
    assert plain is a.simulate("traffic", pattern="uniform")
    d = teled.to_dict()
    assert d["telemetry"]["unique_rounds"] == teled.telemetry.unique_rounds
    json.dumps(d)


def test_telemetry_through_collective_driver():
    sim = Analysis(build("hypercube(4)")).simulate(
        "all_reduce", "ring", telemetry=True)
    tel = sim.telemetry
    assert tel is not None
    assert int(tel.counts.sum()) == sim.rounds


# --------------------------------------------------------------------------
# recompile hygiene over the nine bench families (satellite: one trace per
# same-shape engine group; counters replace the old monkey-patch probes)
# --------------------------------------------------------------------------

#: small instances of the nine benchmark families of
#: benchmarks/collective_sim.py (same constructors, test-sized parameters).
BENCH_FAMILIES_SMALL = [
    "lps(5,13)", "slimfly(5)", "torus(4,2)", "hypercube(4)", "ccc(3)",
    "butterfly(2,3)", "petersen_torus(3,3)", "dragonfly",
    "xpander(32,4,0,40)",
]


def _survey_nine():
    return survey(BENCH_FAMILIES_SMALL, columns=["instance", "nodes", "rho2"],
                  dense_threshold=8, lanczos_iters=40)


def test_nine_families_cover_the_bench_specs():
    import pathlib
    src = pathlib.Path(__file__).resolve().parents[1] \
        / "benchmarks" / "collective_sim.py"
    text = src.read_text()
    for spec in BENCH_FAMILIES_SMALL:
        fam = spec.split("(")[0]
        assert fam in text, f"family {fam} not in the bench spec list"


def test_survey_one_batched_solve_per_same_shape_group():
    """torus(4,2) and hypercube(4) share (n=16, deg=4): exactly ONE batched
    group of exactly TWO instances; every other family solves per-instance."""
    jax.clear_caches()
    before = obs.counters()
    res = _survey_nine()
    assert len(res) == len(BENCH_FAMILIES_SMALL)
    d = obs.counter_delta(before)
    assert d.get("survey/lanczos_groups", 0) == 1
    assert d.get("survey/lanczos_grouped_instances", 0) == 2
    # at least 2 grouped + 7 singleton survey solves (the xpander build's
    # annealer adds its own signed-Lanczos solves on top)
    assert d.get("lanczos/solves", 0) >= len(BENCH_FAMILIES_SMALL)
    assert d.get("lanczos/iters", 0) >= 40 * len(BENCH_FAMILIES_SMALL)
    # trace-time backend resolution: one matvec closure per singleton, all on
    # the ambient default backend (PR-8 invariant, via counters not probes)
    from repro.kernels import spmv as KS
    assert d.get("spmv/matvec/" + KS.default_backend(), 0) == 7


def test_survey_rerun_adds_no_engine_retraces():
    """An identical re-survey must add NO jit traces beyond the per-instance
    Lanczos solves (whose fresh matvec closures always retrace); the batched
    same-shape group and every other engine hit their jit caches."""
    jax.clear_caches()
    _survey_nine()                       # populate every jit cache
    before = obs.counters("jit_trace/")
    _survey_nine()
    d = obs.counter_delta(before, "jit_trace/")
    assert set(d) <= {"jit_trace/lanczos_scan"}, f"unexpected retraces: {d}"
    # exactly the 7 ungrouped per-instance solves — the batched group must
    # hit its shape-keyed cache (0 new traces from it)
    assert d.get("jit_trace/lanczos_scan", 0) == 7


def test_same_shape_trio_one_batched_trace():
    """Three same-shape random_regular instances: one group, one batched
    Lanczos trace; a second identical survey re-traces nothing."""
    specs = ["random_regular(64,4,0)", "random_regular(64,4,1)",
             "random_regular(64,4,2)"]
    jax.clear_caches()
    before = obs.counters()
    survey(specs, columns=["instance", "rho2"], dense_threshold=8,
           lanczos_iters=30)
    d = obs.counter_delta(before)
    assert d.get("survey/lanczos_groups", 0) == 1
    assert d.get("survey/lanczos_grouped_instances", 0) == 3
    assert d.get("jit_trace/lanczos_scan", 0) == 1   # ONE vmapped trace
    before = obs.counters("jit_trace/")
    survey(specs, columns=["instance", "rho2"], dense_threshold=8,
           lanczos_iters=30)
    assert obs.counter_delta(before, "jit_trace/") == {}


def test_survey_trace_hook_records_rows(tmp_path):
    path = tmp_path / "survey_trace.json"
    survey(["petersen", "ccc(3)"], trace=path)
    doc = json.loads(path.read_text())
    names = [e["name"] for e in doc["traceEvents"]]
    assert names.count("survey/row") == 2
    assert "survey/build" in names
    rows = [e for e in doc["traceEvents"] if e["name"] == "survey/row"]
    assert {r["args"]["instance"] for r in rows} == {"petersen", "ccc(3)"}
