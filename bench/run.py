"""The benchmark: one cell of ``BENCHMARK.json``, run on the chips it asks for.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name:

* ``BENCHMARK.json`` names the cell, its configuration and its chips, and
  lists the metrics with the cells each is read in;
* ``bench/cells/<cell>.json`` holds the query kind, its traffic parameters and
  the limits of its correctness check;
* ``bench/configs/<config>.json`` holds the deployment (the registry spec);
* ``bench/queries/<kind>.py`` sets the cell up from the seed, runs one query
  through the public API, and checks answers against a plain reference;
* ``bench/metrics/<metric>.py`` reads one metric from the run's record.

A run sets up (import, topology from the seed, one warm-up query of the
cell's own shapes), then runs whole queries back to back until ``--seconds``
have passed, finishing the query in flight.  ``--trace 0`` reports the
end-to-end metrics with tracing off; ``--trace 1`` enables the program's
``repro.obs`` spans, takes a ``jax.profiler`` trace of the window and
reports the per-layer metrics.  After the window the program's state is
freed and a sample of the window's answers, drawn from the seed, is compared
with the plain reference.  The last line of standard output is one JSON
object; without a TPU, or with fewer or more chips than the cell asks for,
the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from typing import Callable, List, Optional  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_module(path: pathlib.Path):
    """Import one file of the benchmark by path."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: pathlib.Path = ROOT) -> dict:
    """The cell ``name`` with its workload entry, cell file and config file,
    and the names of the metrics it reports with and without tracing."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    wl = {w["name"]: w for w in spec["workloads"]}.get(name)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = json.loads((root / "bench" / "cells" / f"{name}.json").read_text())
    cfgs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / cfgs[wl["config"]]["file"]).read_text())

    def listed(metric):
        return name in metric.get("workloads", [name])

    return dict(
        name=name, chips=int(wl["chips"]), cell=cell, config=config,
        end_to_end=[m for m in spec["end_to_end"] if listed(m)],
        per_layer=[m for m in spec["per_layer"] if listed(m)],
        peaks_path=root / "bench" / "peaks.json")


def query_seed(seed: int, index: int) -> int:
    """The Lanczos and sampling seed of query ``index`` of a run."""
    import numpy as np

    ss = np.random.SeedSequence([seed % 2 ** 64, index % 2 ** 64])
    return int(ss.generate_state(1)[0] % 2 ** 30)


def device_peak(kind: str, peaks_path: pathlib.Path) -> dict:
    """Peaks of ``kind`` from the table; a device not in it is an error."""
    table = json.loads(peaks_path.read_text())
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in {peaks_path.name} "
                       f"(known: {sorted(table['devices'])})")
    return table["devices"][kind]


class Window:
    """Runs queries back to back and keeps what each produced."""

    def __init__(self, query: Callable, state: dict, seed: int):
        self.query, self.state, self.seed = query, state, seed
        self.answers: List[dict] = []
        self.times: List[tuple] = []
        self.failed = 0

    def run(self, seconds: float) -> None:
        t_first = time.perf_counter()
        i = 0
        while True:
            qseed = query_seed(self.seed, i)
            t0 = time.perf_counter()
            try:
                self.answers.append(self.query(self.state, qseed))
            except Exception:  # a failed query counts and the window goes on
                traceback.print_exc()
                self.failed += 1
            t1 = time.perf_counter()
            self.times.append((t0, t1))
            i += 1
            if t1 - t_first >= seconds:
                break

    @property
    def span(self) -> tuple:
        return self.times[0][0], self.times[-1][1]


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             root: pathlib.Path = ROOT) -> dict:
    """Set up, measure and check one cell; return the result object.

    ``main`` calls this once it has found the chips the cell asks for; the
    tests call it directly on the CPU with a cell of their own.
    """
    import jax
    import numpy as np

    c = load_cell(name, root)
    cfg, cell = c["config"], c["cell"]
    devices = jax.devices()[:c["chips"]]
    kind = load_module(root / "bench" / "queries" / f"{cell['query']}.py")
    readers = {m["name"]: load_module(root / "bench" / "metrics" /
                                      f"{m['name']}.py")
               for m in (c["per_layer"] if trace else c["end_to_end"])}
    peak = device_peak(devices[0].device_kind, c["peaks_path"]) \
        if trace else None
    from repro import obs

    compile_s = [0.0]

    def on_duration(event: str, secs: float, **_) -> None:
        if event == COMPILE_EVENT:
            compile_s[0] += secs

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    state = kind.setup(cfg, cell, seed)

    trace_dir = None
    if trace:
        trace_dir = root / "bench" / "out" / f"trace-{name}"
        shutil.rmtree(trace_dir, ignore_errors=True)
        obs.reset_spans()
        obs.enable()
        jax.profiler.start_trace(str(trace_dir))
        with obs.span("bench/clock"):
            marker_perf = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench/clock_marker"):
                pass
        clock_span = obs.trace_events()[-1]
        span_offset = marker_perf - clock_span["ts"] / 1e6
    traces0 = obs.counters("jit_trace/")
    compile0 = compile_s[0]
    setup_s = time.perf_counter() - T_START
    win = Window(kind.query, state, seed)
    win.run(seconds)
    t0, t1 = win.span
    if trace:
        jax.profiler.stop_trace()
        obs.disable()
    record = dict(
        queries=len(win.times), graphs=sum(a["graphs"] for a in win.answers),
        window_s=t1 - t0, setup_s=setup_s,
        query_s=[b - a for a, b in win.times],
        peak_bytes=max(int((d.memory_stats() or {}).get("peak_bytes_in_use",
                                                           0))
                       for d in devices),
        compile_s=compile_s[0] - compile0,
        jit_traces=obs.counter_delta(traces0, "jit_trace/"),
        work=[w for _ in win.answers for w in kind.work(state)],
        peak=peak, spans=[], trace=None)
    if trace:
        tr = load_module(root / "bench" / "devtrace.py")
        record["spans"] = [e for e in obs.trace_events()
                           if e["name"] != "bench/clock"]
        record["trace"] = tr.reduce_trace(
            tr.find_xplane(str(trace_dir)), marker_perf, (t0, t1),
            record["spans"], span_offset)
        shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = {}
    for m in (c["per_layer"] if trace else c["end_to_end"]):
        reader = readers[m["name"]]
        value = reader.read(record)
        if value is not None:
            metrics[m["name"]] = dict(value=value, unit=m["unit"])
            if hasattr(reader, "bound"):
                metrics[m["name"]]["bound"] = reader.bound(record)

    state.pop("program", None)
    gc.collect()
    rng = np.random.default_rng([seed % 2 ** 64, 0xC4EC])
    checks = kind.check(state, win.answers, rng)
    for key in cell["limits"]:
        checks.setdefault(key, None)
    checks = {k: dict(value=v, limit=cell["limits"][k])
              for k, v in checks.items()}
    correct = (win.failed == 0 and bool(win.answers) and all(
        ch["value"] is not None and ch["value"] <= ch["limit"]
        for ch in checks.values()))
    device = dict(platform=devices[0].platform, kind=devices[0].device_kind,
                  count=len(devices), memory_peak_bytes=record["peak_bytes"])
    result = dict(correct=correct, attempted=len(win.times),
                  failed=win.failed, metrics=metrics, device=device)
    if trace:
        tr_rec = record["trace"]
        device.update(busy_s=tr_rec["busy_s"], window_s=tr_rec["window_s"])
        result["breakdown"] = tr.breakdown(tr_rec)
    result["slowest_query_s"] = max(record["query_s"])
    result["checks"] = checks
    return result


def prepare_jax(chips: int) -> Optional[str]:
    """Point JAX's persistent compilation cache at the checkout's fixed
    ``.jax_cache/`` (every program cached, however short its compile) and
    say what is wrong if JAX does not find exactly ``chips`` TPUs."""
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return f"needs a TPU, JAX found {devices[0].platform}"
    if len(devices) != chips:
        return f"needs {chips} chips, JAX found {len(devices)}"
    return None


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    c = load_cell(args.workload)
    problem = prepare_jax(c["chips"])
    if problem:
        print(f"bench: {args.workload}: {problem}", file=sys.stderr)
        return 2
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    for key, ch in result["checks"].items():
        ok = ch["value"] is not None and ch["value"] <= ch["limit"]
        print(f"check {key} {ch['value']!r} limit {ch['limit']!r} "
              f"{'ok' if ok else 'FAILED'}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
