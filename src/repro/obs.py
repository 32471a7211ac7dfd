"""Zero-dependency tracing + metrics: the observability substrate of repro.

Three primitives, threaded through every engine (spectral, routing, traffic,
faults, synthesis, simulate, workloads, the spmv kernel dispatcher):

* **Spans** — :func:`span` / :func:`traced` record hierarchical wall-time
  intervals with tags and the peak-RSS high-water delta across the span.
  Recording is **off by default** (a disabled span is a shared no-op object);
  :func:`tracing` / :func:`enable` turn it on.  The buffer renders as
  Chrome-trace-event JSON (:func:`write_trace`, loadable in Perfetto /
  ``chrome://tracing``), a text tree (:func:`render_tree`), or an aggregated
  :class:`MetricsReport` (:func:`metrics_report`).
* **Counters** — :func:`count` / :func:`counters` are always on (a dict
  increment under a lock — nanoseconds, never gated on :func:`enabled`).
  The engines maintain the canonical counter namespace:

  - ``jit_trace/<engine>`` — incremented inside a jitted body, so it counts
    XLA (re)traces, not calls: a jit cache hit replays a compiled trace
    without re-entering Python.  The no-retrace regression gate asserts
    these stay flat across repeated identical runs.
  - ``spmv/pallas_trace`` — Pallas-kernel traces (the old
    ``kernel_trace_count`` probe, now a first-class counter).
  - ``spmv/dispatch/<backend>`` — :func:`repro.kernels.spmv.spmv` dispatch
    decisions (trace-time under jit, per-call eagerly).
  - ``spmv/matvec/<backend>`` — matvec closures created per resolved
    backend (the trace-time backend-resolution invariant of the survey).
  - ``lanczos/solves`` / ``lanczos/iters`` /
    ``lanczos/breakdown_truncations`` — host-side Lanczos accounting.
  - ``lanczos/shared_table_tiles`` / ``lanczos/per_sample_tiles`` — batched
    Laplacian tiles solved through one shared table or a table a sample.
  - ``survey/lanczos_groups`` / ``survey/lanczos_grouped_instances`` — the
    PR-1 same-shape batching decisions.

* **JAX's compile pipeline** — while spans are enabled, each of JAX's
  ``jax.monitoring`` time spans for tracing a function to a jaxpr, lowering
  it to MLIR and compiling it for the backend is recorded as a span
  (``jax/trace``, ``jax/lower``, ``jax/compile``, tagged ``fun=``) nested
  under the span open at the time, so a retrace says which step it
  happened in.  And every enabled span also enters a
  ``jax.profiler.TraceAnnotation`` of its name, so a ``jax.profiler``
  session shows the spans on the host line of the device trace's clock.
  Both need ``jax`` to be imported already; this module never imports it.
* **Telemetry** — the per-round simulator arrays live in
  :class:`repro.core.simulate.RoundTelemetry` (``run_schedule(telemetry=
  True)``); this module only carries the span/counter side.

Everything here is stdlib-only (``time``/``resource``/``json``/``threading``)
so ``tools/``-style consumers can import it with no numpy/jax installed;
the JAX hooks above look ``jax`` up in ``sys.modules`` and stay idle
without it.
RSS figures use ``getrusage(RUSAGE_SELF).ru_maxrss`` (KiB on Linux): a
*high-water* mark, so a span's ``rss_delta_kb`` reports how much the process
peak grew during the span (0 for work below the current peak), not live heap.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import json
import pathlib
import sys
import threading
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Union

try:                                    # Unix; absent on Windows — RSS -> 0
    import resource as _resource
except ImportError:                     # pragma: no cover
    _resource = None

__all__ = [
    "span", "traced", "tracing", "enable", "disable", "enabled",
    "count", "counters", "counter_delta", "reset_counters",
    "trace_events", "reset_spans", "reset", "write_trace", "render_tree",
    "metrics_report", "MetricsReport", "SpanStat", "peak_rss_kb",
]

_LOCK = threading.Lock()
_COUNTERS: Dict[str, int] = {}
_EVENTS: List[Dict[str, Any]] = []      # completed spans, Chrome "X" phase
_ENABLED = False
_T0 = time.perf_counter()               # trace-time origin (ts=0)
_TLS = threading.local()


def peak_rss_kb() -> int:
    """Process peak RSS high-water mark in KiB (0 where unsupported)."""
    if _resource is None:               # pragma: no cover
        return 0
    return int(_resource.getrusage(_resource.RUSAGE_SELF).ru_maxrss)


# --------------------------------------------------------------------------
# counters (always on)
# --------------------------------------------------------------------------

def count(name: str, inc: int = 1) -> None:
    """Increment counter ``name`` by ``inc`` (thread-safe, never gated)."""
    with _LOCK:
        _COUNTERS[name] = _COUNTERS.get(name, 0) + int(inc)


def counters(prefix: Optional[str] = None) -> Dict[str, int]:
    """Snapshot of all counters, optionally filtered to a name prefix."""
    with _LOCK:
        snap = dict(_COUNTERS)
    if prefix is None:
        return snap
    return {k: v for k, v in snap.items() if k.startswith(prefix)}


def counter_delta(before: Dict[str, int],
                  prefix: Optional[str] = None) -> Dict[str, int]:
    """Counters that changed since the ``before`` snapshot (non-zero deltas
    only) — the idiom behind every no-retrace assertion::

        before = obs.counters("jit_trace/")
        run_again()
        assert obs.counter_delta(before, "jit_trace/") == {}
    """
    after = counters(prefix)
    keys = set(before) | set(after)
    out = {}
    for k in keys:
        if prefix is not None and not k.startswith(prefix):
            continue
        d = after.get(k, 0) - before.get(k, 0)
        if d:
            out[k] = d
    return out


def reset_counters() -> None:
    with _LOCK:
        _COUNTERS.clear()


# --------------------------------------------------------------------------
# spans (off unless enabled)
# --------------------------------------------------------------------------

def enabled() -> bool:
    """Whether spans are currently being recorded."""
    return _ENABLED


def enable() -> None:
    """Start recording spans (counters are always on regardless)."""
    global _ENABLED
    _jax_annotation()
    _ENABLED = True


def disable() -> None:
    global _ENABLED
    _ENABLED = False


class _NullSpan:
    """Shared no-op context — the full cost of a disabled span."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def tag(self, **tags: Any) -> None:
        pass


_NULL_SPAN = _NullSpan()

#: JAX's compile-pipeline ``jax.monitoring`` time spans -> span names
JAX_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax/trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax/lower",
    "/jax/core/compile/backend_compile_duration": "jax/compile",
}
_ANNOTATION: Optional[type] = None      # jax.profiler.TraceAnnotation, once hooked


def _jax_annotation() -> Optional[type]:
    """``jax.profiler.TraceAnnotation`` if ``jax`` is imported, else None.

    The first call that finds ``jax`` also registers the listener that
    records JAX's compile pipeline as spans (:data:`JAX_SPANS`)."""
    global _ANNOTATION
    if _ANNOTATION is None:
        jax = sys.modules.get("jax")
        if getattr(jax, "profiler", None) is None or \
                getattr(jax, "monitoring", None) is None:
            return None
        with _LOCK:
            if _ANNOTATION is None:
                jax.monitoring.register_event_time_span_listener(
                    _on_jax_time_span)
                _ANNOTATION = jax.profiler.TraceAnnotation
    return _ANNOTATION


def _on_jax_time_span(event: str, start: float, end: float,
                      **kwargs: Any) -> None:
    """Record one JAX compile step (``time.time()`` start and end) as a span
    on this module's clock, one level below the innermost open span."""
    if not _ENABLED or event not in JAX_SPANS:
        return
    shift = time.perf_counter() - time.time()
    depth = len(getattr(_TLS, "stack", ()))
    ev = dict(name=JAX_SPANS[event], ph="X", cat="jax",
              ts=(start + shift - _T0) * 1e6, dur=(end - start) * 1e6,
              pid=1, tid=threading.get_ident() & 0xFFFF,
              args=dict(fun=str(kwargs.get("fun_name", "")), depth=depth))
    with _LOCK:
        _EVENTS.append(ev)


class _Span:
    __slots__ = ("name", "tags", "_t_start", "_rss0", "_depth", "_ann")

    def __init__(self, name: str, tags: Dict[str, Any]):
        self.name = name
        self.tags = tags

    def __enter__(self):
        stack = getattr(_TLS, "stack", None)
        if stack is None:
            stack = _TLS.stack = []
        self._depth = len(stack)
        stack.append(self)
        self._rss0 = peak_rss_kb()
        ann = _jax_annotation()
        self._ann = None if ann is None else ann(self.name)
        if self._ann is not None:       # last, so both clocks bracket the same work
            self._ann.__enter__()
        self._t_start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t_end = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        rss1 = peak_rss_kb()
        stack = _TLS.stack
        if stack and stack[-1] is self:
            stack.pop()
        args = dict(self.tags)
        args["rss_delta_kb"] = max(0, rss1 - self._rss0)
        args["depth"] = self._depth
        ev = dict(name=self.name, ph="X", cat=str(self.tags.get("phase", "span")),
                  ts=(self._t_start - _T0) * 1e6,
                  dur=(t_end - self._t_start) * 1e6,
                  pid=1, tid=threading.get_ident() & 0xFFFF, args=args)
        with _LOCK:
            _EVENTS.append(ev)
        return False

    def tag(self, **tags: Any) -> None:
        """Add tags known only once the span's work has run."""
        self.tags = {**self.tags, **tags}


def span(name: str, **tags: Any):
    """Context manager recording one hierarchical span.

    ``tags`` are attached verbatim (Chrome-trace ``args``); the reserved tag
    ``phase=`` ("build" / "compile" / "execute") feeds the per-phase wall-time
    breakdown of :func:`metrics_report`.  When recording is disabled this
    returns a shared no-op object — safe on hot paths.
    """
    if not _ENABLED:
        return _NULL_SPAN
    return _Span(name, tags)


def traced(name: Optional[str] = None, phase: Optional[str] = None,
           **tags: Any) -> Callable:
    """Decorator form of :func:`span` — zero overhead while disabled::

        @obs.traced("routing/analyze", phase="execute")
        def analyze_routing(...): ...
    """
    def deco(fn: Callable) -> Callable:
        label = name or fn.__name__
        static = dict(tags)
        if phase is not None:
            static["phase"] = phase

        @functools.wraps(fn)
        def wrapper(*a, **kw):
            if not _ENABLED:
                return fn(*a, **kw)
            with _Span(label, static):
                return fn(*a, **kw)

        return wrapper

    return deco


@contextlib.contextmanager
def tracing(path: Optional[Union[str, pathlib.Path]] = None):
    """Enable span recording inside the block; optionally write the Chrome
    trace JSON to ``path`` on exit.  Nests: an inner ``tracing()`` inside an
    already-enabled region neither clears the buffer nor disables recording
    on exit (the outermost activation owns both)."""
    global _ENABLED
    prev = _ENABLED
    if not prev:
        reset_spans()
    _jax_annotation()
    _ENABLED = True
    try:
        yield
    finally:
        _ENABLED = prev
        if path is not None:
            write_trace(path)


def trace_events() -> List[Dict[str, Any]]:
    """Copy of the recorded span buffer (Chrome trace-event dicts)."""
    with _LOCK:
        return [dict(e) for e in _EVENTS]


def reset_spans() -> None:
    """Clear the span buffer (counters untouched)."""
    with _LOCK:
        _EVENTS.clear()


def reset() -> None:
    """Clear spans AND counters (test isolation)."""
    reset_spans()
    reset_counters()


def write_trace(path: Union[str, pathlib.Path],
                events: Optional[Iterable[Dict[str, Any]]] = None) -> str:
    """Write the span buffer (or ``events``) as Chrome trace-event JSON
    (``{"traceEvents": [...]}``, ts/dur in microseconds — the format Perfetto
    and ``chrome://tracing`` load directly).  Returns the path written."""
    evs = trace_events() if events is None else list(events)
    p = pathlib.Path(path)
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(json.dumps(
        dict(traceEvents=evs, displayTimeUnit="ms"), indent=1))
    return str(p)


def render_tree(events: Optional[Iterable[Dict[str, Any]]] = None) -> str:
    """Text rendering of the span hierarchy (indent = nesting depth)::

        survey/row [instance=slimfly(13)]  41.2ms
          spectral/rho2_lanczos  38.9ms  (+12.0MB peak)
    """
    evs = trace_events() if events is None else list(events)
    evs.sort(key=lambda e: e["ts"])
    lines = []
    for e in evs:
        args = e.get("args", {})
        depth = int(args.get("depth", 0))
        tags = {k: v for k, v in args.items()
                if k not in ("depth", "rss_delta_kb")}
        tag_s = (" [" + ", ".join(f"{k}={v}" for k, v in sorted(tags.items()))
                 + "]") if tags else ""
        rss = int(args.get("rss_delta_kb", 0))
        rss_s = f"  (+{rss / 1024:.1f}MB peak)" if rss else ""
        lines.append(f"{'  ' * depth}{e['name']}{tag_s}  "
                     f"{e['dur'] / 1e3:.1f}ms{rss_s}")
    return "\n".join(lines)


# --------------------------------------------------------------------------
# aggregation
# --------------------------------------------------------------------------

@dataclasses.dataclass
class SpanStat:
    """Aggregate of every recorded span sharing one name."""
    name: str
    calls: int
    total_seconds: float
    max_seconds: float
    rss_delta_kb: int          # summed peak-RSS growth across the spans

    def to_dict(self) -> Dict[str, Any]:
        return dict(name=self.name, calls=self.calls,
                    total_seconds=round(self.total_seconds, 6),
                    max_seconds=round(self.max_seconds, 6),
                    rss_delta_kb=self.rss_delta_kb)


def _interval_union_seconds(intervals: List[tuple]) -> float:
    """Total length of the union of (start, end) intervals — phase seconds
    without double-counting nested same-phase spans."""
    if not intervals:
        return 0.0
    intervals.sort()
    total, cur_lo, cur_hi = 0.0, intervals[0][0], intervals[0][1]
    for lo, hi in intervals[1:]:
        if lo > cur_hi:
            total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        else:
            cur_hi = max(cur_hi, hi)
    return total + (cur_hi - cur_lo)


@dataclasses.dataclass
class MetricsReport:
    """Aggregated view of one recording window.

    ``spans`` aggregates by span name; ``phases`` maps each ``phase=`` tag to
    the union-length of its spans' wall intervals (seconds — nested or
    overlapping same-phase spans are not double-counted); ``counters`` is a
    snapshot; ``peak_rss_kb`` the process high-water mark at report time.
    """
    spans: Dict[str, SpanStat]
    phases: Dict[str, float]
    counters: Dict[str, int]
    peak_rss_kb: int

    def to_dict(self) -> Dict[str, Any]:
        return dict(
            spans={k: v.to_dict() for k, v in sorted(self.spans.items())},
            phases={k: round(v, 6) for k, v in sorted(self.phases.items())},
            counters=dict(sorted(self.counters.items())),
            peak_rss_kb=self.peak_rss_kb)

    def report(self) -> str:
        """Compact text block for CLI output."""
        lines = [f"peak RSS        : {self.peak_rss_kb / 2**20:.2f} GiB"]
        if self.phases:
            ph = ", ".join(f"{k} {v:.3f}s" for k, v in sorted(self.phases.items()))
            lines.append(f"phases          : {ph}")
        for st in sorted(self.spans.values(), key=lambda s: -s.total_seconds):
            lines.append(f"  {st.name:32s} x{st.calls:<4d} "
                         f"{st.total_seconds * 1e3:9.1f}ms total, "
                         f"{st.max_seconds * 1e3:8.1f}ms max")
        return "\n".join(lines)


def metrics_report(events: Optional[Iterable[Dict[str, Any]]] = None
                   ) -> MetricsReport:
    """Aggregate the span buffer (or ``events``) into a :class:`MetricsReport`."""
    evs = trace_events() if events is None else list(events)
    spans: Dict[str, SpanStat] = {}
    phase_ivals: Dict[str, List[tuple]] = {}
    for e in evs:
        dur_s = e["dur"] / 1e6
        st = spans.get(e["name"])
        if st is None:
            spans[e["name"]] = SpanStat(e["name"], 1, dur_s, dur_s,
                                        int(e["args"].get("rss_delta_kb", 0)))
        else:
            st.calls += 1
            st.total_seconds += dur_s
            st.max_seconds = max(st.max_seconds, dur_s)
            st.rss_delta_kb += int(e["args"].get("rss_delta_kb", 0))
        phase = e["args"].get("phase")
        if phase is not None:
            phase_ivals.setdefault(str(phase), []).append(
                (e["ts"], e["ts"] + e["dur"]))
    phases = {p: _interval_union_seconds(iv) / 1e6
              for p, iv in phase_ivals.items()}
    return MetricsReport(spans=spans, phases=phases, counters=counters(),
                        peak_rss_kb=peak_rss_kb())
