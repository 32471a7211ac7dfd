"""Reduce a JAX profiler trace of the measured window to device figures.

The profiler writes an ``.xplane.pb`` file; ``jax.profiler.ProfileData``
reads it.  Each TPU is a plane named ``/device:TPU:<i>`` with a line of
``XLA Modules`` (one event per executed program, named
``jit_<function>(<fingerprint>)``) and a line of ``XLA Ops`` (one event per
HLO operation; a ``while`` or ``call`` event encloses the operations of its
body).  Host threads are lines of the ``/host:CPU`` plane; the Python thread
(``python``) carries every ``jax.profiler.TraceAnnotation``.  All events
share one nanosecond clock that starts with the trace.

The benchmark opens one ``TraceAnnotation`` named :data:`MARKER` and records
``time.perf_counter()`` beside it, which puts the trace, the harness's own
clock and the program's ``repro.obs`` spans on one time axis.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

MARKER = "bench/clock_marker"
DEVICE_PREFIX = "/device:TPU:"


def find_xplane(trace_dir: str) -> str:
    """The one ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {len(paths)}")
    return paths[0]


def module_name(event_name: str) -> str:
    """``jit_f(1234)`` -> ``jit_f``."""
    return event_name.split("(", 1)[0]


def op_name(event_name: str) -> str:
    """``%fusion.7 = f32[...] fusion(...)`` -> ``fusion.7``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def span_seconds(spans: Sequence[dict], prefix: str) -> float:
    """Seconds covered by the ``repro.obs`` spans whose name starts with
    ``prefix`` (their union: a span nested in another of the same layer is
    not counted twice)."""
    ivs = [(e["ts"], e["ts"] + e["dur"]) for e in spans
           if e["name"].startswith(prefix)]
    return sum(hi - lo for lo, hi in _union(ivs)) / 1e6


def _clip(lo: float, hi: float, w0: float, w1: float):
    lo, hi = max(lo, w0), min(hi, w1)
    return (lo, hi) if hi > lo else None


def _self_times(ops: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Exclusive nanoseconds per op name: an enclosing op (a loop, a call)
    is charged only for the time none of the ops inside it runs."""
    out: Dict[str, float] = {}
    stack: List[list] = []          # [end, name, child_ns]

    def close(entry):
        end, name, child, start = entry
        out[name] = out.get(name, 0.0) + (end - start) - child

    for start, end, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and start >= stack[-1][0]:
            close(stack.pop())
        if stack:
            stack[-1][2] += end - start
        stack.append([end, name, 0.0, start])
    while stack:
        close(stack.pop())
    return out


def reduce_trace(xplane_path: str, marker_perf: float, window: Sequence[float],
                 spans: Sequence[dict] = (), span_offset: float = 0.0,
                 top: int = 10) -> dict:
    """Device figures of the window ``[t0, t1]`` (``time.perf_counter()``).

    ``spans`` are ``repro.obs`` trace events (``ts``/``dur`` in microseconds
    on the obs clock); ``span_offset`` is perf_counter minus the obs clock,
    in seconds.  Returns::

        devices     number of TPU planes with events
        window_s    length of the window
        busy_s      seconds in which some operation ran, averaged over devices
        module_s    {program name: device seconds summed over devices}
        op_self_s   {module:op: exclusive device seconds summed over devices}
        idle_gaps   [(innermost span open at the gap, seconds)], longest first
    """
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    marker_ns = None
    device_planes = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            device_planes.append(plane)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == MARKER:
                        marker_ns = ev.start_ns
    if marker_ns is None:
        raise ValueError(f"no {MARKER!r} annotation in {xplane_path}")

    def to_ns(perf: float) -> float:
        return marker_ns + (perf - marker_perf) * 1e9

    w0, w1 = to_ns(window[0]), to_ns(window[1])
    module_ns: Dict[str, float] = {}
    self_ns: Dict[str, float] = {}
    busy: List[float] = []
    gaps: List[Tuple[float, float]] = []
    for i, plane in enumerate(sorted(device_planes, key=lambda p: p.name)):
        lines = {line.name: line for line in plane.lines}
        mods = []
        for ev in (lines["XLA Modules"].events if "XLA Modules" in lines
                   else ()):
            iv = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, w0, w1)
            if iv:
                name = module_name(ev.name)
                module_ns[name] = module_ns.get(name, 0.0) + iv[1] - iv[0]
                mods.append((iv[0], iv[1], name))
        ops = []
        for ev in (lines["XLA Ops"].events if "XLA Ops" in lines else ()):
            iv = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, w0, w1)
            if iv:
                ops.append((iv[0], iv[1], op_name(ev.name)))
        if not ops:
            continue
        mods.sort()
        named = []
        j = 0
        for start, end, name in sorted(ops):
            while j + 1 < len(mods) and mods[j + 1][0] <= start:
                j += 1
            owner = mods[j][2] if mods and mods[j][0] <= start else "?"
            named.append((start, end, f"{owner}:{name}"))
        for name, ns in _self_times(named).items():
            self_ns[name] = self_ns.get(name, 0.0) + ns
        union = _union([(s, e) for s, e, _ in ops])
        busy.append(sum(e - s for s, e in union))
        if i == 0:
            edges = [w0] + [x for iv in union for x in iv] + [w1]
            gaps = [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
                    if edges[k + 1] > edges[k]]
    n_dev = len(busy)
    span_ivs = []
    for ev in spans:
        lo = to_ns(ev["ts"] / 1e6 + span_offset)
        span_ivs.append((lo, lo + ev["dur"] * 1e3,
                         int(ev.get("args", {}).get("depth", 0)), ev["name"]))
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return dict(
        devices=n_dev,
        window_s=(w1 - w0) / 1e9,
        busy_s=(sum(busy) / n_dev / 1e9) if n_dev else 0.0,
        module_s={k: v / 1e9 for k, v in module_ns.items()},
        op_self_s={k: v / 1e9 for k, v in self_ns.items()},
        idle_gaps=[(_innermost(span_ivs, (g0 + g1) / 2), (g1 - g0) / 1e9)
                   for g0, g1 in longest],
    )


def _innermost(spans: Sequence[tuple], t: float) -> str:
    best: Optional[tuple] = None
    for lo, hi, depth, name in spans:
        if lo <= t < hi and (best is None or depth > best[0]):
            best = (depth, name)
    return best[1] if best else "harness (between queries)"


def breakdown(reduced: dict, top: int = 10) -> dict:
    """The result line's ``breakdown``: the device operations with the most
    exclusive time, and the longest idle gaps by the span the host was in."""
    ops = sorted(reduced["op_self_s"].items(), key=lambda kv: -kv[1])[:top]
    return dict(device_ops=[[k, v] for k, v in ops],
                idle_gaps=[[k, v] for k, v in reduced["idle_gaps"][:top]])
