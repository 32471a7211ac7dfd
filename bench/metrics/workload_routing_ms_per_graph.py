"""Host-clock milliseconds per graph in the ``routing/analyze`` spans that run
inside a ``workloads/simulate`` span: the all-sources BFS and float64 path
counts each executed training step routes its demand over."""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
import devtrace  # noqa: E402


def read(rec: dict):
    outer = [(e["ts"], e["ts"] + e["dur"]) for e in rec["spans"]
             if e["name"] == "workloads/simulate"]
    inner = [e for e in rec["spans"] if e["name"] == "routing/analyze" and any(
        lo <= e["ts"] and e["ts"] + e["dur"] <= hi for lo, hi in outer)]
    if not rec["graphs"] or not inner:
        return None
    return 1e3 * devtrace.span_seconds(inner, "routing/analyze") \
        / rec["graphs"]
