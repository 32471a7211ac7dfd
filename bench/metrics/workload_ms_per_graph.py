"""Host-clock milliseconds per graph in the program's ``workloads/simulate``
``repro.obs`` spans (their union): executing a training step's
communication on a degraded sample, from its routing to the round engine."""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
import devtrace  # noqa: E402


def read(rec: dict):
    spans = [e for e in rec["spans"] if e["name"] == "workloads/simulate"]
    if not rec["graphs"] or not spans:
        return None
    return 1e3 * devtrace.span_seconds(spans, "workloads/simulate") \
        / rec["graphs"]
