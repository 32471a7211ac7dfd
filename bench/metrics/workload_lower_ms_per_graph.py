"""Host-clock milliseconds per graph in the program's ``workloads/lower``
``repro.obs`` spans: the ECMP lowering of each phase's demand onto the
links, over all sources."""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
import devtrace  # noqa: E402


def read(rec: dict):
    spans = [e for e in rec["spans"] if e["name"] == "workloads/lower"]
    if not rec["graphs"] or not spans:
        return None
    return 1e3 * devtrace.span_seconds(spans, "workloads/lower") \
        / rec["graphs"]
