"""Host-clock milliseconds per graph in the program's ``routing/analyze``
``repro.obs`` spans (their union, so nested spans count once)."""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
import devtrace  # noqa: E402


def read(rec: dict):
    if not rec["graphs"] or not any(e["name"].startswith("routing/analyze")
                                    for e in rec["spans"]):
        return None
    return 1e3 * devtrace.span_seconds(rec["spans"], "routing/analyze") / rec["graphs"]
