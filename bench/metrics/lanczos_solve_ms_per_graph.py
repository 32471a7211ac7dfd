"""Host-clock milliseconds per graph in the program's ``lanczos/solve``
``repro.obs`` spans (their union): the jitted Lanczos solves to their
coefficients on the host, so the one-chip and the four-chip batched solve
read side by side."""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
import devtrace  # noqa: E402


def read(rec: dict):
    spans = [e for e in rec["spans"] if e["name"] == "lanczos/solve"]
    if not rec["graphs"] or not spans:
        return None
    return 1e3 * devtrace.span_seconds(spans, "lanczos/solve") / rec["graphs"]
