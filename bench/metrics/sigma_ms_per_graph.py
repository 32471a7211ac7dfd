"""Host-clock milliseconds per graph in the routing layer's float64
path-count step: the union of the program's ``routing/sigma`` spans, from
the upload of the distances to the counts on the host."""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
import devtrace  # noqa: E402


def read(rec: dict):
    if not rec["graphs"] or not any(e["name"] == "routing/sigma"
                                    for e in rec["spans"]):
        return None
    return 1e3 * devtrace.span_seconds(rec["spans"], "routing/sigma") / rec["graphs"]
