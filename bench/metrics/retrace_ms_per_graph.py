"""Host-clock milliseconds per graph that JAX spends tracing, lowering and
compiling programs: the union of the ``jax/trace``, ``jax/lower`` and
``jax/compile`` spans ``repro.obs`` records from JAX's monitoring events."""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
import devtrace  # noqa: E402


def read(rec: dict):
    if not rec["graphs"] or not any(e["name"].startswith("jax/")
                                    for e in rec["spans"]):
        return None
    return 1e3 * devtrace.span_seconds(rec["spans"], "jax/") / rec["graphs"]
