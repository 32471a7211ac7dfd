"""Host-clock milliseconds per graph that the spectral layer spends outside
its jitted solves: the union of the ``spectral/*`` spans less the part of it
covered by ``lanczos/solve`` spans (start vectors, deflation rows, operand
uploads, the tridiagonal eigensolves)."""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
import devtrace  # noqa: E402


def _union(spans, keep):
    return devtrace._union([(e["ts"], e["ts"] + e["dur"]) for e in spans
                            if keep(e["name"])])


def read(rec: dict):
    spectral = _union(rec["spans"], lambda s: s.startswith("spectral/"))
    solves = _union(rec["spans"], lambda s: s == "lanczos/solve")
    if not rec["graphs"] or not spectral or not solves:
        return None
    covered = sum(max(0.0, min(hi, b) - max(lo, a))
                  for lo, hi in spectral for a, b in solves)
    own = sum(hi - lo for lo, hi in spectral) - covered
    return own / 1e3 / rec["graphs"]
