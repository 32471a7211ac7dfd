"""Share of the Lanczos programs' device time the chip would need at least
(%): the window's solves' bytes over HBM bandwidth, or their operations over
peak where that is larger (``bench/work.py``, from (B, n, k, m, signed)
alone), divided by the summed device time of the jitted Lanczos programs,
found by name in the profiler trace."""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
import work  # noqa: E402

#: the jitted Lanczos programs of ``repro.core.spectral``
PROGRAMS = ("jit_lanczos_tridiag", "jit__lanczos_with_basis",
            "jit__lap_lanczos_batched", "jit__signed_lanczos_batched",
            "jit__lanczos_tridiag_batched")


def read(rec: dict):
    tr = rec["trace"]
    if not tr or not rec["work"]:
        return None
    device_s = sum(s for name, s in tr["module_s"].items() if name in PROGRAMS)
    if device_s <= 0:
        return None
    least = sum(work.least_seconds(work.lanczos_work(*w), rec["peak"])[0]
                for w in rec["work"])
    return 100.0 * least / device_s


def bound(rec: dict) -> str:
    """Which peak bounds the window's solves: ``hbm`` or ``compute``."""
    kinds = {work.least_seconds(work.lanczos_work(*w), rec["peak"])[1]
             for w in rec["work"]}
    return "+".join(sorted(kinds))
