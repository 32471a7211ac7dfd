"""Share of the window in which no operation ran on the device (%):
1 - (union of the device's op intervals / window), from the profiler trace,
averaged over the chips used."""


def read(rec: dict):
    tr = rec["trace"]
    if not tr or tr["devices"] == 0 or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
