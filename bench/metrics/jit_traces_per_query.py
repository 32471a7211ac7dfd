"""JIT traces per query: the ``repro.obs`` ``jit_trace/*`` counters' growth
over the window (each counts a trace of one jitted engine) over queries."""


def read(rec: dict):
    if not rec["queries"]:
        return None
    return sum(rec["jit_traces"].values()) / rec["queries"]
