"""Process start to the first timed query: imports, the topology built from
the seed, and the warm-up query of the cell's own shapes."""


def read(rec: dict):
    return rec["setup_s"]
