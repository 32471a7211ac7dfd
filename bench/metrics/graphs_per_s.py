"""Graph instances fully analysed per second: the graphs of every completed
query over the time from the first query's start to the last one's end."""


def read(rec: dict):
    return rec["graphs"] / rec["window_s"] if rec["window_s"] > 0 else None
