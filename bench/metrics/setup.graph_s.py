"""Seconds of set-up spent building (or loading from the cache) and
partitioning the cell's graph (host clock)."""


def read(run):
    return run.setup["graph_s"]
