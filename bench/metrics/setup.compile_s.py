"""Seconds of set-up spent lowering and compiling the epoch, or loading
it from the persistent compile cache (host clock)."""


def read(run):
    return run.setup["compile_s"]
