"""Share of the traced window in which no operation ran on a chip,
averaged over the chips: 1 - busy / window."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace.busy_s() / run.trace.window_s)
