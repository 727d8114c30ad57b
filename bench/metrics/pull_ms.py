"""Device milliseconds per pull and chip of the stale-halo pull
(Algorithm 1 line 5): every op under the program's ``digest/pull`` scope
(the ``cond`` and both branches, gather or collective), over the pulls in
the window (epochs r with r % sync_interval == 0); ``None`` where the
window holds none."""
from bench import scopes


def read(run):
    ms, n = scopes.layer_ms(run), scopes.pulls(run)
    if ms is None or not n:
        return None
    return ms["pull"] / n
