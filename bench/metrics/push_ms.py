"""Device milliseconds per push and chip of the push to the stale store
(Algorithm 1 lines 9-10): the self time of the program's ``digest/push``
scope, its Theorem-1 ``staleness`` probe left out, over the pushes in the
window (epochs r with (r - 1) % sync_interval == 0); ``None`` where the
window holds none."""
from bench import scopes


def read(run):
    ms, n = scopes.layer_ms(run), scopes.pushes(run)
    if ms is None or not n:
        return None
    return ms["push"] / n
