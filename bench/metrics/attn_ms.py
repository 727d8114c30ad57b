"""Device milliseconds per epoch and chip of GAT's attention: every op
under a layer's ``attention`` scope (scores, softmax), forward and
backward, each busy instant counted once (``scopes.layer_ms``)."""
from bench import scopes


def read(run):
    ms = scopes.layer_ms(run)
    return None if ms is None else ms["attention"] / run.epochs
