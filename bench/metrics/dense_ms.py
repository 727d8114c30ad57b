"""Device milliseconds per epoch and chip of the dense transforms: every
op under a layer's ``transform`` scope, forward and backward."""
from bench import scopes


def read(run):
    ms = scopes.layer_ms(run)
    return None if ms is None else ms["transform"] / run.epochs
