"""Device milliseconds per epoch and chip of the optimizer: the gradient
mean over subgraphs and the update, the program's ``digest/opt`` scope."""
from bench import scopes


def read(run):
    ms = scopes.layer_ms(run)
    return None if ms is None else ms["opt"] / run.epochs
