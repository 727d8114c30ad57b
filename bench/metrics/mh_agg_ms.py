"""Device milliseconds per epoch and chip of the multi-head aggregation:
every op under a layer's ``aggregate`` scope (``jit(multihead_spmm)``
and what feeds it), forward and backward, each busy instant counted
once (``scopes.layer_ms``), so a gather loop's event and its body's
events are not both counted."""
from bench import scopes


def read(run):
    ms = scopes.layer_ms(run)
    return None if ms is None else ms["aggregate"] / run.epochs
