"""Device milliseconds per epoch and chip that lie in no program scope
(``bench/scopes.py``): ops the program does not name, and ops a compiler
pass made that enclose no named op of one layer."""
from bench import scopes


def read(run):
    ms = scopes.layer_ms(run)
    return None if ms is None else ms[scopes.UNSCOPED] / run.epochs
