"""Device milliseconds per epoch and chip of the neighbour aggregation:
every op whose op_name lies under ``jit(spmm)`` or ``jit(halo_spmm)``
(``repro.kernels.spmm``), kernel or not, forward and backward."""
from bench.trace_reduce import is_aggregation


def read(run):
    if run.trace is None:
        return None
    seconds = run.trace.op_s(lambda _, op_name: is_aggregation(op_name))
    if seconds <= 0:
        return None
    return 1000.0 * seconds / run.chips / run.epochs
