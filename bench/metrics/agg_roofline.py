"""The aggregation's share of its roofline: the least time its required
work could take on the chips (the larger of required FLOPs over the bf16
peak and required bytes over HBM bandwidth, ``counts.aggregation``) over
the device time of its ops (as ``agg_ms`` selects them)."""
from bench.trace_reduce import is_aggregation


def read(run):
    if run.trace is None or run.peak is None:
        return None
    seconds = run.trace.op_s(lambda _, op_name: is_aggregation(op_name))
    if seconds <= 0:
        return None
    c, p = run.counts, run.peak
    least = max(c["agg_flops"] / p["bf16_flops_per_s"],
                c["agg_bytes"] / p["hbm_bytes_per_s"]) * run.epochs
    return 100.0 * least / seconds
