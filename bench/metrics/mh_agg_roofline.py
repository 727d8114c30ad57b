"""The multi-head aggregation's share of its roofline: the least time its
required work could take on the chips (the larger of the model file's
``agg_flops`` over the bf16 peak and its ``agg_bytes`` over HBM
bandwidth, per epoch) over the device time under the layers'
``aggregate`` scope (as ``mh_agg_ms`` reads it)."""
from bench import scopes


def read(run):
    ms = scopes.layer_ms(run)
    if ms is None or run.peak is None or ms["aggregate"] <= 0:
        return None
    c, p = run.counts, run.peak
    least = max(c["agg_flops"] / p["bf16_flops_per_s"],
                c["agg_bytes"] / p["hbm_bytes_per_s"]) * run.epochs
    return 100.0 * least / (ms["aggregate"] / 1000.0 * run.chips)
