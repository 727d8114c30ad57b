"""The whole epoch's share of the chips' bf16 peak: the required FLOPs of
an epoch (``counts.epoch``: dense transforms and aggregation, forward and
backward) over the traced window's mean epoch time times the chips'
peak.  Float32 matmuls at the default precision run on the bf16 MXU
path, so bf16 is the peak they are held to."""


def read(run):
    if run.peak is None:
        return None
    epoch_s = run.window_s / run.epochs
    peak = run.peak["bf16_flops_per_s"] * run.chips
    return 100.0 * run.counts["epoch_flops"] / (epoch_s * peak)
