"""The whole step's share of the chip's peak: the least time the peaks
allow one boosting iteration at the cell's shape, over the traced window's
seconds per iteration.  One pass over the rows (bin bytes + 12 B of label and
score read and write per row) at the HBM peak, or 4 x rows x features
accumulate operations at the FLOP peak, whichever is larger."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not ctx["traced_trees"]:
        return None
    rows, feats, chips = ctx["rows"], ctx["features"], ctx["chips"]
    peak = ctx["peak"]
    by_bytes = rows * (feats * ctx["bin_bytes"] + 12) / (
        peak["hbm_bytes_per_s"] * chips)
    by_flops = 4.0 * rows * feats / (peak["flops_per_s"] * chips)
    per_iter = trace["window_s"] / len(ctx["traced_trees"])
    return 100.0 * max(by_bytes, by_flops) / per_iter
