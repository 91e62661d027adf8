"""A kernel set's share of its HBM roofline, the work reckoned from the trees.

Every split reads its parent's rows at least once: bin bytes per row plus 8 B
of gradient and hessian.  The least time for the traced trees is those bytes
over the HBM peak of all the cell's chips; the time taken is the summed
device time (mean over the devices) of the operations that the named file
under kernels/ matches."""

from trace_reduce import pattern_seconds


def read(ctx, kernels):
    trace = ctx["trace"]
    if trace is None or not ctx["traced_trees"]:
        return None
    secs, _ = pattern_seconds(trace["op_seconds"], ctx["kernels"](kernels))
    rows_read = sum(int(t.internal_count.sum()) for t in ctx["traced_trees"])
    row_bytes = ctx["features"] * ctx["bin_bytes"] + 8
    least = rows_read * row_bytes / (
        ctx["peak"]["hbm_bytes_per_s"] * ctx["chips"])
    return 100.0 * least / secs
