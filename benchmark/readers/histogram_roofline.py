"""The leaf histograms' share of their roofline, the work reckoned from the
trees (the histogram-subtraction trick is taken as given: a split builds its
smaller child and subtracts for the other).

Rows histogrammed in a tree: the root's, plus for every split the smaller of
its two children's counts (`internal_count` for an internal child,
`leaf_count` for a leaf).  A row costs `features x bins x 2 planes` multiply-
adds (2 operations each) as a one-hot contraction, against the FLOP peak, and
`features x bin_bytes + 8` bytes read, against the HBM peak; the larger is
the least time.  The time taken is the device seconds of `phases` as
device_phase.py attributes them.  `bins` comes from the metric's file: the
harness hands a reader the bin bytes and not the bin count, and the metric
lists the cells of one bin count."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from device_phase import attribute, phase_by_name, scope_tables  # noqa: E402


def count_of(tree, child):
    """Rows under a child pointer: >= 0 an internal node, < 0 leaf ~child."""
    return int(tree.internal_count[child] if child >= 0
               else tree.leaf_count[-child - 1])


def rows_histogrammed(tree):
    if tree.num_leaves < 2:
        return int(tree.leaf_count.sum())
    return int(tree.internal_count[0]) + sum(
        min(count_of(tree, left), count_of(tree, right))
        for left, right in zip(tree.left_child, tree.right_child))


def least_seconds(ctx, bins):
    rows = sum(rows_histogrammed(t) for t in ctx["traced_trees"])
    peak, chips, feats = ctx["peak"], ctx["chips"], ctx["features"]
    by_flops = rows * feats * bins * 2 * 2.0 / (peak["flops_per_s"] * chips)
    by_bytes = rows * (feats * ctx["bin_bytes"] + 8.0) / (
        peak["hbm_bytes_per_s"] * chips)
    return max(by_flops, by_bytes)


def read(ctx, programs, phases, bins):
    trace = ctx["trace"]
    if trace is None or not ctx["traced_trees"]:
        return None
    per_phase, _ = attribute(trace["op_seconds"],
                             phase_by_name(scope_tables(programs)))
    secs = sum(per_phase.get(p, 0.0) for p in phases)
    if secs <= 0:        # a program with no such phase: nothing to read
        return None
    return 100.0 * least_seconds(ctx, bins) / secs
