"""The categorical share of the window's splits, from the model text read
after the window: bit 0 of each split's `decision_type`.  The trees of a
configuration's own reference carry it (references/expo.py); trees that do
not, and a window with no split, read nothing."""


def read(ctx):
    kinds = [t.decision_type for t in ctx["window_trees"]
             if hasattr(t, "decision_type")]
    splits = sum(len(k) for k in kinds)
    if not splits:
        return None
    return 100.0 * sum(int((k & 1).sum()) for k in kinds) / splits
