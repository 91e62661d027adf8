"""Mean number of leaves of the window's trees."""


def read(ctx):
    trees = ctx["window_trees"]
    if not trees:
        return None
    return sum(t.num_leaves for t in trees) / len(trees)
