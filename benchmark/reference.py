"""The plain reference that decides `correct`, and the held-out loss.

It imports nothing of the program.  From the program it takes only what the
timed path produced: the model text (each tree's splits, leaf values and leaf
counts) and the training scores after the window.  Everything those are held
against it computes itself, from the raw f32 table and the labels:

* every row is routed through every tree by the tree's own (feature,
  threshold) tests on the raw values, never through the program's bins, so a
  leaf's row count is right only if binning, histogram counts and partition
  all agree with the plain `x <= threshold` semantics;
* for the first `recompute` trees the reference follows the boosting loop in
  f64: its own initial score (log-odds of the label mean), its own gradients
  and hessians from its own scores, summed per leaf, give the leaf values
  -lr * G / (H + lambda_l2) that the program's are compared with, and its own
  values (not the program's) move its scores on;
* the training scores the program ends with are compared row by row with the
  reference's.

`check` does all of it for run.py.  This is the default reference of a
configuration, for the binary objective on numerical splits: one that names
another (`"reference": "<stem>"`) brings `references/<stem>.py` with the same
`check`, and may import this module's pieces and replace what differs.

Routing is one matrix product per block of rows: with d[a] = +1/-1 for the
test at internal node a, and P[a, l] = +1/-1/0 for leaf l lying left of,
right of, or not under a, a row is in leaf l exactly when (d @ P)[l] equals
l's depth.  +-1 and 0 are exact in bf16 and the sums are small integers, so
the product is exact in any precision.
"""

import math

import numpy as np

BLOCK_ROWS = 1 << 20


class Tree:
    """One tree of the model text, numeric splits only."""

    def __init__(self, fields):
        def arr(key, dtype, default=None):
            if key not in fields:
                if default is None:
                    raise ValueError(f"model text: tree without {key}")
                return default
            return np.array(fields[key].split(), dtype=dtype)

        self.num_leaves = int(fields["num_leaves"])
        if int(fields.get("num_cat", 0)):
            raise ValueError("model text: categorical splits are not "
                             "something this reference routes")
        n = self.num_leaves - 1
        self.leaf_value = arr("leaf_value", np.float64)
        empty = np.zeros(0, dtype=np.int64)
        self.split_feature = arr("split_feature", np.int64, empty)
        self.threshold = arr("threshold", np.float64, empty.astype(float))
        self.left_child = arr("left_child", np.int64, empty)
        self.right_child = arr("right_child", np.int64, empty)
        self.leaf_count = arr("leaf_count", np.int64,
                              np.zeros(self.num_leaves, dtype=np.int64))
        self.internal_count = arr("internal_count", np.int64, empty)
        if not (len(self.split_feature) == len(self.threshold)
                == len(self.left_child) == len(self.right_child) == n
                and len(self.leaf_value) == self.num_leaves):
            raise ValueError("model text: tree arrays of the wrong length")

    def paths(self):
        """(P, depth): P[a, l] is +1 where leaf l lies under the left child
        of internal node a, -1 under the right, 0 elsewhere."""
        n = self.num_leaves - 1
        P = np.zeros((n, self.num_leaves), dtype=np.int8)
        depth = np.zeros(self.num_leaves, dtype=np.int32)
        stack = [(0, [])] if n else []
        while stack:
            node, path = stack.pop()
            for child, side in ((self.left_child[node], 1),
                                (self.right_child[node], -1)):
                here = path + [(node, side)]
                if child >= 0:
                    stack.append((int(child), here))
                else:
                    leaf = -int(child) - 1
                    for a, s in here:
                        P[a, leaf] = s
                    depth[leaf] = len(here)
        return P, depth


def parse_model(text):
    """The trees of a LightGBM model text, in order."""
    trees, fields = [], None
    for line in text.splitlines():
        if line.startswith("Tree="):
            fields = {}
            trees.append(fields)
        elif line.startswith("end of trees"):
            break
        elif fields is not None and "=" in line:
            key, _, value = line.partition("=")
            fields[key] = value
    return [Tree(f) for f in trees]


def _f32_floor(t):
    """Largest f32 <= t, so that for an f32 x: x <= t iff x <= _f32_floor(t)."""
    t32 = np.asarray(t, dtype=np.float64).astype(np.float32)
    over = t32.astype(np.float64) > t
    return np.where(over, np.nextafter(t32, np.float32(-np.inf)), t32)


def _padded(tree, width):
    """One tree's arrays padded to `width` leaves, so that every tree of a
    run goes through the same compiled program."""
    feat = np.zeros(width - 1, dtype=np.int32)
    thr = np.full(width - 1, np.inf, dtype=np.float32)
    P = np.zeros((width - 1, width), dtype=np.int8)
    depth = np.full(width, 1 << 20, dtype=np.int32)
    n = tree.num_leaves - 1
    p, d = tree.paths()
    feat[:n] = tree.split_feature
    thr[:n] = _f32_floor(tree.threshold)
    P[:n, :tree.num_leaves] = p
    depth[:tree.num_leaves] = d     # a stump: depth[0] = 0, every row hits
    return feat, thr, P, depth


def route(X, trees, block_rows=BLOCK_ROWS):
    """Leaf index of every row of X in every tree: (len(trees), rows) int16.
    Plain jax.numpy on the default device, one block of rows at a time; the
    trees are arguments, not constants, so one compiled program serves every
    tree, seed and run."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def route_block(xb, f, t, p, d):
        test = jnp.take(xb.T, f, axis=0) <= t[:, None]    # (nodes, rows)
        sign = jnp.where(test, 1.0, -1.0).astype(jnp.bfloat16)
        hit = jnp.dot(p.astype(jnp.bfloat16).T, sign,
                      preferred_element_type=jnp.float32)  # (leaves, rows)
        return jnp.argmax(hit - d[:, None].astype(jnp.float32),
                          axis=0).astype(jnp.int16)

    rows = len(X)
    block_rows = min(block_rows, -(-rows // 1024) * 1024)
    width = max(2, max(t.num_leaves for t in trees))
    padded = [tuple(jnp.asarray(a) for a in _padded(t, width))
              for t in trees]
    out = np.empty((len(trees), rows), dtype=np.int16)
    for a in range(0, rows, block_rows):
        b = min(rows, a + block_rows)
        xb = X[a:b]
        if b - a < block_rows:                         # keep one shape
            xb = np.concatenate(
                [xb, np.zeros((block_rows - (b - a), X.shape[1]), X.dtype)])
        xb = jnp.asarray(xb)
        for i, tree in enumerate(padded):
            out[i, a:b] = np.asarray(route_block(xb, *tree))[:b - a]
    return out


def sample_rows(seed, rows, size):
    """Sorted distinct row numbers drawn from the seed, about `size` of them
    (all rows where `size` is no smaller than `rows`)."""
    if size >= rows:
        return np.arange(rows)
    rng = np.random.default_rng([int(seed), 2])
    return np.unique(rng.integers(0, rows, size=size))


def initial_score(y):
    """LightGBM's boost_from_average for the binary objective, sigmoid 1."""
    p = float(np.mean(y, dtype=np.float64))
    return math.log(p / (1.0 - p))


def logloss(y, raw):
    """Mean binary log-loss in nats of raw scores, in f64."""
    sign = 2.0 * np.asarray(y, dtype=np.float64) - 1.0
    return float(np.mean(np.logaddexp(0.0, -sign * raw)))


def predict_raw(leaf_of, trees):
    """Raw score of the forest on rows already routed (`leaf_of` from
    `route`), by the trees' own leaf values."""
    return sum(t.leaf_value[leaf_of[i]] for i, t in enumerate(trees))


def follow(leaf_of, y, trees, params, recompute, sum_dtype=np.float64):
    """Follow the boosting loop over `trees`, whose rows `route` has placed
    (`leaf_of`), as the module's text sets out.

    Returns (numbers, scores, values, notes): `numbers` holds the gaps,
    `scores` the reference's training scores after the last tree, `values`
    each tree's leaf values as the reference has them, `notes` a line per
    recomputed tree on where its widest gap sits.  `sum_dtype` is the
    precision the per-leaf sums are taken in: f64 for the reference, and a
    lower one only where the reference stands in the program's place as the
    control (tests/test_benchmark.py)."""
    lr = float(params["learning_rate"])
    l2 = float(params.get("lambda_l2", 0.0))
    y64 = np.asarray(y, dtype=np.float64)
    init = initial_score(y)
    scores = np.full(len(y), init, dtype=np.float64)
    count_gap, value_gap, value_mgap = 0.0, 0.0, 0.0
    values_out, notes = [], []
    for i, tree in enumerate(trees):
        leaf = leaf_of[i]
        L = tree.num_leaves
        counts = np.bincount(leaf, minlength=L)[:L]
        # the model's leaf counts are the split search's estimates from the
        # hessians (as LightGBM's search makes them): reported, not compared
        count_gap = max(count_gap, float((
            np.abs(counts - tree.leaf_count)
            / np.maximum(counts, np.median(counts))).max()))
        if i < recompute:
            p = 1.0 / (1.0 + np.exp(-scores))
            g = (p - y64).astype(sum_dtype)
            h = (p * (1.0 - p)).astype(sum_dtype)
            G = _leaf_sums(leaf, g, L, sum_dtype)
            H = _leaf_sums(leaf, h, L, sum_dtype)
            with np.errstate(invalid="ignore", divide="ignore"):
                values = np.where(counts > 0, -lr * G / (H + l2), 0.0)
            if i == 0:      # boost_from_average is folded into tree 0
                values = values + init
            diff = np.abs(tree.leaf_value - values)
            gap = diff / np.maximum(np.abs(values), np.median(np.abs(values)))
            value_gap = float(np.max([value_gap, gap.max()]))  # keeps a NaN
            value_mgap = float(np.max([value_mgap, np.median(gap)]))
            k = int(np.argmax(gap))
            notes.append(
                f"tree {i}: widest leaf gap {gap[k]:.3e} at leaf {k} "
                f"(rows {counts[k]}, program {tree.leaf_value[k]:.6g}, "
                f"reference {values[k]:.6g}); median leaf {np.median(gap):.3e}; "
                f"gaps over 1e-4: {int((gap > 1e-4).sum())} of {L}, rows in "
                f"them {int(counts[gap > 1e-4].sum())}")
        else:
            values = tree.leaf_value
        values_out.append(values)
        scores += values[leaf] - (init if i == 0 else 0.0)
    numbers = {"leaf_count_gap": count_gap, "leaf_value_gap": value_gap,
               "leaf_value_median_gap": value_mgap}
    return numbers, scores, values_out, notes


def check(model_text, X, y, rows, prog_scores, params, wl, seed, iterations):
    """What the window produced, against the plain reference.

    `X`, `y`: the table as generated, the `rows` training rows first, then
    the held-out ones; `prog_scores`: the program's training scores after
    the window; `wl`: the workload's file; `iterations`: the trees the
    model should hold.  Returns (numbers, heldout, trees, notes): the
    numbers the workload's limits may name, the held-out loss in nats at
    `heldout_trees` trees, the parsed trees and a line per recomputed
    tree."""
    trees = parse_model(model_text)
    Xt, yt, yh = X[:rows], y[:rows], y[rows:]
    # the first `recompute_trees` trees: every row, training and held-out,
    # is routed, and the reference follows the boosting loop through them
    head = max(wl["recompute_trees"], wl["heldout_trees"])
    leaf_of = route(X, trees[:head])
    numbers, ref_scores, _, notes = follow(
        leaf_of[:, :rows], yt, trees[:head], params, wl["recompute_trees"])
    # the later trees: a sample of rows drawn from the seed is routed on
    # through them, and the program's final scores are held to the
    # reference's on those rows
    sample = sample_rows(seed, rows, wl["score_sample_rows"])
    ref_final = ref_scores[sample]
    if len(trees) > head:
        tail = route(Xt[sample], trees[head:])
        ref_final = ref_final + predict_raw(tail, trees[head:])
    numbers["leaf_count_sum_gap"] = max(
        abs(int(t.leaf_count.sum()) - rows) for t in trees)
    numbers["trees_missing"] = abs(iterations - len(trees))
    whole = len(prog_scores) == rows and numbers["trees_missing"] == 0
    diff = (np.abs(prog_scores[sample] - ref_final) if whole
            else np.array([1e30]))
    numbers["train_score_gap"] = float(diff.max())
    numbers["train_score_median_gap"] = float(np.median(diff))
    cut = wl["heldout_trees"]
    heldout = (logloss(yh, predict_raw(leaf_of[:cut, rows:], trees[:cut]))
               if len(trees) >= cut else 1e30)
    return numbers, heldout, trees, notes


def _leaf_sums(leaf, v, L, dtype):
    if dtype == np.float64:
        return np.bincount(leaf, weights=v, minlength=L)[:L]
    # a lower precision (the control): sum each leaf's rows in that type
    order = np.argsort(leaf, kind="stable")
    bounds = np.searchsorted(leaf[order], np.arange(L + 1))
    vs = v[order]
    return np.array([vs[bounds[k]:bounds[k + 1]].sum(dtype=dtype)
                     for k in range(L)], dtype=np.float64)
