"""The plain reference of the Expo deployment: reference.py's check for a
table with categorical columns.  It imports nothing of the program.

What differs from reference.py:

* a categorical node (bit 0 of `decision_type`) sends a row left exactly
  when `int(x)` (truncated toward zero) is a member of the node's
  category bitset (`cat_boundaries` / `cat_threshold` of the model text,
  the node's `threshold` indexing them): NaN, negative and unkept codes go
  right.  Each node's bitset is a row of 32-bit words, and a row's word is
  picked by a select over them, so routing stays elementwise and one
  compiled program serves every tree;
* the leaf values it recomputes are -lr * G / (H + lambda_l2 + cat_l2) for
  the two children of a many-vs-many categorical split (one on a feature
  of more than `max_cat_to_onehot` bins, counting the bin every unkept
  category shares) and -lr * G / (H + lambda_l2) otherwise.

The rest is reference.py's own: the trees' arrays, its initial score, its
f64 sums, the seeded sample of rows the final scores are compared on, the
held-out binary log-loss, and the same numbers under the same names.
"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import reference  # noqa: E402

BLOCK_ROWS = reference.BLOCK_ROWS


class Tree(reference.Tree):
    """One tree of the model text, numerical and categorical splits."""

    def __init__(self, fields):
        super().__init__({**fields, "num_cat": "0"})
        n = self.num_leaves - 1
        dt = fields.get("decision_type")
        self.decision_type = (np.array(dt.split(), dtype=np.int64) if dt
                              else np.zeros(n, dtype=np.int64))
        self.is_cat = (self.decision_type & 1) != 0
        words = np.zeros((n, 1), dtype=np.uint32)
        if int(fields.get("num_cat", 0)):
            bounds = np.array(fields["cat_boundaries"].split(), np.int64)
            flat = np.array(fields["cat_threshold"].split(), np.uint32)
            width = int(np.diff(bounds).max())
            words = np.zeros((n, width), dtype=np.uint32)
            for a in np.flatnonzero(self.is_cat):
                k = int(self.threshold[a])
                row = flat[bounds[k]:bounds[k + 1]]
                words[a, :len(row)] = row
        self.cat_words = words


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


def feature_bins(model_text):
    """{feature: bins} of the categorical features of the model text's
    `feature_infos`: its kept categories plus the one bin of the rest."""
    for line in model_text.splitlines():
        if line.startswith("feature_infos="):
            infos = line.partition("=")[2].split()
            return {f: len(info.split(":")) + 1
                    for f, info in enumerate(infos)
                    if not info.startswith("[") and info != "none"}
    return {}


def _padded(tree, width, words):
    """reference._padded plus the categorical flag and bitset of each
    node, padded to `words` 32-bit words."""
    feat, thr, P, depth = reference._padded(tree, width)
    is_cat = np.zeros(width - 1, dtype=bool)
    bits = np.zeros((width - 1, words), dtype=np.uint32)
    n = tree.num_leaves - 1
    is_cat[:n] = tree.is_cat
    bits[:n, :tree.cat_words.shape[1]] = tree.cat_words
    return feat, thr, is_cat, bits.view(np.int32), P, depth


def route(X, trees, block_rows=BLOCK_ROWS):
    """Leaf index of every row of X in every tree: (len(trees), rows)
    int16, as reference.route, with the categorical test beside the
    numerical one."""
    import jax
    import jax.numpy as jnp

    words = max(1, max(t.cat_words.shape[1] for t in trees))

    @jax.jit
    def route_block(xb, f, t, c, w, p, d):
        v = jnp.take(xb.T, f, axis=0)                      # (nodes, rows)
        code = jnp.trunc(v)
        ok = jnp.isfinite(v) & (code >= 0) & (code < 32 * words)
        k = jnp.where(ok, code, 0).astype(jnp.int32)
        word = jnp.broadcast_to(w[:, :1], k.shape)
        for i in range(1, words):
            word = jnp.where((k >> 5) == i, w[:, i:i + 1], word)
        member = ok & ((jax.lax.shift_right_logical(word, k & 31) & 1) != 0)
        test = jnp.where(c[:, None], member, v <= t[:, None])
        sign = jnp.where(test, 1.0, -1.0).astype(jnp.bfloat16)
        hit = jnp.dot(p.astype(jnp.bfloat16).T, sign,
                      preferred_element_type=jnp.float32)  # (leaves, rows)
        return jnp.argmax(hit - d[:, None].astype(jnp.float32),
                          axis=0).astype(jnp.int16)

    rows = len(X)
    block_rows = min(block_rows, -(-rows // 1024) * 1024)
    width = max(2, max(t.num_leaves for t in trees))
    padded = [tuple(jnp.asarray(a) for a in _padded(t, width, words))
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


def leaf_l2(tree, params, bins):
    """Per leaf, the L2 its value is regularised with: lambda_l2, plus
    cat_l2 where the split that made the leaf is a many-vs-many
    categorical one (`bins`: feature_bins of the model text)."""
    l2 = float(params.get("lambda_l2", 0.0))
    cat_l2 = float(params.get("cat_l2", 10.0))
    onehot = int(params.get("max_cat_to_onehot", 4))
    out = np.full(tree.num_leaves, l2)
    for a in range(tree.num_leaves - 1):
        f = int(tree.split_feature[a])
        if tree.is_cat[a] and bins.get(f, 0) > onehot:
            for child in (tree.left_child[a], tree.right_child[a]):
                if child < 0:
                    out[-int(child) - 1] = l2 + cat_l2
    return out


def follow(leaf_of, y, trees, params, recompute, bins):
    """reference.follow with each leaf's own L2 (leaf_l2)."""
    lr = float(params["learning_rate"])
    y64 = np.asarray(y, dtype=np.float64)
    init = reference.initial_score(y)
    scores = np.full(len(y), init, dtype=np.float64)
    count_gap, value_gap, value_mgap = 0.0, 0.0, 0.0
    notes = []
    for i, tree in enumerate(trees):
        leaf = leaf_of[i]
        L = tree.num_leaves
        counts = np.bincount(leaf, minlength=L)[:L]
        count_gap = max(count_gap, float((
            np.abs(counts - tree.leaf_count)
            / np.maximum(counts, np.median(counts))).max()))
        if i < recompute:
            p = 1.0 / (1.0 + np.exp(-scores))
            G = np.bincount(leaf, weights=p - y64, minlength=L)[:L]
            H = np.bincount(leaf, weights=p * (1.0 - p), minlength=L)[:L]
            with np.errstate(invalid="ignore", divide="ignore"):
                values = np.where(
                    counts > 0, -lr * G / (H + leaf_l2(tree, params, bins)),
                    0.0)
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
                f"reference {values[k]:.6g}); median leaf "
                f"{np.median(gap):.3e}; categorical splits "
                f"{int(tree.is_cat.sum())} of {L - 1}")
        else:
            values = tree.leaf_value
        scores += values[leaf] - (init if i == 0 else 0.0)
    numbers = {"leaf_count_gap": count_gap, "leaf_value_gap": value_gap,
               "leaf_value_median_gap": value_mgap}
    return numbers, scores, notes


def check(model_text, X, y, rows, prog_scores, params, wl, seed, iterations):
    """reference.check's three parts on categorical trees: (a) every
    training and held-out row routed through the first trees on the raw
    values, (b) the boosting loop followed in f64 through them, (c) a
    seeded sample of rows routed through the later trees and the final
    scores compared.  Returns (numbers, heldout, trees, notes)."""
    trees = parse_model(model_text)
    bins = feature_bins(model_text)
    Xt, yt, yh = X[:rows], y[:rows], y[rows:]
    head = max(wl["recompute_trees"], wl["heldout_trees"])
    leaf_of = route(X, trees[:head])
    numbers, ref_scores, notes = follow(
        leaf_of[:, :rows], yt, trees[:head], params, wl["recompute_trees"],
        bins)
    sample = reference.sample_rows(seed, rows, wl["score_sample_rows"])
    ref_final = ref_scores[sample]
    if len(trees) > head:
        tail = route(Xt[sample], trees[head:])
        ref_final = ref_final + reference.predict_raw(tail, trees[head:])
    numbers["leaf_count_sum_gap"] = max(
        abs(int(t.leaf_count.sum()) - rows) for t in trees)
    numbers["trees_missing"] = abs(iterations - len(trees))
    whole = len(prog_scores) == rows and numbers["trees_missing"] == 0
    diff = (np.abs(prog_scores[sample] - ref_final) if whole
            else np.array([1e30]))
    numbers["train_score_gap"] = float(diff.max())
    numbers["train_score_median_gap"] = float(np.median(diff))
    cut = wl["heldout_trees"]
    heldout = (reference.logloss(
        yh, reference.predict_raw(leaf_of[:cut, rows:], trees[:cut]))
        if len(trees) >= cut else 1e30)
    return numbers, heldout, trees, notes
