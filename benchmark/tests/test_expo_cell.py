"""The Expo cell (categorical columns), rehearsed on the CPU.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_expo_cell.py -q

`--rehearse` shrinks the rows (30,000) and allows the CPU; every line says
`platform: cpu` and no number of it is a device number.  The rehearsal runs
the XLA partition and histogram: the kernels need a TPU or
`tpu_kernel_interpret` (tests/test_categorical_kernel.py interprets them on
this table).
"""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from test_benchmark import (BENCH, SPEC, cell_metrics, contract,  # noqa: E402
                            rehearse)

CELL = "expo-cat-l255-b255.rows44m"
WORKLOAD = json.load(open(os.path.join(BENCH, "workloads", CELL + ".json")))
CONFIG = json.load(open(os.path.join(
    BENCH, "configs", WORKLOAD["config"] + ".json")))


def _load(folder, stem):
    path = os.path.join(BENCH, folder, stem + ".py")
    spec = importlib.util.spec_from_file_location(f"{folder}_{stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


GENERATOR = _load("generators", CONFIG["generator"])
REFERENCE = _load("references", CONFIG["reference"])


def test_the_cell_is_the_deployment_on_one_chip():
    entry = [w for w in SPEC["workloads"] if w["name"] == CELL][0]
    assert entry["chips"] == WORKLOAD["chips"] == 1
    assert WORKLOAD["rows"] == 44_000_000
    assert WORKLOAD["holdout_rows"] == CONFIG["published_test_rows"]
    assert CONFIG["features"] == 8
    assert CONFIG["reduced"] == ["iterations", "rows"]
    assert [k for k in CONFIG["params"] if k.startswith(("tpu_", "cat_",
                                                         "max_cat"))] == []
    listed = {m["name"] for m in SPEC["per_layer"]
              if CELL in m.get("workloads", ())}
    assert {"cat_search_s_per_iter", "categorical_split_share",
            "partition_roofline"} <= listed
    assert "histogram_roofline" not in listed


def test_generator_is_seeded_repeats_and_tells_two_seeds_apart():
    X, y, kw = GENERATOR.make(2**31 + 5, 3000, 500, CONFIG)
    X2, y2 = GENERATOR.make_table(2**31 + 5, 3500, threads=1)
    X3, y3, _ = GENERATOR.make(2**31 + 6, 3000, 500, CONFIG)
    assert X.shape == (3500, 8) and X.dtype == np.float32
    assert np.array_equal(X, X2) and np.array_equal(y, y2)
    assert not np.array_equal(X, X3) and not np.array_equal(y, y3)
    assert kw == {"categorical_feature": [0, 1, 2, 4, 5, 6]}
    cats = X[:, kw["categorical_feature"]]
    assert (cats == np.rint(cats)).all() and (cats >= 0).all()
    assert (cats.min(axis=0) >= [1, 1, 1, 0, 0, 0]).all()
    assert (cats.max(axis=0) <= [12, 31, 7, 19, 299, 299]).all()
    assert 1 <= X[:, 3].min() and X[:, 3].max() <= 2400
    assert 30 <= X[:, 7].min() and X[:, 7].max() <= 4983
    assert set(np.unique(y)) == {0.0, 1.0} and 0.15 < y.mean() < 0.25


def test_generator_chunks_agree_across_the_chunk_boundary(monkeypatch):
    X, y = GENERATOR.make_table(7, 3000)
    monkeypatch.setattr(GENERATOR, "CHUNK_ROWS", 1024)
    Xc, yc = GENERATOR.make_table(7, 3000)
    assert X.shape == Xc.shape and not np.array_equal(X, Xc)
    Xa, ya = GENERATOR.make_table(7, 2048)
    assert np.array_equal(Xc[:2048], Xa) and np.array_equal(yc[:2048], ya)


def _small_model(rows=6000, trees=3):
    import lightgbm_tpu as lgb
    X, y, kw = GENERATOR.make(2**31 + 9, rows, 0, CONFIG)
    params = dict(CONFIG["params"], num_leaves=31)
    bst = lgb.train(params, lgb.Dataset(X, label=y, **kw),
                    num_boost_round=trees)
    return X, y, params, bst


def members(tree, node):
    """The category codes that go left at categorical node `node`."""
    bits = np.unpackbits(tree.cat_words[node].astype("<u4").view(np.uint8),
                         bitorder="little")
    return set(np.flatnonzero(bits).tolist())


def test_categorical_routing_agrees_with_a_row_by_row_traversal():
    X, _, _, bst = _small_model()
    trees = REFERENCE.parse_model(bst.model_to_string())
    assert sum(t.is_cat.sum() for t in trees) > 0
    # codes the table never holds: NaN, negative, fractional, unkept
    X = X.copy()
    X[::7, 5] = np.nan
    X[1::7, 4] = -3.0
    X[2::7, 0] = 2.5
    X[3::7, 6] = 1000.0
    leaf = REFERENCE.route(X, trees, block_rows=2048)
    for i, t in enumerate(trees):
        for r in range(0, len(X), 13):
            node = 0
            while node >= 0:
                x = X[r, t.split_feature[node]]
                if t.is_cat[node]:
                    go_left = (np.isfinite(x) and x >= 0
                               and int(x) in members(t, node))
                else:
                    go_left = x <= t.threshold[node]
                node = t.left_child[node] if go_left \
                    else t.right_child[node]
            assert leaf[i, r] == -node - 1, (i, r)


def test_leaf_values_take_cat_l2_on_the_children_of_a_categorical_split():
    X, y, params, bst = _small_model(trees=1)
    text = bst.model_to_string()
    tree = REFERENCE.parse_model(text)[0]
    bins = REFERENCE.feature_bins(text)
    assert set(bins) == {0, 1, 2, 4, 5, 6} and min(bins.values()) > 4
    l2 = REFERENCE.leaf_l2(tree, params, bins)
    assert set(np.unique(l2)) == {0.0, 10.0}
    leaf = REFERENCE.route(X, [tree])
    sound, _, _ = REFERENCE.follow(leaf, y, [tree], params, 1, bins)
    plain, _, _ = REFERENCE.follow(leaf, y, [tree], params, 1, {})
    assert sound["leaf_value_median_gap"] <= WORKLOAD["limits"][
        "leaf_value_median_gap"]
    # a leaf regularised with the plain L2 alone misses by far more
    assert plain["leaf_value_gap"] > 100 * sound["leaf_value_gap"]


@pytest.mark.parametrize("fault", ["none", "category_moved",
                                   "half_the_batch"])
def test_a_broken_run_reads_not_correct(fault):
    """A sound rehearsal passes; the model text with one category moved
    out of one node's set, and a dataset of half the rows, fail."""
    script = ("faulty_run.py" if fault == "half_the_batch"
              else "expo_faults.py")
    rc, last, out = rehearse(CELL, 0, extra=("--rehearse",),
                             program=(os.path.join(HERE, script), fault))
    assert rc == 0, out[-3000:]
    line = json.loads(last)
    assert line["correct"] is (fault == "none"), (fault, line["compared"])


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_ends_in_a_line_the_validator_accepts(trace):
    rc, last, out = rehearse(CELL, trace, extra=("--rehearse",))
    assert rc == 0, out[-3000:]
    line = json.loads(last)
    assert contract.problems(
        {k: v for k, v in line.items() if k != "compared"},
        cell_metrics(SPEC, CELL, trace), bool(trace)) == []
    assert line["device"]["platform"] == "cpu"
    assert "plan: partition=xla hist=xla search=xla" in out
    new = {"cat_search_s_per_iter", "categorical_split_share"}
    assert (new <= set(line["metrics"])) == bool(trace)
    if trace:
        assert line["metrics"]["cat_search_s_per_iter"]["value"] > 0
        assert 0 < line["metrics"]["categorical_split_share"]["value"] < 100
    assert line["correct"] is True and line["failed"] == 0, line["compared"]


def test_readers_read_nothing_where_there_is_nothing():
    sys.path.insert(0, os.path.join(BENCH, "readers"))
    import named_phase
    import tree_splits
    import types
    numeric = types.SimpleNamespace(num_leaves=3)
    assert tree_splits.read({"window_trees": [numeric]}) is None
    cat = types.SimpleNamespace(decision_type=np.array([1, 0, 9, 2]))
    assert tree_splits.read({"window_trees": [cat, numeric]}) == 50.0
    from lightgbm_tpu.obs import scopes
    scopes._reset()
    ctx = {"trace": {"op_seconds": {"fusion.1": 1.0}}, "traced_trees": []}
    assert named_phase.read(ctx, ["train.fused_step"], ["search_cat"]) \
        is None
