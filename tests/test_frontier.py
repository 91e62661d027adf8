"""Frontier-batched tree growth (tpu_frontier_k, models/learner.py
_build_tree_frontier): growing the top-K frontier leaves per while-loop
step must produce trees BIT-IDENTICAL to the K=1 oracle — including at
the num_leaves budget boundary, where the oracle-order replay prunes
speculative splits and the tree-end undo pass restores the pruned
ranges' physical row order (next-iteration f32 accumulation order).

The undo reads ONE N-long snapshot row that each step fills over the
ranges it is about to partition (``_snapshot_rowids``): the prune tests
run under the XLA partition and under the interpreted Pallas partition,
over several trees in a row, and with a window narrower than the leaves.

Order-dependent machinery (forced splits, monotone constraints, CEGB,
extra_trees, bynode sampling, interaction constraints, parallel
learners) must fall back to K=1 with a warning.
"""

import json
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.dataset import BinnedDataset
from lightgbm_tpu.models.learner import SerialTreeLearner


def _data(seed=7, n=700, f=6, cat=False):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    if cat:
        X[:, -1] = rng.randint(0, 10, size=n)
    y = (X[:, 0] + 0.5 * np.sin(X[:, 1] * 2)
         + 0.4 * rng.randn(n) > 0).astype(np.float64)
    return X, y


BASE = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
        "min_data_in_leaf": 5, "metric": ""}


def _trees(bst):
    """Model text minus the [param] dump (tpu_frontier_k legitimately
    differs between the arms; the TREES must not)."""
    return [ln for ln in bst.model_to_string().splitlines()
            if not ln.startswith("[")]


def _train(X, y, nbr=2, cat=False, **kw):
    p = {**BASE, **kw}
    if cat:
        p["categorical_feature"] = [X.shape[1] - 1]
    return lgb.train(p, lgb.Dataset(X, label=y), num_boost_round=nbr)


# ---------------------------------------------------------------------------
# bit-identity matrix vs the K=1 oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("extra,cat", [
    ({}, False),                                              # plain
    ({"bagging_fraction": 0.6, "bagging_freq": 1}, False),    # bagging
    ({"data_sample_strategy": "goss"}, False),                # GOSS
    ({"use_quantized_grad": True}, False),                    # quantized
    ({}, True),                                               # categorical
    ({"min_gain_to_split": 5.0}, False),                      # early stop
    ({"lambda_l1": 0.5, "lambda_l2": 3.0,
      "path_smooth": 1.0}, False),                            # regularized
])
def test_frontier_bitidentity(extra, cat):
    X, y = _data(cat=cat)
    b1 = _train(X, y, cat=cat, **extra)
    bk = _train(X, y, cat=cat, tpu_frontier_k=3, **extra)
    assert bk._gbdt.learner.plan.frontier_k == 3
    assert _trees(b1) == _trees(bk)
    d = np.abs(np.asarray(b1.predict(X[:200]))
               - np.asarray(bk.predict(X[:200]))).max()
    assert float(d) == 0.0


def test_frontier_budget_boundary_partial_steps():
    """num_leaves budgets that do not divide by K force partial final
    steps (k_step shrinks to the remaining budget); trees must still be
    bit-identical, for several K including K > the frontier width of
    the early tree."""
    X, y = _data(seed=3)
    for L, K in ((8, 5), (12, 4), (15, 7)):
        b1 = _train(X, y, num_leaves=L)
        bk = _train(X, y, num_leaves=L, tpu_frontier_k=K)
        assert _trees(b1) == _trees(bk), (L, K)


@pytest.mark.slow  # 6.7 s: tier-1 window trim (PR 14) — frontier
# bit-identity keeps its fast in-window representatives in
# test_frontier_bitidentity (the multiclass lane also rides
# test_chunkpolicy.py::test_chunk_bitidentity)
def test_frontier_multiclass_and_regression():
    X, y = _data(seed=11)
    ym = (np.abs(X[:, 0]) + X[:, 1] > 1).astype(float) + (X[:, 2] > 0)
    for params, yy in ((
            {"objective": "multiclass", "num_class": 3}, ym), (
            {"objective": "regression"}, X[:, 0] + 0.3 * X[:, 1])):
        p1 = {**BASE, **params}
        b1 = lgb.train(p1, lgb.Dataset(X, label=yy), num_boost_round=2)
        bk = lgb.train({**p1, "tpu_frontier_k": 4},
                       lgb.Dataset(X, label=yy), num_boost_round=2)
        assert _trees(b1) == _trees(bk), params["objective"]


def test_frontier_eager_path():
    X, y = _data(seed=5)
    b1 = _train(X, y, tpu_fused_iteration=False)
    bk = _train(X, y, tpu_fused_iteration=False, tpu_frontier_k=3)
    assert _trees(b1) == _trees(bk)


def test_frontier_mega_xla_interplay():
    """The mega-kernel XLA-oracle path has no histogram state at all;
    the frontier body must reuse its per-leaf both-children pass and
    stay bit-identical to the K=1 mega learner."""
    X, y = _data(seed=9)
    b1 = _train(X, y, tpu_megakernel="xla")
    bk = _train(X, y, tpu_megakernel="xla", tpu_frontier_k=3)
    assert b1._gbdt.learner.plan.mega == "xla"
    assert bk._gbdt.learner.plan.mega == "xla"
    assert bk._gbdt.learner.plan.frontier_k == 3
    assert _trees(b1) == _trees(bk)


@pytest.mark.slow
def test_frontier_megakernel_interpret_interplay():
    """Interpreter-mode Pallas mega-kernel under frontier batching:
    the k-loop drives one mega program per selected leaf and trees stay
    bit-identical to the K=1 mega learner (slow: interpreter)."""
    X, y = _data(seed=13, n=600)
    kw = {"tpu_kernel_interpret": True, "tpu_megakernel": "pallas",
          "tpu_row_chunk": 256}
    b1 = _train(X, y, nbr=1, **kw)
    bk = _train(X, y, nbr=1, tpu_frontier_k=3, **kw)
    assert b1._gbdt.learner.plan.mega == "pallas"
    assert bk._gbdt.learner.plan.mega == "pallas"
    assert _trees(b1) == _trees(bk)


# ---------------------------------------------------------------------------
# speculation/prune internals: the replay's invariants where pruning
# actually engages
# ---------------------------------------------------------------------------
# the partitions the frontier body drives: the XLA window partition, and
# the Pallas kernel through the interpreter under the plan the 42M-row
# benchmark cells run (partition=pallas search=xla mega=off: path_smooth
# keeps the general XLA search, so neither the pair-search kernel nor the
# mega-kernel takes the partition over)
PARTITIONS = {
    "xla": {},
    "pallas": {"tpu_kernel_interpret": True, "tpu_row_chunk": 256,
               "path_smooth": 1.0},
}
RECORD_FIELDS = ("s", "leaf_start", "leaf_cnt", "leaf_value",
                 "leaf_sum_g", "leaf_sum_h", "best_gain",
                 "node_feature", "node_threshold", "node_gain",
                 "node_left", "node_right", "indices")


def _masked_tree(X, y, seed, k, extra=None):
    """One tree on a seeded 55% gradient mask (noisy gains at a binding
    budget of 12 leaves), with the replay's state exported."""
    import jax.numpy as jnp
    mask = np.random.RandomState(seed).rand(len(y)) < 0.55
    grad = np.where(mask, 0.5 - y, 0.0).astype(np.float32)
    hess = np.where(mask, 0.25, 0.0).astype(np.float32)
    cfg = Config({**BASE, "num_leaves": 12, "tpu_frontier_k": k,
                  **(extra or {})})
    lr = SerialTreeLearner(BinnedDataset.from_matrix(X, cfg, label=y), cfg)
    lr._frontier_debug = True
    return lr, lr.build_tree(jnp.asarray(grad), jnp.asarray(hess),
                             bag_cnt=int(mask.sum()))


def _pruned_splits(rec):
    """(debug arrays, executed splits the replay never committed)."""
    dbg = {k: np.asarray(v) for k, v in rec["frontier_debug"].items()}
    return dbg, [j for j in range(int(rec["made"]))
                 if dbg["ora_of"][j] < 0]


@pytest.mark.parametrize("partition", sorted(PARTITIONS))
def test_frontier_prune_engages_and_stays_bitidentical(partition):
    """Noisy (bagged) gains at a binding budget make children outrank
    speculative picks, so some speculative splits must be PRUNED
    (made > committed); the replay bounds the overshoot by K-1 and the
    renumber+undo passes keep the record bit-identical to the oracle."""
    X, y = _data(seed=7, n=900)
    K = 4
    pruned_seen = 0
    for seed in range(6 if partition == "xla" else 3):
        lr, a = _masked_tree(X, y, seed, 1, PARTITIONS[partition])
        lr, b = _masked_tree(X, y, seed, K, PARTITIONS[partition])
        assert lr.plan.partition == partition
        assert lr.plan.frontier_k == K
        for field in RECORD_FIELDS:
            assert np.array_equal(np.asarray(a[field]),
                                  np.asarray(b[field])), (seed, field)
        made = int(np.asarray(b["made"]))
        m = int(np.asarray(b["s"]))
        assert made - m <= K - 1          # overshoot bound
        pruned_seen += int(made > m)
    assert pruned_seen > 0, \
        "no seed engaged pruning: the boundary lane tests nothing"


def test_frontier_snapshot_off_every_boundary(monkeypatch):
    """The undo snapshot with a window (96 rows) narrower than the leaves:
    a pruned range that starts and ends off a 128 boundary, off the
    window and off the row chunk, copied in several windows; and a pruned
    split whose sibling subtree was partitioned again AFTER its snapshot
    (ranges of live uncommitted splits are never selected again, so the
    later copies cannot reach the rows the undo restores)."""
    from lightgbm_tpu.models import learner as learner_mod
    from lightgbm_tpu.models.learner import ND_CNTP, ND_START
    monkeypatch.setattr(learner_mod, "_SNAP_WINDOW", 96)
    X, y = _data(seed=7, n=900)
    off_boundary = later_sibling = 0
    for seed in (0, 1, 5):
        _, a = _masked_tree(X, y, seed, 1)
        lr, b = _masked_tree(X, y, seed, 4)
        for field in RECORD_FIELDS:
            assert np.array_equal(np.asarray(a[field]),
                                  np.asarray(b[field])), (seed, field)
        dbg, pruned = _pruned_splits(b)
        assert pruned
        nm, made = dbg["nodemat"], int(b["made"])
        for j in pruned:
            st, cn = int(nm[ND_START, j]), int(nm[ND_CNTP, j])
            off_boundary += (st % 128 != 0 and (st + cn) % 128 != 0
                             and cn > 2 * 96 and cn % 96 != 0
                             and cn % lr.row_chunk != 0)
            item = int(np.nonzero(dbg["it_split"] == j)[0][0])
            par = (item - 1) // 2
            ps, pc = int(nm[ND_START, par]), int(nm[ND_CNTP, par])
            later_sibling += any(
                ps <= int(nm[ND_START, j2]) < ps + pc
                and not st <= int(nm[ND_START, j2]) < st + cn
                for j2 in range(j + 1, made))
    assert off_boundary > 0 and later_sibling > 0


@pytest.mark.parametrize("window", [64, 96, 1 << 18])
def test_snapshot_rowids_copies_exactly_the_range(monkeypatch, window):
    """``_snapshot_rowids`` against numpy: interior ranges off every
    boundary, one row, no row, a range that ends at the buffer's end (the
    last window is clamped and re-copies rows it already holds), and the
    whole buffer; everything outside the range stays as it was."""
    import jax.numpy as jnp
    from lightgbm_tpu.models import learner as learner_mod
    monkeypatch.setattr(learner_mod, "_SNAP_WINDOW", window)
    X, y = _data(n=300)
    lr = _learner_for({"tpu_frontier_k": 4}, X, y)
    Np = lr.N_pad
    rng = np.random.RandomState(0)
    ghi = rng.randn(8, Np).astype(np.float32)
    snap = rng.randn(Np).astype(np.float32)
    for start, cnt in ((131, 333), (7, 1), (500, 0), (Np - 211, 211),
                       (Np - 70, 65), (0, Np)):
        got = np.asarray(lr._snapshot_rowids(
            jnp.asarray(snap), jnp.asarray(ghi), jnp.int32(start),
            jnp.int32(cnt)))
        want = snap.copy()
        want[start:start + cnt] = ghi[2, start:start + cnt]
        assert np.array_equal(got, want), (start, cnt)


@pytest.mark.parametrize("partition", sorted(PARTITIONS))
def test_frontier_undo_feeds_the_next_tree(partition):
    """Several trees in a row through the booster, pruning in every one
    (bagged gains, 12 leaves): the undone layout is what the next tree's
    histograms accumulate over, so the trees AND the physical row layout
    left after the last one equal the K=1 learner's bit for bit."""
    from lightgbm_tpu import obs
    X, y = _data(seed=7, n=900)
    kw = {"num_leaves": 12, "bagging_fraction": 0.55, "bagging_freq": 1,
          **PARTITIONS[partition]}
    obs.get().reset(mode="counters")
    try:
        b1 = _train(X, y, nbr=4, **kw)
        trees1 = _trees(b1)
        assert "train.frontier.pruned_splits" not in \
            b1.telemetry_report(include_memory=False)["counters"]
        bk = _train(X, y, nbr=4, tpu_frontier_k=4, **kw)
        treesk = _trees(bk)
        counters = bk.telemetry_report(include_memory=False)["counters"]
    finally:
        obs.get().reset(mode="off")
    assert bk._gbdt.learner.plan.partition == partition
    assert counters["train.frontier.undo_trees"] >= 3   # of 4 trees
    assert trees1 == treesk
    for a, b in zip(b1._gbdt._phys, bk._gbdt._phys):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_frontier_prune_counters():
    """``train.frontier.pruned_splits`` / ``undo_trees`` say how often the
    snapshot's only reader runs; with telemetry off nothing is counted,
    and the record's extra scalar changes no model text."""
    from lightgbm_tpu import obs
    X, y = _data(seed=7, n=900)
    kw = {"num_leaves": 12, "bagging_fraction": 0.55, "bagging_freq": 1,
          "tpu_frontier_k": 4}
    texts = {}
    for mode in ("off", "counters"):
        obs.get().reset(mode=mode)
        try:
            bst = _train(X, y, nbr=5, **kw)
            texts[mode] = bst.model_to_string()    # drains the records
            counters = bst.telemetry_report(
                include_memory=False)["counters"]
        finally:
            obs.get().reset(mode="off")
        if mode == "off":
            assert not any(k.startswith("train.frontier") for k in counters)
        else:
            trees = counters["train.frontier.undo_trees"]
            assert 0 < trees <= 5
            assert trees <= counters["train.frontier.pruned_splits"] \
                <= 3 * trees                       # at most K-1 a tree
    assert texts["off"] == texts["counters"]
    # the eager path counts the same way (one scalar read per tree, only
    # when telemetry is on)
    obs.get().reset(mode="counters")
    try:
        bst = _train(X, y, nbr=2, tpu_fused_iteration=False, **kw)
        counters = bst.telemetry_report(include_memory=False)["counters"]
    finally:
        obs.get().reset(mode="off")
    assert 0 < counters["train.frontier.undo_trees"] <= 2


# ---------------------------------------------------------------------------
# fallbacks and config plumbing
# ---------------------------------------------------------------------------
def _learner_for(params, X, y):
    cfg = Config({**BASE, **params})
    ds = BinnedDataset.from_matrix(X, cfg, label=y)
    return SerialTreeLearner(ds, cfg)


def test_frontier_fallbacks_to_k1(tmp_path):
    X, y = _data()
    forced = tmp_path / "forced.json"
    forced.write_text(json.dumps({"feature": 0, "threshold": 0.0}))
    fallback_params = [
        {"monotone_constraints": "1,0,0,0,0,0"},
        {"monotone_constraints": "1,0,0,0,0,0",
         "monotone_constraints_method": "intermediate"},
        {"forcedsplits_filename": str(forced)},
        {"cegb_penalty_split": 0.1},
        {"cegb_penalty_feature_lazy": "0.1,0.1,0.1,0.1,0.1,0.1"},
        {"extra_trees": True},
        {"feature_fraction_bynode": 0.5},
        {"interaction_constraints": "[0,1],[2,3]"},
    ]
    for p in fallback_params:
        lr = _learner_for({**p, "tpu_frontier_k": 4}, X, y)
        assert lr.plan.frontier_k == 1, p
    # a fallback-engaged training equals the plain learner exactly
    b1 = _train(X, y, monotone_constraints="1,0,0,0,0,0")
    bk = _train(X, y, monotone_constraints="1,0,0,0,0,0",
                tpu_frontier_k=4)
    assert bk._gbdt.learner.plan.frontier_k == 1
    assert _trees(b1) == _trees(bk)


def test_frontier_k_plumbing():
    X, y = _data()
    # auto on CPU stays 1 (compile-budget heuristic; README)
    assert _learner_for({}, X, y).plan.frontier_k == 1
    assert _learner_for({"tpu_frontier_k": "auto"}, X,
                        y).plan.frontier_k == 1
    # explicit K engages anywhere, capped at num_leaves - 1
    assert _learner_for({"tpu_frontier_k": 6}, X, y).plan.frontier_k == 6
    assert _learner_for({"tpu_frontier_k": 99}, X, y).plan.frontier_k == 14
    assert _learner_for({"tpu_frontier_k": 1}, X, y).plan.frontier_k == 1
    with pytest.raises(ValueError):
        _learner_for({"tpu_frontier_k": 0}, X, y)
    with pytest.raises(ValueError):
        _learner_for({"tpu_frontier_k": "bogus"}, X, y)


def test_frontier_model_io_round_trip(tmp_path):
    """Frontier-trained boosters save/load/predict like any other."""
    X, y = _data(seed=21)
    bk = _train(X, y, tpu_frontier_k=3)
    p1 = np.asarray(bk.predict(X[:100]))
    out = tmp_path / "m.txt"
    bk.save_model(str(out))
    b2 = lgb.Booster(model_file=str(out))
    p2 = np.asarray(b2.predict(X[:100]))
    np.testing.assert_array_equal(p1, p2)
