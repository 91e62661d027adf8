"""Layered dense traversal (ops/forest_tensor.py + the serving
engine's ``predict_kernel`` knob).

The contract under test: the f32 layered path is BIT-IDENTICAL to the
stacked while-loop oracle (ops/predict.py) — leaves integer-equal,
raw scores byte-equal — across the NaN/missing-default, categorical,
multiclass, iteration-slicing, empty-tree/single-leaf and
quantized-plane matrix; the bf16 leaf plane is a tolerance path; and
the layered pack really is quantized (u8/u16 planes) with no
data-dependent while loop (the jaxlint ``predict.layered`` budget pins
the lowered text; here we pin the semantics)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.ops import forest_tensor
from lightgbm_tpu.ops.predict import predict_leaf_binned

BASE = {"verbosity": -1, "min_data_in_leaf": 10, "metric": ""}
N, F = 4500, 8


def _matrix(seed=7):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(N, F))
    X[:, 5] = rng.randint(0, 12, size=N)      # categorical column
    X[::7, 2] = np.nan                        # NaN column
    signal = (X[:, 0] * 2 + np.sin(X[:, 1] * 2)
              + np.where(np.isin(X[:, 5], [2, 5, 7]), 1.5, -0.5)
              + np.nan_to_num(X[:, 2]))
    return X, signal


def _train(params, X, y, rounds=8):
    bst = lgb.train(dict(BASE, **params), lgb.Dataset(X, label=y),
                    num_boost_round=rounds)
    bst._gbdt._flush_pending()
    return bst


@pytest.fixture(scope="module")
def reg_pair():
    """The same regression forest served by both kernels (training is
    deterministic, so the two boosters hold bit-identical trees)."""
    X, signal = _matrix()
    y = signal + 0.1 * np.random.RandomState(1).normal(size=N)
    Xn = X[:, :5]
    lay = _train({"objective": "regression", "num_leaves": 31,
                  "predict_kernel": "layered"}, Xn, y)
    loop = _train({"objective": "regression", "num_leaves": 31,
                   "predict_kernel": "loop"}, Xn, y)
    return lay, loop, Xn.astype(np.float64)


@pytest.fixture(scope="module")
def cat_pair():
    """Binary + categorical splits + NaN column under both kernels."""
    X, signal = _matrix(11)
    y = (signal > np.quantile(signal, 0.7)).astype(np.float64)
    p = {"objective": "binary", "num_leaves": 31,
         "categorical_feature": [5], "enable_bundle": False}
    lay = _train(dict(p, predict_kernel="layered"), X, y)
    loop = _train(dict(p, predict_kernel="loop"), X, y)
    return lay, loop, X.astype(np.float64)


@pytest.fixture(scope="module")
def mc_pair():
    X, signal = _matrix(13)
    y = np.digitize(signal, np.quantile(signal, [1 / 3, 2 / 3]))
    p = {"objective": "multiclass", "num_class": 3, "num_leaves": 15,
         "categorical_feature": [5], "enable_bundle": False}
    lay = _train(dict(p, predict_kernel="layered"), X, y, rounds=5)
    loop = _train(dict(p, predict_kernel="loop"), X, y, rounds=5)
    return lay, loop, X.astype(np.float64)


# ---------------------------------------------------------------------------
# bit-parity matrix: layered vs loop oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("pair", ["reg", "cat", "mc"])
def test_layered_raw_bit_identical(pair, reg_pair, cat_pair, mc_pair):
    lay, loop, X = {"reg": reg_pair, "cat": cat_pair,
                    "mc": mc_pair}[pair]
    a = np.asarray(lay.predict(X, raw_score=True))
    b = np.asarray(loop.predict(X, raw_score=True))
    assert lay._gbdt.serving._warm("insession"), \
        "layered engine must be serving"
    assert lay._gbdt.serving._kernel_for(
        lay._gbdt.serving._packs["insession"][1]) == "layered"
    if pair == "reg":
        # both kernels end in the same jnp.sum(vals * mask, axis=0), but
        # the installed XLA:CPU sums the trees in another association in
        # the two fusion contexts (4.8e-7 at most on O(1) scores, about
        # half the elements; first seen at PR 21, ROADMAP C8): f32
        # rounding of a tree sum, not a different tree walk, which
        # test_layered_leaves_equal holds bit for bit
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)
    else:
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("pair", ["reg", "cat", "mc"])
def test_layered_leaves_equal(pair, reg_pair, cat_pair, mc_pair):
    lay, loop, X = {"reg": reg_pair, "cat": cat_pair,
                    "mc": mc_pair}[pair]
    la = np.asarray(lay.predict(X[:700], pred_leaf=True))
    lb = np.asarray(loop.predict(X[:700], pred_leaf=True))
    np.testing.assert_array_equal(la, lb)


def test_layered_slicing_bit_identical(reg_pair):
    lay, loop, X = reg_pair
    for s, m in [(0, 3), (2, 3), (3, -1), (1, 100)]:
        a = np.asarray(lay.predict(X[:300], raw_score=True,
                                   start_iteration=s, num_iteration=m))
        b = np.asarray(loop.predict(X[:300], raw_score=True,
                                    start_iteration=s, num_iteration=m))
        np.testing.assert_array_equal(a, b)


def test_layered_early_stop_bit_identical(cat_pair):
    lay, loop, X = cat_pair
    kw = dict(raw_score=True, pred_early_stop=True,
              pred_early_stop_freq=3, pred_early_stop_margin=2.0)
    np.testing.assert_array_equal(
        np.asarray(lay.predict(X, **kw)),
        np.asarray(loop.predict(X, **kw)))


def test_layered_compile_counts_pinned(reg_pair):
    """The kernel swap must not change the pinned one-trace-per-
    (kind, bucket) contract."""
    lay, _, X = reg_pair
    eng = lay._gbdt.serving
    for n in (700, 600, 900):
        lay.predict(X[:n], raw_score=True)
        lay.predict(X[:n], pred_leaf=True)
    tr = eng.stats()["traces"]
    assert tr[("raw", 1024)] == 1, tr
    assert tr[("leaf", 1024)] == 1, tr


# ---------------------------------------------------------------------------
# kernel-level: quantized planes, empty/single-leaf trees
# ---------------------------------------------------------------------------
def test_quantized_plane_dtypes(reg_pair):
    lay, _, X = reg_pair
    pack = lay._gbdt.serving._packs["insession"][1]
    layers = pack["per_k"][0]["layers"]
    assert layers["flags8"].dtype == jnp.uint8
    assert layers["bins"].dtype == jnp.uint16
    assert layers["kids"].dtype in (jnp.int16, jnp.int32)
    assert pack["layers_depth"] is not None and pack["layers_depth"] > 0


def _stacked_forest_with_empty_tree():
    """Two trees: one real 1-split tree, one ZERO-node (single-leaf)
    tree — the stacked empty-tree guard matrix."""
    T, n = 2, 1
    host = {
        "col": np.zeros((T, n), np.int32),
        "bin_start": np.zeros((T, n), np.int32),
        "is_bundled": np.zeros((T, n), np.int32),
        "num_bin": np.full((T, n), 8, np.int32),
        "default_bin": np.zeros((T, n), np.int32),
        "missing_type": np.zeros((T, n), np.int32),
        "threshold": np.full((T, n), 3, np.int32),
        "default_left": np.zeros((T, n), np.int32),
        "left": np.full((T, n), -1, np.int32),    # ~leaf 0
        "right": np.full((T, n), -2, np.int32),   # ~leaf 1
        "num_nodes": np.asarray([1, 0], np.int32),
    }
    return host


def test_empty_and_single_leaf_trees_match_loop_oracle():
    host = _stacked_forest_with_empty_tree()
    layers = forest_tensor.pack_layered(host)
    assert layers is not None
    depth = layers.pop("max_depth")
    assert depth == 1
    binned = jnp.asarray(
        np.arange(8, dtype=np.int32).reshape(8, 1))     # (n, G=1)
    got = np.asarray(forest_tensor.predict_leaf_layered(
        binned, layers, depth))
    nodes = {k: jnp.asarray(v) for k, v in host.items()}
    want = np.asarray(jax.vmap(
        lambda nd: predict_leaf_binned(binned, nd))(nodes))
    np.testing.assert_array_equal(got, want)
    # the zero-node tree lands every row on leaf 0
    np.testing.assert_array_equal(got[1], np.zeros(8, np.int32))
    # bins 0..3 go left (leaf 0), 4..7 right (leaf 1)
    np.testing.assert_array_equal(got[0],
                                  (np.arange(8) > 3).astype(np.int32))


def test_all_empty_forest_is_leaf_zero():
    host = _stacked_forest_with_empty_tree()
    host["num_nodes"] = np.asarray([0, 0], np.int32)
    layers = forest_tensor.pack_layered(host)
    depth = layers.pop("max_depth")
    assert depth == 0
    binned = jnp.asarray(np.arange(4, dtype=np.int32).reshape(4, 1))
    got = np.asarray(forest_tensor.predict_leaf_layered(
        binned, layers, depth))
    np.testing.assert_array_equal(got, np.zeros((2, 4), np.int32))


def test_overdeep_forest_falls_back_to_loop(monkeypatch, reg_pair):
    """A forest past the unroll ceiling must refuse the layered pack
    (the engine then serves from the loop oracle)."""
    monkeypatch.setattr(forest_tensor, "MAX_UNROLL_DEPTH", 1)
    lay, _, X = reg_pair
    g = lay._gbdt
    eng = g.serving
    host = jax.device_get([(d["nodes"], d["leaf_value"])
                           for d in g.device_trees])
    stacked = {name: np.stack([h[0][name] for h in host])
               for name in host[0][0]}
    assert forest_tensor.pack_layered(stacked) is None
    # a fresh pack built under the ceiling serves loop-side
    eng.invalidate()
    pack = eng._pack("insession", eng._insession_pack)
    assert pack["layers_depth"] is None
    assert eng._kernel_for(pack) == "loop"
    out = lay.predict(X[:300], raw_score=True)
    ref = sum(t.predict(X[:300]) for t in g.models)
    np.testing.assert_allclose(np.asarray(out).reshape(-1), ref,
                               rtol=1e-6, atol=1e-6)
    # restore the layered pack for later tests
    monkeypatch.undo()
    eng.invalidate()
    eng._pack("insession", eng._insession_pack)


# ---------------------------------------------------------------------------
# bf16 leaf plane (opt-in tolerance path)
# ---------------------------------------------------------------------------
def test_bf16_leaf_plane_tolerance():
    X, signal = _matrix(17)
    y = signal + 0.1 * np.random.RandomState(3).normal(size=N)
    Xn = X[:, :5]
    f32 = _train({"objective": "regression", "num_leaves": 15}, Xn, y,
                 rounds=5)
    bf = _train({"objective": "regression", "num_leaves": 15,
                 "predict_bf16_leaves": True}, Xn, y, rounds=5)
    pack = bf._gbdt.serving._packs
    a = np.asarray(f32.predict(Xn, raw_score=True))
    b = np.asarray(bf.predict(Xn, raw_score=True))
    assert bf._gbdt.serving._warm("insession")
    deltas = bf._gbdt.serving._packs["insession"][1]["per_k"][0]["deltas"]
    assert deltas.dtype == jnp.bfloat16
    # bf16 has ~3 decimal digits: leaf values are O(1), 5 trees sum
    rel = np.max(np.abs(a - b) / (np.abs(a) + 1e-3))
    assert rel < 0.05, rel
    # leaves (integer traversal) stay exact — only values quantize
    np.testing.assert_array_equal(
        np.asarray(f32.predict(Xn[:200], pred_leaf=True)),
        np.asarray(bf.predict(Xn[:200], pred_leaf=True)))


def test_bf16_refit_keeps_dtype_and_zero_retrace():
    """The leaf-refresh fast path must preserve the bf16 plane dtype
    (an f32 refresh would change dtypes and re-trace)."""
    rng = np.random.RandomState(5)
    X = rng.normal(size=(4500, 5))
    y = X[:, 0] + 0.1 * rng.normal(size=4500)
    bst = _train({"objective": "regression", "num_leaves": 15,
                  "predict_bf16_leaves": True}, X, y, rounds=4)
    g = bst._gbdt
    bst.predict(X, raw_score=True)
    snap = g.serving.trace_snapshot()
    g.apply_refit_leaf_values(
        [np.asarray(t.leaf_value) * 0.5 for t in g.models])
    bst.predict(X, raw_score=True)
    assert g.serving.new_traces_since(snap) == {}, \
        "bf16 refit refresh must not re-trace"
    deltas = g.serving._packs["insession"][1]["per_k"][0]["deltas"]
    assert deltas.dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# predict_kernel knob plumbing
# ---------------------------------------------------------------------------
def test_unknown_kernel_rejected():
    rng = np.random.RandomState(9)
    X = rng.normal(size=(4500, 4))
    y = X[:, 0] + 0.1 * rng.normal(size=4500)
    bst = _train({"objective": "regression", "num_leaves": 7,
                  "predict_kernel": "warp"}, X, y, rounds=2)
    with pytest.raises(lgb.LightGBMError, match="predict_kernel"):
        bst.predict(X, raw_score=True)


def test_forced_layered_on_ineligible_forest_warns_and_serves(
        monkeypatch):
    monkeypatch.setattr(forest_tensor, "MAX_UNROLL_DEPTH", 0)
    rng = np.random.RandomState(19)
    X = rng.normal(size=(4500, 4))
    y = X[:, 0] + 0.1 * rng.normal(size=4500)
    bst = _train({"objective": "regression", "num_leaves": 7,
                  "predict_kernel": "layered"}, X, y, rounds=2)
    out = np.asarray(bst.predict(X, raw_score=True))
    assert bst._gbdt.serving._warned_layered
    ref = sum(t.predict(X) for t in bst._gbdt.models)
    np.testing.assert_allclose(out.reshape(-1), ref, rtol=1e-6,
                               atol=1e-6)


# ---------------------------------------------------------------------------
# multi-forest stacking (kernel level; the service path is covered in
# test_predict_engine.py)
# ---------------------------------------------------------------------------
def test_stack_forests_padded_slots_are_noops(reg_pair, mc_pair):
    lay, _, X = reg_pair
    mc, _, Xmc = mc_pair
    packs, deltas = [], []
    for bst in (lay,):
        g = bst._gbdt
        pack = g.serving._pack("insession",
                               g.serving._insession_pack)
        for pk in pack["per_k"]:
            hp = {k: np.asarray(v) for k, v in pk["layers"].items()}
            hp["max_depth"] = pack["layers_depth"]
            packs.append(hp)
            deltas.append(np.asarray(pk["deltas"], np.float32))
    # a second tiny forest forces tree/node padding of the first
    host = _stacked_forest_with_empty_tree()
    tiny = forest_tensor.pack_layered(host)
    td = tiny.pop("max_depth")
    tiny_np = {k: np.asarray(v) for k, v in tiny.items()}
    tiny_np["max_depth"] = td
    packs.append(tiny_np)
    deltas.append(np.asarray([[0.5, -0.5], [2.0, 0.0]], np.float32))
    stacked = forest_tensor.stack_forests(packs, deltas)
    assert stacked is not None
    depth = stacked.pop("max_depth")
    g = lay._gbdt
    binned0 = np.asarray(g.serving._bin(X[:64], False))
    G_max = max(binned0.shape[1], 1)
    binned_f = np.zeros((2, 64, G_max), binned0.dtype)
    binned_f[0, :, :binned0.shape[1]] = binned0
    binned_f[1, :, 0] = np.arange(64) % 8
    out = np.asarray(forest_tensor.predict_raw_layered_forests(
        jnp.asarray(binned_f), stacked, stacked["tree_mask"], depth))
    ref0 = np.asarray(lay.predict(X[:64], raw_score=True)) \
        - g.init_scores[0]
    np.testing.assert_allclose(out[0], ref0, rtol=0, atol=1e-6)
    bins = np.arange(64) % 8
    ref1 = np.where(bins > 3, -0.5, 0.5) + 2.0
    np.testing.assert_allclose(out[1], ref1, rtol=0, atol=0)


def test_loop_kernel_skips_layered_plane_build(reg_pair):
    """predict_kernel=loop must not build (or upload) layered planes
    the forced oracle can never read — they are ~45% extra resident
    pack bytes per model."""
    _, loop, X = reg_pair
    loop.predict(X, raw_score=True)            # warm: pack builds
    pack = loop._gbdt.serving._packs["insession"][1]
    assert pack["layers_depth"] is None
    assert all(pk["layers"] is None for pk in pack["per_k"])
