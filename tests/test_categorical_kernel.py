"""Categorical splits on the row kernels (ops/partition_pallas.py: the
decision's category-set arm), interpreted on the CPU at toy sizes, and what
the program records of them.

* ``lgbm_partition`` decides category sets of 1, 7 and 32 bins, sets that
  touch bin 255 and numerical splits of a categorical table as the XLA
  partition does, over a leaf of several chunks; bin 0 never goes left;
* five trees on the Expo deployment's table (benchmark/generators/expo.py)
  on both kernels pass the cell's own reference (benchmark/references/
  expo.py) under the cell's limits, and with gradients on a dyadic grid
  (every histogram exact in any summation order) the model text is the
  XLA partition's to the byte;
* ``train.split.categorical`` and ``train.split.categorical_members``
  under telemetry on, and the ``search_cat`` phase of the step.
"""

import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.obs import scopes
from lightgbm_tpu.ops import partition_pallas

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "expo-cat-l255-b255.rows44m"
LIMITS = json.load(open(os.path.join(
    ROOT, "benchmark", "workloads", CELL + ".json")))["limits"]
ROWS, C = 6000, 256


def _load(folder, stem):
    path = os.path.join(ROOT, "benchmark", folder, stem + ".py")
    spec = importlib.util.spec_from_file_location(f"{folder}_{stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


GENERATOR = _load("generators", "expo")
REFERENCE = _load("references", "expo")


@pytest.fixture(autouse=True)
def _telemetry_off():
    obs.get().reset(mode="off")
    yield
    obs.get().reset(mode="off")


def _table(rows=ROWS, seed=2**31 + 11):
    X, y, kw = GENERATOR.make(seed, rows, 0, {})
    return X, y, kw


def _params(**extra):
    return {"objective": "binary", "num_leaves": 15, "max_bin": 255,
            "learning_rate": 0.1, "verbosity": -1, "tpu_row_chunk": C,
            **extra}


def _booster(**extra):
    X, y, kw = _table()
    ds = lgb.Dataset(X, label=y, **kw)
    return lgb.Booster(_params(**extra), ds), X, y


def _stable(pb, pg, start, cnt, left):
    """The kernel's layout: lefts in order, rights behind them in order."""
    order = np.concatenate([np.where(left)[0], np.where(~left)[0]]) + start
    pb, pg = pb.copy(), pg.copy()
    pb[:, start:start + cnt] = pb[:, order]
    pg[:, start:start + cnt] = pg[:, order]
    return pb, pg


def _set(bins):
    s = np.zeros(256, bool)
    s[list(bins)] = True
    return s


SPLITS = {
    "set_of_1": _set([17]),
    "set_of_7": _set([1, 2, 30, 31, 32, 33, 200]),
    "set_of_32": _set(range(97, 225, 4)),
    "touches_bin_255": _set([63, 64, 127, 128, 191, 192, 254, 255]),
    "numerical_split": None,
}


@pytest.fixture(scope="module")
def learners():
    """The kernel's learner and the XLA partition's on one categorical
    table, each with its partition jitted once for every case."""
    kernel = _booster(tpu_kernel_interpret=True)[0]._gbdt.learner
    oracle = _booster(tpu_partition_kernel="xla")[0]._gbdt.learner
    return (kernel, oracle, jax.jit(kernel._partition_leaf),
            jax.jit(oracle._partition_leaf))


@pytest.mark.parametrize("name", sorted(SPLITS))
def test_partition_decides_category_sets_as_the_xla_partition(name,
                                                              learners):
    """``lgbm_partition`` with its categorical arm against
    ``learner.py:_partition_leaf``'s XLA form, on bins of every value
    0-255 in the split column, over leaves of 5 to 8 chunks.  The kernel's
    layout is the stable one, bit for bit in the bins and in every live
    payload row; the XLA form has the same lefts, bit for bit, and the same
    rows on the right (payload row 2 numbers them), its chunks packed
    backward from the range's end."""
    kernel, oracle, partition, xla_partition = learners
    assert kernel.has_categorical and kernel.plan.partition == "pallas"
    assert oracle.plan.partition == "xla"
    G, Np = oracle.G, oracle.N_pad
    assert Np == kernel.N_pad and kernel._pb_rows == 32
    rng = np.random.RandomState(len(name))
    bins = rng.randint(0, 256, (G, Np)).astype(np.uint8)
    carrier = np.zeros((kernel._pb_rows, Np), np.uint8)
    carrier[:G] = bins
    pg = rng.randn(8, Np).astype(np.float32)
    pg[2] = np.arange(Np)
    live = kernel._ghi_live
    cat = SPLITS[name]
    for trial in range(2):
        start = kernel.row0 + int(rng.randint(0, 700))
        cnt = int(rng.randint(4 * C + 1, 8 * C))
        col = int(rng.randint(0, G))
        if cat is None:
            thr = int(rng.randint(1, 255))
            scalars = (0, 0, 256, 0, 0, thr, 0, False, np.zeros(256, bool))
            left = bins[col, start:start + cnt] <= thr
        else:
            scalars = (0, 0, 256, 0, 0, 0, 0, True, cat)
            left = cat[bins[col, start:start + cnt]]
            assert not left[bins[col, start:start + cnt] == 0].any()
        scalars = tuple(jnp.asarray(v) for v in scalars)
        got, got_nl = partition(
            {"part_bins": jnp.asarray(carrier), "part_ghi": jnp.asarray(pg),
             "sc_packed": jnp.zeros((partition_pallas.sc_rows_for(32), Np),
                                    jnp.int32)}, start, cnt, col, scalars)
        ref, ref_nl = xla_partition(
            {"part_bins": jnp.asarray(bins), "part_ghi": jnp.asarray(pg),
             "sc32": jnp.zeros((G + 8, Np), jnp.int32)},
            start, cnt, col, scalars)
        nl = int(left.sum())
        assert 0 < nl < cnt
        assert int(got_nl) == int(ref_nl) == nl
        want_pb, want_pg = _stable(bins, pg, start, cnt, left)
        got_pb = np.asarray(got["part_bins"])
        got_pg = np.asarray(got["part_ghi"])
        np.testing.assert_array_equal(got_pb[:G], want_pb)
        assert not got_pb[G:].any()
        np.testing.assert_array_equal(got_pg[:live].view(np.int32),
                                      want_pg[:live].view(np.int32))
        ref_pb = np.asarray(ref["part_bins"])
        ref_pg = np.asarray(ref["part_ghi"])
        lefts = slice(0, start + nl)
        np.testing.assert_array_equal(ref_pb[:, lefts], want_pb[:, lefts])
        np.testing.assert_array_equal(ref_pg[:live, lefts],
                                      want_pg[:live, lefts])
        rights = slice(start + nl, start + cnt)
        by_id = np.argsort(ref_pg[2, rights], kind="stable")
        np.testing.assert_array_equal(ref_pb[:, rights][:, by_id],
                                      want_pb[:, rights])


def test_cat_words_are_the_sets_bits():
    cat = SPLITS["touches_bin_255"]
    words = np.asarray(partition_pallas.cat_words(jnp.asarray(cat)))
    assert words.dtype == np.int32 and words.shape == (8,)
    bits = np.unpackbits(words.view(np.uint8), bitorder="little")
    np.testing.assert_array_equal(bits.astype(bool), cat)
    short = np.asarray(partition_pallas.cat_words(jnp.asarray(cat[:13])))
    assert short[0] == words[0] & ((1 << 13) - 1) and not short[1:].any()


def test_five_trees_on_the_kernels_pass_the_cells_reference():
    """The deployment's table through ``lgb.train`` with both kernels
    interpreted: the plan keeps them, most splits are category sets, and
    the cell's own reference holds the trees, the leaf values (cat_l2 on
    the children of a categorical split) and the scores under the cell's
    five limits."""
    X, y, kw = GENERATOR.make(2**31 + 11, ROWS, 500, {})
    params = _params(tpu_kernel_interpret=True)
    bst = lgb.train(params, lgb.Dataset(X[:ROWS], label=y[:ROWS], **kw),
                    num_boost_round=5)
    kp = bst._gbdt.kernel_plan()
    assert (kp["partition"], kp["hist"], kp["search"], kp["mega"]) \
        == ("pallas", "pallas", "xla", "off")
    text = bst.model_to_string()
    wl = {"recompute_trees": 5, "heldout_trees": 5,
          "score_sample_rows": ROWS}
    numbers, heldout, trees, _ = REFERENCE.check(
        text, X, y, ROWS, np.asarray(bst._gbdt.scores, np.float64), params,
        wl, 7, 5)
    assert all(t.num_leaves == 15 for t in trees)
    assert sum(t.is_cat.sum() for t in trees) > 0.5 * 5 * 14
    assert 0 < heldout < np.log(2)
    for name, limit in LIMITS.items():
        assert numbers[name] <= limit, (name, numbers)


def _dyadic(preds, ds):
    """A binary-like objective whose gradients and hessians lie on a 2^-6
    grid: every histogram sum is then exact in f32 in any order, so the
    histogram kernel and the XLA loop agree bit for bit and the trees
    differ only if the partitions do."""
    label = ds.get_label()
    g = np.where(label > 0, -0.25, 0.25) \
        + np.clip(np.round(np.asarray(preds) * 16), -8, 8) / 64
    return g, np.full(len(label), 0.25)


LEARNERS = {
    "serial": {},
    "data_parallel_4": {"tree_learner": "data"},
    "frontier_k4": {"tpu_frontier_k": 4},
}


@pytest.mark.parametrize("learner", sorted(LEARNERS))
def test_model_text_is_the_xla_partitions(learner, monkeypatch):
    """The serial learner, the data-parallel one on four virtual devices
    (each decides its own rows' category sets in its own kernel) and the
    batched tree body of four leaves a step: with both kernels
    interpreted, the model text is the XLA partition's to the byte."""
    if learner == "data_parallel_4":
        cpus = jax.devices()[:4]
        monkeypatch.setattr(jax, "devices", lambda *a, **k: cpus)
    X, y, kw = _table()
    texts = []
    for extra in ({"tpu_kernel_interpret": True},
                  {"tpu_partition_kernel": "xla"}):
        bst = lgb.train(_params(objective=_dyadic, **LEARNERS[learner],
                                **extra),
                        lgb.Dataset(X, label=y, **kw), num_boost_round=5)
        kp = bst._gbdt.kernel_plan()
        kernels = "pallas" if "tpu_kernel_interpret" in extra else "xla"
        assert (kp["partition"], kp["hist"]) == (kernels, kernels), kp
        assert kp["frontier_k"] == (4 if learner == "frontier_k4" else 1)
        sharded = bst._gbdt.sharded_builder
        assert (sharded is not None) == (learner == "data_parallel_4")
        if sharded is not None:
            assert sharded.ndev == 4
        text = bst.model_to_string()
        texts.append(text[:text.index("end of trees")])
    assert "num_cat=0" not in texts[0]
    assert texts[0] == texts[1]


def _popcount(words):
    return int(np.unpackbits(
        np.asarray(words, np.uint32).view(np.uint8)).sum())


@pytest.mark.parametrize("mode", ["off", "counters"])
def test_categorical_split_counters(mode):
    """The tree's categorical splits and the sum of their sets' sizes, as
    the model text has them, under telemetry on; nothing with it off."""
    X, y, kw = _table()
    params = _params(telemetry=mode)
    bst = lgb.train(params, lgb.Dataset(X, label=y, **kw),
                    num_boost_round=3)
    bst._gbdt._flush_pending()
    counters = bst.telemetry_report(include_memory=False).get("counters", {})
    if mode == "off":
        assert "train.split.categorical" not in counters
        return
    trees = REFERENCE.parse_model(bst.model_to_string())
    splits = sum(int(t.is_cat.sum()) for t in trees)
    members = sum(_popcount(t.cat_words[a]) for t in trees
                  for a in np.flatnonzero(t.is_cat))
    assert splits > 0 and members >= splits
    assert counters["train.split.categorical"] == splits
    assert counters["train.split.categorical_members"] == members


def test_numerical_tables_count_no_categorical_split():
    X, y, _ = _table()
    bst = lgb.train(_params(telemetry="counters"), lgb.Dataset(X, label=y),
                    num_boost_round=2)
    bst._gbdt._flush_pending()
    counters = bst.telemetry_report(include_memory=False)["counters"]
    assert "train.split.categorical" not in counters


@pytest.mark.parametrize("categorical", [True, False],
                         ids=["categorical", "numerical"])
def test_search_cat_is_a_phase_of_categorical_steps_only(categorical):
    scopes._reset()
    X, y, kw = _table()
    bst = lgb.Booster(_params(), lgb.Dataset(
        X, label=y, **(kw if categorical else {})))
    bst.update()
    np.asarray(bst._gbdt.scores)
    step = set(scopes.scope_table()["train.fused_step"].values())
    assert "search" in step
    assert ("search_cat" in step) == categorical
