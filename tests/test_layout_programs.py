"""The one-chip layout programs, ``lgbm_layout_init`` and
``lgbm_layout_resume``, hold no data.

They build the fused step's payload block ``ghi`` from the scores and the
objective's per-row payload (the binary objective's signed label weight,
a regression's label and weight, a multiclass label and weight).  Those
rows are the programs' arguments: closed over, each would lower as an
N-element constant, which the compiler digests on every cold build and
which puts the labels into the compile cache's key (168 MB a program at
42M rows).  Checked at toy size on the CPU: the lowered text carries no
dense constant, is the same for another data set of the same shape, and
the ``ghi`` it builds is, bit for bit, the one a NumPy build gives.
"""

import re

import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu as lgb

N, F = 1500, 8

CASES = {
    "binary": {"objective": "binary"},
    "regression_weighted": {"objective": "regression"},
    "multiclass": {"objective": "multiclass", "num_class": 3},
}

_CONSTANT = re.compile(
    r"stablehlo\.constant dense<(.)[^>]*> : tensor<((?:\d+x)*)([a-z]+)(\d+)>")


def _booster(case, seed):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(N, F))
    z = X[:, 0] - 0.5 * X[:, 1] + 0.3 * rng.normal(size=N)
    if case == "binary":
        y = (z > 0).astype(float)
    elif case == "multiclass":
        y = np.digitize(z, [-0.5, 0.5]).astype(float)
    else:
        y = z
    w = rng.uniform(0.5, 2.0, size=N)
    params = {"num_leaves": 7, "min_data_in_leaf": 5, "verbosity": -1,
              "metric": "", **CASES[case]}
    bst = lgb.Booster(params, lgb.Dataset(X, label=y, weight=w))
    g = bst._gbdt
    assert g._fused_phys is not None and g._init_phys_fn is None, \
        "the case must take the one-chip fused physical path"
    return g, y, w


def _lowered_texts(g):
    """StableHLO of the adopting init and of the resume, lowered at the
    booster's own shapes."""
    lr = g.learner
    pays = g._phys_payloads
    rowid_bits = jnp.zeros((lr.N_pad,), jnp.float32)
    init = g._init_phys_adopt.lower(lr._part0, g.scores, *pays)
    resume = g._init_phys_perm.lower(lr._part0, rowid_bits, g.scores, *pays)
    return init.as_text(), resume.as_text()


def _dense_constant_bytes(text):
    """Bytes of each non-splat constant of a StableHLO module."""
    out = []
    for first, dims, _, bits in _CONSTANT.findall(text):
        if first in "[\"":
            n = int(np.prod([int(d) for d in dims.split("x") if d] or [1]))
            out.append(n * int(bits) // 8)
    return out


@pytest.mark.parametrize("case", sorted(CASES))
def test_layout_programs_hold_no_dense_constant(case):
    g, _, _ = _booster(case, seed=1)
    assert len(g._phys_payloads) == {"binary": 1, "regression_weighted": 2,
                                     "multiclass": 2}[case]
    for text in _lowered_texts(g):
        assert "stablehlo" in text
        assert max(_dense_constant_bytes(text), default=0) <= 1024


@pytest.mark.parametrize("case", sorted(CASES))
def test_layout_programs_lower_the_same_for_other_data(case):
    """Two data sets of one shape, with other features, labels and
    weights, give the same program text: the cache key holds no data."""
    a, ya, wa = _booster(case, seed=2)
    b, yb, wb = _booster(case, seed=3)
    assert not np.array_equal(ya, yb) and not np.array_equal(wa, wb)
    assert _lowered_texts(a) == _lowered_texts(b)


def _expected_ghi(g, y, w, case):
    """The payload block built in NumPy: zero gradient and hessian rows,
    the row-id bits (pad rows carry N), the scores a row per class, the
    objective's rows, zero rows to the block's height; every row padded
    by ``row0`` columns in front."""
    lr = g.learner
    C, Npad = lr.row0, lr.N_pad
    y32, w32 = y.astype(np.float32), w.astype(np.float32)
    if case == "binary":
        pays = [np.where(y > 0, 1.0, -1.0).astype(np.float32) * w32]
    else:
        pays = [y32, w32]
    scores = np.asarray(g.scores, np.float32).reshape(N, -1).T
    rowid = np.full(Npad, N, np.int32)
    rowid[C:C + N] = np.arange(N, dtype=np.int32)
    rows = [np.zeros(Npad, np.float32), np.zeros(Npad, np.float32),
            rowid.view(np.float32)]
    for r in [*scores, *pays]:
        row = np.zeros(Npad, np.float32)
        row[C:C + N] = r
        rows.append(row)
    rows += [np.zeros(Npad, np.float32)] * (lr._ghi_rows - len(rows))
    return np.stack(rows)


@pytest.mark.parametrize("case", sorted(CASES))
def test_layout_init_builds_the_numpy_block(case):
    """``lgbm_layout_init`` passes the bins through and builds the NumPy
    block bit for bit; the resume from that layout (row ids the identity)
    builds it again, with the bins unchanged."""
    g, y, w = _booster(case, seed=4)
    lr = g.learner
    bins = np.asarray(lr._part0)
    expected = _expected_ghi(g, y, w, case).view(np.int32)
    pb, ghi = g._init_phys(lr._part0, g.scores, *g._phys_payloads)
    np.testing.assert_array_equal(np.asarray(pb), bins)
    np.testing.assert_array_equal(np.asarray(ghi).view(np.int32), expected)
    pb2, ghi2 = g._init_phys_perm(pb, ghi[2], g.scores, *g._phys_payloads)
    np.testing.assert_array_equal(np.asarray(pb2), bins)
    np.testing.assert_array_equal(np.asarray(ghi2).view(np.int32), expected)
