"""End-to-end coverage for the split mega-kernel path (tpu_megakernel):
the Pallas program (run through the interpreter off-TPU) must build
BIT-IDENTICAL trees to its XLA oracle formulation, the oracle itself
must agree numerically with the default subtraction path, and every
unsupported route must fall back cleanly at learner init.

The mega path's histogram chunk grid is the parent cover (not the
children's own ranges), so mega trees are bit-identical to the mega XLA
oracle but only NUMERICALLY equivalent to the subtraction-path trees —
the assertions below encode exactly that contract.
"""

import os

import numpy as np
import pytest

import lightgbm_tpu as lgb


def _data(seed=5, n=1200, f=6):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.5 * np.sin(X[:, 1] * 2)
         + 0.4 * rng.randn(n) > 0).astype(np.float64)
    return X, y


BASE = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
        "min_data_in_leaf": 20, "tpu_row_chunk": 256}


def _train(X, y, nbr=2, **kw):
    return lgb.train({**BASE, **kw}, lgb.Dataset(X, label=y),
                     num_boost_round=nbr)


def _trees(bst):
    """Model text minus the [param] dump (params legitimately differ
    between the arms; the TREES must not)."""
    return [ln for ln in bst.model_to_string().splitlines()
            if not ln.startswith("[")]


def test_mega_xla_matches_default_path_numerically():
    """The oracle formulation is the same math as the subtraction path
    up to f32 summation grouping: predictions agree to float noise."""
    X, y = _data()
    b0 = _train(X, y, nbr=5)
    b1 = _train(X, y, nbr=5, tpu_megakernel="xla")
    assert b0._gbdt.learner.plan.mega == "off"       # CPU auto: off
    assert b1._gbdt.learner.plan.mega == "xla"
    d = float(np.abs(b0.predict(X[:400]) - b1.predict(X[:400])).max())
    assert d < 1e-4, d


@pytest.mark.parametrize("extra", [
    {},
    # 13 s each (interpreter-mode training): tier-1 window offenders
    # per test_durations.json; the plain case stays as the fast
    # in-window representative of the interpret-mega lane, the
    # sampling/quantized variants keep full coverage in the slow lane
    pytest.param({"bagging_fraction": 0.6, "bagging_freq": 1},
                 marks=pytest.mark.slow),
    pytest.param({"data_sample_strategy": "goss"},
                 marks=pytest.mark.slow),
    pytest.param({"use_quantized_grad": True},
                 marks=pytest.mark.slow),
])
def test_mega_interpret_bitexact_vs_oracle(extra):
    """The acceptance contract: mega-kernel (interpret mode on CPU)
    trees bit-identical to the XLA oracle at L=31, including
    bagging/GOSS masks and quantized integer gradient carriers.

    BOTH arms run with tpu_kernel_interpret=True so partition and split
    search use the identical implementations and the comparison isolates
    exactly the mega-kernel's fused histogram semantics.  (On CPU the
    Pallas pair-search and the XLA vmapped search differ by last-ulp
    gemm rounding — an implementation-lane difference the TPU MXU does
    not have — so mixing search implementations across arms is not a
    valid bit-exactness comparison.)"""
    X, y = _data(seed=11, n=900)
    kw = {"num_leaves": 31, "tpu_kernel_interpret": True, **extra}
    bx = _train(X, y, tpu_megakernel="xla", **kw)
    bp = _train(X, y, tpu_megakernel="pallas", **kw)
    lr = bp._gbdt.learner
    assert lr.plan.mega == "pallas" and lr.plan.partition == "pallas"
    assert bx._gbdt.learner.plan.mega == "xla"
    assert _trees(bx) == _trees(bp)
    d = np.abs(bx.predict(X[:300]) - bp.predict(X[:300])).max()
    assert float(d) == 0.0


@pytest.mark.slow
def test_mega_interpret_bitexact_L255():
    """The L=255 geometry of the acceptance contract (slow: interpret
    mode pays per-split interpreter cost across a deep leaf-wise tree)."""
    X, y = _data(seed=17, n=3000, f=8)
    kw = {"num_leaves": 255, "min_data_in_leaf": 10,
          "tpu_kernel_interpret": True}
    bx = _train(X, y, nbr=1, tpu_megakernel="xla", **kw)
    bp = _train(X, y, nbr=1, tpu_megakernel="pallas", **kw)
    assert bp._gbdt.learner.plan.mega == "pallas"
    assert _trees(bx) == _trees(bp)


def test_nonmega_interpret_kernels_structural():
    """The pre-existing kernel stack (partition + pair-search +
    flat-hist RMW) run through the interpreter must reproduce the pure
    XLA path's tree STRUCTURE and agree numerically — the off-TPU lane
    for the kernels the TPU selfcheck exercises on device.  (Bitwise
    equality holds on the TPU MXU but not across CPU gemm shapes: the
    pair-search kernel and the XLA search stack their prefix matmuls
    differently, which rounds differently under Eigen.)"""
    X, y = _data(seed=19)
    bx = _train(X, y, tpu_megakernel="off")
    bi = _train(X, y, tpu_megakernel="off", tpu_kernel_interpret=True)
    lr = bi._gbdt.learner
    assert (lr.plan.partition == "pallas" and lr.plan.search == "pallas"
            and lr.plan.hist_state == "flat")
    struct = ("split_feature=", "threshold=", "left_child=",
              "right_child=", "num_leaves=", "decision_type=")
    sx = [ln for ln in _trees(bx) if ln.startswith(struct)]
    si = [ln for ln in _trees(bi) if ln.startswith(struct)]
    assert sx == si
    d = float(np.abs(bx.predict(X[:300]) - bi.predict(X[:300])).max())
    assert d < 1e-5, d


def test_mega_fallback_routes_clean_at_init():
    """Unsupported routes must fall back to the current split path at
    learner init (no mid-train surprises): categorical features, u16
    bins (max_bin > 256), cegb-lazy payloads, forced splits."""
    X, y = _data(n=800)
    # categorical
    Xc = X.copy()
    Xc[:, 3] = np.random.RandomState(0).randint(0, 5, len(Xc))
    bc = lgb.train({**BASE, "tpu_megakernel": "xla",
                    "categorical_feature": [3]},
                   lgb.Dataset(Xc, label=y, categorical_feature=[3]),
                   num_boost_round=2)
    assert bc._gbdt.learner.plan.mega == "off"
    # u16 bins
    b16 = _train(X, y, tpu_megakernel="xla", max_bin=300)
    assert b16._gbdt.learner.plan.mega == "off"
    assert b16._gbdt.learner.B > 256
    # cegb-lazy
    lazy = ",".join(["0.1"] * X.shape[1])
    bl = _train(X, y, tpu_megakernel="xla",
                cegb_penalty_feature_lazy=lazy)
    assert bl._gbdt.learner.plan.mega == "off"
    # forced splits
    import json
    import tempfile
    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as fh:
        json.dump({"feature": 0, "threshold": 0.0}, fh)
        fname = fh.name
    try:
        bf = _train(X, y, tpu_megakernel="xla",
                    forcedsplits_filename=fname)
    finally:
        os.remove(fname)
    assert bf._gbdt.learner.plan.mega == "off"
    # every fallback still trains a usable model
    for b in (bc, b16, bl, bf):
        assert np.isfinite(b.predict(X[:50])).all()


def test_mega_off_and_unknown_modes():
    X, y = _data(n=600)
    boff = _train(X, y, tpu_megakernel="off")
    assert boff._gbdt.learner.plan.mega == "off"
    bauto = _train(X, y)            # auto on CPU without interpret: off
    assert bauto._gbdt.learner.plan.mega == "off"
