"""Leaf-size-adaptive chunk policy (ops/chunkpolicy.py).

The tentpole contract: ``tpu_chunk_policy=adaptive`` trains trees
BIT-IDENTICAL to ``fixed`` (the base-grid oracle) while the per-leaf
histogram/partition passes band small leaves onto smaller menu widths.
Covered here:

* the bit-identity matrix across bagging / GOSS / quantized /
  categorical / multiclass / cegb-lazy / frontier-K / mega-xla /
  eager-path configurations;
* the compiled-variant registry pin: <= menu-size traced variants per
  pass over a full training run, and warm updates add none;
* ``tpu_row_chunk=auto`` / ``tpu_chunk_policy=auto`` ignoring a planted
  same-fingerprint chunk-sweep trajectory entry;
* the ``train.chunk.waste`` telemetry gauges;
* the PR-10 ``rec["hist"]`` dead-export deletion.
"""

import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.dataset import BinnedDataset
from lightgbm_tpu.models.learner import SerialTreeLearner
from lightgbm_tpu.ops import chunkpolicy


def _data(seed=7, n=3000, f=8, cat=False):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    if cat:
        X[:, -1] = rng.randint(0, 12, size=n)
    y = (X[:, 0] + 0.5 * np.sin(X[:, 1] * 2)
         + 0.4 * rng.randn(n) > 0).astype(np.float64)
    return X, y


BASE = {"objective": "binary", "num_leaves": 31, "verbosity": -1,
        "min_data_in_leaf": 5, "metric": ""}


def _trees(bst):
    """Model text minus the [param] dump (tpu_chunk_policy legitimately
    differs between the arms; the TREES must not)."""
    return [ln for ln in bst.model_to_string().splitlines()
            if not ln.startswith("[")]


def _train(X, y, nbr=3, cat=False, **kw):
    p = {**BASE, **kw}
    if cat:
        p["categorical_feature"] = [X.shape[1] - 1]
    return lgb.train(p, lgb.Dataset(X, label=y), num_boost_round=nbr)


# ---------------------------------------------------------------------------
# policy unit behavior
# ---------------------------------------------------------------------------
def test_menu_derivation_and_bands():
    pol = chunkpolicy.ChunkPolicy(4096, adaptive=True)
    assert pol.sizes == (4096, 1024, 256, 64)
    assert pol.hist_sizes == (4096, 256, 64)
    assert chunkpolicy.ChunkPolicy(256, adaptive=True).sizes == (256, 64)
    assert len(chunkpolicy.ChunkPolicy(1 << 15, adaptive=True).sizes) <= 4
    # band_of: smallest covering width; multi-chunk leaves stay base
    assert pol.band_of(5000) == 0
    assert pol.band_of(2000) == 0     # (1024, 4096]: base single chunk
    assert pol.band_of(1000) == 1
    assert pol.band_of(200) == 2
    assert pol.band_of(64) == 3
    assert pol.padded_rows(200) == 256
    assert pol.padded_rows(5000) == 8192
    fixed = chunkpolicy.ChunkPolicy(4096, adaptive=False)
    assert fixed.band_of(10) == 0
    assert fixed.padded_rows(10) == 4096


def test_traced_band_matches_host_band():
    import jax.numpy as jnp
    pol = chunkpolicy.ChunkPolicy(4096, adaptive=True)
    for cnt in (0, 1, 64, 65, 256, 257, 1024, 1025, 4096, 9000):
        got = int(pol.band(jnp.int32(cnt), pol.sizes))
        want = pol.band_of(max(cnt, 1))
        if cnt:
            assert got == want, cnt
        trips = [int(t) for t in pol.small_trips(jnp.int32(cnt),
                                                 pol.sizes)]
        assert sum(trips) == (1 if 0 < cnt <= 1024 else 0), cnt
        cover = int(pol.base_cover(jnp.int32(cnt), pol.sizes))
        assert cover == (0 if cnt <= 1024 else -(-cnt // 4096)), cnt


def test_parse_row_chunk():
    assert chunkpolicy.parse_row_chunk("auto") is None
    assert chunkpolicy.parse_row_chunk(512) == 512
    assert chunkpolicy.parse_row_chunk("512") == 512
    with pytest.raises(ValueError):
        chunkpolicy.parse_row_chunk("never")
    with pytest.raises(ValueError):
        chunkpolicy.parse_row_chunk(-4)


def test_waste_stats():
    pol = chunkpolicy.ChunkPolicy(4096, adaptive=True)
    s = chunkpolicy.waste_stats([10, 100, 1000, 5000], pol)
    assert s["live_rows"] == 6110
    # partition bands process 64 + 256 + 1024 + 8192 rows; the
    # histogram bands (capped at 256) 64 + 256 + 4096 + 8192 — the
    # 1000-row leaf's full base-width hist chunk must be counted
    assert s["padded_rows"] == 9536 + 12608
    assert s["waste"] == pytest.approx(1 - 2 * 6110 / (9536 + 12608))
    assert s["fixed_waste"] == pytest.approx(1 - 6110 / 20480)
    assert 0.0 < s["waste"] < s["fixed_waste"] < 1.0
    assert s["band_64.leaves"] == 1
    assert s["band_256.occupancy"] == pytest.approx(100 / 256)


# ---------------------------------------------------------------------------
# bit-identity matrix vs the fixed-grid oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("extra,cat", [
    ({}, False),                                              # plain
    ({"bagging_fraction": 0.6, "bagging_freq": 1}, False),    # bagging
    ({"data_sample_strategy": "goss"}, False),                # GOSS
    ({"use_quantized_grad": True}, False),                    # quantized
    ({}, True),                                               # categorical
    ({"objective": "multiclass", "num_class": 3}, False),     # multiclass
    ({"tpu_frontier_k": 3}, False),                           # frontier
    ({"tpu_megakernel": "xla"}, False),                       # mega oracle
    # bonus lanes beyond the required matrix ride the slow tier
    # (tier-1 window; the fast lanes above are the representatives)
    pytest.param({"cegb_tradeoff": 0.5,
                  "cegb_penalty_feature_lazy": ",".join(["0.1"] * 8)},
                 False, marks=pytest.mark.slow),
    pytest.param({"tpu_fused_iteration": False}, False,
                 marks=pytest.mark.slow),                     # eager path
])
def test_chunk_bitidentity(extra, cat):
    X, y = _data(cat=cat)
    if extra.get("objective") == "multiclass":
        y = ((X[:, 0] > 0).astype(float) + (X[:, 1] > 0))
    bf = _train(X, y, cat=cat, tpu_chunk_policy="fixed", **extra)
    ba = _train(X, y, cat=cat, tpu_chunk_policy="adaptive", **extra)
    assert ba._gbdt.learner._chunk_policy.adaptive
    assert len(ba._gbdt.learner._chunk_policy.sizes) >= 2
    assert _trees(bf) == _trees(ba)
    d = np.abs(np.asarray(bf.predict(X[:200]))
               - np.asarray(ba.predict(X[:200]))).max()
    assert float(d) == 0.0


def test_chunk_bitidentity_deep_small_leaves():
    """num_leaves larger than rows/min_data forces the small-leaf
    regime every band is exercised in (the padding-waste case the
    policy targets)."""
    X, y = _data(n=4000)
    bf = _train(X, y, num_leaves=255, min_data_in_leaf=3,
                tpu_chunk_policy="fixed")
    ba = _train(X, y, num_leaves=255, min_data_in_leaf=3,
                tpu_chunk_policy="adaptive")
    assert _trees(bf) == _trees(ba)


@pytest.mark.slow
def test_chunk_interpret_megakernel_fallback():
    """Kernel (Pallas) paths keep their proven base grid: under the
    interpreted mega-kernel the policy must resolve to fixed and trees
    must match a fixed-policy run exactly."""
    X, y = _data(n=600, f=6)
    kw = {"tpu_kernel_interpret": True, "tpu_megakernel": "pallas",
          "tpu_row_chunk": 256}
    bf = _train(X, y, nbr=1, tpu_chunk_policy="fixed", **kw)
    ba = _train(X, y, nbr=1, tpu_chunk_policy="adaptive", **kw)
    assert ba._gbdt.learner.plan.mega == "pallas"
    assert not ba._gbdt.learner._chunk_policy.adaptive
    assert _trees(bf) == _trees(ba)


# ---------------------------------------------------------------------------
# compiled-variant pin (the (pass, chunk-size) compile-count contract)
# ---------------------------------------------------------------------------
def test_variant_counts_bounded_by_menu():
    X, y = _data()
    chunkpolicy.reset_variant_log()
    bst = _train(X, y, nbr=3, tpu_chunk_policy="adaptive")
    pol = bst._gbdt.learner._chunk_policy
    log = chunkpolicy.variant_log()
    per_pass = {}
    for (pass_name, width), n in log.items():
        per_pass.setdefault(pass_name, set()).add(width)
    assert set(per_pass) >= {"hist", "partition"}
    assert per_pass["hist"] == set(pol.hist_sizes)
    assert per_pass["partition"] == set(pol.sizes)
    for pass_name, widths in per_pass.items():
        assert len(widths) <= len(pol.sizes), (pass_name, widths)
    # warm updates reuse the compiled program: no new traced variants
    snap = chunkpolicy.variant_log()
    bst.update()
    bst.update()
    assert chunkpolicy.variant_log() == snap


# ---------------------------------------------------------------------------
# auto modes follow from the parameters and the data's shape alone: a
# benchmark history that names another winner changes nothing
# ---------------------------------------------------------------------------
def _plant_sweep(ds, metrics):
    """A same-host, same-shape ``chunk_sweep`` entry (what ``ab_bench
    --chunk`` appends) in the history file the session points at."""
    from lightgbm_tpu.obs import regress
    regress.append_entry(
        "chunk_sweep", metrics,
        fingerprint_doc=regress.fingerprint(
            config={}, rows=ds.num_data, features=ds.num_total_features),
        path=os.environ["BENCH_HISTORY_PATH"])


def test_row_chunk_auto_consults_history(tmp_path, monkeypatch):
    monkeypatch.setenv("BENCH_HISTORY_PATH", str(tmp_path / "hist.jsonl"))
    X, y = _data(n=3000)
    cfg = Config({**BASE, "tpu_row_chunk": "auto"})
    ds = BinnedDataset.from_matrix(X, cfg, label=y)
    _plant_sweep(ds, {"best_row_chunk": 512})
    # the constant, capped by the rows (3000 -> 4096)
    assert SerialTreeLearner(ds, cfg).row_chunk == 4096 \
        == chunkpolicy.DEFAULT_ROW_CHUNK
    Xs, ys = _data(n=700)
    small = BinnedDataset.from_matrix(Xs, cfg, label=ys)
    assert SerialTreeLearner(small, cfg).row_chunk == 1024


def test_chunk_policy_auto_consults_history(tmp_path, monkeypatch):
    monkeypatch.setenv("BENCH_HISTORY_PATH", str(tmp_path / "hist.jsonl"))
    X, y = _data(n=3000)
    cfg = Config(dict(BASE))
    ds = BinnedDataset.from_matrix(X, cfg, label=y)
    # a measured verdict that adaptive LOST on this host and shape
    _plant_sweep(ds, {"best_row_chunk": 4096, "adaptive_speedup": 0.8})
    # the small-leaf rule, (num_leaves-1) * base > num_data, decides:
    # 30 * 4096 > 3000 -> adaptive ...
    lr = SerialTreeLearner(ds, cfg)
    assert lr.plan.chunk_adaptive and lr._chunk_policy.adaptive
    # ... and 1 * 256 <= 3000 -> the fixed grid
    cfg2 = Config({**BASE, "num_leaves": 2, "tpu_row_chunk": 256})
    lr2 = SerialTreeLearner(ds, cfg2)
    assert not lr2.plan.chunk_adaptive
    assert "auto" in lr2.plan.why["chunk_adaptive"]
    # explicit settings win over the rule
    cfg_forced = Config({**BASE, "num_leaves": 2, "tpu_row_chunk": 256,
                         "tpu_chunk_policy": "adaptive"})
    assert SerialTreeLearner(ds, cfg_forced)._chunk_policy.adaptive


# ---------------------------------------------------------------------------
# telemetry: padding-waste gauges
# ---------------------------------------------------------------------------
def test_chunk_waste_gauges():
    from lightgbm_tpu import obs
    X, y = _data()
    sess = obs.get()
    prev = sess.mode
    try:
        sess.set_mode("counters")
        bst = _train(X, y, nbr=2, tpu_chunk_policy="adaptive")
        bst._gbdt._flush_pending()
        rep = bst.telemetry_report()
    finally:
        sess.set_mode(prev)
    gauges = rep["gauges"]
    assert 0.0 <= gauges["train.chunk.waste"] < 1.0
    # the adaptive bands must beat the fixed grid's padding on this
    # small-leaf-heavy shape
    assert gauges["train.chunk.waste"] < gauges["train.chunk.fixed_waste"]
    assert any(k.startswith("train.chunk.band_") for k in gauges)


# ---------------------------------------------------------------------------
# rec["hist"] dead export (PR-10 note) is gone
# ---------------------------------------------------------------------------
def test_record_drops_hist_state():
    X, y = _data(n=800, f=5)
    cfg = Config(dict(BASE))
    ds = BinnedDataset.from_matrix(X, cfg, label=y)
    lr = SerialTreeLearner(ds, cfg)
    grad = (0.5 - y).astype(np.float32)
    hess = np.full(len(y), 0.25, np.float32)
    rec = lr.build_tree(grad, hess)
    assert "hist" not in rec
    assert "leaf_cnt" in rec and "indices" in rec
