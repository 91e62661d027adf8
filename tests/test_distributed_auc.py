"""Exact-global distributed AUC (PR-3, round-6 verdict ask #5).

``distributed_exact_auc=true`` gathers (score, label, weight) rows
across ranks and evaluates the tie-aware AUC over the full dataset —
exact under data-parallel row sharding, where the default per-rank
weighted mean (metric.py _rank_mean) is an explicit approximation.

The 8-rank group is emulated over the suite's 8 virtual devices by
sharding one dataset 8 ways and faking the network facade's
num_machines/global_concat with the full shard set, mirroring how
rank-sharded metrics see their local rows."""

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.dataset import Metadata
from lightgbm_tpu.models import metric as metric_mod
from lightgbm_tpu.models.metric import AUCMetric, _weighted_auc
from lightgbm_tpu.parallel import network
from lightgbm_tpu.parallel.distributed import rank_shard_indices

N_RANKS = 8


def _make(rng, n=4003, weighted=True):
    score = rng.normal(size=n)
    label = (rng.rand(n) < 1 / (1 + np.exp(-score
                                           + 0.5 * rng.normal(size=n)))
             ).astype(np.float64)
    # duplicate scores exercise the tie-handling arm
    score[:n // 10] = np.round(score[:n // 10], 1)
    weight = rng.uniform(0.1, 3.0, size=n) if weighted else None
    if weight is not None:
        # Metadata stores weights as f32 (reference label_t); the
        # exactness claim is vs single-device eval of the SAME stored
        # data, so quantize the fixture identically
        weight = weight.astype(np.float32).astype(np.float64)
    return score, label, weight


def _fake_network(monkeypatch, shards):
    """Patch the facade: 8 machines; global_concat returns the full
    concatenation by matching the caller's local shard."""
    monkeypatch.setattr(network, "num_machines", lambda: N_RANKS)

    def fake_concat(local):
        local = np.asarray(local)
        for quantity in shards.values():
            for piece in quantity:
                if piece.shape == local.shape and np.array_equal(
                        piece, local, equal_nan=True):
                    return np.concatenate(quantity, axis=0)
        raise AssertionError("global_concat got an unknown shard")

    monkeypatch.setattr(network, "global_concat", fake_concat)
    # the default path's weighted mean uses global_sum over pairs
    monkeypatch.setattr(
        network, "global_sum",
        lambda vals: np.asarray(vals, dtype=np.float64) * N_RANKS)


@pytest.mark.parametrize("weighted", [False, True])
def test_exact_auc_equals_single_device(rng, monkeypatch, weighted):
    import jax
    import jax.numpy as jnp
    score, label, weight = _make(rng, weighted=weighted)
    # the f64 single-device reference — the metric's exact path also
    # evaluates under x64 (f32 cumsums would void the 1e-12 claim)
    with jax.enable_x64(True):
        exact_single = float(_weighted_auc(
            jnp.asarray(score), jnp.asarray(label),
            jnp.asarray(weight) if weight is not None else None))

    idx = [rank_shard_indices(len(score), r, N_RANKS)
           for r in range(N_RANKS)]
    shards = {
        "score": [score[i] for i in idx],
        "label": [label[i] for i in idx],
        "weight": [(weight[i] if weight is not None
                    else np.ones(len(i))) for i in idx],
    }
    _fake_network(monkeypatch, shards)
    cfg = Config({"objective": "binary", "metric": "auc",
                  "distributed_exact_auc": True})
    per_rank = []
    for r in range(N_RANKS):
        m = AUCMetric(cfg)
        meta = Metadata(len(idx[r]))
        meta.set_label(label[idx[r]])
        if weight is not None:
            meta.set_weight(weight[idx[r]])
        m.init(meta)
        (_, val), = m.eval(score[idx[r]], None)
        per_rank.append(val)
    # every rank reports the SAME value, equal to single-device exact
    assert max(per_rank) - min(per_rank) < 1e-15
    assert abs(per_rank[0] - exact_single) < 1e-12


def test_default_stays_warned_weighted_mean(rng, monkeypatch):
    """Without the option the approximation (with its one-time warning)
    is unchanged — per-rank AUC weighted by sum_weight."""
    score, label, _ = _make(rng, n=1600, weighted=False)
    idx = [rank_shard_indices(len(score), r, N_RANKS)
           for r in range(N_RANKS)]
    shards = {"score": [score[i] for i in idx],
              "label": [label[i] for i in idx],
              "weight": [np.ones(len(i)) for i in idx]}
    _fake_network(monkeypatch, shards)
    monkeypatch.setattr(metric_mod, "_RANK_MEAN_WARNED", False)
    cfg = Config({"objective": "binary", "metric": "auc"})
    m = AUCMetric(cfg)
    meta = Metadata(len(idx[0]))
    meta.set_label(label[idx[0]])
    m.init(meta)
    import jax.numpy as jnp
    (_, val), = m.eval(score[idx[0]], None)
    local = float(_weighted_auc(jnp.asarray(score[idx[0]]),
                                jnp.asarray(label[idx[0]]), None))
    # the fake global_sum scales num and den alike -> rank-0 mean
    # equals its local AUC here; the point is the exact path was NOT
    # taken and the approximation warning fired
    assert abs(val - local) < 1e-12
    assert metric_mod._RANK_MEAN_WARNED


def test_global_concat_single_process_identity(rng):
    x = rng.normal(size=(17, 2))
    np.testing.assert_array_equal(network.global_concat(x), x)
