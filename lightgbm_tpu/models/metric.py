"""Evaluation metrics (vectorized JAX).

TPU-native re-implementation of the reference metric matrix
(src/metric/metric.cpp:19-120 factory; regression_metric.hpp,
binary_metric.hpp, multiclass_metric.hpp, rank_metric.hpp,
xentropy_metric.hpp): each metric is a jit-friendly reduction over device
arrays; ranking metrics reuse the padded query buckets of the rank objectives.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..dataset import Metadata
from ..utils import log

K_EPSILON = 1e-15

_RANK_MEAN_WARNED = False


class Metric:
    name = "metric"
    is_max_better = False

    def __init__(self, config: Config):
        self.config = config

    def init(self, metadata: Metadata) -> None:
        self.num_data = metadata.num_data
        self.label = jnp.asarray(metadata.label, dtype=jnp.float32)
        self.weight = (jnp.asarray(metadata.weight, dtype=jnp.float32)
                       if metadata.weight is not None else None)
        self.sum_weight = (float(np.sum(metadata.weight))
                           if metadata.weight is not None else float(self.num_data))
        self.metadata = metadata

    def eval(self, score, objective) -> List[Tuple[str, float]]:
        """score: raw (unconverted) model output."""
        raise NotImplementedError

    def _wmean(self, values):
        """Weighted mean of a per-row loss; under multi-process training
        the numerator/denominator sums are reduced ACROSS ranks so every
        process reports the metric over the full rank-sharded dataset.
        (The reference evaluates on each machine's local shard only — no
        Network calls exist in src/metric/; the global reduction here is
        deliberate so distributed logs agree with single-process runs.)"""
        if self.weight is not None:
            vs = float(jnp.sum(values * self.weight))
            ws = self.sum_weight
        else:
            vs = float(jnp.sum(values))
            ws = float(int(np.prod(values.shape)))
        vs, ws = _global_pair(vs, ws)
        return vs / max(ws, K_EPSILON)

    def _rank_mean(self, value: float) -> float:
        """Cross-rank aggregation for non-decomposable metrics (AUC, NDCG
        family): the sum_weight-weighted mean of per-rank values.  Exact
        only when every rank sees the full data (feature-parallel); an
        explicit approximation for rank-sharded rows."""
        from ..parallel import network
        global _RANK_MEAN_WARNED
        if network.num_machines() > 1 and not _RANK_MEAN_WARNED:
            # surface the approximation once so early-stopping users know
            # (cross-rank score pairs are never compared; the reference
            # reports per-machine metrics instead — src/metric/ has no
            # Network calls)
            _RANK_MEAN_WARNED = True
            log.warning(
                "non-decomposable metric aggregated as a weighted mean "
                "of per-rank values under data-parallel row sharding — "
                "an approximation of the true global metric")
        vs, ws = _global_pair(value * self.sum_weight, self.sum_weight)
        return vs / max(ws, K_EPSILON)


def _global_pair(vsum: float, wsum: float) -> Tuple[float, float]:
    from ..parallel import network
    if network.num_machines() <= 1:
        return vsum, wsum
    out = network.global_sum([vsum, wsum])
    return float(out[0]), float(out[1])


def _global_queries(totals: "np.ndarray", num_queries: int) -> float:
    """Sum per-rank DCG/AP totals (in place) and query counts across the
    process group so ranking metrics cover the full sharded dataset."""
    from ..parallel import network
    if network.num_machines() <= 1:
        return float(num_queries)
    out = network.global_sum(list(totals) + [float(num_queries)])
    totals[:] = out[:-1]
    return float(out[-1])


def _convert(score, objective):
    if objective is not None:
        return objective.convert_output(score)
    return score


# ---------------------------------------------------------------------------
# Regression metrics (reference: src/metric/regression_metric.hpp)
# ---------------------------------------------------------------------------
class _PointwiseMetric(Metric):
    def point_loss(self, pred, label):
        raise NotImplementedError

    def transform(self, value: float) -> float:
        return value

    def eval(self, score, objective):
        pred = _convert(score, objective)
        loss = self.point_loss(pred, self.label)
        return [(self.name, self.transform(float(self._wmean(loss))))]


class L2Metric(_PointwiseMetric):
    name = "l2"

    def point_loss(self, pred, label):
        return (pred - label) ** 2


class RMSEMetric(L2Metric):
    name = "rmse"

    def transform(self, value):
        return math.sqrt(value)


class L1Metric(_PointwiseMetric):
    name = "l1"

    def point_loss(self, pred, label):
        return jnp.abs(pred - label)


class QuantileMetric(_PointwiseMetric):
    name = "quantile"

    def point_loss(self, pred, label):
        alpha = float(self.config.alpha)
        delta = label - pred
        return jnp.where(delta >= 0, alpha * delta, (alpha - 1.0) * delta)


class HuberMetric(_PointwiseMetric):
    name = "huber"

    def point_loss(self, pred, label):
        alpha = float(self.config.alpha)
        diff = pred - label
        return jnp.where(jnp.abs(diff) <= alpha, 0.5 * diff * diff,
                         alpha * (jnp.abs(diff) - 0.5 * alpha))


class FairMetric(_PointwiseMetric):
    name = "fair"

    def point_loss(self, pred, label):
        c = float(self.config.fair_c)
        x = jnp.abs(pred - label)
        return c * x - c * c * jnp.log1p(x / c)


class PoissonMetric(_PointwiseMetric):
    name = "poisson"

    def point_loss(self, pred, label):
        eps = 1e-10
        return pred - label * jnp.log(jnp.maximum(pred, eps))


class MAPEMetric(_PointwiseMetric):
    name = "mape"

    def point_loss(self, pred, label):
        return jnp.abs((label - pred) / jnp.maximum(1.0, jnp.abs(label)))


class GammaMetric(_PointwiseMetric):
    name = "gamma"

    def point_loss(self, pred, label):
        psi = 1.0
        theta = -1.0 / jnp.maximum(pred, 1e-10)
        a = psi
        b = -jnp.log(-theta)
        c = 1.0 / psi * jnp.log(label / psi) - jnp.log(label) - 0  # lgamma(1/psi)=0
        return -((label * theta - b) / a + c)


class GammaDevianceMetric(_PointwiseMetric):
    name = "gamma_deviance"

    def point_loss(self, pred, label):
        epsilon = 1e-9
        tmp = label / jnp.maximum(pred, epsilon)
        return tmp - jnp.log(tmp) - 1.0

    def transform(self, value):
        return value * 2.0


class TweedieMetric(_PointwiseMetric):
    name = "tweedie"

    def point_loss(self, pred, label):
        rho = float(self.config.tweedie_variance_power)
        eps = 1e-10
        p = jnp.maximum(pred, eps)
        a = label * jnp.exp((1.0 - rho) * jnp.log(p)) / (1.0 - rho)
        b = jnp.exp((2.0 - rho) * jnp.log(p)) / (2.0 - rho)
        return -a + b


# ---------------------------------------------------------------------------
# Binary metrics (reference: src/metric/binary_metric.hpp)
# ---------------------------------------------------------------------------
class BinaryLoglossMetric(_PointwiseMetric):
    name = "binary_logloss"

    def point_loss(self, pred, label):
        p = jnp.clip(pred, K_EPSILON, 1.0 - K_EPSILON)
        return -(label * jnp.log(p) + (1.0 - label) * jnp.log(1.0 - p))


class BinaryErrorMetric(_PointwiseMetric):
    name = "binary_error"

    def point_loss(self, pred, label):
        pred_label = (pred > 0.5).astype(jnp.float32)
        return (pred_label != label).astype(jnp.float32)


def _weighted_auc(score, label, weight):
    """Tie-aware weighted AUC via sorted cumulative sums
    (reference: src/metric/binary_metric.hpp AUCMetric::Eval)."""
    order = jnp.argsort(-score, stable=True)
    s = score[order]
    y = label[order]
    w = weight[order] if weight is not None else jnp.ones_like(s)
    wp = w * (y > 0)
    wn = w * (y <= 0)
    tp = jnp.cumsum(wp)
    fp = jnp.cumsum(wn)
    n = s.shape[0]
    is_end = jnp.concatenate([s[1:] != s[:-1], jnp.array([True])])
    # previous boundary's (tp, fp) per position: "last seen" exclusive scan
    def combine(a, b):
        av, af, avalid = a
        bv, bf, bvalid = b
        return (jnp.where(bvalid, bv, av), jnp.where(bvalid, bf, af),
                avalid | bvalid)
    tagged = (jnp.where(is_end, tp, 0.0), jnp.where(is_end, fp, 0.0), is_end)
    inc = jax.lax.associative_scan(combine, tagged)
    prev_tp = jnp.concatenate([jnp.zeros(1), inc[0][:-1]])
    prev_fp = jnp.concatenate([jnp.zeros(1), inc[1][:-1]])
    area = jnp.sum(jnp.where(is_end, (fp - prev_fp) * (tp + prev_tp) * 0.5, 0.0))
    total_p = tp[-1]
    total_n = fp[-1]
    return jnp.where((total_p > 0) & (total_n > 0),
                     area / (total_p * total_n), 1.0)


class AUCMetric(Metric):
    name = "auc"
    is_max_better = True

    def eval(self, score, objective):
        from ..parallel import network
        if network.num_machines() > 1 and bool(
                getattr(self.config, "distributed_exact_auc", False)):
            # EXACT global AUC under data-parallel row sharding: gather
            # every rank's (score, label, weight) rows once and run the
            # tie-aware sorted-cumsum evaluation over the full dataset.
            # The sort makes rank concatenation order irrelevant, so
            # this equals the single-process value to fp roundoff.
            # (The warned per-rank weighted mean stays the default:
            # the gather is O(total rows) host traffic per eval.)
            # gather the ORIGINAL f64 metadata arrays, not the f32
            # device copies init() keeps — and keep the whole gather +
            # evaluation under x64, else the allgather and the sorted
            # cumsums silently truncate to f32 (collapsing distinct
            # scores into ties) and the exactness claim is void
            meta = self.metadata
            with jax.enable_x64(True):
                s = network.global_concat(
                    np.asarray(score, dtype=np.float64))
                y = network.global_concat(np.asarray(meta.label,
                                                     dtype=np.float64))
                w_local = (np.asarray(meta.weight, dtype=np.float64)
                           if meta.weight is not None
                           else np.ones(len(np.asarray(meta.label)),
                                        dtype=np.float64))
                w = network.global_concat(w_local)
                return [(self.name, float(_weighted_auc(
                    jnp.asarray(s), jnp.asarray(y), jnp.asarray(w))))]
        return [(self.name, self._rank_mean(float(_weighted_auc(
            jnp.asarray(score), self.label, self.weight))))]


class AveragePrecisionMetric(Metric):
    name = "average_precision"
    is_max_better = True

    def eval(self, score, objective):
        order = jnp.argsort(-jnp.asarray(score), stable=True)
        y = self.label[order]
        w = self.weight[order] if self.weight is not None else jnp.ones_like(y)
        tp = jnp.cumsum(w * (y > 0))
        total = jnp.cumsum(w)
        precision = tp / jnp.maximum(total, K_EPSILON)
        pos_w = w * (y > 0)
        ap = jnp.sum(precision * pos_w) / jnp.maximum(jnp.sum(pos_w), K_EPSILON)
        return [(self.name, self._rank_mean(float(ap)))]


# ---------------------------------------------------------------------------
# Multiclass metrics (reference: src/metric/multiclass_metric.hpp)
# ---------------------------------------------------------------------------
class MultiLoglossMetric(Metric):
    name = "multi_logloss"

    def eval(self, score, objective):
        p = _convert(score, objective)  # (N, K) softmax
        lbl = self.label.astype(jnp.int32)
        p_true = jnp.take_along_axis(p, lbl[:, None], axis=1)[:, 0]
        loss = -jnp.log(jnp.maximum(p_true, K_EPSILON))
        return [(self.name, float(self._wmean(loss)))]


class MultiErrorMetric(Metric):
    name = "multi_error"

    def eval(self, score, objective):
        k = int(self.config.multi_error_top_k)
        lbl = self.label.astype(jnp.int32)
        true_score = jnp.take_along_axis(score, lbl[:, None], axis=1)[:, 0]
        # error if the true class' score is not within the top k
        num_better = jnp.sum(score > true_score[:, None], axis=1)
        err = (num_better >= k).astype(jnp.float32)
        return [(self.name, float(self._wmean(err)))]


class AucMuMetric(Metric):
    """AUC-mu for multiclass (reference: src/metric/multiclass_metric.hpp
    AucMuMetric:183, following Kleiman & Page 2019): average over class
    pairs (i, j) of the AUC of the projection onto the partition-weight
    difference vector, with optional `auc_mu_weights` (K*K, row-major,
    zero diagonal)."""
    name = "auc_mu"
    is_max_better = True

    def init(self, metadata):
        super().init(metadata)
        K = int(self.config.num_class)
        self.K = K
        spec = str(self.config.auc_mu_weights or "").strip()
        if spec:
            vals = [float(v) for v in spec.replace(" ", "").split(",") if v]
            if len(vals) != K * K:
                from ..utils import log as _log
                _log.fatal("auc_mu_weights must have %d elements, found %d",
                           K * K, len(vals))
            W = np.asarray(vals, dtype=np.float64).reshape(K, K)
            np.fill_diagonal(W, 0.0)
        else:
            W = 1.0 - np.eye(K)
        self.W = W

    def eval(self, score, objective):
        score = np.asarray(score, dtype=np.float64)   # (N, K) raw
        lbl = np.asarray(self.label).astype(np.int64)
        w = np.asarray(self.weight) if self.weight is not None else None
        K = self.K
        total = 0.0
        for i in range(K):
            ii = np.nonzero(lbl == i)[0]
            if len(ii) == 0:
                continue
            for j in range(i + 1, K):
                jj = np.nonzero(lbl == j)[0]
                if len(jj) == 0:
                    continue
                v = self.W[i] - self.W[j]                   # (K,)
                t1 = v[i] - v[j]
                idx = np.concatenate([ii, jj])
                dist = t1 * (score[idx] @ v)
                is_i = lbl[idx] == i
                wi = w[idx] if w is not None else np.ones(len(idx))
                # rank with ties counted half (reference: the sequential
                # num_j/num_current_j scan, multiclass_metric.hpp:282-323)
                order = np.lexsort((~is_i, dist))   # ties: class j first
                d_s = dist[order]
                i_s = is_i[order]
                w_s = wi[order]
                wj = np.where(~i_s, w_s, 0.0)
                cum_j = np.concatenate([[0.0], np.cumsum(wj)])[:-1]
                # per tied-group j-weight for the 0.5 correction
                grp = np.concatenate([[True], np.abs(np.diff(d_s)) > 1e-15])
                gid = np.cumsum(grp) - 1
                grp_j = np.zeros(gid[-1] + 1)
                np.add.at(grp_j, gid, wj)
                grp_start_cum = cum_j[np.nonzero(grp)[0]]
                s_ij = np.sum(np.where(
                    i_s, w_s * (grp_start_cum[gid] + 0.5 * grp_j[gid]), 0.0))
                den_i = np.sum(wi[:len(ii)]) if w is not None else len(ii)
                den_j = np.sum(w[jj]) if w is not None else len(jj)
                total += (s_ij / den_i) / den_j
        ans = (2.0 * total / K) / (K - 1)
        return [(self.name, self._rank_mean(float(ans)))]


# ---------------------------------------------------------------------------
# Ranking metrics (reference: src/metric/rank_metric.hpp, dcg_calculator.cpp)
# ---------------------------------------------------------------------------
class NDCGMetric(Metric):
    name = "ndcg"
    is_max_better = True

    def init(self, metadata: Metadata) -> None:
        super().init(metadata)
        if metadata.query_boundaries is None:
            log.fatal("The NDCG metric requires query information")
        self.eval_at = list(self.config.eval_at_list) or [1, 2, 3, 4, 5]
        if self.config.label_gain:
            gains = np.asarray([float(x) for x in str(self.config.label_gain).split(",")])
        else:
            gains = (2.0 ** np.arange(32)) - 1.0
        qb = np.asarray(metadata.query_boundaries)
        sizes = np.diff(qb)
        lbl = np.asarray(metadata.label).astype(np.int32)
        self.query_weights = None
        # bucket queries by padded size (shared pattern with LambdarankNDCG)
        buckets: Dict[int, List[int]] = {}
        for q, sz in enumerate(sizes):
            p = 1
            while p < sz:
                p <<= 1
            buckets.setdefault(max(p, 2), []).append(q)
        self.buckets = []
        gain_of = gains[lbl]
        for p, qs in sorted(buckets.items()):
            doc_idx = np.full((len(qs), p), -1, dtype=np.int32)
            idcg = np.zeros((len(qs), len(self.eval_at)), dtype=np.float64)
            for row, q in enumerate(qs):
                n = sizes[q]
                doc_idx[row, :n] = np.arange(qb[q], qb[q + 1])
                g_sorted = np.sort(gain_of[qb[q]:qb[q + 1]])[::-1]
                disc = 1.0 / np.log2(np.arange(2, n + 2))
                for ki, k in enumerate(self.eval_at):
                    kk = min(k, n)
                    idcg[row, ki] = np.sum(g_sorted[:kk] * disc[:kk])
            self.buckets.append({
                "P": p,
                "doc_idx": jnp.asarray(doc_idx),
                "idcg": jnp.asarray(idcg.astype(np.float32)),
            })
        self.gains_dev = jnp.asarray(gain_of.astype(np.float32))
        self.num_queries = len(sizes)

    def eval(self, score, objective):
        score = jnp.asarray(score)
        # per-bucket sums stay ON DEVICE inside the loop and sync once
        # at the end: a float() per (bucket, k) serializes one blocking
        # device round-trip per size bucket per eval round (jaxlint
        # JL001); cross-bucket accumulation runs in f64 on host exactly
        # as before
        bucket_sums = []
        for b in self.buckets:
            P = b["P"]
            doc_idx = b["doc_idx"]
            valid = doc_idx >= 0
            idx = jnp.maximum(doc_idx, 0)
            s = jnp.where(valid, score[idx], -jnp.inf)
            g = jnp.where(valid, self.gains_dev[idx], 0.0)
            order = jnp.argsort(-s, axis=1, stable=True)
            g_sorted = jnp.take_along_axis(g, order, axis=1)
            disc = 1.0 / jnp.log2(2.0 + jnp.arange(P, dtype=jnp.float32))
            per_k = []
            for ki, k in enumerate(self.eval_at):
                kk = min(k, P)
                dcg = jnp.sum(g_sorted[:, :kk] * disc[:kk], axis=1)
                idcg = b["idcg"][:, ki]
                ndcg = jnp.where(idcg > 0, dcg / jnp.maximum(idcg, K_EPSILON), 1.0)
                per_k.append(jnp.sum(ndcg))
            bucket_sums.append(jnp.stack(per_k))
        totals = np.sum(np.asarray(jax.device_get(bucket_sums),
                                   dtype=np.float64), axis=0) \
            if bucket_sums else np.zeros(len(self.eval_at))
        nq = _global_queries(totals, self.num_queries)
        return [(f"ndcg@{k}", totals[ki] / nq)
                for ki, k in enumerate(self.eval_at)]


class MapMetric(Metric):
    name = "map"
    is_max_better = True

    def init(self, metadata: Metadata) -> None:
        super().init(metadata)
        if metadata.query_boundaries is None:
            log.fatal("The MAP metric requires query information")
        self.eval_at = list(self.config.eval_at_list) or [1, 2, 3, 4, 5]
        qb = np.asarray(metadata.query_boundaries)
        sizes = np.diff(qb)
        buckets: Dict[int, List[int]] = {}
        for q, sz in enumerate(sizes):
            p = 1
            while p < sz:
                p <<= 1
            buckets.setdefault(max(p, 2), []).append(q)
        self.buckets = []
        for p, qs in sorted(buckets.items()):
            doc_idx = np.full((len(qs), p), -1, dtype=np.int32)
            for row, q in enumerate(qs):
                n = sizes[q]
                doc_idx[row, :n] = np.arange(qb[q], qb[q + 1])
            self.buckets.append({"P": p, "doc_idx": jnp.asarray(doc_idx)})
        self.num_queries = len(sizes)

    def eval(self, score, objective):
        score = jnp.asarray(score)
        # same one-sync-per-eval batching as NDCGMetric.eval (jaxlint
        # JL001): device sums per bucket, host f64 cross-bucket total
        bucket_sums = []
        for b in self.buckets:
            P = b["P"]
            doc_idx = b["doc_idx"]
            valid = doc_idx >= 0
            idx = jnp.maximum(doc_idx, 0)
            s = jnp.where(valid, score[idx], -jnp.inf)
            y = jnp.where(valid, self.label[idx] > 0, False)
            order = jnp.argsort(-s, axis=1, stable=True)
            y_sorted = jnp.take_along_axis(y, order, axis=1).astype(jnp.float32)
            cum_rel = jnp.cumsum(y_sorted, axis=1)
            pos = jnp.arange(1, P + 1, dtype=jnp.float32)
            prec = cum_rel / pos
            per_k = []
            for ki, k in enumerate(self.eval_at):
                kk = min(k, P)
                ap_num = jnp.sum(prec[:, :kk] * y_sorted[:, :kk], axis=1)
                denom = jnp.maximum(jnp.minimum(cum_rel[:, -1], float(kk)), 1.0)
                ap = ap_num / denom
                per_k.append(jnp.sum(ap))
            bucket_sums.append(jnp.stack(per_k))
        totals = np.sum(np.asarray(jax.device_get(bucket_sums),
                                   dtype=np.float64), axis=0) \
            if bucket_sums else np.zeros(len(self.eval_at))
        nq = _global_queries(totals, self.num_queries)
        return [(f"map@{k}", totals[ki] / nq)
                for ki, k in enumerate(self.eval_at)]


# ---------------------------------------------------------------------------
# Cross-entropy metrics (reference: src/metric/xentropy_metric.hpp)
# ---------------------------------------------------------------------------
class CrossEntropyMetric(_PointwiseMetric):
    name = "xentropy"

    def point_loss(self, pred, label):
        p = jnp.clip(pred, K_EPSILON, 1.0 - K_EPSILON)
        return -(label * jnp.log(p) + (1.0 - label) * jnp.log(1.0 - p))


class CrossEntropyLambdaMetric(Metric):
    name = "xentlambda"

    def eval(self, score, objective):
        # hhat = log1p(exp(score)); loss vs label under lambda parameterization
        hhat = jnp.log1p(jnp.exp(jnp.asarray(score)))
        y = self.label
        loss = hhat - y * jnp.log(jnp.maximum(1.0 - jnp.exp(-hhat), K_EPSILON)) - hhat
        # xentlambda loss: yl*log(z) terms; use KL-style formulation
        z = 1.0 - jnp.exp(-hhat)
        loss = -(y * jnp.log(jnp.maximum(z, K_EPSILON)) +
                 (1.0 - y) * jnp.log(jnp.maximum(1.0 - z, K_EPSILON)))
        return [(self.name, float(self._wmean(loss)))]


class KLDivMetric(Metric):
    name = "kullback_leibler"

    def eval(self, score, objective):
        p = jnp.clip(_convert(score, objective), K_EPSILON, 1.0 - K_EPSILON)
        y = jnp.clip(self.label, 0.0, 1.0)
        ce = -(y * jnp.log(p) + (1.0 - y) * jnp.log(1.0 - p))
        ent = jnp.where((y > 0) & (y < 1),
                        -(y * jnp.log(y) + (1.0 - y) * jnp.log(1.0 - y)), 0.0)
        return [(self.name, float(self._wmean(ce - ent)))]


_METRICS = {
    "l2": L2Metric, "mse": L2Metric, "rmse": RMSEMetric, "l1": L1Metric,
    "mae": L1Metric, "quantile": QuantileMetric, "huber": HuberMetric,
    "fair": FairMetric, "poisson": PoissonMetric, "mape": MAPEMetric,
    "gamma": GammaMetric, "gamma_deviance": GammaDevianceMetric,
    "tweedie": TweedieMetric,
    "binary_logloss": BinaryLoglossMetric, "binary_error": BinaryErrorMetric,
    "auc": AUCMetric, "average_precision": AveragePrecisionMetric,
    "multi_logloss": MultiLoglossMetric, "multi_error": MultiErrorMetric,
    "auc_mu": AucMuMetric,
    "ndcg": NDCGMetric, "map": MapMetric,
    "xentropy": CrossEntropyMetric, "xentlambda": CrossEntropyLambdaMetric,
    "kullback_leibler": KLDivMetric,
}

_DEFAULT_METRIC_FOR_OBJECTIVE = {
    "regression": "l2", "regression_l1": "l1", "huber": "huber", "fair": "fair",
    "poisson": "poisson", "quantile": "quantile", "mape": "mape",
    "gamma": "gamma", "tweedie": "tweedie",
    "binary": "binary_logloss",
    "multiclass": "multi_logloss", "multiclassova": "multi_logloss",
    "cross_entropy": "xentropy", "cross_entropy_lambda": "xentlambda",
    "lambdarank": "ndcg", "rank_xendcg": "ndcg",
}


def create_metrics(config: Config, for_objective: Optional[str] = None) -> List[Metric]:
    """reference: Metric::CreateMetric (src/metric/metric.cpp:19)."""
    names = list(config.metric_list)
    if not names and for_objective:
        default = _DEFAULT_METRIC_FOR_OBJECTIVE.get(for_objective)
        if default:
            names = [default]
    out = []
    for name in names:
        if name in ("", "custom", "none"):
            continue
        cls = _METRICS.get(name)
        if cls is None:
            log.warning("Unknown metric %s, ignoring", name)
            continue
        out.append(cls(config))
    return out
