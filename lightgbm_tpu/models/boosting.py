"""Boosting engines: GBDT, DART, RF, with bagging and GOSS sampling.

TPU-native re-design of the reference boosting layer (src/boosting/gbdt.cpp,
dart.hpp, rf.hpp, bagging.hpp, goss.hpp): the per-iteration loop
(gbdt.cpp TrainOneIter:338-441) orchestrates device-resident state — scores,
gradients, the binned dataset, and the tree learner's partition arrays all
stay in HBM; the host only sequences iterations and pulls finished trees.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..dataset import BinnedDataset
from ..obs import memory as obs_memory
from ..obs import scopes
from ..obs import telemetry as obs
from ..ops.predict import predict_leaf_binned, predict_leaf_binned_t
from ..robustness import faultinject
from ..robustness.guard import NonFiniteGuard
from ..utils import log
from ..utils.log import LightGBMError
from .learner import SerialTreeLearner
from .metric import Metric, create_metrics
from .objective import ObjectiveFunction
from .serving import ServingEngine
from .tree import Tree, tree_from_device_record

K_EPSILON = 1e-15
# linear-leaf refit: relative ridge added to the normal-equation
# diagonal so near-singular systems degrade toward the constant leaf
# instead of emitting large coefficients (_fit_linear_leaves)
_LINEAR_RIDGE_EPS = 1e-10


import os as _os

DEBUG_CHECKS = _os.environ.get("LIGHTGBM_TPU_DEBUG", "") == "1"


def debug_validate_record(host_record, num_nodes: int, num_data: int,
                          row0: int) -> None:
    """LIGHTGBM_TPU_DEBUG=1 invariant checks on a materialized tree
    record — the analog of the reference's DEBUG CheckSplit /
    CheckAllDataInLeaf validation (serial_tree_learner.h:174-176):

      * child pointers reference valid nodes/leaves and every leaf is
        reached exactly once;
      * the physical leaf ranges partition [row0, row0 + num_data);
      * leaf values and gains are finite.
    Raises AssertionError with a diagnostic on violation."""
    L = num_nodes + 1
    if num_nodes == 0:
        return
    left = np.asarray(host_record["node_left"])[:num_nodes]
    right = np.asarray(host_record["node_right"])[:num_nodes]
    seen_leaves = []
    for arr in (left, right):
        for v in arr:
            if v < 0:
                seen_leaves.append(~v)
            else:
                assert 0 <= v < num_nodes, f"child node {v} out of range"
    assert sorted(seen_leaves) == list(range(L)), \
        f"leaves reached {sorted(seen_leaves)} != 0..{L - 1}"
    lv = np.asarray(host_record["leaf_value"])[:L]
    assert np.isfinite(lv).all(), "non-finite leaf value"
    starts = np.asarray(host_record["leaf_start"])[:L]
    cnts = np.asarray(host_record["leaf_cnt"])[:L]
    order = np.argsort(starts)
    s, c = starts[order], cnts[order]
    assert int(c.sum()) == num_data, \
        f"leaf counts sum {int(c.sum())} != {num_data}"
    assert s[0] == row0, f"first leaf starts at {s[0]} != {row0}"
    assert (s[1:] == s[:-1] + c[:-1]).all(), \
        "leaf ranges are not disjoint-contiguous"


@functools.partial(jax.jit, static_argnames=("l1", "l2", "mds"))
def _quant_renew_device(idx, grad, hess, starts, cnts, old_values,
                        l1, l2, mds):
    """Per-leaf true-gradient sums via prefix-sum differencing over the
    partitioned row order (pad rows sit outside every leaf range, so
    their clipped-gather values never enter a difference)."""
    from ..ops.split import leaf_output
    nmax = grad.shape[0] - 1
    gp = jnp.take(grad, jnp.minimum(idx, nmax))
    hp = jnp.take(hess, jnp.minimum(idx, nmax))
    cg = jnp.concatenate([jnp.zeros((1,), jnp.float32), jnp.cumsum(gp)])
    ch = jnp.concatenate([jnp.zeros((1,), jnp.float32), jnp.cumsum(hp)])
    sum_g = jnp.take(cg, starts + cnts) - jnp.take(cg, starts)
    sum_h = jnp.take(ch, starts + cnts) - jnp.take(ch, starts)
    new = leaf_output(sum_g, sum_h + 2e-15, l1, l2, mds)
    return jnp.where(cnts > 0, new, old_values)


@functools.partial(jax.jit, static_argnums=(1,))
@scopes.phase("scores_read")
def lgbm_scores_read(ghi, num_data):
    """Scatter the physically-ordered score row back to original row
    order (rowid rides as bitcast row 2; pad rows carry the sentinel
    ``num_data`` and drop)."""
    rowid = jax.lax.bitcast_convert_type(ghi[2], jnp.int32)
    return jnp.zeros((num_data,), jnp.float32).at[rowid].set(
        ghi[3], mode="drop")


def _scores_from_phys_multiproc(ghi, local_num_data, sb):
    """Rank-sharded fused state -> this process's LOCAL scores, on the
    host: rowids are GLOBAL mesh ids (device d owns [d*local_n, ...)),
    so under multi-process each rank folds only its addressable shards
    back to its local row order.  (A single SPMD scatter cannot produce
    a per-rank local array from global ids.)"""
    out = np.zeros((local_num_data,), np.float32)
    if sb.mode == "feature":
        # rows replicated: any shard carries every row with ids 0..N
        blk = np.asarray(ghi.addressable_shards[0].data)
        rowid = blk[2].view(np.int32)
        valid = (rowid >= 0) & (rowid < local_num_data)
        out[rowid[valid]] = blk[3][valid]
        return jnp.asarray(out)
    proc_off = jax.process_index() * sb.local_ndev * sb.local_n
    for shard in ghi.addressable_shards:
        blk = np.asarray(shard.data)
        lid = blk[2].view(np.int32) - proc_off
        valid = (lid >= 0) & (lid < local_num_data)
        out[lid[valid]] = blk[3][valid]
    return jnp.asarray(out)


@functools.partial(jax.jit, static_argnums=(1, 2))
@scopes.phase("scores_read")
def lgbm_scores_read_mc(ghi, num_data, num_class):
    """Multiclass variant: rows 3..3+K-1 are the per-class score rows."""
    rowid = jax.lax.bitcast_convert_type(ghi[2], jnp.int32)
    return jnp.zeros((num_data, num_class), jnp.float32).at[rowid].set(
        ghi[3:3 + num_class].T, mode="drop")


def _renew_leaves_percentile(rec, resid, pweight, sel, alpha: float,
                             Npad: int):
    """Per-leaf (weighted) percentile of residuals over the PARTITIONED
    row order — the device analog of the L1-family RenewTreeOutput
    (regression_objective.hpp:18-80 PercentileFun/WeightedPercentileFun
    applied through SerialTreeLearner::RenewTreeOutput).

    Leaves are contiguous physical row ranges, so one global sort keyed
    by ``(leaf_id << 23) | global_residual_rank`` groups every leaf's
    IN-BAG rows contiguously in residual order (out-of-bag and pad rows
    carry rank +inf and fall to each group's tail); the percentile then
    reads one or two gathered elements per leaf.  Requires
    N_pad <= 2^23 and <= 256 leaf slots so the key fits a non-negative
    int32 (the caller gates on both).

    resid/sel/pweight are (Npad,) physical-order arrays; sel False marks
    out-of-bag and pad rows.  Returns the renewed leaf-value vector
    (old values where a leaf has no in-bag rows)."""
    leaf_start = rec["leaf_start"]
    leaf_cnt = rec["leaf_cnt"]
    old = rec["leaf_value"]
    Lslots = old.shape[0]
    iota = jax.lax.iota(jnp.int32, Npad)

    # leaf id per physical position: count starts <= p, then map the
    # ordinal through the starts sorted by position.  Pad rows attach to
    # a neighboring leaf's group but always sort beyond its in-bag count.
    starts_valid = jnp.where(leaf_cnt > 0, leaf_start, Npad + 1)
    order_starts = jnp.argsort(starts_valid).astype(jnp.int32)
    marks = jnp.zeros((Npad,), jnp.int32).at[starts_valid].add(
        1, mode="drop")
    o = jnp.cumsum(marks)
    leaf_at = jnp.take(order_starts, jnp.clip(o - 1, 0, Lslots - 1))

    sort_val = jnp.where(sel, resid, jnp.inf)
    ord1 = jnp.argsort(sort_val).astype(jnp.int32)
    rank = jnp.zeros((Npad,), jnp.int32).at[ord1].set(iota)
    key = (leaf_at << 23) | rank
    ord2 = jnp.argsort(key).astype(jnp.int32)
    r_s = jnp.take(resid, ord2)

    # group offsets: keys ascend with leaf id, so groups are laid out in
    # id order and offsets are an exclusive prefix over group sizes
    sizes = jnp.zeros((Lslots,), jnp.int32).at[leaf_at].add(1)
    off = jnp.cumsum(sizes) - sizes

    selc = jnp.concatenate([jnp.zeros((1,), jnp.float32),
                            jnp.cumsum(sel.astype(jnp.float32))])
    nb = (jnp.take(selc, leaf_start + leaf_cnt)
          - jnp.take(selc, leaf_start)).astype(jnp.int32)

    if pweight is None:
        fp = (nb - 1).astype(jnp.float32) * alpha
        lo = jnp.floor(fp).astype(jnp.int32)
        bias = fp - lo.astype(jnp.float32)
        i1 = off + jnp.clip(lo, 0, jnp.maximum(nb - 1, 0))
        i2 = off + jnp.clip(lo + 1, 0, jnp.maximum(nb - 1, 0))
        v1 = jnp.take(r_s, i1)
        v2 = jnp.take(r_s, i2)
        v = v1 + (v2 - v1) * bias
        v = jnp.where(nb == 1, jnp.take(r_s, off), v)
    else:
        # reference WeightedPercentileFun (regression_objective.hpp:50-88):
        # pos = upper_bound(weighted cdf, alpha * total), interpolate
        # only when the next point's weight >= 1 and pos is interior.
        # Matches _weighted_percentile_host exactly (stable sort order).
        wsel = pweight * sel.astype(jnp.float32)
        w_s = jnp.take(wsel, ord2)
        wc = jnp.concatenate([jnp.zeros((1,), jnp.float32),
                              jnp.cumsum(wsel)])
        sw = jnp.take(wc, leaf_start + leaf_cnt) - jnp.take(wc, leaf_start)
        wc_s = jnp.cumsum(w_s)
        base = jnp.where(off > 0, jnp.take(wc_s, jnp.maximum(off - 1, 0)),
                         0.0)
        leaf_s = jnp.take(leaf_at, ord2)
        local_j = iota - jnp.take(off, leaf_s)
        cum = wc_s - jnp.take(base, leaf_s)        # inclusive per-leaf cdf
        thr_s = alpha * jnp.take(sw, leaf_s)
        cond = (cum > thr_s) & (local_j < jnp.take(nb, leaf_s))
        big = jnp.int32(Npad + 1)
        first = jnp.full((Lslots,), big, jnp.int32).at[leaf_s].min(
            jnp.where(cond, iota, big))
        last = off + jnp.maximum(nb - 1, 0)
        pos = jnp.where(first < big, first, last)
        pos = jnp.clip(pos, off, last)
        lpos = pos - off
        v2 = jnp.take(r_s, pos)
        v1 = jnp.take(r_s, jnp.maximum(pos - 1, off))
        w_next = jnp.take(w_s, jnp.minimum(pos + 1, last))
        cdf_pos = jnp.take(wc_s, pos) - base
        cdf_next = cdf_pos + w_next
        thr = alpha * sw
        interp = (thr - cdf_pos) / jnp.maximum(cdf_next - cdf_pos,
                                               jnp.float32(1e-30)) * (v2 - v1) + v1
        use_i = (lpos > 0) & (lpos < nb - 1) & (w_next >= 1.0)
        v = jnp.where(use_i, interp, v2)
    return jnp.where(nb > 0, v, old)


def _small_record(rec, scalars=("s", "made", "feat_used")):
    """The per-tree record without its row-length buffers: the node and
    leaf arrays and the named scalars (``made``: splits the frontier body
    executed, committed or pruned; absent from the K=1 body's record)."""
    return {k: v for k, v in rec.items()
            if k.startswith(("node_", "leaf_")) or k in scalars}


def _phys_leaf_delta(rec, Npad: int):
    """Per-row score delta from the physical leaf ranges: leaves are
    disjoint contiguous row windows, so scatter +/- leaf values at the
    range boundaries and prefix-sum — the +v/-v pairs of each closed
    range cancel exactly before the next range opens.  The flat prefix
    sum runs as a 2-D lane cumsum + small row-carry pass (a 1-D cumsum
    over N_pad lowers lane-serial on TPU, ~1.1 ms/Mrow measured)."""
    d = jnp.zeros((Npad,), jnp.float32)
    d = d.at[rec["leaf_start"]].add(rec["leaf_value"], mode="drop")
    d = d.at[rec["leaf_start"] + rec["leaf_cnt"]].add(
        -rec["leaf_value"], mode="drop")
    d2 = d.reshape(Npad // 256, 256)
    within = jnp.cumsum(d2, axis=1)
    carry = jnp.cumsum(within[:, -1]) - within[:, -1]   # (rows,)
    return (within + carry[:, None]).reshape(Npad)


def _rows_to_host(holder, num_data: int) -> None:
    """Hand every per-row device array of an objective or a metric back
    to the host.  The fused step over a device mesh carries the payload
    rows sharded with the bins, so the copies their ``init`` made (label,
    weights, their signed product: each sized by every shard's rows, all
    on the first device) serve no step; an eager gradient pass or a
    metric's evaluation still reads them, as host arrays."""
    for name, value in list(vars(holder).items()):
        if (isinstance(value, jax.Array) and value.ndim >= 1
                and value.shape[0] == num_data):
            setattr(holder, name, np.asarray(value))


def _learner_memory_arrays(lr):
    """Telemetry memory provider: the learner's resident device
    buffers (master binned partition buffer + helper tables)."""
    return [v for v in vars(lr).values()
            if getattr(v, "nbytes", None) is not None]


def _gbdt_memory_arrays(g):
    """Telemetry memory provider: training-side score/physical state
    plus the per-tree device arrays.  The binned residency is fully
    visible here: the live ``_phys`` carrier or the retired
    ``_phys_carrier`` (bins + rowid row) IS the training copy of the
    binned matrix once the fused path adopts the master buffer."""
    out = [] if g._scores_arr is None else [g._scores_arr]
    phys = getattr(g, "_phys", None)
    if phys is not None:
        out.extend(phys)
    carrier = getattr(g, "_phys_carrier", None)
    if carrier is not None:
        out.extend(carrier)
    for dt in g.device_trees:
        if dt is not None:
            out.append(dt["nodes"])
            out.append(dt["leaf_value"])
    return out


def _unpermute_bins(part_bins, rowid_bits, N, C, Npad):
    """Invert the partition permutation of a physical bins carrier back
    to the pristine identity layout: column ``C + i`` of the output
    holds original row ``i``'s bins, all pad columns are zero — exactly
    the ingest buffer the carrier adopted.  Exact (integer gather), so
    re-initializing from the result is bit-identical to initializing
    from the never-donated master buffer."""
    iota = jax.lax.iota(jnp.int32, Npad)
    rowid = jnp.where((iota >= C) & (iota < C + N), iota - C, N)
    old = jax.lax.bitcast_convert_type(rowid_bits, jnp.int32)
    # pos[i] = physical column currently holding original row i
    pos = jnp.zeros((N,), jnp.int32).at[old].set(iota, mode="drop")
    src = jnp.take(pos, jnp.minimum(rowid, N - 1))
    bins = jnp.take(part_bins, src, axis=1)
    return jnp.where((rowid < N)[None, :], bins, 0).astype(part_bins.dtype)


class GBDT:
    """Gradient Boosting Decision Tree engine (reference: src/boosting/gbdt.cpp)."""

    def __init__(self, config: Config, train_data: Optional[BinnedDataset],
                 objective: Optional[ObjectiveFunction]):
        self.config = config
        self.train_data = train_data
        self.objective = objective
        # non-finite guard rails (robustness/guard.py); active policy
        # keeps training on the eager path (fused gating below)
        self._nf_guard = NonFiniteGuard.from_config(config)
        self.models: List[Tree] = []
        self.device_trees: List[Dict[str, Any]] = []  # node arrays + leaf values
        self._continued = False        # set by continue_from
        # bumped on every structural model change (append/pop/scale) so
        # derived caches (the serving engine's packed forests) can never
        # serve a stale model of the same length
        self._model_version = 0
        # device-resident serving engine: packed forests, bucketed
        # batches, compiled-predictor cache (models/serving.py)
        self.serving = ServingEngine(self)
        self.iter = 0
        self.shrinkage_rate = float(config.learning_rate)
        self.num_tree_per_iteration = (objective.num_model_per_iteration
                                       if objective else max(config.num_class, 1))
        self.num_class = max(config.num_class, 1)
        self.average_output = False
        self.init_scores = [0.0] * self.num_tree_per_iteration
        self.max_feature_idx = 0
        self.feature_names: List[str] = []
        self.label_idx = 0
        self.valid_sets: List[Tuple[BinnedDataset, List[Metric], jnp.ndarray]] = []
        self.valid_scores: List[jnp.ndarray] = []
        self.train_metrics: List[Metric] = []
        self.best_iter: Dict[str, int] = {}
        self.es_first_metric_only = bool(config.first_metric_only)
        # physical-order fused state: (part_bins, part_ghi) kept permuted
        # across consecutive fused iterations (see _setup_fused_phys)
        self._phys = None
        # retired carrier: (part_bins, rowid_bits) kept after the scores
        # materialize — under single-copy residency this pair IS the
        # binned training data (the master buffer was donated into it),
        # so it must survive every score read/write until a pristine
        # copy is rebuilt (_ensure_part0) or training resumes
        self._phys_carrier = None
        self._fused_phys = None
        self._init_phys_fn = None
        self._init_phys_adopt = None
        self._init_phys_perm = None
        self._scores_arr = None
        self._scores_read_sharded = None
        # programs whose executable obs/scopes.py already holds for this
        # booster (each is registered at its first call)
        self._scope_registered = set()
        # model & data health (obs/health.py): the training flight
        # recorder (None when health=off) and the reference data profile
        # persisted with the model; all host-side bookkeeping
        self.flight = None
        self.health_profile = None

        if train_data is not None:
            self._setup_training(train_data)

    # ------------------------------------------------------------------
    # Train scores.  In the physical fused mode the authoritative scores
    # live PERMUTED as a row of the partition payload; reading `.scores`
    # materializes them back to original row order (one scatter) and
    # drops the physical state, and any external write invalidates it —
    # the next fused iteration rebuilds the physical layout from scratch.
    @property
    def scores(self):
        if getattr(self, "_phys", None) is not None:
            pb, ghi = self._phys
            self._phys = None
            obs.counter("train.layout_drops")
            # the bins + rowid row stay resident as the retired carrier:
            # they are the ONLY binned copy (single-copy residency) and
            # the next fused init / traversal / recovery reads them
            self._phys_carrier = (pb, ghi[2])
            K = self.num_tree_per_iteration
            sb = self.sharded_builder
            with obs.span("train.scores_read"):
                if K > 1:
                    self._scores_arr = lgbm_scores_read_mc(
                        ghi, self.num_data, K)
                    self._register_once("train.scores_read",
                                        lgbm_scores_read_mc,
                                        ghi, self.num_data, K)
                elif sb is not None and sb.nproc > 1:
                    # folded on the host: no program to register
                    self._scores_arr = _scores_from_phys_multiproc(
                        ghi, self.num_data, sb)
                elif self._scores_read_sharded is not None:
                    # each shard folds its own rows: the result stays
                    # cut over the mesh
                    self._scores_arr = self._scores_read_sharded(ghi)
                    self._register_once("train.scores_read",
                                        self._scores_read_sharded, ghi)
                else:
                    self._scores_arr = lgbm_scores_read(ghi, self.num_data)
                    self._register_once("train.scores_read",
                                        lgbm_scores_read,
                                        ghi, self.num_data)
        return self._scores_arr

    def _register_once(self, program, jitted, *args):
        """After the first call of ``jitted`` by this booster, hand
        obs/scopes.py the executable that call made (scopes.register_call:
        nothing is traced or compiled a second time).  ``args`` are live
        arrays of the call's shapes: its outputs where it donated inputs."""
        key = (program, id(jitted))
        if key not in self._scope_registered:
            self._scope_registered.add(key)
            scopes.register_call(program, jitted, *args)

    @scores.setter
    def scores(self, v):
        if getattr(self, "_phys", None) is not None:
            # an external write drops the physical scores but must NOT
            # drop the bins: they may be the only binned copy left
            pb, ghi = self._phys
            self._phys = None
            obs.counter("train.layout_drops")
            self._phys_carrier = (pb, ghi[2])
        self._scores_arr = v

    # ------------------------------------------------------------------
    # Train-set leaf traversal over the live binned resident.  There is
    # no standing row-major train matrix anymore (single-copy binned
    # residency): leaf lookups read whichever resident is live — the
    # fused physical carrier (bins permuted, scattered back to original
    # order through the rowid row), the learner's pristine master
    # buffer, or as a last resort a TRANSIENT device copy of the host
    # matrix — and always return (N,) leaf ids in original row order.
    def _traverse_train(self, nodes):
        src = self._phys if self._phys is not None \
            else self._phys_carrier
        sb = self.sharded_builder
        if src is not None and (sb is None or sb.nproc == 1):
            pb, second = src
            rowid_bits = second[2] if second.ndim == 2 else second
            return self._traverse_phys_fn(nodes, pb, rowid_bits)
        p0 = getattr(self.learner, "_part0", None)
        if p0 is not None and not p0.is_deleted():
            return self._traverse_part0_fn(nodes, p0)
        if sb is not None and sb.nproc == 1:
            return self._traverse_sharded_fn(nodes, sb.binned_sharded)
        binned = self.train_data.binned
        if binned is None:
            binned = self.train_data.host_binned()
        return self._traverse_rows_fn(nodes, jnp.asarray(binned))

    def _recover_pristine_part0(self):
        """Rebuild the pristine (pb_rows, N_pad) master buffer from the
        live physical carrier (one exact unpermute gather).  Serves the
        ingest's recovery callback (pickle / save_binary / a second
        booster on the same dataset) and the eager-path crossing."""
        src = self._phys if self._phys is not None \
            else self._phys_carrier
        if src is None:
            raise LightGBMError(
                "binned master buffer was donated to the fused trainer "
                "and no physical carrier is live to recover it from")
        pb, second = src
        rowid_bits = second[2] if second.ndim == 2 else second
        return self._unpermute_fn(pb, rowid_bits)

    def _adopt_master_buffer(self) -> None:
        """Called right after the identity init forwards the learner's
        master buffer into the physical carrier: the fused step donates
        that buffer in place every iteration, so every OTHER reference
        must let go now (a later read would observe donated memory).
        The ingest keeps a recovery callback instead of the buffer."""
        lr = self.learner
        p0 = lr._part0
        lr._part0 = None
        ing = getattr(lr, "_ingest", None)
        if ing is None:
            return
        if (getattr(ing, "buffer", None) is p0
                or getattr(lr, "_part0_from_ingest", False)):
            # the flag also covers the sublane-padded case (_pb_rows >
            # G): part0 is then pad(buffer) — the recovered carrier's
            # first G rows ARE the master buffer, so the ingest's own
            # copy is redundant either way
            ing.release_buffer(self._recover_pristine_part0)

    def _ensure_part0(self) -> None:
        """The eager tree build reads the learner's pristine master
        buffer; if the fused carrier adopted it, rebuild it (and hand
        the ingest its buffer back) so eager and fused iterations can
        interleave.  Residency returns to ONE pristine copy and the
        next fused init restarts from the identity layout — the exact
        state a never-fused run would be in."""
        lr = self.learner
        if getattr(lr, "_part0", None) is not None:
            return
        if self.sharded_builder is not None:
            return               # its eager build reads the mesh's own bins
        if self._phys is None and self._phys_carrier is None:
            return
        _ = self.scores          # materialize pending fused scores first
        pb = self._recover_pristine_part0()
        self._phys_carrier = None
        lr._part0 = pb
        ing = getattr(lr, "_ingest", None)
        if (ing is not None and getattr(ing, "buffer", None) is None
                and pb.shape[1] == ing.n_pad and pb.shape[0] >= ing.G):
            # extra sublane-pad rows beyond G are zeros; every ingest
            # consumer slices [:G]
            ing.buffer = pb
            ing._recover = None

    # ------------------------------------------------------------------
    def _setup_training(self, train_data: BinnedDataset) -> None:
        cfg = self.config
        self.sharded_builder = None
        if cfg.tree_learner != "serial":
            import jax as _jax
            ndev = len(_jax.devices())
            if ndev > 1:
                from ..parallel.trainer import ShardedTreeBuilder
                self.sharded_builder = ShardedTreeBuilder(train_data, cfg)
                log.info("Using %s-parallel tree learner over %d devices",
                         cfg.tree_learner, ndev)
            elif _jax.default_backend() == "tpu":
                raise LightGBMError(
                    f"tree_learner={cfg.tree_learner} needs more than one "
                    "device but only one TPU chip is visible; use "
                    "tree_learner=serial on a one-chip host")
            else:
                log.warning("tree_learner=%s requested but only one device is "
                            "visible; training serially", cfg.tree_learner)
        # the sharded builder's learner is THE learner: a second, serial
        # one would put a master buffer sized by every shard's rows on
        # the first device
        self.learner = (self.sharded_builder.learner
                        if self.sharded_builder is not None
                        else SerialTreeLearner(train_data, cfg))
        self.num_data = train_data.num_data
        self.max_feature_idx = train_data.num_total_features - 1
        self.feature_names = list(train_data.feature_names)
        from ..obs import health as obs_health
        obs_health.configure_from_config(cfg)
        if obs_health.enabled():
            self.flight = obs_health.FlightRecorder.from_config(cfg)
            self.health_profile = train_data.reference_profile()
        if self.objective is not None:
            self.objective.init(train_data.metadata)
        self.train_metrics = create_metrics(
            cfg, self.objective.name if self.objective else None)
        for m in self.train_metrics:
            m.init(train_data.metadata)

        K = self.num_tree_per_iteration
        shape = (self.num_data,) if K == 1 else (self.num_data, K)
        self.scores = jnp.zeros(shape, dtype=jnp.float32)
        if train_data.metadata.init_score is not None:
            init = np.asarray(train_data.metadata.init_score, dtype=np.float32)
            if K > 1:
                init = init.reshape(K, self.num_data).T
            self.scores = jnp.asarray(init.reshape(shape))
            self.has_init_score = True
        else:
            self.has_init_score = False

        # boost from average (reference: gbdt.cpp:313-336)
        if (self.objective is not None and not self.has_init_score
                and cfg.boost_from_average):
            from ..parallel import network
            for k in range(K):
                s = self.objective.boost_from_score(k)
                # ObtainAutomaticInitialScore (gbdt.cpp:303-311): the
                # per-rank init scores agree by mean across processes
                # (objectives with internal sum-syncs are already equal,
                # the mean is then the identity)
                if network.num_machines() > 1:
                    s = network.global_sync_by_mean(s)
                if abs(s) > K_EPSILON:
                    self.init_scores[k] = s
                    if K == 1:
                        self.scores = self.scores + s
                    else:
                        self.scores = self.scores.at[:, k].add(s)
                    log.info("Start training from score %f", s)

        # quantized-gradient training state
        # (reference: gradient_discretizer.{hpp,cpp})
        self.use_quant = bool(cfg.use_quantized_grad)
        if self.use_quant:
            self.quant_rng = jax.random.PRNGKey(
                cfg.seed if cfg.seed is not None else 12345)

        # model-lifetime CEGB used-feature set (reference:
        # CostEfficientGradientBoosting::is_feature_used_in_split_)
        self._cegb_feat_used = None
        # model-lifetime cegb-lazy per-(row, feature) used bitset
        self._cegb_lazy_aux = None
        # lagged fused-iteration records awaiting host materialization
        self._pending_recs: List[Dict[str, Any]] = []
        # consecutive empty trees (stop detection across class trees)
        self._empty_run = 0

        # sampling state
        self.bag_rng = jax.random.PRNGKey(cfg.bagging_seed)
        self.feat_rng = jax.random.PRNGKey(cfg.feature_fraction_seed)
        self.goss = cfg.data_sample_strategy == "goss"
        # HBM attribution for telemetry (obs/memory.py): the learner's
        # master binned buffer and the training-side score state are
        # the two big per-booster residents besides the serving packs
        obs_memory.register("train.binned", self.learner,
                            _learner_memory_arrays)
        obs_memory.register("train.state", self, _gbdt_memory_arrays)
        # balanced (per-class) bagging engages whenever either class
        # fraction is below 1 (reference: bagging.hpp:88)
        self.balanced_bagging = (
            cfg.bagging_freq > 0
            and (cfg.pos_bagging_fraction < 1.0
                 or cfg.neg_bagging_fraction < 1.0)
            and train_data.metadata.label is not None)
        self.need_bagging = (not self.goss and cfg.bagging_freq > 0
                             and (cfg.bagging_fraction < 1.0
                                  or self.balanced_bagging))
        if cfg.bagging_by_query:
            log.warning("bagging_by_query is accepted for config "
                        "compatibility but is not implemented by the "
                        "reference this framework tracks; it is IGNORED")
        self._cached_bag = None
        # ---- train-set traversal programs (single-copy residency) ----
        # each reads a different live binned resident; the dispatcher
        # (_traverse_train) picks per call.  The phys variant traverses
        # the PERMUTED carrier and scatters leaf ids back to original
        # row order through the bitcast rowid row (sentinel ids >= N
        # drop out of the scatter).
        _G = self.learner.G
        _C = self.learner.row0
        _N = self.num_data

        @scopes.phase("scores_read")
        def _tr_phys(nodes, pb, rowid_bits):
            rowid = jax.lax.bitcast_convert_type(rowid_bits, jnp.int32)
            leaf = predict_leaf_binned_t(pb[:_G], nodes)
            return jnp.zeros((_N,), jnp.int32).at[rowid].set(
                leaf, mode="drop")

        self._traverse_phys_fn = jax.jit(_tr_phys)
        self._traverse_part0_fn = jax.jit(scopes.phase("scores_read")(
            lambda nodes, p0: predict_leaf_binned_t(
                p0[:_G, _C:_C + _N], nodes)))
        self._traverse_rows_fn = jax.jit(scopes.phase("scores_read")(
            lambda nodes, binned: predict_leaf_binned(binned, nodes)))
        self._unpermute_fn = jax.jit(scopes.phase("scores_read")(
            functools.partial(_unpermute_bins, N=_N, C=_C,
                              Npad=self.learner.N_pad)))
        sb = self.sharded_builder
        if sb is not None:
            _Np = self.learner.N_pad

            @scopes.phase("scores_read")
            def _tr_sharded(nodes, pb):
                # the mesh's pristine blocks, each [C pad][rows][pad]
                leaf = predict_leaf_binned_t(pb[:_G], nodes)
                if sb.mode == "feature":
                    return leaf[_C:_C + _N]
                return leaf.reshape(sb.ndev, _Np)[
                    :, _C:_C + sb.local_n].reshape(-1)[:_N]

            self._traverse_sharded_fn = jax.jit(_tr_sharded)

        # ---- fused training step ----
        # One jitted program per boosting iteration: gradients -> tree build
        # -> score update, with only two host round-trips (dispatch + small
        # record fetch).  Vital on TPU where per-dispatch latency dominates
        # the eager path (the TPU analog of the reference keeping the whole
        # iteration inside C++, gbdt.cpp:338-441).
        self._fused = None
        # GOSS and plain bagging fold into the fused physical program
        # (their masks are pure jnp); balanced/query bagging do not yet
        fused_on = bool(getattr(cfg, "tpu_fused_iteration", True))
        common_ok = (
            fused_on and self._nf_guard is None
            and self.sharded_builder is None and self.objective is not None
            and getattr(self.objective, "is_jit_safe", True)
            and not cfg.linear_tree
            and not cfg.cegb_penalty_feature_lazy)
        if common_ok and K == 1:
            self._setup_fused_step()
        elif (common_ok and K > 1 and not self.use_quant and not self.goss
              and not (self.need_bagging and self.balanced_bagging)
              and not self.objective.is_renew_tree_output
              and self._mc_fused_kind() is not None):
            # multiclass: all K class trees build inside ONE program per
            # iteration (gbdt.cpp:379's per-class Train loop, device-side)
            self._setup_fused_multiclass()
        elif (fused_on and self._nf_guard is None
              and self.sharded_builder is not None
              and self.objective is not None
              and getattr(self.objective, "is_jit_safe", True)
              and K == 1 and not cfg.linear_tree
              and not cfg.cegb_penalty_feature_lazy
              and not self.use_quant and not self.goss
              and not (self.need_bagging and self.balanced_bagging)
              and not self.objective.is_renew_tree_output):
            # distributed learners: the fused physical program runs
            # shard_map'd over the mesh — same per-shard state the
            # serial path keeps, with the collectives the sharded build
            # already contains
            self._setup_fused_sharded()
        if self._fused is None and train_data is not None:
            reasons = []
            if self._nf_guard is not None:
                reasons.append(f"nonfinite_policy={self._nf_guard.policy} "
                               "(the per-iteration guard verdict needs "
                               "the eager path)")
            if self.sharded_builder is not None:
                why = getattr(self, "_fused_sharded_reason",
                              "sampling/renewal combo not yet fused")
                reasons.append(f"tree_learner={cfg.tree_learner} ({why})")
            if K != 1:
                reasons.append(f"num_class={self.num_class} (payload rows "
                               "or sampling combo unsupported)")
            if cfg.linear_tree:
                reasons.append("linear_tree")
            if self.need_bagging and self.balanced_bagging:
                reasons.append("balanced bagging (needs a label-sign "
                               "payload row)")
            if cfg.cegb_penalty_feature_lazy:
                reasons.append("cegb_penalty_feature_lazy")
            if self.objective is not None \
                    and self.objective.is_renew_tree_output:
                reasons.append(f"objective={self.objective.name} "
                               "(renewal needs the physical path: GOSS/"
                               "quantized combo or size limits exceeded)")
            if self.objective is not None \
                    and not getattr(self.objective, "is_jit_safe", True):
                reasons.append(f"objective={self.objective.name} "
                               "(not jit-safe)")
            log.info("fused single-program iteration DISABLED (%s): each "
                     "iteration pays per-dispatch host latency",
                     ", ".join(reasons) or
                     "objective lacks gradients_from_payload")
        log.info("kernel plan: %s", " ".join(
            f"{k}={v}" for k, v in self.kernel_plan().items()))
        for k, v in self.learner.plan.why.items():
            log.info("  %s: %s", k, v)

    def kernel_plan(self) -> Dict[str, Any]:
        """What actually builds the trees: the tree-building learner's
        resolved kernels (learner.kernel_plan) plus whether the whole
        iteration runs as one fused program."""
        sb = self.sharded_builder
        plan = self.learner.kernel_plan()
        plan["fused"] = "on" if self._fused is not None else "off"
        plan["tree_learner"] = sb.mode if sb is not None else "serial"
        return plan

    def _setup_fused_step(self) -> None:
        lr_ = self.learner
        obj = self.objective
        shrink = self.shrinkage_rate
        N = self.num_data
        L = lr_.L
        Npad = lr_.N_pad

        # physical-order fast path: the objective's row-aligned gradient
        # inputs and the scores RIDE the partition payload, so the score
        # update is a boundary prefix sum + row add — no O(N) scatter
        # back to original order (5.5 ms/Mrow, the single largest
        # per-iteration row cost).  Requires the concrete objective class
        # to define gradients_from_payload (inheriting it would silently
        # pair a subclass's overridden gradients with the base formula).
        if obj.is_renew_tree_output and (
                self.use_quant or self.goss
                or Npad > (1 << 23) or lr_.L > 255):
            # leaf renewal fuses only through the physical path's packed
            # percentile sort ((leaf << 23) | rank int32 key), and the
            # GOSS in-bag set is not recoverable post-partition
            return
        if self.need_bagging and self.balanced_bagging:
            # balanced bagging reads the label sign per row inside the
            # program; only payloads carrying a sign row support it
            fields = obj.payload_fields or ()
            if not any(n in ("label", "signed_label_weight")
                       for n in fields if getattr(obj, n, None) is not None):
                return
        if (type(obj).__dict__.get("gradients_from_payload") is not None
                and obj.gradient_payload() is not None):
            names = [n for n in obj.payload_fields
                     if getattr(obj, n) is not None]
            if 4 + len(names) <= lr_._ghi_rows:
                self._setup_fused_phys(names)
                return
        if self.use_quant or self.goss or self.need_bagging \
                or obj.is_renew_tree_output:
            # these fold only into the physical path (discretizer,
            # renewal and sampling masks live inside that program)
            return

        def lgbm_fused_step(part_bins, scores, feature_mask, seed, feat_used):
            # trace-time-only host hook: one call == one XLA compile of
            # this program (obs retrace detector; zero HLO)
            obs.compile_event("train.fused_step")
            with scopes.scope("gradients"):
                grad, hess = obj.get_gradients(scores)
            rec = lr_._build_impl(part_bins, grad, hess, jnp.int32(N),
                                  feature_mask, seed, feat_used)
            # per-row score delta from the physical leaf ranges: leaves are
            # disjoint contiguous row windows, so scatter +/- leaf values at
            # the range boundaries and prefix-sum — the +v/-v pairs of each
            # closed range cancel exactly before the next range opens — then
            # ONE scatter maps physical rows back to original row order
            with scopes.scope("score_update"):
                d = jnp.zeros((Npad + 1,), jnp.float32)
                d = d.at[rec["leaf_start"]].add(rec["leaf_value"],
                                                mode="drop")
                d = d.at[rec["leaf_start"] + rec["leaf_cnt"]].add(
                    -rec["leaf_value"], mode="drop")
                delta_phys = jnp.cumsum(d)[:-1]
                delta = jnp.zeros((N,), jnp.float32).at[rec["indices"]].set(
                    delta_phys, mode="drop")
                new_scores = scores + delta * shrink
            small = _small_record(rec)
            small["leaf_delta"] = rec["leaf_value"] * shrink
            return new_scores, small

        self._fused = jax.jit(lgbm_fused_step, donate_argnums=(1,))

    def _setup_fused_phys(self, names) -> None:
        """Physical-order fused iteration (see _setup_fused_step).

        Payload row layout: 0 grad, 1 hess, 2 rowid-bits, 3 score,
        4.. the objective's ``names`` arrays, zero-padded to 8 rows.
        The TPU analog of the reference keeping gradients, scores and
        the data partition resident across an iteration
        (gbdt.cpp:338-441 + data_partition.hpp) — with the row order
        itself device-owned."""
        lr_ = self.learner
        obj = self.objective
        shrink = self.shrinkage_rate
        N = self.num_data
        Npad = lr_.N_pad
        C = lr_.row0
        # quantized renewal needs the TRUE gradients in POST-partition
        # order: they ride two extra payload rows through the partition
        q_renew_rows = 2 if (self.use_quant
                             and self.config.quant_train_renew_leaf) else 0
        tg_row = 4 + len(names)
        th_row = tg_row + 1
        lr_._ghi_live = 4 + len(names) + q_renew_rows
        payload_arrs = [jnp.asarray(getattr(obj, n), jnp.float32)
                        for n in names]

        def ghi0(scores):
            iota = jax.lax.iota(jnp.int32, Npad)
            rowid = jnp.where((iota >= C) & (iota < C + N), iota - C, N)
            rows = [jnp.zeros((Npad,), jnp.float32),
                    jnp.zeros((Npad,), jnp.float32),
                    jax.lax.bitcast_convert_type(rowid, jnp.float32),
                    jnp.pad(scores, (C, Npad - C - N))]
            rows += [jnp.pad(a, (C, Npad - C - N)) for a in payload_arrs]
            rows += [jnp.zeros((Npad,), jnp.float32)
                     for _ in range(lr_._ghi_rows - len(rows))]
            return jnp.stack(rows)

        @scopes.phase("layout_init")
        def lgbm_layout_init(part_bins, scores):
            # the bins pass through UNTOUCHED; with the bins argument
            # DONATED, XLA aliases the output onto the input buffer, so
            # the physical carrier ADOPTS the learner's master buffer
            # instead of copying it (single-copy residency) —
            # _adopt_master_buffer retires every other reference right
            # after.  The non-donating jit keeps the pre-adoption
            # semantics for lowering-only probes (jaxlint).
            return part_bins, ghi0(scores)

        @scopes.phase("layout_init")
        def lgbm_layout_resume(part_bins, rowid_bits, scores):
            # resume from a RETIRED carrier (scores were read between
            # iterations): unpermute the bins back to the identity
            # layout, so the rebuilt state — and every tree after it —
            # is bit-identical to an init from the pristine buffer
            bins = _unpermute_bins(part_bins, rowid_bits, N, C, Npad)
            return bins, ghi0(scores)

        self._init_phys = jax.jit(lgbm_layout_init)
        self._init_phys_adopt = jax.jit(lgbm_layout_init, donate_argnums=(0,))
        self._init_phys_perm = jax.jit(lgbm_layout_resume, donate_argnums=(0,))

        use_quant = self.use_quant
        cfg = self.config
        q_bins = float(cfg.num_grad_quant_bins)
        q_stoch = bool(cfg.stochastic_rounding)
        q_renew = bool(cfg.quant_train_renew_leaf)
        q_const_h = bool(obj.is_constant_hessian)
        q_key = jax.random.PRNGKey(cfg.seed if cfg.seed is not None
                                   else 12345)
        l1_, l2_, mds_ = (float(cfg.lambda_l1), float(cfg.lambda_l2),
                          float(cfg.max_delta_step))
        use_goss = self.goss
        use_bag = self.need_bagging and not self.balanced_bagging
        use_balanced = self.need_bagging and self.balanced_bagging
        bag_key = jax.random.PRNGKey(cfg.bagging_seed)
        bag_freq = max(int(cfg.bagging_freq), 1)
        bag_frac = float(cfg.bagging_fraction)
        pos_frac = float(cfg.pos_bagging_fraction)
        neg_frac = float(cfg.neg_bagging_fraction)
        sign_idx = None
        if use_balanced:
            sign_idx = names.index("label") if "label" in names \
                else names.index("signed_label_weight")
        g_top_k = max(int(N * cfg.top_rate), 1)
        g_other_k = max(int(N * cfg.other_rate), 1)
        # L1-family renewal state (the gate in _setup_fused_step already
        # excluded GOSS/quantized combos and oversize payloads)
        renew_alpha = (float(obj.renew_leaf_alpha())
                       if obj.is_renew_tree_output else None)
        label_idx = names.index("label") if "label" in names else None
        weight_idx = names.index("weight") if "weight" in names else None
        renew_w_fn = (obj.renew_weights_from_payload
                      if hasattr(type(obj), "renew_weights_from_payload")
                      else None)

        def lgbm_fused_step(part_bins, ghi, feature_mask, seed, feat_used):
            obs.compile_event("train.fused_step")   # trace-time only
            with scopes.scope("gradients"):
                rowid = jax.lax.bitcast_convert_type(ghi[2], jnp.int32)
                vf = (rowid != N).astype(jnp.float32)   # pad rows: grad/hess 0
                payload = {n: ghi[4 + i] for i, n in enumerate(names)}
                g, h = obj.gradients_from_payload(ghi[3], **payload)
                g = g * vf
                h = h * vf
            bag_cnt = jnp.int32(N)
            with scopes.scope("sampling"):
                if use_goss:
                    # in-program GOSS (goss.hpp Helper:116-165): pad rows
                    # carry zero importance and never select
                    imp = jnp.abs(g * h)
                    threshold = jax.lax.top_k(imp, g_top_k)[0][-1]
                    is_top = (imp >= threshold) & (vf > 0)
                    kg = jax.random.fold_in(bag_key, seed)
                    n_top = jnp.sum(is_top.astype(jnp.int32))
                    rest = jnp.maximum(N - n_top, 1)
                    prob = g_other_k / rest.astype(jnp.float32)
                    keep_other = ((~is_top) & (vf > 0) &
                                  (jax.random.uniform(kg, g.shape) < prob))
                    multiply = (N - g_top_k) / g_other_k
                    scale = jnp.where(is_top, 1.0,
                                      jnp.where(keep_other, multiply, 0.0))
                    g = g * scale
                    h = h * scale
                    bag_cnt = jnp.sum((is_top | keep_other).astype(jnp.int32))
                elif use_bag:
                    # bag redrawn per bagging_freq period: the key depends on
                    # the PERIOD index, so iterations inside one period see
                    # the identical mask (bagging.hpp semantics).  Draws are
                    # indexed by ORIGINAL row id — the physical permutation
                    # changes every iteration, so a draw over physical
                    # positions would silently re-bag mid-period
                    kb = jax.random.fold_in(bag_key, (seed - 1) // bag_freq)
                    u = jax.random.uniform(kb, (N + 1,))
                    sel = (jnp.take(u, jnp.minimum(rowid, N)) < bag_frac) \
                        & (vf > 0)
                    sf = sel.astype(jnp.float32)
                    g = g * sf
                    h = h * sf
                    bag_cnt = jnp.sum(sel.astype(jnp.int32))
                elif use_balanced:
                    # per-class Bernoulli (reference: bagging.hpp
                    # BalancedBaggingHelper:180-200); label signs ride the
                    # payload, draws are indexed by original row id
                    kb = jax.random.fold_in(bag_key, (seed - 1) // bag_freq)
                    u = jnp.take(jax.random.uniform(kb, (N + 1,)),
                                 jnp.minimum(rowid, N))
                    posr = ghi[4 + sign_idx] > 0
                    sel = jnp.where(posr, u < pos_frac, u < neg_frac) \
                        & (vf > 0)
                    sf = sel.astype(jnp.float32)
                    g = g * sf
                    h = h * sf
                    # the ACTUAL drawn count, not the sizing estimate
                    # (bagging.hpp:46 bag_data_cnt_ = left_cnt)
                    bag_cnt = jnp.sum(sel.astype(jnp.int32))
            hist_scale = None
            with scopes.scope("quantize"):
                if use_quant:
                    # in-program discretizer (reference:
                    # GradientDiscretizer::DiscretizeGradients); integer
                    # carriers ride the payload, the scale goes to the
                    # histogram (bf16 int-exact accumulation)
                    gs = jnp.maximum(jnp.max(jnp.abs(g)) / (q_bins / 2.0),
                                     1e-30)
                    max_h = jnp.max(jnp.abs(h))
                    hs = jnp.maximum(max_h if q_const_h else max_h / q_bins,
                                     1e-30)
                    if q_stoch:
                        kg, kh = jax.random.split(
                            jax.random.fold_in(q_key, seed))
                        rg = jax.random.uniform(kg, g.shape)
                        rh = jax.random.uniform(kh, h.shape)
                    else:
                        rg = rh = 0.5
                    ig = jnp.trunc(g / gs + jnp.where(g >= 0, rg, -rg))
                    ih = (jnp.ones_like(h) if q_const_h
                          else jnp.trunc(h / hs + rh))
                    g_q = ig * vf
                    h_q = ih * vf
                    hist_scale = jnp.stack([gs, hs])
                else:
                    g_q, h_q = g, h
            with scopes.scope("gradients"):
                ghi = ghi.at[0].set(g_q).at[1].set(h_q)
                if use_quant and q_renew:
                    # true grads ride the partition so the renewal reads
                    # them in the record's row order
                    ghi = ghi.at[tg_row].set(g).at[th_row].set(h)
            rec = lr_._build_tree_impl(part_bins, ghi, bag_cnt,
                                       feature_mask, seed, feat_used,
                                       None, hist_scale)
            with scopes.scope("leaf_renew"):
                if use_quant and q_renew:
                    # leaf renewal from the TRUE gradients in POST-partition
                    # order: per-leaf sums are prefix differences at the
                    # range boundaries (reference: RenewIntGradTreeOutput)
                    from ..ops.split import leaf_output as _leaf_out
                    cg = jnp.concatenate([jnp.zeros((1,), jnp.float32),
                                          jnp.cumsum(rec["part_ghi"][tg_row])])
                    ch = jnp.concatenate([jnp.zeros((1,), jnp.float32),
                                          jnp.cumsum(rec["part_ghi"][th_row])])
                    ls = rec["leaf_start"]
                    lc = rec["leaf_cnt"]
                    sum_g = jnp.take(cg, ls + lc) - jnp.take(cg, ls)
                    sum_h = jnp.take(ch, ls + lc) - jnp.take(ch, ls)
                    renewed = _leaf_out(sum_g, sum_h + 2e-15, l1_, l2_, mds_)
                    rec["leaf_value"] = jnp.where(lc > 0, renewed,
                                                  rec["leaf_value"])
                if renew_alpha is not None:
                    # L1-family leaf renewal: per-leaf residual percentile in
                    # POST-partition order (RegressionL1loss::RenewTreeOutput)
                    ghi_p = rec["part_ghi"]
                    rowid_p = jax.lax.bitcast_convert_type(ghi_p[2], jnp.int32)
                    valid_p = rowid_p != N
                    if use_bag:
                        kb = jax.random.fold_in(bag_key,
                                                (seed - 1) // bag_freq)
                        u = jax.random.uniform(kb, (N + 1,))
                        sel_p = (jnp.take(u, jnp.minimum(rowid_p, N))
                                 < bag_frac) & valid_p
                    elif use_balanced:
                        kb = jax.random.fold_in(bag_key,
                                                (seed - 1) // bag_freq)
                        u = jnp.take(jax.random.uniform(kb, (N + 1,)),
                                     jnp.minimum(rowid_p, N))
                        posr = ghi_p[4 + sign_idx] > 0
                        sel_p = jnp.where(posr, u < pos_frac,
                                          u < neg_frac) & valid_p
                    else:
                        sel_p = valid_p
                    resid = ghi_p[4 + label_idx] - ghi_p[3]
                    if renew_w_fn is not None:
                        pw = renew_w_fn(
                            ghi_p[4 + label_idx],
                            ghi_p[4 + weight_idx] if weight_idx is not None
                            else None)
                    elif weight_idx is not None:
                        pw = ghi_p[4 + weight_idx]
                    else:
                        pw = None
                    rec["leaf_value"] = _renew_leaves_percentile(
                        rec, resid, pw, sel_p, renew_alpha, Npad)
            with scopes.scope("score_update"):
                ghi_out = rec["part_ghi"].at[3].add(
                    shrink * _phys_leaf_delta(rec, Npad))
            small = _small_record(rec)
            small["leaf_delta"] = rec["leaf_value"] * shrink
            return rec["part_bins"], ghi_out, small

        self._fused_phys = jax.jit(lgbm_fused_step, donate_argnums=(0, 1))
        self._fused = self._fused_phys    # gate for train_one_iter

    def _mc_fused_kind(self):
        """Which fused-multiclass formula the CONCRETE objective class
        provides: 'snapshot' (softmax family) or 'perclass' (OVA), else
        None.  Checked on the concrete class's own __dict__ — a subclass
        overriding get_gradients must not silently inherit the base
        fused formula (same guard as the K==1 payload gate)."""
        d = type(self.objective).__dict__
        if (d.get("fused_prob_snapshot") is not None
                and d.get("fused_class_gradients_from_prob") is not None):
            return "snapshot"
        if d.get("fused_class_gradients") is not None:
            return "perclass"
        return None

    def _setup_fused_multiclass(self) -> None:
        """Physical-order fused multiclass iteration: all K class trees
        build inside ONE jitted program (the device analog of gbdt.cpp:379's
        per-class Train loop).  Payload rows: 0 grad, 1 hess, 2 rowid-bits,
        3..3+K-1 per-class scores, 3+K label, [3+K+1 weight] — every row
        rides each class tree's partition, so after tree k the whole block
        (including the other classes' scores) is consistently permuted and
        tree k+1 reads softmax inputs in the CURRENT physical order."""
        lr_ = self.learner
        obj = self.objective
        cfg = self.config
        K = self.num_tree_per_iteration
        shrink = self.shrinkage_rate
        N = self.num_data
        Npad = lr_.N_pad
        C = lr_.row0
        has_w = obj.weight is not None
        need = 4 + K + (1 if has_w else 0)
        if need > lr_._ghi_rows:
            return    # Pallas partition caps the payload at 8 f32 rows
        lr_._ghi_live = need
        lbl_row = 3 + K
        w_row = lbl_row + 1
        label_arr = jnp.asarray(obj.label, jnp.float32)
        weight_arr = obj.weight

        def ghi0(scores):
            iota = jax.lax.iota(jnp.int32, Npad)
            rowid = jnp.where((iota >= C) & (iota < C + N), iota - C, N)
            ghi = jnp.zeros((lr_._ghi_rows, Npad), jnp.float32)
            ghi = ghi.at[2].set(
                jax.lax.bitcast_convert_type(rowid, jnp.float32))
            for k in range(K):
                ghi = ghi.at[3 + k].set(
                    jnp.pad(scores[:, k], (C, Npad - C - N)))
            ghi = ghi.at[lbl_row].set(jnp.pad(label_arr, (C, Npad - C - N)))
            if has_w:
                ghi = ghi.at[w_row].set(
                    jnp.pad(weight_arr, (C, Npad - C - N)))
            return ghi

        @scopes.phase("layout_init")
        def lgbm_layout_init(part_bins, scores):
            # bins pass through untouched; donated in the _adopt
            # variant so the carrier adopts the master buffer (see
            # _setup_fused_phys / single-copy residency);
            # _adopt_master_buffer retires the other refs
            return part_bins, ghi0(scores)

        @scopes.phase("layout_init")
        def lgbm_layout_resume(part_bins, rowid_bits, scores):
            bins = _unpermute_bins(part_bins, rowid_bits, N, C, Npad)
            return bins, ghi0(scores)

        self._init_phys = jax.jit(lgbm_layout_init)
        self._init_phys_adopt = jax.jit(lgbm_layout_init, donate_argnums=(0,))
        self._init_phys_perm = jax.jit(lgbm_layout_resume, donate_argnums=(0,))

        use_bag = self.need_bagging and not self.balanced_bagging
        bag_key = jax.random.PRNGKey(cfg.bagging_seed)
        bag_freq = max(int(cfg.bagging_freq), 1)
        bag_frac = float(cfg.bagging_fraction)

        needs_snap = self._mc_fused_kind() == "snapshot"

        def lgbm_fused_step(part_bins, ghi, feature_mask, seed, feat_used):
            obs.compile_event("train.fused_step")   # trace-time only
            smalls = []
            P = None
            if needs_snap:
                # softmax couples the classes: ALL K gradients come from
                # the PRE-iteration scores (gbdt.cpp Boosting computes
                # them before any class tree).  Snapshot the
                # probabilities by ORIGINAL row id; each class tree
                # gathers them back through its own permutation.
                with scopes.scope("gradients"):
                    rowid0 = jax.lax.bitcast_convert_type(ghi[2], jnp.int32)
                    p0 = obj.fused_prob_snapshot(ghi[3:3 + K])
                    P = jnp.zeros((K, N + 1), jnp.float32).at[
                        :, jnp.minimum(rowid0, N)].set(p0)
            for k in range(K):
                with scopes.scope("gradients"):
                    rowid = jax.lax.bitcast_convert_type(ghi[2], jnp.int32)
                    vf = (rowid != N).astype(jnp.float32)
                    if needs_snap:
                        p_k = jnp.take(P[k], jnp.minimum(rowid, N))
                        g, h = obj.fused_class_gradients_from_prob(
                            k, p_k, ghi[lbl_row],
                            ghi[w_row] if has_w else None)
                    else:
                        g, h = obj.fused_class_gradients(
                            k, ghi[3:3 + K], ghi[lbl_row],
                            ghi[w_row] if has_w else None)
                bag_cnt = jnp.int32(N)
                if use_bag:
                    # one bag per ITERATION shared by all K class trees
                    # (bagging.hpp), drawn by original row id (see the
                    # binary fused step)
                    with scopes.scope("sampling"):
                        kb = jax.random.fold_in(bag_key,
                                                (seed - 1) // bag_freq)
                        u = jax.random.uniform(kb, (N + 1,))
                        sel = (jnp.take(u, jnp.minimum(rowid, N))
                               < bag_frac) & (vf > 0)
                        sf = sel.astype(jnp.float32)
                        g = g * sf
                        h = h * sf
                        bag_cnt = jnp.sum(sel.astype(jnp.int32))
                else:
                    with scopes.scope("gradients"):
                        g = g * vf
                        h = h * vf
                with scopes.scope("gradients"):
                    ghi = ghi.at[0].set(g).at[1].set(h)
                rec = lr_._build_tree_impl(part_bins, ghi, bag_cnt,
                                           feature_mask, seed * K + k,
                                           feat_used)
                part_bins = rec["part_bins"]
                ghi = rec["part_ghi"]
                with scopes.scope("score_update"):
                    ghi = ghi.at[3 + k].add(
                        shrink * _phys_leaf_delta(rec, Npad))
                feat_used = rec["feat_used"]
                small = _small_record(rec)
                small["leaf_delta"] = rec["leaf_value"] * shrink
                smalls.append(small)
            return part_bins, ghi, smalls

        self._fused_phys = jax.jit(lgbm_fused_step, donate_argnums=(0, 1))
        self._fused = self._fused_phys

    def _setup_fused_sharded(self) -> None:
        """Fused physical iteration over the device mesh: the per-shard
        analog of _setup_fused_phys, shard_map'd so one dispatch per
        iteration covers gradients -> sharded tree build (with its psum
        collectives) -> score update.  The eager sharded path pays
        several host round-trips per iteration that this removes.

        Rows stay in each shard's PHYSICAL order; rowids carry GLOBAL
        original indices (shard d owns [d*local_n, d*local_n+count_d)),
        so bagging draws and the original-order score materialization
        are shard-layout independent."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        sb = self.sharded_builder
        lr_ = sb.learner
        obj = self.objective
        cfg = self.config
        if (type(obj).__dict__.get("gradients_from_payload") is None
                or obj.gradient_payload() is None):
            self._fused_sharded_reason = \
                "objective lacks gradients_from_payload"
            return
        names = [n for n in obj.payload_fields
                 if getattr(obj, n) is not None]
        if 4 + len(names) > lr_._ghi_rows:
            self._fused_sharded_reason = "payload exceeds the ghi rows"
            return
        lr_._ghi_live = 4 + len(names)
        shrink = self.shrinkage_rate
        # rowid space is GLOBAL across the whole mesh so bagging draws
        # agree on every process.  Mesh ids are GAPPED when ranks hold
        # unequal row counts (device d owns [d*local_n, d*local_n+cnt_d)
        # with local_n the max over ranks), so the pad sentinel must sit
        # ABOVE the whole id space — ndev*local_n — not at sb.N: a
        # sentinel of sb.N would collide with a real row's id and
        # silently drop it from training
        N = sb.N
        SENT = sb.ndev * sb.local_n
        Npad = lr_.N_pad
        C = lr_.row0
        ndev = sb.ndev
        local_n = sb.local_n
        mesh = sb.mesh
        AXIS = "data"
        repl_rows = sb.mode == "feature"
        payload_arrs = [np.asarray(getattr(obj, n), np.float32)
                        for n in names]

        def shard_rows(arr):
            # this process's rows, laid out as one local_n block per
            # LOCAL device (mirroring the builder's binned blocking);
            # sb._put assembles the global mesh array across processes
            arr = np.asarray(arr, np.float32)
            if repl_rows:
                return sb._put(arr, NamedSharding(mesh, P()))
            total = sb.local_ndev * local_n if sb.nproc > 1 \
                else ndev * local_n
            if len(arr) < total:
                arr = np.concatenate(
                    [arr, np.zeros(total - len(arr), np.float32)])
            return sb._put(arr, NamedSharding(mesh, P(AXIS)))

        row_spec = P() if repl_rows else P(AXIS)
        state_spec = P() if repl_rows else P(None, AXIS)

        @scopes.phase("layout_init")
        def init_shard(binned, scores, counts, *payloads):
            # binned: this shard's pristine (pb_rows, N_pad) block, in the
            # layout the step reads (the step donates its carrier, so the
            # carrier is a copy of it); scores/payloads (rows,); counts (1,)
            pb = binned
            iota = jax.lax.iota(jnp.int32, Npad)
            li = iota - C
            valid = (li >= 0) & (li < counts[0])
            base = (jnp.int32(0) if repl_rows
                    else jax.lax.axis_index(AXIS) * local_n)
            rowid = jnp.where(valid, base + li, SENT)
            nrows = scores.shape[0]

            def rowpad(a):
                return jnp.pad(a, (C, Npad - C - nrows))
            rows = [jnp.zeros((Npad,), jnp.float32),
                    jnp.zeros((Npad,), jnp.float32),
                    jax.lax.bitcast_convert_type(rowid, jnp.float32),
                    rowpad(scores)]
            rows += [rowpad(p) for p in payloads]
            rows += [jnp.zeros((Npad,), jnp.float32)
                     for _ in range(lr_._ghi_rows - len(rows))]
            return pb, jnp.stack(rows)

        n_pay = len(payload_arrs)
        cnt_spec = P() if repl_rows else P(AXIS)
        # feature mode: every device computes the IDENTICAL state (split
        # decisions are synced by the build's all-gather), but the vma
        # checker can't see through the varying intermediates — disable
        # the static check for the replicated layout only
        # ... and for interpreted Pallas kernels (see the builder)
        smap = functools.partial(
            jax.shard_map, mesh=mesh,
            check_vma=not (repl_rows or sb.interpreted_kernels))
        init_sharded = jax.jit(smap(
            init_shard,
            in_specs=(state_spec, row_spec, cnt_spec) + (row_spec,) * n_pay,
            out_specs=(state_spec, state_spec)))

        def init_fn():
            scores_sh = shard_rows(np.asarray(self._scores_arr))
            pays = [shard_rows(p) for p in payload_arrs]
            counts = (sb._put(np.asarray([N], np.int32),
                              NamedSharding(mesh, P()))
                      if repl_rows else sb.local_counts)
            phys = init_sharded(sb.binned_sharded, scores_sh,
                                counts, *pays)
            # the carrier's score row is the state from here on: a copy in
            # original order, sized by every shard's rows, stays on no
            # device (a read of `.scores` makes one anew)
            self._scores_arr = None
            return phys

        self._init_phys_fn = init_fn

        if not repl_rows and ndev * local_n == N:
            # rows cut evenly: shard d's scores are rows [d*local_n,
            # (d+1)*local_n) of the original order, so each shard scatters
            # its own score row by rowid and the pieces ARE the scores,
            # cut over the mesh: no device sorts or holds every shard's
            # rows.  (An uneven cut, and replicated rows, take the one
            # scatter of lgbm_scores_read.)
            @scopes.phase("scores_read")
            def lgbm_scores_read_shard(ghi):
                rowid = jax.lax.bitcast_convert_type(ghi[2], jnp.int32)
                li = rowid - jax.lax.axis_index(AXIS) * local_n
                return jnp.zeros((local_n,), jnp.float32).at[li].set(
                    ghi[3], mode="drop")

            self._scores_read_sharded = jax.jit(smap(
                lgbm_scores_read_shard, in_specs=(state_spec,),
                out_specs=P(AXIS)))

        use_bag = self.need_bagging and not self.balanced_bagging
        bag_key = jax.random.PRNGKey(cfg.bagging_seed)
        bag_freq = max(int(cfg.bagging_freq), 1)
        bag_frac = float(cfg.bagging_fraction)
        mode = sb.mode
        F = lr_.F

        def lgbm_fused_step(pb, ghi, feature_mask, seed, feat_used):
            obs.compile_event("train.fused_step")   # trace-time only
            with scopes.scope("gradients"):
                rowid = jax.lax.bitcast_convert_type(ghi[2], jnp.int32)
                vf = (rowid != SENT).astype(jnp.float32)
                payload = {n: ghi[4 + i] for i, n in enumerate(names)}
                g, h = obj.gradients_from_payload(ghi[3], **payload)
                g = g * vf
                h = h * vf
            if use_bag:
                # draws by GLOBAL row id: every shard layout sees the
                # same bag for a given period (bagging.hpp semantics)
                with scopes.scope("sampling"):
                    kb = jax.random.fold_in(bag_key,
                                            (seed - 1) // bag_freq)
                    u = jax.random.uniform(kb, (SENT + 1,))
                    sel = (jnp.take(u, jnp.minimum(rowid, SENT))
                           < bag_frac) & (vf > 0)
                    sf = sel.astype(jnp.float32)
                    g = g * sf
                    h = h * sf
                    bag_cnt = jnp.sum(sel.astype(jnp.int32))
            else:
                with scopes.scope("gradients"):
                    bag_cnt = jnp.sum(vf).astype(jnp.int32)
            if mode == "feature":
                d = jax.lax.axis_index(AXIS)
                per = (F + ndev - 1) // ndev
                fidx = jnp.arange(F)
                feature_mask = feature_mask & (fidx >= d * per) \
                    & (fidx < (d + 1) * per)
            with scopes.scope("gradients"):
                ghi = ghi.at[0].set(g).at[1].set(h)
            rec = lr_._build_tree_impl(pb, ghi, bag_cnt, feature_mask,
                                       seed, feat_used)
            with scopes.scope("score_update"):
                ghi_out = rec["part_ghi"].at[3].add(
                    shrink * _phys_leaf_delta(rec, Npad))
            small = _small_record(rec)
            # per-shard leaf offsets must not leak out replicated
            small.pop("leaf_start", None)
            small.pop("leaf_cnt", None)
            small["leaf_delta"] = small["leaf_value"] * shrink

            def replicate(x):
                if x.dtype == jnp.bool_:
                    return jax.lax.pmax(x.astype(jnp.int32),
                                        AXIS).astype(jnp.bool_)
                return jax.lax.pmax(x, AXIS)

            with scopes.scope("hist_sync"):
                small = jax.tree.map(replicate, small)
            return rec["part_bins"], ghi_out, small

        self._fused_phys = jax.jit(smap(
            lgbm_fused_step,
            in_specs=(state_spec, state_spec, P(), P(), P()),
            out_specs=(state_spec, state_spec, P())),
            donate_argnums=(0, 1))
        self._fused = self._fused_phys
        for holder in (obj, *self.train_metrics):
            _rows_to_host(holder, self.num_data)
        log.info("fused sharded iteration ENABLED (%s-parallel over %d "
                 "devices)", mode, ndev)

    def _init_phys_layout(self) -> None:
        """(Re)make the physical row layout the fused step runs on: at the
        first iteration, and again after every scores read or write."""
        if self._init_phys_fn is not None:   # sharded layout (a closure
            # over mesh arrays it places itself: not registered)
            self._phys = tuple(self._init_phys_fn())
            self._phys_carrier = None
        elif self._phys_carrier is not None:
            # resume from the retired carrier: the bins are
            # unpermuted back to the identity layout in-program,
            # bit-identical to an init from the master buffer
            pb, rowid_bits = self._phys_carrier
            self._phys_carrier = None
            self._phys = tuple(self._init_phys_perm(
                pb, rowid_bits, self._scores_arr))
            self._register_once("train.layout_init", self._init_phys_perm,
                                self._phys[0], rowid_bits, self._scores_arr)
        else:
            self._phys = tuple(self._init_phys_adopt(
                self.learner._part0, self._scores_arr))
            self._register_once("train.layout_init", self._init_phys_adopt,
                                self._phys[0], self._scores_arr)
            # the donating identity init aliased the master
            # buffer into the carrier; retire the (now stale)
            # learner/ingest references
            self._adopt_master_buffer()

    def _train_one_iter_fused(self) -> bool:
        """Fast path: the whole iteration in one device program.

        Host round-trips would put a floor under the iteration time, so
        the small tree record is copied to the host ASYNCHRONOUSLY
        and materialized with a one-iteration lag (its transfer overlaps the
        next iteration's device compute).  Consumers of `models` call
        `_flush_pending()` first."""
        feature_mask = self._feature_mask(self.iter)
        if self._cegb_feat_used is not None:
            feat_used = self._cegb_feat_used
        else:
            if not hasattr(self, "_zeros_fused"):
                self._zeros_fused = jnp.zeros((self.learner.F,), dtype=bool)
            feat_used = self._zeros_fused
        if self._fused_phys is not None:
            if self._phys is None:
                with obs.span("train.layout_init"):
                    self._init_phys_layout()
            with obs.span("train.dispatch"):
                pb, ghi, rec = self._fused_phys(
                    self._phys[0], self._phys[1], feature_mask,
                    self.iter + 1, feat_used)
            self._phys = (pb, ghi)
            self._register_once("train.fused_step", self._fused_phys,
                                pb, ghi, feature_mask, self.iter + 1,
                                feat_used)
        else:
            with obs.span("train.dispatch"):
                self.scores, rec = self._fused(
                    self.learner._part0, self.scores, feature_mask,
                    self.iter + 1, feat_used)
            self._register_once("train.fused_step", self._fused,
                                self.learner._part0, self.scores,
                                feature_mask, self.iter + 1, feat_used)
        recs = rec if isinstance(rec, list) else [rec]
        if self.learner.has_cegb:
            self._cegb_feat_used = recs[-1]["feat_used"]
        for r in recs:
            small = _small_record(r, scalars=("s", "made"))
            for v in small.values():
                try:
                    v.copy_to_host_async()
                except Exception:
                    break
            self._pending_recs.append(small)
        self.iter += 1
        # with validation sets the record is needed NOW (scores update per
        # iteration); otherwise records accumulate and are drained in
        # BATCHES with one device_get each, so the training loop never
        # waits on a host materialization per iteration
        lag = 0 if self.valid_sets else 32
        should_stop = False
        if len(self._pending_recs) > (2 * lag if lag else 0):
            should_stop = self._drain_pending(lag)
        if should_stop:
            log.warning("Stopped training because there are no more leaves "
                        "that meet the split requirements")
        return should_stop

    def _drain_pending(self, lag: int) -> bool:
        """Materialize pending records down to ``lag``, fetching them all
        with ONE host transfer."""
        n = len(self._pending_recs) - lag
        if n <= 0:
            return False
        with obs.span("train.drain_records"):
            batch_host = jax.device_get(self._pending_recs[:n])
        K = self.num_tree_per_iteration
        for host_record in batch_host:
            if self._materialize_pending(host_record):
                # stop fires only at an iteration boundary, so the
                # remaining records are whole discarded iterations
                self.iter -= len(self._pending_recs) // K
                self._pending_recs.clear()
                return True
        return False

    def _materialize_pending(self, host_record=None) -> bool:
        """Convert the oldest pending device record into a host tree."""
        small = self._pending_recs.pop(0)
        if host_record is None:
            host_record = jax.device_get(small)
        num_nodes = int(host_record["s"])
        if DEBUG_CHECKS and "leaf_start" in host_record:
            debug_validate_record(host_record, num_nodes, self.num_data,
                                  self.learner.row0)
        nodes = self.learner.node_arrays_for_predict(small)
        delta_leaf = small["leaf_delta"]
        K = self.num_tree_per_iteration
        k_cls = len(self.models) % K
        for vi, (vd, metrics, binned) in enumerate(self.valid_sets):
            leaf_v = predict_leaf_binned(binned, nodes)
            if K == 1:
                self.valid_scores[vi] = self.valid_scores[vi] + \
                    jnp.take(delta_leaf, leaf_v)
            else:
                self.valid_scores[vi] = self.valid_scores[vi].at[
                    :, k_cls].add(jnp.take(delta_leaf, leaf_v))
        tree = tree_from_device_record(
            host_record, num_nodes, self.train_data.bin_mappers,
            None, shrinkage=self.shrinkage_rate)
        if (len(self.models) < K
                and abs(self.init_scores[k_cls]) > K_EPSILON):
            if num_nodes > 0:
                tree.leaf_value = tree.leaf_value + self.init_scores[k_cls]
                tree.internal_value = (tree.internal_value
                                       + self.init_scores[k_cls])
            else:
                tree.leaf_value = np.asarray([self.init_scores[k_cls]])
        self._health_record_tree(host_record, num_nodes)
        self._telemetry_chunk_waste(host_record, num_nodes)
        self._telemetry_pruned_splits(host_record, num_nodes)
        self._telemetry_hist_sync(num_nodes)
        self.models.append(tree)
        self.device_trees.append({
            "nodes": nodes, "leaf_value": delta_leaf,
            "has_cat_split": bool(
                np.any(host_record["node_is_cat"][:num_nodes]))})
        self._model_version += 1
        self.serving.invalidate()
        # stop only when a FULL iteration's K class trees are all empty
        # (gbdt.cpp TrainOneIter's per-class should_continue)
        self._empty_run = self._empty_run + 1 if num_nodes == 0 else 0
        return self._empty_run >= K and len(self.models) % K == 0

    def _flush_pending(self) -> None:
        """Materialize all lagged fused-iteration records (no-op usually)."""
        if getattr(self, "_pending_recs", None):
            self._drain_pending(0)

    # -- health flight recorder (obs/health.py) -------------------------
    def _health_effective_rows(self) -> int:
        """This iteration's effective sample count under GOSS/bagging —
        the host-side derivation (the actual balanced-bagging draw is a
        device scalar; reading it here would add the exact JL001 host
        sync the sampling paths were scrubbed of)."""
        cfg = self.config
        N = self.num_data
        if getattr(self, "goss", False):
            top_k = max(int(N * cfg.top_rate), 1)
            other_k = max(int(N * cfg.other_rate), 1)
            return min(top_k + other_k, N)
        if getattr(self, "need_bagging", False):
            if self.balanced_bagging:
                label = self.train_data.metadata.label
                pos = int((np.asarray(label) > 0).sum())
                return max(int(pos * cfg.pos_bagging_fraction
                               + (N - pos) * cfg.neg_bagging_fraction), 1)
            return max(int(N * cfg.bagging_fraction), 1)
        return N

    def _health_record_tree(self, host_record, num_nodes: int) -> None:
        """Feed one just-materialized host tree record to the flight
        recorder (a no-op unless health != off armed one at setup).
        Called at BOTH materialization sites — the lagged fused drain
        and the eager loop — with values already on the host, so it
        adds zero device ops and zero syncs by construction (the
        jaxlint ``health.off`` budget pins the lowering either way)."""
        if self.flight is None:
            return
        K = self.num_tree_per_iteration
        idx = len(self.models)             # the tree about to append
        self.flight.record_tree(idx // K, idx % K, host_record,
                                num_nodes,
                                effective_rows=self._health_effective_rows())

    # -- frontier-body speculation counters (obs/telemetry.py) ----------
    @staticmethod
    def _telemetry_pruned_splits(record, num_nodes: int) -> None:
        """``train.frontier.pruned_splits`` / ``train.frontier.undo_trees``:
        how many speculative splits the frontier body (tpu_frontier_k > 1)
        executed and pruned again, and in how many trees — the trees whose
        tree-end undo pass, the rowid snapshot's only reader, ran.  The
        K=1 body's record has no ``made``; off costs nothing."""
        if obs.get().mode == "off" or "made" not in record:
            return
        pruned = int(record["made"]) - num_nodes
        if pruned > 0:
            obs.counter("train.frontier.pruned_splits", pruned)
            obs.counter("train.frontier.undo_trees")

    # -- data-parallel histogram sync (obs/telemetry.py) ----------------
    def _telemetry_hist_sync(self, num_nodes: int) -> None:
        """``train.parallel.hist_sync_bytes``: the bytes one shard hands
        to the tree's histogram sums (the root's and each split's smaller
        child's ``(G, B, 2)`` f32 histogram), from shapes alone;
        ``train.parallel.shards``: the mesh's size.  tree_learner=data
        only (voting sums elected features, feature sums nothing)."""
        sb = self.sharded_builder
        if obs.get().mode == "off" or sb is None or sb.mode != "data":
            return
        lr = self.learner
        obs.counter("train.parallel.hist_sync_bytes",
                    (num_nodes + 1) * lr.G * lr.B * 2 * 4)
        obs.gauge("train.parallel.shards", sb.ndev)

    # -- chunk-policy padding-waste gauges (obs/telemetry.py) -----------
    def _telemetry_chunk_waste(self, host_record, num_nodes: int) -> None:
        """Per-band live-row occupancy + padding-waste gauges of the
        just-materialized tree under the active chunk policy
        (``train.chunk.*``, surfaced in ``Booster.telemetry_report()``).
        Host arithmetic on leaf counts the trainer already transferred
        — zero device ops, zero syncs, no-op with telemetry off."""
        sess = obs.get()
        if sess.mode == "off" or "leaf_cnt" not in host_record:
            return
        policy = getattr(self.learner, "_chunk_policy", None)
        if policy is None:
            return
        from ..ops.chunkpolicy import waste_stats
        counts = np.asarray(host_record["leaf_cnt"])[:num_nodes + 1]
        stats = waste_stats(counts, policy)
        sess.gauge("train.chunk.waste", stats["waste"])
        sess.gauge("train.chunk.fixed_waste", stats["fixed_waste"])
        for k, v in stats.items():
            if k.startswith("band_"):
                sess.gauge(f"train.chunk.{k}", v)

    # ------------------------------------------------------------------
    def continue_from(self, trees, train_pred: np.ndarray) -> None:
        """Continued training from a loaded model (reference:
        application.cpp:94-97 — a Predictor over the input model seeds the
        scores — plus GBDT::MergeFrom, gbdt.h:70, and the python engine's
        ``train(init_model=)``, python-package/lightgbm/engine.py:150-186).

        ``trees`` become the head of the model list; train scores are
        rebuilt as (dataset init_score) + ``train_pred`` (the init model's
        raw prediction over the RAW train rows — bin-space evaluation
        would be wrong whenever this dataset's bin boundaries differ from
        the loaded model's thresholds).  The caller (Booster) owns the raw
        matrices and computes the predictions.
        """
        import copy as _copy
        self._flush_pending()
        if self.models:
            raise ValueError("continue_from requires a fresh booster")
        K = self.num_tree_per_iteration
        self.models = [_copy.deepcopy(t) for t in trees]
        # loaded trees carry real-valued thresholds only — no device (bin)
        # node arrays.  Rollback past the continuation boundary is refused.
        self.device_trees = [None] * len(self.models)
        self.iter = len(self.models) // K
        self._model_version += 1
        self.serving.invalidate()
        # DART continuation: init-model trees are excluded from dropping
        # (reference: dart.hpp:108-122 draws over the session's iter_ only,
        # offset by num_init_iteration_)
        if hasattr(self, "init_iters"):
            self.init_iters = self.iter
        self._continued = True
        # the loaded model's boost_from_average lives in its first tree
        # (folded at materialization), so the fresh booster's must not
        # apply on top
        self.init_scores = [0.0] * K

        n = self.num_data
        shape = (n,) if K == 1 else (n, K)
        base = np.zeros(shape, dtype=np.float32)
        meta = self.train_data.metadata
        if meta.init_score is not None:
            init = np.asarray(meta.init_score, dtype=np.float32)
            if K > 1:
                init = init.reshape(K, n).T
            base = init.reshape(shape)
        pred = np.asarray(train_pred, dtype=np.float32)
        self.scores = jnp.asarray(base + pred.reshape(shape))

    def add_valid_data(self, valid_data: BinnedDataset,
                       extra_score=None) -> None:
        metrics = create_metrics(
            self.config, self.objective.name if self.objective else None)
        for m in metrics:
            m.init(valid_data.metadata)
        binned = jnp.asarray(valid_data.binned)
        K = self.num_tree_per_iteration
        shape = (valid_data.num_data,) if K == 1 else (valid_data.num_data, K)
        score = jnp.zeros(shape, dtype=jnp.float32)
        if valid_data.metadata.init_score is not None:
            init = np.asarray(valid_data.metadata.init_score, dtype=np.float32)
            if K > 1:
                init = init.reshape(K, valid_data.num_data).T
            score = jnp.asarray(init.reshape(shape))
        else:
            for k in range(K):
                if abs(self.init_scores[k]) > K_EPSILON:
                    if K == 1:
                        score = score + self.init_scores[k]
                    else:
                        score = score.at[:, k].add(self.init_scores[k])
        if extra_score is not None:
            # continued training: the loaded model's contribution (its own
            # average-boost folded into tree 0) rides on top of init_score
            extra = np.asarray(extra_score, dtype=np.float32)
            score = score + jnp.asarray(extra.reshape(score.shape))
        elif self._continued:
            raise ValueError("validation sets added to a continued booster "
                             "need the init model's predictions "
                             "(Booster.add_valid computes them)")
        self.valid_sets.append((valid_data, metrics, binned))
        self.valid_scores.append(score)

    # ------------------------------------------------------------------
    def _compute_gradients(self):
        g, h = self.objective.get_gradients(self.scores)
        return g, h

    def _bagging_mask(self, it: int):
        """Row sampling (reference: bagging.hpp).  Returns (mask (N,) bool or
        None, bag_cnt).  The learner never gathers rows: out-of-bag rows keep
        their place with zeroed gradients (TPU row gathers are latency-bound,
        masking is bandwidth-free)."""
        cfg = self.config
        N = self.num_data
        if not self.need_bagging:
            return None, None
        if it % cfg.bagging_freq == 0 or self._cached_bag is None:
            self.bag_rng, sub = jax.random.split(self.bag_rng)
            if self.balanced_bagging:
                # per-class Bernoulli (reference: bagging.hpp
                # BalancedBaggingHelper:180-200); the bag count estimate
                # is the reference's bag_data_cnt_ (:100)
                label = jnp.asarray(self.train_data.metadata.label)
                pos = label > 0
                u = jax.random.uniform(sub, (N,))
                mask = jnp.where(pos, u < cfg.pos_bagging_fraction,
                                 u < cfg.neg_bagging_fraction)
                # the ACTUAL drawn count (bagging.hpp:46
                # bag_data_cnt_ = left_cnt), not the sizing estimate —
                # kept as a device scalar: build_tree takes it traced,
                # so an int() here is a host sync per bagging redraw
                # for nothing (jaxlint JL001)
                cnt = jnp.maximum(jnp.sum(mask.astype(jnp.int32)),
                                  jnp.int32(1))
            else:
                cnt = max(int(N * cfg.bagging_fraction), 1)
                mask = jnp.zeros((N,), bool).at[
                    jax.random.permutation(sub, N)[:cnt]].set(True)
            self._cached_bag = (mask, cnt)
        return self._cached_bag

    def _goss_sample(self, grad, hess, it: int):
        """GOSS (reference: goss.hpp Helper:116-165): keep the top_rate fraction
        by |g*h|, sample other_rate of the rest and up-weight by
        (1-top_rate)/other_rate.  Unselected rows get zeroed gradients."""
        cfg = self.config
        N = self.num_data
        if grad.ndim == 2:
            imp = jnp.sum(jnp.abs(grad * hess), axis=1)
        else:
            imp = jnp.abs(grad * hess)
        top_k = max(int(N * cfg.top_rate), 1)
        other_k = max(int(N * cfg.other_rate), 1)
        threshold = jax.lax.top_k(imp, top_k)[0][-1]
        is_top = imp >= threshold
        self.bag_rng, sub = jax.random.split(self.bag_rng)
        n_top = jnp.sum(is_top.astype(jnp.int32))
        rest = jnp.maximum(N - n_top, 1)
        prob = other_k / rest.astype(jnp.float32)
        keep_other = (~is_top) & (jax.random.uniform(sub, (N,)) < prob)
        selected = is_top | keep_other
        multiply = (N - top_k) / other_k
        scale = jnp.where(keep_other, multiply, 0.0)
        scale = jnp.where(is_top, 1.0, scale)
        if grad.ndim == 2:
            grad = grad * scale[:, None]
            hess = hess * scale[:, None]
        else:
            grad = grad * scale
            hess = hess * scale
        cnt = jnp.sum(selected.astype(jnp.int32))
        return grad, hess, selected, cnt

    def _feature_mask(self, it: int):
        frac = float(self.config.feature_fraction)
        F = self.learner.F
        if frac >= 1.0 or F <= 1:
            if not hasattr(self, "_ones_fmask"):
                self._ones_fmask = jnp.ones((F,), dtype=bool)
            return self._ones_fmask
        k = max(int(F * frac), 1)
        self.feat_rng, sub = jax.random.split(self.feat_rng)
        perm = jax.random.permutation(sub, F)
        mask = jnp.zeros((F,), dtype=bool).at[perm[:k]].set(True)
        return mask

    def _discretize_gradients(self, grad, hess, row_sampling=False):
        """Quantized-gradient training: stochastic rounding of (g, h) onto a
        `num_grad_quant_bins`-level integer grid, returned on float carriers
        so histogram sums equal integer-sum x scale exactly (f32 holds int
        sums < 2^24 losslessly).  Mirrors GradientDiscretizer::
        DiscretizeGradients (src/treelearner/gradient_discretizer.cpp:70):
        grad_scale = max|g| / (bins/2), hess_scale = max|h| / bins (or
        max|h| for constant-hessian objectives), truncation toward zero with
        a uniform random offset away from zero.

        The TPU-native histogram already accumulates on the MXU, so the
        reference's 8/16/32-bit per-leaf accumulator selection
        (SetNumBitsInHistogramBin) is unnecessary: the win retained here is
        the regularization/accuracy semantics of quantized training."""
        cfg = self.config
        bins = float(cfg.num_grad_quant_bins)
        max_g = jnp.max(jnp.abs(grad))
        max_h = jnp.max(jnp.abs(hess))
        # the constant-hessian shortcut (every int hessian := 1) is only
        # valid when hessians are untouched by sampling: bagging zeroes
        # out-of-bag rows and GOSS re-weights, so those paths must quantize
        # hessians like any non-constant objective
        const_h = (self.objective is not None
                   and self.objective.is_constant_hessian
                   and not row_sampling)
        gs = jnp.maximum(max_g / (bins / 2.0), 1e-30)
        hs = jnp.maximum(max_h if const_h else max_h / bins, 1e-30)
        if cfg.stochastic_rounding:
            self.quant_rng, sub = jax.random.split(self.quant_rng)
            kg, kh = jax.random.split(sub)
            rg = jax.random.uniform(kg, grad.shape)
            rh = jax.random.uniform(kh, hess.shape)
        else:
            rg = rh = 0.5
        ig = jnp.trunc(grad / gs + jnp.where(grad >= 0, rg, -rg))
        ih = jnp.ones_like(hess) if const_h else jnp.trunc(hess / hs + rh)
        # INTEGER carriers + a separate (2,) scale: the histogram then
        # accumulates exact small integers, which the learner computes
        # with bfloat16 one-hot matmuls at double MXU rate — the TPU
        # analog of the reference's int16 histogram fast path
        # (feature_histogram.hpp:293-374) — and scales once per leaf
        return ig, ih, jnp.stack([gs, hs])

    def _leaf_rows(self, record, num_nodes: int):
        """Per-leaf train row lookup via device traversal of the built tree.

        Partition-record-independent (the sharded learners never replicate
        their per-shard partition arrays off the mesh), so renewal / linear
        fitting work identically for serial and distributed training.
        Returns ``rows(leaf) -> np.ndarray`` of original row ids.
        """
        nodes = self.learner.node_arrays_for_predict(record)
        leaf_idx = np.asarray(self._traverse_train(nodes))
        order = np.argsort(leaf_idx, kind="stable")
        bounds = np.searchsorted(leaf_idx[order],
                                 np.arange(num_nodes + 2))

        def rows(leaf: int) -> np.ndarray:
            return order[bounds[leaf]:bounds[leaf + 1]]

        return rows

    def _renew_quant_leaf_outputs(self, record, num_nodes: int, grad, hess):
        """Recompute leaf outputs from the TRUE (un-quantized) gradient sums
        (reference: GradientDiscretizer::RenewIntGradTreeOutput,
        gradient_discretizer.cpp:209).

        Serial records carry the physical leaf ranges, so the renewal is
        one device program: permute the true gradients into partition
        order and difference their prefix sums at the range boundaries.
        Sharded records (no partition arrays off the mesh) fall back to a
        traversal-based host loop."""
        from ..ops.split import leaf_output
        cfg = self.config
        if "indices" in record:
            return _quant_renew_device(
                record["indices"], jnp.asarray(grad), jnp.asarray(hess),
                record["leaf_start"], record["leaf_cnt"],
                record["leaf_value"],
                cfg.lambda_l1, cfg.lambda_l2, cfg.max_delta_step)
        num_leaves = num_nodes + 1
        leaf_rows = self._leaf_rows(record, num_nodes)
        g = np.asarray(grad)
        h = np.asarray(hess)
        new_values = np.asarray(record["leaf_value"]).copy()
        for leaf in range(num_leaves):
            rows = leaf_rows(leaf)
            if len(rows) == 0:
                continue
            sum_g = float(g[rows].sum())
            sum_h = float(h[rows].sum())
            new_values[leaf] = float(leaf_output(
                sum_g, sum_h + 2e-15, cfg.lambda_l1, cfg.lambda_l2,
                cfg.max_delta_step))
        return jnp.asarray(new_values)

    def _fit_linear_leaves(self, tree, record, num_nodes: int, grad, hess):
        """Fit per-leaf linear models on the raw features along each leaf's
        path (reference: LinearTreeLearner::CalculateLinear,
        linear_tree_learner.cpp:173): weighted normal equations
        coeffs = -(X^T H X + linear_lambda I)^-1 X^T g over the leaf's
        non-NaN rows, constant fallback when the system is under-determined.
        The Eigen fullPivLu solve becomes numpy lstsq."""
        cfg = self.config
        raw = self.train_data.raw_data
        num_leaves = num_nodes + 1
        nf = np.asarray(record["node_feature"])
        nl = np.asarray(record["node_left"])
        nr = np.asarray(record["node_right"])
        nc = (np.asarray(record["node_is_cat"])
              if "node_is_cat" in record else np.zeros(len(nf), bool))
        paths = [[] for _ in range(num_leaves)]
        if num_nodes > 0:
            stack = [(0, [])]
            while stack:
                node, path = stack.pop()
                feats = path if nc[node] else path + [int(nf[node])]
                for child in (int(nl[node]), int(nr[node])):
                    if child < 0:
                        paths[~child] = feats
                    else:
                        stack.append((child, feats))
        leaf_rows = self._leaf_rows(record, num_nodes)
        g = np.asarray(grad, dtype=np.float64)
        h = np.asarray(hess, dtype=np.float64)
        lam = float(cfg.linear_lambda)
        shr = self.shrinkage_rate
        tree.is_linear = True
        for leaf in range(num_leaves):
            feats = list(dict.fromkeys(paths[leaf]))
            rows = leaf_rows(leaf)
            tree.leaf_features[leaf] = []
            tree.leaf_coeff[leaf] = []
            tree.leaf_const[leaf] = float(tree.leaf_value[leaf])
            if not feats or len(rows) == 0:
                continue
            Xl = raw[np.ix_(rows, np.asarray(feats, np.intp))] \
                .astype(np.float64)
            ok = ~np.isnan(Xl).any(axis=1)
            Xl, gi, hi = Xl[ok], g[rows][ok], h[rows][ok]
            if len(Xl):
                # a constant column carries no signal but makes its
                # normal-equation row a multiple of the intercept's:
                # lstsq on the (numerically) singular system returned
                # huge mutually-cancelling coefficients that explode
                # away from the training rows.  The reference drops
                # such features from the leaf before solving
                # (linear_tree_learner.cpp CalculateLinear)
                varying = np.ptp(Xl, axis=0) > 0
                feats = [f for f, v in zip(feats, varying) if v]
                Xl = Xl[:, varying]
            d = len(feats)
            if d == 0 or len(Xl) < d + 1:
                continue
            Xa = np.concatenate([Xl, np.ones((len(Xl), 1))], axis=1)
            XTHX = (Xa * hi[:, None]).T @ Xa
            XTHX[np.arange(d), np.arange(d)] += lam
            # the reference's ridge epsilon on the whole diagonal keeps
            # a near-singular system (collinear columns survive the
            # constant-column drop) from emitting large coefficients
            diag = np.arange(d + 1)
            XTHX[diag, diag] += _LINEAR_RIDGE_EPS * (1.0 +
                                                     XTHX[diag, diag])
            XTg = Xa.T @ gi
            try:
                coeffs = -np.linalg.solve(XTHX, XTg)
            except np.linalg.LinAlgError:
                continue                    # keep the constant leaf
            if not np.all(np.isfinite(coeffs)):
                continue                    # keep the constant leaf
            keep = np.abs(coeffs[:d]) > 1e-35   # reference: kZeroThreshold
            tree.leaf_features[leaf] = [feats[i] for i in range(d)
                                        if keep[i]]
            tree.leaf_coeff[leaf] = [float(coeffs[i] * shr)
                                     for i in range(d) if keep[i]]
            tree.leaf_const[leaf] = float(coeffs[d] * shr)

    def _set_leafwise_linear(self, tree, record, num_nodes: int) -> None:
        """linear_tree_mode=leafwise_gain: per-leaf linear models come out
        of the device record — each leaf's (const, coeff, feature) is its
        OWN best whole-leaf single-feature fit, read off the leaf's own
        split search (models/learner.py LM_LIN_* rows; ops/split.py:
        find_best_split_linear self_* fields), so there is NO extra data
        pass and NO host solve.  ``leaf_lin_feat`` is already an ORIGINAL
        feature id; shrinkage scales (const, coeff) exactly like the
        refit path, and ``leaf_value`` stays the constant fallback for
        NaN rows."""
        tree.is_linear = True
        num_leaves = num_nodes + 1
        shr = self.shrinkage_rate
        const = np.asarray(record["leaf_lin_const"],
                           np.float64)[:num_leaves]
        coeff = np.asarray(record["leaf_lin_coeff"],
                           np.float64)[:num_leaves]
        feat = np.asarray(record["leaf_lin_feat"])[:num_leaves]
        for leaf in range(num_leaves):
            c = float(coeff[leaf])
            if abs(c) <= 1e-35:             # reference: kZeroThreshold
                tree.leaf_features[leaf] = []
                tree.leaf_coeff[leaf] = []
                tree.leaf_const[leaf] = float(tree.leaf_value[leaf])
            else:
                tree.leaf_features[leaf] = [int(feat[leaf])]
                tree.leaf_coeff[leaf] = [c * shr]
                tree.leaf_const[leaf] = float(const[leaf]) * shr

    def _linear_tree_deltas(self, nodes, tree, init_score_adjust=0.0):
        """Per-row (train, [valid...]) deltas through the linear leaves;
        recomputable at any time from the host tree, so nothing per-row needs
        to be retained for rollback (reference: Tree::AddPredictionToScore
        linear arm)."""
        leaf_train = np.asarray(self._traverse_train(nodes))
        delta = tree._linear_output(self.train_data.raw_data, leaf_train) \
            - init_score_adjust
        out = [jnp.asarray(delta.astype(np.float32))]
        for vd, metrics, binned in self.valid_sets:
            leaf_v = np.asarray(predict_leaf_binned(binned, nodes))
            dv = tree._linear_output(vd.raw_data, leaf_v) - init_score_adjust
            out.append(jnp.asarray(dv.astype(np.float32)))
        return out

    def _apply_score_update_linear(self, nodes, tree, k: int) -> None:
        deltas = self._linear_tree_deltas(nodes, tree)
        if self.num_tree_per_iteration == 1:
            self.scores = self.scores + deltas[0]
        else:
            self.scores = self.scores.at[:, k].add(deltas[0])
        for vi in range(len(self.valid_sets)):
            dv = deltas[vi + 1]
            if self.num_tree_per_iteration == 1:
                self.valid_scores[vi] = self.valid_scores[vi] + dv
            else:
                self.valid_scores[vi] = self.valid_scores[vi].at[:, k].add(dv)

    # ------------------------------------------------------------------
    def _assert_trainable(self) -> None:
        if getattr(self, "_serving_only", False):
            # refit(inplace=True) rewrote the leaf values: the training
            # scores (and any physical fused state) no longer match the
            # model, so another update would silently train on stale
            # state (the PR 6 known hazard — now a loud error)
            raise LightGBMError(
                "cannot update() a serving-only booster: "
                "refit(inplace=True) rewrote its leaf values, so the "
                "training-side scores no longer match the model; "
                "continue training from a fresh booster "
                "(train(init_model=...)) instead")

    def train_one_iter(self, grad=None, hess=None) -> bool:
        """One boosting iteration (reference: gbdt.cpp TrainOneIter:338).

        Returns True when training should stop (no further splits possible).
        """
        self._assert_trainable()
        if grad is None and hess is None and self._fused is not None:
            return self._train_one_iter_fused()
        # the eager path appends trees directly: any lagged fused records
        # must land first so model order matches training order
        self._flush_pending()
        # eager builds read the learner's pristine master buffer; rebuild
        # it if the fused carrier adopted it (mixed fused/eager training)
        self._ensure_part0()
        if grad is None or hess is None:
            with obs.span("train.gradients"):
                grad, hess = self._compute_gradients()
        else:
            grad = jnp.asarray(grad, dtype=jnp.float32)
            hess = jnp.asarray(hess, dtype=jnp.float32)
            if self.num_tree_per_iteration > 1 and grad.ndim == 1:
                grad = grad.reshape(self.num_tree_per_iteration, self.num_data).T
                hess = hess.reshape(self.num_tree_per_iteration, self.num_data).T

        if faultinject.is_active():
            grad, hess = faultinject.maybe_corrupt_gradients(
                self.iter, grad, hess)
        if self._nf_guard is not None:
            # one device-side reduction over (grad, hess, scores) BEFORE
            # sampling (bagging's zeroing could mask a poisoned row); a
            # skipped iteration builds no tree from the bad batch
            grad, hess, skip = self._nf_guard.filter(
                self.iter, grad, hess, self.scores)
            if skip:
                self.iter += 1
                return False

        use_sharded = self.sharded_builder is not None
        bag_mask = bag_cnt = None
        # sampling is a full-length row predicate + gradient masking, so it
        # composes with the sharded learners exactly as with the serial one
        # (reference: bagging.hpp:13 / goss.hpp:18 compose with every
        # parallel learner); only the per-shard in-bag counts differ
        if self.goss:
            grad, hess, bag_mask, bag_cnt = self._goss_sample(
                grad, hess, self.iter)
        else:
            bag_mask, bag_cnt = self._bagging_mask(self.iter)
            if bag_mask is not None:
                m = bag_mask if grad.ndim == 1 else bag_mask[:, None]
                grad = jnp.where(m, grad, 0.0)
                hess = jnp.where(m, hess, 0.0)
        self._bag_mask_host = (np.asarray(bag_mask)
                               if bag_mask is not None else None)

        feature_mask = self._feature_mask(self.iter)
        K = self.num_tree_per_iteration
        should_stop = True
        for k in range(K):
            gk = grad[:, k] if K > 1 else grad
            hk = hess[:, k] if K > 1 else hess
            gk_true, hk_true = gk, hk
            qscale = None
            if self.use_quant:
                gk, hk, qscale = self._discretize_gradients(
                    gk, hk,
                    row_sampling=self.goss or (bag_mask is not None))
                if use_sharded:
                    # the sharded builders take pre-scaled carriers
                    gk = gk * qscale[0]
                    hk = hk * qscale[1]
                    qscale = None
            tree_seed = self.iter * K + k + 1
            with obs.span("train.tree_build"):
                if use_sharded:
                    record = self.sharded_builder.build_tree(
                        gk, hk, feature_mask, seed=tree_seed,
                        feat_used=self._cegb_feat_used,
                        bag_mask=self._bag_mask_host,
                        lazy_aux=self._cegb_lazy_aux)
                    if isinstance(record, tuple):
                        record, self._cegb_lazy_aux = record
                else:
                    record = self.learner.build_tree(
                        gk, hk, bag_cnt, feature_mask, seed=tree_seed,
                        feat_used=self._cegb_feat_used,
                        lazy_aux=self._cegb_lazy_aux,
                        hist_scale=qscale)
            if self.learner.has_cegb:
                # coupled AND lazy penalties persist for the model
                # lifetime (the sharded builder already returned its
                # mesh-layout lazy aux above)
                self._cegb_feat_used = record["feat_used"]
                if (not use_sharded
                        and self.learner.cegb_lazy is not None):
                    self._cegb_lazy_aux = \
                        self.learner.lazy_aux_to_original_order(record)
            num_nodes = int(record["s"])
            if num_nodes > 0:
                should_stop = False
            leaf_value_dev = record["leaf_value"]
            if (self.use_quant and self.config.quant_train_renew_leaf
                    and num_nodes > 0):
                leaf_value_dev = self._renew_quant_leaf_outputs(
                    record, num_nodes, gk_true, hk_true)
            if (self.objective is not None
                    and self.objective.is_renew_tree_output and num_nodes > 0):
                leaf_value_dev = self._renew_tree_output(record, num_nodes, k)
            # device score update via traversal
            nodes = self.learner.node_arrays_for_predict(record)
            delta_leaf = leaf_value_dev * self.shrinkage_rate
            use_linear = self.config.linear_tree
            if not use_linear:
                with obs.span("train.score_update"):
                    self._apply_score_update(nodes, delta_leaf, k)
            # host tree for the model
            host_record = {key: np.asarray(val) for key, val in record.items()
                           if key.startswith(("node_", "leaf_"))}
            host_record["leaf_value"] = np.asarray(leaf_value_dev)
            if DEBUG_CHECKS and "leaf_start" in host_record \
                    and not use_sharded:
                debug_validate_record(host_record, num_nodes,
                                      self.num_data, self.learner.row0)
            tree = tree_from_device_record(
                host_record, num_nodes, self.train_data.bin_mappers,
                None, shrinkage=self.shrinkage_rate)
            if use_linear:
                if "leaf_lin_const" in record:
                    # leafwise_gain: the models came out of the winning
                    # split candidates — no host refit pass
                    self._set_leafwise_linear(tree, record, num_nodes)
                else:
                    # fit on the TRUE gradients, not the quantized
                    # carriers
                    self._fit_linear_leaves(tree, record, num_nodes,
                                            gk_true, hk_true)
                self._apply_score_update_linear(nodes, tree, k)
            # fold the boost-from-average init score into the first
            # iteration's trees (reference: gbdt.cpp:408-424 AddBias /
            # AsConstantTree) so the saved model is self-contained
            if (len(self.models) < K and abs(self.init_scores[k]) > K_EPSILON):
                if num_nodes > 0:
                    tree.leaf_value = tree.leaf_value + self.init_scores[k]
                    tree.internal_value = tree.internal_value + self.init_scores[k]
                    if tree.is_linear:
                        tree.leaf_const = tree.leaf_const + self.init_scores[k]
                else:
                    tree.leaf_value = np.asarray([self.init_scores[k]])
                    if tree.is_linear:
                        tree.leaf_const = np.asarray([self.init_scores[k]])
            self._health_record_tree(host_record, num_nodes)
            self._telemetry_chunk_waste(host_record, num_nodes)
            self._telemetry_pruned_splits(record, num_nodes)
            self._telemetry_hist_sync(num_nodes)
            self.models.append(tree)
            self.device_trees.append({
                "nodes": nodes, "leaf_value": delta_leaf,
                "has_cat_split": bool(
                    np.any(host_record["node_is_cat"][:num_nodes]))})
        self.iter += 1
        if should_stop:
            log.warning("Stopped training because there are no more leaves "
                        "that meet the split requirements")
        return should_stop

    def _apply_score_update(self, nodes, delta_leaf, k: int) -> None:
        leaf_train = self._traverse_train(nodes)
        delta = jnp.take(delta_leaf, leaf_train)
        if self.num_tree_per_iteration == 1:
            self.scores = self.scores + delta
        else:
            self.scores = self.scores.at[:, k].add(delta)
        for vi, (vd, metrics, binned) in enumerate(self.valid_sets):
            leaf_v = predict_leaf_binned(binned, nodes)
            dv = jnp.take(delta_leaf, leaf_v)
            if self.num_tree_per_iteration == 1:
                self.valid_scores[vi] = self.valid_scores[vi] + dv
            else:
                self.valid_scores[vi] = self.valid_scores[vi].at[:, k].add(dv)

    def _renew_tree_output(self, record, num_nodes: int, k: int):
        """L1-family leaf renewal (reference: RegressionL1loss::RenewTreeOutput;
        applied through SerialTreeLearner::RenewTreeOutput)."""
        alpha = self.objective.renew_leaf_alpha()
        weights = self.objective.renew_weights()
        num_leaves = num_nodes + 1
        leaf_rows = self._leaf_rows(record, num_nodes)
        label = np.asarray(self.objective.label)
        score = np.asarray(self.scores if self.num_tree_per_iteration == 1
                           else self.scores[:, k])
        w = np.asarray(weights) if weights is not None else None
        new_values = np.asarray(record["leaf_value"]).copy()
        from .objective import _weighted_percentile_host
        for leaf in range(num_leaves):
            rows = leaf_rows(leaf)
            if len(rows) == 0:
                continue
            bm = getattr(self, "_bag_mask_host", None)
            if bm is not None:
                rows = rows[bm[rows]]
                if len(rows) == 0:
                    continue
            resid = label[rows] - score[rows]
            new_values[leaf] = _weighted_percentile_host(
                resid, None if w is None else w[rows], alpha)
        return jnp.asarray(new_values, dtype=jnp.float32)

    # ------------------------------------------------------------------
    def eval_metrics(self) -> Dict[str, List[Tuple[str, float, bool]]]:
        """Evaluate all metrics; returns {dataset_name: [(metric, value, is_max_better)]}."""
        with obs.span("train.eval"):
            return self._eval_metrics_impl()

    def _eval_metrics_impl(self):
        out: Dict[str, List[Tuple[str, float, bool]]] = {}
        if self.train_metrics and self.config.is_provide_training_metric:
            res = []
            for m in self.train_metrics:
                for name, val in m.eval(self.scores, self.objective):
                    res.append((name, val, m.is_max_better))
            out["training"] = res
        for vi, (vd, metrics, _) in enumerate(self.valid_sets):
            res = []
            for m in metrics:
                for name, val in m.eval(self.valid_scores[vi], self.objective):
                    res.append((name, val, m.is_max_better))
            out[f"valid_{vi}"] = res
        return out

    def eval_valid(self, vi: int = 0):
        if vi >= len(self.valid_sets):
            return []
        _, metrics, _ = self.valid_sets[vi]
        res = []
        for m in metrics:
            for name, val in m.eval(self.valid_scores[vi], self.objective):
                res.append((name, val, m.is_max_better))
        return res

    def eval_train(self):
        res = []
        for m in self.train_metrics:
            for name, val in m.eval(self.scores, self.objective):
                res.append((name, val, m.is_max_better))
        return res

    # ------------------------------------------------------------------
    def num_trees(self) -> int:
        self._flush_pending()
        return len(self.models)

    @property
    def current_iteration(self) -> int:
        return self.iter

    def _cat_sentinel_ok(self) -> bool:
        """Whether the categorical OOV-sentinel device-predict scheme is
        sound for this dataset: every categorical feature must be alone
        in its group (EFB bundling folds bins so an out-of-range sentinel
        can't ride through) and leave headroom for one extra bin code in
        the binned dtype."""
        td = self.train_data
        if td is None or not getattr(td, "groups", None):
            return False
        from ..ops.binning import BIN_CATEGORICAL
        u8 = td._bin_dtype() == np.uint8
        for grp in td.groups:
            for f in grp.feature_indices:
                bm = td.bin_mappers[f]
                if bm.bin_type == BIN_CATEGORICAL:
                    if len(grp.feature_indices) > 1:
                        return False
                    if u8 and bm.num_bin >= 256:
                        return False
        return True

    def _predict_raw_device(self, data: np.ndarray, start_iteration: int,
                            end_iter: int):
        """Batch prediction on device via the serving engine
        (models/serving.py): rows are binned with the TRAINING mappers
        (exact for in-session trees — thresholds are bin uppers), padded
        to a power-of-two bucket, and traverse the packed forest in one
        jitted vmap — the TPU replacement for the reference's OpenMP
        batch predictor (predictor.hpp:30).  ``start``/``end`` slicing
        is a tree mask, so repeated serving calls never re-stack or
        re-trace.  Piece-wise linear forests take this path too (the
        pack carries coefficient planes and the engine applies them to
        the raw rows).  Returns None when this model can't take the
        device path (loaded trees, no train data)."""
        return self.serving.raw_insession(np.asarray(data),
                                          start_iteration, end_iter)

    def _predict_raw_device_loaded(self, data: np.ndarray,
                                   start_iteration: int, end_iter: int,
                                   leaves_only: bool = False):
        """Device batch prediction for LOADED models (real thresholds, no
        bin mappers) via the serving engine: raw values convert to
        per-feature threshold-index space with exact float64
        searchsorted on the host, and the trees traverse on device in
        integer space (ops/predict.py predict_leaf_thridx) — the device
        analog of the reference's OpenMP batch predictor
        (predictor.hpp:30) for model_file boosters.  Returns None for
        categorical/linear trees."""
        if leaves_only:
            return self.serving.leaves_loaded(np.asarray(data),
                                              start_iteration, end_iter)
        return self.serving.raw_loaded(np.asarray(data),
                                       start_iteration, end_iter)

    def predict_raw(self, data: np.ndarray, start_iteration: int = 0,
                    num_iteration: int = -1,
                    pred_early_stop: bool = False,
                    pred_early_stop_freq: int = 10,
                    pred_early_stop_margin: float = 10.0) -> np.ndarray:
        """Raw-score batch prediction on host feature values
        (reference: gbdt_prediction.cpp PredictRaw).

        With ``pred_early_stop``, rows whose margin already exceeds
        ``pred_early_stop_margin`` stop accumulating trees every
        ``pred_early_stop_freq`` iterations (reference:
        prediction_early_stop.cpp CreatePredictionEarlyStopInstance —
        |score| for binary, top1-top2 gap for multiclass)."""
        self._flush_pending()
        data = np.asarray(data, dtype=np.float64)
        n = data.shape[0]
        K = self.num_tree_per_iteration
        # init scores are folded into the first iteration's trees (AddBias),
        # so raw prediction is a plain sum over trees
        out = np.zeros((n, K), dtype=np.float64)
        total_iters = len(self.models) // K
        end_iter = total_iters if num_iteration <= 0 else min(
            total_iters, start_iteration + num_iteration)
        use_es = (pred_early_stop and not self.average_output
                  and (K > 1 or (self.objective is not None
                                 and self.objective.name in
                                 ("binary", "cross_entropy",
                                  "cross_entropy_lambda"))))
        if not use_es:
            dev = self._predict_raw_device(data, start_iteration, end_iter)
            if dev is None:
                dev = self._predict_raw_device_loaded(
                    data, start_iteration, end_iter)
            if dev is not None:
                if self.average_output and end_iter > start_iteration:
                    dev /= (end_iter - start_iteration)
                return dev[:, 0] if K == 1 else dev
        else:
            # early stopping routes through the same engine: blocks of
            # ``freq`` iterations accumulate on device (tree-masked) and
            # settled rows leave the bucket between blocks
            dev = self.serving.raw_early_stop(
                data, start_iteration, end_iter, pred_early_stop_freq,
                pred_early_stop_margin)
            if dev is not None:
                return dev[:, 0] if K == 1 else dev
        active = np.ones(n, dtype=bool) if use_es else None
        any_stopped = False
        for it in range(start_iteration, end_iter):
            if use_es and (it - start_iteration) > 0 and \
                    (it - start_iteration) % pred_early_stop_freq == 0:
                if K == 1:
                    margin = np.abs(out[:, 0])
                else:
                    part = np.partition(out, K - 2, axis=1)
                    margin = part[:, K - 1] - part[:, K - 2]
                active &= margin < pred_early_stop_margin
                any_stopped = not active.all()
                if not active.any():
                    break
            # avoid copying the full matrix while every row is still active
            if use_es and any_stopped:
                rows = np.nonzero(active)[0]
                sub = data[rows]
            else:
                rows = slice(None)
                sub = data
            for k in range(K):
                out[rows, k] += self.models[it * K + k].predict(sub)
        if self.average_output and end_iter > start_iteration:
            out /= (end_iter - start_iteration)
        return out[:, 0] if K == 1 else out

    def predict(self, data: np.ndarray, raw_score: bool = False, **kw) -> np.ndarray:
        raw = self.predict_raw(data, **kw)
        if raw_score or self.objective is None:
            return raw
        conv = self.objective.convert_output(jnp.asarray(raw))
        return np.asarray(conv)

    def predict_leaf_index(self, data: np.ndarray, start_iteration: int = 0,
                           num_iteration: int = -1) -> np.ndarray:
        """Leaf index per (row, tree) over iterations [start, start+num)
        (reference: predictor.hpp predict_leaf_index + the c_api's
        start_iteration/num_iteration slicing)."""
        self._flush_pending()
        data = np.asarray(data, dtype=np.float64)
        K = self.num_tree_per_iteration
        total_iters = len(self.models) // max(K, 1)
        end_iter = total_iters if num_iteration <= 0 else min(
            total_iters, start_iteration + num_iteration)
        # a start past the model end yields an empty (n, 0) result like
        # the other pred kinds, not a negative-dimension crash
        end_iter = max(end_iter, start_iteration)
        dev = self.serving.leaves_insession(data, start_iteration, end_iter)
        if dev is None:
            dev = self._predict_raw_device_loaded(
                data, start_iteration, end_iter, leaves_only=True)
        if dev is not None:
            return dev
        out = np.zeros((data.shape[0], (end_iter - start_iteration) * K),
                       dtype=np.int32)
        for t in range(start_iteration * K, end_iter * K):
            out[:, t - start_iteration * K] = \
                self.models[t].predict_leaf(data)
        return out

    def predict_contrib(self, data: np.ndarray, start_iteration: int = 0,
                        num_iteration: int = -1) -> np.ndarray:
        """SHAP feature contributions (reference: c_api predict with
        predict_contrib=true): the serving engine's vectorized device
        TreeSHAP (ops/shap.py) when the model is device-eligible, else
        the exact host recursion (models/shap.py, the oracle)."""
        from .shap import predict_contrib as host_contrib
        self._flush_pending()
        data = np.asarray(data, dtype=np.float64)
        K = self.num_tree_per_iteration
        total_iters = len(self.models) // max(K, 1)
        # 0 means "all iterations", matching predict_raw /
        # predict_leaf_index (the reference wrapper's num_iteration<=0)
        if num_iteration <= 0:
            num_iteration = -1
        end_iter = total_iters if num_iteration < 0 else min(
            total_iters, start_iteration + num_iteration)
        dev = self.serving.contrib(data, start_iteration, end_iter)
        if dev is not None:
            n = data.shape[0]
            nf = self.max_feature_idx + 1
            if K == 1:
                return dev[:, 0, :]
            return dev.reshape(n, K * (nf + 1))
        return host_contrib(self, data, start_iteration, num_iteration)

    def apply_refit_leaf_values(self, new_values) -> None:
        """Commit refit leaf values IN PLACE (Booster.refit(inplace=True)
        and the continual-training runtime's per-tick refit): rewrite
        every host tree's leaf values, mirror them into the device-tree
        delta arrays, and bump the serving mutation counter EAGERLY —
        like update/rollback already do — so a pack warmed before the
        refit can never serve pre-refit values.  The warm in-session
        pack takes the leaf-only fast path (serving.refit_leaf_values):
        its stacked node arrays survive and only the small delta rows
        re-transfer, so a refit tick never re-packs or re-traces.

        ``new_values`` holds one array per tree, already shrunk and
        (for the first iteration's trees) already carrying the
        boost-from-average fold — the refit accumulation is
        self-contained, so ``init_scores`` zeroes like continue_from.

        In-place refit is a SERVING mutation: the training-side scores
        and physical fused state are no longer consistent with the
        model, so continued ``train_one_iter`` after it is unsupported
        (train via a fresh booster / init_model instead)."""
        self._flush_pending()
        if len(new_values) != len(self.models):
            raise ValueError(
                f"refit produced {len(new_values)} leaf arrays for "
                f"{len(self.models)} trees")
        for ti, vals in enumerate(new_values):
            vals = np.asarray(vals, dtype=np.float64)
            tree = self.models[ti]
            tree.leaf_value = vals.copy()
            if ti < len(self.device_trees):
                dt = self.device_trees[ti]
                if dt is not None:
                    slot = np.zeros(dt["leaf_value"].shape, np.float32)
                    n = min(len(vals), slot.shape[0])
                    slot[:n] = vals[:n]
                    dt["leaf_value"] = jnp.asarray(slot)
        self.init_scores = [0.0] * self.num_tree_per_iteration
        # training-side state is stale from here on (see docstring);
        # train_one_iter refuses serving-only boosters loudly.  The
        # bins must survive as the retired carrier though — under
        # single-copy residency they may be the dataset's only binned
        # copy (pickle / save_binary / a second booster recover it)
        if self._phys is not None:
            pb, ghi = self._phys
            self._phys = None
            self._phys_carrier = (pb, ghi[2])
        self._serving_only = True
        self._model_version += 1
        self.serving.refit_leaf_values(
            [np.asarray(v, np.float64) for v in new_values])

    def rollback_one_iter(self) -> None:
        """reference: gbdt.cpp RollbackOneIter:443."""
        self._flush_pending()
        self._empty_run = 0
        if self.iter <= 0:
            return
        K = self.num_tree_per_iteration
        if any(self.device_trees[-k] is None for k in range(1, K + 1)):
            log.warning("cannot roll back past the init_model boundary "
                        "(loaded trees have no device arrays)")
            return
        self._model_version += 1
        self.serving.invalidate()
        for k in range(K):
            dt = self.device_trees.pop()
            tree = self.models.pop()
            nodes, delta_leaf = dt["nodes"], dt["leaf_value"]
            kk = K - 1 - k
            if tree.is_linear:
                # recompute the per-row deltas from the host tree; undo the
                # init-score fold if this was a first-iteration tree
                t_idx = len(self.models)
                adj = (self.init_scores[kk]
                       if t_idx < K and abs(self.init_scores[kk]) > K_EPSILON
                       else 0.0)
                deltas = self._linear_tree_deltas(nodes, tree,
                                                  init_score_adjust=adj)
                delta = deltas[0]
                valid_dvs = deltas[1:]
            else:
                leaf_train = self._traverse_train(nodes)
                delta = jnp.take(delta_leaf, leaf_train)
                valid_dvs = None
            if K == 1:
                self.scores = self.scores - delta
            else:
                self.scores = self.scores.at[:, kk].add(-delta)
            for vi, (vd, metrics, binned) in enumerate(self.valid_sets):
                if valid_dvs is not None:
                    dv = valid_dvs[vi]
                else:
                    leaf_v = predict_leaf_binned(binned, nodes)
                    dv = jnp.take(delta_leaf, leaf_v)
                if K == 1:
                    self.valid_scores[vi] = self.valid_scores[vi] - dv
                else:
                    self.valid_scores[vi] = self.valid_scores[vi].at[:, kk].add(-dv)
        self.iter -= 1


class DART(GBDT):
    """DART boosting (reference: src/boosting/dart.hpp:23)."""

    def __init__(self, config: Config, train_data, objective):
        if config.linear_tree:
            log.fatal("Cannot use linear tree with DART boosting "
                      "(reference: config.cpp linear_tree checks)")
        super().__init__(config, train_data, objective)
        # DART's drop/normalize bookkeeping needs each tree materialized
        # IMMEDIATELY after its iteration; the fused path's lag breaks that
        self._fused = None
        self.drop_rng = np.random.RandomState(config.drop_seed)
        self.tree_weights: List[float] = []  # per SESSION iteration (dart.hpp:196)
        self.sum_weight = 0.0
        # continuation boundary: trees below this iteration came from an
        # init model and are never dropped (dart.hpp num_init_iteration_)
        self.init_iters = 0

    def train_one_iter(self, grad=None, hess=None) -> bool:
        # select trees to drop (reference: dart.hpp DroppingTrees:97 —
        # per-tree Bernoulli draws; non-uniform mode weights each tree by
        # its stored weight relative to the average, capped by max_drop)
        # (serving-only guard BEFORE the drop bookkeeping mutates scores)
        self._assert_trainable()
        self._flush_pending()
        cfg = self.config
        K = self.num_tree_per_iteration
        # only the session's own iterations are droppable; init-model trees
        # sit below the boundary (dart.hpp:108-122, num_init_iteration_)
        n_droppable = len(self.models) // K - self.init_iters
        base_lr = float(cfg.learning_rate)
        drop_iters: List[int] = []
        if n_droppable > 0 and self.drop_rng.rand() >= cfg.skip_drop:
            drop_rate = float(cfg.drop_rate)
            max_drop = int(cfg.max_drop)
            if cfg.uniform_drop:
                if max_drop > 0:
                    drop_rate = min(drop_rate, max_drop / n_droppable)
                for i in range(n_droppable):
                    if self.drop_rng.rand() < drop_rate:
                        drop_iters.append(self.init_iters + i)
                        if max_drop > 0 and len(drop_iters) >= max_drop:
                            break
            else:
                inv_avg = (len(self.tree_weights) / self.sum_weight
                           if self.sum_weight > 0 else 0.0)
                if max_drop > 0 and self.sum_weight > 0:
                    drop_rate = min(drop_rate,
                                    max_drop * inv_avg / self.sum_weight)
                for i in range(n_droppable):
                    p = drop_rate * self.tree_weights[i] * inv_avg
                    if self.drop_rng.rand() < p:
                        drop_iters.append(self.init_iters + i)
                        if max_drop > 0 and len(drop_iters) >= max_drop:
                            break
        k_drop = len(drop_iters)
        # remove dropped trees' contributions from the TRAIN scores only
        # (validation scores are corrected in the normalize step, exactly
        # like the reference's Shrinkage(-1)+AddScore / Normalize dance)
        for it in drop_iters:
            for k in range(K):
                self._add_tree_to_scores(it * K + k, -1.0, valid=False)
        # the NEW tree trains at reduced shrinkage so its score update and
        # stored values agree from the start (dart.hpp:131-146)
        if cfg.xgboost_dart_mode:
            self.shrinkage_rate = (base_lr if k_drop == 0
                                   else base_lr / (base_lr + k_drop))
        else:
            self.shrinkage_rate = base_lr / (1.0 + k_drop)
        stop = super().train_one_iter(grad, hess)
        # normalize dropped trees (reference: dart.hpp Normalize:158):
        # each dropped tree's final weight is old * k/(k+1) (non-xgboost)
        # or old * k/(k+lr) (xgboost mode); train scores lost the full
        # tree, valid scores lost nothing yet
        if k_drop > 0:
            kf = float(k_drop)
            final = (kf / (kf + 1.0) if not cfg.xgboost_dart_mode
                     else kf / (kf + base_lr))
            for it in drop_iters:
                for k in range(K):
                    t_idx = it * K + k
                    self._add_tree_to_scores(t_idx, final, valid=False)
                    self._add_tree_to_scores(t_idx, final - 1.0, train=False)
                    self._scale_tree(t_idx, final)
                if not cfg.uniform_drop:
                    self.tree_weights[it - self.init_iters] *= final
            if not cfg.uniform_drop:
                self.sum_weight = sum(self.tree_weights)
        if not cfg.uniform_drop:
            self.tree_weights.append(self.shrinkage_rate)
            self.sum_weight += self.shrinkage_rate
        return stop

    def rollback_one_iter(self) -> None:
        # keep the non-uniform drop bookkeeping aligned: the rolled-back
        # iteration's weight must leave tree_weights/sum_weight or every
        # later selection and normalize step reads a shifted entry
        n_before = len(self.models)
        super().rollback_one_iter()
        if (len(self.models) < n_before and not self.config.uniform_drop
                and self.tree_weights):
            self.sum_weight -= self.tree_weights.pop()

    def _scale_tree(self, t_idx: int, factor: float) -> None:
        self.models[t_idx].leaf_value *= factor
        self.models[t_idx].internal_value *= factor
        dt = self.device_trees[t_idx]
        dt["leaf_value"] = dt["leaf_value"] * factor
        self._model_version += 1
        self.serving.invalidate()

    def _add_tree_to_scores(self, t_idx: int, factor: float,
                            train: bool = True, valid: bool = True) -> None:
        dt = self.device_trees[t_idx]
        K = self.num_tree_per_iteration
        k = t_idx % K
        if train:
            leaf_train = self._traverse_train(dt["nodes"])
            delta = jnp.take(dt["leaf_value"], leaf_train) * factor
            if K == 1:
                self.scores = self.scores + delta
            else:
                self.scores = self.scores.at[:, k].add(delta)
        if not valid:
            return
        for vi, (vd, metrics, binned) in enumerate(self.valid_sets):
            leaf_v = predict_leaf_binned(binned, dt["nodes"])
            dv = jnp.take(dt["leaf_value"], leaf_v) * factor
            if K == 1:
                self.valid_scores[vi] = self.valid_scores[vi] + dv
            else:
                self.valid_scores[vi] = self.valid_scores[vi].at[:, k].add(dv)


class RF(GBDT):
    """Random forest mode (reference: src/boosting/rf.hpp:25)."""

    def __init__(self, config: Config, train_data, objective):
        if config.bagging_freq <= 0 or config.bagging_fraction >= 1.0:
            if config.feature_fraction >= 1.0:
                log.fatal("Random forest mode requires bagging "
                          "(bagging_freq > 0 and bagging_fraction < 1) or "
                          "feature_fraction < 1")
        super().__init__(config, train_data, objective)
        # the fused fast path captures GBDT gradient/shrinkage semantics at
        # trace time; RF overrides both (fixed-score gradients, shrinkage 1)
        self._fused = None
        self.average_output = True
        self.shrinkage_rate = 1.0
        # gradients are always taken at the init score
        self._base_grad = None

    def _compute_gradients(self):
        if self._base_grad is None:
            K = self.num_tree_per_iteration
            shape = ((self.num_data,) if K == 1 else (self.num_data, K))
            base = jnp.zeros(shape, dtype=jnp.float32)
            for k in range(K):
                if abs(self.init_scores[k]) > K_EPSILON:
                    if K == 1:
                        base = base + self.init_scores[k]
                    else:
                        base = base.at[:, k].add(self.init_scores[k])
            self._base_grad = self.objective.get_gradients(base)
        return self._base_grad

    def _apply_score_update(self, nodes, delta_leaf, k: int) -> None:
        # scores store the running SUM; metrics divide by iteration count via
        # average_output handling in eval (approximated by scaling on read)
        super()._apply_score_update(nodes, delta_leaf, k)


def create_boosting(config: Config, train_data, objective) -> GBDT:
    """reference: Boosting::CreateBoosting (include/LightGBM/boosting.h:314)."""
    b = config.boosting
    if b == "gbdt":
        return GBDT(config, train_data, objective)
    if b == "dart":
        return DART(config, train_data, objective)
    if b == "rf":
        return RF(config, train_data, objective)
    log.fatal("Unknown boosting type %s", b)
