"""Leaf-wise histogram tree learner, fully on device.

TPU-native re-design of the reference's serial learner
(src/treelearner/serial_tree_learner.cpp:179-239) following the structure of
the CUDA single-GPU learner (src/treelearner/cuda/cuda_single_gpu_tree_learner.cpp:155-293):
the whole per-tree loop — histogram build, histogram subtraction, best-split
search, leaf partition, tree-structure update — runs inside one jitted
``lax.while_loop``; no per-split host round-trips.

Key TPU adaptations vs. the CUDA design:
  * Rows are **physically partitioned by leaf**: the binned matrix, the
    grad/hess pair and the original row ids are reordered together on every
    split, so each leaf occupies one contiguous row range.  Histograms then
    read straight HBM slices — the random-index gathers that a literal port
    of the CUDA learner (leaf index lists + gather) would need are absent,
    because TPU gathers are latency-bound while contiguous DMA runs at full
    HBM bandwidth.  This mirrors the effect of CUDADataPartition's
    SplitInnerKernel (cuda_data_partition.cu:907) which also moves payload.
  * Histograms are MXU one-hot matmuls over the leaf slice (ops/histogram.py:
    Pallas kernel on TPU, chunked einsum elsewhere), not shared-memory
    atomics.
  * The leaf partition is a single sequential pass over fixed-size chunks
    with a running (left, right) offset carry: lefts are packed forward from
    the range start, rights backward from the range end (stability across
    chunks is not required — histogram sums and future partitions are
    order-invariant), then the scratch range is copied back.
  * Variable leaf sizes inside the static-shape jit are handled by fixed-size
    row chunks with a *dynamic* trip count (``lax.fori_loop``).
  * The smaller child's histogram is computed, the larger one obtained by
    subtraction from the parent (reference: serial_tree_learner.cpp:334-374,
    FeatureHistogram::Subtract), with per-leaf histogram slots in HBM
    replacing the reference's LRU HistogramPool.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from .. import obs
from ..dataset import BinnedDataset
from ..obs import scopes
from ..ops import split as split_ops
from ..ops.chunkpolicy import ChunkPolicy
from ..ops.histogram import leaf_hist_slice
from ..ops.histogram_pallas import leaf_hist_pallas
from ..ops.partition import split_decision
from ..utils import log
from . import plan as plan_mod

NEG_INF = float("-inf")

# ---------------------------------------------------------------------------
# Packed while-loop state: per-leaf scalars live as rows of one (NLF, L) f32
# matrix and per-node scalars as rows of one (NND, nodes) f32 matrix (ints
# bitcast into the f32 container).  A split then updates TWO columns of each
# matrix instead of ~45 separate arrays — on TPU the per-op overhead of the
# many tiny dynamic-updates dominated the whole tree build.
# ---------------------------------------------------------------------------
(LM_START, LM_CNT, LM_CNT_G, LM_SUM_G, LM_SUM_H, LM_DEPTH, LM_CMIN, LM_CMAX,
 LM_VALUE, LM_PARENT, LM_PSIDE, LM_BGAIN, LM_BFEAT, LM_BTHR, LM_BDL,
 LM_BLCNT, LM_BRCNT, LM_BLSG, LM_BLSH, LM_BRSG, LM_BRSH, LM_BLOUT,
 LM_BROUT, LM_BISCAT, LM_FORCED) = range(25)
NLF = 25

# Piece-wise-linear leafwise-gain rows (linear_tree_mode=leafwise_gain
# only): the leaf's OWN fitted linear model — const + coeff over the
# raw value of LM_LIN_FEAT (an ORIGINAL feature id), the best
# whole-leaf single-feature fit read off the leaf's own split search
# (ops/split.py:find_best_split_linear self_* fields).  Constant mode
# keeps the (NLF, L+1) leafmat — self._nlf gates the packing at Python
# level so constant-gain bodies lower bit-identically to the
# pre-linear build (jaxlint tier-B `linear.gain` pins this).
(LM_LIN_CONST, LM_LIN_COEF, LM_LIN_FEAT) = range(NLF, NLF + 3)
NLF_LINEAR = NLF + 3

(ND_FEATURE, ND_FEATURE_ENUM, ND_THRESHOLD, ND_DL, ND_GAIN, ND_LEFT,
 ND_RIGHT, ND_IVALUE, ND_IWEIGHT, ND_ICOUNT, ND_COL, ND_BIN_START,
 ND_IS_BUNDLED, ND_NUM_BIN, ND_DEFAULT_BIN, ND_MISSING, ND_IS_CAT) = range(17)
NND = 17

# The frontier-batched mode (tpu_frontier_k > 1) appends parent-leaf
# SNAPSHOT rows to its node matrix — the start/count/sum_g/depth of the
# leaf each split consumed — so the oracle-order renumber pass can
# reconstruct the leaf record of a PRUNED speculative split without a
# host round-trip (see _renumber_frontier).
(ND_START, ND_CNTP, ND_SUM_G, ND_DEPTH) = range(NND, NND + 4)
NND_FR = NND + 4
# rows per window of the frontier body's undo snapshot copy
# (_snapshot_rowids)
_SNAP_WINDOW = 1 << 18


def _i2f(x):
    return jax.lax.bitcast_convert_type(
        jnp.asarray(x, jnp.int32), jnp.float32)


def _f2i(x):
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def _pack_bits(rows):
    """Stack mixed int / f32 rows as one int32 matrix of raw bits (ints
    as they are, floats bitcast).  The frontier body packs and moves its
    leaf/node matrices in the INT domain: XLA:TPU lowers f32
    stack/concatenate fusions through float arithmetic that flushes
    denormals to zero — and a small int32 bitcast into an f32 container
    IS a denormal (measured on the v5e, PR 21: every such row came back
    0).  The K=1 body predates this helper and packs with row writes,
    which are pure moves."""
    def bits(r):
        r = jnp.asarray(r)
        if jnp.issubdtype(r.dtype, jnp.integer):
            return r.astype(jnp.int32)
        return _f2i(r.astype(jnp.float32))
    return jnp.stack([bits(r) for r in rows])


def parse_monotone_constraints(spec, num_total_features: int) -> np.ndarray:
    """Parse the `monotone_constraints` param ("1,-1,0" / list) into a
    per-original-feature int8 array (reference: config parsing of
    monotone_constraints, config_auto.cpp)."""
    out = np.zeros(num_total_features, dtype=np.int32)
    if spec is None:
        return out
    if isinstance(spec, str):
        spec = spec.strip().strip("()[]")
        if not spec:
            return out
        items = [s for s in spec.replace(" ", "").split(",") if s]
    else:
        items = list(spec)
    vals = [int(v) for v in items]
    if len(vals) > num_total_features:
        raise ValueError(
            f"monotone_constraints has {len(vals)} entries but the dataset "
            f"has {num_total_features} features")
    out[:len(vals)] = vals
    if np.any((out < -1) | (out > 1)):
        raise ValueError("monotone_constraints entries must be -1, 0 or 1")
    return out


def parse_interaction_constraints(spec, num_total_features: int):
    """Parse interaction_constraints ("[0,1,2],[2,3]" or list of lists) into
    a (C, F_total) bool matrix of allowed-feature sets (reference:
    col_sampler.hpp SetInteractionConstraints)."""
    if spec is None:
        return None
    if isinstance(spec, str):
        s = spec.strip()
        if not s:
            return None
        import json as _json
        groups = _json.loads(f"[{s}]" if not s.startswith("[[") else s)
    else:
        groups = [list(g) for g in spec]
    if not groups:
        return None
    out = np.zeros((len(groups), num_total_features), dtype=bool)
    for i, g in enumerate(groups):
        for f in g:
            f = int(f)
            if not 0 <= f < num_total_features:
                raise ValueError(
                    f"interaction_constraints feature {f} out of range")
            out[i, f] = True
    return out


def parse_per_feature_penalty(spec, num_total_features: int):
    """Parse cegb_penalty_feature_{lazy,coupled} ("0.1,0.2,...")."""
    if spec is None:
        return None
    if isinstance(spec, str):
        s = spec.strip().strip("()[]")
        if not s:
            return None
        vals = [float(v) for v in s.replace(" ", "").split(",") if v]
    else:
        vals = [float(v) for v in spec]
    if len(vals) != num_total_features:
        raise ValueError(
            f"per-feature penalty has {len(vals)} entries, expected "
            f"{num_total_features}")
    return np.asarray(vals, dtype=np.float32)


class SerialTreeLearner:
    """Builds one tree per call, entirely on device.

    With ``axis_name`` set, the same program runs SPMD inside ``shard_map``:
      * ``parallel_mode='data'``  — rows sharded; per-leaf histograms are
        ``psum``ed over ICI so every device sees global statistics and makes
        identical split decisions (TPU analog of the reference
        DataParallelTreeLearner's ReduceScatter+Allreduce,
        src/treelearner/data_parallel_tree_learner.cpp:282-441).
      * ``parallel_mode='feature'`` — rows replicated, the split *search* is
        sharded via a per-device feature mask and the winning split is agreed
        with an arg-max reduction (TPU analog of FeatureParallelTreeLearner's
        SyncUpGlobalBestSplit, src/treelearner/parallel_tree_learner.h:209).
    """

    def __init__(self, dataset: BinnedDataset, config: Config,
                 axis_name: Optional[str] = None,
                 parallel_mode: str = "serial",
                 num_shards: int = 1,
                 local_num_data: Optional[int] = None,
                 global_num_data: Optional[int] = None):
        self.ds = dataset
        self.cfg = config
        self.axis_name = axis_name
        self.parallel_mode = parallel_mode
        self.num_shards = num_shards
        meta = dataset.feature_meta_arrays()
        self.G = max(dataset.num_groups, 1)
        self.B = max(dataset.max_group_bins, 2)
        self.F = len(meta["feature"])
        self.BF = int(meta["num_bin"].max()) if self.F else 2
        self.L = config.num_leaves
        self.max_splits = self.L - 1

        # ---- per-feature device metadata ----
        grp = meta["group"]
        is_bundled = np.zeros(self.F, dtype=np.int32)
        for g, ginfo in enumerate(dataset.groups):
            if len(ginfo.feature_indices) > 1:
                is_bundled[grp == g] = 1
        self.ctx = split_ops.SplitContext(
            num_bin=jnp.asarray(meta["num_bin"]),
            missing_type=jnp.asarray(meta["missing_type"]),
            default_bin=jnp.asarray(meta["default_bin"]),
            is_categorical=jnp.asarray(meta["is_categorical"]),
            feature_index=jnp.asarray(meta["feature"]),
        )
        self.f_group = jnp.asarray(grp)
        self.f_bin_start = jnp.asarray(meta["bin_start"])
        self.f_is_bundled = jnp.asarray(is_bundled)
        self.has_categorical = bool(np.any(meta["is_categorical"]))
        # per-feature metadata packed as COLUMNS of one matrix so the hot
        # loop reads all of a feature's scalars with one lane-dynamic slice
        # (rows: feature_index, group, bin_start, is_bundled, num_bin,
        # default_bin, missing_type, monotone — see body unpack)
        self._fmeta_np = np.stack([
            np.asarray(meta["feature"], np.int32),
            np.asarray(grp, np.int32),
            np.asarray(meta["bin_start"], np.int32),
            is_bundled.astype(np.int32),
            np.asarray(meta["num_bin"], np.int32),
            np.asarray(meta["default_bin"], np.int32),
            np.asarray(meta["missing_type"], np.int32),
            np.zeros(self.F, np.int32),   # monotone filled below
        ]) if self.F else np.zeros((8, 1), np.int32)

        # ---- monotone constraints ----
        mono_all = parse_monotone_constraints(
            config.monotone_constraints, dataset.num_total_features)
        mono_used = mono_all[meta["feature"]].astype(np.int32)
        mono_used[meta["is_categorical"] != 0] = 0  # numerical only
        self.use_mc = bool(np.any(mono_used != 0))
        self.monotone = jnp.asarray(mono_used) if self.use_mc else None
        self.monotone_penalty = float(config.monotone_penalty)
        # `intermediate`/`advanced` select the REGION-EXACT refresh (see
        # _mc_refresh): per-leaf bin ranges + pairwise comparability replace
        # the reference's recursive constraint propagation + per-leaf split
        # recomputation (IntermediateLeafConstraints::Update /
        # GoUpToFindLeavesToUpdate, monotone_constraints.hpp:516-740).
        self.mc_mode = "basic"
        if self.use_mc and config.monotone_constraints_method in (
                "intermediate", "advanced"):
            # `advanced` additionally evaluates candidate children against
            # PER-THRESHOLD bound arrays (the vectorized analog of
            # AdvancedLeafConstraints' constraint segments,
            # monotone_constraints.hpp:858) in the per-split children
            # searches; leaf OUTPUT bounds (the refresh) stay the
            # whole-box scalars in both modes, which is what the
            # reference enforces for leaf values too.
            self.mc_mode = config.monotone_constraints_method
            self.mono_enums = [int(i) for i in np.where(mono_used != 0)[0]]
            self.mono_signs = [int(mono_used[i]) for i in self.mono_enums]
        if self.F:
            self._fmeta_np[7] = mono_used
        self._fmeta = jnp.asarray(self._fmeta_np)
        # ---- interaction constraints ----
        ic = parse_interaction_constraints(
            config.interaction_constraints, dataset.num_total_features)
        self.ic_masks = None
        if ic is not None:
            # map original-feature sets onto the used-feature enumeration
            self.ic_masks = jnp.asarray(ic[:, meta["feature"]])  # (C, F)

        # ---- CEGB ----
        self.cegb_count_coeff = 0.0
        self.cegb_coupled = None
        tradeoff = float(config.cegb_tradeoff)
        if float(config.cegb_penalty_split) > 0:
            self.cegb_count_coeff = tradeoff * float(config.cegb_penalty_split)
        coupled = parse_per_feature_penalty(
            config.cegb_penalty_feature_coupled, dataset.num_total_features)
        if coupled is not None:
            self.cegb_coupled = jnp.asarray(tradeoff * coupled[meta["feature"]])
        # lazy per-(row, feature) penalties (reference:
        # CostEfficientGradientBoosting::DetectSplits 'delta' term +
        # UpdateUsedFeatures, cost_effective_gradient_boosting.hpp): a
        # packed per-row used-feature BITSET (ceil(F/32) int32 rows) rides
        # the partition payload; each child split search subtracts
        # penalty[f] * (#rows in the child whose bit f is still 0)
        self.cegb_lazy = None
        self.aux_rows = 0
        lazy = parse_per_feature_penalty(
            config.cegb_penalty_feature_lazy, dataset.num_total_features)
        if lazy is not None and self.F > 0:
            self.cegb_lazy = jnp.asarray(tradeoff * lazy[meta["feature"]])
            self.aux_rows = (self.F + 31) // 32
        self.has_cegb = (self.cegb_count_coeff > 0
                         or self.cegb_coupled is not None
                         or self.cegb_lazy is not None)

        # ---- forced splits ----
        self.forced = None
        if config.forcedsplits_filename:
            if parallel_mode == "voting":
                log.warning("forcedsplits_filename is not supported with "
                            "tree_learner=voting (local histograms); ignored")
            else:
                self.forced = self._load_forced_splits(
                    config.forcedsplits_filename, dataset, meta)

        # ---- per-node column sampling ----
        self.frac_bynode = float(config.feature_fraction_bynode)
        self.has_bynode = 0.0 < self.frac_bynode < 1.0

        # ---- extra_trees (reference: feature_histogram.hpp USE_RAND) ----
        self.extra_trees = bool(config.extra_trees)
        self.extra_seed = int(config.extra_seed)

        # ---- feature_contri per-feature gain scaling ----
        fc_all = parse_per_feature_penalty(
            config.feature_contri or None, dataset.num_total_features)
        self.feature_contri = None
        if fc_all is not None and np.any(fc_all != 1.0):
            self.feature_contri = jnp.asarray(fc_all[meta["feature"]])

        self.cat_params = None
        if self.has_categorical:
            self.cat_params = {
                "max_cat_threshold": int(config.max_cat_threshold),
                "cat_l2": float(config.cat_l2),
                "cat_smooth": float(config.cat_smooth),
                "max_cat_to_onehot": int(config.max_cat_to_onehot),
                "min_data_per_group": int(config.min_data_per_group),
            }

        # feature-view gather: (F, BF) flat indices into (G*B [+1 pad slot])
        gather = np.full((self.F, self.BF), self.G * self.B, dtype=np.int32)
        fix_mask = np.zeros(self.F, dtype=np.float32)
        default_pos = np.zeros(self.F, dtype=np.int32)
        for i in range(self.F):
            g = int(grp[i])
            nb = int(meta["num_bin"][i])
            if is_bundled[i]:
                shift = int(meta["bin_start"][i])
                for b in range(1, nb):
                    gather[i, b] = g * self.B + shift + b
                fix_mask[i] = 1.0
                default_pos[i] = int(meta["default_bin"][i])  # == 0 for bundled
            else:
                for b in range(nb):
                    gather[i, b] = g * self.B + b
                default_pos[i] = int(meta["default_bin"][i])
        self.feat_gather = jnp.asarray(gather)
        self.fix_mask = jnp.asarray(fix_mask)
        self.default_pos = jnp.asarray(default_pos)
        # identity feature->group mapping (no bundling): the (F, BF, 2)
        # view is a plain slice — no gather, no default-bin reconstruction
        # (bins >= num_bin never occur, so those hist cells are zero)
        self._plain_view = (self.F == self.G
                            and not np.any(is_bundled)
                            and np.array_equal(grp, np.arange(self.F)))

        # ---- row geometry ----
        # a dataset built through the direct-to-device construction path
        # (ops/construct.py DeviceIngest) may carry its packed bins ONLY
        # in the transposed (G, N_pad) device buffer; the host matrix is
        # then optional and recoverable on demand
        self._ingest = (getattr(dataset, "device_ingest", None)
                        if local_num_data is None else None)
        if local_num_data is None:
            if dataset.binned is None and self._ingest is None:
                raise ValueError("dataset has no binned data")
            self.N = dataset.num_data
        else:
            self.N = local_num_data
        host_bin_dtype = np.dtype(
            dataset.binned.dtype if dataset.binned is not None
            else (self._ingest.dtype if self._ingest is not None
                  else np.uint8))
        self.l1 = float(config.lambda_l1)
        self.max_delta_step = float(config.max_delta_step)
        self.path_smooth = float(config.path_smooth)
        # tpu_kernel_interpret runs every Pallas kernel through the
        # interpreter, enabling the kernel code paths on any backend
        # (the off-TPU correctness lane for the kernels; SLOW)
        self._interp = bool(config.tpu_kernel_interpret)

        # ---- which split-step program: decided in models/plan.py ----
        self.plan = plan_mod.resolve(plan_mod.PlanFacts(
            backend=jax.default_backend(), interpret=self._interp,
            rows=self.N, global_rows=global_num_data,
            F=self.F, G=self.G, B=self.B,
            num_leaves=self.L, host_bin_dtype=str(host_bin_dtype),
            has_bins=(dataset.binned is not None
                      or self._ingest is not None),
            plain_view=self._plain_view,
            has_categorical=self.has_categorical, use_mc=self.use_mc,
            has_cegb=self.has_cegb, cegb_lazy=self.cegb_lazy is not None,
            path_smooth=self.path_smooth, forced=self.forced is not None,
            extra_trees=self.extra_trees, has_bynode=self.has_bynode,
            feature_contri=self.feature_contri is not None,
            interaction_constraints=self.ic_masks is not None,
            l1=self.l1, max_delta_step=self.max_delta_step,
            linear_gain_requested=(
                bool(config.linear_tree)
                and config.linear_tree_mode == "leafwise_gain"),
            quantized=bool(config.use_quantized_grad),
            parallel_mode=parallel_mode, axis_name=axis_name is not None,
            num_shards=num_shards,
            **{k: getattr(config, k) for k in plan_mod.OPTION_FIELDS}))
        plan = self.plan
        for unmet in plan.unmet:
            log.warning("%s", unmet)
        pallas_part = plan.partition == "pallas"
        self.row_chunk = plan.row_chunk
        self._chunk_bits = self.row_chunk.bit_length() - 1
        C = self.row_chunk
        # layout: [C front-pad rows][N data rows][>=2C tail-pad rows]; the
        # front pad keeps the right-aligned partition windows non-negative,
        # the tail pad keeps chunk windows in bounds.  TWO tail chunks: the
        # Pallas partition's pass-2 destination windows start at the
        # 128-aligned floor of an arbitrary leaf offset, so the last
        # (RMW-blended) window can overhang the chunk-aligned cover by up
        # to C-1 rows.  Root range starts at C.
        self.row0 = C
        self.N_pad = C + ((self.N + C - 1) // C + 2) * C
        # ---- Pallas partition kernel ----
        # The Pallas kernel (ops/partition_pallas.py) streams aligned
        # window DMAs through an in-VMEM compaction (about 0.6 ms per
        # 1M rows on the v5e, PERF.md section 6, PR 34;
        # the XLA formulation has not been timed there at the cells'
        # size).  DMA tiling requires sublane-padded row buffers: bins
        # to a multiple of 32 (u8 tile), grad/hess/rowid to 8 f32 rows.
        self._pb_rows = self.G
        # (8, N_pad) f32 ghi payload in BOTH partition modes: rows are
        # (grad, hess, rowid-bits, then optional score/objective-payload
        # rows for the physical fused step, zero-padded).  The Pallas DMA
        # tiling needs 8 f32 sublanes anyway; the XLA path's per-row
        # gather cost is width-independent (PERF.md).
        self._ghi_rows = 8
        self._ghi_live = 3     # rows the Pallas kernel must carry
        if pallas_part:
            # whole passes of the kernel (one, of the 32-padded rows,
            # unless the width is over its VMEM: models/plan.py)
            self._pb_rows = -(-self.G // plan.pass_rows) * plan.pass_rows
            # how the width is tiled (Booster.telemetry_report(),
            # telemetry on): the partition's passes over the payload and
            # the u8 tiles the histogram kernel's grid walks
            obs.gauge("train.partition.payload_tiles",
                      self._pb_rows // plan.pass_rows)
            if plan.hist == "pallas":
                obs.gauge("train.hist.feature_tiles", -(-self.G // 32))
        # fused multiclass carries K score rows + label (+ weight) through
        # the partition; the XLA path takes any row count (its per-row
        # gather cost is width-independent), the Pallas kernel is capped
        # at its 8-row f32 tile (partition_pallas.py asserts GH == 8)
        K_cls = max(int(config.num_class), 1)
        if K_cls > 1 and not pallas_part:
            need = 4 + K_cls + (1 if dataset.metadata.weight is not None
                                else 0)
            if need > self._ghi_rows:
                self._ghi_rows = ((need + 7) // 8) * 8

        # Row layout: the binned matrix TRANSPOSED to (G, N_pad) in its
        # native bin dtype, plus a packed (3, N_pad) grad/hess/rowid matrix.
        # Rows live on the MINOR (lane) axis: in (N, G) orientation XLA's
        # layout heuristic prefers column-major for the multi-MB buffers
        # (G < 128 would waste 4.5x footprint row-major) while the
        # partition's row-gather loops demand row-major, and the
        # disagreement inserted full-buffer transpose copies EVERY split.
        # (G, N) row-major is bit-identical to (N, G) column-major, so all
        # consumers now agree.  The partition still moves rows with
        # vectorized 2-D gathers on chunk-local transposes + contiguous
        # window writes.  Rows are never gathered by bag index:
        # bagging/GOSS zero the out-of-bag gradients instead.
        self._part0 = None
        # True when _part0 is the ingest's master buffer (or its
        # sublane-padded extension): the fused trainer may then ADOPT
        # the buffer and release the ingest's reference (single-copy
        # residency, boosting._adopt_master_buffer)
        self._part0_from_ingest = False
        if local_num_data is None:
            ing = self._ingest
            if (ing is not None and ing.N == self.N
                    and ing.matches(self.row_chunk, self.N_pad,
                                    host_bin_dtype)):
                # construction already streamed the transposed layout to
                # the device: no host transpose, no host pad copy
                self._part0 = ing.part0(self._pb_rows)
                self._part0_from_ingest = True
            else:
                binned = dataset.binned
                if binned is None and ing is not None:
                    # geometry changed between construction and train
                    # (e.g. a different tpu_row_chunk): an out-of-core
                    # dataset re-streams its retained chunk source into
                    # a fresh ingest buffer at THIS geometry (epoch
                    # re-streaming, dataset.py restream_ingest) — the
                    # full host matrix never materializes
                    restream = getattr(dataset, "restream_ingest", None)
                    if restream is not None and getattr(
                            dataset, "_stream_src", None):
                        ing2 = restream(self.row_chunk)
                        if (ing2 is not None and ing2.N == self.N
                                and ing2.matches(self.row_chunk,
                                                 self.N_pad,
                                                 host_bin_dtype)):
                            self._part0 = ing2.part0(self._pb_rows)
                            self._part0_from_ingest = True
                            # drop the stale-geometry buffer: keeping
                            # both would hold 2x the binned footprint
                            # for the whole training run
                            self._ingest = ing = ing2
                    if self._part0 is None:
                        # last resort: recover the host matrix once and
                        # rebuild through the oracle path
                        binned = ing.host_binned()
                if self._part0 is None:
                    binned = np.ascontiguousarray(binned)
                    if binned.shape[1] < self.G:   # zero usable features
                        binned = np.zeros((binned.shape[0], self.G),
                                          binned.dtype)
                    pad = np.zeros((self._pb_rows, self.N_pad),
                                   binned.dtype)
                    pad[:self.G, C:C + self.N] = binned.T
                    self._part0 = jnp.asarray(pad)

        # ---- scalars ----
        self.l2 = float(config.lambda_l2)
        self.min_gain_to_split = float(config.min_gain_to_split)
        self.min_data_in_leaf = int(config.min_data_in_leaf)
        self.min_sum_hessian = float(config.min_sum_hessian_in_leaf)
        self.max_depth = int(config.max_depth)
        self.top_k = int(config.top_k)

        # ---- piece-wise linear leafwise gain (linear_tree_mode) ----
        # Split gain over leaf-local linear models inside the device
        # search (ops/split.py:find_best_split_linear); a configuration
        # the plan excludes trains in the post-hoc refit mode, exactly
        # like before.
        self.linear_lambda = float(config.linear_lambda)
        self._nlf = NLF_LINEAR if plan.linear_gain else NLF
        self._rep_vals = None
        if plan.linear_gain:
            # per-(feature, bin) representative raw values — the linear
            # moment planes are rank-1 scalings of the histogram by this
            # table (ops/histogram.py:linear_moment_planes).  Empirical
            # within-bin means (one host pass over the retained raw
            # matrix) rather than bin bounds: bound-reps overestimate x
            # by up to a bin width, which measurably biases fitted
            # slopes in wide tail bins.
            raw = getattr(dataset, "raw_data", None)
            rep = np.zeros((self.F, self.BF), np.float32)
            for i, orig in enumerate(meta["feature"]):
                col = raw[:, orig] if raw is not None else None
                rep[i] = dataset.bin_mappers[orig].bin_rep_values(
                    self.BF, values=col)
            self._rep_vals = jnp.asarray(rep)

        # ReduceScatter histogram ownership (reference placement:
        # data_parallel_tree_learner.cpp:282-296) — see _psum
        self._scatter_per = (-(-self.G // num_shards)
                             if plan.scatter_groups else 0)

        # Pallas split-search kernel: one program per split evaluates
        # both children (ops/split_pallas.py)
        if plan.search == "pallas":
            half = np.zeros((self.F, 8), np.int32)
            half[:, 0] = meta["num_bin"]
            half[:, 1] = meta["missing_type"]
            half[:, 2] = meta["default_bin"]
            self._fmeta_pair = jnp.asarray(np.concatenate([half, half]))

        # ---- flat histogram state + Pallas RMW (fast serial path) ----
        # The (L+1, G, B, 2) state's per-split dynamic-slice read causes
        # XLA to materialize two full-state copies per split (PERF.md
        # "fixed-cost smoking gun"); the flat (L+1, 8, WL) state is
        # updated in place by ops/hist_state_pallas.py with one-row DMAs.
        self._flat_geom = None
        if plan.hist_state == "flat":
            from ..ops.hist_state_pallas import flat_geometry
            self._flat_geom = flat_geometry(self.G, self.B)

        # ---- leaf-size-adaptive chunk policy (ops/chunkpolicy.py) ----
        # Per-leaf hist/partition passes pick their chunk width from a
        # bounded static menu so small leaves stop paying the worst-case
        # padded chunk (68% of the CPU iteration, PERF.md round 12).
        # Band dispatch is zero-trip fori_loops — never lax.switch/cond,
        # whose branch plumbing copies the multi-MB row buffers per
        # split.  Trees stay BIT-identical to tpu_chunk_policy=fixed
        # (see chunkpolicy module docstring; pinned by
        # tests/test_chunkpolicy.py and ab_bench --chunk).
        self._chunk_policy = ChunkPolicy(plan.row_chunk,
                                         adaptive=plan.chunk_adaptive)

        axes = (0, 0, 0, 0, 0, 0, 0, 0, 0, 0, None)
        if self.cegb_lazy is not None:
            axes = axes + (0,)
        if self.extra_trees:
            axes = axes + (0,)
        self._best_split_vmapped = jax.vmap(self._leaf_best_split,
                                            in_axes=axes)
        self._build = jax.jit(self._build_impl)

    def kernel_plan(self) -> Dict[str, Any]:
        """The kernels this learner resolved to, as one printable record
        (selection is by backend and shape eligibility only — a kernel
        named here that cannot compile raises, it is never swapped)."""
        return self.plan.kernel_plan()

    def _rand_bins(self, key):
        """One random threshold per feature (reference:
        meta_->rand.NextInt(0, num_bin - 2), feature_histogram.hpp:204)."""
        u = jax.random.uniform(key, (self.F,))
        span = jnp.maximum(self.ctx.num_bin - 2, 1).astype(jnp.float32)
        return jnp.floor(u * span).astype(jnp.int32)

    # ------------------------------------------------------------------
    @scopes.phase("histogram")
    def _hist_leaf(self, part_bins, part_ghi, start, cnt, scale=None):
        if self.plan.hist == "pallas":
            # f32 gradients only: the plan keeps quantized carriers on
            # the XLA loop below
            return self._hist_leaf_kernel(part_bins, part_ghi, start, cnt)
        # quantized training rides INTEGER gradient carriers: the one-hot
        # matmuls run in bfloat16 (exact for the small int grid, double
        # MXU rate — the int16-histogram analog).  The histogram stays
        # in the INTEGER domain here — exact at any summation order and
        # through the whole parent-minus-child subtraction chain; the
        # (grad, hess) scales apply once at the split-search inputs
        # (_scale_hist).  Scaling per-histogram instead was an FMA trap:
        # LLVM contracted `parent - h*scale` into a fused
        # multiply-subtract in some compilation contexts and not others,
        # so "identical" programs drifted by ULPs (the frontier-batched
        # body's bit-identity contract caught it, PERF.md round 12).
        dtype = jnp.bfloat16 if scale is not None else jnp.float32
        if self._chunk_policy.adaptive:
            # leaf-size-adaptive bands (eligibility guarantees the
            # plain-XLA path); quantized integer carriers are exact at
            # any width by construction
            from ..ops.histogram import leaf_hist_banded
            return leaf_hist_banded(
                part_bins, part_ghi, start, cnt, num_bins=self.B,
                policy=self._chunk_policy, dtype=dtype,
                vary=self._pvary, num_groups=self.G)
        return leaf_hist_slice(part_bins, part_ghi, start, cnt,
                               num_bins=self.B, row_chunk=self.row_chunk,
                               vary=self._pvary, num_groups=self.G,
                               dtype=dtype)

    def _hist_leaf_kernel(self, part_bins, part_ghi, start, cnt,
                          flat_geom=None):
        """One ``lgbm_histogram`` launch for the leaf (plan.hist=pallas)."""
        return leaf_hist_pallas(
            part_bins, part_ghi, start, cnt, num_bins=self.B,
            row_chunk=self.row_chunk, num_groups=self.G,
            flat_geom=flat_geom, interpret=self._interp)

    @staticmethod
    def _scale_hist(h, scale):
        """Integer-domain quantized histogram -> gain domain at a
        split-search input ((..., 2) trailing (grad, hess) planes times
        (gs, hs)).  Identity when quantized carriers are off."""
        if scale is None:
            return h
        return h * scale[None, None, :]

    @scopes.phase("histogram")
    def _hist_leaf_flat(self, part_bins, part_ghi, start, cnt):
        """Smaller-child histogram directly in the lane-flattened (8, WL)
        slot layout of the Pallas hist-state RMW kernel."""
        if self.plan.hist == "pallas":
            return self._hist_leaf_kernel(part_bins, part_ghi, start, cnt,
                                          flat_geom=self._flat_geom)
        return leaf_hist_slice(part_bins, part_ghi, start, cnt,
                               num_bins=self.B, row_chunk=self.row_chunk,
                               vary=self._pvary, num_groups=self.G,
                               flat_geom=self._flat_geom)

    def _flatten_hist(self, h):
        """(G, B, 2) histogram -> one (8, WL) flat state slot."""
        Gf, Bf, WL = self._flat_geom
        x = jnp.moveaxis(h, 2, 0)                       # (2, G, B)
        x = jnp.pad(x, ((0, 0), (0, Gf - self.G), (0, Bf - self.B)))
        return x.reshape(8, WL)

    def _goes_left(self, colv, scalars):
        """Per-row decision from raw group-column values.

        Bundled features decode bin b (≠ default) at offset ``bstart + b``
        (reference: FeatureGroup bin offsets, include/LightGBM/feature_group.h).
        Categorical nodes test bin membership in the split's category set
        (reference: DenseBin::Split categorical arm, src/io/dense_bin.hpp).
        """
        bstart, isb, nb, dbin, mtype, thr, dl, is_cat, cat_set = scalars
        gb = colv.astype(jnp.int32)
        fb_raw = gb - bstart
        in_r = (fb_raw >= 1) & (fb_raw <= nb - 1)
        fb = jnp.where(isb == 1, jnp.where(in_r, fb_raw, dbin), gb)
        num_left = split_decision(fb, thr, dl, mtype, dbin, nb - 1)
        if not self.has_categorical:   # keep the all-numerical hot path lean
            return num_left
        # membership via one-hot AND (C-length 1-D gathers serialize on TPU)
        oh = fb[:, None] == jax.lax.broadcasted_iota(
            jnp.int32, (1, cat_set.shape[0]), 1)
        cat_left = jnp.any(oh & cat_set[None, :], axis=1)
        return jnp.where(is_cat, cat_left, num_left)

    @scopes.phase("bookkeeping")
    def _snapshot_rowids(self, snap, part_ghi, start, cnt):
        """Copy the rowid row of the leaf range [start, start+cnt) into
        the frontier body's undo snapshot ``snap`` (f32[N_pad], rowid
        bits), leaving every other element of it as it was.

        Fixed-width windows with a masked tail, like the partition's own
        window writes, so the cost follows the rows about to be
        partitioned and not N.  The window is wider than ``row_chunk``
        (one trip moves one row of the payload, not G+8) and clamped to
        the buffer's end; the mask is on absolute positions, so a
        clamped window re-copies rows of the range it already holds."""
        Np = snap.shape[0]
        W = min(_SNAP_WINDOW, Np)

        def window(ci, snap):
            off = jnp.minimum(start + ci * W, Np - W)
            pos = off + jax.lax.iota(jnp.int32, W)
            mask = (pos >= start) & (pos < start + cnt)
            row = jax.lax.dynamic_slice(part_ghi, (2, off), (1, W))[0]
            win = jax.lax.dynamic_slice(snap, (off,), (W,))
            return jax.lax.dynamic_update_slice(
                snap, jnp.where(mask, row, win), (off,))

        return jax.lax.fori_loop(0, (cnt + W - 1) // W, window, snap)

    @scopes.phase("partition")
    def _partition_leaf(self, st, start, cnt, col, decision_scalars):
        """Two-way partition of the contiguous leaf range [start, start+cnt).

        TPUs scatter into HBM one element at a time (scalar-core DMA), so the
        global scatter a literal CUDA port would use is off the table.
        Each fixed-size chunk is compacted LOCALLY (packed-key sort +
        row-gather on the chunk transpose) and written with contiguous
        window updates.  This replaces the CUDA bitvector +
        AggregateBlockOffset + SplitInner kernels
        (cuda_data_partition.cu:288-907).

        Lefts are forward-packed from the range start and rights backward
        from the range end into the scratch buffers, then the copy-back
        loop composes every destination window from the scratches.  (An
        in-place variant that wrote lefts directly into the row buffers —
        safe because the left frontier never passes the read frontier —
        measured ~1.7x SLOWER end-to-end: the read-modify-write hazard on
        the loop-carried row buffers defeats XLA's in-place scheduling.)
        """
        if self.plan.partition == "pallas":
            return self._partition_leaf_pallas(st, start, cnt, col,
                                               decision_scalars)
        pol = self._chunk_policy
        C = self.row_chunk
        G = self.G
        from ..ops.chunkpolicy import note_variant
        note_variant("partition", C)
        # leaf-size-adaptive banding: the base chunk loops run ZERO
        # trips when a smaller menu width covers the leaf, and each
        # smaller width appends a zero-or-one-trip single-window pass
        # below (bit-identical row moves at any width — see
        # ops/partition.py window_order)
        base_cover = (pol.base_cover(cnt, pol.sizes) if pol.adaptive
                      else None)
        part_bins = st["part_bins"]
        # grad/hess/rowid (+ score/objective payload rows in the fused
        # physical mode) live PERMANENTLY as one (R, N_pad) f32 matrix
        # (ints bitcast to f32) so the per-chunk permute is one 2-D gather
        # on the chunk transpose (1-D gathers serialize on TPU) and no
        # per-split pack/unpack of the full row payload is materialized.
        part_ghi = st["part_ghi"]
        R = part_ghi.shape[0]
        n_chunks = ((cnt + C - 1) // C if base_cover is None
                    else base_cover)

        def blend(dst, val, off, mask):
            # (rows-on-lanes window write at column offset ``off``)
            win = jax.lax.dynamic_slice(dst, (0, off),
                                        (dst.shape[0], val.shape[1]))
            return jax.lax.dynamic_update_slice(
                dst, jnp.where(mask[None, :], val, win), (0, off))

        part_aux = st.get("part_aux")
        sc_aux0 = st.get("sc_aux")
        W = self.aux_rows

        col_onehot = (jax.lax.iota(jnp.int32, self.G) == col)[:, None]

        def scatter_pass(ci, carry):
            nl, nr, sc, sa = carry
            row0 = start + ci * C
            bch = jax.lax.dynamic_slice(part_bins, (0, row0), (G, C))
            gch = jax.lax.dynamic_slice(part_ghi, (0, row0), (R, C))
            # split-column extraction via masked reduction: a dynamic_slice
            # with a runtime SUBLANE offset lowers to a slow per-tile path
            colv = jnp.sum(bch.astype(jnp.int32) * col_onehot, axis=0)
            valid = (ci * C + jax.lax.iota(jnp.int32, C)) < cnt
            gl = self._goes_left(colv, decision_scalars) & valid
            gr = valid & ~gl
            gli = gl.astype(jnp.int32)
            gri = gr.astype(jnp.int32)
            inv = (~valid).astype(jnp.int32)
            nlc = jnp.sum(gli)
            nrc = jnp.sum(gri)
            lrank = jnp.cumsum(gli) - gli
            rrank = jnp.cumsum(gri) - gri
            irank = jnp.cumsum(inv) - inv
            # local destination: [lefts | padding | rights(right-aligned)]
            dloc = jnp.where(gl, lrank,
                             jnp.where(gr, C - nrc + rrank, nlc + irank))
            # inverse permutation via a SINGLE-operand sort of packed
            # (dest << log2C) | src keys: XLA's multi-operand sort (what
            # jnp.argsort lowers to) runs ~50x slower on TPU than the
            # one-array form, and this sort dominated the whole partition
            iot0 = jax.lax.iota(jnp.int32, C)
            packed = ((dloc << self._chunk_bits) | iot0).astype(jnp.uint32)
            order = (jax.lax.sort(packed) & jnp.uint32(C - 1)).astype(
                jnp.int32)
            # permute rows via a row-gather on the chunk TRANSPOSE: the big
            # buffers only ever see contiguous (G, C) window slices/updates,
            # so their row-major (G, N) layout is never contested; the
            # transposes are VMEM-local tile shuffles
            both32 = jnp.concatenate(
                [bch.astype(jnp.int32),
                 jax.lax.bitcast_convert_type(gch, jnp.int32)], axis=0)
            bothc = jnp.take(both32, order, axis=1)      # (G+R, C)
            iot = jax.lax.iota(jnp.int32, C)
            lmask = iot < nlc
            # rights window [start+cnt-nr-C, +C), mask last nrc rows; the
            # front pad rows of the arrays keep this offset non-negative
            rmask = iot >= C - nrc
            roff = start + cnt - nr - C
            # the fused (G+3) i32 block feeds ONE scratch, halving the
            # masked window writes; rows split back only at copy-back
            sc = blend(blend(sc, bothc, start + nl, lmask), bothc, roff,
                       rmask)
            if part_aux is not None:
                ach = jax.lax.dynamic_slice(part_aux, (0, row0), (W, C))
                acomp = jnp.take(ach, order, axis=1)
                sa = blend(blend(sa, acomp, start + nl, lmask), acomp,
                           roff, rmask)
            return nl + nlc, nr + nrc, sc, sa

        sa0 = sc_aux0 if sc_aux0 is not None else jnp.zeros((), jnp.int32)
        carry0 = self._pvary((jnp.int32(0), jnp.int32(0), st["sc32"], sa0))
        nl, nr, sc, sa = jax.lax.fori_loop(
            0, n_chunks, scatter_pass, carry0)

        def copyback(ci, carry):
            pb, pg, pa = carry
            row0 = start + ci * C
            valid = (ci * C + jax.lax.iota(jnp.int32, C)) < cnt
            win = jax.lax.dynamic_slice(sc, (0, row0), (G + R, C))
            pb = blend(pb, win[:G].astype(pb.dtype), row0, valid)
            pg = blend(pg, jax.lax.bitcast_convert_type(win[G:], jnp.float32),
                       row0, valid)
            if part_aux is not None:
                pa = blend(pa, jax.lax.dynamic_slice(sa, (0, row0), (W, C)),
                           row0, valid)
            return pb, pg, pa

        pa0 = part_aux if part_aux is not None else jnp.zeros((), jnp.int32)
        part_bins, part_ghi, part_aux = jax.lax.fori_loop(
            0, n_chunks, copyback, self._pvary((part_bins, part_ghi, pa0)))
        moved = {
            "part_bins": part_bins,
            "part_ghi": part_ghi,
            "sc32": sc,
        }
        if self.aux_rows:
            moved["part_aux"] = part_aux
            moved["sc_aux"] = sa
        if pol.adaptive:
            # exactly one band executes per split; the others cost a
            # zero-trip loop header.  The window pass skips the scratch
            # + copyback entirely (single window: no cross-chunk
            # hazards), writing byte-identical buffers.
            for w, trip in zip(pol.sizes[1:],
                               pol.small_trips(cnt, pol.sizes)):
                moved, nl_w = self._partition_leaf_window(
                    moved, start, cnt, col, decision_scalars, w, trip)
                nl = nl + nl_w
        return moved, nl

    @scopes.phase("partition")
    def _partition_leaf_window(self, bufs, start, cnt, col,
                               decision_scalars, width: int, trip):
        """Single-window leaf partition at a smaller menu width: one
        (G+R, W) read, one packed-key sort, one gather, masked window
        writes — wrapped in a ``trip``-gated fori_loop so a non-selected
        band skips at runtime without a conditional (lax.cond/switch
        would copy the multi-MB row buffers every split)."""
        from ..ops.chunkpolicy import note_variant
        from ..ops.partition import window_order
        note_variant("partition", width)
        G = self.G
        W = width
        aw = self.aux_rows
        col_onehot = (jax.lax.iota(jnp.int32, G) == col)[:, None]

        def body(_, carry):
            pb, pg, pa, nl = carry
            PBR = pb.shape[0]
            R = pg.shape[0]
            bch = jax.lax.dynamic_slice(pb, (0, start), (PBR, W))
            gch = jax.lax.dynamic_slice(pg, (0, start), (R, W))
            colv = jnp.sum(bch[:G].astype(jnp.int32) * col_onehot, axis=0)
            valid = jax.lax.iota(jnp.int32, W) < cnt
            gl = self._goes_left(colv, decision_scalars)
            order, nlc = window_order(gl, valid, W)
            both32 = jnp.concatenate(
                [bch.astype(jnp.int32),
                 jax.lax.bitcast_convert_type(gch, jnp.int32)], axis=0)
            perm = jnp.take(both32, order, axis=1)
            vm = valid[None, :]
            pb = jax.lax.dynamic_update_slice(
                pb, jnp.where(vm, perm[:PBR].astype(pb.dtype), bch),
                (0, start))
            pg = jax.lax.dynamic_update_slice(
                pg, jnp.where(vm, jax.lax.bitcast_convert_type(
                    perm[PBR:], jnp.float32), gch), (0, start))
            if aw:
                ach = jax.lax.dynamic_slice(pa, (0, start), (aw, W))
                pa = jax.lax.dynamic_update_slice(
                    pa, jnp.where(vm, jnp.take(ach, order, axis=1), ach),
                    (0, start))
            return pb, pg, pa, nl + nlc

        pa0 = bufs["part_aux"] if aw else jnp.zeros((), jnp.int32)
        carry0 = self._pvary((bufs["part_bins"], bufs["part_ghi"], pa0,
                              jnp.int32(0)))
        pb, pg, pa, nl = jax.lax.fori_loop(0, trip, body, carry0)
        out = {**bufs, "part_bins": pb, "part_ghi": pg}
        if aw:
            out["part_aux"] = pa
        return out, nl

    @scopes.phase("partition")
    def _partition_leaf_pallas(self, st, start, cnt, col, decision_scalars):
        """Pallas-kernel leaf partition (see ops/partition_pallas.py):
        bit-identical layout to the XLA path above at ~30x lower cost on
        this stack."""
        from ..ops.partition_pallas import (partition_leaf_pallas,
                                            make_scalars)
        bstart, isb, nb, dbin, mtype, thr, dl, is_cat, cat_set = \
            decision_scalars
        scalars = make_scalars(start, cnt, col, bstart, isb, nb, dbin,
                               mtype, thr, dl)
        pb, pg, sp, nl = partition_leaf_pallas(
            st["part_bins"], st["part_ghi"], st["sc_packed"],
            scalars, row_chunk=self.row_chunk, ghi_live=self._ghi_live,
            pack_rowid=self.plan.pack_rowid, pass_rows=self.plan.pass_rows,
            interpret=self._interp)
        moved = {"part_bins": pb, "part_ghi": pg, "sc_packed": sp}
        return moved, nl[0, 0]

    @scopes.phase("split_mega")
    def _split_leaf_mega(self, st, start, cnt, col, decision_scalars,
                         hist_scale=None):
        """Mega-path split: partition the leaf AND produce BOTH
        children's histograms (ops/split_megakernel_pallas.py) — one
        Pallas program in "pallas" mode, the bit-identical XLA oracle
        formulation in "xla" mode.  Returns (moved, left_cnt,
        (hl_g, hl_h, hr_g, hr_h)) with the hist planes (G, Bp)."""
        from ..ops.split_megakernel_pallas import (both_children_hist_xla,
                                                   split_megakernel_pallas,
                                                   unpack_hist4)
        bstart, isb, nb, dbin, mtype, thr, dl, is_cat, cat_set = \
            decision_scalars
        if self.plan.mega == "pallas":
            from ..ops.partition_pallas import make_scalars
            scalars = make_scalars(start, cnt, col, bstart, isb, nb, dbin,
                                   mtype, thr, dl)
            pb, pg, sp, nl, acc = split_megakernel_pallas(
                st["part_bins"], st["part_ghi"], st["sc_packed"], scalars,
                row_chunk=self.row_chunk, num_bins=self.B,
                num_groups=self.G, ghi_live=self._ghi_live,
                pack_rowid=self.plan.pack_rowid, interpret=self._interp)
            moved = {"part_bins": pb, "part_ghi": pg, "sc_packed": sp}
            left_cnt = nl[0, 0]
        else:
            # oracle mode: the SAME chunk grid and accumulation math as
            # the kernel, as plain XLA ops, over the pre-partition rows
            if self._chunk_policy.adaptive:
                from ..ops.split_megakernel_pallas import (
                    both_children_hist_banded)
                acc = both_children_hist_banded(
                    st["part_bins"], st["part_ghi"], start, cnt, col,
                    (bstart, isb, nb, dbin, mtype, thr, dl),
                    policy=self._chunk_policy, num_bins=self.B,
                    num_groups=self.G, vary=self._pvary)
            else:
                acc = both_children_hist_xla(
                    st["part_bins"], st["part_ghi"], start, cnt, col,
                    (bstart, isb, nb, dbin, mtype, thr, dl),
                    row_chunk=self.row_chunk, num_bins=self.B,
                    num_groups=self.G, vary=self._pvary)
            moved, left_cnt = self._partition_leaf(st, start, cnt, col,
                                                   decision_scalars)
        hl_g, hl_h, hr_g, hr_h = unpack_hist4(acc, self.B)
        if hist_scale is not None:
            # quantized training: integer carriers accumulated exactly;
            # the (grad, hess) scales apply once per histogram.  The
            # barrier pins the products' rounding across compilation
            # contexts (see _hist_leaf).
            hl_g, hl_h, hr_g, hr_h = jax.lax.optimization_barrier(
                (hl_g * hist_scale[0], hl_h * hist_scale[1],
                 hr_g * hist_scale[0], hr_h * hist_scale[1]))
        return moved, left_cnt, (hl_g, hl_h, hr_g, hr_h)

    # ------------------------------------------------------------------
    def _load_forced_splits(self, filename, dataset, meta):
        """Flatten the forced-splits JSON (reference: forced_split_json_
        BFS in SerialTreeLearner::ForceSplits, serial_tree_learner.cpp:614)
        into parallel arrays: feature enum, bin threshold, child node ids."""
        import json as _json
        with open(filename) as f:
            root = _json.load(f)
        enum_of = {int(orig): i for i, orig in enumerate(meta["feature"])}
        feats, bins_, lefts, rights = [], [], [], []

        def add(node):
            if (not isinstance(node, dict) or "feature" not in node
                    or "threshold" not in node):
                return -1
            orig = int(node["feature"])
            if orig not in enum_of:
                log.warning("forced split on unused feature %d ignored", orig)
                return -1
            fi = enum_of[orig]
            if int(meta["is_categorical"][fi]):
                log.warning("forced split on categorical feature %d ignored",
                            orig)
                return -1
            bm = dataset.bin_mappers[orig]
            thr_bin = bm.value_to_bin(float(node["threshold"]))
            idx = len(feats)
            feats.append(fi)
            bins_.append(int(thr_bin))
            lefts.append(-1)
            rights.append(-1)
            lefts[idx] = add(node.get("left"))
            rights[idx] = add(node.get("right"))
            return idx

        if add(root) < 0:
            return None
        return {
            "feature": jnp.asarray(np.asarray(feats, np.int32)),
            "bin": jnp.asarray(np.asarray(bins_, np.int32)),
            "left": jnp.asarray(np.asarray(lefts, np.int32)),
            "right": jnp.asarray(np.asarray(rights, np.int32)),
        }

    def _forced_split_info(self, hist_group, f_enum, thr, sum_g, sum_h, cnt):
        """Split stats at a fixed (feature, bin) threshold (reference:
        FeatureHistogram::GatherInfoForThresholdNumerical,
        feature_histogram.hpp:502): reverse-scan semantics — the right side
        holds bins in (thr, bmax], the default bin is skipped for
        zero-missing features, missing goes left."""
        K_EPS = split_ops.K_EPSILON
        feat_hist = self._feat_view(hist_group, sum_g, sum_h)
        fh = feat_hist[f_enum]                                 # (BF, 2)
        nb = self.ctx.num_bin[f_enum]
        mtype = self.ctx.missing_type[f_enum]
        dbin = self.ctx.default_bin[f_enum]
        bins = jnp.arange(self.BF)
        is_nan = mtype == split_ops.MISSING_NAN
        is_zero = mtype == split_ops.MISSING_ZERO
        bmax = nb - 1 - is_nan.astype(jnp.int32)
        rmask = (bins > thr) & (bins <= bmax) & \
            ~(is_zero & (bins == dbin))
        rg = jnp.sum(fh[:, 0] * rmask)
        rh = jnp.sum(fh[:, 1] * rmask) + K_EPS
        sum_h_tot = sum_h + 2 * K_EPS
        cnt_factor = cnt.astype(jnp.float32) / sum_h_tot
        rc = jnp.sum(jnp.floor(fh[:, 1] * cnt_factor + 0.5).astype(jnp.int32)
                     * rmask)
        lg = sum_g - rg
        lh = sum_h_tot - rh
        lc = cnt - rc
        args = (self.l1, self.l2, self.max_delta_step)
        gain_shift = split_ops.leaf_gain(sum_g, sum_h_tot, *args)
        gain = (split_ops.leaf_gain(lg, lh, *args) +
                split_ops.leaf_gain(rg, rh, *args))
        rel = gain - (gain_shift + self.min_gain_to_split)
        valid = (lc >= 1) & (rc >= 1) & (rel >= 0) & (thr < nb - 1)
        return {
            "gain": rel, "valid": valid, "threshold": thr,
            "lsg": lg, "lsh": lh - K_EPS, "rsg": rg, "rsh": rh - K_EPS,
            "lcnt": lc.astype(jnp.int32), "rcnt": rc.astype(jnp.int32),
            "lout": split_ops.leaf_output(lg, lh, *args),
            "rout": split_ops.leaf_output(rg, rh, *args),
        }

    def _lazy_counts(self, part_aux, start, l_cnt, r_cnt):
        """(2, F) counts of rows whose feature bit is still 0 for the two
        children ranges [start, start+l_cnt) and [start+l_cnt, +r_cnt)
        (reference: the per-row feature-used tracking behind
        cegb_penalty_feature_lazy, cost_effective_gradient_boosting.hpp)."""
        C = self.row_chunk
        W = self.aux_rows
        F = self.F
        cnt = l_cnt + r_cnt
        n_chunks = (cnt + C - 1) // C

        def body(ci, acc):
            row0 = start + ci * C
            ach = jax.lax.dynamic_slice(part_aux, (0, row0), (W, C))
            pos = ci * C + jax.lax.iota(jnp.int32, C)
            valid = pos < cnt
            is_l = pos < l_cnt
            bits = jnp.stack([(ach >> k) & 1 for k in range(32)], axis=1)
            notused = 1 - bits.reshape(W * 32, C)[:F]          # (F, C)
            accl = acc[0] + jnp.sum(notused * (valid & is_l), axis=1)
            accr = acc[1] + jnp.sum(notused * (valid & ~is_l), axis=1)
            return jnp.stack([accl, accr])

        counts = jax.lax.fori_loop(0, n_chunks, body,
                                   self._pvary(jnp.zeros((2, F),
                                                         jnp.int32)))
        # data/voting parallel: counts are shard-local but _sync_best is a
        # no-op there (devices rely on identical psum'd inputs to pick
        # identical splits) — the lazy penalty must therefore be GLOBAL or
        # the replicated tree state silently diverges
        if self.axis_name is not None and self.parallel_mode in ("data",
                                                                 "voting"):
            with scopes.scope("hist_sync"):
                counts = jax.lax.psum(counts, self.axis_name)
        return counts

    def _lazy_mark(self, part_aux, start, cnt, f_enum):
        """Set the used-bit of ``f_enum`` for rows [start, start+cnt)
        (reference: CostEfficientGradientBoosting::UpdateUsedFeatures)."""
        C = self.row_chunk
        W = self.aux_rows
        # OR the bit into the matching word row via a broadcast mask — a
        # dynamic_slice with a runtime SUBLANE offset lowers to a slow
        # per-tile path
        word_mask = (jax.lax.iota(jnp.int32, W) == f_enum // 32)[:, None]
        bit = (jnp.int32(1) << (f_enum % 32)) * word_mask       # (W, 1)
        n_chunks = (cnt + C - 1) // C

        def body(ci, pa):
            row0 = start + ci * C
            ach = jax.lax.dynamic_slice(pa, (0, row0), (W, C))
            valid = ((ci * C + jax.lax.iota(jnp.int32, C)) < cnt)[None, :]
            return jax.lax.dynamic_update_slice(
                pa, jnp.where(valid, ach | bit, ach), (0, row0))

        return jax.lax.fori_loop(0, n_chunks, body, part_aux)

    def _allowed_from_used(self, used):
        """Interaction constraints (reference: col_sampler.hpp GetByNode):
        a node may split on the union of all constraint sets that contain
        every feature already used on its path."""
        compat = ~jnp.any(used[None, :] & ~self.ic_masks, axis=1)   # (C,)
        return jnp.any(self.ic_masks & compat[:, None], axis=0)     # (F,)

    def _bynode_mask(self, key):
        """feature_fraction_bynode sampling (reference: col_sampler.hpp
        SampleUsedFeaturesByNode approximated with a uniform-score top-k)."""
        k = max(int(round(self.F * self.frac_bynode)), 1)
        scores = jax.random.uniform(key, (self.F,))
        kth = jnp.sort(scores)[self.F - k]
        return scores >= kth

    @scopes.phase("search")
    def _leaf_best_split(self, hist_group, sum_g, sum_h, cnt, local_cnt,
                         depth, cmin, cmax, parent_out, feature_mask,
                         feat_used, *rest):
        # trailing optional operands in a fixed order (vmap needs flat
        # positional args): cegb-lazy counts, then extra_trees rand bins
        i = 0
        lazy_cnt = None
        if self.cegb_lazy is not None and len(rest) > i:
            lazy_cnt = rest[i]
            i += 1
        rand_bins = rest[i] if (self.extra_trees and len(rest) > i) else None
        if self.F == 0:   # no usable features: every tree is a stub
            z = jnp.float32(0.0)
            zi = jnp.int32(0)
            return split_ops.BestSplit(
                gain=jnp.float32(-jnp.inf), feature=zi, threshold=zi,
                default_left=jnp.bool_(False),
                left_sum_g=z, left_sum_h=z, right_sum_g=z, right_sum_h=z,
                left_count=zi, right_count=zi, left_output=z, right_output=z,
                is_cat=jnp.bool_(False),
                cat_set=jnp.zeros((self.BF,), jnp.bool_))
        if self.parallel_mode == "voting" and self.axis_name is not None:
            return self._leaf_best_split_voting(
                hist_group, sum_g, sum_h, cnt, local_cnt, depth, cmin, cmax,
                parent_out, feature_mask, feat_used, lazy_cnt=lazy_cnt,
                rand_bins=rand_bins)
        if self.plan.scatter_groups:
            # each device searches only the groups it owns post-scatter;
            # the election in _sync_best agrees on the global winner
            d = jax.lax.axis_index(self.axis_name)
            owned = (jax.lax.iota(jnp.int32, self.F)
                     // self._scatter_per) == d
            feature_mask = feature_mask & owned
        feat_hist = self._feat_view(hist_group, sum_g, sum_h)
        best = self._find_best(feat_hist, sum_g, sum_h, cnt, depth,
                               cmin, cmax, feature_mask, feat_used=feat_used,
                               parent_out=parent_out, lazy_cnt=lazy_cnt,
                               rand_bins=rand_bins)
        return self._depth_guard(best, depth)

    def _feat_view(self, hist_group, sum_g, sum_h):
        """(G, B, 2) group histogram -> (F, BF, 2) per-feature view with the
        default-bin stats of bundled features reconstructed from the leaf
        totals (reference: FixHistogram, cuda_histogram_constructor.cu:738)."""
        if self._plain_view:
            return hist_group[:, :self.BF]
        flat = hist_group.reshape(self.G * self.B, 2)
        flat = jnp.concatenate([flat, jnp.zeros((1, 2), dtype=flat.dtype)], axis=0)
        feat_hist = jnp.take(flat, self.feat_gather, axis=0)  # (F, BF, 2)
        known = feat_hist.sum(axis=1)
        fix = (jnp.stack([sum_g, sum_h]) - known) * self.fix_mask[:, None]
        return feat_hist.at[jnp.arange(self.F), self.default_pos].add(fix)

    @scopes.phase("search")
    def _find_best(self, feat_hist, sum_g, sum_h, cnt, depth, cmin, cmax,
                   feature_mask, feat_used=None, parent_out=None,
                   with_feature_gains=False, lazy_cnt=None,
                   rand_bins=None):
        cegb_delta = None
        if self.cegb_coupled is not None and feat_used is not None:
            cegb_delta = jnp.where(feat_used, 0.0, self.cegb_coupled)
        if self.cegb_lazy is not None and lazy_cnt is not None:
            lazy_term = self.cegb_lazy * lazy_cnt.astype(jnp.float32)
            cegb_delta = (lazy_term if cegb_delta is None
                          else cegb_delta + lazy_term)
        if self.plan.linear_gain:
            return split_ops.find_best_split_linear(
                feat_hist, self.ctx, sum_g, sum_h, cnt,
                self.l2, self.min_gain_to_split, self.min_data_in_leaf,
                self.min_sum_hessian, self._rep_vals, self.linear_lambda,
                feature_mask, rand_bins=rand_bins)
        if (self.plan.fast_search and cegb_delta is None
                and not with_feature_gains):
            return split_ops.find_best_split_fast(
                feat_hist, self.ctx, sum_g, sum_h, cnt,
                self.l1, self.l2, self.max_delta_step,
                self.min_gain_to_split, self.min_data_in_leaf,
                self.min_sum_hessian, feature_mask,
                rand_bins=rand_bins,
                feature_contri=self.feature_contri)
        return split_ops.find_best_split(
            feat_hist, self.ctx, sum_g, sum_h, cnt,
            self.l1, self.l2, self.max_delta_step, self.min_gain_to_split,
            self.min_data_in_leaf, self.min_sum_hessian, feature_mask,
            cat_params=self.cat_params,
            monotone=self.monotone if self.use_mc else None,
            cmin=cmin, cmax=cmax, depth=depth,
            monotone_penalty=self.monotone_penalty,
            cegb_count_coeff=self.cegb_count_coeff,
            cegb_feature_delta=cegb_delta,
            path_smooth=self.path_smooth,
            parent_output=parent_out,
            with_feature_gains=with_feature_gains,
            rand_bins=rand_bins,
            feature_contri=self.feature_contri)

    def _depth_guard(self, best, depth):
        depth_ok = (self.max_depth <= 0) | (depth < self.max_depth)
        gain = jnp.where(depth_ok, best.gain, -jnp.inf)
        return best._replace(gain=gain)

    # ------------------------------------------------------------------
    def _mc_refresh(self, st, lm, nleaves, feature_mask,
                    hist_scale=None):
        """Region-exact `intermediate` monotone mode.

        TPU-native replacement for the reference's recursive
        constraint-propagation walk (IntermediateLeafConstraints::
        GoUpToFindLeavesToUpdate + RecomputeBestSplitForLeaf,
        monotone_constraints.hpp:516-740, serial_tree_learner.cpp): every
        leaf carries its bin-range box (leaf_lo/leaf_hi over used
        features); two leaves are COMPARABLE along monotone feature m when
        their boxes overlap in every other feature and are disjoint along
        m.  Each split recomputes, from scratch, every leaf's output bounds
        from the current outputs of all comparable leaves — the sound
        fixed point the reference's incremental traversal approximates —
        then re-runs the split search for leaves whose bounds changed.
        Fully vectorized over (leaf x leaf) pairs; only traced when
        monotone_constraints_method selects it.
        """
        L = self.L
        lo = st["leaf_lo"][:L]                       # (L, F)
        hi = st["leaf_hi"][:L]
        vals = lm[LM_VALUE, :L]
        exist = jax.lax.iota(jnp.int32, L) < nleaves
        # pairwise per-feature box intersection: [row Y, col X, feature]
        inter = ((lo[:, None, :] <= hi[None, :, :]) &
                 (lo[None, :, :] <= hi[:, None, :]))
        miss = jnp.sum(~inter, axis=2)               # (L, L)
        pair_ok = exist[:, None] & exist[None, :]
        newmin = jnp.full((L,), -jnp.inf, jnp.float32)
        newmax = jnp.full((L,), jnp.inf, jnp.float32)
        for m, sign in zip(self.mono_enums, self.mono_signs):
            only_m = (miss - (~inter[:, :, m]).astype(jnp.int32)) == 0
            x_below = hi[None, :, m] < lo[:, None, m]    # X entirely below Y
            x_above = lo[None, :, m] > hi[:, None, m]
            lower = x_below if sign > 0 else x_above     # out(Y) >= out(X)
            upper = x_above if sign > 0 else x_below     # out(Y) <= out(X)
            lmask = only_m & lower & pair_ok
            umask = only_m & upper & pair_ok
            newmin = jnp.maximum(newmin, jnp.max(
                jnp.where(lmask, vals[None, :], -jnp.inf), axis=1))
            newmax = jnp.minimum(newmax, jnp.min(
                jnp.where(umask, vals[None, :], jnp.inf), axis=1))
        changed = exist & ((newmin != lm[LM_CMIN, :L]) |
                           (newmax != lm[LM_CMAX, :L]))
        lm = lm.at[LM_CMIN, :L].set(jnp.where(exist, newmin, lm[LM_CMIN, :L]))
        lm = lm.at[LM_CMAX, :L].set(jnp.where(exist, newmax, lm[LM_CMAX, :L]))
        # re-run the split search for every changed leaf (the reference
        # recomputes exactly the affected set; computing all-under-mask is
        # the vectorized equivalent)
        extra = ()
        if self.cegb_lazy is not None:
            # lazy counts are not re-derived on constraint refresh (the
            # cegb-lazy x intermediate-monotone interplay is not modeled)
            extra = (jnp.zeros((L, self.F), jnp.int32),)
        if self.extra_trees:
            # the constraint-refresh re-search draws fresh per-leaf random
            # thresholds from a fixed stream (the reference redraws on
            # every RecomputeBestSplitForLeaf call)
            base = jax.random.PRNGKey(self.extra_seed ^ 0x9E37)
            keys = jax.vmap(lambda i: jax.random.fold_in(base, i))(
                jnp.arange(L))
            extra = extra + (jax.vmap(self._rand_bins)(keys),)
        # per-leaf effective masks: interaction-constraint/bynode masks are
        # stored per leaf; under feature-parallel the device-local feature
        # shards are UNIONed so every device recomputes the identical
        # refresh (no _sync_best needed for a replicated computation)
        mask0 = feature_mask
        if self.axis_name is not None and self.parallel_mode == "feature":
            with scopes.scope("hist_sync"):
                mask0 = jax.lax.pmax(
                    feature_mask.astype(jnp.int32), self.axis_name) > 0
        masks = jnp.broadcast_to(mask0, (L, self.F))
        if "leaf_fmask" in st:
            masks = masks & st["leaf_fmask"][:L]
        best = self._best_split_vmapped(
            self._scale_hist(st["hist"][:L], hist_scale),
            lm[LM_SUM_G, :L], lm[LM_SUM_H, :L],
            _f2i(lm[LM_CNT_G, :L]), _f2i(lm[LM_CNT, :L]),
            _f2i(lm[LM_DEPTH, :L]), newmin, newmax, lm[LM_VALUE, :L],
            masks, st["feat_used"],
            *extra)
        overlay = {
            LM_BGAIN: best.gain,
            LM_BFEAT: _i2f(best.feature),
            LM_BTHR: _i2f(best.threshold),
            LM_BDL: best.default_left.astype(jnp.float32),
            LM_BLCNT: _i2f(best.left_count),
            LM_BRCNT: _i2f(best.right_count),
            LM_BLSG: best.left_sum_g, LM_BLSH: best.left_sum_h,
            LM_BRSG: best.right_sum_g, LM_BRSH: best.right_sum_h,
            LM_BLOUT: best.left_output, LM_BROUT: best.right_output,
            LM_BISCAT: best.is_cat.astype(jnp.float32),
        }
        for row, new in overlay.items():
            lm = lm.at[row, :L].set(jnp.where(changed, new, lm[row, :L]))
        if not self.has_categorical:
            return lm, None
        cat = st["best_cat_set"]
        cat = cat.at[:L].set(jnp.where(changed[:, None], best.cat_set,
                                       cat[:L]))
        return lm, cat

    def _child_boxes(self, st, bl_oh, f_enum, is_cat, mtype, nb, dbin,
                     dl, thr):
        """The two children's bin-range boxes for the split being applied:
        parent box tightened along the split feature for numerical splits
        (categorical boxes stay whole — conservative).  Rows in the
        default/missing bin follow default_left regardless of the
        threshold: when that bin falls on the far side, the
        default-direction child's box must stay un-tightened along the
        split feature or the pairwise comparability test would wrongly
        exclude rows the child actually contains."""
        F = self.F
        prow_lo = jnp.max(
            jnp.where(bl_oh[:, None], st["leaf_lo"], 0), axis=0)
        prow_hi = jnp.max(
            jnp.where(bl_oh[:, None], st["leaf_hi"], 0), axis=0)
        f1h = jax.lax.broadcasted_iota(jnp.int32, (F,), 0) == f_enum
        tighten = f1h & ~is_cat
        d_eff = jnp.where(mtype == 2, nb - 1, dbin)
        has_miss = mtype != 0
        miss_l = has_miss & dl & (d_eff > thr)
        miss_r = has_miss & (~dl) & (d_eff <= thr)
        l_hi = jnp.where(tighten & ~miss_l,
                         jnp.minimum(prow_hi, thr), prow_hi)
        r_lo = jnp.where(tighten & ~miss_r,
                         jnp.maximum(prow_lo, thr + 1), prow_lo)
        return prow_lo, prow_hi, l_hi, r_lo

    def _advanced_bounds(self, lo_all, hi_all, vals, exist, c_lo, c_hi):
        """Per-(feature, threshold) output bounds for ONE candidate child
        box — the vectorized analog of the reference's advanced
        constraint segments (AdvancedLeafConstraints::UpdateConstraints +
        ComputeConstraintsPerThreshold, monotone_constraints.hpp:858).

        For a split of this child's box on feature f at threshold t, the
        LEFT grandchild covers f-bins [c_lo[f], t] and the RIGHT
        (t+1, c_hi[f]]; a leaf X constrains a grandchild iff X's box is
        disjoint from the child's range along the monotone feature m,
        overlaps it in every other feature, and overlaps the
        grandchild's f-range.  The t-dependence is monotone in t, so
        each bound array is a scatter of leaf outputs at box edges
        followed by a prefix (left) / shifted-suffix (right) running
        extremum over the bin axis.

        Args:
          lo_all/hi_all: (L, F) all leaves' bin boxes; vals: (L,) leaf
          outputs; exist: (L,) liveness; c_lo/c_hi: (F,) this child's box.
        Returns (cmin_l, cmax_l, cmin_r, cmax_r), each (F, BF).
        """
        F, BF, L = self.F, self.BF, lo_all.shape[0]
        inter = (lo_all <= c_hi[None, :]) & (c_lo[None, :] <= hi_all)
        miss = jnp.sum(~inter, axis=1)                    # (L,)
        f_idx = jnp.broadcast_to(jnp.arange(F)[None, :], (L, F))
        lo_c = jnp.clip(lo_all, 0, BF - 1)
        hi_c = jnp.clip(hi_all, 0, BF - 1)
        neg = jnp.float32(-jnp.inf)
        pos = jnp.float32(jnp.inf)
        cmin_l = jnp.full((F, BF), neg)
        cmax_l = jnp.full((F, BF), pos)
        cmin_r = jnp.full((F, BF), neg)
        cmax_r = jnp.full((F, BF), pos)

        def scat_max(mask, at):
            return jnp.full((F, BF), neg).at[f_idx, at].max(
                jnp.where(mask, vals[:, None], neg))

        def scat_min(mask, at):
            return jnp.full((F, BF), pos).at[f_idx, at].min(
                jnp.where(mask, vals[:, None], pos))

        def prefix_max(a):
            return jax.lax.associative_scan(jnp.maximum, a, axis=1)

        def prefix_min(a):
            return jax.lax.associative_scan(jnp.minimum, a, axis=1)

        def shifted_suffix_max(a):
            # out[t] = max over b > t of a[b]
            s = jax.lax.associative_scan(jnp.maximum, a, axis=1,
                                         reverse=True)
            return jnp.concatenate(
                [s[:, 1:], jnp.full((F, 1), neg)], axis=1)

        def shifted_suffix_min(a):
            s = jax.lax.associative_scan(jnp.minimum, a, axis=1,
                                         reverse=True)
            return jnp.concatenate(
                [s[:, 1:], jnp.full((F, 1), pos)], axis=1)

        for m, sign in zip(self.mono_enums, self.mono_signs):
            miss_ex_m = miss - (~inter[:, m]).astype(jnp.int32)
            x_below = hi_all[:, m] < c_lo[m]
            x_above = lo_all[:, m] > c_hi[m]
            # X whose outputs FLOOR this child (lower set) / CAP it
            lower = (x_below if sign > 0 else x_above) & exist
            upper = (x_above if sign > 0 else x_below) & exist

            # --- split feature f != m: X disjoint along m vs the FULL
            # child range, overlap in every feature except m and f, and
            # f-range overlap with the grandchild's shrunken f-range
            ok_f = (miss_ex_m[:, None]
                    - (~inter).astype(jnp.int32)) == 0     # (L, F)
            not_m = jnp.arange(F)[None, :] != m
            base_l = ok_f & not_m & (hi_all >= c_lo[None, :])
            base_r = ok_f & not_m & (lo_all <= c_hi[None, :])
            # left grandchild [c_lo, t]: applies once t >= X.lo[f]
            cmin_l = jnp.maximum(cmin_l, prefix_max(
                scat_max(base_l & lower[:, None], lo_c)))
            cmax_l = jnp.minimum(cmax_l, prefix_min(
                scat_min(base_l & upper[:, None], lo_c)))
            # right grandchild (t, c_hi]: applies while t < X.hi[f]
            cmin_r = jnp.maximum(cmin_r, shifted_suffix_max(
                scat_max(base_r & lower[:, None], hi_c)))
            cmax_r = jnp.minimum(cmax_r, shifted_suffix_min(
                scat_min(base_r & upper[:, None], hi_c)))

            # --- split ON m itself (the reference's inner-feature case):
            # the grandchild's m-range shrinks, so disjointness is judged
            # against it; only overlap-except-m is required of X
            ok_m = (miss_ex_m == 0) & exist
            onec = (jnp.arange(F) == m).astype(jnp.float32)[:, None]
            # left grandchild [c_lo[m], t]:
            #   X above it iff X.lo[m] > t  (bound fades as t grows)
            #   X below it iff X.hi[m] < c_lo[m]  (t-independent)
            above_l = shifted_suffix_max(
                scat_max((ok_m & ~x_below)[:, None]
                         & (jnp.arange(F)[None, :] == m), lo_c)) \
                if sign < 0 else shifted_suffix_min(
                scat_min((ok_m & ~x_below)[:, None]
                         & (jnp.arange(F)[None, :] == m), lo_c))
            below_vals_min = jnp.max(jnp.where(ok_m & x_below, vals, neg)) \
                if sign > 0 else None
            below_vals_max = jnp.min(jnp.where(ok_m & x_below, vals, pos)) \
                if sign < 0 else None
            if sign > 0:
                # above-X caps the left grandchild; below-X floors it
                cmax_l = jnp.minimum(cmax_l, jnp.where(
                    onec > 0, above_l, pos))
                cmin_l = jnp.maximum(cmin_l, jnp.where(
                    onec > 0, below_vals_min, neg))
            else:
                cmin_l = jnp.maximum(cmin_l, jnp.where(
                    onec > 0, above_l, neg))
                cmax_l = jnp.minimum(cmax_l, jnp.where(
                    onec > 0, below_vals_max, pos))
            # right grandchild (t, c_hi[m]]:
            #   X below it iff X.hi[m] <= t  (bound grows with t)
            #   X above it iff X.lo[m] > c_hi[m]  (t-independent)
            below_r = prefix_max(
                scat_max((ok_m & ~x_above)[:, None]
                         & (jnp.arange(F)[None, :] == m), hi_c)) \
                if sign > 0 else prefix_min(
                scat_min((ok_m & ~x_above)[:, None]
                         & (jnp.arange(F)[None, :] == m), hi_c))
            above_vals_max = jnp.min(jnp.where(ok_m & x_above, vals, pos)) \
                if sign > 0 else None
            above_vals_min = jnp.max(jnp.where(ok_m & x_above, vals, neg)) \
                if sign < 0 else None
            if sign > 0:
                cmin_r = jnp.maximum(cmin_r, jnp.where(
                    onec > 0, below_r, neg))
                cmax_r = jnp.minimum(cmax_r, jnp.where(
                    onec > 0, above_vals_max, pos))
            else:
                cmax_r = jnp.minimum(cmax_r, jnp.where(
                    onec > 0, below_r, pos))
                cmin_r = jnp.maximum(cmin_r, jnp.where(
                    onec > 0, above_vals_min, neg))
        return cmin_l, cmax_l, cmin_r, cmax_r

    @scopes.phase("search")
    def _leaf_best_split_voting(self, hist_local, sum_g, sum_h, cnt,
                                local_cnt, depth, cmin, cmax, parent_out,
                                feature_mask, feat_used=None, lazy_cnt=None,
                                rand_bins=None):
        """PV-Tree voting split search (reference:
        voting_parallel_tree_learner.cpp): each device votes its top-k
        features by LOCAL gain, the global top-2k features are elected by
        vote count (psum replaces the Allgather of LightSplitInfo votes,
        :364), and only the elected features' group histograms cross ICI —
        a fixed-size (<= 2*top_k, B, 2) gather-psum-scatter standing in for
        the sparse ReduceScatter (:387) — before the final, globally
        identical split evaluation (best-split sync, :465)."""
        ax = self.axis_name
        # local leaf totals: every feature group covers all rows, so group 0
        # sums to the local (grad, hess) totals of the leaf
        local_sum_g = hist_local[0, :, 0].sum()
        local_sum_h = hist_local[0, :, 1].sum()
        feat_hist_loc = self._feat_view(hist_local, local_sum_g, local_sum_h)
        _, gains_loc = self._find_best(
            feat_hist_loc, local_sum_g, local_sum_h, local_cnt, depth,
            cmin, cmax, feature_mask, feat_used=feat_used,
            parent_out=parent_out, with_feature_gains=True)
        k = min(self.top_k, self.F)
        topv, topi = jax.lax.top_k(gains_loc, k)
        votes = jnp.zeros((self.F,), jnp.int32).at[topi].add(
            jnp.isfinite(topv).astype(jnp.int32))
        with scopes.scope("hist_sync"):
            votes_g = jax.lax.psum(votes, ax)
        # elect 2k features by vote count; smaller feature index breaks ties
        ek = min(2 * self.top_k, self.F)
        fiota = jnp.arange(self.F, dtype=jnp.int32)
        score = votes_g * jnp.int32(self.F) + (jnp.int32(self.F) - 1 - fiota)
        _, elected = jax.lax.top_k(score, ek)
        elected_mask = jnp.zeros((self.F,), jnp.bool_).at[elected].set(True)
        # sync ONLY the elected features' groups: ek is static, so the
        # collective payload is (ek, B, 2) regardless of G
        eg = self.f_group[elected]                      # (ek,) group ids
        with scopes.scope("hist_sync"):
            sub_glob = jax.lax.psum(jnp.take(hist_local, eg, axis=0), ax)
        hist_glob = jnp.zeros_like(hist_local).at[eg].set(sub_glob)
        feat_hist = self._feat_view(hist_glob, sum_g, sum_h)
        best = self._find_best(feat_hist, sum_g, sum_h, cnt, depth,
                               cmin, cmax, feature_mask & elected_mask,
                               feat_used=feat_used, parent_out=parent_out,
                               lazy_cnt=lazy_cnt, rand_bins=rand_bins)
        return self._depth_guard(best, depth)

    # ------------------------------------------------------------------
    def _pvary(self, x):
        """Mark a value as device-varying for shard_map's vma type system
        (loop carries initialized from constants need this under SPMD)."""
        if self.axis_name is None:
            return x

        def mark(a):
            if self.axis_name in jax.typeof(a).vma:
                return a
            return jax.lax.pcast(a, (self.axis_name,), to="varying")

        return jax.tree.map(mark, x)

    @scopes.phase("hist_sync")
    def _psum(self, x):
        """Histogram sync: global sums only in data-parallel mode (voting
        keeps leaf histograms LOCAL and syncs only elected features at
        split-evaluation time).

        With tpu_data_hist_sync="scatter" the reference's ReduceScatter
        ownership is preserved (data_parallel_tree_learner.cpp:282-296):
        psum_scatter hands each device the GLOBAL sums of its OWN group
        slice only (each element crosses the wire once, vs ndev times
        for the full psum), the non-owned groups stay zero, the search
        masks to owned features, and the winner is elected by the same
        all-gather arg-max the feature-parallel mode uses."""
        if self.axis_name is not None and self.parallel_mode == "data":
            if self.plan.scatter_groups:
                per = self._scatter_per
                Gp = per * self.num_shards
                xp = jnp.pad(x, ((0, Gp - self.G), (0, 0), (0, 0)))
                own = jax.lax.psum_scatter(
                    xp.reshape(self.num_shards, per, *x.shape[1:]),
                    self.axis_name, scatter_dimension=0, tiled=False)
                d = jax.lax.axis_index(self.axis_name)
                full = jnp.zeros((Gp,) + x.shape[1:], x.dtype)
                full = jax.lax.dynamic_update_slice(
                    full, own, (d * per,) + (0,) * (x.ndim - 1))
                return full[:self.G]
            return jax.lax.psum(x, self.axis_name)
        return x

    @scopes.phase("hist_sync")
    def _psum_scalar(self, x):
        """Row-statistic sync (counts, grad/hess totals): rows are sharded
        in both data- and voting-parallel modes."""
        if self.axis_name is not None and self.parallel_mode in ("data",
                                                                 "voting"):
            return jax.lax.psum(x, self.axis_name)
        return x

    @scopes.phase("hist_sync")
    def _sync_best(self, best):
        """Agree on the global best split across feature-sharded devices
        (reference: SyncUpGlobalBestSplit, parallel_tree_learner.h:209-232).
        Also elects the winner under ReduceScatter histogram ownership
        (data-parallel scatter mode): devices are ordered by owned
        feature range, so the arg-max's first-max tie-break matches the
        serial scan order."""
        if self.axis_name is None or not (
                self.parallel_mode == "feature" or self.plan.scatter_groups):
            return best
        gathered = jax.tree.map(
            lambda a: jax.lax.all_gather(a, self.axis_name), best)
        winner = jnp.argmax(gathered.gain)
        return jax.tree.map(lambda a: a[winner], gathered)

    @scopes.phase("bookkeeping")
    def _build_tree_impl(self, part_bins, part_ghi0, bag_cnt,
                         feature_mask, seed, feat_used_init=None, aux0=None,
                         hist_scale=None):
        """Core tree loop over a prebuilt (8, N_pad) row payload whose
        rows are (grad, hess, rowid-bits, extras...); the extras ride the
        partition untouched (physical-order fused step).

        Scope: whatever the build issues outside a narrower ``lgbm.*``
        scope (histogram, hist_state, search, partition, split_mega) is
        ``lgbm.bookkeeping``: leaf election, the packed-scalar gathers,
        node/leaf matrix writes, the frontier replay, renumbering and the
        undo of pruned partitions."""
        if self.plan.frontier_k > 1:
            # batched frontier growth (the eligibility gate guarantees
            # feat_used_init/aux0 are absent: no CEGB in batched mode)
            return self._build_tree_frontier(part_bins, part_ghi0, bag_cnt,
                                             feature_mask, hist_scale)
        L, G, B, F = self.L, self.G, self.B, self.F
        nodes = self.max_splits
        rng0 = jax.random.PRNGKey(seed)

        root_mask = feature_mask
        if self.ic_masks is not None:
            root_mask = root_mask & self._allowed_from_used(
                jnp.zeros((F,), jnp.bool_))
        if self.has_bynode:
            root_mask = root_mask & self._bynode_mask(
                jax.random.fold_in(rng0, 0))
        # coupled CEGB penalties persist across trees: the caller threads the
        # model-lifetime used-feature set back in each iteration (reference:
        # CostEfficientGradientBoosting::is_feature_used_in_split_)
        feat_used0 = (jnp.zeros((F,), jnp.bool_) if feat_used_init is None
                      else feat_used_init)

        root_local = self._hist_leaf(
            part_bins, part_ghi0, jnp.int32(self.row0), jnp.int32(self.N),
            scale=hist_scale)
        root_hist = self._psum(root_local)
        bag_cnt_g = self._psum_scalar(bag_cnt)
        # in voting mode root_hist stays LOCAL; in scatter mode only the
        # owned groups survive in root_hist — either way the leaf totals
        # come from the LOCAL histogram reduced across ranks
        if self.parallel_mode == "voting" or self.plan.scatter_groups:
            sum_g = self._psum_scalar(root_local[0, :, 0].sum())
            sum_h = self._psum_scalar(root_local[0, :, 1].sum())
        else:
            sum_g = root_hist[0, :, 0].sum()
            sum_h = root_hist[0, :, 1].sum()
        if hist_scale is not None:
            # integer-domain quantized totals -> gain domain (once)
            sum_g = sum_g * hist_scale[0]
            sum_h = sum_h * hist_scale[1]
        neg_inf = jnp.float32(-jnp.inf)
        pos_inf = jnp.float32(jnp.inf)
        lazy_extra = ()
        if self.cegb_lazy is not None:
            if aux0 is None:
                aux0 = jnp.zeros((self.aux_rows, part_bins.shape[1]),
                                 jnp.int32)
            lazy_extra = (self._lazy_counts(
                aux0, jnp.int32(self.row0), jnp.int32(self.N),
                jnp.int32(0))[0],)
        rngx = None
        if self.extra_trees:
            rngx = jax.random.fold_in(
                jax.random.PRNGKey(self.extra_seed), seed)
            lazy_extra = lazy_extra + (
                self._rand_bins(jax.random.fold_in(rngx, 0)),)
        best0 = self._sync_best(self._leaf_best_split(
            self._scale_hist(root_hist, hist_scale), sum_g, sum_h,
            bag_cnt_g, bag_cnt, jnp.int32(0),
            neg_inf, pos_inf, jnp.float32(0.0), root_mask, feat_used0,
            *lazy_extra))

        # one TRASH slot is appended to every leaf/node-indexed buffer:
        # iterations whose split is invalid (stop, or an abandoned forced
        # split) still execute the body but write to the trash column, so the
        # while body needs NO lax.cond — conditionals force whole-state
        # copies of the multi-MB row buffers every iteration (measured ~60%
        # of the tree build).
        root_forced = jnp.int32(0 if self.forced is not None else -1)
        col0 = jnp.stack([
            _i2f(self.row0), _i2f(self.N), _i2f(bag_cnt_g),
            sum_g, sum_h, _i2f(0),
            jnp.float32(-jnp.inf), jnp.float32(jnp.inf),
            jnp.float32(0.0), _i2f(-1), _i2f(0),
            best0.gain, _i2f(best0.feature), _i2f(best0.threshold),
            best0.default_left.astype(jnp.float32),
            _i2f(best0.left_count), _i2f(best0.right_count),
            best0.left_sum_g, best0.left_sum_h,
            best0.right_sum_g, best0.right_sum_h,
            best0.left_output, best0.right_output,
            best0.is_cat.astype(jnp.float32), _i2f(root_forced)])
        if self.plan.linear_gain:
            # the root's own whole-leaf model from its search (a
            # root-only tree still predicts linearly)
            col0 = jnp.concatenate([col0, jnp.stack([
                best0.self_const, best0.self_coeff,
                _i2f(best0.self_feature)])])
        leafmat = jnp.zeros((self._nlf, L + 1), jnp.float32) \
            .at[LM_BGAIN].set(jnp.float32(NEG_INF)) \
            .at[LM_CMIN].set(jnp.float32(-jnp.inf)) \
            .at[LM_CMAX].set(jnp.float32(jnp.inf)) \
            .at[LM_PARENT].set(_i2f(jnp.full((L + 1,), -1, jnp.int32))) \
            .at[LM_FORCED].set(_i2f(jnp.full((L + 1,), -1, jnp.int32))) \
            .at[:, 0].set(col0)

        use_mega = self.plan.mega != "off"
        use_flat = (self.plan.hist_state == "flat" and hist_scale is None
                    and not use_mega)
        state = {
            "s": jnp.int32(0),
            "done": jnp.bool_(False),
            "part_bins": part_bins,
            "part_ghi": part_ghi0,
            "leafmat": leafmat,
            "nodemat": jnp.zeros((NND, nodes + 1), jnp.float32),
            "feat_used": feat_used0,
        }
        if not use_mega:
            # the mega path computes BOTH children's histograms per split
            # and consumes them in-register: no per-leaf histogram state
            # rides the while loop at all (and with it go the two
            # contextual full-state copies per split — PERF.md round 4)
            with scopes.scope("hist_state"):
                if use_flat:
                    state["hist"] = jnp.zeros(
                        (L + 1, 8, self._flat_geom[2]),
                        jnp.float32).at[0].set(self._flatten_hist(root_hist))
                else:
                    state["hist"] = jnp.zeros(
                        (L + 1, G, B, 2),
                        dtype=jnp.float32).at[0].set(root_hist)
        if self.has_categorical:
            state["best_cat_set"] = jnp.zeros(
                (L + 1, self.BF), jnp.bool_).at[0].set(best0.cat_set)
            state["node_cat_set"] = jnp.zeros((nodes + 1, self.BF),
                                              jnp.bool_)
        if self.plan.partition == "pallas":
            from ..ops.partition_pallas import sc_rows_for
            state["sc_packed"] = jnp.zeros(
                (sc_rows_for(self.plan.pass_rows), part_bins.shape[1]),
                jnp.int32)
        else:
            state["sc32"] = jnp.zeros((G + self._ghi_rows,
                                       part_bins.shape[1]), jnp.int32)

        if self.ic_masks is not None:
            state["leaf_used"] = jnp.zeros((L + 1, F), jnp.bool_)

        if self.cegb_lazy is not None:
            state["part_aux"] = aux0
            state["sc_aux"] = jnp.zeros_like(aux0)

        if self.use_mc and self.mc_mode in ("intermediate", "advanced"):
            # root box covers every bin of every used feature
            state["leaf_lo"] = jnp.zeros((L + 1, F), jnp.int32)
            state["leaf_hi"] = jnp.broadcast_to(
                self.ctx.num_bin - 1, (L + 1, F)).astype(jnp.int32)
            if self.ic_masks is not None or self.has_bynode:
                # per-leaf effective feature masks so the constraint
                # refresh re-search honors interaction/bynode restrictions
                state["leaf_fmask"] = jnp.broadcast_to(
                    root_mask, (L + 1, F)).astype(jnp.bool_)

        # uniform vma typing under shard_map: mark the whole state varying
        state = self._pvary(state)

        def cond(st):
            return (st["s"] < nodes) & (~st["done"])

        def body(st):
            lm = st["leafmat"]
            bgain_row = lm[LM_BGAIN, :L]
            best_leaf = jnp.argmax(bgain_row).astype(jnp.int32)
            gain = bgain_row[best_leaf]

            # forced splits take precedence over the free search
            # (reference: ForceSplits, serial_tree_learner.cpp:614)
            forced_ok = jnp.bool_(False)
            skip_pending = jnp.bool_(False)
            forced_node = jnp.int32(0)
            forced_info = None
            if self.forced is not None:
                fids = _f2i(lm[LM_FORCED, :L])
                f_leaf = jnp.argmax(fids >= 0).astype(jnp.int32)
                has_f = jnp.any(fids >= 0)
                forced_node = jnp.maximum(fids[f_leaf], 0)
                fcol = jax.lax.dynamic_slice(
                    lm, (0, f_leaf), (self._nlf, 1))[:, 0]
                forced_info = self._forced_split_info(
                    self._scale_hist(st["hist"][f_leaf], hist_scale),
                    self.forced["feature"][forced_node],
                    self.forced["bin"][forced_node],
                    fcol[LM_SUM_G], fcol[LM_SUM_H], _f2i(fcol[LM_CNT_G]))
                depth_ok = (self.max_depth <= 0) | \
                    (_f2i(fcol[LM_DEPTH]) < self.max_depth)
                forced_ok = has_f & forced_info["valid"] & depth_ok
                # a failed forced split is abandoned WITHOUT consuming a
                # split step; free search resumes next iteration
                skip_pending = has_f & ~forced_ok
                st = {**st, "leafmat": jnp.where(
                    skip_pending,
                    lm.at[LM_FORCED, f_leaf].set(_i2f(-1)), lm)}
                lm = st["leafmat"]
                best_leaf = jnp.where(forced_ok, f_leaf, best_leaf)
                gain = jnp.where(forced_ok, forced_info["gain"], gain)

            # an invalid iteration still runs the body but writes to the
            # TRASH slots and processes 0 rows — no lax.cond, no copies
            valid = forced_ok | ((gain > 0) & ~skip_pending)

            # one read of the chosen leaf's packed scalars
            pcol = jax.lax.dynamic_slice(lm, (0, best_leaf),
                                         (self._nlf, 1))[:, 0]

            adv_cat_set = None
            adv_reject = jnp.bool_(False)
            if self.use_mc and self.mc_mode == "advanced":
                # re-search the CHOSEN leaf with per-threshold bounds
                # before executing its split: the stored (refresh) search
                # used whole-box scalars, which both clamps child outputs
                # and can reject splits the advanced segments allow.
                # Leaf SELECTION keeps the conservative stored gains (one
                # advanced search per executed split keeps the cost
                # linear; the reference's advanced mode is similarly the
                # slow path).
                bl1 = jax.lax.iota(jnp.int32, L + 1) == best_leaf
                y_lo = jnp.max(jnp.where(bl1[:, None], st["leaf_lo"], 0),
                               axis=0)
                y_hi = jnp.max(jnp.where(bl1[:, None], st["leaf_hi"], 0),
                               axis=0)
                ab = self._advanced_bounds(
                    st["leaf_lo"][:L], st["leaf_hi"][:L],
                    lm[LM_VALUE, :L],
                    jax.lax.iota(jnp.int32, L) < (st["s"] + 1),
                    y_lo, y_hi)
                # the advanced arrays already encode every comparable
                # leaf; the leaf's own whole-box scalars (LM_CMIN/CMAX)
                # bound its VALUE, not its children, and folding them in
                # would collapse advanced back to intermediate
                cmin_t = (ab[0], ab[2])
                cmax_t = (ab[1], ab[3])
                maskY = feature_mask
                if "leaf_fmask" in st:
                    maskY = maskY & jnp.any(
                        st["leaf_fmask"] & bl1[:, None], axis=0)
                adv_extra = ()
                if self.cegb_lazy is not None:
                    # cegb-lazy counts are not re-derived here (same
                    # stance as the constraint refresh)
                    adv_extra = (jnp.zeros((2, F), jnp.int32),)
                if self.extra_trees:
                    adv_extra = adv_extra + (self._rand_bins(
                        jax.random.fold_in(
                            jax.random.PRNGKey(self.extra_seed ^ 0x51AD),
                            st["s"])),)
                adv = self._sync_best(self._leaf_best_split(
                    self._scale_hist(st["hist"][best_leaf], hist_scale),
                    pcol[LM_SUM_G],
                    pcol[LM_SUM_H], _f2i(pcol[LM_CNT_G]),
                    _f2i(pcol[LM_CNT]), _f2i(pcol[LM_DEPTH]),
                    cmin_t, cmax_t, pcol[LM_VALUE], maskY,
                    st["feat_used"], *adv_extra))
                pcol = pcol.at[LM_BGAIN].set(adv.gain) \
                    .at[LM_BFEAT].set(_i2f(adv.feature)) \
                    .at[LM_BTHR].set(_i2f(adv.threshold)) \
                    .at[LM_BDL].set(adv.default_left.astype(jnp.float32)) \
                    .at[LM_BLCNT].set(_i2f(adv.left_count)) \
                    .at[LM_BRCNT].set(_i2f(adv.right_count)) \
                    .at[LM_BLSG].set(adv.left_sum_g) \
                    .at[LM_BLSH].set(adv.left_sum_h) \
                    .at[LM_BRSG].set(adv.right_sum_g) \
                    .at[LM_BRSH].set(adv.right_sum_h) \
                    .at[LM_BLOUT].set(adv.left_output) \
                    .at[LM_BROUT].set(adv.right_output) \
                    .at[LM_BISCAT].set(adv.is_cat.astype(jnp.float32))
                if self.has_categorical:
                    adv_cat_set = adv.cat_set
                stored_gain = gain
                gain = jnp.where(forced_ok, gain, adv.gain)
                valid = forced_ok | ((gain > 0) & ~skip_pending)
                # persist the advanced gain into the leafmat: when the
                # re-search REJECTS a split the stored (conservative)
                # positive gain would re-select this leaf forever; the
                # write also keeps future leaf selection on the advanced
                # basis.  (Lane-dynamic column write — the fast pattern.)
                lm = jnp.where(forced_ok, lm,
                               lm.at[LM_BGAIN, best_leaf].set(adv.gain))
                st = {**st, "leafmat": lm}
                # a rejection consumes NO split step and must not end
                # the tree: other leaves may still carry positive gains
                # (their next argmax sees the demoted gain just written)
                adv_reject = ~forced_ok & ~skip_pending \
                    & (adv.gain <= 0) & (stored_gain > 0)

            if True:
                s = st["s"]
                new_leaf = s + 1
                wr_a = jnp.where(valid, best_leaf, jnp.int32(L))
                wr_b = jnp.where(valid, new_leaf, jnp.int32(L))
                wr_s = jnp.where(valid, s, jnp.int32(nodes))
                f_enum = _f2i(pcol[LM_BFEAT])
                thr = _f2i(pcol[LM_BTHR])
                dl = pcol[LM_BDL] > 0.5
                is_cat = pcol[LM_BISCAT] > 0.5
                # row reads/writes on (L, ...) matrices use masked
                # reductions/selects: dynamic indexing on the SUBLANE axis
                # lowers to a slow per-tile path (~80us per occurrence,
                # measured; the masked forms are plain VPU passes)
                bl_oh = jax.lax.iota(jnp.int32, L + 1) == best_leaf
                if self.has_categorical:
                    cat_set = (adv_cat_set if adv_cat_set is not None else
                               jnp.any(st["best_cat_set"] & bl_oh[:, None],
                                       axis=0))
                else:
                    cat_set = jnp.zeros((1,), jnp.bool_)
                if forced_info is not None:
                    f_enum = jnp.where(forced_ok,
                                       self.forced["feature"][forced_node],
                                       f_enum)
                    thr = jnp.where(forced_ok, forced_info["threshold"], thr)
                    dl = jnp.where(forced_ok, True, dl)
                    is_cat = jnp.where(forced_ok, False, is_cat)
                    cat_set = jnp.where(forced_ok,
                                        jnp.zeros_like(cat_set), cat_set)
                # ONE lane-dynamic column slice replaces ~8 scalar
                # dynamic-indexes into the per-feature metadata vectors
                fcolm = jax.lax.dynamic_slice(
                    self._fmeta, (0, f_enum), (self._fmeta.shape[0], 1))[:, 0]
                (orig_feat, col, bstart, isb, nb, dbin, mtype,
                 mono_f) = (fcolm[0], fcolm[1], fcolm[2], fcolm[3],
                            fcolm[4], fcolm[5], fcolm[6], fcolm[7])
                start = _f2i(pcol[LM_START])
                cnt = jnp.where(valid, _f2i(pcol[LM_CNT]), 0)
                cnt_g = _f2i(pcol[LM_CNT_G])

                mega_hists = None
                if use_mega:
                    moved, left_cnt, mega_hists = self._split_leaf_mega(
                        st, start, cnt, col,
                        (bstart, isb, nb, dbin, mtype, thr, dl, is_cat,
                         cat_set), hist_scale)
                else:
                    moved, left_cnt = self._partition_leaf(
                        st, start, cnt, col,
                        (bstart, isb, nb, dbin, mtype, thr, dl, is_cat,
                         cat_set))
                right_cnt = cnt - left_cnt
                # bag-aware counts come from the (global) histogram estimate
                # cached with the best split, not from physical range sizes:
                # out-of-bag rows live in the ranges with zeroed gradients
                left_cnt_g = _f2i(pcol[LM_BLCNT])
                right_cnt_g = _f2i(pcol[LM_BRCNT])
                if forced_info is not None:
                    left_cnt_g = jnp.where(forced_ok, forced_info["lcnt"],
                                           left_cnt_g)
                    right_cnt_g = jnp.where(forced_ok, forced_info["rcnt"],
                                            right_cnt_g)
                l_start = start
                r_start = start + left_cnt

                # smaller child's histogram; larger by subtraction.  The
                # smaller/larger choice must use GLOBAL counts so every
                # device computes (and psums) the same child's histogram.
                # (On the mega path BOTH children came from the kernel —
                # no subtraction, no histogram state.)
                if not use_mega:
                    small_is_left = left_cnt_g <= right_cnt_g
                    sm_start = jnp.where(small_is_left, l_start, r_start)
                    sm_cnt = jnp.where(small_is_left, left_cnt, right_cnt)
                if use_mega:
                    hist = None
                    hist_left = hist_right = None
                    if self.plan.search != "pallas":
                        hl_g, hl_h, hr_g, hr_h = mega_hists
                        hist_left = jnp.stack(
                            [hl_g[:, :B], hl_h[:, :B]], axis=2)
                        hist_right = jnp.stack(
                            [hr_g[:, :B], hr_h[:, :B]], axis=2)
                elif use_flat:
                    # in-place one-row DMA read/subtract/write of the
                    # lane-flattened state (ops/hist_state_pallas.py) —
                    # replaces the dynamic-slice formulation whose
                    # contextual full-state copies cost ~7 ms/iter
                    from ..ops.hist_state_pallas import hist_rmw_pallas
                    small_flat = self._hist_leaf_flat(
                        moved["part_bins"], moved["part_ghi"],
                        sm_start, sm_cnt)
                    with scopes.scope("hist_state"):
                        hist, hl_flat, hr_flat = hist_rmw_pallas(
                            st["hist"], small_flat,
                            jnp.stack([best_leaf, wr_a, wr_b,
                                       small_is_left.astype(jnp.int32)]),
                            interpret=self._interp)
                    hist_left = hist_right = None
                else:
                    hist_small = self._psum(self._hist_leaf(
                        moved["part_bins"], moved["part_ghi"],
                        sm_start, sm_cnt, scale=hist_scale))
                    with scopes.scope("hist_state"):
                        # the parent's slot is read out before either
                        # child is written: fused into the second write
                        # the read keeps the old state alive across the
                        # first, which costs a copy of the whole state a
                        # split (1 GB at 2000 features; PERF.md, PR 35)
                        parent_hist, state_hist = \
                            jax.lax.optimization_barrier(
                                (st["hist"][best_leaf], st["hist"]))
                        hist_large = parent_hist - hist_small
                        hist_left = jnp.where(small_is_left, hist_small,
                                              hist_large)
                        hist_right = jnp.where(small_is_left, hist_large,
                                               hist_small)
                        hist = state_hist.at[wr_a].set(
                            hist_left).at[wr_b].set(hist_right)

                lsg = pcol[LM_BLSG]
                lsh = pcol[LM_BLSH]
                rsg = pcol[LM_BRSG]
                rsh = pcol[LM_BRSH]
                lout = pcol[LM_BLOUT]
                rout = pcol[LM_BROUT]
                if forced_info is not None:
                    lsg = jnp.where(forced_ok, forced_info["lsg"], lsg)
                    lsh = jnp.where(forced_ok, forced_info["lsh"], lsh)
                    rsg = jnp.where(forced_ok, forced_info["rsg"], rsg)
                    rsh = jnp.where(forced_ok, forced_info["rsh"], rsh)
                    lout = jnp.where(forced_ok, forced_info["lout"], lout)
                    rout = jnp.where(forced_ok, forced_info["rout"], rout)
                depth_child = _f2i(pcol[LM_DEPTH]) + 1

                # basic-mode monotone bounds for the children (reference:
                # BasicLeafConstraints::Update, monotone_constraints.hpp:488)
                p_cmin = pcol[LM_CMIN]
                p_cmax = pcol[LM_CMAX]
                if self.use_mc:
                    mid = (lout + rout) * 0.5
                    num_split = ~is_cat
                    l_cmin = jnp.where(num_split & (mono_f < 0),
                                       jnp.maximum(p_cmin, mid), p_cmin)
                    l_cmax = jnp.where(num_split & (mono_f > 0),
                                       jnp.minimum(p_cmax, mid), p_cmax)
                    r_cmin = jnp.where(num_split & (mono_f > 0),
                                       jnp.maximum(p_cmin, mid), p_cmin)
                    r_cmax = jnp.where(num_split & (mono_f < 0),
                                       jnp.minimum(p_cmax, mid), p_cmax)
                else:
                    l_cmin = r_cmin = p_cmin
                    l_cmax = r_cmax = p_cmax

                # record the internal node (reference: Tree::Split, tree.cpp)
                upd = dict(moved)
                if self.has_categorical:
                    upd["node_cat_set"] = jnp.where(
                        (jax.lax.iota(jnp.int32, nodes + 1) == wr_s)[:, None],
                        cat_set[None, :], st["node_cat_set"])
                ncol = jnp.stack([
                    _i2f(orig_feat), _i2f(f_enum),
                    _i2f(thr), dl.astype(jnp.float32), gain,
                    _i2f(-(best_leaf + 1)), _i2f(-(new_leaf + 1)),
                    pcol[LM_VALUE], pcol[LM_SUM_H], _i2f(cnt_g),
                    _i2f(col), _i2f(bstart), _i2f(isb), _i2f(nb),
                    _i2f(dbin), _i2f(mtype), is_cat.astype(jnp.float32)])
                nm = st["nodemat"].at[:, wr_s].set(ncol)
                # fix the parent's child pointer (read-modify-write of ONE
                # nodemat column)
                p = _f2i(pcol[LM_PARENT])
                side = _f2i(pcol[LM_PSIDE])
                sp = jnp.where(valid, jnp.maximum(p, 0), jnp.int32(nodes))
                par = jax.lax.dynamic_slice(nm, (0, sp), (NND, 1))[:, 0]
                par = par.at[ND_LEFT].set(jnp.where(
                    (p >= 0) & (side == 0), _i2f(s), par[ND_LEFT]))
                par = par.at[ND_RIGHT].set(jnp.where(
                    (p >= 0) & (side == 1), _i2f(s), par[ND_RIGHT]))
                nm = nm.at[:, sp].set(par)
                upd["nodemat"] = nm

                # child best splits (single traced program via vmap over the
                # stacked pair — halves the while-body program size)
                # per-child feature masks: interaction constraints narrow to
                # sets compatible with the path, bynode sampling re-draws
                f_onehot = jax.lax.broadcasted_iota(
                    jnp.int32, (F,), 0) == f_enum
                feat_used_new = (st["feat_used"] | f_onehot
                                 if self.has_cegb else st["feat_used"])
                mask_l = mask_r = feature_mask
                if self.ic_masks is not None:
                    used_child = jnp.any(
                        st["leaf_used"] & bl_oh[:, None], axis=0) | f_onehot
                    allowed = self._allowed_from_used(used_child)
                    mask_l = mask_l & allowed
                    mask_r = mask_r & allowed
                if self.has_bynode:
                    kstep = jax.random.fold_in(rng0, s + 1)
                    kl, kr = jax.random.split(kstep)
                    mask_l = mask_l & self._bynode_mask(kl)
                    mask_r = mask_r & self._bynode_mask(kr)

                lazy_pair = ()
                if self.cegb_lazy is not None:
                    # mark the split feature used for the leaf's rows FIRST
                    # (children then see zero lazy penalty for it), then
                    # count still-unused rows per feature for both children
                    aux_m = self._lazy_mark(moved["part_aux"], start, cnt,
                                            f_enum)
                    upd["part_aux"] = aux_m
                    lazy_pair = (self._lazy_counts(
                        aux_m, start, left_cnt, cnt - left_cnt),)
                if self.extra_trees:
                    klx, krx = jax.random.split(
                        jax.random.fold_in(rngx, s + 1))
                    lazy_pair = lazy_pair + (jnp.stack(
                        [self._rand_bins(klx), self._rand_bins(krx)]),)

                if self.forced is not None:
                    forced_l = jnp.where(forced_ok,
                                         self.forced["left"][forced_node],
                                         jnp.int32(-1))
                    forced_r = jnp.where(forced_ok,
                                         self.forced["right"][forced_node],
                                         jnp.int32(-1))
                else:
                    forced_l = forced_r = jnp.int32(-1)

                def child_head(cstart, ccnt, ccnt_g, csg, csh, cout, cmin_,
                               cmax_, side):
                    return jnp.stack([
                        _i2f(cstart), _i2f(ccnt), _i2f(ccnt_g), csg, csh,
                        _i2f(depth_child), cmin_, cmax_, cout, _i2f(s),
                        _i2f(side)])

                head_l = child_head(l_start, left_cnt, left_cnt_g, lsg,
                                    lsh, lout, l_cmin, l_cmax, 0)
                head_r = child_head(r_start, right_cnt, right_cnt_g, rsg,
                                    rsh, rout, r_cmin, r_cmax, 1)

                if self.plan.search == "pallas":
                    # both children's searches as ONE kernel emitting the
                    # packed [LM_BGAIN..LM_BISCAT] leafmat segments
                    from ..ops.split_pallas import best_split_pair_pallas
                    BFs = self.BF
                    if use_mega:
                        hl_g, hl_h, hr_g, hr_h = mega_hists
                        hg = jnp.concatenate([hl_g[:, :BFs],
                                              hr_g[:, :BFs]], axis=0)
                        hh = jnp.concatenate([hl_h[:, :BFs],
                                              hr_h[:, :BFs]], axis=0)
                    elif use_flat:
                        Gf, Bf, _ = self._flat_geom
                        hl = hl_flat.reshape(2, Gf, Bf)
                        hr = hr_flat.reshape(2, Gf, Bf)
                        hg = jnp.concatenate([hl[0, :G, :BFs],
                                              hr[0, :G, :BFs]], axis=0)
                        hh = jnp.concatenate([hl[1, :G, :BFs],
                                              hr[1, :G, :BFs]], axis=0)
                    else:
                        hg = jnp.concatenate([hist_left[:, :BFs, 0],
                                              hist_right[:, :BFs, 0]],
                                             axis=0)
                        hh = jnp.concatenate([hist_left[:, :BFs, 1],
                                              hist_right[:, :BFs, 1]],
                                             axis=0)
                        if hist_scale is not None:
                            # integer-domain state -> gain domain
                            hg = hg * hist_scale[0]
                            hh = hh * hist_scale[1]
                    onesF = jnp.ones((F, 1), jnp.float32)
                    dep_f = depth_child.astype(jnp.float32)

                    def iblock(csg, csh, ccnt_g, mask):
                        return jnp.concatenate([
                            onesF * csg, onesF * csh,
                            onesF * ccnt_g.astype(jnp.float32),
                            onesF * dep_f,
                            mask.astype(jnp.float32)[:, None],
                            jnp.zeros((F, 3), jnp.float32)], axis=1)

                    info = jnp.concatenate(
                        [iblock(lsg, lsh, left_cnt_g, mask_l),
                         iblock(rsg, rsh, right_cnt_g, mask_r)], axis=0)
                    with scopes.scope("search"):
                        tile = best_split_pair_pallas(
                            hg, hh, self._fmeta_pair, info,
                            l1=self.l1, l2=self.l2,
                            max_delta_step=self.max_delta_step,
                            min_gain_to_split=self.min_gain_to_split,
                            min_data_in_leaf=self.min_data_in_leaf,
                            min_sum_hessian=self.min_sum_hessian,
                            max_depth=self.max_depth,
                            interpret=self._interp)
                    col_l = jnp.concatenate(
                        [head_l, tile[0, :13],
                         _i2f(forced_l)[None]])
                    col_r = jnp.concatenate(
                        [head_r, tile[1, :13],
                         _i2f(forced_r)[None]])
                else:
                    if self.use_mc and self.mc_mode in ("intermediate",
                                                        "advanced"):
                        child_boxes = self._child_boxes(
                            st, bl_oh, f_enum, is_cat, mtype, nb, dbin,
                            dl, thr)
                    if self.use_mc and self.mc_mode == "advanced":
                        # per-threshold children bounds (the reference's
                        # AdvancedLeafConstraints segments) for the TWO
                        # candidate children, folded with their scalar
                        # (basic + refresh) bounds
                        prow_lo, prow_hi, l_hi_box, r_lo_box = child_boxes
                        lo_all = st["leaf_lo"][:L]
                        hi_all = st["leaf_hi"][:L]
                        vals_all = lm[LM_VALUE, :L]
                        exist_l = jax.lax.iota(jnp.int32, L) < (s + 1)
                        abl = self._advanced_bounds(
                            lo_all, hi_all, vals_all, exist_l,
                            prow_lo, l_hi_box)
                        abr = self._advanced_bounds(
                            lo_all, hi_all, vals_all, exist_l,
                            r_lo_box, prow_hi)
                        # fold ONLY the sibling mid-refinement (the
                        # reference's BasicLeafConstraints::Update for the
                        # split just applied); the parent's whole-box
                        # scalars would collapse advanced to intermediate
                        mid_v = (lout + rout) * 0.5
                        num_sp = ~is_cat
                        lmin_m = jnp.where(num_sp & (mono_f < 0), mid_v,
                                           -jnp.inf)
                        lmax_m = jnp.where(num_sp & (mono_f > 0), mid_v,
                                           jnp.inf)
                        rmin_m = jnp.where(num_sp & (mono_f > 0), mid_v,
                                           -jnp.inf)
                        rmax_m = jnp.where(num_sp & (mono_f < 0), mid_v,
                                           jnp.inf)
                        cmin_arg = (
                            jnp.stack([jnp.maximum(abl[0], lmin_m),
                                       jnp.maximum(abr[0], rmin_m)]),
                            jnp.stack([jnp.maximum(abl[2], lmin_m),
                                       jnp.maximum(abr[2], rmin_m)]))
                        cmax_arg = (
                            jnp.stack([jnp.minimum(abl[1], lmax_m),
                                       jnp.minimum(abr[1], rmax_m)]),
                            jnp.stack([jnp.minimum(abl[3], lmax_m),
                                       jnp.minimum(abr[3], rmax_m)]))
                    else:
                        cmin_arg = jnp.stack([l_cmin, r_cmin])
                        cmax_arg = jnp.stack([l_cmax, r_cmax])
                    both = self._best_split_vmapped(
                        self._scale_hist(jnp.stack([hist_left,
                                                    hist_right]),
                                         hist_scale),
                        jnp.stack([lsg, rsg]), jnp.stack([lsh, rsh]),
                        jnp.stack([left_cnt_g, right_cnt_g]),
                        jnp.stack([left_cnt, right_cnt]),
                        jnp.stack([depth_child, depth_child]),
                        cmin_arg, cmax_arg,
                        jnp.stack([lout, rout]),
                        jnp.stack([mask_l, mask_r]), feat_used_new,
                        *lazy_pair)
                    best_l = self._sync_best(
                        jax.tree.map(lambda a: a[0], both))
                    best_r = self._sync_best(
                        jax.tree.map(lambda a: a[1], both))

                    def seg13(bs):
                        return jnp.stack([
                            bs.gain, _i2f(bs.feature), _i2f(bs.threshold),
                            bs.default_left.astype(jnp.float32),
                            _i2f(bs.left_count), _i2f(bs.right_count),
                            bs.left_sum_g, bs.left_sum_h,
                            bs.right_sum_g, bs.right_sum_h,
                            bs.left_output, bs.right_output,
                            bs.is_cat.astype(jnp.float32)])

                    col_l = jnp.concatenate(
                        [head_l, seg13(best_l), _i2f(forced_l)[None]])
                    col_r = jnp.concatenate(
                        [head_r, seg13(best_r), _i2f(forced_r)[None]])
                    if self.plan.linear_gain:
                        # each child's model comes from its OWN search
                        # (best whole-leaf single-feature fit)
                        col_l = jnp.concatenate([col_l, jnp.stack([
                            best_l.self_const, best_l.self_coeff,
                            _i2f(best_l.self_feature)])])
                        col_r = jnp.concatenate([col_r, jnp.stack([
                            best_r.self_const, best_r.self_coeff,
                            _i2f(best_r.self_feature)])])
                lm2 = lm.at[:, wr_a].set(col_l).at[:, wr_b].set(col_r)

                iot_l1 = jax.lax.iota(jnp.int32, L + 1)
                upd.update({
                    "s": s + valid.astype(jnp.int32),
                    "done": ~valid & ~skip_pending & ~adv_reject,
                    **({} if use_mega else {"hist": hist}),
                    "leafmat": lm2,
                    "feat_used": jnp.where(valid, feat_used_new,
                                           st["feat_used"]),
                    **({"leaf_used": jnp.where(
                        ((iot_l1 == wr_a) | (iot_l1 == wr_b))[:, None],
                        used_child[None, :], st["leaf_used"])}
                       if self.ic_masks is not None else {}),
                })
                if self.has_categorical:
                    new_cat = jnp.where(
                        (iot_l1 == wr_a)[:, None], best_l.cat_set[None, :],
                        jnp.where((iot_l1 == wr_b)[:, None],
                                  best_r.cat_set[None, :],
                                  st["best_cat_set"]))
                    upd["best_cat_set"] = new_cat
                if (self.use_mc and self.mc_mode in ("intermediate", "advanced")
                        and "leaf_fmask" in st):
                    upd["leaf_fmask"] = jnp.where(
                        (iot_l1 == wr_a)[:, None], mask_l[None, :],
                        jnp.where((iot_l1 == wr_b)[:, None],
                                  mask_r[None, :], st["leaf_fmask"]))
                if self.use_mc and self.mc_mode in ("intermediate", "advanced"):
                    # per-leaf bin-range boxes (computed once before the
                    # children search — see _child_boxes)
                    prow_lo, prow_hi, l_hi, r_lo = child_boxes
                    leaf_lo = jnp.where(
                        (iot_l1 == wr_a)[:, None], prow_lo[None, :],
                        jnp.where((iot_l1 == wr_b)[:, None], r_lo[None, :],
                                  st["leaf_lo"]))
                    leaf_hi = jnp.where(
                        (iot_l1 == wr_a)[:, None], l_hi[None, :],
                        jnp.where((iot_l1 == wr_b)[:, None],
                                  prow_hi[None, :], st["leaf_hi"]))
                    upd["leaf_lo"] = leaf_lo
                    upd["leaf_hi"] = leaf_hi
                    st2 = {**st, **upd}
                    lm3, cat3 = self._mc_refresh(
                        st2, lm2, upd["s"] + 1, feature_mask,
                        hist_scale=hist_scale)
                    upd["leafmat"] = jnp.where(valid, lm3, lm2)
                    if cat3 is not None:
                        upd["best_cat_set"] = jnp.where(valid, cat3,
                                                        upd["best_cat_set"])
                return self._pvary(upd)

        if self.F == 0:   # no splittable features: the root is the only leaf
            return self._unpack_state(state)
        final = jax.lax.while_loop(cond, body, state)
        return self._unpack_state(final)

    # ------------------------------------------------------------------
    # Frontier-batched growth (tpu_frontier_k > 1)
    # ------------------------------------------------------------------
    def _build_tree_frontier(self, part_bins, part_ghi0, bag_cnt,
                             feature_mask, hist_scale=None):
        """Grow the top-K frontier leaves per while-loop step.

        Splitting leaf A never changes leaf B's histogram or best split
        (per-leaf statistics depend only on the leaf's own rows), so K
        splits per step are semantics-preserving — EXCEPT that leaf-wise
        order decides WHICH splits fit the ``num_leaves`` budget and how
        nodes/leaves are numbered.  Both are restored exactly by an
        ORACLE-ORDER REPLAY carried in the loop:

        * Every potential leaf is an *item*: item 0 is the root, items
          ``1 + 2j + side`` are the children of our j-th executed split,
          the last item is a write-trash slot.  The replay maintains the
          K=1 oracle's priority queue over items (``avail``) and pops it
          with the oracle's exact election (max gain, smallest oracle
          leaf slot on ties — ops/split.py ``oracle_next_pick``).  A pop
          of a split item commits it with the next oracle split index; a
          pop of an UNSPLIT item stalls the replay: that item is the
          oracle's guaranteed next split and seeds the next step's batch.
        * Each step splits the stalled item plus the top-(K-1) remaining
          positive-gain frontier candidates (speculative: the oracle may
          or may not reach them within budget).  Including the stalled
          item commits >= 1 oracle split per step, and the batch width
          shrinks per the slot-reserve rule ``k <= slots_left - needed
          + 1`` so at most K-1 speculative splits ever outlive the
          budget — total splits are bounded by (L-1) + (K-1).
        * After the loop, ``_renumber_frontier`` prunes the uncommitted
          speculative splits and rebuilds leafmat/nodemat in oracle
          numbering (child pointers from the replay arrays, pruned-leaf
          records from per-split parent snapshots), yielding trees
          bit-identical to the K=1 learner.

        Pruned speculative partitions are UNDONE at tree end: f32
        histogram accumulation is not order-invariant, so a permuted
        row order inside a pruned leaf's range would ULP-perturb the
        NEXT tree's histograms.  ONE N-long snapshot row suffices:
        before a leaf is partitioned, the rowid row of ITS range is
        copied into the snapshot (``_snapshot_rowids``).  A leaf becomes
        selectable only when the replay commits its parent split, so
        while a split is uncommitted none of its rows is partitioned
        again and no later copy can overwrite a range that a live
        uncommitted split still needs.  The tree-end undo pass
        inverse-gathers the (at most K-1, mutually disjoint) pruned
        ranges back into their snapshot order — restoring the exact
        physical layout the K=1 oracle would hand the next iteration.

        The amortization: ONE top-k election, ONE (NLF, K) leafmat
        gather, ONE K-row parent-hist gather (replacing the K dynamic
        slices whose contextual full-state copies are the round-4
        fixed-cost smoking gun), ONE 2K-wide vmapped children search and
        ONE 2K-column scatter per step, with only the per-leaf
        partition/histogram passes (the payload-bound work) looping over
        the K selected leaves.
        """
        L, G, B, F, K = self.L, self.G, self.B, self.F, self.plan.frontier_k
        MS = (L - 1) + (K - 1)      # split slots: budget + speculative slack
        SL = MS + 2                 # leaf slots incl. one trash slot
        TRASH = SL - 1
        NI = 2 * MS + 2             # items: root + 2 per split + trash
        IT = NI - 1                 # trash item
        use_mega = self.plan.mega != "off"
        neg_inf = jnp.float32(-jnp.inf)
        pos_inf = jnp.float32(jnp.inf)

        # ---- root (the K=1 path's root prep, serial-mode form) ----
        root_hist = self._hist_leaf(part_bins, part_ghi0,
                                    jnp.int32(self.row0),
                                    jnp.int32(self.N), scale=hist_scale)
        sum_g = root_hist[0, :, 0].sum()
        sum_h = root_hist[0, :, 1].sum()
        if hist_scale is not None:
            # integer-domain quantized totals -> gain domain (once)
            sum_g = sum_g * hist_scale[0]
            sum_h = sum_h * hist_scale[1]
        feat_used0 = jnp.zeros((F,), jnp.bool_)
        best0 = self._leaf_best_split(
            self._scale_hist(root_hist, hist_scale), sum_g, sum_h,
            bag_cnt, bag_cnt, jnp.int32(0),
            neg_inf, pos_inf, jnp.float32(0.0), feature_mask, feat_used0)
        # leafmat/nodemat are carried as int32 BITS in this body (see
        # _pack_bits); _renumber_frontier hands the f32 containers back
        col0 = _pack_bits([
            self.row0, self.N, bag_cnt,
            sum_g, sum_h, 0,
            neg_inf, pos_inf,
            jnp.float32(0.0), -1, 0,
            best0.gain, best0.feature, best0.threshold,
            best0.default_left.astype(jnp.float32),
            best0.left_count, best0.right_count,
            best0.left_sum_g, best0.left_sum_h,
            best0.right_sum_g, best0.right_sum_h,
            best0.left_output, best0.right_output,
            best0.is_cat.astype(jnp.float32), -1])
        leafmat = jnp.zeros((NLF, SL), jnp.int32) \
            .at[LM_BGAIN].set(_f2i(neg_inf)) \
            .at[LM_CMIN].set(_f2i(neg_inf)) \
            .at[LM_CMAX].set(_f2i(pos_inf)) \
            .at[LM_PARENT].set(-1) \
            .at[LM_FORCED].set(-1) \
            .at[:, 0].set(col0)

        state = {
            "made": jnp.int32(0),       # splits executed (incl. speculative)
            "m": jnp.int32(0),          # oracle splits committed by the replay
            "done": ~(best0.gain > 0),
            "part_bins": part_bins,
            "part_ghi": part_ghi0,
            "leafmat": leafmat,
            "nodemat": jnp.zeros((NND_FR, MS + 1), jnp.int32),
            "feat_used": feat_used0,
            # oracle-replay item arrays
            "it_gain": jnp.full((NI,), neg_inf).at[0].set(best0.gain),
            "it_slot": jnp.zeros((NI,), jnp.int32),
            "it_split": jnp.full((NI,), -1, jnp.int32),
            "it_oslot": jnp.full((NI,), 2 ** 30, jnp.int32).at[0].set(0),
            "avail": jnp.zeros((NI,), jnp.bool_).at[0].set(True),
            "u_item": jnp.int32(0),     # the oracle's guaranteed next split
            "pop_split": jnp.full((L,), -1, jnp.int32),
            "ora_of": jnp.full((MS + 1,), -1, jnp.int32),
            "slot_item": jnp.full((L + 1,), -1, jnp.int32).at[0].set(0),
            # pre-partition rowid order of every range partitioned so
            # far, for the tree-end undo of pruned speculative splits
            "snap": jnp.zeros((part_bins.shape[1],), jnp.float32),
        }
        if not use_mega:
            with scopes.scope("hist_state"):
                state["hist"] = jnp.zeros((SL, G, B, 2),
                                          jnp.float32).at[0].set(root_hist)
        if self.has_categorical:
            state["best_cat_set"] = jnp.zeros(
                (SL, self.BF), jnp.bool_).at[0].set(best0.cat_set)
            state["node_cat_set"] = jnp.zeros((MS + 1, self.BF), jnp.bool_)
        if self.plan.partition == "pallas":
            from ..ops.partition_pallas import sc_rows_for
            state["sc_packed"] = jnp.zeros(
                (sc_rows_for(self.plan.pass_rows), part_bins.shape[1]),
                jnp.int32)
        else:
            state["sc32"] = jnp.zeros((G + self._ghi_rows,
                                       part_bins.shape[1]), jnp.int32)
        buf_keys = ("part_bins", "part_ghi", "snap",
                    "sc_packed" if self.plan.partition == "pallas" else "sc32")

        def cond(st):
            return (~st["done"]) & (st["made"] < MS)

        def body(st):
            lm = st["leafmat"]
            iotK = jax.lax.iota(jnp.int32, K)
            # ---- select the step's batch: the oracle's guaranteed-next
            # split plus the top-(K-1) speculative candidates ----
            cand = st["avail"] & (st["it_split"] < 0) & (st["it_gain"] > 0)
            scores = jnp.where(cand, st["it_gain"], neg_inf)
            sel_items, sel_ok = split_ops.frontier_topk(
                scores, st["u_item"], K)
            ncand = jnp.sum(sel_ok.astype(jnp.int32))
            # shrink K to the remaining budget on the final steps AND to
            # the slot-reserve rule (enough split slots must remain to
            # finish one committed split per step)
            needed = jnp.int32(L - 1) - st["m"]
            s_left = jnp.int32(MS) - st["made"]
            k_step = jnp.minimum(jnp.minimum(jnp.int32(K), needed),
                                 s_left - needed + 1)
            k_step = jnp.clip(jnp.minimum(k_step, ncand), 1, K)
            active = iotK < k_step
            sel_items = jnp.where(active, sel_items, IT)
            sel_slots = jnp.where(active,
                                  jnp.take(st["it_slot"], sel_items),
                                  TRASH)
            j_idx = jnp.where(active, st["made"] + iotK, jnp.int32(MS))
            wrb_slots = jnp.where(active, st["made"] + 1 + iotK,
                                  jnp.int32(TRASH))

            # ---- ONE gather of the K chosen leaves' packed scalars ----
            pbits = jnp.take(lm, sel_slots, axis=1)           # (NLF, K)
            pcols = _i2f(pbits)       # float view, for the real-f32 rows
            f_enums = pbits[LM_BFEAT]
            thrs = pbits[LM_BTHR]
            dls = pcols[LM_BDL] > 0.5
            is_cats = pcols[LM_BISCAT] > 0.5
            starts = pbits[LM_START]
            cnts = jnp.where(active, pbits[LM_CNT], 0)
            lcg = pbits[LM_BLCNT]
            rcg = pbits[LM_BRCNT]
            small_is_left = lcg <= rcg
            # one batched gather over the packed per-feature metadata
            # (replaces K per-split lane-dynamic slices)
            fmeta_k = jnp.take(self._fmeta, f_enums, axis=1)  # (8, K)
            if self.has_categorical:
                cat_sets = jnp.take(st["best_cat_set"], sel_slots, axis=0)
            else:
                cat_sets = jnp.zeros((K, 1), jnp.bool_)
            if not use_mega:
                # subtraction trick: ONE gather over the K parents
                # replaces K dynamic-slices of the histogram state (the
                # round-4 contextual double-copy pathology, PERF.md)
                with scopes.scope("hist_state"):
                    parent_hists = jnp.take(st["hist"], sel_slots, axis=0)

            # ---- per-leaf payload passes: the k-loop runs ONLY the
            # partitions (selected leaves occupy disjoint row ranges, so
            # the passes commute and later lanes read ranges earlier
            # lanes never touched) ----
            depth_c = pbits[LM_DEPTH] + 1
            bufs0 = {kk: st[kk] for kk in buf_keys}
            use_ppair = use_mega and self.plan.search == "pallas"
            if use_mega:
                acc0 = tuple(jnp.zeros((K, G, B), jnp.float32)
                             for _ in range(4))
            else:
                acc0 = (jnp.zeros((K, G, B, 2), jnp.float32),)
            carry0 = (bufs0, acc0, jnp.zeros((K,), jnp.int32),
                      jnp.zeros((13, 2 * K), jnp.int32))

            def kbody(k, carry):
                bufs, acc, lcnt, seg = carry
                fm = jax.lax.dynamic_slice(fmeta_k, (0, k), (8, 1))[:, 0]
                dsc = (fm[2], fm[3], fm[4], fm[5], fm[6],
                       thrs[k], dls[k], is_cats[k], cat_sets[k])
                start = starts[k]
                cnt = cnts[k]
                # lane 0 is the oracle's guaranteed-next split: the
                # replay commits it, so it is never undone and needs no
                # snapshot.  The snapshot reads the rows the partition
                # is about to overwrite in place; nothing else orders
                # the two, and unordered XLA:TPU keeps the pre-partition
                # payload alive in a copy of part_ghi per split.  The
                # barrier hands the partition its range only once the
                # snapshot is taken.
                with scopes.scope("bookkeeping"):
                    snap2, start = jax.lax.optimization_barrier(
                        (self._snapshot_rowids(
                            bufs["snap"], bufs["part_ghi"], start,
                            jnp.where(k == 0, 0, cnt)),
                         start))
                bufs = {**bufs, "snap": snap2}
                if use_mega:
                    moved, left_cnt, mh = self._split_leaf_mega(
                        bufs, start, cnt, fm[1], dsc, hist_scale)
                    acc = tuple(a.at[k].set(p[:, :B])
                                for a, p in zip(acc, mh))
                    if use_ppair:
                        # the Pallas pair-search kernel, one program per
                        # split exactly like the K=1 body (its last-ulp
                        # gemm rounding differs from the XLA search, so
                        # mixing implementations would break the
                        # bit-identity contract on kernel backends)
                        from ..ops.split_pallas import (
                            best_split_pair_pallas)
                        BFs = self.BF
                        hg = jnp.concatenate([mh[0][:, :BFs],
                                              mh[2][:, :BFs]], axis=0)
                        hh = jnp.concatenate([mh[1][:, :BFs],
                                              mh[3][:, :BFs]], axis=0)
                        onesF = jnp.ones((F, 1), jnp.float32)
                        dep_f = (depth_c[k]).astype(jnp.float32)

                        def iblock(csg, csh, ccnt_g):
                            return jnp.concatenate([
                                onesF * csg, onesF * csh,
                                onesF * ccnt_g.astype(jnp.float32),
                                onesF * dep_f,
                                feature_mask.astype(
                                    jnp.float32)[:, None],
                                jnp.zeros((F, 3), jnp.float32)], axis=1)

                        info = jnp.concatenate(
                            [iblock(pcols[LM_BLSG, k], pcols[LM_BLSH, k],
                                    lcg[k]),
                             iblock(pcols[LM_BRSG, k], pcols[LM_BRSH, k],
                                    rcg[k])], axis=0)
                        with scopes.scope("search"):
                            tile = best_split_pair_pallas(
                                hg, hh, self._fmeta_pair, info,
                                l1=self.l1, l2=self.l2,
                                max_delta_step=self.max_delta_step,
                                min_gain_to_split=self.min_gain_to_split,
                                min_data_in_leaf=self.min_data_in_leaf,
                                min_sum_hessian=self.min_sum_hessian,
                                max_depth=self.max_depth,
                                interpret=self._interp)
                        tbits = _f2i(tile)
                        seg = jax.lax.dynamic_update_slice(
                            seg, jnp.transpose(tbits[:1, :13]), (0, k))
                        seg = jax.lax.dynamic_update_slice(
                            seg, jnp.transpose(tbits[1:2, :13]), (0, K + k))
                else:
                    moved, left_cnt = self._partition_leaf(
                        bufs, start, cnt, fm[1], dsc)
                    # the smaller-child histogram stays a PER-LEAF pass
                    # on the leaf's own chunk grid: a lane-batched vmap
                    # was measured and REJECTED (run-until-all-done
                    # semantics cost K x max-lane chunks — 1.9x e2e on
                    # skewed leaf sizes; PERF.md round 12)
                    sm_start = jnp.where(small_is_left[k], start,
                                         start + left_cnt)
                    sm_cnt = jnp.where(small_is_left[k], left_cnt,
                                       cnt - left_cnt)
                    h_small = self._hist_leaf(
                        moved["part_bins"], moved["part_ghi"],
                        sm_start, sm_cnt, scale=hist_scale)
                    with scopes.scope("hist_state"):
                        acc = (acc[0].at[k].set(h_small),)
                return ({**bufs, **moved}, acc, lcnt.at[k].set(left_cnt),
                        seg)

            bufs, acc, left_cnts, seg_pp = jax.lax.fori_loop(
                0, K, kbody, carry0)
            right_cnts = cnts - left_cnts
            l_starts = starts
            r_starts = starts + left_cnts

            # ---- children histograms -> state / search inputs ----
            ch_slots = jnp.concatenate([sel_slots, wrb_slots])
            upd_hist = {}
            with scopes.scope("hist_state"):
                if use_mega:
                    hist_left = jnp.stack([acc[0], acc[1]], axis=3)
                    hist_right = jnp.stack([acc[2], acc[3]], axis=3)
                else:
                    small = acc[0]
                    large = parent_hists - small
                    sel_b = small_is_left[:, None, None, None]
                    hist_left = jnp.where(sel_b, small, large)
                    hist_right = jnp.where(sel_b, large, small)
                    # ONE 2K-row scatter replaces 2K per-split state updates
                    upd_hist["hist"] = st["hist"].at[ch_slots].set(
                        jnp.concatenate([hist_left, hist_right], axis=0))

            def seg13(bs):
                return _pack_bits([
                    bs.gain, bs.feature, bs.threshold,
                    bs.default_left.astype(jnp.float32),
                    bs.left_count, bs.right_count,
                    bs.left_sum_g, bs.left_sum_h,
                    bs.right_sum_g, bs.right_sum_h,
                    bs.left_output, bs.right_output,
                    bs.is_cat.astype(jnp.float32)])

            two = jnp.concatenate
            sum_g2 = two([pcols[LM_BLSG], pcols[LM_BRSG]])
            sum_h2 = two([pcols[LM_BLSH], pcols[LM_BRSH]])
            cnt_g2 = two([lcg, rcg])
            depth2 = two([depth_c, depth_c])
            out2 = two([pcols[LM_BLOUT], pcols[LM_BROUT]])

            # ---- ONE 2K-wide batched best-split search over all the
            # step's children (vs 2 per split before: the vmapped search
            # is elementwise/scan-structured per lane, so batch width
            # cannot change per-lane rounding — re-verified empirically
            # by the bit-identity matrix in tests/test_frontier.py) ----
            if use_ppair:
                # the Pallas pair searches already ran per split inside
                # the k-loop and emitted the packed segments directly
                seg13_2k = seg_pp
                ccat_2k = jnp.zeros((2 * K, 1), jnp.bool_)
            else:
                hist2k = two([hist_left, hist_right], axis=0)
                if not use_mega:   # mega planes arrive already scaled
                    hist2k = self._scale_hist(hist2k, hist_scale)
                both = self._best_split_vmapped(
                    hist2k, sum_g2, sum_h2, cnt_g2,
                    two([left_cnts, right_cnts]), depth2,
                    jnp.full((2 * K,), neg_inf),
                    jnp.full((2 * K,), pos_inf),
                    out2, jnp.broadcast_to(feature_mask, (2 * K, F)),
                    st["feat_used"])
                seg13_2k = seg13(both)                    # (13, 2K)
                ccat_2k = both.cat_set

            head = _pack_bits([
                two([l_starts, r_starts]),
                two([left_cnts, right_cnts]),
                cnt_g2,
                sum_g2, sum_h2,
                depth2,
                jnp.full((2 * K,), neg_inf), jnp.full((2 * K,), pos_inf),
                out2,
                two([j_idx, j_idx]),
                two([jnp.zeros((K,), jnp.int32),
                     jnp.ones((K,), jnp.int32)])])        # (11, 2K)
            cols = jnp.concatenate(
                [head, seg13_2k, jnp.full((1, 2 * K), -1, jnp.int32)],
                axis=0)
            lm2 = lm.at[:, ch_slots].set(cols)

            # ---- nodemat: ONE K-column scatter (child pointers and the
            # parent fixups are derived at renumber time) ----
            ncols = _pack_bits([
                fmeta_k[0], f_enums, thrs,
                dls.astype(jnp.float32), pbits[LM_BGAIN],
                -(sel_slots + 1), -(wrb_slots + 1),
                pbits[LM_VALUE], pbits[LM_SUM_H], pbits[LM_CNT_G],
                fmeta_k[1], fmeta_k[2], fmeta_k[3],
                fmeta_k[4], fmeta_k[5], fmeta_k[6],
                is_cats.astype(jnp.float32),
                pbits[LM_START], pbits[LM_CNT], pbits[LM_SUM_G],
                pbits[LM_DEPTH]])                         # (NND_FR, K)
            nm2 = st["nodemat"].at[:, j_idx].set(ncols)

            # ---- replay item bookkeeping ----
            ch_items = two([jnp.where(active, 1 + 2 * j_idx, IT),
                            jnp.where(active, 2 + 2 * j_idx, IT)])
            it_gain2 = st["it_gain"].at[ch_items].set(
                _i2f(seg13_2k[0])).at[IT].set(neg_inf)
            it_slot2 = st["it_slot"].at[ch_items].set(ch_slots)
            it_split2 = st["it_split"].at[sel_items].set(
                jnp.where(active, j_idx, -1)).at[IT].set(-1)

            upd_cat = {}
            if self.has_categorical:
                upd_cat["best_cat_set"] = st["best_cat_set"].at[
                    ch_slots].set(ccat_2k)
                upd_cat["node_cat_set"] = st["node_cat_set"].at[
                    j_idx].set(cat_sets)

            # ---- advance the oracle replay: pop committed splits until
            # it stalls on a leaf not yet split (next step's required
            # candidate), exhausts the num_leaves budget, or runs out of
            # positive gains (tree done).  Amortized: total pops over the
            # whole tree <= splits executed. ----
            sim0 = {
                "avail": st["avail"], "it_oslot": st["it_oslot"],
                "slot_item": st["slot_item"],
                "pop_split": st["pop_split"], "ora_of": st["ora_of"],
                "m": st["m"], "u_item": st["u_item"], "done": st["done"],
                "stop": jnp.bool_(False),
            }

            def sim_cond(c):
                return ~c["stop"]

            def sim_body(c):
                it, gmax = split_ops.oracle_next_pick(
                    it_gain2, c["it_oslot"], c["avail"])
                budget_done = c["m"] >= jnp.int32(L - 1)
                dead = ~(gmax > 0)       # covers the empty-queue case
                j2 = it_split2[it]
                can_pop = (~budget_done) & (~dead) & (j2 >= 0)
                stall = (~budget_done) & (~dead) & (j2 < 0)
                i = c["m"]
                j2c = jnp.maximum(j2, 0)
                cl = 1 + 2 * j2c
                cr = cl + 1
                itx = jnp.where(can_pop, it, IT)
                clx = jnp.where(can_pop, cl, IT)
                crx = jnp.where(can_pop, cr, IT)
                po = c["it_oslot"][it]
                avail2 = (c["avail"].at[itx].set(False)
                          .at[clx].set(True).at[crx].set(True)
                          .at[IT].set(False))
                oslot2 = (c["it_oslot"].at[clx].set(po)
                          .at[crx].set(i + 1)
                          .at[IT].set(jnp.int32(2 ** 30)))
                slot_item2 = (c["slot_item"]
                              .at[jnp.where(can_pop, po,
                                            jnp.int32(L))].set(cl)
                              .at[jnp.where(can_pop, i + 1,
                                            jnp.int32(L))].set(cr))
                pop_split2 = c["pop_split"].at[
                    jnp.where(can_pop, i, jnp.int32(L - 1))].set(j2c)
                ora2 = c["ora_of"].at[
                    jnp.where(can_pop, j2c, jnp.int32(MS))].set(i)
                return {
                    "avail": avail2, "it_oslot": oslot2,
                    "slot_item": slot_item2, "pop_split": pop_split2,
                    "ora_of": ora2,
                    "m": c["m"] + can_pop.astype(jnp.int32),
                    "u_item": jnp.where(stall, it, c["u_item"]),
                    "done": c["done"] | budget_done | dead,
                    "stop": ~can_pop,
                }

            sim = jax.lax.while_loop(sim_cond, sim_body, sim0)

            return {
                "made": st["made"] + k_step,
                "m": sim["m"], "done": sim["done"],
                "leafmat": lm2, "nodemat": nm2,
                "feat_used": st["feat_used"],
                "it_gain": it_gain2, "it_slot": it_slot2,
                "it_split": it_split2,
                "it_oslot": sim["it_oslot"], "avail": sim["avail"],
                "u_item": sim["u_item"],
                "pop_split": sim["pop_split"], "ora_of": sim["ora_of"],
                "slot_item": sim["slot_item"],
                **{kk: bufs[kk] for kk in buf_keys},
                **upd_hist, **upd_cat,
            }

        final = jax.lax.while_loop(cond, body, state)

        # ---- undo the pruned speculative partitions: restore each
        # pruned range to its snapshot (= oracle) row order so the next
        # iteration's f32 accumulation order is bit-identical to K=1.
        # Runs ONCE per tree, and only when something was actually
        # pruned: in the common all-committed case the cond skips the
        # O(N) position scatter and the two full-payload gathers.
        Np = part_bins.shape[1]
        jar = jnp.arange(MS, dtype=jnp.int32)
        is_pruned = (jar < final["made"]) & (final["ora_of"][:MS] < 0)

        def _undo(ops):
            pb0, pg0 = ops
            iota_n = jax.lax.iota(jnp.int32, Np)
            pr_j, _ = jax.lax.top_k(jnp.where(is_pruned, jar, -1),
                                    min(K, MS))
            src_bits = pg0[2]
            anymask = jnp.zeros((Np,), jnp.bool_)
            for t in range(min(K, MS)):
                jt = pr_j[t]
                jc = jnp.maximum(jt, 0)
                ncol = jax.lax.dynamic_slice(final["nodemat"], (0, jc),
                                             (NND_FR, 1))[:, 0]
                stt = ncol[ND_START]
                cntt = ncol[ND_CNTP]
                mask = (jt >= 0) & (iota_n >= stt) & (iota_n < stt + cntt)
                src_bits = jnp.where(mask, final["snap"], src_bits)
                anymask = anymask | mask
            cur = jnp.clip(_f2i(pg0[2]), 0, self.N)
            pos_of = jnp.zeros((self.N + 1,),
                               jnp.int32).at[cur].set(iota_n)
            perm = jnp.where(
                anymask,
                jnp.take(pos_of, jnp.clip(_f2i(src_bits), 0, self.N)),
                iota_n)
            return (jnp.take(pb0, perm, axis=1),
                    jnp.take(pg0, perm, axis=1))

        pb1, pg1 = jax.lax.cond(
            jnp.any(is_pruned), _undo, lambda ops: ops,
            (final["part_bins"], final["part_ghi"]))
        final = {**final, "part_bins": pb1, "part_ghi": pg1}
        return self._unpack_state(self._renumber_frontier(final))

    def _renumber_frontier(self, st: Dict[str, Any]) -> Dict[str, Any]:
        """Prune uncommitted speculative splits and renumber the batched
        build into the K=1 oracle's numbering.

        Runs ONCE per tree, outside the while loop, fully vectorized (no
        per-split loop): oracle split i executed our split pop_split[i];
        oracle leaf slot l holds item slot_item[l].  A leaf whose item we
        speculatively split (pruned) is reconstructed from that split's
        parent-snapshot nodemat rows; its speculative best-split columns
        LM_BLCNT..LM_BROUT are zeroed (the oracle stores the candidate
        children stats there, but nothing downstream of _unpack_state
        reads them — only LM_BGAIN, which the snapshot preserves).
        Output shapes match the K=1 path exactly: leafmat (NLF, L+1),
        nodemat (NND, L), s = committed split count."""
        L, K = self.L, self.plan.frontier_k
        MS = (L - 1) + (K - 1)
        NI = 2 * MS + 2
        nodes = self.max_splits
        m = st["m"]
        neg_inf = jnp.float32(-jnp.inf)
        pos_inf = jnp.float32(jnp.inf)
        it_split = st["it_split"]
        it_oslot = st["it_oslot"]
        ora_of = st["ora_of"]

        # ---- leaves ----
        lidx = jax.lax.iota(jnp.int32, L)
        items = st["slot_item"][:L]
        has = (lidx <= m) & (items >= 0)
        itc = jnp.clip(items, 0, NI - 1)
        slots = jnp.take(st["it_slot"], itc)
        from_lm = jnp.take(st["leafmat"], slots, axis=1)      # (NLF, L)
        par_j = jnp.clip((itc - 1) // 2, 0, MS)
        par_pop = jnp.where(itc > 0, jnp.take(ora_of, par_j), -1)
        par_side = jnp.where(itc > 0, (itc - 1) % 2, 0)
        from_lm = from_lm.at[LM_PARENT].set(par_pop) \
                         .at[LM_PSIDE].set(par_side)
        jw = jnp.take(it_split, itc)
        jwc = jnp.clip(jw, 0, MS)
        snap = jnp.take(st["nodemat"], jwc, axis=1)           # (NND_FR, L)
        # leafmat/nodemat arrive as int32 BITS (see _pack_bits): every
        # row below is raw bits, so the stack is an int stack
        zer = jnp.zeros((L,), jnp.int32)
        recon = _pack_bits([
            snap[ND_START], snap[ND_CNTP], snap[ND_ICOUNT],
            snap[ND_SUM_G], snap[ND_IWEIGHT], snap[ND_DEPTH],
            jnp.full((L,), neg_inf), jnp.full((L,), pos_inf),
            snap[ND_IVALUE], par_pop, par_side,
            snap[ND_GAIN], snap[ND_FEATURE_ENUM], snap[ND_THRESHOLD],
            snap[ND_DL], zer, zer, zer, zer, zer, zer, zer, zer,
            snap[ND_IS_CAT],
            jnp.full((L,), -1, jnp.int32)])                   # (NLF, L)
        init_col = jnp.zeros((NLF, 1), jnp.int32) \
            .at[LM_BGAIN].set(_f2i(neg_inf)).at[LM_CMIN].set(_f2i(neg_inf)) \
            .at[LM_CMAX].set(_f2i(pos_inf)) \
            .at[LM_PARENT].set(-1) \
            .at[LM_FORCED].set(-1)
        init_cols = jnp.broadcast_to(init_col, (NLF, L))
        pruned = has & (jw >= 0)
        lm_f = jnp.where(pruned[None, :], recon,
                         jnp.where(has[None, :], from_lm, init_cols))
        lm_f = jnp.concatenate([lm_f, init_col], axis=1)      # (NLF, L+1)

        # ---- nodes ----
        nidx = jax.lax.iota(jnp.int32, nodes)
        jvec = st["pop_split"][:nodes]
        nvalid = nidx < m
        jc = jnp.clip(jvec, 0, MS)
        ncols = jnp.take(st["nodemat"], jc, axis=1)           # (NND_FR, nodes)
        cl = 1 + 2 * jc
        cr = cl + 1
        jl = jnp.take(it_split, cl)
        jr = jnp.take(it_split, cr)
        ol = jnp.take(ora_of, jnp.clip(jl, 0, MS))
        orr = jnp.take(ora_of, jnp.clip(jr, 0, MS))
        left_ptr = jnp.where((jl >= 0) & (ol >= 0), ol,
                             -(jnp.take(it_oslot, cl) + 1))
        right_ptr = jnp.where((jr >= 0) & (orr >= 0), orr,
                              -(jnp.take(it_oslot, cr) + 1))
        ncols = ncols.at[ND_LEFT].set(left_ptr) \
                     .at[ND_RIGHT].set(right_ptr)
        nm_f = jnp.where(nvalid[None, :], ncols[:NND], 0)
        nm_f = jnp.concatenate(
            [nm_f, jnp.zeros((NND, 1), jnp.int32)], axis=1)    # (NND, L)

        drop = ("leafmat", "nodemat", "hist", "it_gain", "it_slot",
                "it_split", "it_oslot", "avail", "u_item", "pop_split",
                "ora_of", "slot_item", "m", "best_cat_set",
                "node_cat_set", "snap")
        out = {k: v for k, v in st.items() if k not in drop}
        if getattr(self, "_frontier_debug", False):
            # test-only introspection of the replay (tests/test_frontier)
            out["frontier_debug"] = {k: st[k] for k in drop if k in st}
        # "made" stays in the record: made - s = splits pruned and undone
        out["s"] = m
        out["leafmat"] = _i2f(lm_f)
        out["nodemat"] = _i2f(nm_f)
        if self.has_categorical:
            leaf_cs = jnp.take(st["best_cat_set"], slots, axis=0)
            prn_cs = jnp.take(st["node_cat_set"], jwc, axis=0)
            bcs = jnp.where(pruned[:, None], prn_cs,
                            jnp.where(has[:, None], leaf_cs, False))
            out["best_cat_set"] = jnp.concatenate(
                [bcs, jnp.zeros((1, self.BF), jnp.bool_)], axis=0)
            ncs = jnp.where(nvalid[:, None],
                            jnp.take(st["node_cat_set"], jc, axis=0),
                            False)
            out["node_cat_set"] = jnp.concatenate(
                [ncs, jnp.zeros((1, self.BF), jnp.bool_)], axis=0)
        return out

    def _unpack_state(self, st: Dict[str, Any]) -> Dict[str, Any]:
        """Expand the packed leaf/node matrices back into the per-field
        record the rest of the framework consumes (runs ONCE per tree,
        outside the while loop)."""
        L = self.L
        nodes = self.max_splits
        lm = st["leafmat"][:, :L]         # drop the trash slots
        nm = st["nodemat"][:, :nodes]
        # the histogram state is while-loop carry only: nothing
        # downstream consumes it, and exporting it materialized an
        # (L+1, G, B, 2) buffer per tree on the eager path (the PR-10
        # frontier path already dropped it — now both paths agree)
        rec = {k: v for k, v in st.items()
               if k not in ("leafmat", "nodemat", "hist")}
        if "best_cat_set" in st:
            rec["best_cat_set"] = st["best_cat_set"][:L]
            rec["node_cat_set"] = st["node_cat_set"][:nodes]
        rec["indices"] = _f2i(st["part_ghi"][2])
        rec["part_grad"] = st["part_ghi"][0]
        rec["part_hess"] = st["part_ghi"][1]

        def li(r):
            return _f2i(lm[r])

        def ni(r):
            return _f2i(nm[r])

        rec.update({
            "leaf_start": li(LM_START), "leaf_cnt": li(LM_CNT),
            "leaf_cnt_g": li(LM_CNT_G), "leaf_sum_g": lm[LM_SUM_G],
            "leaf_sum_h": lm[LM_SUM_H], "leaf_depth": li(LM_DEPTH),
            "leaf_value": lm[LM_VALUE], "best_gain": lm[LM_BGAIN],
            "node_feature": ni(ND_FEATURE),
            "node_feature_enum": ni(ND_FEATURE_ENUM),
            "node_threshold": ni(ND_THRESHOLD),
            "node_default_left": nm[ND_DL] > 0.5,
            "node_gain": nm[ND_GAIN],
            "node_left": ni(ND_LEFT), "node_right": ni(ND_RIGHT),
            "node_internal_value": nm[ND_IVALUE],
            "node_internal_weight": nm[ND_IWEIGHT],
            "node_internal_count": ni(ND_ICOUNT),
            "node_col": ni(ND_COL), "node_bin_start": ni(ND_BIN_START),
            "node_is_bundled": ni(ND_IS_BUNDLED),
            "node_num_bin": ni(ND_NUM_BIN),
            "node_default_bin": ni(ND_DEFAULT_BIN),
            "node_missing_type": ni(ND_MISSING),
            "node_is_cat": nm[ND_IS_CAT] > 0.5,
        })
        if self.plan.linear_gain:
            # per-leaf linear model: const + coeff over the raw value
            # of leaf_lin_feat (ORIGINAL feature id, from the leaf's
            # own search — boosting._set_leafwise_linear consumes it)
            rec.update({
                "leaf_lin_const": lm[LM_LIN_CONST],
                "leaf_lin_coeff": lm[LM_LIN_COEF],
                "leaf_lin_feat": li(LM_LIN_FEAT),
            })
        return rec

    # ------------------------------------------------------------------
    def _build_impl(self, part_bins0, grad, hess, bag_cnt, feature_mask,
                    seed=0, feat_used_init=None, aux0=None,
                    hist_scale=None):
        """Front/tail-pad the per-row arrays and run the tree loop.

        ``grad``/``hess`` are (N,) in ORIGINAL row order with out-of-bag rows
        already zeroed by the caller (bagging/GOSS never gather rows — TPU
        row gathers are latency-bound); ``bag_cnt`` is the in-bag row count
        used for count estimation.  ``aux0`` is the model-lifetime cegb-lazy
        used-feature bitset, (aux_rows, N) in ORIGINAL row order.
        """
        C = self.row0
        tail = self.N_pad - C - self.N
        grad_p = jnp.pad(grad, (C, tail))
        hess_p = jnp.pad(hess, (C, tail))
        iota = jax.lax.iota(jnp.int32, self.N_pad)
        rowid = jnp.where((iota >= C) & (iota < C + self.N), iota - C, self.N)
        # row writes rather than jnp.stack+concat; tpu_selfcheck.py step 3
        # checks the bitcast rowid row survives a full build on the chip
        part_ghi0 = jnp.zeros((self._ghi_rows, self.N_pad), jnp.float32) \
            .at[0].set(grad_p).at[1].set(hess_p) \
            .at[2].set(jax.lax.bitcast_convert_type(rowid, jnp.float32))
        if aux0 is not None:
            aux0 = jnp.pad(aux0, ((0, 0), (C, tail)))
        return self._build_tree_impl(part_bins0, part_ghi0,
                                     bag_cnt, feature_mask, seed,
                                     feat_used_init, aux0, hist_scale)

    def lazy_aux_to_original_order(self, rec) -> jnp.ndarray:
        """Scatter the partitioned used-feature bitset back to original row
        order (for carrying across boosting iterations)."""
        idx = rec["indices"]
        return jnp.zeros((self.aux_rows, self.N), jnp.int32).at[:, idx].set(
            rec["part_aux"], mode="drop")

    def build_tree(self, grad, hess, bag_cnt=None,
                   feature_mask=None, seed: int = 0,
                   feat_used=None, lazy_aux=None,
                   hist_scale=None) -> Dict[str, Any]:
        """Train one tree; returns the device state record."""
        if feature_mask is None:
            feature_mask = jnp.ones((self.F,), dtype=bool)
        if feat_used is None:
            feat_used = jnp.zeros((self.F,), dtype=bool)
        grad = jnp.asarray(grad, dtype=jnp.float32)
        hess = jnp.asarray(hess, dtype=jnp.float32)
        if bag_cnt is None:
            bag_cnt = self.N
        if self.cegb_lazy is not None and lazy_aux is None:
            lazy_aux = jnp.zeros((self.aux_rows, self.N), jnp.int32)
        return self._build(self._part0, grad, hess, jnp.int32(bag_cnt),
                           feature_mask, jnp.int32(seed), feat_used,
                           lazy_aux, hist_scale)

    def node_arrays_for_predict(self, st: Dict[str, Any]) -> Dict[str, Any]:
        node = {
            "col": st["node_col"],
            "bin_start": st["node_bin_start"],
            "is_bundled": st["node_is_bundled"],
            "num_bin": st["node_num_bin"],
            "default_bin": st["node_default_bin"],
            "missing_type": st["node_missing_type"],
            "threshold": st["node_threshold"],
            "default_left": st["node_default_left"],
            "left": st["node_left"],
            "right": st["node_right"],
            "num_nodes": st["s"],
        }
        if self.has_categorical:   # keys gate the cat arm in predict_leaf_binned
            node["is_cat"] = st["node_is_cat"]
            node["cat_set"] = st["node_cat_set"]
        return node
