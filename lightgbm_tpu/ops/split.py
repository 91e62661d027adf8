"""Best-split search over histograms, vectorized across (feature, bin).

TPU-native replacement for the reference's per-feature sequential threshold
scan (src/treelearner/feature_histogram.hpp FindBestThresholdSequentially:830,
GetSplitGains:759, CalculateSplittedLeafOutput:717) and the CUDA best-split
kernels (src/treelearner/cuda/cuda_best_split_finder.cu): the forward/reverse
accumulations become masked cumulative sums over the bin axis, gains are
evaluated for every (feature, bin, direction) candidate at once on the VPU,
and the arg-max reduction reproduces the reference's scan-order tie-breaking:

  * reverse scan runs "first" (forward replaces only on strictly-greater gain),
  * within the reverse scan larger thresholds win ties,
  * within the forward scan smaller thresholds win ties,
  * across features the smaller feature index wins ties.

Missing-value handling mirrors the reference dispatch
(feature_histogram.hpp FuncForNumricalL3:272-455):
  * MissingType::Zero  -> both scans skip the default(zero) bin; zeros follow
    ``default_left`` (reverse scan => default_left=True).
  * MissingType::NaN   -> the last bin holds NaNs; the reverse scan keeps it
    out of the right side (NaN defaults left), the forward scan keeps it right.
  * MissingType::None  -> single reverse scan, no skipping.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import F32_DOT_PRECISION

K_EPSILON = 1e-15
K_MIN_SCORE = -jnp.inf

MISSING_NONE = 0
MISSING_ZERO = 1
MISSING_NAN = 2


class SplitContext(NamedTuple):
    """Static per-feature metadata, device-resident (shapes (F,))."""
    num_bin: jnp.ndarray        # int32
    missing_type: jnp.ndarray   # int32
    default_bin: jnp.ndarray    # int32
    is_categorical: jnp.ndarray  # int32 (categorical handled separately)
    feature_index: jnp.ndarray  # int32 original feature id (for reporting)


class BestSplit(NamedTuple):
    gain: jnp.ndarray           # f32 scalar, relative gain (already minus shift)
    feature: jnp.ndarray        # int32, index into the used-feature enumeration
    threshold: jnp.ndarray      # int32 bin threshold
    default_left: jnp.ndarray   # bool
    left_sum_g: jnp.ndarray
    left_sum_h: jnp.ndarray
    right_sum_g: jnp.ndarray
    right_sum_h: jnp.ndarray
    left_count: jnp.ndarray     # int32 (hessian-estimated, like the reference)
    right_count: jnp.ndarray
    left_output: jnp.ndarray
    right_output: jnp.ndarray
    is_cat: jnp.ndarray         # bool — categorical split
    cat_set: jnp.ndarray        # (BF,) bool — feature-local bins going LEFT


class BestSplitLinear(NamedTuple):
    """``BestSplit`` plus the searched leaf's OWN fitted linear model
    ``value(x) = const + coeff * x`` (linear_tree_mode=leafwise_gain):
    the best whole-leaf single-feature fit, read off the same moment
    prefix sums the candidate scan uses (last cumsum entry per feature
    = whole-leaf totals — zero extra passes).  This model is what the
    leaf predicts with if it is never split again, and its gain is the
    shift the split candidates must beat.  ``left_output`` /
    ``right_output`` keep the constant outputs — they stay the NaN-row
    fallback value of the linear leaves."""
    gain: jnp.ndarray
    feature: jnp.ndarray
    threshold: jnp.ndarray
    default_left: jnp.ndarray
    left_sum_g: jnp.ndarray
    left_sum_h: jnp.ndarray
    right_sum_g: jnp.ndarray
    right_sum_h: jnp.ndarray
    left_count: jnp.ndarray
    right_count: jnp.ndarray
    left_output: jnp.ndarray
    right_output: jnp.ndarray
    is_cat: jnp.ndarray
    cat_set: jnp.ndarray
    self_const: jnp.ndarray     # f32 — this leaf's model intercept
    self_coeff: jnp.ndarray     # f32 — this leaf's model slope
    self_feature: jnp.ndarray   # int32 — ORIGINAL feature id of the model


def _threshold_l1(s, l1):
    return jnp.sign(s) * jnp.maximum(0.0, jnp.abs(s) - l1)


def leaf_output(sum_g, sum_h, l1, l2, max_delta_step):
    """reference: CalculateSplittedLeafOutput (feature_histogram.hpp:717)."""
    ret = -_threshold_l1(sum_g, l1) / (sum_h + l2)
    if max_delta_step > 0:
        ret = jnp.clip(ret, -max_delta_step, max_delta_step)
    return ret


def _leaf_gain_given_output(sum_g, sum_h, l1, l2, out):
    sg = _threshold_l1(sum_g, l1)
    return -(2.0 * sg * out + (sum_h + l2) * out * out)


def leaf_gain(sum_g, sum_h, l1, l2, max_delta_step):
    """reference: GetLeafGain (feature_histogram.hpp:800)."""
    if max_delta_step > 0:
        out = leaf_output(sum_g, sum_h, l1, l2, max_delta_step)
        return _leaf_gain_given_output(sum_g, sum_h, l1, l2, out)
    sg = _threshold_l1(sum_g, l1)
    return sg * sg / (sum_h + l2)


def find_best_split_categorical(feat_hist: jnp.ndarray, ctx: SplitContext,
                                sum_g, sum_h_tot, num_data,
                                l1: float, l2: float, max_delta_step: float,
                                min_gain_shift, min_data_in_leaf: int,
                                min_sum_hessian: float,
                                max_cat_threshold: int, cat_l2: float,
                                cat_smooth: float, max_cat_to_onehot: int,
                                min_data_per_group: int,
                                cmin=None, cmax=None):
    """Per-feature best categorical split, vectorized over (feature, bin).

    Mirrors FindBestThresholdCategoricalInner
    (src/treelearner/feature_histogram.cpp:144-340):
      * one-vs-rest when ``num_bin <= max_cat_to_onehot`` (plain lambda_l2);
      * otherwise bins with estimated count >= cat_smooth are sorted ascending
        by ``sum_g / (sum_h + cat_smooth)`` and prefix sets are scanned from
        both ends (at most ``min(max_cat_threshold, (used+1)/2)`` categories),
        with ``lambda_l2 + cat_l2`` regularization and candidate evaluation
        gated on ``min_data_per_group`` rows accumulated since the previous
        candidate;
      * bin 0 (the NaN/other bin) is never part of the left set, so missing
        and unseen categories always go right (default_left=false).

    The sequential C++ scan becomes masked cumulative sums along the sorted
    bin axis plus one short `lax.scan` carrying the per-feature
    ``cnt_cur_group`` counter; break conditions (monotone in the scan
    position) become cumulative-max masks.

    Returns per-feature arrays: (gain (F,), member (F, BF) bool,
    left_g, left_h_incl_eps, left_count, l2_eff (F,)).
    """
    F, BF, _ = feat_hist.shape
    G = feat_hist[..., 0]
    H = feat_hist[..., 1]
    cnt_factor = num_data / sum_h_tot
    l2c = l2 + cat_l2

    def pair_gain(lg, lh, rg, rh, l2_eff):
        """Two-sided gain; with monotone bounds active the child outputs are
        clipped to [cmin, cmax] first (reference: constrained
        CalculateSplittedLeafOutput + GetLeafGainGivenOutput)."""
        if cmin is None:
            return (leaf_gain(lg, lh, l1, l2_eff, max_delta_step) +
                    leaf_gain(rg, rh, l1, l2_eff, max_delta_step))
        lo = jnp.clip(leaf_output(lg, lh, l1, l2_eff, max_delta_step),
                      cmin, cmax)
        ro = jnp.clip(leaf_output(rg, rh, l1, l2_eff, max_delta_step),
                      cmin, cmax)
        return (_leaf_gain_given_output(lg, lh, l1, l2_eff, lo) +
                _leaf_gain_given_output(rg, rh, l1, l2_eff, ro))

    bins = jax.lax.broadcasted_iota(jnp.int32, (F, BF), 1)
    nb = ctx.num_bin[:, None]
    in_range = (bins >= 1) & (bins < nb)
    cnt_bin = jnp.floor(H * cnt_factor + 0.5).astype(jnp.int32) * in_range
    num_data_i = num_data.astype(jnp.int32) if hasattr(num_data, "astype") \
        else jnp.int32(num_data)

    use_onehot = ctx.num_bin <= max_cat_to_onehot        # (F,)

    # ---- one-vs-rest (feature_histogram.cpp:184-239) ----
    hess_t = H + K_EPSILON
    other_g = sum_g - G
    other_h = sum_h_tot - H - K_EPSILON
    other_cnt = num_data_i - cnt_bin
    gain_oh = pair_gain(G, hess_t, other_g, other_h, l2)
    valid_oh = (in_range & (cnt_bin >= min_data_in_leaf) &
                (H >= min_sum_hessian) & (other_cnt >= min_data_in_leaf) &
                (other_h >= min_sum_hessian) & (gain_oh > min_gain_shift))
    gain_oh = jnp.where(valid_oh, gain_oh, K_MIN_SCORE)
    best_oh = jnp.argmax(gain_oh, axis=1)                 # (F,)
    best_oh_gain = jnp.take_along_axis(gain_oh, best_oh[:, None], 1)[:, 0]
    member_oh = bins == best_oh[:, None]

    # ---- sorted prefix sets (feature_histogram.cpp:240-339) ----
    valid_s = in_range & (cnt_bin.astype(jnp.float32) >= cat_smooth)
    ratio = jnp.where(valid_s, G / (H + cat_smooth), jnp.inf)
    order = jnp.argsort(ratio, axis=1, stable=True)       # ascending
    inv_rank = jnp.argsort(order, axis=1, stable=True)    # bin -> sorted pos
    used = valid_s.sum(axis=1).astype(jnp.int32)          # (F,)
    max_num_cat = jnp.minimum(jnp.int32(max_cat_threshold), (used + 1) // 2)

    sG = jnp.take_along_axis(jnp.where(valid_s, G, 0.0), order, axis=1)
    sH = jnp.take_along_axis(jnp.where(valid_s, H, 0.0), order, axis=1)
    sC = jnp.take_along_axis(jnp.where(valid_s, cnt_bin, 0), order, axis=1)
    pg = jnp.cumsum(sG, axis=1)
    ph = jnp.cumsum(sH, axis=1)
    pc = jnp.cumsum(sC, axis=1)
    tvg = pg[:, -1:]
    tvh = ph[:, -1:]
    tvc = pc[:, -1:]

    pos = jax.lax.broadcasted_iota(jnp.int32, (F, BF), 1)

    def prefix_at(p, idx):
        """p[:, idx] with idx == -1 -> 0 (idx is (F, BF) int32)."""
        v = jnp.take_along_axis(p, jnp.maximum(idx, 0), axis=1)
        return jnp.where(idx >= 0, v, jnp.zeros_like(v))

    # forward (dir=+1): left set = sorted[0..i]
    lg_f = pg
    lh_f = ph + K_EPSILON
    lc_f = pc
    # reverse (dir=-1): left set = sorted[used-1-i .. used-1]
    rev_idx = used[:, None] - 2 - pos
    lg_r = tvg - prefix_at(pg, rev_idx)
    lh_r = tvh - prefix_at(ph, rev_idx) + K_EPSILON
    lc_r = tvc - prefix_at(pc, rev_idx)

    in_loop = (pos < used[:, None]) & (pos < max_num_cat[:, None])
    # per-step counts in each direction's visit order: forward visits sorted
    # position i at step i, reverse visits sorted position used-1-i
    step_cnt_fwd = sC
    step_cnt_rev = prefix_at(pc, used[:, None] - 1 - pos) - \
        prefix_at(pc, used[:, None] - 2 - pos)

    def candidates(lg, lh, lc, step_cnt):
        rg = sum_g - lg
        rh = sum_h_tot - lh
        rc = num_data_i - lc
        left_ok = (lc >= min_data_in_leaf) & (lh >= min_sum_hessian)
        broken = ((rc < min_data_in_leaf) | (rc < min_data_per_group) |
                  (rh < min_sum_hessian))
        not_broken = jnp.cumsum(broken.astype(jnp.int32), axis=1) == 0

        # cnt_cur_group gate: scan along the sorted axis, carry (F,) counter
        def step(c, xs):
            cnt_i, ok_i = xs
            c = c + cnt_i
            ev = ok_i & (c >= min_data_per_group)
            return jnp.where(ev, 0, c), ev

        # the carry derives from the (possibly device-varying) inputs so
        # shard_map's vma typing accepts the scan (a constant zero carry
        # is unvarying and trips "carry input/output types differ")
        carry0 = (step_cnt[:, 0] * 0).astype(jnp.int32)
        _, ev = jax.lax.scan(
            step, carry0,
            (step_cnt.T, (left_ok & not_broken & in_loop).T))
        evaluated = ev.T
        gain = pair_gain(lg, lh, rg, rh, l2c)
        gain = jnp.where(evaluated & (gain > min_gain_shift),
                         gain, K_MIN_SCORE)
        return gain

    gain_fwd = candidates(lg_f, lh_f, lc_f, step_cnt_fwd)
    gain_rev = candidates(lg_r, lh_r, lc_r, step_cnt_rev)
    best_i_f = jnp.argmax(gain_fwd, axis=1)               # first wins ties
    best_g_f = jnp.take_along_axis(gain_fwd, best_i_f[:, None], 1)[:, 0]
    best_i_r = jnp.argmax(gain_rev, axis=1)
    best_g_r = jnp.take_along_axis(gain_rev, best_i_r[:, None], 1)[:, 0]
    use_rev = best_g_r > best_g_f                         # dir=+1 wins ties
    best_sorted_gain = jnp.where(use_rev, best_g_r, best_g_f)
    k = jnp.where(use_rev, best_i_r, best_i_f) + 1        # num cats in set
    member_fwd = inv_rank < k[:, None]
    member_rev = (inv_rank >= used[:, None] - k[:, None]) & \
                 (inv_rank < used[:, None])
    member_sorted = jnp.where(use_rev[:, None], member_rev, member_fwd) & valid_s

    # ---- merge the two modes (exclusive per feature) ----
    gain_c = jnp.where(use_onehot, best_oh_gain, best_sorted_gain)
    member = jnp.where(use_onehot[:, None], member_oh, member_sorted)
    oh_g = jnp.take_along_axis(G, best_oh[:, None], 1)[:, 0]
    oh_h = jnp.take_along_axis(H, best_oh[:, None], 1)[:, 0] + K_EPSILON
    oh_c = jnp.take_along_axis(cnt_bin, best_oh[:, None], 1)[:, 0]
    sel = lambda a_f, a_r: jnp.where(  # noqa: E731
        use_rev, jnp.take_along_axis(a_r, best_i_r[:, None], 1)[:, 0],
        jnp.take_along_axis(a_f, best_i_f[:, None], 1)[:, 0])
    lg_c = jnp.where(use_onehot, oh_g, sel(lg_f, lg_r))
    lh_c = jnp.where(use_onehot, oh_h, sel(lh_f, lh_r))
    lc_c = jnp.where(use_onehot, oh_c, sel(lc_f, lc_r).astype(jnp.int32))
    l2_eff = jnp.where(use_onehot, l2, l2c)
    return gain_c, member, lg_c, lh_c, lc_c, l2_eff


def find_best_split_fast(feat_hist: jnp.ndarray, ctx: SplitContext,
                         sum_g, sum_h, num_data,
                         l1: float, l2: float, max_delta_step: float,
                         min_gain_to_split: float, min_data_in_leaf: int,
                         min_sum_hessian: float,
                         feature_mask: jnp.ndarray | None = None,
                         rand_bins: jnp.ndarray | None = None,
                         feature_contri: jnp.ndarray | None = None):
    """Lean all-numerical best-split search.

    Bit-identical to ``find_best_split`` for plain configs (no
    categorical / monotone / CEGB / path smoothing / voting gains), but
    restructured for HLO op count — the per-split fixed cost of the tree
    loop on TPU is op-dispatch-bound (PERF.md), not FLOP-bound:

      * ONE stacked cumulative sum over a (6, F, BF) tensor replaces the
        six per-stat scans;
      * the reference's scan-order tie-breaking
        (FindBestThresholdSequentially, feature_histogram.hpp:830 — the
        reverse scan first, larger thresholds winning reverse ties,
        smaller forward ties, smaller feature index across features)
        is encoded into a rank every candidate carries — per feature
        the reverse scan's thresholds descending, then the forward
        scan's ascending — so one min over the ranks of the largest
        gains replaces the per-feature/per-direction arg-max cascade;
      * the winner's statistics are read by masked sums over the same
        (F, BF) grids: nothing is reversed, concatenated or flattened,
        whose relayouts grow with the width.

    Counts ride the f32 cumsum (exact for leaves below 2^24 rows; the
    caller gates on dataset size).
    """
    F, BF, _ = feat_hist.shape
    G = feat_hist[..., 0]
    H = feat_hist[..., 1]
    sum_h_tot = sum_h + 2 * K_EPSILON
    num_data = num_data.astype(jnp.float32) if hasattr(num_data, "astype") \
        else jnp.float32(num_data)
    cnt_factor = num_data / sum_h_tot

    bins = jax.lax.broadcasted_iota(jnp.int32, (F, BF), 1)
    nb = ctx.num_bin[:, None]
    in_range = bins < nb
    missing = ctx.missing_type[:, None]
    dflt = ctx.default_bin[:, None]
    is_zero_miss = missing == MISSING_ZERO
    is_nan_miss = missing == MISSING_NAN
    two_scan = (nb > 2) & (missing != MISSING_NONE)
    cnt_bin = jnp.floor(H * cnt_factor + 0.5) * in_range      # f32, exact

    mask_f = in_range & ~(is_zero_miss & (bins == dflt))
    bmax = nb - 1 - (is_nan_miss & two_scan).astype(jnp.int32)
    mask_r = (in_range & ~(two_scan & is_zero_miss & (bins == dflt)) &
              (bins <= bmax))

    z = jnp.float32(0.0)
    stacked = jnp.stack([
        jnp.where(mask_f, G, z), jnp.where(mask_f, H, z),
        jnp.where(mask_f, cnt_bin, z),
        jnp.where(mask_r, G, z), jnp.where(mask_r, H, z),
        jnp.where(mask_r, cnt_bin, z)])                       # (6, F, BF)
    if jax.default_backend() == "tpu":
        # prefix sums as ONE inclusive lower-triangular matmul on the
        # MXU: XLA's cumsum lowering costs a log-depth pass cascade per
        # operand, and the per-split cost on TPU is op-DISPATCH-bound.
        # F32_DOT_PRECISION keeps the integer counts exact below 2^24.
        tri = (jax.lax.broadcasted_iota(jnp.int32, (BF, BF), 0) <=
               jax.lax.broadcasted_iota(jnp.int32, (BF, BF), 1)
               ).astype(jnp.float32)
        cs = jax.lax.dot_general(
            stacked, tri, (((2,), (0,)), ((), ())),
            precision=F32_DOT_PRECISION,
            preferred_element_type=jnp.float32)               # (6, F, BF)
    else:
        # off-TPU the triangular matmul is O(F*BF^2) of REAL work — it
        # dominated the CPU host's per-iteration fixed cost (~44 MFLOP
        # per split at F=28, BF=255: ~60% of the 65k-row iteration,
        # PERF.md round 12) — where the log-depth cumsum is O(F*BF)
        cs = jnp.cumsum(stacked, axis=2)                      # (6, F, BF)

    left_g_f = cs[0]
    left_h_f = cs[1] + K_EPSILON
    left_c_f = cs[2]
    right_g_f = sum_g - left_g_f
    right_h_f = sum_h_tot - left_h_f
    right_c_f = num_data - left_c_f

    right_g_r = cs[3, :, -1:] - cs[3]
    right_h_r = cs[4, :, -1:] - cs[4] + K_EPSILON
    right_c_r = cs[5, :, -1:] - cs[5]
    left_g_r = sum_g - right_g_r
    left_h_r = sum_h_tot - right_h_r
    left_c_r = num_data - right_c_r

    gain_f = (leaf_gain(left_g_f, left_h_f, l1, l2, max_delta_step) +
              leaf_gain(right_g_f, right_h_f, l1, l2, max_delta_step))
    gain_r = (leaf_gain(left_g_r, left_h_r, l1, l2, max_delta_step) +
              leaf_gain(right_g_r, right_h_r, l1, l2, max_delta_step))

    gain_shift = leaf_gain(sum_g, sum_h_tot, l1, l2, max_delta_step)
    min_gain_shift = gain_shift + min_gain_to_split
    mdl = jnp.float32(min_data_in_leaf)

    def common_valid(lc, rc, lh, rh):
        return ((lc >= mdl) & (rc >= mdl) &
                (lh >= min_sum_hessian) & (rh >= min_sum_hessian))

    valid_f = (two_scan & in_range & (bins <= nb - 2) &
               ~(is_zero_miss & (bins == dflt)) &
               common_valid(left_c_f, right_c_f, left_h_f, right_h_f) &
               (gain_f > min_gain_shift))
    valid_r = (in_range & (bins <= bmax - 1) &
               ~(two_scan & is_zero_miss & (bins == dflt - 1)) &
               common_valid(left_c_r, right_c_r, left_h_r, right_h_r) &
               (gain_r > min_gain_shift))
    if feature_mask is not None:
        valid_f &= feature_mask[:, None]
        valid_r &= feature_mask[:, None]
    if rand_bins is not None:
        # extra_trees: each feature evaluates ONE random threshold
        # (feature_histogram.hpp USE_RAND arms, rand_threshold)
        at_rand = bins == rand_bins[:, None]
        valid_f &= at_rand
        valid_r &= at_rand

    neg = jnp.float32(K_MIN_SCORE)
    if feature_contri is not None:
        # per-feature gain scaling (feature_histogram.hpp:174
        # `output->gain *= meta_->penalty`): candidates compete on the
        # SCALED relative gain, so the flat argmax runs on it directly
        fc = feature_contri[:, None]
        cand_f = jnp.where(valid_f, (gain_f - min_gain_shift) * fc, neg)
        cand_r = jnp.where(valid_r, (gain_r - min_gain_shift) * fc, neg)
    else:
        cand_f = jnp.where(valid_f, gain_f, neg)
        cand_r = jnp.where(valid_r, gain_r, neg)
    # The winner is the candidate of the largest gain that comes first in
    # the reference's scan order: feature-major, within a feature the
    # reverse scan's thresholds descending, then the forward scan's
    # ascending.  Every candidate carries that rank as a key, and the
    # winner is the smallest key among the largest gains: elementwise
    # operations and whole reductions over the two (F, BF) grids, with no
    # lane reversal, no concatenation and no flattening (at 2000 features
    # the flat (F * 2 * BF) form took 14 ms a split on the v5e, three
    # relayouts and one arg-max of a million elements: PERF.md, PR 35).
    # ops/split_pallas.py ranks its candidates the same way.
    feat = jax.lax.broadcasted_iota(jnp.int32, (F, BF), 0)
    per_f = 2 * BF
    # the two scans side by side on a new MAJOR axis (no lane moves), so
    # that each step below is one reduction
    cand = jnp.stack([cand_r, cand_f])                        # (2, F, BF)
    key = jnp.stack([feat * per_f + (BF - 1 - bins),
                     feat * per_f + BF + bins])
    best_gain = jnp.max(cand)
    widx = jnp.min(jnp.where(cand >= best_gain, key,
                             jnp.int32(F * per_f)))
    # default_left: reverse scan => True, except single-scan NaN features
    dl_r = jnp.broadcast_to((two_scan | ~is_nan_miss).astype(jnp.float32),
                            (F, BF))
    stats = jnp.stack([
        jnp.stack([left_g_r, left_g_f]), jnp.stack([left_h_r, left_h_f]),
        jnp.stack([left_c_r, left_c_f]),
        jnp.stack([dl_r, jnp.zeros((F, BF), jnp.float32)])])  # (4, 2, F, BF)
    picked = jnp.sum(jnp.where(key == widx, stats, z), axis=(1, 2, 3))
    lg, lh, lc_f32, dl = picked[0], picked[1], picked[2], picked[3]

    best_f = widx // per_f
    r = widx - best_f * per_f
    best_t = jnp.where(r < BF, BF - 1 - r, r - BF)

    rg = sum_g - lg
    rh = sum_h_tot - lh
    rc = num_data - lc_f32
    args = (l1, l2, max_delta_step)
    gain_out = (best_gain if feature_contri is not None
                else best_gain - min_gain_shift)
    return BestSplit(
        gain=jnp.where(best_gain > neg, gain_out, neg),
        feature=best_f.astype(jnp.int32),
        threshold=best_t.astype(jnp.int32),
        default_left=dl > 0.5,
        left_sum_g=lg, left_sum_h=lh - K_EPSILON,
        right_sum_g=rg, right_sum_h=rh - K_EPSILON,
        left_count=lc_f32.astype(jnp.int32),
        right_count=rc.astype(jnp.int32),
        left_output=leaf_output(lg, lh, *args),
        right_output=leaf_output(rg, rh, *args),
        is_cat=jnp.bool_(False),
        cat_set=jnp.zeros((1,), jnp.bool_),
    )


def _linear_side(g, h, xg, xh, xxh, l2: float, lam: float):
    """Closed-form leaf gain + model over ``f(x) = coeff*x + const``.

    Centered ridge normal equations: with ``xm = Σxh/Σh`` the
    h-weighted mean, the 2x2 system diagonalizes into the constant part
    and an independent slope part over the centered regressor —

        gain  = g^2/(h + l2)  +  xgc^2/(var + lam)
        coeff = -xgc/(var + lam),  const = -g/(h + l2) - coeff*xm

    where ``xgc = Σxg - xm*Σg`` and ``var = Σx^2h - xm*Σxh`` (the
    h-weighted variance mass).  ``lam`` is ``linear_lambda`` on the
    slope, ``l2`` stays on the (centered) intercept — the constant term
    and NaN-fallback value therefore match the constant search exactly.
    The centered form avoids the catastrophic f32 cancellation of the
    raw determinant when x barely varies inside a leaf; a
    non-positive ``var`` (constant regressor, or cancellation noise)
    falls back to the constant model — the reference's degenerate-leaf
    behaviour (linear_tree_learner.cpp singular-XTHX guard)."""
    xm = xh / h
    xgc = xg - xm * g
    var = xxh - xm * xh
    lin_ok = var > 0.0
    denom = jnp.where(lin_ok, var + lam, jnp.float32(1.0))
    coeff = jnp.where(lin_ok, -xgc / denom, jnp.float32(0.0))
    gain = g * g / (h + l2) + jnp.where(lin_ok, xgc * xgc / denom,
                                        jnp.float32(0.0))
    const = -g / (h + l2) - coeff * xm
    return gain, coeff, const


def find_best_split_linear(feat_hist: jnp.ndarray, ctx: SplitContext,
                           sum_g, sum_h, num_data,
                           l2: float, min_gain_to_split: float,
                           min_data_in_leaf: int, min_sum_hessian: float,
                           rep_vals: jnp.ndarray, linear_lambda: float,
                           feature_mask: jnp.ndarray | None = None,
                           rand_bins: jnp.ndarray | None = None):
    """Piece-wise-linear best-split search (linear_tree_mode=
    leafwise_gain): split gain is computed over leaf-local LINEAR
    models, vectorized over (feature, bin, direction) exactly like
    ``find_best_split_fast`` — same masks, same candidate order, same
    tie-breaking, same packed winner read.

    The linear moment planes Σx·g, Σx·h, Σx·x·h are NOT extra matmul
    accumulations: within one bin the (binned) regressor is a per-bin
    constant, so each moment plane is the existing G/H histogram scaled
    by the per-(feature, bin) representative value ``rep_vals`` (F, BF)
    (see ops/histogram.py:linear_moment_planes — strictly cheaper than
    accumulating extra one-hot columns, and the subtraction trick holds
    automatically).  ``rep_vals`` must be 0 at the NaN bin and at the
    MISSING_ZERO default bin (the rows routed by ``default_left``), so
    both scan directions share ONE set of moment prefix sums: missing
    rows contribute zero moment mass wherever they land.

    Gain per side is the centered closed form of ``_linear_side``.

    The gain shift is the searched leaf's OWN fitted model gain, not
    the constant parent gain: the leaf already predicts with its best
    whole-leaf single-feature model (fitted here from the per-feature
    moment TOTALS — the last prefix-sum entry, so it is free), and a
    split replaces that model with two children fitted on the split
    feature only.  Shifting by the constant gain overstates every
    candidate by (self model gain - constant gain) and measurably
    picks splits that LOSE realized training loss — the children drop
    the slope the parent's model carried.  With the self-model shift,
    ``gain`` is the exact realized surrogate improvement of the split
    (f32 histogram noise aside).

    ``l1`` / ``max_delta_step`` / monotone / CEGB are ineligible for
    this mode (the caller gates and falls back to refit).  Returns
    ``BestSplitLinear`` — the leaf's own (const, coeff, feature) model
    rides along for the tree builder to record."""
    F, BF, _ = feat_hist.shape
    G = feat_hist[..., 0]
    H = feat_hist[..., 1]
    sum_h_tot = sum_h + 2 * K_EPSILON
    num_data = num_data.astype(jnp.float32) if hasattr(num_data, "astype") \
        else jnp.float32(num_data)
    cnt_factor = num_data / sum_h_tot

    bins = jax.lax.broadcasted_iota(jnp.int32, (F, BF), 1)
    nb = ctx.num_bin[:, None]
    in_range = bins < nb
    missing = ctx.missing_type[:, None]
    dflt = ctx.default_bin[:, None]
    is_zero_miss = missing == MISSING_ZERO
    is_nan_miss = missing == MISSING_NAN
    two_scan = (nb > 2) & (missing != MISSING_NONE)
    cnt_bin = jnp.floor(H * cnt_factor + 0.5) * in_range      # f32, exact

    mask_f = in_range & ~(is_zero_miss & (bins == dflt))
    bmax = nb - 1 - (is_nan_miss & two_scan).astype(jnp.int32)
    mask_r = (in_range & ~(two_scan & is_zero_miss & (bins == dflt)) &
              (bins <= bmax))

    z = jnp.float32(0.0)
    rep = jnp.where(in_range, rep_vals.astype(jnp.float32), z)
    XG = rep * G
    XH = rep * H
    XXH = rep * XH
    stacked = jnp.stack([
        jnp.where(mask_f, G, z), jnp.where(mask_f, H, z),
        jnp.where(mask_f, cnt_bin, z),
        jnp.where(mask_r, G, z), jnp.where(mask_r, H, z),
        jnp.where(mask_r, cnt_bin, z),
        XG, XH, XXH])                                         # (9, F, BF)
    if jax.default_backend() == "tpu":
        tri = (jax.lax.broadcasted_iota(jnp.int32, (BF, BF), 0) <=
               jax.lax.broadcasted_iota(jnp.int32, (BF, BF), 1)
               ).astype(jnp.float32)
        cs = jax.lax.dot_general(
            stacked, tri, (((2,), (0,)), ((), ())),
            precision=F32_DOT_PRECISION,
            preferred_element_type=jnp.float32)               # (9, F, BF)
    else:
        cs = jnp.cumsum(stacked, axis=2)                      # (9, F, BF)

    left_g_f = cs[0]
    left_h_f = cs[1] + K_EPSILON
    left_c_f = cs[2]
    right_g_f = sum_g - left_g_f
    right_h_f = sum_h_tot - left_h_f
    right_c_f = num_data - left_c_f

    right_g_r = cs[3, :, -1:] - cs[3]
    right_h_r = cs[4, :, -1:] - cs[4] + K_EPSILON
    right_c_r = cs[5, :, -1:] - cs[5]
    left_g_r = sum_g - right_g_r
    left_h_r = sum_h_tot - right_h_r
    left_c_r = num_data - right_c_r

    # moment prefix sums are direction-agnostic (missing rows carry
    # zero moment mass): left = inclusive prefix, right = total - left
    lxg, lxh, lxxh = cs[6], cs[7], cs[8]
    rxg = cs[6, :, -1:] - lxg
    rxh = cs[7, :, -1:] - lxh
    rxxh = cs[8, :, -1:] - lxxh

    lam = jnp.float32(linear_lambda)
    lgain_f, _, _ = _linear_side(left_g_f, left_h_f,
                                 lxg, lxh, lxxh, l2, lam)
    rgain_f, _, _ = _linear_side(right_g_f, right_h_f,
                                 rxg, rxh, rxxh, l2, lam)
    lgain_r, _, _ = _linear_side(left_g_r, left_h_r,
                                 lxg, lxh, lxxh, l2, lam)
    rgain_r, _, _ = _linear_side(right_g_r, right_h_r,
                                 rxg, rxh, rxxh, l2, lam)
    gain_f = lgain_f + rgain_f
    gain_r = lgain_r + rgain_r

    # the leaf's OWN model: best whole-leaf single-feature fit over the
    # moment totals (feature_mask-restricted, like the candidates — the
    # sampled-out features stay invisible to this node).  Degenerate
    # features (trivial/categorical rep rows are all-zero, or var<=0)
    # fall back inside _linear_side to the constant model, so the
    # argmax always yields a usable (coeff, const) pair.
    sf_gain, sf_coeff, sf_const = _linear_side(
        sum_g, sum_h_tot, cs[6, :, -1], cs[7, :, -1], cs[8, :, -1],
        l2, lam)
    sf_cand = sf_gain if feature_mask is None else \
        jnp.where(feature_mask, sf_gain, jnp.float32(K_MIN_SCORE))
    sf_j = jnp.argmax(sf_cand).astype(jnp.int32)
    self_gain = sf_gain[sf_j]
    self_coeff = sf_coeff[sf_j]
    self_const = sf_const[sf_j]
    self_feature = ctx.feature_index[sf_j]

    # shift: the leaf's own model gain (see docstring) — a split must
    # beat the model the leaf already predicts with
    min_gain_shift = self_gain + min_gain_to_split
    mdl = jnp.float32(min_data_in_leaf)

    def common_valid(lc, rc, lh, rh):
        return ((lc >= mdl) & (rc >= mdl) &
                (lh >= min_sum_hessian) & (rh >= min_sum_hessian))

    valid_f = (two_scan & in_range & (bins <= nb - 2) &
               ~(is_zero_miss & (bins == dflt)) &
               common_valid(left_c_f, right_c_f, left_h_f, right_h_f) &
               (gain_f > min_gain_shift))
    valid_r = (in_range & (bins <= bmax - 1) &
               ~(two_scan & is_zero_miss & (bins == dflt - 1)) &
               common_valid(left_c_r, right_c_r, left_h_r, right_h_r) &
               (gain_r > min_gain_shift))
    if feature_mask is not None:
        valid_f &= feature_mask[:, None]
        valid_r &= feature_mask[:, None]
    if rand_bins is not None:
        at_rand = bins == rand_bins[:, None]
        valid_f &= at_rand
        valid_r &= at_rand

    neg = jnp.float32(K_MIN_SCORE)
    cand_f = jnp.where(valid_f, gain_f, neg)
    cand_r = jnp.where(valid_r, gain_r, neg)
    gains = jnp.concatenate([cand_r[:, ::-1], cand_f], axis=1)
    dl_r = jnp.broadcast_to((two_scan | ~is_nan_miss).astype(jnp.float32),
                            (F, BF))
    stats = jnp.stack([
        jnp.concatenate([left_g_r[:, ::-1], left_g_f], axis=1),
        jnp.concatenate([left_h_r[:, ::-1], left_h_f], axis=1),
        jnp.concatenate([left_c_r[:, ::-1], left_c_f], axis=1),
        jnp.concatenate([dl_r, jnp.zeros((F, BF), jnp.float32)], axis=1),
    ]).reshape(4, F * 2 * BF)

    flat = gains.reshape(F * 2 * BF)
    widx = jnp.argmax(flat).astype(jnp.int32)
    best_gain = flat[widx]
    picked = jax.lax.dynamic_slice(stats, (0, widx), (4, 1))[:, 0]
    lg, lh, lc_f32, dl = picked[0], picked[1], picked[2], picked[3]

    per_f = 2 * BF
    best_f = widx // per_f
    r = widx - best_f * per_f
    best_t = jnp.where(r < BF, BF - 1 - r, r - BF)

    rg = sum_g - lg
    rh = sum_h_tot - lh
    rc = num_data - lc_f32
    invalid = best_gain <= neg
    return BestSplitLinear(
        gain=jnp.where(invalid, neg, best_gain - min_gain_shift),
        feature=best_f.astype(jnp.int32),
        threshold=best_t.astype(jnp.int32),
        default_left=dl > 0.5,
        left_sum_g=lg, left_sum_h=lh - K_EPSILON,
        right_sum_g=rg, right_sum_h=rh - K_EPSILON,
        left_count=lc_f32.astype(jnp.int32),
        right_count=rc.astype(jnp.int32),
        left_output=leaf_output(lg, lh, 0.0, l2, 0.0),
        right_output=leaf_output(rg, rh, 0.0, l2, 0.0),
        is_cat=jnp.bool_(False),
        cat_set=jnp.zeros((1,), jnp.bool_),
        self_const=self_const, self_coeff=self_coeff,
        self_feature=self_feature,
    )


def find_best_split(feat_hist: jnp.ndarray, ctx: SplitContext,
                    sum_g, sum_h, num_data,
                    l1: float, l2: float, max_delta_step: float,
                    min_gain_to_split: float, min_data_in_leaf: int,
                    min_sum_hessian: float,
                    feature_mask: jnp.ndarray | None = None,
                    cat_params: dict | None = None,
                    monotone: jnp.ndarray | None = None,
                    cmin=None, cmax=None, depth=None,
                    monotone_penalty: float = 0.0,
                    cegb_count_coeff: float = 0.0,
                    cegb_feature_delta: jnp.ndarray | None = None,
                    path_smooth: float = 0.0, parent_output=None,
                    with_feature_gains: bool = False,
                    rand_bins: jnp.ndarray | None = None,
                    feature_contri: jnp.ndarray | None = None):
    """Find the best numerical split for one leaf.

    Args:
      feat_hist: (F, BF, 2) per-feature histogram view (default-bin stats
        already reconstructed for bundled features).
      ctx: per-feature metadata.
      sum_g/sum_h/num_data: leaf aggregates (sum_h WITHOUT the 2*eps pad; the
        pad is applied here like FindBestThreshold, feature_histogram.hpp:165).
      feature_mask: optional (F,) bool — features allowed at this node
        (feature_fraction / interaction constraints).
      monotone: optional (F,) int32 per-feature monotone direction (+1/-1/0);
        when given, basic-mode monotone constraints are active (reference:
        monotone_constraints.hpp BasicLeafConstraints + the USE_MC arms of
        feature_histogram.hpp GetSplitGains): child outputs are clipped to
        the leaf's [cmin, cmax] bounds, candidates violating the direction
        are rejected, and `monotone_penalty` shrinks gains of splits on
        monotone features by depth (serial_tree_learner.cpp:988).
      with_feature_gains: also return the (F,) per-feature best gains
        (absolute, K_MIN_SCORE where invalid) — used by the voting-parallel
        learner's local vote (voting_parallel_tree_learner.cpp).
    """
    F, BF, _ = feat_hist.shape
    G = feat_hist[..., 0]
    H = feat_hist[..., 1]
    sum_h_tot = sum_h + 2 * K_EPSILON
    num_data = num_data.astype(jnp.float32) if hasattr(num_data, "astype") else jnp.float32(num_data)
    cnt_factor = num_data / sum_h_tot

    bins = jax.lax.broadcasted_iota(jnp.int32, (F, BF), 1)
    nb = ctx.num_bin[:, None]
    in_range = bins < nb
    missing = ctx.missing_type[:, None]
    dflt = ctx.default_bin[:, None]
    is_zero_miss = missing == MISSING_ZERO
    is_nan_miss = missing == MISSING_NAN
    two_scan = (ctx.num_bin[:, None] > 2) & (missing != MISSING_NONE)

    # per-bin estimated counts (reference rounds per bin: Common::RoundInt)
    cnt_bin = jnp.floor(H * cnt_factor + 0.5).astype(jnp.int32) * in_range

    # --- forward scan (missing goes right) ---
    skip_fwd = is_zero_miss & (bins == dflt)
    Gf = jnp.where(in_range & ~skip_fwd, G, 0.0)
    Hf = jnp.where(in_range & ~skip_fwd, H, 0.0)
    Cf = jnp.where(in_range & ~skip_fwd, cnt_bin, 0)
    left_g_f = jnp.cumsum(Gf, axis=1)
    left_h_f = jnp.cumsum(Hf, axis=1) + K_EPSILON
    left_c_f = jnp.cumsum(Cf, axis=1)
    right_g_f = sum_g - left_g_f
    right_h_f = sum_h_tot - left_h_f
    right_c_f = num_data.astype(jnp.int32) - left_c_f

    # --- reverse scan (missing goes left) ---
    # right side accumulates bins (t, bmax]; bmax excludes the NaN bin.
    # The single-scan fallback (num_bin<=2 or MissingType::None,
    # feature_histogram.hpp:421-451) neither skips the default bin nor
    # excludes the NaN bin, hence the `two_scan` factors.
    bmax = nb - 1 - (is_nan_miss & two_scan).astype(jnp.int32)
    skip_rev = two_scan & is_zero_miss & (bins == dflt)
    mask_rev = in_range & ~skip_rev & (bins <= bmax)
    Gr = jnp.where(mask_rev, G, 0.0)
    Hr = jnp.where(mask_rev, H, 0.0)
    Cr = jnp.where(mask_rev, cnt_bin, 0)
    cum_g_r = jnp.cumsum(Gr, axis=1)
    cum_h_r = jnp.cumsum(Hr, axis=1)
    cum_c_r = jnp.cumsum(Cr, axis=1)
    tot_g_r = cum_g_r[:, -1:]
    tot_h_r = cum_h_r[:, -1:]
    tot_c_r = cum_c_r[:, -1:]
    right_g_r = tot_g_r - cum_g_r
    right_h_r = tot_h_r - cum_h_r + K_EPSILON
    right_c_r = tot_c_r - cum_c_r
    left_g_r = sum_g - right_g_r
    left_h_r = sum_h_tot - right_h_r
    left_c_r = num_data.astype(jnp.int32) - right_c_r

    use_mc = monotone is not None
    use_smooth = path_smooth > 0.0
    # advanced monotone mode passes PER-SIDE, per-(feature, threshold)
    # bound arrays ((cmin_left, cmin_right) tuples of (F, BF)); the
    # intermediate/basic modes pass scalars shared by both children
    # (monotone_constraints.hpp:858 AdvancedLeafConstraints vs :488)
    if isinstance(cmin, tuple):
        cmin_l, cmin_r = cmin
        cmax_l, cmax_r = cmax
        # the parent's own (whole-box) bounds are the loosest per-side
        # bounds: min over thresholds of each side's bound envelope
        cmin_p = jnp.minimum(jnp.min(cmin_l), jnp.min(cmin_r))
        cmax_p = jnp.maximum(jnp.max(cmax_l), jnp.max(cmax_r))
    else:
        cmin_l = cmin_r = cmin
        cmax_l = cmax_r = cmax
        cmin_p, cmax_p = cmin, cmax
    if use_smooth:
        # reference: USE_SMOOTHING arm of FindBestThresholdSequentially —
        # gain shift is evaluated at the leaf's CURRENT output
        gain_shift = _leaf_gain_given_output(sum_g, sum_h_tot, l1, l2,
                                             parent_output)
    elif use_mc:
        parent_out_est = jnp.clip(
            leaf_output(sum_g, sum_h_tot, l1, l2, max_delta_step),
            cmin_p, cmax_p)
        gain_shift = _leaf_gain_given_output(sum_g, sum_h_tot, l1, l2,
                                             parent_out_est)
    else:
        gain_shift = leaf_gain(sum_g, sum_h_tot, l1, l2, max_delta_step)
    min_gain_shift = gain_shift + min_gain_to_split

    def child_output(g, h, c, side):
        out = leaf_output(g, h, l1, l2, max_delta_step)
        if use_smooth:
            # reference: CalculateSplittedLeafOutput smoothing arm
            # (feature_histogram.hpp:717): shrink toward the parent output
            # proportionally to n/path_smooth
            f = c.astype(jnp.float32) / path_smooth
            out = out * f / (f + 1.0) + parent_output / (f + 1.0)
        if use_mc:
            out = jnp.clip(out, cmin_l if side == "l" else cmin_r,
                           cmax_l if side == "l" else cmax_r)
        return out

    def side_gain(gl, hl, gr, hr, cl, cr):
        if not (use_mc or use_smooth):
            return (leaf_gain(gl, hl, l1, l2, max_delta_step) +
                    leaf_gain(gr, hr, l1, l2, max_delta_step))
        lo = child_output(gl, hl, cl, "l")
        ro = child_output(gr, hr, cr, "r")
        g = (_leaf_gain_given_output(gl, hl, l1, l2, lo) +
             _leaf_gain_given_output(gr, hr, l1, l2, ro))
        if use_mc:
            mono = monotone[:, None]
            bad = ((mono > 0) & (lo > ro)) | ((mono < 0) & (lo < ro))
            g = jnp.where(bad, K_MIN_SCORE, g)
        return g

    gain_f = side_gain(left_g_f, left_h_f, right_g_f, right_h_f,
                       left_c_f, right_c_f)
    gain_r = side_gain(left_g_r, left_h_r, right_g_r, right_h_r,
                       left_c_r, right_c_r)

    def common_valid(lc, rc, lh, rh):
        return ((lc >= min_data_in_leaf) & (rc >= min_data_in_leaf) &
                (lh >= min_sum_hessian) & (rh >= min_sum_hessian))

    # forward thresholds: t in [0, num_bin-2], skip t == default_bin (Zero)
    valid_f = (two_scan & in_range & (bins <= nb - 2) &
               ~(is_zero_miss & (bins == dflt)) &
               common_valid(left_c_f, right_c_f, left_h_f, right_h_f) &
               (gain_f > min_gain_shift))
    # reverse thresholds: t in [0, bmax-1], skip t == default_bin-1 (Zero)
    valid_r = (in_range & (bins <= bmax - 1) &
               ~(two_scan & is_zero_miss & (bins == dflt - 1)) &
               common_valid(left_c_r, right_c_r, left_h_r, right_h_r) &
               (gain_r > min_gain_shift))

    numerical = ctx.is_categorical[:, None] == 0
    valid_f &= numerical
    valid_r &= numerical
    if feature_mask is not None:
        valid_f &= feature_mask[:, None]
        valid_r &= feature_mask[:, None]
    if rand_bins is not None:
        # extra_trees: each feature evaluates ONE random threshold
        # (feature_histogram.hpp USE_RAND arms)
        at_rand = bins == rand_bins[:, None]
        valid_f &= at_rand
        valid_r &= at_rand

    neg = jnp.float32(K_MIN_SCORE)
    gain_f = jnp.where(valid_f, gain_f, neg)
    gain_r = jnp.where(valid_r, gain_r, neg)

    # per-feature best, with scan-order tie-breaking
    best_t_f = jnp.argmax(gain_f, axis=1)            # first (smallest t) wins
    best_gain_f = jnp.take_along_axis(gain_f, best_t_f[:, None], axis=1)[:, 0]
    rev_flip = gain_r[:, ::-1]
    best_t_r_flip = jnp.argmax(rev_flip, axis=1)      # largest t wins ties
    best_t_r = BF - 1 - best_t_r_flip
    best_gain_r = jnp.take_along_axis(gain_r, best_t_r[:, None], axis=1)[:, 0]

    use_fwd = best_gain_f > best_gain_r              # strict: reverse wins ties
    feat_gain = jnp.where(use_fwd, best_gain_f, best_gain_r)
    feat_thresh = jnp.where(use_fwd, best_t_f, best_t_r)
    # default_left: reverse scan => True; single-scan NaN feature => False
    single_nan = (~two_scan & is_nan_miss)[:, 0]
    feat_default_left = jnp.where(use_fwd, False, True) & ~single_nan

    # ---- categorical features (exclusive with the numerical scans) ----
    cat_mask = ctx.is_categorical != 0
    if cat_params is not None:
        (gain_c, member_c, lg_c, lh_c, lc_c, l2_eff_c) = \
            find_best_split_categorical(
                feat_hist, ctx, sum_g, sum_h_tot, num_data,
                l1, l2, max_delta_step, min_gain_shift,
                min_data_in_leaf, min_sum_hessian,
                cat_params["max_cat_threshold"], cat_params["cat_l2"],
                cat_params["cat_smooth"], cat_params["max_cat_to_onehot"],
                cat_params["min_data_per_group"],
                cmin=cmin_p if use_mc else None,
                cmax=cmax_p if use_mc else None)
        if feature_mask is not None:
            gain_c = jnp.where(feature_mask, gain_c, neg)
        feat_gain = jnp.where(cat_mask, gain_c, feat_gain)
    else:
        member_c = jnp.zeros((F, BF), jnp.bool_)
        lg_c = jnp.zeros((F,))
        lh_c = jnp.zeros((F,))
        lc_c = jnp.zeros((F,), jnp.int32)
        l2_eff_c = jnp.full((F,), l2)

    if feature_contri is not None:
        # per-feature gain scaling (feature_histogram.hpp:174), applied
        # BEFORE the CEGB delta like the reference (the penalty scales
        # inside FindBestThreshold; CEGB subtracts at
        # serial_tree_learner.cpp:982)
        rel = feat_gain - min_gain_shift
        feat_gain = jnp.where(feat_gain > neg,
                              min_gain_shift + rel * feature_contri, neg)

    if cegb_count_coeff > 0.0 or cegb_feature_delta is not None:
        # CEGB: subtract the split cost from the (relative) gain
        # (reference: CostEfficientGradientBoosting::DeltaGain,
        # cost_effective_gradient_boosting.hpp; applied at
        # serial_tree_learner.cpp:982-986)
        delta = cegb_count_coeff * num_data
        if cegb_feature_delta is not None:
            delta = delta + cegb_feature_delta
        rel = feat_gain - min_gain_shift - delta
        feat_gain = jnp.where(feat_gain > neg, min_gain_shift + rel, neg)

    if use_mc and monotone_penalty > 0:
        # gain *= penalty for splits on monotone features
        # (serial_tree_learner.cpp:987-991; penalty from
        # monotone_constraints.hpp:357 as a function of leaf depth)
        d = depth.astype(jnp.float32)
        pen = jnp.where(
            monotone_penalty >= d + 1.0, K_EPSILON,
            jnp.where(jnp.float32(monotone_penalty) <= 1.0,
                      1.0 - monotone_penalty / jnp.exp2(d) + K_EPSILON,
                      1.0 - jnp.exp2(monotone_penalty - 1.0 - d) + K_EPSILON))
        rel = feat_gain - min_gain_shift
        rel = jnp.where(monotone != 0, rel * pen, rel)
        feat_gain = jnp.where(feat_gain > neg, min_gain_shift + rel, neg)

    best_f = jnp.argmax(feat_gain)                   # smallest feature wins ties
    best_gain = feat_gain[best_f]
    best_t = feat_thresh[best_f]
    fwd_sel = use_fwd[best_f]
    is_cat = cat_mask[best_f]

    lg_n = jnp.where(fwd_sel, left_g_f[best_f, best_t], left_g_r[best_f, best_t])
    lh_n = jnp.where(fwd_sel, left_h_f[best_f, best_t], left_h_r[best_f, best_t])
    lc_n = jnp.where(fwd_sel, left_c_f[best_f, best_t], left_c_r[best_f, best_t])
    lg = jnp.where(is_cat, lg_c[best_f], lg_n)
    lh = jnp.where(is_cat, lh_c[best_f], lh_n)
    lc = jnp.where(is_cat, lc_c[best_f], lc_n)
    l2_out = jnp.where(is_cat, l2_eff_c[best_f], l2)
    rg = sum_g - lg
    rh = sum_h_tot - lh
    rc = num_data.astype(jnp.int32) - lc

    lout_best = leaf_output(lg, lh, l1, l2_out, max_delta_step)
    rout_best = leaf_output(rg, rh, l1, l2_out, max_delta_step)
    if use_smooth:
        fl = lc.astype(jnp.float32) / path_smooth
        fr = rc.astype(jnp.float32) / path_smooth
        lout_best = lout_best * fl / (fl + 1.0) + parent_output / (fl + 1.0)
        rout_best = rout_best * fr / (fr + 1.0) + parent_output / (fr + 1.0)
    if use_mc:
        def _at_best(b, parent):
            # per-threshold (F, BF) bound arrays (advanced mode) index at
            # the chosen split; a categorical winner's best_t is leftover
            # from the masked numerical scan, so categorical splits use
            # the whole-box parent bound instead.  Scalars pass through.
            if getattr(b, "ndim", 0) != 2:
                return b
            return jnp.where(is_cat, parent, b[best_f, best_t])
        lout_best = jnp.clip(lout_best, _at_best(cmin_l, cmin_p),
                             _at_best(cmax_l, cmax_p))
        rout_best = jnp.clip(rout_best, _at_best(cmin_r, cmin_p),
                             _at_best(cmax_r, cmax_p))

    best = BestSplit(
        gain=jnp.where(best_gain > neg, best_gain - min_gain_shift, neg),
        feature=best_f.astype(jnp.int32),
        threshold=jnp.where(is_cat, 0, best_t).astype(jnp.int32),
        default_left=jnp.where(is_cat, False, feat_default_left[best_f]),
        left_sum_g=lg, left_sum_h=lh - K_EPSILON,
        right_sum_g=rg, right_sum_h=rh - K_EPSILON,
        left_count=lc.astype(jnp.int32), right_count=rc.astype(jnp.int32),
        left_output=lout_best,
        right_output=rout_best,
        is_cat=is_cat,
        cat_set=member_c[best_f],
    )
    if with_feature_gains:
        return best, feat_gain
    return best


# ---------------------------------------------------------------------------
# Frontier-batched growth: top-K leaf selection (models/learner.py)
# ---------------------------------------------------------------------------
def oracle_next_pick(gains, oracle_slots, avail):
    """The K=1 oracle's next-leaf election over a frontier of candidate
    items: maximum gain, ties broken by the SMALLEST oracle leaf slot —
    exactly the first-max semantics of ``jnp.argmax`` over the oracle's
    leaf-indexed gain row (the serial learner's selection at
    models/learner.py ``body``).  Vectorized like the (feature, bin)
    gain argmax above: one masked max + one masked min + one argmax.

    Args: gains (I,) f32; oracle_slots (I,) i32 (valid where avail);
    avail (I,) bool.  Returns (item, gain) of the elected candidate
    (item is arbitrary-but-deterministic when nothing is available:
    gains must be -inf there so the caller's gain check gates it).
    """
    masked = jnp.where(avail, gains, K_MIN_SCORE)
    gmax = jnp.max(masked)
    tie = avail & (masked == gmax)
    big = jnp.int32(2 ** 30)
    slot = jnp.min(jnp.where(tie, oracle_slots, big))
    item = jnp.argmax(tie & (oracle_slots == slot)).astype(jnp.int32)
    return item, gmax


def frontier_topk(scores, required, k):
    """Select the step's split batch: the ``required`` item (the oracle's
    guaranteed-next split) plus the top-(k-1) remaining candidates by
    score.  ``scores`` must already be ``-inf`` for non-candidates.
    Returns (items (k,), ok (k,) validity mask); slot 0 is always the
    required item (the caller masks its own validity)."""
    required = jnp.asarray(required, jnp.int32)
    if k == 1:
        return required[None], jnp.ones((1,), jnp.bool_)
    rest = scores.at[required].set(K_MIN_SCORE)
    topv, topi = jax.lax.top_k(rest, k - 1)
    sel = jnp.concatenate([required[None], topi.astype(jnp.int32)])
    ok = jnp.concatenate([jnp.ones((1,), jnp.bool_), jnp.isfinite(topv)])
    return sel, ok
