"""Pallas TPU kernel for the all-numerical best-split search.

One program per split evaluates BOTH children of the freshly split leaf:
the while-body's split search is op-dispatch-bound on this stack
(~80 us/split as ~25 XLA ops, PERF.md), while the actual compute is
trivial — one (12F, BF) prefix-sum matmul on the MXU and a few VPU
passes over (2F, BF) grids.  Collapsing it into a single all-VMEM
pallas_call (no DMAs, no scalar prefetch — the kernel class that
compiles through the remote Mosaic toolchain) removes the dispatch
overhead.

Semantics match ops/split.py:find_best_split_fast (itself equivalent to
the reference FindBestThresholdSequentially dispatch,
feature_histogram.hpp:272-455):
  * forward scan (missing right) and reverse scan (missing left) with
    MissingType::Zero default-bin skipping and the NaN-bin exclusion;
  * the reference's scan-order tie-breaking is encoded as a
    per-candidate PREFERENCE KEY (feature-major; within a feature the
    reverse scan's thresholds descending, then the forward scan's
    ascending): the winner is the minimum key among maximum-gain
    candidates, so no lane reversal is needed in-kernel;
  * counts ride f32 (exact below 2^24 rows);
  * the depth guard (models/learner.py _depth_guard) is folded into the
    candidate validity mask.

The output tile rows are the packed leafmat column segment
[LM_BGAIN..LM_BISCAT] (models/learner.py) for the left (row 0) and
right (row 1) child, with int fields bitcast into the f32 container —
the caller splices them into the leaf matrix with one dynamic update
per child.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import F32_DOT_PRECISION

K_EPSILON = 1e-15

# fmeta columns (per stacked child-feature row)
FM_NUM_BIN = 0
FM_MISSING = 1
FM_DEFAULT = 2

# info columns (per stacked child-feature row)
IN_SUM_G = 0
IN_SUM_H = 1
IN_NUM_DATA = 2
IN_DEPTH = 3
IN_MASK = 4

OUT_FIELDS = 13     # lanes of each output row = LM_BGAIN..LM_BISCAT


def vmem_bytes(num_features: int, num_bins: int) -> int:
    """Scoped VMEM of ``lgbm_split_search``, which holds everything
    whole: the two (2F, BF) histogram planes, the (12F, BF) stack of
    masked planes and its prefix sums, some twenty (2F, BF) grids of
    gains, masks and keys, the (BF, BF) triangle and the two (2F, 8)
    tables (a row of 8 is a row of 128 lanes).  (Held against the v5e's
    compiler, PR 35: at 255 bins 230 and 232 features compile and 234 do
    not, this is over the limit from 231; at 63 bins 464 compile and 480
    do not, this is over from 454.)"""
    BF = -(-num_bins // 128) * 128
    plane = 2 * num_features * BF * 4
    return 34 * plane + BF * BF * 4 + 2 * 2 * num_features * 128 * 4


@functools.partial(jax.jit, static_argnames=(
    "l1", "l2", "max_delta_step", "min_gain_to_split", "min_data_in_leaf",
    "min_sum_hessian", "max_depth", "interpret"))
def best_split_pair_pallas(hist_g, hist_h, fmeta, info,
                           *, l1: float, l2: float, max_delta_step: float,
                           min_gain_to_split: float, min_data_in_leaf: int,
                           min_sum_hessian: float, max_depth: int,
                           interpret: bool = False):
    """Best numerical split for two sibling leaves.

    Args:
      hist_g / hist_h: (2F, BF) f32 — gradient / hessian histograms;
        the left child's F feature rows stacked above the right child's.
      fmeta: (2F, 8) i32 — FM_* columns (static per-feature metadata,
        duplicated per child block).
      info: (2F, 8) f32 — IN_* columns (per-split leaf scalars broadcast
        over each child block; IN_MASK is the per-child feature mask).
    Returns an (8, 128) f32 tile; rows 0/1 hold the children's packed
    leafmat segments (see module docstring).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    F2, BF = hist_g.shape
    F = F2 // 2
    NEG = float("-inf")

    def thr_l1(g):
        # sign(g)*max(0,|g|-l1) without jnp.sign (untested lowering);
        # the where-form is identical (both give 0 at g == 0)
        mag = jnp.maximum(0.0, jnp.abs(g) - l1)
        return jnp.where(g < 0, -mag, mag)

    def leaf_out(g, h):
        ret = -thr_l1(g) / (h + l2)
        if max_delta_step > 0:
            ret = jnp.clip(ret, -max_delta_step, max_delta_step)
        return ret

    def leaf_gain(g, h):
        s = thr_l1(g)
        if max_delta_step > 0:
            out = leaf_out(g, h)
            return -(2.0 * s * out + (h + l2) * out * out)
        return s * s / (h + l2)

    def kernel(hg_ref, hh_ref, fm_ref, li_ref, out):
        hg = hg_ref[:]
        hh = hh_ref[:]
        nb2 = fm_ref[:, FM_NUM_BIN:FM_NUM_BIN + 1]        # (2F, 1)
        mtype2 = fm_ref[:, FM_MISSING:FM_MISSING + 1]
        dflt2 = fm_ref[:, FM_DEFAULT:FM_DEFAULT + 1]
        sum_g = li_ref[:, IN_SUM_G:IN_SUM_G + 1]          # (2F, 1)
        sum_h_tot = li_ref[:, IN_SUM_H:IN_SUM_H + 1] + 2 * K_EPSILON
        num_data = li_ref[:, IN_NUM_DATA:IN_NUM_DATA + 1]
        depth = li_ref[:, IN_DEPTH:IN_DEPTH + 1]
        fmask2 = (li_ref[:, IN_MASK:IN_MASK + 1] > 0).astype(jnp.int32)
        cnt_factor = num_data / sum_h_tot

        bins = jax.lax.broadcasted_iota(jnp.int32, (F2, BF), 1)
        in_range_i = (bins < nb2).astype(jnp.int32)
        zero_i = (mtype2 == 1).astype(jnp.int32)
        nan_i = (mtype2 == 2).astype(jnp.int32)
        two_scan_i = ((nb2 > 2) & (mtype2 != 0)).astype(jnp.int32)
        cnt_bin = jnp.floor(hh * cnt_factor + 0.5) * in_range_i

        at_dflt_i = (bins == dflt2).astype(jnp.int32)
        mf = (in_range_i * (1 - zero_i * at_dflt_i)).astype(jnp.float32)
        bmax = nb2 - 1 - nan_i * two_scan_i
        mr = (in_range_i * (1 - two_scan_i * zero_i * at_dflt_i) *
              (bins <= bmax).astype(jnp.int32)).astype(jnp.float32)

        stacked = jnp.concatenate([
            hg * mf, hh * mf, cnt_bin * mf,
            hg * mr, hh * mr, cnt_bin * mr], axis=0)       # (12F, BF)
        tri = (jax.lax.broadcasted_iota(jnp.int32, (BF, BF), 0) <=
               jax.lax.broadcasted_iota(jnp.int32, (BF, BF), 1)
               ).astype(jnp.float32)
        cs = jax.lax.dot_general(
            stacked, tri, (((1,), (0,)), ((), ())),
            precision=F32_DOT_PRECISION,
            preferred_element_type=jnp.float32)            # (12F, BF)

        lg_f = cs[0:F2]
        lh_f = cs[F2:2 * F2] + K_EPSILON
        lc_f = cs[2 * F2:3 * F2]
        rg_f = sum_g - lg_f
        rh_f = sum_h_tot - lh_f
        rc_f = num_data - lc_f

        cg_r = cs[3 * F2:4 * F2]
        ch_r = cs[4 * F2:5 * F2]
        cc_r = cs[5 * F2:6 * F2]
        # totals from the prefix matmul's LAST column: a separate sum
        # reduce rounds differently and the right-side subtraction
        # amplifies the mismatch vs the XLA fast search
        tot_g = cg_r[:, BF - 1:BF]
        tot_h = ch_r[:, BF - 1:BF]
        tot_c = cc_r[:, BF - 1:BF]
        rg_r = tot_g - cg_r
        rh_r = tot_h - ch_r + K_EPSILON
        rc_r = tot_c - cc_r
        lg_r = sum_g - rg_r
        lh_r = sum_h_tot - rh_r
        lc_r = num_data - rc_r

        gain_f = leaf_gain(lg_f, lh_f) + leaf_gain(rg_f, rh_f)
        gain_r = leaf_gain(lg_r, lh_r) + leaf_gain(rg_r, rh_r)

        gain_shift = leaf_gain(sum_g, sum_h_tot)           # (2F, 1)
        mgs = gain_shift + min_gain_to_split
        mdl = jnp.float32(min_data_in_leaf)

        def cvalid(lc, rc, lh, rh):
            return ((lc >= mdl).astype(jnp.int32) *
                    (rc >= mdl).astype(jnp.int32) *
                    (lh >= min_sum_hessian).astype(jnp.int32) *
                    (rh >= min_sum_hessian).astype(jnp.int32))

        valid_f = (two_scan_i * in_range_i *
                   (bins <= nb2 - 2).astype(jnp.int32) *
                   (1 - zero_i * at_dflt_i) *
                   cvalid(lc_f, rc_f, lh_f, rh_f) *
                   (gain_f > mgs).astype(jnp.int32) * fmask2)
        valid_r = (in_range_i * (bins <= bmax - 1).astype(jnp.int32) *
                   (1 - two_scan_i * zero_i *
                    (bins == dflt2 - 1).astype(jnp.int32)) *
                   cvalid(lc_r, rc_r, lh_r, rh_r) *
                   (gain_r > mgs).astype(jnp.int32) * fmask2)
        if max_depth > 0:
            depth_ok = (depth < max_depth).astype(jnp.int32)
            valid_f = valid_f * depth_ok
            valid_r = valid_r * depth_ok

        gf = jnp.where(valid_f != 0, gain_f, NEG)
        gr = jnp.where(valid_r != 0, gain_r, NEG)

        # preference keys (feature-major; rev desc-t then fwd asc-t)
        feat = jax.lax.broadcasted_iota(jnp.int32, (F2, BF), 0)
        feat = jnp.where(feat >= F, feat - F, feat)
        pref_r = feat * (2 * BF) + (BF - 1 - bins)
        pref_f = feat * (2 * BF) + BF + bins
        # single-scan NaN features flip default_left off for reverse
        # winners (find_best_split_fast dl_r); kept as a (2F, 1) column —
        # materializing it as a broadcast grid crashes Mosaic
        snan_col = ((1 - two_scan_i) * nan_i).astype(jnp.float32)

        acc = jnp.zeros((8, 128), jnp.float32)
        rows8 = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0)
        lanes8 = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)
        for c in range(2):
            s = slice(c * F, (c + 1) * F)
            gmax = jnp.maximum(jnp.max(gf[s]), jnp.max(gr[s]))
            key_r = jnp.where(gr[s] >= gmax, pref_r[s], jnp.int32(1 << 30))
            key_f = jnp.where(gf[s] >= gmax, pref_f[s], jnp.int32(1 << 30))
            win = jnp.minimum(jnp.min(key_r), jnp.min(key_f))
            sel_r = (key_r == win).astype(jnp.float32)
            sel_f = (key_f == win).astype(jnp.float32)

            def pick(a_r, a_f, s=s, sel_r=sel_r, sel_f=sel_f):
                return jnp.sum(a_r[s] * sel_r) + jnp.sum(a_f[s] * sel_f)

            lg = pick(lg_r, lg_f)
            lh = pick(lh_r, lh_f)
            lc = pick(lc_r, lc_f)
            wfeat = win // (2 * BF)
            r = win - wfeat * (2 * BF)
            is_rev_i = (r < BF).astype(jnp.int32)
            thr = jnp.where(is_rev_i != 0, BF - 1 - r, r - BF)
            sel_row = jnp.sum(sel_r, axis=1, keepdims=True)
            snan_pick = jnp.sum(snan_col[s] * sel_row)
            dl = is_rev_i.astype(jnp.float32) * (1.0 - snan_pick)

            sg_c = jnp.max(li_ref[s, IN_SUM_G:IN_SUM_G + 1])
            sh_c = jnp.max(li_ref[s, IN_SUM_H:IN_SUM_H + 1]) \
                + 2 * K_EPSILON
            nd_c = jnp.max(li_ref[s, IN_NUM_DATA:IN_NUM_DATA + 1])
            rg = sg_c - lg
            rh = sh_c - lh
            rc = nd_c - lc
            shift_c = leaf_gain(sg_c, sh_c) + min_gain_to_split
            has_win = (win < (1 << 30)).astype(jnp.float32)
            gain_rel = jnp.where(has_win > 0, gmax - shift_c, NEG)

            def bitf(x):
                # tpu.bitcast needs vector operands; go through (1, 1)
                v = jnp.broadcast_to(x, (1, 1)).astype(jnp.int32)
                return jax.lax.bitcast_convert_type(v, jnp.float32)

            vals = [
                gain_rel,
                bitf(wfeat),
                bitf(thr),
                dl,
                bitf(lc),
                bitf(rc),
                lg, lh - K_EPSILON, rg, rh - K_EPSILON,
                leaf_out(lg, lh), leaf_out(rg, rh),
                jnp.float32(0.0),          # is_cat: numerical only
            ]
            for k, v in enumerate(vals):
                acc = jnp.where((rows8 == c) & (lanes8 == k), v, acc)
        out[:] = acc

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
        interpret=interpret,
        name="lgbm_split_search",
    )(hist_g, hist_h, fmeta, info)
