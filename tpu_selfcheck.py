"""One-command TPU verification of everything the CPU suite cannot reach
(`python tpu_selfcheck.py`, run on the chip through the chip tool; exits
non-zero when the backend is not a TPU).

Covers, in order:
  1. partition kernel vs the NumPy oracle (bit-exact, incl. rowid rows);
  2. split-search kernel vs the XLA fast search;
  3. rowid-row integrity through a full build_tree (the bitcast rowid row
     must come back a permutation of the row ids);
  4. hist-state RMW kernel vs numpy;
  5. split mega-kernel vs the NumPy partition oracle (bit-exact) + the
     XLA both-children histogram oracle (f32 rounding, incl. the
     zero-count trash-slot call);
  6. leaf-histogram kernel vs a float64 NumPy oracle and vs the XLA chunk
     loop, at 255 and 63 bins (unaligned starts, zero and one row, foreign
     rows holding huge gradients), and one precision step down: with
     F32_DOT_PRECISION lowered it must be off by bf16's rounding;
  7. end-to-end train parity: Pallas kernels vs the XLA path
     (tpu_megakernel=off), then mega-pallas vs mega-xla.  Every arm
     asserts the kernel plan it asked for is the one that engaged.

Row moves, counts and layouts are compared bit-exactly.  f32 matmul
results are compared across compilers (Mosaic vs XLA) to f32 rounding:
both run the dots at precision=HIGHEST, but Mosaic's fp32 contraction
and XLA's multi-pass bf16 emulation differ in the last ulp on the chip
(measured 9.5e-7 on O(1) histogram sums, PR 21).  Arms that share a
compiler and differ only in layout stay bit-identical.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

C, G32 = 1024, 32
NP = 10 * C


def _oracle(pb, pg, start, cnt, col, bstart, isb, nb, dbin, mtype, thr, dl):
    pb = pb.copy(); pg = pg.copy()
    colv = pb[col, start:start+cnt].astype(np.int32)
    fb_raw = colv - bstart
    in_r = (fb_raw >= 1) & (fb_raw <= nb - 1)
    fb = np.where(isb == 1, np.where(in_r, fb_raw, dbin), colv)
    miss = (fb == dbin) if mtype == 1 else ((fb == nb-1) if mtype == 2
                                            else np.zeros_like(fb, bool))
    gl = np.where(miss, dl != 0, fb <= thr)
    order = np.concatenate([np.where(gl)[0], np.where(~gl)[0]]) + start
    pb[:, start:start+cnt] = pb[:, order]
    pg[:, start:start+cnt] = pg[:, order]
    return pb, pg, int(gl.sum())


def check_partition(rng):
    import jax.numpy as jnp
    from lightgbm_tpu.ops.partition_pallas import (
        make_scalars, partition_leaf_pallas, sc_rows_for)
    for trial in range(6):
        pack = trial >= 3          # trials 3-5 exercise pack_rowid
        pb = rng.randint(0, 250, (G32, NP)).astype(np.uint8)
        if pack:
            pb[28:] = 0            # pad-row invariant pack_rowid relies on
        pg = rng.randn(8, NP).astype(np.float32)
        start = int(rng.randint(C, 5*C)); cnt = int(rng.randint(0, 4*C))
        col = int(rng.randint(0, 28)); isb = int(rng.rand() < 0.3)
        nb = int(rng.randint(10, 250))
        bstart = int(rng.randint(0, 5)) if isb else 0
        dbin = int(rng.randint(0, nb)); mtype = int(rng.randint(0, 3))
        thr = int(rng.randint(0, nb)); dl = int(rng.rand() < 0.5)
        epb, epg, enl = _oracle(pb, pg, start, cnt, col, bstart, isb, nb,
                                dbin, mtype, thr, dl)
        sc = make_scalars(start, cnt, col, bstart, isb, nb, dbin, mtype,
                          thr, dl)
        rpb, rpg, _, rnl = partition_leaf_pallas(
            jnp.asarray(pb), jnp.asarray(pg),
            jnp.zeros((sc_rows_for(G32), NP), jnp.int32), sc, row_chunk=C,
            ghi_live=5 if pack else 3, pack_rowid=pack)
        assert int(np.asarray(rnl)[0, 0]) == enl, trial
        np.testing.assert_array_equal(np.asarray(rpb), epb)
        nliv = 5 if pack else 3
        np.testing.assert_array_equal(np.asarray(rpg)[:nliv].view(np.int32),
                                      epg[:nliv].view(np.int32))
    return "partition kernel vs oracle (incl pack_rowid)"


def check_search(rng):
    import jax.numpy as jnp
    from lightgbm_tpu.ops import split as so
    from lightgbm_tpu.ops.split_pallas import best_split_pair_pallas
    F, BF = 28, 255
    num_bin = rng.randint(3, BF + 1, size=F).astype(np.int32)
    missing = rng.randint(0, 3, size=F).astype(np.int32)
    dflt = np.where(missing == 1, rng.randint(0, 3, size=F),
                    0).astype(np.int32)
    ctx = so.SplitContext(jnp.asarray(num_bin), jnp.asarray(missing),
                          jnp.asarray(dflt), jnp.zeros(F, jnp.int32),
                          jnp.arange(F, dtype=jnp.int32))
    half = np.zeros((F, 8), np.int32)
    half[:, 0] = num_bin; half[:, 1] = missing; half[:, 2] = dflt
    fmeta = jnp.asarray(np.concatenate([half, half]))
    hists, infos, refs = [], [], []
    for c in range(2):
        hist = np.zeros((F, BF, 2), np.float32)
        for f in range(F):
            hist[f, :num_bin[f], 0] = rng.normal(size=num_bin[f])
            hist[f, :num_bin[f], 1] = rng.uniform(0.01, 2.0, size=num_bin[f])
        sum_g = float(hist[0, :, 0].sum()); sum_h = float(hist[0, :, 1].sum())
        mask = rng.rand(F) > 0.2
        refs.append(so.find_best_split_fast(
            jnp.asarray(hist), ctx, jnp.float32(sum_g), jnp.float32(sum_h),
            jnp.int32(2000), 0.0, 1e-3, 0.0, 0.0, 5, 1e-3,
            jnp.asarray(mask)))
        hists.append(hist)
        info = np.zeros((F, 8), np.float32)
        info[:, 0] = sum_g; info[:, 1] = sum_h; info[:, 2] = 2000
        info[:, 3] = 1.0; info[:, 4] = mask
        infos.append(info)
    tile = np.asarray(best_split_pair_pallas(
        jnp.asarray(np.concatenate([hists[0][..., 0], hists[1][..., 0]])),
        jnp.asarray(np.concatenate([hists[0][..., 1], hists[1][..., 1]])),
        fmeta, jnp.asarray(np.concatenate(infos)),
        l1=0.0, l2=1e-3, max_delta_step=0.0, min_gain_to_split=0.0,
        min_data_in_leaf=5, min_sum_hessian=1e-3, max_depth=0))
    for c, ref in enumerate(refs):
        assert tile[c, 1:2].view(np.int32)[0] == int(ref.feature)
        assert tile[c, 2:3].view(np.int32)[0] == int(ref.threshold)
    return "search kernel vs XLA fast search"


def _e2e_data(rng):
    X = rng.normal(size=(40000, 8)).astype(np.float32)
    return X, (X[:, 0] > 0).astype(np.float32)


def check_rowid(rng):
    import lightgbm_tpu as lgb
    X, y = _e2e_data(rng)
    N = len(y)
    bst = lgb.Booster(params={"objective": "binary", "num_leaves": 31,
                              "verbosity": -1, "metric": ""},
                      train_set=lgb.Dataset(X, label=y))
    g = bst._gbdt
    grad, hess = g._compute_gradients()
    rec = g.learner.build_tree(grad, hess, N, g._feature_mask(0), seed=1)
    idx = np.asarray(rec["indices"])
    r0 = g.learner.row0
    assert np.array_equal(np.sort(idx[r0:r0+N]), np.arange(N)), \
        "rowid row corrupted"
    return "rowid integrity"


def check_hist_rmw(rng):
    import jax.numpy as jnp
    from lightgbm_tpu.ops.hist_state_pallas import (flat_geometry,
                                                    hist_rmw_pallas)
    WL = flat_geometry(28, 255)[2]
    st_h = rng.randn(34, 8, WL).astype(np.float32)
    small = rng.randn(8, WL).astype(np.float32)
    for (bl, wa, wb, sil) in [(3, 3, 7, 1), (5, 5, 9, 0), (2, 33, 33, 1)]:
        out, lft, rgt = hist_rmw_pallas(
            jnp.asarray(st_h), jnp.asarray(small),
            jnp.asarray([bl, wa, wb, sil], jnp.int32))
        large = st_h[bl] - small
        el = small if sil else large
        er = large if sil else small
        np.testing.assert_array_equal(np.asarray(lft), el)
        np.testing.assert_array_equal(np.asarray(rgt), er)
        exp = st_h.copy(); exp[wa] = el; exp[wb] = er
        np.testing.assert_array_equal(np.asarray(out), exp)
    return "hist-state RMW kernel"


def check_megakernel(rng):
    import jax.numpy as jnp
    from lightgbm_tpu.ops.partition_pallas import make_scalars, sc_rows_for
    from lightgbm_tpu.ops.split_megakernel_pallas import (
        both_children_hist_xla, split_megakernel_pallas)
    G, B = 28, 255
    for trial in range(4):
        pb = rng.randint(0, 250, (G32, NP)).astype(np.uint8)
        pg = rng.randn(8, NP).astype(np.float32)
        start = int(rng.randint(C, 5*C))
        cnt = 0 if trial == 3 else int(rng.randint(1, 4*C))   # 3: trash slot
        col = int(rng.randint(0, G)); nb = int(rng.randint(10, 250))
        mtype = int(rng.randint(0, 3)); dbin = int(rng.randint(0, nb))
        thr = int(rng.randint(0, nb)); dl = int(rng.rand() < 0.5)
        epb, epg, enl = _oracle(pb, pg, start, cnt, col, 0, 0, nb, dbin,
                                mtype, thr, dl)
        sc = make_scalars(start, cnt, col, 0, 0, nb, dbin, mtype, thr, dl)
        rpb, rpg, _, rnl, acc = split_megakernel_pallas(
            jnp.asarray(pb), jnp.asarray(pg),
            jnp.zeros((sc_rows_for(G32), NP), jnp.int32), sc, row_chunk=C,
            num_bins=B, num_groups=G)
        assert int(np.asarray(rnl)[0, 0]) == enl, trial
        np.testing.assert_array_equal(np.asarray(rpb), epb)
        np.testing.assert_array_equal(np.asarray(rpg)[:3].view(np.int32),
                                      epg[:3].view(np.int32))
        acc_o = both_children_hist_xla(
            jnp.asarray(pb), jnp.asarray(pg), jnp.int32(start),
            jnp.int32(cnt), jnp.int32(col),
            tuple(jnp.int32(v) for v in (0, 0, nb, dbin, mtype, thr, dl)),
            row_chunk=C, num_bins=B, num_groups=G)
        np.testing.assert_allclose(np.asarray(acc), np.asarray(acc_o),
                                   rtol=1e-6, atol=1e-5)
        if cnt == 0:
            assert not np.asarray(acc).any()
    return "mega-kernel vs partition+hist oracles"


def check_histogram(rng):
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops import histogram_pallas as hp
    from lightgbm_tpu.ops.histogram import leaf_hist_slice
    G = 28
    worst = 0.0
    for B in (255, 63):
        pb = np.zeros((G32, NP), np.uint8)
        pb[:G] = rng.randint(0, B, (G, NP))
        kw = dict(num_bins=B, row_chunk=C, num_groups=G)
        for start, cnt in ((C + 37, 0), (C + 37, 1), (2 * C - 1, C),
                           (C + 300, 5 * C + 17), (3 * C, 2 * C)):
            pg = (rng.randn(8, NP) * 1e30).astype(np.float32)
            pg[:2, start:start + cnt] = rng.randn(2, cnt)
            ref = np.zeros((G, B, 2))
            rows = slice(start, start + cnt)
            for g in range(G):
                for j in range(2):
                    np.add.at(ref[g, :, j], pb[g, rows],
                              pg[j, rows].astype(np.float64))
            args = (jnp.asarray(pb), jnp.asarray(pg), jnp.int32(start),
                    jnp.int32(cnt))
            got = np.asarray(hp.leaf_hist_pallas(*args, **kw))
            loop = np.asarray(leaf_hist_slice(*args, **kw))
            scale = max(np.abs(ref).max(), 1e-30)
            assert np.isfinite(got).all()
            if cnt == 0:
                assert not got.any()
            for other in (ref, loop):
                gap = float(np.abs(got - other).max() / scale)
                worst = max(worst, gap)
                assert gap < 5e-6, (B, start, cnt, gap)
        # one precision step down (benchmark/control.py's fault), on the
        # last range above
        high = hp.F32_DOT_PRECISION
        hp.F32_DOT_PRECISION = jax.lax.Precision.DEFAULT
        try:
            low = np.asarray(hp.leaf_hist_pallas(*args, **kw))
        finally:
            hp.F32_DOT_PRECISION = high
        gap = float(np.abs(low - ref).max() / scale)
        assert 1e-4 < gap < 1e-2, (B, gap)
    return f"histogram kernel vs f64 oracle and XLA loop ({worst:.2e})"


def check_e2e(rng):
    import lightgbm_tpu as lgb
    X, y = _e2e_data(rng)

    def train(expect, **tpu):
        b = lgb.train({"objective": "binary", "num_leaves": 63,
                       "verbosity": -1, "min_data_in_leaf": 20, **tpu},
                      lgb.Dataset(X, label=y), num_boost_round=8)
        plan = b._gbdt.kernel_plan()
        assert {k: plan[k] for k in expect} == expect, \
            f"asked for {expect}, resolved {plan}"
        return b.predict(X[:3000], raw_score=True)

    ref = train({"partition": "xla", "hist": "xla", "search": "xla",
                 "mega": "off"},
                tpu_partition_kernel="xla", tpu_megakernel="off")
    pallas = {"partition": "pallas", "hist": "pallas", "search": "pallas",
              "mega": "off"}
    flat = train({**pallas, "hist_state": "flat"}, tpu_megakernel="off")
    xstate = train({**pallas, "hist_state": "xla"}, tpu_megakernel="off",
                   tpu_hist_state="xla")
    # same kernels, different histogram-state layout: bit-identical
    assert np.array_equal(flat, xstate)
    d1 = float(np.abs(flat - ref).max())
    mega_ref = train({"mega": "xla"}, tpu_megakernel="xla")
    mega = train({"partition": "pallas", "mega": "pallas"},
                 tpu_megakernel="pallas")
    d2 = float(np.abs(mega - mega_ref).max())
    d3 = float(np.abs(mega_ref - ref).max())
    assert max(d1, d2, d3) < 1e-4, (d1, d2, d3)
    return (f"e2e pallas vs xla ({d1:.2e}), mega vs mega-oracle ({d2:.2e}), "
            f"mega vs subtraction path ({d3:.2e}); histogram-state "
            "layouts bit-identical, plans asserted")


STEPS = (check_partition, check_search, check_rowid, check_hist_rmw,
         check_megakernel, check_histogram, check_e2e)


def main() -> int:
    import jax
    if not __debug__:
        sys.exit("tpu_selfcheck: the checks are asserts; run without -O")
    if jax.default_backend() != "tpu":
        print(f"TPU SELF-CHECK: FAILED — backend is {jax.default_backend()}, "
              "not tpu", file=sys.stderr)
        return 1
    rng = np.random.RandomState(7)
    for i, step in enumerate(STEPS, 1):
        print(f"[{i}/{len(STEPS)}] {step(rng)}: OK", flush=True)
    print(f"TPU SELF-CHECK: ALL OK ({len(STEPS)}/{len(STEPS)})", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
