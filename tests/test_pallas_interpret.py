"""Off-TPU correctness lane for the Pallas kernels via the interpreter
(Pallas correctness must not depend on TPU availability).  Small shapes —
the interpreter is slow.  The real Mosaic lowering of the same kernels is
checked on the chip by `python tpu_selfcheck.py` (kernel-vs-oracle steps
1, 5 and the Pallas-vs-XLA end-to-end parity of step 6), which the
builder runs through the chip tool; no pytest lane can reach a TPU under
this suite's CPU-pinned conftest."""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops.partition_pallas import (partition_leaf_pallas,
                                               make_scalars, sc_rows_for)
from lightgbm_tpu.ops import split as so
from lightgbm_tpu.ops.split_pallas import best_split_pair_pallas
from lightgbm_tpu.ops.split_megakernel_pallas import (
    both_children_hist_xla, split_megakernel_pallas, unpack_hist4)


def _oracle(pb, pg, start, cnt, col, bstart, isb, nb, dbin, mtype, thr, dl):
    pb = pb.copy()
    pg = pg.copy()
    colv = pb[col, start:start + cnt].astype(np.int32)
    fb_raw = colv - bstart
    in_r = (fb_raw >= 1) & (fb_raw <= nb - 1)
    fb = np.where(isb == 1, np.where(in_r, fb_raw, dbin), colv)
    if mtype == 1:
        miss = fb == dbin
    elif mtype == 2:
        miss = fb == nb - 1
    else:
        miss = np.zeros_like(fb, bool)
    gl = np.where(miss, dl != 0, fb <= thr)
    order = np.concatenate([np.where(gl)[0], np.where(~gl)[0]]) + start
    pb[:, start:start + cnt] = pb[:, order]
    pg[:, start:start + cnt] = pg[:, order]
    return pb, pg, int(gl.sum())


@pytest.mark.parametrize("trial", [0, 1, 2])
def test_partition_kernel_interpreted(trial):
    C, G32 = 256, 32
    Np = 8 * C
    rng = np.random.RandomState(trial)
    pb = rng.randint(0, 250, (G32, Np)).astype(np.uint8)
    pg = rng.randn(8, Np).astype(np.float32)
    start = int(rng.randint(C, 4 * C))
    cnt = int(rng.randint(0, 3 * C))
    col = int(rng.randint(0, 28))
    nb = int(rng.randint(10, 250))
    mtype = int(rng.randint(0, 3))
    dbin = int(rng.randint(0, nb))
    thr = int(rng.randint(0, nb))
    dl = int(rng.rand() < 0.5)
    epb, epg, enl = _oracle(pb, pg, start, cnt, col, 0, 0, nb, dbin,
                            mtype, thr, dl)
    sc = make_scalars(start, cnt, col, 0, 0, nb, dbin, mtype, thr, dl)
    rpb, rpg, _, rnl = partition_leaf_pallas(
        jnp.asarray(pb), jnp.asarray(pg),
        jnp.zeros((sc_rows_for(G32), Np), jnp.int32), sc,
        row_chunk=C, interpret=True)
    assert int(np.asarray(rnl)[0, 0]) == enl
    np.testing.assert_array_equal(np.asarray(rpb), epb)
    np.testing.assert_array_equal(
        np.asarray(rpg)[:3].view(np.int32), epg[:3].view(np.int32))


# The kernel's own edges, each against the NumPy stable partition: the
# split column holds the pattern itself (0 goes left, 1 goes right), so a
# case says exactly which lane of which chunk holds what.  (start, cnt,
# pattern over the range); C = 256, the cover starts at start's 128-floor.
_EDGE_C = 256
_EDGE_CASES = {
    "all_left": (_EDGE_C + 37, 2 * _EDGE_C + 11, np.zeros_like),
    "all_right": (_EDGE_C + 37, 2 * _EDGE_C + 11, np.ones_like),
    "alternating": (_EDGE_C + 37, 2 * _EDGE_C + 11, lambda i: i % 2),
    "one_right_at_lane_0": (2 * _EDGE_C, _EDGE_C + 50, lambda i: i == 0),
    "one_right_at_lane_C-1": (2 * _EDGE_C, _EDGE_C + 50,
                              lambda i: i == _EDGE_C - 1),
    "short_unaligned": (_EDGE_C + 77, 100, lambda i: (i * 7 // 3) % 2),
    "empty": (3 * _EDGE_C + 17, 0, lambda i: i),
    "cover_exactly_two_chunks": (_EDGE_C + 40, 2 * _EDGE_C - 40,
                                 lambda i: (i * 5 // 7) % 2),
    "three_chunks_unaligned": (_EDGE_C + 5, 2 * _EDGE_C + 100,
                               lambda i: (i // 3) % 2),
}


@functools.lru_cache(maxsize=None)
def _edge_partition(pack):
    return jax.jit(functools.partial(
        partition_leaf_pallas, row_chunk=_EDGE_C, pack_rowid=pack,
        interpret=True))


@pytest.mark.parametrize("pack", [False, True],
                         ids=["rowid_row", "rowid_packed"])
@pytest.mark.parametrize("case", sorted(_EDGE_CASES))
def test_partition_kernel_edges_interpreted(case, pack):
    C, G32, G, col = _EDGE_C, 32, 28, 5
    Np = 6 * C
    start, cnt, pattern = _EDGE_CASES[case]
    rng = np.random.RandomState(7)
    pb = rng.randint(0, 250, (G32, Np)).astype(np.uint8)
    pb[G:] = 0                     # pad rows zero: the dataset invariant
    pb[col, start:start + cnt] = pattern(np.arange(cnt))
    pg = rng.randn(8, Np).astype(np.float32)
    epb, epg, enl = _oracle(pb, pg, start, cnt, col, 0, 0, 4, 0, 0, 0, 0)
    assert enl == int((pb[col, start:start + cnt] == 0).sum())
    sc = make_scalars(start, cnt, col, 0, 0, 4, 0, 0, 0, 0)
    rpb, rpg, _, rnl = _edge_partition(pack)(
        jnp.asarray(pb), jnp.asarray(pg),
        jnp.zeros((sc_rows_for(G32), Np), jnp.int32), sc)
    assert int(np.asarray(rnl)[0, 0]) == enl
    np.testing.assert_array_equal(np.asarray(rpb), epb)
    np.testing.assert_array_equal(
        np.asarray(rpg)[:3].view(np.int32), epg[:3].view(np.int32))


def _compact_rows(payload, flags, block, lead):
    """``_compact`` (plain values: no kernel around it) on every row of
    ``flags`` against the NumPy stable partition: the flagged lanes in
    order from position ``lead``, the others from position
    ``-lead % block`` and mirrored inside each block (position q at lane
    ``q ^ (block - 1)``).  Only a side's own positions are compared: the
    others hold the other side or stale copies by contract."""
    from lightgbm_tpu.ops import partition_pallas as pp
    n, C = flags.shape
    rights = 1 - flags
    pnr = np.cumsum(rights, axis=1) - rights
    got = jax.jit(jax.vmap(
        lambda f, d: pp._compact(jnp.asarray(payload, jnp.int32), f[None],
                                 d[None], C, block, lead)))(
        jnp.asarray(flags, jnp.int32), jnp.asarray(pnr, jnp.int32))
    pos = np.arange(C + block)
    for out, first, off, at in ((got[0], rights, lead, pos),
                                (got[1], flags, -lead % block,
                                 pos ^ (block - 1))):
        order = np.argsort(first, axis=1, kind="stable")         # lanes
        want = np.zeros((n, payload.shape[0], C + block), np.int32)
        want[:, :, off:off + C] = payload[:, order].transpose(1, 0, 2)
        count = C - first.sum(axis=1)
        kept = ((pos >= off) & (pos < off + count[:, None]))[:, None, :]
        np.testing.assert_array_equal(
            np.where(kept, np.asarray(out)[:, :, at], 0),
            np.where(kept, want, 0))


@pytest.mark.parametrize("P, lead", [(3, 0), (11, 1), (19, 3)],
                         ids=["P3", "P11", "P19"])
def test_compaction_network_every_flag_row_of_16_lanes(P, lead):
    """The two-way compaction alone, outside any kernel, at block width 4
    x 4 blocks on all 2^16 flag rows of a 16-lane chunk: both sides come
    back packed in their order from their first position on, for a
    payload of one, two and three sublane tiles.  The block network never
    clears a lane an element has left and runs each side over the other
    side's elements, so this is the proof by cases that neither a stale
    copy nor the other side reaches a live lane, and that one permutation
    a block puts every element on its final lane."""
    C = 16
    flags = (np.arange(1 << C)[:, None] >> np.arange(C)) & 1     # (2^16, C)
    payload = np.arange(C)[None, :] + 100 * np.arange(P)[:, None]
    _compact_rows(payload, flags, 4, lead)


@pytest.mark.parametrize("P, lead, share", [
    (11, 0, 0.5), (13, 77, 0.05), (13, 127, 0.95), (8, 1, 0.5)],
    ids=["P11_half", "P13_few", "P13_most", "P8_half"])
def test_compaction_blocks_of_128_lanes(P, lead, share):
    """The kernels' block width, 128 lanes x 2 blocks: random flag rows
    at three densities and the edge rows (no flag, every flag, one flag
    in the last lane, one hole in the first), with payload words that use
    all 32 bits (the sign bit, 0xFF bytes, the f32 patterns of negative
    and denormal values): the words move by a lane gather and must come
    back to the bit."""
    C = 256
    rng = np.random.RandomState(P + lead)
    flags = (rng.rand(64, C) < share).astype(np.int64)
    flags[0] = 0
    flags[1] = 1
    flags[2] = np.arange(C) == C - 1
    flags[3] = np.arange(C) != 0
    payload = rng.randint(-2**31, 2**31, (P, C), dtype=np.int64).astype(
        np.int32)
    payload[0, :8] = np.array([-2**31, -1, 0x00FF00FF, 0x7FFFFFFF, 0xFF00,
                               0x00FF0000, 255, 0], np.int64).astype(np.int32)
    payload[1, :4] = np.array([-1.5, -0.0, 1e-42, -3e-39],
                              np.float32).view(np.int32)
    _compact_rows(payload, flags, 128, lead)


def test_split_kernel_interpreted():
    rng = np.random.RandomState(3)
    F, BF = 7, 31
    num_bin = rng.randint(3, BF + 1, size=F).astype(np.int32)
    missing = rng.randint(0, 3, size=F).astype(np.int32)
    dflt = np.where(missing == 1, rng.randint(0, 3, size=F), 0).astype(np.int32)
    ctx = so.SplitContext(jnp.asarray(num_bin), jnp.asarray(missing),
                          jnp.asarray(dflt), jnp.zeros(F, jnp.int32),
                          jnp.arange(F, dtype=jnp.int32))
    half = np.zeros((F, 8), np.int32)
    half[:, 0] = num_bin
    half[:, 1] = missing
    half[:, 2] = dflt
    fmeta = jnp.asarray(np.concatenate([half, half]))
    hists, infos, refs = [], [], []
    for c in range(2):
        hist = np.zeros((F, BF, 2), np.float32)
        for f in range(F):
            hist[f, :num_bin[f], 0] = rng.normal(size=num_bin[f])
            hist[f, :num_bin[f], 1] = rng.uniform(0.01, 2.0,
                                                  size=num_bin[f])
        sum_g = float(hist[0, :, 0].sum())
        sum_h = float(hist[0, :, 1].sum())
        cnt = 1000 + 200 * c
        mask = rng.rand(F) > 0.2
        refs.append(so.find_best_split_fast(
            jnp.asarray(hist), ctx, jnp.float32(sum_g),
            jnp.float32(sum_h), jnp.int32(cnt), 0.0, 1e-3, 0.0, 0.0,
            5, 1e-3, jnp.asarray(mask)))
        hists.append(hist)
        info = np.zeros((F, 8), np.float32)
        info[:, 0] = sum_g
        info[:, 1] = sum_h
        info[:, 2] = cnt
        info[:, 3] = 1.0
        info[:, 4] = mask
        infos.append(info)
    hg = jnp.asarray(np.concatenate([hists[0][..., 0], hists[1][..., 0]]))
    hh = jnp.asarray(np.concatenate([hists[0][..., 1], hists[1][..., 1]]))
    tile = np.asarray(best_split_pair_pallas(
        hg, hh, fmeta, jnp.asarray(np.concatenate(infos)),
        l1=0.0, l2=1e-3, max_delta_step=0.0, min_gain_to_split=0.0,
        min_data_in_leaf=5, min_sum_hessian=1e-3, max_depth=0,
        interpret=True))
    for c, ref in enumerate(refs):
        row = tile[c]
        assert row[1:2].view(np.int32)[0] == int(ref.feature)
        assert row[2:3].view(np.int32)[0] == int(ref.threshold)
        np.testing.assert_allclose(row[0], float(ref.gain),
                                   rtol=2e-4, atol=1e-5)


@pytest.mark.parametrize("trial", [0, 1])
def test_megakernel_interpreted(trial):
    """Mega-kernel: the partition must match the NumPy oracle bit-exact
    AND the both-children histogram accumulator must match the XLA
    oracle (both_children_hist_xla) bit-exact — the same chunk grid and
    accumulation math by construction."""
    C, G32, G, B = 256, 32, 28, 255
    Np = 8 * C
    rng = np.random.RandomState(60 + trial)
    pb = rng.randint(0, 250, (G32, Np)).astype(np.uint8)
    pg = rng.randn(8, Np).astype(np.float32)
    start = int(rng.randint(C, 4 * C))
    cnt = int(rng.randint(1, 3 * C))
    col = int(rng.randint(0, G))
    nb = int(rng.randint(10, 250))
    mtype = int(rng.randint(0, 3))
    dbin = int(rng.randint(0, nb))
    thr = int(rng.randint(0, nb))
    dl = int(rng.rand() < 0.5)
    epb, epg, enl = _oracle(pb, pg, start, cnt, col, 0, 0, nb, dbin,
                            mtype, thr, dl)
    sc = make_scalars(start, cnt, col, 0, 0, nb, dbin, mtype, thr, dl)
    rpb, rpg, _, rnl, acc = split_megakernel_pallas(
        jnp.asarray(pb), jnp.asarray(pg),
        jnp.zeros((sc_rows_for(G32), Np), jnp.int32), sc,
        row_chunk=C, num_bins=B, num_groups=G, interpret=True)
    assert int(np.asarray(rnl)[0, 0]) == enl
    np.testing.assert_array_equal(np.asarray(rpb), epb)
    np.testing.assert_array_equal(
        np.asarray(rpg)[:3].view(np.int32), epg[:3].view(np.int32))
    acc_oracle = both_children_hist_xla(
        jnp.asarray(pb), jnp.asarray(pg), jnp.int32(start), jnp.int32(cnt),
        jnp.int32(col),
        tuple(jnp.int32(v) for v in (0, 0, nb, dbin, mtype, thr, dl)),
        row_chunk=C, num_bins=B, num_groups=G)
    np.testing.assert_array_equal(np.asarray(acc), np.asarray(acc_oracle))
    # independent NumPy reference for the histogram VALUES (allclose:
    # different summation order than the f32 matmul accumulation)
    colv = pb[col, start:start + cnt].astype(np.int32)
    if mtype == 1:
        miss = colv == dbin
    elif mtype == 2:
        miss = colv == nb - 1
    else:
        miss = np.zeros_like(colv, bool)
    gl = np.where(miss, dl != 0, colv <= thr)
    hl_g, hl_h, hr_g, hr_h = [np.asarray(x) for x in unpack_hist4(acc, B)]
    gseg = pg[0, start:start + cnt].astype(np.float64)
    hseg = pg[1, start:start + cnt].astype(np.float64)
    for gi in (0, col, G - 1):
        binseg = pb[gi, start:start + cnt]
        for side, (eg, eh) in ((gl, (hl_g, hl_h)), (~gl, (hr_g, hr_h))):
            refg = np.zeros(256)
            refh = np.zeros(256)
            np.add.at(refg, binseg[side], gseg[side])
            np.add.at(refh, binseg[side], hseg[side])
            np.testing.assert_allclose(eg[gi], refg, rtol=1e-4, atol=1e-4)
            np.testing.assert_allclose(eh[gi], refh, rtol=1e-4, atol=1e-4)


def test_megakernel_zero_count_interpreted():
    """cnt == 0 (the trash-slot iteration): no rows move, the left count
    clamps to 0 and the histogram accumulator is all-zero."""
    C, G32, G, B = 256, 32, 28, 255
    Np = 8 * C
    rng = np.random.RandomState(99)
    pb = rng.randint(0, 250, (G32, Np)).astype(np.uint8)
    pg = rng.randn(8, Np).astype(np.float32)
    sc = make_scalars(3 * C + 17, 0, 5, 0, 0, 200, 0, 0, 100, 0)
    rpb, rpg, _, rnl, acc = split_megakernel_pallas(
        jnp.asarray(pb), jnp.asarray(pg),
        jnp.zeros((sc_rows_for(G32), Np), jnp.int32), sc,
        row_chunk=C, num_bins=B, num_groups=G, interpret=True)
    assert int(np.asarray(rnl)[0, 0]) == 0
    np.testing.assert_array_equal(np.asarray(rpb), pb)
    np.testing.assert_array_equal(np.asarray(rpg)[:3], pg[:3])
    assert not np.asarray(acc).any()


@pytest.mark.parametrize("trial", [0, 1])
def test_partition_kernel_pack_rowid_interpreted(trial):
    """pack_rowid rides ghi row 2 inside the spare packed-bin bytes;
    HBM layout must be unchanged (pad bin rows zero, rowid row exact)."""
    C, G32, G = 256, 32, 28
    Np = 8 * C
    rng = np.random.RandomState(100 + trial)
    pb = rng.randint(0, 250, (G32, Np)).astype(np.uint8)
    pb[G:] = 0                     # pad rows zero: the dataset invariant
    pg = rng.randn(8, Np).astype(np.float32)
    start = int(rng.randint(C, 4 * C))
    cnt = int(rng.randint(1, 3 * C))
    col = int(rng.randint(0, G))
    nb = int(rng.randint(10, 250))
    thr = int(rng.randint(0, nb))
    epb, epg, enl = _oracle(pb, pg, start, cnt, col, 0, 0, nb, 0, 0, thr, 0)
    sc = make_scalars(start, cnt, col, 0, 0, nb, 0, 0, thr, 0)
    for ghi_live in (3, 5):
        rpb, rpg, _, rnl = partition_leaf_pallas(
            jnp.asarray(pb), jnp.asarray(pg),
            jnp.zeros((sc_rows_for(G32), Np), jnp.int32), sc,
            row_chunk=C, ghi_live=ghi_live, pack_rowid=True,
            interpret=True)
        assert int(np.asarray(rnl)[0, 0]) == enl
        np.testing.assert_array_equal(np.asarray(rpb), epb)
        np.testing.assert_array_equal(
            np.asarray(rpg)[:ghi_live].view(np.int32),
            epg[:ghi_live].view(np.int32))


# ---------------------------------------------------------------------------
# lgbm_histogram (ops/histogram_pallas.py) against the XLA chunk loop
# ---------------------------------------------------------------------------
_HC = 256                              # row_chunk of these cases
_HIST_CNT = {"zero": 0, "one": 1, "chunk_less_1": _HC - 1, "chunk": _HC,
             "three_chunks_17": 3 * _HC + 17}


@functools.lru_cache(maxsize=None)
def _hist_pair(B, G, flat):
    """(kernel, XLA loop) jitted once per shape: start and cnt are traced,
    as in the tree loop."""
    from lightgbm_tpu.ops.hist_state_pallas import flat_geometry
    from lightgbm_tpu.ops.histogram import leaf_hist_slice
    from lightgbm_tpu.ops.histogram_pallas import leaf_hist_pallas
    kw = dict(num_bins=B, row_chunk=_HC, num_groups=G,
              flat_geom=flat_geometry(G, B) if flat else None)
    return (jax.jit(functools.partial(leaf_hist_pallas, interpret=True,
                                      **kw)),
            jax.jit(functools.partial(leaf_hist_slice, **kw)))


def _hist_case(B, G, cnt, flat=False):
    """Rows outside [start, start + cnt) hold gradients 1e30 times the
    leaf's: a single one that leaked would swamp every bin.  ``start`` is
    unaligned to 128, so the kernel's cover begins before the leaf.

    Not bit-equal by construction: the kernel sums the three bf16 limbs of
    a weight separately, over the chunks of the 128-aligned cover, and adds
    the limb sums last; the XLA loop sums f32 products over chunks that
    begin at ``start``.  Every product is exact in both, so the two differ
    by f32 reassociation only: 1e-6 of the plane's largest bin (measured
    1.7e-7 at most)."""
    start = _HC + 37
    Np = 8 * _HC
    rng = np.random.RandomState(7 * B + G)
    pb = np.zeros((32, Np), np.uint8)
    pb[:G] = rng.randint(0, B, (G, Np))
    pg = (rng.randn(8, Np) * 1e30).astype(np.float32)
    pg[:2, start:start + cnt] = rng.randn(2, cnt)
    kernel, loop = _hist_pair(B, G, flat)
    args = (jnp.asarray(pb), jnp.asarray(pg), jnp.int32(start),
            jnp.int32(cnt))
    got, ref = np.asarray(kernel(*args)), np.asarray(loop(*args))
    assert got.shape == ref.shape and np.isfinite(got).all()
    if cnt == 0:
        assert not got.any()
    np.testing.assert_allclose(got, ref, rtol=1e-6,
                               atol=1e-6 * max(np.abs(ref).max(), 1e-30))


@pytest.mark.parametrize("cnt", sorted(_HIST_CNT))
@pytest.mark.parametrize("G", [28, 5])
@pytest.mark.parametrize("B", [255, 63, 16])
def test_histogram_kernel_interpreted(B, G, cnt):
    _hist_case(B, G, _HIST_CNT[cnt])


@pytest.mark.parametrize("B", [255, 63])
def test_histogram_kernel_flat_slot_interpreted(B):
    """The (8, WL) slot of the hist-state RMW kernel: the same tail as the
    XLA loop's (ops/histogram.py:hist_tail)."""
    _hist_case(B, 28, 3 * _HC + 17, flat=True)


def test_histogram_kernel_follows_the_dot_precision(monkeypatch):
    """benchmark/control.py plants its fault by lowering F32_DOT_PRECISION
    in every module that has it: the kernel then runs ONE bf16 limb, and
    the histogram is off by bf16's rounding, 2^-9 of a weight, not f32's."""
    from lightgbm_tpu.ops import histogram_pallas as hp
    from lightgbm_tpu.ops.histogram import leaf_hist_slice
    rng = np.random.RandomState(3)
    Np, G, B = 8 * _HC, 28, 255
    pb = np.zeros((32, Np), np.uint8)
    pb[:G] = rng.randint(0, B, (G, Np))
    pg = rng.randn(8, Np).astype(np.float32)
    args = (jnp.asarray(pb), jnp.asarray(pg), _HC + 37, 3 * _HC)
    kw = dict(num_bins=B, row_chunk=_HC, num_groups=G)
    ref = np.asarray(leaf_hist_slice(*args, **kw))
    sound = np.asarray(hp.leaf_hist_pallas(*args, interpret=True, **kw))
    monkeypatch.setattr(hp, "F32_DOT_PRECISION", jax.lax.Precision.DEFAULT)
    low = np.asarray(hp.leaf_hist_pallas(*args, interpret=True, **kw))
    scale = np.abs(ref).max()
    assert np.abs(sound - ref).max() < 1e-6 * scale
    assert 1e-4 * scale < np.abs(low - ref).max() < 1e-2 * scale
