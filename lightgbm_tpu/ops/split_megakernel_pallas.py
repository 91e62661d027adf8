"""Split mega-kernel: partition + BOTH children's histograms in one
Pallas program per split.

The round-5 cost model (PERF.md) pinned the remaining e2e slope on
per-row INSTRUCTION count: the partition kernel's compaction
is VPU-issue-bound, the smaller-child histogram hides behind them, and
the per-split fixed work (histogram dispatch, smaller/larger selection,
parent-histogram subtraction, the flat hist-state RMW pass, and the two
contextual f32[L+1, G, B, 2] state copies XLA materializes around the
parent-slot dynamic slice) is what the CUDA-band target still pays.
The GPU GBDT literature (Mitchell & Frank arXiv:1806.11248, Wen et al.
arXiv:1706.08359) lands on the same design point: fuse partition and
histogram construction into one pass over the rows while they are
resident in fast memory.

This kernel extends the proven partition program
(ops/partition_pallas.py — identical pass-1/pass-2 structure, DMA
discipline and compaction: its ``_compact``, ``_stage`` and ``_slide``,
built strictly from the probe-proven Mosaic subset) with an in-VMEM
accumulation of BOTH
children's histograms while each chunk's rows are already loaded for
the compaction:

  * per chunk, after the split decision, the (G, C) bin rows and the
    (1, C) grad/hess rows are reduced into a (G, 4*BH, 16) accumulator
    with the digit-decomposed one-hot matmul of ops/histogram.py
    (hi = bin >> 4 weighted masks x lo = bin & 15 one-hot, MXU f32);
  * the 4*BH weighted sublanes are (left-grad, left-hess, right-grad,
    right-hess) — both children in one matmul per group;
  * rows outside the leaf range (the 128-aligned cover's foreign edges)
    carry zero weight, so bagging/GOSS masks (zeroed gradients) and the
    quantized integer carriers flow through unchanged.

Downstream, the tree loop consumes the two children histograms
IN-REGISTER for the split search: no parent histogram read, no
subtraction trick, no (L+1)-slot histogram state in the while-loop
carry at all — the two per-split parent-hist copies are structurally
gone, not just cheaper.

Bit-exactness contract: ``both_children_hist_xla`` below is the XLA
oracle — the same chunk grid (the parent cover's aligned chunks, NOT
the children's own ranges), the same decision arithmetic and the same
``_chunk_hist_group`` math, so kernel and oracle accumulate
bit-identically.  NOTE this grid differs from the subtraction path's
(child-range chunks + parent-minus-small), so mega-mode trees are
bit-identical to the mega XLA oracle but only numerically equivalent
(different f32 summation grouping) to the subtraction-path trees.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .partition_pallas import (S_A0B, S_REM, S_CNT, S_COL, S_BSTART, S_ISB,
                               S_NB, S_DBIN, S_MTYPE, S_THR, S_DL,
                               _decide_left, _excl_prefix_rights, _cdiv,
                               payload_codecs)
from . import F32_DOT_PRECISION
from . import partition_pallas as _pp


def hist_geometry(num_bins: int):
    """(BH, Bp): high-digit cardinality and the padded bin axis of the
    digit-decomposed accumulator (bin b lives at [hi=b>>4, lo=b&15])."""
    BH = (num_bins + 15) // 16
    return BH, BH * 16


def _chunk_hist_group(bins_row, wl_g, wl_h, wr_g, wr_h, BH, iota_hi,
                      iota_lo):
    """One group's both-children histogram partial for one chunk.

    Args:
      bins_row: (1, C) i32 bin values of this group.
      wl_g/wl_h/wr_g/wr_h: (1, C) f32 child-masked grad/hess rows
        (out-of-range and out-of-bag rows already zero).
      iota_hi/iota_lo: (BH, C) / (16, C) i32 row iotas.
    Returns the (4*BH, 16) f32 partial: element [j*BH + hi, lo] is the
    sum of weight row j over rows with bin == hi*16 + lo.

    Shared verbatim by the Pallas kernel and the XLA oracle so both
    accumulate bit-identically (same shapes, same dot, same order).
    """
    hi = jax.lax.shift_right_logical(
        bins_row, jnp.broadcast_to(4, bins_row.shape))
    lo = bins_row & 15
    m_hi = hi == iota_hi                                   # (BH, C)
    oh_lo = (lo == iota_lo).astype(jnp.float32)            # (16, C)
    zero = jnp.float32(0.0)
    w4 = jnp.concatenate(
        [jnp.where(m_hi, wl_g, zero), jnp.where(m_hi, wl_h, zero),
         jnp.where(m_hi, wr_g, zero), jnp.where(m_hi, wr_h, zero)],
        axis=0)                                            # (4BH, C)
    return jax.lax.dot_general(
        w4, oh_lo, (((1,), (1,)), ((), ())),
        precision=F32_DOT_PRECISION,
        preferred_element_type=jnp.float32)                # (4BH, 16)


def unpack_hist4(acc, num_bins: int):
    """(G, 4*BH, 16) accumulator -> four (G, Bp) planes
    (left-grad, left-hess, right-grad, right-hess), bins flattened
    row-major (b = hi*16 + lo)."""
    G = acc.shape[0]
    BH, Bp = hist_geometry(num_bins)
    h4 = acc.reshape(G, 4, Bp)
    return h4[:, 0], h4[:, 1], h4[:, 2], h4[:, 3]


def both_children_hist_xla(part_bins, part_ghi, start, cnt, col,
                           dec_scalars, *, row_chunk: int, num_bins: int,
                           num_groups: int, vary=lambda x: x, cover=None):
    """XLA oracle for the mega-kernel's histogram half: BOTH children's
    histograms of the leaf range [start, start+cnt) accumulated over the
    PARENT cover's chunk grid from the PRE-partition rows.

    Must be called before the partition moves the rows.  Returns the
    (G, 4*BH, 16) accumulator (see ``unpack_hist4``); bit-identical to
    the Pallas kernel's histogram output by construction.

    ``cover`` overrides the chunk trip count (the leaf-size-adaptive
    policy passes the cover length; 0 skips the pass at runtime).
    """
    bstart, isb, nb, dbin, mtype, thr, dl = dec_scalars
    G = num_groups
    C = row_chunk
    BH, _ = hist_geometry(num_bins)
    start = jnp.asarray(start, jnp.int32)
    a0b = jax.lax.shift_right_logical(start, 7)
    rem = start - a0b * 128
    total = rem + cnt
    n_chunks = (jnp.where(cnt > 0, _cdiv(total, C), 0) if cover is None
                else cover)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
    iota_hi = jax.lax.broadcasted_iota(jnp.int32, (BH, C), 0)
    iota_lo = jax.lax.broadcasted_iota(jnp.int32, (16, C), 0)
    col_onehot = (jax.lax.iota(jnp.int32, G) == col)[:, None]

    def body(ci, acc):
        base = a0b * 128 + ci * C
        bch = jax.lax.dynamic_slice(
            part_bins, (0, base), (part_bins.shape[0], C))[:G].astype(
                jnp.int32)
        gh = jax.lax.dynamic_slice(part_ghi, (0, base), (2, C))
        g_row = gh[0:1]
        h_row = gh[1:2]
        # split-column extraction via masked reduction (sublane-dynamic
        # slices are the slow path — PERF.md round 2)
        colv = jnp.sum(bch * col_onehot, axis=0, keepdims=True)   # (1, C)
        gl_i = _decide_left(colv, bstart, isb, nb, dbin, mtype, thr, dl)
        pos = ci * C + lane
        inside_i = ((pos >= rem) & (pos < total)).astype(jnp.int32)
        in_l = (inside_i * gl_i) != 0
        in_r = (inside_i * (1 - gl_i)) != 0
        zero = jnp.float32(0.0)
        wl_g = jnp.where(in_l, g_row, zero)
        wl_h = jnp.where(in_l, h_row, zero)
        wr_g = jnp.where(in_r, g_row, zero)
        wr_h = jnp.where(in_r, h_row, zero)
        parts = jnp.stack([
            _chunk_hist_group(bch[gi:gi + 1], wl_g, wl_h, wr_g, wr_h,
                              BH, iota_hi, iota_lo)
            for gi in range(G)])                          # (G, 4BH, 16)
        return acc + parts

    acc0 = vary(jnp.zeros((G, 4 * BH, 16), jnp.float32))
    return jax.lax.fori_loop(0, n_chunks, body, acc0)


def both_children_hist_banded(part_bins, part_ghi, start, cnt, col,
                              dec_scalars, *, policy, num_bins: int,
                              num_groups: int, vary=lambda x: x):
    """Leaf-size-adaptive mega-oracle histogram (ops/chunkpolicy.py).

    The mega grid is 128-ALIGNED (chunks start at the aligned floor of
    the leaf offset), so a band applies when the leaf's ALIGNED cover
    ``(start & 127) + cnt`` fits one chunk of that width; band widths
    share the histogram menu's exactness cap.  Dispatch is zero-trip
    fori_loops, same as the plain-path bands — exactly one variant
    executes per split."""
    from .chunkpolicy import note_variant
    sizes = policy.hist_sizes
    start_i = jnp.asarray(start, jnp.int32)
    eff = (start_i & 127) + cnt
    band = policy.band(eff, sizes)
    live = cnt > 0
    base_cover = jnp.where(
        live & (band == 0), _cdiv(eff, sizes[0]), 0)
    note_variant("mega_hist", sizes[0])
    acc = both_children_hist_xla(
        part_bins, part_ghi, start, cnt, col, dec_scalars,
        row_chunk=sizes[0], num_bins=num_bins, num_groups=num_groups,
        vary=vary, cover=base_cover)
    for i, w in enumerate(sizes[1:], 1):
        note_variant("mega_hist", w)
        trip = ((band == i) & live).astype(jnp.int32)
        acc = acc + both_children_hist_xla(
            part_bins, part_ghi, start, cnt, col, dec_scalars,
            row_chunk=w, num_bins=num_bins, num_groups=num_groups,
            vary=vary, cover=trip)
    return acc


def vmem_bytes(row_chunk: int, num_bins: int, num_groups: int) -> int:
    """Scoped VMEM of ``lgbm_split_mega``: one whole-width pass of the
    partition kernel (it moves every bin row at once) and the
    (G, 4 * BH, 16) accumulator, whose rows of 16 lanes take rows of 128.
    (Held against the v5e's compiler, PR 35: at 255 bins and a 4096-row
    chunk 92 and 124 features compile, in 38 and 51 s, and 137 do not;
    at a 2048-row chunk 137 and 224, the widest this admits there,
    compile, in 39 and 72 s.)"""
    g32 = -(-num_groups // 32) * 32
    BH, _ = hist_geometry(num_bins)
    return (_pp.vmem_bytes(g32, row_chunk)
            + num_groups * (-(-4 * BH // 8) * 8) * 128 * 4)


def split_megakernel_pallas(part_bins, part_ghi, sc_packed, scalars, *,
                            row_chunk: int, num_bins: int, num_groups: int,
                            ghi_live: int = 3, pack_rowid: bool = False,
                            interpret: bool = False):
    """Two-way stable partition of the leaf range (scalar layout: the
    S_* constants of ops/partition_pallas.py) PLUS both children's
    histograms, in one Pallas program.

    Args match ``partition_leaf_pallas`` plus:
      num_bins / num_groups: histogram geometry (bins per group; real
        group rows of ``part_bins`` — the rest are DMA-tile padding).

    Returns (part_bins', part_ghi', sc_packed', nl, hist_acc): the first
    three aliased in place; nl an (8, 128) i32 tile with the left count
    at [0, 0]; hist_acc the (G, 4*BH, 16) f32 accumulator of
    ``unpack_hist4``.  A cnt == 0 call (trash-slot iteration) moves no
    rows and returns a zero hist_acc.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    G32, Np = part_bins.shape
    GH = part_ghi.shape[0]
    assert GH == 8 and G32 % 32 == 0, (G32, GH)
    SCR = sc_packed.shape[0]
    assert (sc_packed.shape[1] == Np and SCR % 8 == 0
            and sc_packed.dtype == jnp.int32)
    C = row_chunk
    assert C >= 256 and (C & (C - 1)) == 0 and Np % 128 == 0
    G = num_groups
    assert 0 < G <= G32
    BH, _ = hist_geometry(num_bins)
    assert 3 <= ghi_live <= GH
    P, W, pack_bins, unpack_bins, make_payload, split_payload = \
        payload_codecs(G32, ghi_live, pack_rowid)
    assert P <= SCR
    # late-bound so tools/profile_partition.py's ablation of the
    # compaction applies here too
    compact = _pp._compact

    def kernel(s_ref, pb_in, pg_in, sp_in, pb, pg, sp, nl_ref, hist_ref,
               rb, rg, rs, stgl, stgr, wb, wg, wp, exb, exg, acc, sems):
        a0b = s_ref[S_A0B]
        rem = s_ref[S_REM]
        cnt = s_ref[S_CNT]
        col = s_ref[S_COL]
        total = rem + cnt
        n_chunks = jnp.where(cnt > 0, _cdiv(total, C), 0)

        lane = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
        iota_hi = jax.lax.broadcasted_iota(jnp.int32, (BH, C), 0)
        iota_lo = jax.lax.broadcasted_iota(jnp.int32, (16, C), 0)
        # split column lives at byte (col % 4) of packed word (col // 4)
        col_w = jax.lax.shift_right_logical(col, 2)
        col_sh = (col & 3) * 8
        word_oh = (jax.lax.broadcasted_iota(jnp.int32, (W, 1), 0) == col_w
                   ).astype(jnp.int32)

        acc[:] = jnp.zeros_like(acc)

        def start_read(ci, slot):
            pltpu.make_async_copy(
                pb_in.at[:, pl.ds(a0b * 128 + ci * C, C)],
                rb.at[slot], sems.at[slot, 0]).start()
            pltpu.make_async_copy(
                pg_in.at[:, pl.ds(a0b * 128 + ci * C, C)],
                rg.at[slot], sems.at[slot, 1]).start()

        def wait_read(slot):
            pltpu.make_async_copy(
                pb_in.at[:, pl.ds(0, C)], rb.at[slot],
                sems.at[slot, 0]).wait()
            pltpu.make_async_copy(
                pg_in.at[:, pl.ds(0, C)], rg.at[slot],
                sems.at[slot, 1]).wait()

        @pl.when(n_chunks > 0)
        def _():
            start_read(0, 0)

        def body(ci, carry):
            fill_l, fill_r, nfl, nfr, nl_cnt = carry
            slot = jax.lax.rem(ci, 2)

            @pl.when(ci + 1 < n_chunks)
            def _():
                start_read(ci + 1, 1 - slot)
            wait_read(slot)

            bins_i = rb[slot].astype(jnp.int32)               # (G32, C)
            packed = pack_bins(rb[slot])                      # (W, C)
            ghi_i = jax.lax.bitcast_convert_type(
                rg[slot], jnp.int32)[0:ghi_live]
            payload = make_payload(packed, ghi_i)             # (P, C)

            # --- decision (numerical splits) ---
            word = jnp.sum(packed * word_oh, axis=0,
                           keepdims=True)                     # (1, C)
            colv = jax.lax.shift_right_logical(
                word, jnp.broadcast_to(col_sh, word.shape)) & 255
            gl_i = _decide_left(colv, s_ref[S_BSTART], s_ref[S_ISB],
                                s_ref[S_NB], s_ref[S_DBIN], s_ref[S_MTYPE],
                                s_ref[S_THR], s_ref[S_DL])

            pos = ci * C + lane                 # cover-relative position
            before_i = (pos < rem).astype(jnp.int32)
            inside_i = ((pos >= rem) & (pos < total)).astype(jnp.int32)
            left = jnp.where((before_i != 0) |
                             ((inside_i != 0) & (gl_i != 0)), 1, 0)

            # --- both-children histogram accumulation: the rows are in
            # VMEM anyway; foreign cover-edge rows carry zero weight ---
            g_row = rg[slot][0:1]
            h_row = rg[slot][1:2]
            in_l = (inside_i * gl_i) != 0
            in_r = (inside_i * (1 - gl_i)) != 0
            zero = jnp.float32(0.0)
            wl_g = jnp.where(in_l, g_row, zero)
            wl_h = jnp.where(in_l, h_row, zero)
            wr_g = jnp.where(in_r, g_row, zero)
            wr_h = jnp.where(in_r, h_row, zero)
            for gi in range(G):
                acc[gi] = acc[gi] + _chunk_hist_group(
                    bins_i[gi:gi + 1], wl_g, wl_h, wr_g, wr_h,
                    BH, iota_hi, iota_lo)

            pnr = _excl_prefix_rights(left, C)       # rights before lane
            nlc = jnp.sum(left)
            nl_cnt = nl_cnt + nlc
            nrc = C - nlc

            lcomp, rcomp = compact(payload, left, pnr, C,
                                   lead=fill_l & 127)
            fill_l, fl_l = _pp._stage(stgl, lcomp, fill_l, nlc, C)
            fill_r, fl_r = _pp._stage(stgr, rcomp, fill_r, nrc, C,
                                      mirrored=True)

            # lefts: unpack and flush in place (deferred-wait DMA
            # discipline identical to partition_leaf_pallas)
            @pl.when(fl_l > 0)
            def _():
                @pl.when(nfl > 0)
                def _():
                    pltpu.make_async_copy(
                        wb, pb.at[:, pl.ds(0, C)], sems.at[0, 2]).wait()
                    pltpu.make_async_copy(
                        wg, pg.at[:, pl.ds(0, C)], sems.at[1, 2]).wait()
                pk_l, gl_l = split_payload(stgl[:, 0:C])
                wb[:] = unpack_bins(pk_l)
                wg[:] = jax.lax.bitcast_convert_type(
                    jnp.concatenate(
                        [gl_l,
                         jnp.zeros((GH - ghi_live, C), jnp.int32)], axis=0),
                    jnp.float32)
                pltpu.make_async_copy(
                    wb, pb.at[:, pl.ds(a0b * 128 + nfl * C, C)],
                    sems.at[0, 2]).start()
                pltpu.make_async_copy(
                    wg, pg.at[:, pl.ds(a0b * 128 + nfl * C, C)],
                    sems.at[1, 2]).start()
                stgl[:, 0:C] = stgl[:, C:2 * C]

            # rights: flush STILL PACKED to the i32 scratch
            @pl.when(fl_r > 0)
            def _():
                @pl.when(nfr > 0)
                def _():
                    pltpu.make_async_copy(
                        wp, sp.at[:, pl.ds(0, C)], sems.at[0, 3]).wait()
                wp[0:P] = stgr[:, 0:C]
                pltpu.make_async_copy(
                    wp, sp.at[:, pl.ds(a0b * 128 + nfr * C, C)],
                    sems.at[0, 3]).start()
                stgr[:, 0:C] = stgr[:, C:2 * C]

            return fill_l, fill_r, nfl + fl_l, nfr + fl_r, nl_cnt

        fill_l, fill_r, nfl, nfr, nl_cnt = jax.lax.fori_loop(
            0, n_chunks, body,
            (jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.int32(0),
             jnp.int32(0)))

        hist_ref[:] = acc[:]

        @pl.when(nfl > 0)
        def _():
            pltpu.make_async_copy(
                wb, pb.at[:, pl.ds(0, C)], sems.at[0, 2]).wait()
            pltpu.make_async_copy(
                wg, pg.at[:, pl.ds(0, C)], sems.at[1, 2]).wait()

        @pl.when(nfr > 0)
        def _():
            pltpu.make_async_copy(
                wp, sp.at[:, pl.ds(0, C)], sems.at[0, 3]).wait()

        # Final partial flushes (full-window writes; garbage tails are
        # rewritten by pass 2 or never read)
        @pl.when(fill_l > 0)
        def _():
            pk_f, gl_f = split_payload(stgl[:, 0:C])
            wb[:] = unpack_bins(pk_f)
            wg[:] = jax.lax.bitcast_convert_type(
                jnp.concatenate(
                    [gl_f,
                     jnp.zeros((GH - ghi_live, C), jnp.int32)], axis=0),
                jnp.float32)
            cb = pltpu.make_async_copy(
                wb, pb.at[:, pl.ds(a0b * 128 + nfl * C, C)], sems.at[0, 2])
            cg = pltpu.make_async_copy(
                wg, pg.at[:, pl.ds(a0b * 128 + nfl * C, C)], sems.at[1, 2])
            cb.start(); cg.start(); cb.wait(); cg.wait()

        @pl.when(fill_r > 0)
        def _():
            wp[0:P] = stgr[:, 0:C]
            cp = pltpu.make_async_copy(
                wp, sp.at[:, pl.ds(a0b * 128 + nfr * C, C)], sems.at[0, 3])
            cp.start(); cp.wait()

        nl_true = jnp.where(cnt > 0, nl_cnt - rem, 0)
        nl_ref[:] = jnp.broadcast_to(nl_true, (8, 128)).astype(jnp.int32)

        # ---- pass 2: slide staged rights into [start+nl, aligned_end)
        # (identical to partition_leaf_pallas pass 2) ----
        s_r = n_chunks * C - nl_cnt
        dst_off = rem + nl_true
        dwb = a0b + jax.lax.shift_right_logical(dst_off, 7)
        r0 = dst_off - jax.lax.shift_right_logical(dst_off, 7) * 128
        n_d = jnp.where(s_r > 0, _cdiv(r0 + s_r, C), 0)
        aligned_total = n_chunks * C

        def body2(j, _):
            slot = jax.lax.rem(j, 2)
            read_src = j * C < s_r

            @pl.when(read_src)
            def _():
                pltpu.make_async_copy(
                    sp.at[:, pl.ds(a0b * 128 + j * C, C)],
                    rs.at[slot], sems.at[slot, 0]).start()
            dlo = dst_off - r0 + j * C
            lo = jnp.where(j == 0, r0, 0)
            hi = jnp.minimum(C, aligned_total - dlo)
            need_rmw = (lo > 0) | (hi < C)

            @pl.when(need_rmw)
            def _():
                cb = pltpu.make_async_copy(
                    pb.at[:, pl.ds(dwb * 128 + j * C, C)], exb,
                    sems.at[0, 3])
                cg = pltpu.make_async_copy(
                    pg.at[:, pl.ds(dwb * 128 + j * C, C)], exg,
                    sems.at[1, 3])
                cb.start(); cg.start(); cb.wait(); cg.wait()

            @pl.when(read_src)
            def _():
                pltpu.make_async_copy(
                    sp.at[:, pl.ds(0, C)], rs.at[slot],
                    sems.at[slot, 0]).wait()

            cur_p = rs[slot][0:P]
            prv_p = rs[1 - slot][0:P]
            out_p = _pp._slide(prv_p, cur_p, r0, C)
            pk_2, out_gl = split_payload(out_p)
            valid = (lane >= lo) & (lane < hi)

            @pl.when(j > 0)
            def _():
                pltpu.make_async_copy(
                    wb, pb.at[:, pl.ds(0, C)], sems.at[0, 2]).wait()
                pltpu.make_async_copy(
                    wg, pg.at[:, pl.ds(0, C)], sems.at[1, 2]).wait()
            exg_i = jax.lax.bitcast_convert_type(exg[:], jnp.int32)
            wb[:] = unpack_bins(jnp.where(valid, pk_2, pack_bins(exb[:])))
            wg[:] = jax.lax.bitcast_convert_type(
                jnp.concatenate(
                    [jnp.where(valid, out_gl, exg_i[0:ghi_live]),
                     exg_i[ghi_live:GH]],
                    axis=0),
                jnp.float32)
            pltpu.make_async_copy(
                wb, pb.at[:, pl.ds(dwb * 128 + j * C, C)],
                sems.at[0, 2]).start()
            pltpu.make_async_copy(
                wg, pg.at[:, pl.ds(dwb * 128 + j * C, C)],
                sems.at[1, 2]).start()
            return 0

        jax.lax.fori_loop(0, n_d, body2, 0)

        @pl.when(n_d > 0)
        def _():
            pltpu.make_async_copy(
                wb, pb.at[:, pl.ds(0, C)], sems.at[0, 2]).wait()
            pltpu.make_async_copy(
                wg, pg.at[:, pl.ds(0, C)], sems.at[1, 2]).wait()

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3 +
                  [pl.BlockSpec(memory_space=pltpu.VMEM)] * 2,
        scratch_shapes=[
            pltpu.VMEM((2, G32, C), jnp.uint8),      # rb
            pltpu.VMEM((2, GH, C), jnp.float32),     # rg
            pltpu.VMEM((2, SCR, C), jnp.int32),      # rs
            pltpu.VMEM((P, 2 * C), jnp.int32),       # stgl
            pltpu.VMEM((P, 2 * C), jnp.int32),       # stgr
            pltpu.VMEM((G32, C), jnp.uint8),         # wb
            pltpu.VMEM((GH, C), jnp.float32),        # wg
            pltpu.VMEM((SCR, C), jnp.int32),         # wp
            pltpu.VMEM((G32, C), jnp.uint8),         # exb
            pltpu.VMEM((GH, C), jnp.float32),        # exg
            pltpu.VMEM((G, 4 * BH, 16), jnp.float32),  # acc
            pltpu.SemaphoreType.DMA((2, 4)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct(part_bins.shape, part_bins.dtype),
            jax.ShapeDtypeStruct(part_ghi.shape, part_ghi.dtype),
            jax.ShapeDtypeStruct(sc_packed.shape, sc_packed.dtype),
            jax.ShapeDtypeStruct((8, 128), jnp.int32),
            jax.ShapeDtypeStruct((G, 4 * BH, 16), jnp.float32),
        ],
        grid_spec=grid_spec,
        input_output_aliases={1: 0, 2: 1, 3: 2},
        interpret=interpret,
        name="lgbm_split_mega",
    )(scalars, part_bins, part_ghi, sc_packed)
    return out
