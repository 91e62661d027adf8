"""Pallas TPU kernel for the leaf two-way partition.

TPU-native replacement for the reference DataPartition::Split
(src/treelearner/data_partition.hpp:118-149) and the CUDA
bitvector + AggregateBlockOffset + SplitInner pipeline
(src/treelearner/cuda/cuda_data_partition.cu:288-907): aligned window
DMAs and an in-VMEM roll-network compaction.  On the v5e the kernel is
bound by the network's lane rotates, not by DMA: a 4096-row chunk of the
benchmark's geometry took 6.2 us before PR 30 trimmed the network's
step and takes 4.0 us since, against 0.2 us for its 36 B a row at the
HBM peak (PERF.md sections 5 and 6).  The XLA formulation of the same
partition (models/learner.py:_partition_leaf) is kept as the CPU /
fallback path and as the correctness oracle — both produce bit-identical
layouts (lefts forward-packed in original order, rights behind them in
original order).

Design notes (all constraints below were probed on the live toolchain):
  * Window DMAs compile only with provably 128-aligned dynamic lane
    offsets (``i * 128``) and tile-multiple sublane counts (8 for 32-bit
    types, 32 for u8).  Leaf ranges are arbitrary, so the kernel reads
    the 128-aligned cover of the range and marks the foreign edge rows:
    rows before ``start`` ride as unconditional LEFTS, rows at/after
    ``start + cnt`` as unconditional RIGHTS.  Stable compaction then
    returns them to exactly their original positions.
  * No sort / gather / cumsum lower inside Pallas TPU kernels.  Prefix
    sums are computed with strictly-lower-triangular one-hot matmuls on
    the MXU; the stable two-way compaction is a log2(C)-step binary shift
    network built from ``pltpu.roll`` (bool rolls don't lower — all
    masks stay i32).
  * The compaction payload is PACKED: 4 u8 bin rows ride per i32 row
    (row r of the packed block holds storage rows {r, W+r, 2W+r, 3W+r},
    W = G32/4) and only the 3 live grad/hess/rowid rows of the f32
    payload are carried, so the shift network moves (W+3, C) lanes
    instead of (G32+8, C) — the network's cost is proportional to
    sublane count and dominated the unpacked kernel (~4x the data).
  * Pass 1 streams the cover once: lefts are unpacked and flushed
    forward IN PLACE from the cover base (the left write frontier
    provably trails the read frontier), rights are flushed forward
    STILL PACKED into a (16, N_pad) i32 scratch.  Pass 2 slides the
    staged rights into their final windows with a two-window
    roll-select on the packed payload, unpacking only at the final
    write and read-modify-writing only the partial edge windows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import varying_like


# scalar-operand layout (prefetched i32 vector)
S_A0B = 0       # start >> 7  (128-block index of the aligned cover base)
S_REM = 1       # start & 127
S_CNT = 2       # number of rows in the leaf range
S_COL = 3       # group row of the split feature in the binned matrix
S_BSTART = 4    # bundled bin offset
S_ISB = 5       # feature is bundled (0/1)
S_NB = 6        # feature num_bin
S_DBIN = 7      # feature default bin
S_MTYPE = 8     # missing type (0 none / 1 zero / 2 nan)
S_THR = 9       # split threshold (bin)
S_DL = 10       # default_left (0/1)
N_SCALARS = 11

def sc_rows_for(g32: int) -> int:
    """Packed-scratch sublanes for a (g32, N) bin matrix: the packed
    words plus up to 8 live ghi rows, rounded to the 32-bit DMA tile."""
    return ((g32 // 4 + 8 + 7) // 8) * 8


SC_ROWS = sc_rows_for(32)   # the common g32=32 geometry


def _excl_prefix_rights(flag_l, C):
    """Exclusive per-lane prefix count of rights (flag_l == 0), via
    strictly-lower-triangular one-hot matmuls on the MXU (cumsum does
    not lower in Pallas TPU)."""
    nb = C // 128
    r = (1 - flag_l).astype(jnp.float32).reshape(nb, 128)
    lt = (jax.lax.broadcasted_iota(jnp.int32, (128, 128), 0) <
          jax.lax.broadcasted_iota(jnp.int32, (128, 128), 1)
          ).astype(jnp.float32)
    within = jax.lax.dot_general(
        r, lt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # (nb, 128) exclusive
    tot = jnp.sum(r, axis=1, keepdims=True)          # (nb, 1)
    ltb = (jax.lax.broadcasted_iota(jnp.int32, (nb, nb), 0) <
           jax.lax.broadcasted_iota(jnp.int32, (nb, nb), 1)
           ).astype(jnp.float32)
    carry = jax.lax.dot_general(
        tot.reshape(1, nb), ltb, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # (1, nb) excl blocks
    return (within + carry.reshape(nb, 1)).reshape(1, C).astype(jnp.int32)


def _compact(payload, flag, shift0, C, logc):
    """Stable compaction of flagged lanes to the front: binary shift
    network, moving each flagged lane left by its deficit (the number of
    unflagged lanes before it).  Monotone deficits make every step
    collision-free; unflagged lanes are treated as holes.

    The kernel is bound by the lane rotates of this loop (a step under 128
    lanes rotates every vreg it rolls; PERF.md section 6, PR 30; counted
    by tools/kernel_ops.py), so a step is ONE roll and one select:
      * The deficit row rides the payload's last sublane tile, which has
        room for it whenever P is no multiple of 8 (11 of 16 at the
        benchmark's geometry), and is tested after the roll: a lane takes
        its right neighbour when the deficit that arrives has the bit.
      * A lane that an element has left is not cleared.  Before step b the
        stale copies of element k sit at o_k - s, for o_k its first lane
        and s the strict subsets of the bits of its deficit below b, that
        is less than 2^b lanes to the right of k itself.  A step moves
        every copy along with k, so the one lane a copy can overwrite lies
        strictly between k's new lane and its old one, where a network
        that keeps the elements' order has no element to lose.  Holes
        carry deficit 0 and never move.  (Every flag row of C = 16:
        tests/test_pallas_interpret.py.)
    Lanes past the flagged count come back holding stale copies: callers
    mask them off (``stage``)."""
    P = payload.shape[0]
    aug = jnp.concatenate([payload, jnp.where(flag != 0, shift0, 0)], axis=0)
    for b in range(logc):
        bit = 1 << b
        rolled = pltpu_roll(aug, C - bit)
        # the mask goes to the tiles as i32: an i1 row is broadcast over
        # sublanes through an extui and a second compare
        take = jnp.broadcast_to(rolled[P:P + 1] & bit, aug.shape) != 0
        aug = jnp.where(take, rolled, aug)
    return aug[0:P]


def payload_codecs(G32: int, ghi_live: int, pack_rowid: bool):
    """Packed-payload codec closures shared by the partition kernel and
    the split mega-kernel (ops/split_megakernel_pallas.py).

    Returns (P, W, pack_bins, unpack_bins, make_payload, split_payload):
    W = G32 // 4 packed bin words; P = compaction payload sublanes.  All
    row picks are STATIC sublane slices — masked row selects/reductions
    take a per-tile slow path in Mosaic (round-5 measurement: an
    iota-compare formulation of the rowid packing ran 15x slower).
    """
    W = G32 // 4
    P = W + ghi_live - (1 if pack_rowid else 0)

    def pack_bins(bins_i32):
        """(G32, C) i32 byte values -> (W, C) packed words."""
        return (bins_i32[0:W] | (bins_i32[W:2 * W] << 8) |
                (bins_i32[2 * W:3 * W] << 16) | (bins_i32[3 * W:4 * W] << 24))

    def unpack_bins(packed):
        """(W, C) packed words -> (G32, C) i32 byte values."""
        return jnp.concatenate(
            [packed & 255, (packed >> 8) & 255,
             (packed >> 16) & 255, (packed >> 24) & 255], axis=0)

    def make_payload(packed, ghi_i):
        """(P, C) compaction payload from packed words + live ghi rows;
        with pack_rowid the rowid bytes overwrite the zero byte-3 slots
        of words W-4..W-1 and ghi row 2 is dropped."""
        if not pack_rowid:
            return jnp.concatenate([packed, ghi_i], axis=0)
        rowid = ghi_i[2:3]                               # (1, C) i32
        top = [packed[W - 4 + j:W - 3 + j] |
               ((jax.lax.shift_right_logical(
                   rowid, jnp.broadcast_to(8 * j, rowid.shape)) & 255)
                << 24)
               for j in range(4)]
        extra = [ghi_i[3:ghi_live]] if ghi_live > 3 else []
        return jnp.concatenate(
            [packed[0:W - 4]] + top + [ghi_i[0:2]] + extra, axis=0)

    def split_payload(pay):
        """(P, C) payload -> ((W, C) clean packed words, (ghi_live, C)
        ghi rows in storage order), reconstructing the rowid row."""
        if not pack_rowid:
            return pay[0:W], pay[W:P]
        rowid = None
        for j in range(4):
            byte_j = (jax.lax.shift_right_logical(
                pay[W - 4 + j:W - 3 + j],
                jnp.broadcast_to(24, (1, pay.shape[1]))) & 255) << (8 * j)
            rowid = byte_j if rowid is None else rowid | byte_j
        packed = jnp.concatenate(
            [pay[0:W - 4], pay[W - 4:W] & 0x00FFFFFF], axis=0)
        tail = [pay[W + 2:P]] if P > W + 2 else []
        ghi = jnp.concatenate([pay[W:W + 2], rowid] + tail, axis=0)
        return packed, ghi

    return P, W, pack_bins, unpack_bins, make_payload, split_payload


def pltpu_roll(x, shift):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.roll(x, shift, 1)


def _cdiv(a, c):
    return jax.lax.div(a + (c - 1), c)


def _decide_left(colv, bstart, isb, nb, dbin, mtype, thr, dl):
    """Numerical split decision on raw group-column values, all-i32
    (bool vectors with Python-literal branches trip an i8->i1
    truncation Mosaic can't lower).  The ONE copy of this arithmetic
    shared by the partition kernel, the split mega-kernel and its XLA
    oracle (ops/split_megakernel_pallas.py) — the mega path's
    bit-exactness contract rides on all of them agreeing; the XLA
    fallback formulation lives in ops/partition.py split_decision /
    models/learner.py _goes_left."""
    fb_raw = colv - bstart
    in_rb = (fb_raw >= 1) & (fb_raw <= nb - 1)
    fb = jnp.where(isb == 1, jnp.where(in_rb, fb_raw, dbin), colv)
    miss_i = jnp.where(
        mtype == 1, (fb == dbin).astype(jnp.int32),
        jnp.where(mtype == 2, (fb == nb - 1).astype(jnp.int32), 0))
    nat_i = (fb <= thr).astype(jnp.int32)
    return jnp.where(miss_i != 0, dl, nat_i)


def partition_leaf_pallas(part_bins, part_ghi, sc_packed, scalars, *,
                          row_chunk: int, ghi_live: int = 3,
                          pack_rowid: bool = False,
                          interpret: bool = False):
    """Two-way stable partition of the leaf range described by
    ``scalars`` (see the S_* layout above), in place.

    Args:
      part_bins: (G32, N_pad) u8 binned matrix, G32 a multiple of 32.
      part_ghi:  (8, N_pad)  f32 packed (grad, hess, rowid-bits, ...).
        Only rows 0..ghi_live-1 are preserved through the partition; the
        trailing pad rows come back zeroed/garbage.  The physical-order
        fused training step rides score and objective payload rows here
        (models/boosting.py _setup_fused_step).
      sc_packed: (SC_ROWS, N_pad) i32 scratch staging the packed rights
      scalars: (N_SCALARS,) i32.
      pack_rowid: ride the rowid-bits ghi row (row 2) inside the 4 spare
        byte slots of the packed bin words (byte 3 of words W-4..W-1 —
        the zero pad rows G..G32) instead of as its own payload sublane.
        The roll network's cost is proportional to payload sublanes
        (PERF.md), so this drops P by one for free when G <= G32-4.
        Kernel-internal only: the HBM layout of part_ghi is unchanged
        and the pad bin rows come back zeroed.
    Returns (part_bins', part_ghi', sc_packed', nl) with the first three
    aliased in place; nl is an (8, 128) i32 tile whose [0, 0] element is
    the left count.
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
    logc = C.bit_length() - 1
    assert 3 <= ghi_live <= GH
    if pack_rowid:
        assert G32 // 4 >= 4, "pack_rowid needs >= 4 packed words"
    # payload sublanes: bins words + live ghi rows (minus the rowid row
    # when it rides inside the spare bin bytes)
    P, W, pack_bins, unpack_bins, make_payload, split_payload = \
        payload_codecs(G32, ghi_live, pack_rowid)
    assert P <= SCR

    def kernel(s_ref, pb_in, pg_in, sp_in, pb, pg, sp, nl_ref,
               rb, rg, rs, stgl, stgr, wb, wg, wp, exb, exg, sems):
        a0b = s_ref[S_A0B]
        rem = s_ref[S_REM]
        cnt = s_ref[S_CNT]
        col = s_ref[S_COL]
        total = rem + cnt
        n_chunks = jnp.where(cnt > 0, _cdiv(total, C), 0)

        lane = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
        # split column lives at byte (col // W) of packed word (col % W)
        col_k = jax.lax.div(col, W)
        col_w = col - col_k * W
        col_sh = col_k * 8
        word_oh = (jax.lax.broadcasted_iota(jnp.int32, (W, 1), 0) == col_w
                   ).astype(jnp.int32)

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
            packed = pack_bins(bins_i)                        # (W, C)
            ghi_i = jax.lax.bitcast_convert_type(
                rg[slot], jnp.int32)[0:ghi_live]
            payload = make_payload(packed, ghi_i)             # (P, C)

            # --- decision (numerical splits; see ops/partition.py
            # split_decision and models/learner.py _goes_left) ---
            word = jnp.sum(packed * word_oh, axis=0,
                           keepdims=True)                     # (1, C)
            colv = jax.lax.shift_right_logical(
                word, jnp.broadcast_to(col_sh, word.shape)) & 255
            gl_i = _decide_left(colv, s_ref[S_BSTART], s_ref[S_ISB],
                                s_ref[S_NB], s_ref[S_DBIN],
                                s_ref[S_MTYPE], s_ref[S_THR], s_ref[S_DL])

            pos = ci * C + lane                 # cover-relative position
            before_i = (pos < rem).astype(jnp.int32)
            inside_i = ((pos >= rem) & (pos < total)).astype(jnp.int32)
            left = jnp.where((before_i != 0) |
                             ((inside_i != 0) & (gl_i != 0)), 1, 0)

            pnr = _excl_prefix_rights(left, C)       # rights before lane
            nlc = jnp.sum(left)
            nl_cnt = nl_cnt + nlc
            nrc = C - nlc

            lcomp = _compact(payload, left, pnr, C, logc)
            rcomp = _compact(payload, 1 - left, lane - pnr, C, logc)

            def stage(stg, comp, fill, n_add):
                # place comp[0:n_add) at staging positions [fill, +n_add)
                rolled = pltpu.roll(comp, fill, 1)
                m1 = (lane >= fill) & (lane < fill + n_add)
                stg[:, 0:C] = jnp.where(m1, rolled, stg[:, 0:C])
                m2 = (lane + C) < (fill + n_add)
                stg[:, C:2 * C] = jnp.where(m2, rolled, stg[:, C:2 * C])
                new_fill = fill + n_add
                flushed = (new_fill >= C).astype(jnp.int32)
                return new_fill - flushed * C, flushed

            fill_l, fl_l = stage(stgl, lcomp, fill_l, nlc)
            fill_r, fl_r = stage(stgr, rcomp, fill_r, nrc)

            # lefts: unpack and flush in place to the row buffers.
            # Flush DMAs are NOT waited inline: the wait happens just
            # before the NEXT overwrite of the staging window (or at the
            # pass-1 drain), overlapping the write with the next chunk's
            # compaction.  Write windows only ever move forward, so the
            # deferred write still lands strictly behind the read
            # frontier.
            @pl.when(fl_l > 0)
            def _():
                @pl.when(nfl > 0)
                def _():
                    pltpu.make_async_copy(
                        wb, pb.at[:, pl.ds(0, C)], sems.at[0, 2]).wait()
                    pltpu.make_async_copy(
                        wg, pg.at[:, pl.ds(0, C)], sems.at[1, 2]).wait()
                pk_l, gl_l = split_payload(stgl[:, 0:C])
                wb[:] = unpack_bins(pk_l).astype(jnp.uint8)
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

        # Drain the deferred in-flight flush DMAs before the staging
        # buffers are overwritten and before pass 2 touches their
        # destination regions.
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

        # Final partial flushes.  Full-window writes: the garbage tail
        # beyond ``fill`` is always rewritten by pass 2 (lefts) or never
        # read (scratch).
        @pl.when(fill_l > 0)
        def _():
            pk_f, gl_f = split_payload(stgl[:, 0:C])
            wb[:] = unpack_bins(pk_f).astype(jnp.uint8)
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

        # drop the foreign prefix; with cnt == 0 the chunk loop never ran
        # (trash-slot iterations call the partition with an arbitrary,
        # usually unaligned start), so the count must clamp to 0
        nl_true = jnp.where(cnt > 0, nl_cnt - rem, 0)
        nl_ref[:] = jnp.broadcast_to(nl_true, (8, 128)).astype(jnp.int32)

        # ---- pass 2: slide staged rights into [start+nl, aligned_end) ----
        s_r = n_chunks * C - nl_cnt                  # staged rights total
        dst_off = rem + nl_true                      # dst0 - a0
        dwb = a0b + jax.lax.shift_right_logical(dst_off, 7)  # block of dw0
        # r0 = dst0 - floor128(dst0), in [0, 128)
        r0 = dst_off - jax.lax.shift_right_logical(dst_off, 7) * 128
        n_d = jnp.where(s_r > 0, _cdiv(r0 + s_r, C), 0)
        aligned_total = n_chunks * C                 # cover size

        def body2(j, _):
            slot = jax.lax.rem(j, 2)
            # read source window j of the staged rights (front-packed
            # from the cover base in scratch); the guard keeps the last
            # (prev-only) destination window from reading past the
            # staged region
            read_src = j * C < s_r

            @pl.when(read_src)
            def _():
                # read through the OUTPUT refs: on TPU they alias the
                # inputs, and the snapshot semantics of interpret mode
                # would otherwise show pass 2 stale pre-pass-1 contents
                pltpu.make_async_copy(
                    sp.at[:, pl.ds(a0b * 128 + j * C, C)],
                    rs.at[slot], sems.at[slot, 0]).start()
            # destination window bounds (cover-relative)
            dlo = dst_off - r0 + j * C               # window start
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

            cur_p = rs[slot][0:P]                    # packed payload
            prv_p = rs[1 - slot][0:P]
            take_prev = lane < r0
            out_p = jnp.where(take_prev, pltpu.roll(prv_p, r0, 1),
                              pltpu.roll(cur_p, r0, 1))
            pk_2, out_gl = split_payload(out_p)      # clean words + ghi
            out_b = unpack_bins(pk_2)                # (G32, C)
            valid = (lane >= lo) & (lane < hi)
            # wait the PREVIOUS window's deferred write before reusing
            # the staging buffers (destination windows are disjoint, so
            # the in-flight write never races this window's RMW read)
            @pl.when(j > 0)
            def _():
                pltpu.make_async_copy(
                    wb, pb.at[:, pl.ds(0, C)], sems.at[0, 2]).wait()
                pltpu.make_async_copy(
                    wg, pg.at[:, pl.ds(0, C)], sems.at[1, 2]).wait()
            exg_i = jax.lax.bitcast_convert_type(exg[:], jnp.int32)
            wb[:] = jnp.where(valid, out_b,
                              exb[:].astype(jnp.int32)).astype(jnp.uint8)
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
                  [pl.BlockSpec(memory_space=pltpu.VMEM)],
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
            pltpu.SemaphoreType.DMA((2, 4)),
        ],
    )
    operands = (scalars, part_bins, part_ghi, sc_packed)
    out = pl.pallas_call(
        kernel,
        out_shape=[
            varying_like(a.shape, a.dtype, *operands)
            for a in (part_bins, part_ghi, sc_packed)
        ] + [varying_like((8, 128), jnp.int32, *operands)],
        grid_spec=grid_spec,
        input_output_aliases={1: 0, 2: 1, 3: 2},
        interpret=interpret,
        name="lgbm_partition",
    )(*operands)
    return out


def make_scalars(start, cnt, col, bstart, isb, nb, dbin, mtype, thr, dl):
    """Pack the kernel's scalar operand (all traced i32)."""
    start = jnp.asarray(start, jnp.int32)
    a0b = jax.lax.shift_right_logical(start, 7)
    rem = start - a0b * 128
    vals = [a0b, rem, cnt, col, bstart, isb, nb, dbin, mtype, thr, dl]
    return jnp.stack([jnp.asarray(v).astype(jnp.int32) for v in vals])
