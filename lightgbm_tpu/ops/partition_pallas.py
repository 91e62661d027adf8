"""Pallas TPU kernel for the leaf two-way partition.

TPU-native replacement for the reference DataPartition::Split
(src/treelearner/data_partition.hpp:118-149) and the CUDA
bitvector + AggregateBlockOffset + SplitInner pipeline
(src/treelearner/cuda/cuda_data_partition.cu:288-907): aligned window
DMAs and an in-VMEM compaction, one lane permutation per 128-lane block
and a shift network over whole blocks.  Until PR 34 the compaction was a
twelve-step roll network and the kernel was bound by its lane rotates
(994 a 4096-row chunk, 4.0 us a chunk at the benchmark's geometry).
Since, nothing in pass 1 rotates: a chunk takes 2.5 us, 2.0 of them
without any compaction (staging, flushes, pass 2 and what the DMAs leave
uncovered), against 0.2 us for its 36 B a row at the HBM peak (PERF.md
sections 5 and 6; tools/kernel_ops.py --bundles shows which unit's issue
slots a form fills).  The XLA formulation of the same partition
(models/learner.py:_partition_leaf) is kept as the CPU / fallback path
and as the correctness oracle.  The kernel's layout is the stable one:
lefts forward-packed in original order, rights behind them in original
order.  The XLA form leaves the same lefts, bit for bit, and the same
rows on the right, but packs each chunk's rights backward from the
range's end, so over a leaf of several chunks its rights stand in reverse
chunk order (tests/test_wide_kernels.py holds both to that); the trees do
not depend on the order of rows inside a leaf.

Design notes (all constraints below were probed on the live toolchain):
  * Window DMAs compile only with provably 128-aligned dynamic lane
    offsets (``i * 128``) and tile-multiple sublane counts (8 for 32-bit
    types, 32 for u8).  Leaf ranges are arbitrary, so the kernel reads
    the 128-aligned cover of the range and marks the foreign edge rows:
    rows before ``start`` ride as unconditional LEFTS, rows at/after
    ``start + cnt`` as unconditional RIGHTS.  Stable compaction then
    returns them to exactly their original positions.
  * No sort / cumsum lowers inside Pallas TPU kernels, and a gather only
    along the lanes of one vreg.  Prefix sums are strictly-lower-
    triangular one-hot matmuls on the MXU; the stable two-way compaction
    (``_compact``) is, per 128-lane block, one permutation of the block's
    lanes (lefts to their final lanes, rights mirrored into the lanes
    that are left), inverted by a one-hot matmul on the MXU and applied
    by the XLU's lane gather, then a log2(C / 128)-step binary shift
    network over whole blocks, which renames vregs and rotates nothing
    (bool values don't lower — all masks stay i32).
  * The compaction payload is PACKED: 4 u8 bin rows ride per i32 row
    (a bitcast of the u8 tile: word w holds storage rows 4w..4w+3) and
    only the live grad/hess/rowid/... rows of the f32 payload are
    carried, so the block network moves (W + live, C) lanes instead of
    (G32 + 8, C) — its cost is proportional to sublane tiles.
  * Pass 1 streams the cover once: each side's chunk is appended to a
    two-window staging buffer by a masked store of whole blocks
    (``_stage``; the compaction already put it at the buffer's lane
    offset); lefts are unpacked and flushed forward IN PLACE from the
    cover base (the left write frontier provably trails the read
    frontier), rights are flushed forward STILL PACKED, and mirrored
    inside each block, into a (16, N_pad) i32 scratch.  Pass 2 slides
    the staged rights into their final windows (``_slide``: one lane
    gather a vreg sets the mirror straight and rotates by the window's
    offset), unpacking only at the final write and read-modify-writing
    only the partial edge windows.
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
S_ISCAT = 11    # categorical split (0/1)
S_CAT0 = 12     # the category set over the feature's bins: bin b is bit
CAT_WORDS = 8   # (b & 31) of word S_CAT0 + (b >> 5), 256 bits for u8 bins
N_SCALARS = S_CAT0 + CAT_WORDS

def sc_rows_for(g32: int) -> int:
    """Packed-scratch sublanes for a (g32, N) bin matrix: the packed
    words plus up to 8 live ghi rows, rounded to the 32-bit DMA tile."""
    return ((g32 // 4 + 8 + 7) // 8) * 8


SC_ROWS = sc_rows_for(32)   # the common g32=32 geometry


def vmem_bytes(pass_rows: int, row_chunk: int, ghi_live: int = 8,
               passes: int = 1) -> int:
    """Scoped VMEM of ``lgbm_partition`` moving ``pass_rows`` u8 sublanes
    a pass: the scratch it declares, plus what Mosaic keeps of the
    compaction's values beside it (the permuted chunk and the block
    network's stages, ``(P + 2, C + 128)`` words each: measured 3.5 to 5.3
    of them over payloads of 13 to 61 rows, AOT for the v5e, PR 35; 5.5
    are reckoned)."""
    C, W = row_chunk, pass_rows // 4
    P, scr = W + ghi_live, sc_rows_for(pass_rows)
    declared = (2 * pass_rows * C + 2 * 8 * C * 4 + 2 * scr * C * 4
                + 2 * P * 2 * C * 4 + 2 * (pass_rows * C + 8 * C * 4)
                + scr * C * 4 + (2 * 32 * C if passes > 1 else 0))
    value = (-(-(P + 2) // 8) * 8) * (C + 128) * 4
    return declared + 11 * value // 2


def pass_rows_for(num_groups: int, row_chunk: int, limit: int) -> int:
    """u8 sublanes a pass of ``lgbm_partition`` moves at this width, a
    multiple of 32: the whole matrix where one pass fits ``limit`` bytes
    of VMEM, else the tiles cut evenly into the fewest passes that do
    (the learner pads its bin rows to a whole number of passes).  0 where
    not even one tile a pass fits."""
    T = -(-num_groups // 32)
    if vmem_bytes(32 * T, row_chunk) <= limit:
        return 32 * T
    fit = [tg for tg in range(1, T)
           if vmem_bytes(32 * tg, row_chunk, passes=2) <= limit]
    if not fit:
        return 0
    passes = -(-T // fit[-1])
    return 32 * -(-T // passes)


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


def _compact(payload, left, pnr, C, block=128, lead=0):
    """Stable two-way compaction of a chunk: ``(lcomp, rcomp)``, each
    ``(P, C + block)``.  The lanes with ``left != 0`` come back packed in
    order from position ``lead`` of ``lcomp`` on, the others from
    position ``-lead % block`` of ``rcomp`` on, where ``rcomp`` is
    MIRRORED inside each block: position q sits at lane
    ``q ^ (block - 1)``.  ``0 <= lead < block`` is where the lefts'
    staging buffer stands inside its block, and the rights' buffer stands
    at ``-lead % block`` because the two fills add up to whole chunks;
    ``_stage`` then stores whole blocks and rotates nothing.  ``pnr`` is
    the number of rights before each lane, so a left moves to position
    ``d = lead + lane - pnr`` and a right to ``d = -lead % block + pnr``.
    Two stages, for ``C // block`` blocks of ``block`` lanes (128 in the
    kernels: one vreg's lanes, one MXU tile):

      1. In the block, one permutation.  The lefts of block k take the
         lanes ``[a, a + n)`` (mod ``block``), ``a`` their first
         position's lane and ``n`` their number; the rights, mirrored,
         take what is left, ``[a + n, a + block)``: their positions start
         at ``-a`` (the two sides' positions before a block add up to
         whole blocks) and the mirror runs them downwards from ``a - 1``.
         So ``dest = left ? d : ~d`` (mod ``block``) sends every lane of
         the block to a lane of its own.  The MXU inverts it: the one-hot
         ``Q[j, i] = (dest[i] == j)`` against the lane numbers (exact in
         bf16), contracted over the lanes of both, gives for every output
         lane the lane it reads, and the XLU's lane gather
         (``take_along_axis`` inside a vreg) moves the payload, which
         never meets a rounding.  Each side's *block deficit* rides in
         the payload's spare sublanes, zero on the other side's lanes:
         source block k is written to output block k + 1 (``lead`` can
         push an element one block to the right), so the deficit is
         ``k + 1 - d // block``, between 0 and ``C // block``.
      2. Across blocks, on the VPU, once a side and each from the same
         permuted chunk.  In each lane column a side's elements have
         increasing source blocks and consecutive destination blocks, so
         their block deficits are monotone: a binary shift network over
         whole blocks (a shift by a multiple of 128 lanes renames vregs
         and rotates nothing), one test of the shifted deficit and one
         select a step.  The other side's elements carry deficit 0 here:
         they are holes, which never move and are written over.  A lane
         that an element has left is not cleared: before step b its
         stale copies sit less than 2^b blocks behind it in its column
         and move along with it, so the one lane a copy can overwrite
         lies strictly between the element's new lane and its old one,
         where a network that keeps the elements' order has no element
         to lose; nothing real wraps around, a deficit being at most its
         element's block.  (Every flag row of 4 x 4 lanes:
         tests/test_pallas_interpret.py.)

    Stage 1 takes the place of the seven network steps under 128 lanes,
    whose lane rotates bound the kernel (PERF.md section 6, PRs 30 and
    34; tools/kernel_ops.py counts what is left).  Everything is static
    slices of values: the blocks are unrolled, which lets the scheduler
    overlap the products' fill and drain (a loop over the blocks was
    three times slower).  Positions outside ``[lead, lead + count)`` of a
    side come back holding the other side or stale copies: callers mask
    them off (``_stage``)."""
    P = payload.shape[0]
    R8 = (P + 2 + 7) // 8 * 8            # + the two block-deficit rows
    logb = block.bit_length() - 1
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
    d_left = lead + lane - pnr
    d_right = ((-lead) & (block - 1)) + pnr
    out_blk = (lane >> logb) + 1
    is_left = left != 0
    aug = jnp.concatenate(
        [payload,
         jnp.where(is_left, out_blk - (d_left >> logb), 0),
         jnp.where(is_left, 0, out_blk - (d_right >> logb)),
         jnp.zeros((R8 - P - 2, C), jnp.int32)], axis=0)
    dest = jnp.where(is_left, d_left, ~d_right) & (block - 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (8, block), 1).astype(
        jnp.float32).astype(jnp.bfloat16)
    two23 = jnp.float32(1 << 23)
    out = [jnp.zeros((R8, block), jnp.int32)]
    for k in range(C // block):
        at = slice(k * block, (k + 1) * block)
        q = jnp.where(jnp.broadcast_to(dest[:, at], sub.shape) == sub,
                      jnp.float32(1.0), jnp.float32(0.0))
        src = jax.lax.dot_general(
            lanes, q.astype(jnp.bfloat16), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)     # (8, block), rows equal
        # an exact small integer plus 2^23 has it in its low bits
        src = jax.lax.bitcast_convert_type(src + two23, jnp.int32) \
            & (block - 1)
        out.append(jnp.take_along_axis(
            aug[:, at], jnp.concatenate([src] * (R8 // 8), axis=0), axis=1))
    both = jnp.concatenate(out, axis=1)                       # (R8, C + block)
    comp = []
    for row in (P, P + 1):
        side = both
        for b in range((C // block).bit_length()):    # deficit <= C // block
            s = block << b
            ahead = jnp.concatenate([side[:, s:], side[:, :s]], axis=1)
            # the mask goes to the tiles as i32: an i1 row is broadcast
            # over sublanes through an extui and a second compare
            take = jnp.broadcast_to(ahead[row:row + 1] & (1 << b),
                                    side.shape) != 0
            side = jnp.where(take, ahead, side)
        comp.append(side[0:P])
    return comp


def _stage(stg, comp, fill, n_add, C, mirrored=False):
    """Append one side of a chunk to its ``(P, 2 * C)`` staging buffer:
    ``comp``'s positions ``[fill % 128, + n_add)`` (``_compact`` with
    that lead) go to the staging positions ``[fill, + n_add)``, a masked
    store of whole 128-lane blocks at a dynamic block offset, which
    rotates nothing.  ``mirrored`` (the rights): position q of ``comp``
    and of the buffer sits at lane ``q ^ 127``.  Returns (the new fill,
    whether the first window filled: the caller flushes it and moves the
    second window down)."""
    from jax.experimental import pallas as pl

    lead = fill & 127
    win = pl.ds(pl.multiple_of(fill - lead, 128), C + 128)
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, C + 128), 1)
    if mirrored:
        pos = pos ^ 127
    keep = (pos >= lead) & (pos < lead + n_add)
    stg[:, win] = jnp.where(keep, comp, stg[:, win])
    new_fill = fill + n_add
    flushed = (new_fill >= C).astype(jnp.int32)
    return new_fill - flushed * C, flushed


def _slide(prev, cur, r0, C):
    """The staged rights' window ``cur`` (mirrored inside each 128-lane
    block, as ``_compact`` leaves them) set straight and slid right by
    ``r0`` lanes (0 <= r0 < 128), with ``prev``'s last ``r0`` positions
    in front: lane l holds position ``l - r0`` of ``cur``, or position
    ``C - r0 + l`` of ``prev`` for l < r0.  Block by block, because r0
    stays inside a block: one lane gather a vreg undoes the mirror and
    rotates (lane l reads lane ``(127 + r0 - l) % 128``), and a block
    takes its first r0 lanes from its left neighbour (a roll of the whole
    window by a dynamic amount would also pay for shifts by whole blocks
    that cannot happen)."""
    from_left = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1) < r0
    read = (127 + r0 - jax.lax.broadcasted_iota(
        jnp.int32, (prev.shape[0], 128), 1)) & 127
    left = jnp.take_along_axis(prev[:, C - 128:C], read, axis=1)
    out = []
    for k in range(C // 128):
        here = jnp.take_along_axis(cur[:, k * 128:(k + 1) * 128], read,
                                   axis=1)
        out.append(jnp.where(from_left, left, here))
        left = here
    return jnp.concatenate(out, axis=1)


def payload_codecs(G32: int, ghi_live: int, pack_rowid: bool):
    """Packed-payload codec closures shared by the partition kernel and
    the split mega-kernel (ops/split_megakernel_pallas.py).

    Returns (P, W, pack_bins, unpack_bins, make_payload, split_payload):
    W = G32 // 4 packed bin words; P = compaction payload sublanes.
    Packing and unpacking the bins are bitcasts of the u8 tile: word w
    holds storage rows 4w..4w+3, row 4w in its low byte (Mosaic makes
    them of sub-element unpacks and packs, cheaper than the shifts and
    ors they replace: about 110 bundles a chunk in pass 1 and 170 a
    window in pass 2, tools/kernel_ops.py --bundles, PR 34).  All row
    picks are STATIC sublane slices — masked row
    selects/reductions take a per-tile slow path in Mosaic (round-5
    measurement: an iota-compare formulation of the rowid packing ran
    15x slower).
    """
    from jax.experimental.pallas import tpu as pltpu

    W = G32 // 4
    P = W + ghi_live - (1 if pack_rowid else 0)

    def pack_bins(bins_u8):
        """(G32, C) u8 tile -> (W, C) packed words."""
        return pltpu.bitcast(bins_u8, jnp.int32)

    def unpack_bins(packed):
        """(W, C) packed words -> (G32, C) u8 tile."""
        return pltpu.bitcast(packed, jnp.uint8)

    def make_payload(packed, ghi_i):
        """(P, C) compaction payload from packed words + live ghi rows;
        with pack_rowid the rowid row takes the place of word W-1 (the
        zero pad rows G32-4..G32-1) and ghi row 2 is dropped."""
        if not pack_rowid:
            return jnp.concatenate([packed, ghi_i], axis=0)
        extra = [ghi_i[3:ghi_live]] if ghi_live > 3 else []
        return jnp.concatenate(
            [packed[0:W - 1], ghi_i[2:3], ghi_i[0:2]] + extra, axis=0)

    def split_payload(pay):
        """(P, C) payload -> ((W, C) clean packed words, (ghi_live, C)
        ghi rows in storage order), the pad rows zero again."""
        if not pack_rowid:
            return pay[0:W], pay[W:P]
        packed = jnp.concatenate(
            [pay[0:W - 1], jnp.zeros((1, pay.shape[1]), jnp.int32)], axis=0)
        tail = [pay[W + 2:P]] if P > W + 2 else []
        ghi = jnp.concatenate([pay[W:W + 2], pay[W - 1:W]] + tail, axis=0)
        return packed, ghi

    return P, W, pack_bins, unpack_bins, make_payload, split_payload


def _cdiv(a, c):
    return jax.lax.div(a + (c - 1), c)


def _decide_left(colv, bstart, isb, nb, dbin, mtype, thr, dl, cat=None):
    """Split decision on raw group-column values, all-i32 (bool vectors
    with Python-literal branches trip an i8->i1 truncation Mosaic can't
    lower): ``bin <= thr`` with the missing-value handling for a
    numerical split and, where ``cat = (is_cat, words)`` is given,
    membership of the feature bin in the category set for a categorical
    one: bit ``fb & 31`` of ``words[fb >> 5]`` (``CAT_WORDS`` scalars,
    picked by a select chain; bin 0, the NaN/other bin, is never in a
    set, so missing and unkept categories go right).  ``cat=None``
    compiles the numerical arm alone.  The ONE copy of this arithmetic
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
    num_i = jnp.where(miss_i != 0, dl, nat_i)
    if cat is None:
        return num_i
    is_cat, words = cat
    at = jax.lax.shift_right_logical(fb, 5)
    word = jnp.broadcast_to(words[0], fb.shape)
    for k in range(1, len(words)):
        word = jnp.where(at == k, words[k], word)
    bit = jax.lax.shift_right_logical(word, fb & 31) & 1
    return jnp.where(is_cat != 0, bit, num_i)


def partition_leaf_pallas(part_bins, part_ghi, sc_packed, scalars, *,
                          row_chunk: int, ghi_live: int = 3,
                          pack_rowid: bool = False,
                          pass_rows: int | None = None,
                          categorical: bool = False,
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
      sc_packed: (sc_rows_for(pass_rows), N_pad) i32 scratch staging
        the packed rights of one pass
      scalars: (N_SCALARS,) i32.
      pass_rows: u8 sublanes of the bins one pass moves (a multiple of
        32 that divides G32; default G32: one pass).  The grid is the
        passes: each runs the two-pass compaction below on its own
        ``pass_rows`` sublanes of the bins, through scratch of that
        height, so the VMEM held (``vmem_bytes``) follows ``pass_rows``
        and not the matrix's width.  Every pass decides the rows anew
        from the split column, read in its ORIGINAL order: the pass that
        holds the column runs last, and the other passes fetch the
        column's own u8 tile beside their bins.  The (grad, hess, ...)
        rows ride in every pass's payload (one traced body) and are
        written by the last alone.
      pack_rowid: ride the rowid-bits ghi row (row 2) in the place of
        packed bin word W-1 (the zero pad rows G32-4..G32-1) instead of
        as its own payload sublane: P drops by one for free when
        G <= G32-4 (the block network's cost goes by sublane tiles, so it
        pays where it saves a tile).  Kernel-internal only: the HBM
        layout of part_ghi is unchanged and the pad bin rows come back
        zeroed.
      categorical: compile the decision's categorical arm (the split's
        ``S_ISCAT`` flag and ``S_CAT0`` set words); without it the kernel
        decides numerical splits alone and lowers as it did before the
        arm existed.
    Returns (part_bins', part_ghi', sc_packed', nl) with the first three
    aliased in place; nl is an (8, 128) i32 tile whose [0, 0] element is
    the left count.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    G32, Np = part_bins.shape
    GH = part_ghi.shape[0]
    assert GH == 8 and G32 % 32 == 0, (G32, GH)
    GT = G32 if pass_rows is None else pass_rows
    assert GT % 32 == 0 and G32 % GT == 0, (G32, GT)
    NP = G32 // GT
    assert NP == 1 or not pack_rowid     # the spare bytes are the last pass's
    SCR = sc_packed.shape[0]
    assert (sc_packed.shape[1] == Np and SCR % 8 == 0
            and sc_packed.dtype == jnp.int32)
    C = row_chunk
    assert C >= 256 and (C & (C - 1)) == 0 and Np % 128 == 0
    assert 3 <= ghi_live <= GH
    # payload sublanes: a pass's bin words + live ghi rows (minus the
    # rowid row when it rides inside the spare bin bytes)
    P, W, pack_bins, unpack_bins, make_payload, split_payload = \
        payload_codecs(GT, ghi_live, pack_rowid)
    assert P <= SCR

    def kernel(s_ref, pb_in, pg_in, sp_in, pb_all, pg, sp, nl_ref,
               rb, rg, rs, stgl, stgr, wb, wg, wp, exb, exg, *more):
        sems = more[-1]
        a0b = s_ref[S_A0B]
        rem = s_ref[S_REM]
        cnt = s_ref[S_CNT]
        col = s_ref[S_COL]
        total = rem + cnt
        n_chunks = jnp.where(cnt > 0, _cdiv(total, C), 0)

        lane = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
        # split column lives at byte (col % 4) of packed word (col // 4)
        col_sh = (col & 3) * 8
        if NP == 1:
            pb_src, pb = pb_in, pb_all
            col_w = jax.lax.shift_right_logical(col, 2)

            def on_last(fn):
                fn()
        else:
            # the column's pass last: the others read it unpermuted
            col_pass = jax.lax.div(col, GT)
            this = jax.lax.rem(pl.program_id(0) + col_pass + 1, NP)
            rows = pl.ds(pl.multiple_of(this * GT, 32), GT)
            pb_src, pb = pb_in.at[rows], pb_all.at[rows]
            rc = more[0]                 # (2, 32, C): the column's u8 tile
            col_rows = pl.ds(pl.multiple_of(
                jax.lax.shift_right_logical(col, 5) * 32, 32), 32)
            col_w = jax.lax.shift_right_logical(col, 2) & 7
            on_last = pl.when(pl.program_id(0) == NP - 1)
        word_oh = (jax.lax.broadcasted_iota(
            jnp.int32, (W if NP == 1 else 8, 1), 0) == col_w
                   ).astype(jnp.int32)

        def start_read(ci, slot):
            pltpu.make_async_copy(
                pb_src.at[:, pl.ds(a0b * 128 + ci * C, C)],
                rb.at[slot], sems.at[slot, 0]).start()
            pltpu.make_async_copy(
                pg_in.at[:, pl.ds(a0b * 128 + ci * C, C)],
                rg.at[slot], sems.at[slot, 1]).start()
            if NP > 1:
                pltpu.make_async_copy(
                    pb_in.at[col_rows, pl.ds(a0b * 128 + ci * C, C)],
                    rc.at[slot], sems.at[slot, 4]).start()

        def wait_read(slot):
            pltpu.make_async_copy(
                pb_src.at[:, pl.ds(0, C)], rb.at[slot],
                sems.at[slot, 0]).wait()
            pltpu.make_async_copy(
                pg_in.at[:, pl.ds(0, C)], rg.at[slot],
                sems.at[slot, 1]).wait()
            if NP > 1:
                pltpu.make_async_copy(
                    pb_in.at[pl.ds(0, 32), pl.ds(0, C)], rc.at[slot],
                    sems.at[slot, 4]).wait()

        def start_write(at):
            """The staged window (wb, wg) to the row buffers at lane
            ``at``; the (grad, hess, ...) rows in the last pass alone."""
            pltpu.make_async_copy(
                wb, pb.at[:, pl.ds(at, C)], sems.at[0, 2]).start()
            on_last(lambda: pltpu.make_async_copy(
                wg, pg.at[:, pl.ds(at, C)], sems.at[1, 2]).start())

        def wait_write():
            pltpu.make_async_copy(
                wb, pb.at[:, pl.ds(0, C)], sems.at[0, 2]).wait()
            on_last(lambda: pltpu.make_async_copy(
                wg, pg.at[:, pl.ds(0, C)], sems.at[1, 2]).wait())

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

            packed = pack_bins(rb[slot])                      # (W, C)
            ghi_i = jax.lax.bitcast_convert_type(
                rg[slot], jnp.int32)[0:ghi_live]
            payload = make_payload(packed, ghi_i)             # (P, C)

            # --- decision (see ops/partition.py split_decision and
            # models/learner.py _goes_left) ---
            col_words = packed if NP == 1 else pack_bins(rc[slot])
            word = jnp.sum(col_words * word_oh, axis=0,
                           keepdims=True)                     # (1, C)
            colv = jax.lax.shift_right_logical(
                word, jnp.broadcast_to(col_sh, word.shape)) & 255
            cat = ((s_ref[S_ISCAT],
                    [s_ref[S_CAT0 + k] for k in range(CAT_WORDS)])
                   if categorical else None)
            gl_i = _decide_left(colv, s_ref[S_BSTART], s_ref[S_ISB],
                                s_ref[S_NB], s_ref[S_DBIN],
                                s_ref[S_MTYPE], s_ref[S_THR], s_ref[S_DL],
                                cat)

            pos = ci * C + lane                 # cover-relative position
            before_i = (pos < rem).astype(jnp.int32)
            inside_i = ((pos >= rem) & (pos < total)).astype(jnp.int32)
            left = jnp.where((before_i != 0) |
                             ((inside_i != 0) & (gl_i != 0)), 1, 0)

            pnr = _excl_prefix_rights(left, C)       # rights before lane
            nlc = jnp.sum(left)
            nl_cnt = nl_cnt + nlc
            nrc = C - nlc

            lcomp, rcomp = _compact(payload, left, pnr, C,
                                    lead=fill_l & 127)

            fill_l, fl_l = _stage(stgl, lcomp, fill_l, nlc, C)
            fill_r, fl_r = _stage(stgr, rcomp, fill_r, nrc, C, mirrored=True)

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
                    wait_write()
                pk_l, gl_l = split_payload(stgl[:, 0:C])
                wb[:] = unpack_bins(pk_l)
                wg[:] = jax.lax.bitcast_convert_type(
                    jnp.concatenate(
                        [gl_l,
                         jnp.zeros((GH - ghi_live, C), jnp.int32)], axis=0),
                    jnp.float32)
                start_write(a0b * 128 + nfl * C)
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
            wait_write()

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
            wb[:] = unpack_bins(pk_f)
            wg[:] = jax.lax.bitcast_convert_type(
                jnp.concatenate(
                    [gl_f,
                     jnp.zeros((GH - ghi_live, C), jnp.int32)], axis=0),
                jnp.float32)
            start_write(a0b * 128 + nfl * C)
            wait_write()

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
                cb.start(); on_last(cg.start)
                cb.wait(); on_last(cg.wait)

            @pl.when(read_src)
            def _():
                pltpu.make_async_copy(
                    sp.at[:, pl.ds(0, C)], rs.at[slot],
                    sems.at[slot, 0]).wait()

            cur_p = rs[slot][0:P]                    # packed payload
            prv_p = rs[1 - slot][0:P]
            out_p = _slide(prv_p, cur_p, r0, C)
            pk_2, out_gl = split_payload(out_p)      # clean words + ghi
            valid = (lane >= lo) & (lane < hi)
            # wait the PREVIOUS window's deferred write before reusing
            # the staging buffers (destination windows are disjoint, so
            # the in-flight write never races this window's RMW read)
            @pl.when(j > 0)
            def _():
                wait_write()
            exg_i = jax.lax.bitcast_convert_type(exg[:], jnp.int32)
            wb[:] = unpack_bins(jnp.where(valid, pk_2, pack_bins(exb[:])))
            wg[:] = jax.lax.bitcast_convert_type(
                jnp.concatenate(
                    [jnp.where(valid, out_gl, exg_i[0:ghi_live]),
                     exg_i[ghi_live:GH]],
                    axis=0),
                jnp.float32)
            start_write(dwb * 128 + j * C)
            return 0

        jax.lax.fori_loop(0, n_d, body2, 0)

        @pl.when(n_d > 0)
        def _():
            wait_write()

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(NP,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3 +
                  [pl.BlockSpec(memory_space=pltpu.VMEM)],
        scratch_shapes=[
            pltpu.VMEM((2, GT, C), jnp.uint8),       # rb
            pltpu.VMEM((2, GH, C), jnp.float32),     # rg
            pltpu.VMEM((2, SCR, C), jnp.int32),      # rs
            pltpu.VMEM((P, 2 * C), jnp.int32),       # stgl
            pltpu.VMEM((P, 2 * C), jnp.int32),       # stgr
            pltpu.VMEM((GT, C), jnp.uint8),          # wb
            pltpu.VMEM((GH, C), jnp.float32),        # wg
            pltpu.VMEM((SCR, C), jnp.int32),         # wp
            pltpu.VMEM((GT, C), jnp.uint8),          # exb
            pltpu.VMEM((GH, C), jnp.float32),        # exg
        ] + ([pltpu.VMEM((2, 32, C), jnp.uint8)] if NP > 1 else []) + [
            pltpu.SemaphoreType.DMA((2, 5 if NP > 1 else 4)),
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


def cat_words(cat_set):
    """A (BF,) bool category set over a feature's bins (BF <= 256) as the
    ``CAT_WORDS`` i32 words of the scalar layout: bin b is bit b & 31 of
    word b >> 5."""
    bits = jnp.pad(jnp.asarray(cat_set, jnp.bool_),
                   (0, 32 * CAT_WORDS - cat_set.shape[0]))
    shifted = (bits.reshape(CAT_WORDS, 32).astype(jnp.uint32)
               << jnp.arange(32, dtype=jnp.uint32))
    return jax.lax.bitcast_convert_type(
        jnp.sum(shifted, axis=1, dtype=jnp.uint32), jnp.int32)


def make_scalars(start, cnt, col, bstart, isb, nb, dbin, mtype, thr, dl,
                 is_cat=False, cat_set=None):
    """Pack the kernel's scalar operand (all traced i32); ``cat_set`` is
    the (BF,) bool category set of a categorical split."""
    start = jnp.asarray(start, jnp.int32)
    a0b = jax.lax.shift_right_logical(start, 7)
    rem = start - a0b * 128
    vals = [a0b, rem, cnt, col, bstart, isb, nb, dbin, mtype, thr, dl,
            is_cat]
    words = (jnp.zeros((CAT_WORDS,), jnp.int32) if cat_set is None
             else cat_words(cat_set))
    return jnp.concatenate(
        [jnp.stack([jnp.asarray(v).astype(jnp.int32) for v in vals]),
         words])
