"""Pallas TPU kernel for one leaf's histogram: one launch per leaf.

The XLA chunk loop of ops/histogram.py is a serial chain of 10-13 device
operations per 4096 rows, each a round trip through VMEM (PERF.md section
6, PR 32).  This kernel walks the same contiguous leaf range
``[start, start + cnt)`` of the (G32, N_pad) u8 binned matrix and the
(8, N_pad) f32 (grad, hess, ...) rows with the double-buffered window
DMAs of ops/partition_pallas.py, and keeps everything between the DMA and
the MXU in vregs and kernel scratch; the accumulator lives in VMEM for
the whole call and is written out once.

The sum it computes, per feature f and bin b = hi * LO + lo::

    hist[f, b] = sum_rows w[row] * (hi[f, row] == hi) * (lo[f, row] == lo)

is one bf16 MXU pass per feature group with f32 accumulation, and exact
in f32:

  * the weights (grad, hess; zero outside the leaf range) are split into
    ``NL`` bf16 limbs by masking mantissa bits, ``w = l0 + l1 + l2``
    exactly for NL = 3; a one-hot is exact in bf16; so every product is
    exact and only the f32 accumulation rounds, as in an f32 matmul at
    ``Precision.HIGHEST``.  The number of limbs follows
    ``F32_DOT_PRECISION`` at trace time: anything but HIGHEST runs ONE
    limb, the weights rounded to bf16, which is what the MXU's default
    precision does to an f32 operand (benchmark/control.py's fault);
  * the streamed (left) operand of a group holds, per feature, the
    ``2 * NL * BH`` rows ``limb_j * (hi == k)``; the stationary (right)
    operand holds the ``LO``-wide low-digit one-hots of ``P = 128 // LO``
    features side by side, so one 128-column MXU weight tile serves P
    features.  Only
    the P diagonal (feature x same feature) blocks of the (P*R, 128)
    product are read; the others are cross-feature sums nobody asked for,
    the price of a full tile.

Rows outside ``[start, start + cnt)`` inside the 128-aligned cover carry
zero weight by position; ``cnt == 0`` moves nothing and returns zeros.

On the v5e the kernel is bound by the MXU: its time is the matmuls' alone
(operands held constant, PERF.md section 6, PR 32), about 0.16 ns a
streamed row plus 4.4 ns a matmul, with the VPU's operand build (31,000
vector operations a 4096-row chunk at 255 bins) hidden under it: 5.4 us a
chunk at 255 bins and 3.1 at 63, against 19.1 and 10.7 for the XLA loop.
That is why the digits are what they are (``digits``): a wider low digit
means fewer streamed rows and more one-hot rows to build.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import F32_DOT_PRECISION, varying_like
from .partition_pallas import _cdiv

# lanes built and contracted per inner step: the accumulator is read and
# written once a step, so longer steps amortise it (PERF.md section 6, PR
# 32: 512 -> 1024 -> 2048 lanes bought 9% and 4% at 255 bins, 15% and 9%
# at 63)
SUB = 2048


def digits(num_bins: int):
    """(LO, BH): the low digit's width and the high digit's cardinality,
    a power of two.  A wider low digit streams fewer rows through the MXU
    (2 * NL * BH a feature and 128 lanes) and builds more one-hot rows on
    the VPU (LO of them).  On the v5e, us a 4096-row chunk at LO = 16 / 32
    / 64: 16.1 / 8.7 / 6.2 at 255 bins, 5.1 / 4.0 / 6.0 at 63, where the
    64-wide one-hot build is what binds (PERF.md section 6, PR 32)."""
    LO = 64 if num_bins > 128 else 32 if num_bins > 32 else 16
    BH = 1
    while BH * LO < num_bins:
        BH *= 2
    return LO, BH


def _num_limbs() -> int:
    return 3 if F32_DOT_PRECISION == jax.lax.Precision.HIGHEST else 1


def _limbs(x, n):
    """``n`` f32 rows, each exact in bf16, that sum to ``x`` exactly for
    n = 3 (8 + 8 + 8 significand bits, by truncation: the remainders are
    exact f32 differences).  n = 1 returns x itself: the cast to bf16
    rounds it."""
    if n == 1:
        return [x]
    out = []
    for _ in range(n - 1):
        top = jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(x, jnp.int32) & jnp.int32(-65536),
            jnp.float32)
        out.append(top)
        x = x - top
    return out + [x]


def layout(num_bins: int, num_groups: int, nl: int):
    """Static geometry of the accumulator: (LO, BH, P, R, NG)."""
    LO, BH = digits(num_bins)
    P = 128 // LO
    R = -(-(2 * nl * BH) // 8) * 8
    NG = -(-num_groups // P)
    return LO, BH, P, R, NG


# the kernel walks the features one u8 sublane tile at a time
TILE = 32


def tiles(num_bins: int, num_groups: int):
    """(T, NGB): the feature tiles the kernel loops over and the MXU
    groups of one tile's accumulator block.  A tile is the ``TILE`` u8
    sublanes of one window DMA, ``TILE // P`` groups; a matrix of one
    tile keeps exactly its own groups (the 28-feature shape: 14 of 16)."""
    LO, _ = digits(num_bins)
    P = 128 // LO
    return -(-num_groups // TILE), min(TILE // P, -(-num_groups // P))


def vmem_bytes(row_chunk: int, num_bins: int, num_groups: int) -> int:
    """Scoped VMEM of ``lgbm_histogram``: the scratch below, the
    pipelined accumulator block twice, and one inner step's two MXU
    operands in f32 and bf16.  It does not grow with the number of
    feature tiles."""
    LO, BH, P, R, _ = layout(num_bins, num_groups, 3)
    _, NGB = tiles(num_bins, num_groups)
    C = row_chunk
    return (2 * TILE * C + 2 * 8 * C * 4 + TILE * C * 4 + R * C * 4
            + 2 * NGB * P * R * 128 * 4
            + (P * R + 128) * min(SUB, C) * 6)


def leaf_hist_acc_pallas(part_bins, part_ghi, start, cnt, *, num_bins: int,
                         row_chunk: int, num_groups: int,
                         interpret: bool = False):
    """The (T * NGB, P*R, 128) f32 accumulator of the leaf range (see
    ``unpack_acc`` and ``tiles``).  part_bins: (G32, N_pad) u8, G32 a
    multiple of 32; part_ghi: (8, N_pad) f32 with grad and hess in rows
    0 and 1.

    The grid is the feature tiles: step ``t`` streams the leaf's rows of
    u8 sublanes ``[32 t, 32 t + 32)`` through the window DMAs, fills that
    tile's accumulator block and leaves it to the pipeline to write out,
    so the unrolled body (one tile's groups) and the VMEM held are those
    of one tile whatever the width; the (grad, hess) rows are read again
    for every tile.  A single tile is one trip."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    G32, Np = part_bins.shape
    GH = part_ghi.shape[0]
    G, C = num_groups, row_chunk
    assert GH == 8 and G32 % 32 == 0 and G <= G32, (G32, GH, G)
    assert C % 128 == 0 and Np % 128 == 0, (C, Np)
    nl = _num_limbs()
    LO, BH, P, R, NG = layout(num_bins, G, nl)
    T, NGB = tiles(num_bins, G)
    last = NG - (T - 1) * NGB        # the last tile's groups
    S = min(SUB, C)
    assert C % S == 0
    assert 8 % BH == 0, (num_bins, BH)   # u8 bins: BH <= 4
    lo_shift, hi_shift = LO.bit_length() - 1, BH.bit_length() - 1
    per_vreg = 8 // BH               # limbs in one 8-sublane plane

    def kernel(s_ref, pb_in, pg_in, acc, rb, rg, bi, lwb, sems):
        t = pl.program_id(0)
        row0 = pl.multiple_of(t * TILE, TILE)
        a0b, rem, cnt_ = s_ref[0], s_ref[1], s_ref[2]
        total = rem + cnt_
        n_chunks = jnp.where(cnt_ > 0, _cdiv(total, C), 0)
        acc[...] = jnp.zeros(acc.shape, jnp.float32)

        def copies(ci, slot):
            base = a0b * 128 + ci * C
            return (pltpu.make_async_copy(
                        pb_in.at[pl.ds(row0, TILE), pl.ds(base, C)],
                        rb.at[slot], sems.at[slot, 0]),
                    pltpu.make_async_copy(pg_in.at[:, pl.ds(base, C)],
                                          rg.at[slot], sems.at[slot, 1]))

        def start_read(ci, slot):
            for c in copies(ci, slot):
                c.start()

        @pl.when(n_chunks > 0)
        def _():
            start_read(0, 0)

        sub8 = jax.lax.broadcasted_iota(jnp.int32, (8, S), 0)
        hi_pat = sub8 & (BH - 1)
        lo_pat = [sub8 + 8 * q for q in range(LO // 8)]
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
        limb8 = jax.lax.broadcasted_iota(jnp.int32, (8, C), 0) >> hi_shift

        def chunk(ci, _):
            slot = jax.lax.rem(ci, 2)

            @pl.when(ci + 1 < n_chunks)
            def _():
                start_read(ci + 1, 1 - slot)
            for c in copies(0, slot):
                c.wait()

            bi[...] = rb[slot].astype(jnp.int32)
            pos = ci * C + lane
            inside = (pos >= rem) & (pos < total)
            zero = jnp.float32(0.0)
            rows = (_limbs(jnp.where(inside, rg[slot][0:1], zero), nl) +
                    _limbs(jnp.where(inside, rg[slot][1:2], zero), nl))
            # the limb planes: row r of lwb holds limb r // BH on every
            # lane, zero past the last limb
            for v in range(R // 8):
                plane = jnp.zeros((8, C), jnp.float32)
                for i, row in enumerate(rows[v * per_vreg:(v + 1) * per_vreg]):
                    plane = jnp.where(limb8 == i,
                                      jnp.broadcast_to(row, (8, C)), plane)
                lwb[8 * v:8 * v + 8, :] = plane

            def group(gi, off, nf):
                """Group ``gi`` of the tile: ``nf`` features' limb rows
                against their low-digit one-hots, one MXU pass."""
                lhs, rhs = [], []
                for f in range(gi * P, gi * P + nf):
                    b8 = jnp.broadcast_to(bi[f:f + 1, pl.ds(off, S)],
                                          (8, S))
                    hi = jax.lax.shift_right_logical(
                        b8, jnp.broadcast_to(lo_shift, b8.shape))
                    lo = b8 & (LO - 1)
                    m_hi = hi == hi_pat
                    for v in range(R // 8):
                        lhs.append(jnp.where(
                            m_hi, lwb[8 * v:8 * v + 8, pl.ds(off, S)],
                            zero))
                    for q in lo_pat:
                        rhs.append(jnp.where(lo == q, jnp.float32(1.0),
                                             zero))
                if nf < P:
                    rhs.append(jnp.zeros(((P - nf) * LO, S), jnp.float32))
                part = jax.lax.dot_general(
                    jnp.concatenate(lhs, axis=0).astype(jnp.bfloat16),
                    jnp.concatenate(rhs, axis=0).astype(jnp.bfloat16),
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                acc[gi, 0:nf * R, :] += part

            def sub(si, _):
                off = pl.multiple_of(si * S, S)
                for gi in range(NGB):
                    if gi < last:
                        # one tile: the matrix's last group may be short;
                        # among several it rides whole (its spare row of
                        # the u8 tile lands in a block nobody reads)
                        group(gi, off, min(P, G - gi * P) if T == 1 else P)
                    else:
                        # past the last tile's groups: the other tiles'
                        pl.when(t < T - 1)(
                            functools.partial(group, gi, off, P))
                return 0

            # the last chunk stops at the leaf's end, not the chunk's
            jax.lax.fori_loop(
                0, jnp.minimum(C // S, _cdiv(total - ci * C, S)), sub, 0)
            return 0

        jax.lax.fori_loop(0, n_chunks, chunk, 0)

    start = jnp.asarray(start, jnp.int32)
    a0b = jax.lax.shift_right_logical(start, 7)
    scalars = jnp.stack([a0b, start - a0b * 128,
                         jnp.asarray(cnt, jnp.int32)])
    return pl.pallas_call(
        kernel,
        out_shape=varying_like((T * NGB, P * R, 128), jnp.float32,
                               scalars, part_bins, part_ghi),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(T,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
            out_specs=pl.BlockSpec((NGB, P * R, 128),
                                   lambda t, s: (t, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, TILE, C), jnp.uint8),     # rb
                pltpu.VMEM((2, GH, C), jnp.float32),     # rg
                pltpu.VMEM((TILE, C), jnp.int32),        # bi
                pltpu.VMEM((R, C), jnp.float32),         # lwb
                pltpu.SemaphoreType.DMA((2, 2)),
            ]),
        interpret=interpret,
        name="lgbm_histogram",
    )(scalars, part_bins, part_ghi)


def unpack_acc(acc, *, num_bins: int, num_groups: int):
    """(T * NGB, P*R, 128) accumulator -> the (G, 2, Bp) planes
    ``leaf_hist_slice`` builds before its tail (b = hi * LO + lo): the
    diagonal block of every feature, its limbs summed smallest first."""
    nl = _num_limbs()
    LO, BH, P, R, _ = layout(num_bins, num_groups, nl)
    NG = acc.shape[0]
    blocks = acc.reshape(NG, P, R, P, LO)
    diag = jnp.stack([blocks[:, p, :2 * nl * BH, p] for p in range(P)],
                     axis=1)                          # (NG, P, 2*nl*BH, LO)
    limbs = diag.reshape(NG * P, 2, nl, BH * LO)[:num_groups]
    per = limbs[:, :, nl - 1]
    for j in range(nl - 2, -1, -1):
        per = per + limbs[:, :, j]
    return per                                        # (G, 2, BH*LO)


def leaf_hist_pallas(part_bins, part_ghi, start, cnt, *, num_bins: int,
                     row_chunk: int, num_groups: int, flat_geom=None,
                     interpret: bool = False):
    """``leaf_hist_slice`` by the kernel: the (G, B, 2) histogram of the
    leaf range, or its flat (8, WL) slot."""
    from .histogram import hist_tail
    acc = leaf_hist_acc_pallas(part_bins, part_ghi, start, cnt,
                               num_bins=num_bins, row_chunk=row_chunk,
                               num_groups=num_groups, interpret=interpret)
    return hist_tail(unpack_acc(acc, num_bins=num_bins,
                                num_groups=num_groups), num_bins, flat_geom)
