"""Histogram construction on TPU.

TPU-native replacement for the reference histogram kernels
(src/io/dense_bin.hpp ConstructHistogram, src/treelearner/cuda/
cuda_histogram_constructor.cu).  TPUs have no fast scatter-add, so the
(rows x groups) -> (groups x bins) accumulation is reformulated as a one-hot
MXU matmul.  Rows are kept *physically partitioned by leaf* (see
models/learner.py), so a leaf's histogram reads one contiguous column slice —
no gathers touch HBM on the hot path.

Row-payload layout is TRANSPOSED: the binned matrix is (G, N_pad) and the
packed (grad, hess, rowid) payload is (3, N_pad), with ROWS ON THE MINOR
(lane) axis.  With the natural (N, G) orientation XLA prefers column-major
for the big buffers (G < 128 lanes would waste 4.5x footprint row-major)
while the partition's row-gather loops demand row-major — the disagreement
materialized as full-buffer transpose copies inside the tree-build while
loop, ~60% of its wall clock.  (G, N) row-major is the same physical bytes
as (N, G) column-major, so every consumer now agrees with the layout XLA
wants and the copies vanish.

``leaf_hist_slice`` is a pure-XLA chunked einsum that runs everywhere: it
is what the CPU, the parallel learners, categorical data, quantized
gradients and the banded chunk policy run, and the oracle of the kernels.
Where the Pallas partition kernel runs (TPU, u8 bins, serial, numerical
features) and the gradients are f32, ``models/plan.py`` gives a leaf's
histogram to ONE Pallas kernel launch instead (``lgbm_histogram``,
ops/histogram_pallas.py, PR 32): compiled for the v5e this loop is a serial
chain of 10-13 device operations per 4096-row chunk, each a round trip
through VMEM, and with its matmuls taken out it still costs 9.9 of its
19.1 us a chunk at 255 bins and 7.7 of 10.7 at 63 (PERF.md section 6).  The mega-kernel (ops/split_megakernel_pallas.py),
below 2^24 rows, accumulates both children's histograms itself and leaves
only the root to either form.  ``hist_tail`` is shared by both.

The contraction layout batches ``gblock`` feature groups into the matmul N
dimension — out[(j),(g,b)] = sum_c gh[j,c] * (bins[g,c]==b) — because the
left operand (grad/hess) is shared across features.  This keeps the MXU's
N dimension wide instead of the naive per-feature (C,B)@(B,2) shape whose
N=2 wastes 126/128 lanes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import F32_DOT_PRECISION


def linear_moment_planes(feat_hist, rep_vals):
    """Per-bin linear moment planes (Σx·g, Σx·h, Σx·x·h) of a leaf,
    derived from its already-accumulated feature-view histogram
    (linear_tree_mode=leafwise_gain).

    The naive plan — ride extra weighted columns (x·g, x·h, x²·h) in
    the one-hot MXU matmuls above — is never necessary: the binned
    regressor is a PER-BIN CONSTANT, so within bin b of feature f

        Σ_{i in bin b} x_i·g_i = rep[f, b] · Σ_{i in bin b} g_i
                               = rep[f, b] · hist[f, b, 0]

    and likewise for the h-moments.  The moments are therefore exact
    rank-1 scalings of the (F, BF, 2) histogram by the representative
    value table (ops/binning.py:bin_rep_values) — zero extra matmul
    throughput, zero extra histogram state, and the parent-minus-child
    subtraction trick holds automatically (the derivation is linear in
    the histogram).  ``rep_vals`` is (F, BF) f32 with 0.0 at the
    NaN/zero-missing bins, which is what lets both split-scan
    directions share one set of moment prefix sums (see
    ops/split.py:find_best_split_linear).

    Returns (3, F, BF): [Σx·g, Σx·h, Σx·x·h].
    """
    xg = rep_vals * feat_hist[..., 0]
    xh = rep_vals * feat_hist[..., 1]
    return jnp.stack([xg, xh, rep_vals * xh])


def leaf_hist_slice(part_bins, part_ghi, start, cnt, *,
                    num_bins: int, row_chunk: int,
                    gblock: int = 0, dtype=jnp.float32, vary=lambda x: x,
                    num_groups: int = 0, flat_geom=None, cover=None):
    """(G, B, 2) histogram of the contiguous partitioned rows
    [start, start+cnt) of the (G, N_pad) binned matrix with matching
    (>=2, N_pad) packed (grad, hess, ...) rows; rows beyond ``cnt``
    inside the last chunk are masked via zeroed grad/hess.

    ``cover`` overrides the chunk trip count (the leaf-size-adaptive
    policy passes the cover length — 0 skips the pass outright, which
    is how a zero-trip band variant costs nothing at runtime).

    Digit-decomposed one-hot accumulation: onehot_B(x) factors as
    onehot_hi(x >> 4) (x) onehot_16(x & 15), so the per-chunk histogram is a
    batched (BH*2, C) @ (C, 16) matmul per feature block — one-hot
    GENERATION drops from O(C*B) to O(C*(BH+16)) elements per feature,
    which is what bounds the naive formulation on the VPU (the MXU matmul
    itself streams at full speed either way).  This is the TPU replacement
    for the reference's scalar scatter-adds (dense_bin.hpp
    ConstructHistogram) and CUDA shared-memory atomics
    (cuda_histogram_constructor.cu).
    """
    G, Np = part_bins.shape
    if num_groups:      # buffer may be sublane-padded for the Pallas
        G = num_groups  # partition kernel's DMA tiling; ignore pad rows
    C = row_chunk
    B = num_bins
    BH = (B + 15) // 16          # high-digit cardinality
    Bp = BH * 16
    if gblock <= 0:
        # keep the per-block intermediates in VMEM: the low-digit one-hot is
        # (gblock, C, 16) and the WEIGHTED high-digit buffer is
        # (gblock, C, 2*BH) — budget both
        gblock = max(1, (4 * 1024 * 1024) // (C * (16 + 2 * BH) * 4))
    nblk = (G + gblock - 1) // gblock
    Gp = nblk * gblock
    n_chunks = (cnt + C - 1) // C if cover is None else cover
    iota_hi = jax.lax.broadcasted_iota(jnp.int32, (1, 1, BH), 2)
    iota_lo = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 16), 2)

    def body(ci, acc):
        row0 = start + ci * C
        bins = jax.lax.dynamic_slice(
            part_bins, (0, row0), (G, C)).astype(jnp.int32)
        gh3 = jax.lax.dynamic_slice(
            part_ghi, (0, row0), (part_ghi.shape[0], C))
        g = gh3[0]
        h = gh3[1]
        if Gp > G:
            bins = jnp.pad(bins, ((0, Gp - G), (0, 0)), constant_values=-1)
        valid = (ci * C + jax.lax.iota(jnp.int32, C)) < cnt
        gv = (g * valid).astype(dtype)[None, :, None]         # (1, C, 1)
        hv = (h * valid).astype(dtype)[None, :, None]
        out = []
        for i in range(nblk):
            blk = bins[i * gblock:(i + 1) * gblock, :]        # (gblk, C)
            hi = blk >> 4
            lo = blk & 15
            m_hi = hi[:, :, None] == iota_hi                  # (gblk, C, BH)
            oh_lo = (lo[:, :, None] == iota_lo).astype(dtype)  # (gblk, C, 16)
            # weighted high-digit one-hots for (grad, hess) side by side,
            # generated DIRECTLY from the comparison mask: materializing
            # the raw f32 oh_hi first costs ~28% of the whole pass
            # (measured; the generation traffic bounds this kernel)
            wg = jnp.concatenate([jnp.where(m_hi, gv, jnp.array(0, dtype)),
                                  jnp.where(m_hi, hv, jnp.array(0, dtype))],
                                 axis=2)
            out.append(jax.lax.dot_general(
                wg, oh_lo,
                dimension_numbers=(((1,), (1,)), ((0,), (0,))),
                precision=F32_DOT_PRECISION,
                preferred_element_type=jnp.float32))  # (gblk, 2*BH, 16)
        # ONE loop-carried array (a tuple of nblk carries costs nblk
        # body-level fusions per split in the outer tree loop)
        return acc + jnp.stack(out)

    acc = vary(jnp.zeros((nblk, gblock, 2 * BH, 16), jnp.float32))
    acc = jax.lax.fori_loop(0, n_chunks, body, acc)
    per = acc.reshape(Gp, 2 * BH, 16)[:G]               # block-major == G
    return hist_tail(per.reshape(G, 2, Bp), B, flat_geom)  # b = hi*16 + lo


def hist_tail(per, num_bins: int, flat_geom=None):
    """The (G, 2, Bp) planes of an accumulator (Bp >= num_bins, the bin
    axis flattened row-major over its digits) -> the (G, B, 2) histogram,
    or with ``flat_geom`` the (8, WL) lane-flattened (2, Gf, Bf) slot of
    the Pallas hist-state RMW kernel (ops/hist_state_pallas.py).  Shared
    by the XLA chunk loop above and ops/histogram_pallas.py."""
    G = per.shape[0]
    if flat_geom is not None:
        Gf, Bf, WL = flat_geom
        jg = jnp.moveaxis(per[:, :, :Bf], 1, 0)         # (2, G, <=Bf)
        jg = jnp.pad(jg, ((0, 0), (0, Gf - G), (0, Bf - jg.shape[2])))
        return jg.reshape(8, WL)
    return jnp.moveaxis(per[:, :, :num_bins], 1, 2)     # (G, B, 2)


def leaf_hist_banded(part_bins, part_ghi, start, cnt, *, num_bins: int,
                     policy, dtype=jnp.float32, vary=lambda x: x,
                     num_groups: int = 0):
    """Leaf-size-adaptive histogram (ops/chunkpolicy.py): the base-grid
    pass runs with a cover of 0 when a smaller band covers the leaf,
    and each smaller menu width runs a zero-or-one-trip single-chunk
    variant.  Exactly one variant executes per call; the others skip at
    runtime (dynamic trip counts — no ``lax.switch``, whose branch
    plumbing copies the multi-MB row buffers).

    Bit-identity: the selected small chunk accumulates the same live
    rows plus exactly-zero masked padding, and the band widths are
    capped at ``HIST_EXACT_MAX`` where the dot reduction provably
    groups the live prefix like the base width does (module docstring
    of chunkpolicy).  Summing the per-variant outputs (all-zero except
    the selected one) reproduces the base path's trailing zero-padding
    adds, so even signed-zero bins match.
    """
    from .chunkpolicy import note_variant
    sizes = policy.hist_sizes
    trips = policy.small_trips(cnt, sizes)
    note_variant("hist", sizes[0])
    out = leaf_hist_slice(part_bins, part_ghi, start, cnt,
                          num_bins=num_bins, row_chunk=sizes[0],
                          dtype=dtype, vary=vary, num_groups=num_groups,
                          cover=policy.base_cover(cnt, sizes))
    for w, trip in zip(sizes[1:], trips):
        note_variant("hist", w)
        out = out + leaf_hist_slice(
            part_bins, part_ghi, start, cnt, num_bins=num_bins,
            row_chunk=w, dtype=dtype, vary=vary, num_groups=num_groups,
            cover=trip)
    return out

