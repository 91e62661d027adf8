"""Pallas TPU kernel for the per-leaf histogram-state read-modify-write.

The tree loop keeps one (L+1)-slot histogram state and, per split, reads
the parent slot, subtracts the freshly built smaller-child histogram,
and writes both children back (the reference's histogram-subtraction
trick, src/treelearner/serial_tree_learner.cpp ConstructHistograms /
FeatureHistogram::Subtract).  Expressed as XLA dynamic-slice +
dynamic-update-slice on a (L+1, G, B, 2) state inside the tree while
loop, the compiler's memory-space assignment materializes TWO full
f32[L+1, G, B, 2] copies per split (contextual alternate-memory
prefetch around the dynamic slice — PERF.md round-4 "fixed-cost smoking
gun", ~7 ms/iter at 255 leaves).  This kernel performs the same
read+subtract+write as explicit one-row DMAs on a lane-flattened state,
with the state aliased in place, so the per-split cost is ~115 KB of
HBM traffic instead of two ~14.6 MB buffer copies.

State layout: (L+1, 8, WL) f32, each slot the row-major flattening of
the (2, Gp, Bp) histogram — [0] all grad rows, [1] all hess rows, padded
so a slot is exactly (8, WL) with WL a lane multiple (128).  Producers
(ops/histogram.py leaf_hist_slice(layout="flat")) emit this form
directly; the only consumer on the fast path is the Pallas split-search
kernel, which reads (G, BF) grad/hess planes — contiguous sub-blocks of
this layout.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def flat_geometry(num_groups: int, num_bins: int):
    """(Gp, Bp, WL) for the flat state: Bp = 16-digit-padded bin axis
    (matches the histogram producer's BH*16), Gp padded so one slot
    flattens to (8, WL) with WL % 128 == 0."""
    Bp = ((num_bins + 15) // 16) * 16
    Bp = max(Bp, 128)
    Gp = num_groups
    while (2 * Gp * Bp) % 1024:
        Gp += 1
    WL = (2 * Gp * Bp) // 8
    return Gp, Bp, WL


def vmem_bytes(num_groups: int, num_bins: int) -> int:
    """Scoped VMEM of ``lgbm_hist_state``: four slots whole (the parent's
    buffer, the smaller child's and the two children's; the subtraction's
    values live in them).  (Held against the v5e's compiler, PR 35: at
    255 bins 2048 and 2060 groups compile and 2080 do not, 16.07 MiB
    against 16; this reckons 16.00 MiB at 2048.  The plan takes the flat
    state only with the pair search, which stops near 230 features, so
    these bytes exclude nothing the search's have not.)"""
    return 4 * 8 * flat_geometry(num_groups, num_bins)[2] * 4


@functools.partial(jax.jit, static_argnames=("interpret",))
def hist_rmw_pallas(hist_state, hist_small, idx, *, interpret: bool = False):
    """In-place child-histogram update of the flat state.

    Args:
      hist_state: (L+1, 8, WL) f32, aliased to output 0.
      hist_small: (8, WL) f32 — the smaller child's histogram slot.
      idx: (4,) i32 — [parent_slot, write_a, write_b, small_is_left].

    Returns (state', left, right): state' aliased in place; left/right
    are (8, WL) VMEM copies of the two children for the split search.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    L1, S, WL = hist_state.shape
    assert S == 8 and WL % 128 == 0

    def kernel(idx_ref, state_in, small_ref, state_out, left_ref,
               right_ref, parent_buf, sems):
        bl = idx_ref[0]
        wa = idx_ref[1]
        wb = idx_ref[2]
        sil = idx_ref[3]
        rd = pltpu.make_async_copy(state_in.at[bl], parent_buf,
                                   sems.at[0])
        rd.start()
        rd.wait()
        small = small_ref[:]
        large = parent_buf[:] - small
        left_ref[:] = jnp.where(sil == 1, small, large)
        right_ref[:] = jnp.where(sil == 1, large, small)
        # children write-back; serialized — the trash-slot iteration has
        # wa == wb and two in-flight DMAs to one destination would race
        ca = pltpu.make_async_copy(left_ref, state_out.at[wa], sems.at[1])
        ca.start()
        ca.wait()
        cb = pltpu.make_async_copy(right_ref, state_out.at[wb], sems.at[1])
        cb.start()
        cb.wait()

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.VMEM)],
        scratch_shapes=[
            pltpu.VMEM((S, WL), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((L1, S, WL), jnp.float32),
            jax.ShapeDtypeStruct((S, WL), jnp.float32),
            jax.ShapeDtypeStruct((S, WL), jnp.float32),
        ],
        grid_spec=grid_spec,
        input_output_aliases={1: 0},
        interpret=interpret,
        name="lgbm_hist_state",
    )(idx.astype(jnp.int32), hist_state, hist_small)
