"""Per-chunk cost attribution for the Pallas partition / split-mega
kernels on a live TPU.  Times R back-to-back partitions of an N-row
leaf under each variant and several chunk sizes, with the
many-reps-in-one-program + single-materialization discipline (one
completion barrier per timed program).

Variants:
  full / nonet — the partition kernel with its two-way compaction and
    with none (the ablation produces a WRONG layout by design; it exists
    only here, for attribution);
  mega                  — the split mega-kernel (partition + BOTH
    children's histograms in one program): its per-chunk delta over
    "full" is the in-kernel histogram cost the e2e paired A/B
    (tools/ab_bench.py --b tpu_megakernel=pallas) trades against the
    per-split fixed work it removes.

Usage: python tools/profile_partition.py [N] [reps]
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from lightgbm_tpu.ops import partition_pallas as pp
from lightgbm_tpu.ops.partition_pallas import (partition_leaf_pallas,
                                               make_scalars, sc_rows_for)

_REAL_COMPACT = pp._compact


def _set_variant(variant):
    """Monkeypatch the compaction for A/B attribution (the ablated kernel
    produces a WRONG partition by design; it exists only here, never in
    the shipped kernel)."""
    if variant == "full":
        pp._compact = _REAL_COMPACT
    elif variant == "nonet":
        def nonet(payload, left, pnr, C, block=128, lead=0):
            wide = jnp.concatenate(
                [payload, jnp.zeros((payload.shape[0], block), jnp.int32)],
                axis=1)
            return wide, wide
        pp._compact = nonet

N = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
REPS = int(sys.argv[2]) if len(sys.argv) > 2 else 30
G32 = 32
GHL = 5      # bench-like payload: grad, hess, rowid, score, slw


def run(C, variant):
    Npad = ((N + 2 * C + 127) // 128) * 128 + 2 * C
    rng = np.random.RandomState(0)
    bins = rng.randint(0, 255, size=(G32, Npad)).astype(np.uint8)
    ghi = rng.normal(size=(8, Npad)).astype(np.float32)
    sc = np.zeros((sc_rows_for(G32), Npad), np.int32)
    scal = make_scalars(jnp.int32(C), jnp.int32(N), 3, 0, 0, 255, 0, 0,
                        128, 1)
    mega = variant == "mega"

    _set_variant(variant if variant in ("full", "nonet") else "full")

    def one(c, _):
        pb, pg, sp = c
        if mega:
            from lightgbm_tpu.ops.split_megakernel_pallas import (
                split_megakernel_pallas)
            pb, pg, sp, nl, acc = split_megakernel_pallas(
                pb, pg, sp, scal, row_chunk=C, num_bins=255,
                num_groups=28, ghi_live=GHL)
            return (pb, pg, sp), nl[0, 0] + jnp.sum(acc).astype(jnp.int32)
        pb, pg, sp, nl = partition_leaf_pallas(
            pb, pg, sp, scal, row_chunk=C, ghi_live=GHL)
        return (pb, pg, sp), nl[0, 0]

    @jax.jit
    def many(pb, pg, sp):
        (pb, pg, sp), nls = jax.lax.scan(
            one, (pb, pg, sp), None, length=REPS)
        return pb, pg, sp, jnp.sum(nls)

    args = (jnp.asarray(bins), jnp.asarray(ghi), jnp.asarray(sc))
    out = many(*args)
    float(out[3])                      # compile + settle
    t0 = time.time()
    out = many(*args)
    float(out[3])                      # host materialization barrier
    wall = time.time() - t0
    chunks = (N + C - 1) // C
    per_chunk = wall / REPS / chunks * 1e6
    print(f"C={C:5d} variant={variant:7s} wall={wall:.3f}s "
          f"per-pass={wall / REPS * 1e3:.2f}ms per-chunk={per_chunk:.2f}us")
    return per_chunk


if __name__ == "__main__":
    print(f"N={N} reps={REPS} device={jax.devices()}")
    from lightgbm_tpu.obs import benchio
    # trajectory wiring: one fingerprinted entry per run with every
    # surviving (chunk, variant) cell as a gated `_us` metric, so
    # on-hardware rounds of this harness are regression-gated too
    with benchio.abort_guard("profile_partition",
                             {"rows": N, "reps": REPS}) as guard:
        metrics = {}
        for C in (4096, 2048, 8192):
            for variant in ("full", "nonet", "mega"):
                try:
                    metrics[f"C{C}_{variant}_per_chunk_us"] = \
                        run(C, variant)
                except Exception as e:
                    print(f"C={C} variant={variant} FAILED: "
                          + str(e).split(chr(10))[0][:100])
        guard.write(dict(metrics), metrics=metrics, rows=N)
