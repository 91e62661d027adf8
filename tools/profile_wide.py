"""What a wide bin matrix costs the two row kernels on a live TPU, and what
the other way of partitioning it would (PERF.md section 6, PR 35).

    python tools/profile_wide.py [features] [rows] [reps]

For a table of ``features`` u8 columns and one leaf of ``rows`` rows split
near the middle, in us a 4096-row chunk:

  partition, bins moved   ``lgbm_partition`` at each width of pass the
                          VMEM allows (``--pass-rows``: the plan's first),
                          the bins riding in the compaction's payload;
  partition, ids only     the same kernel over one 32-sublane tile, which
                          is what a partition of row ids and the f32 payload
                          alone costs at any width, plus what the histogram
                          then pays to fetch the smaller child's rows: one
                          XLA gather of ``rows / 2`` columns of the
                          (G32, N) matrix, and of rows of its (N, G32)
                          transpose;
  histogram               ``lgbm_histogram`` over the leaf whole, and over
                          256 equal leaves of it (what the launches and
                          part-filled chunks of small leaves cost).

Refuses anything but a TPU: a time from another backend is no reading.
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from lightgbm_tpu.ops import VMEM_LIMIT_BYTES
from lightgbm_tpu.ops.histogram_pallas import leaf_hist_pallas
from lightgbm_tpu.ops.partition_pallas import (make_scalars,
                                               partition_leaf_pallas,
                                               pass_rows_for, sc_rows_for)

GHL = 5      # the cells' payload: grad, hess, rowid, score, label


def timed(fn, *args):
    jax.block_until_ready(fn(*args))            # compile and warm
    t0 = time.time()
    jax.block_until_ready(fn(*args))
    return time.time() - t0


def table(G32, Np, seed=0):
    key = jax.random.PRNGKey(seed)
    pb = jax.random.randint(key, (G32, Np), 0, 255,
                            dtype=jnp.int32).astype(jnp.uint8)
    pg = jax.random.normal(jax.random.fold_in(key, 1), (8, Np), jnp.float32)
    return pb, pg


def partition_us(G, N, C, reps, pass_rows):
    G32 = -(-G // pass_rows) * pass_rows
    Np = C + ((N + C - 1) // C + 2) * C
    pb, pg = table(G32, Np)
    sp = jnp.zeros((sc_rows_for(pass_rows), Np), jnp.int32)
    scal = make_scalars(jnp.int32(C + 37), jnp.int32(N), G - 1, 0, 0, 255,
                        0, 0, 128, 1)

    @jax.jit
    def many(pb, pg, sp):
        def one(c, _):
            pb, pg, sp, nl = partition_leaf_pallas(
                *c, scal, row_chunk=C, ghi_live=GHL, pass_rows=pass_rows)
            return (pb, pg, sp), nl[0, 0]
        c, nls = jax.lax.scan(one, (pb, pg, sp), None, length=reps)
        return c[1][0, :8], jnp.sum(nls)

    return timed(many, pb, pg, sp) / reps / N * 4096e6


def gather_us(G, N, C, reps):
    """The smaller child's rows fetched by id from the unmoved matrix."""
    G32 = -(-G // 32) * 32
    pb, _ = table(G32, N)
    ids = jnp.sort(jax.random.permutation(jax.random.PRNGKey(2), N)[:N // 2])
    out = {}
    for name, mat, axis in (("columns of (G32, N)", pb, 1),
                            ("rows of (N, G32)", pb.T.copy(), 0)):
        @jax.jit
        def many(mat, ids):
            def one(i, acc):
                got = jnp.take(mat, ids + (i & 1), axis=axis, mode="clip")
                return acc + jnp.sum(got[:8, :8].astype(jnp.int32))
            return jax.lax.fori_loop(0, reps, one, jnp.int32(0))
        out[name] = timed(many, mat, ids) / reps / (N // 2) * 4096e6
    return out


def histogram_us(G, N, C, leaves):
    G32 = -(-G // 32) * 32
    Np = C + ((N + C - 1) // C + 2) * C
    pb, pg = table(G32, Np)
    kw = dict(num_bins=255, row_chunk=C, num_groups=G)
    per = N // leaves

    @jax.jit
    def whole(pb, pg):
        return leaf_hist_pallas(pb, pg, C + 37, jnp.int32(N), **kw)[:8]

    @jax.jit
    def many(pb, pg):
        def leaf(i, acc):
            return acc + leaf_hist_pallas(pb, pg, C + 37 + i * per,
                                          jnp.int32(per), **kw)[:8]
        return jax.lax.fori_loop(0, leaves, leaf,
                                 jnp.zeros((8, 255, 2), jnp.float32))

    return (timed(whole, pb, pg) / N * 4096e6,
            timed(many, pb, pg) / leaves * 1e6)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("features", type=int, nargs="?", default=2000)
    ap.add_argument("rows", type=int, nargs="?", default=600_000)
    ap.add_argument("reps", type=int, nargs="?", default=5)
    ap.add_argument("--pass-rows", type=int, nargs="*", default=None,
                    help="widths of pass to time (default: the plan's)")
    ap.add_argument("--leaves", type=int, default=256)
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        sys.exit(f"profile_wide: platform={jax.default_backend()}, need tpu "
                 "(no TPU, no time)")
    C = 4096
    G, N = args.features, args.rows
    print(f"features={G} rows={N} reps={args.reps} chunk={C} "
          f"{jax.devices()}", flush=True)
    plan_rows = pass_rows_for(G, C, VMEM_LIMIT_BYTES)
    for rows in args.pass_rows or [plan_rows]:
        us = partition_us(G, N, C, args.reps, rows)
        print(f"partition, bins moved, {rows:4d} sublanes a pass "
              f"({-(-G // rows)} passes): {us:9.3f} us a chunk", flush=True)
    us = partition_us(28, N, C, args.reps, 32)
    print(f"partition, ids only (one tile):                {us:9.3f} us a "
          "chunk of the parent", flush=True)
    for name, us in gather_us(G, N, C, args.reps).items():
        print(f"  + gather of the smaller child, {name}: {us:9.3f} us a "
              "chunk of the child", flush=True)
    whole, small = histogram_us(G, N, C, args.leaves)
    print(f"histogram, one leaf: {whole:9.3f} us a chunk; {args.leaves} "
          f"leaves of {N // args.leaves} rows: {small:9.3f} us a leaf",
          flush=True)


if __name__ == "__main__":
    main()
