"""Stand-alone cost of one leaf's histogram on a live TPU: the form the
plan chooses there (``lgbm_histogram``, ops/histogram_pallas.py) and the XLA
chunk loop (ops/histogram.py) beside it, on one synthetic leaf.

    python tools/profile_hist.py [rows] [bins] [leaves]

Times one pass over ONE leaf of ``rows`` rows (ms, us a 4096-row chunk),
then a pass over ``leaves`` equal leaves in one program (what a launch
costs), and prints the largest gap between the two forms relative to the
largest bin.  The geometry is the benchmark cells': 28 features in 32 u8
sublanes, 4096-row chunks.  Off the TPU the kernel runs interpreted at a
toy size: the times then say nothing.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

G, G32 = 28, 32


def forms(B, C, interpret):
    from lightgbm_tpu.ops.histogram import leaf_hist_slice
    from lightgbm_tpu.ops.histogram_pallas import leaf_hist_pallas
    kw = dict(num_bins=B, row_chunk=C, num_groups=G)
    return {"pallas": lambda *a: leaf_hist_pallas(*a, interpret=interpret,
                                                  **kw),
            "xla": lambda *a: leaf_hist_slice(*a, **kw)}


def run(rows, B, leaves):
    on_tpu = jax.default_backend() == "tpu"
    C = 4096 if on_tpu else 256
    Np = C + ((rows + C - 1) // C + 2) * C
    key = jax.random.PRNGKey(B)
    pb = jax.random.randint(key, (G32, Np), 0, B,
                            dtype=jnp.int32).astype(jnp.uint8)
    pg = jax.random.normal(jax.random.fold_in(key, 1), (8, Np), jnp.float32)
    per = rows // leaves
    out, metrics = {}, {}
    for name, fn in forms(B, C, not on_tpu).items():
        one = jax.jit(fn)

        @jax.jit
        def many(pb, pg):
            def leaf(i, acc):
                return acc + fn(pb, pg, C + 37 + i * per, jnp.int32(per))
            return jax.lax.fori_loop(0, leaves, leaf,
                                     jnp.zeros((G, B, 2), jnp.float32))

        args = (pb, pg, jnp.int32(C + 37), jnp.int32(rows))
        out[name] = np.asarray(one(*args))             # compile and warm
        jax.block_until_ready(many(pb, pg))
        t0 = time.time()
        jax.block_until_ready(one(*args))
        whole = time.time() - t0
        t0 = time.time()
        jax.block_until_ready(many(pb, pg))
        split = time.time() - t0
        metrics[f"{name}_pass_ms"] = whole * 1e3
        metrics[f"{name}_us_per_chunk"] = whole / rows * 4096e6
        metrics[f"{name}_{leaves}_leaves_ms"] = split * 1e3
        print(f"{name:7s} one leaf {whole * 1e3:9.2f} ms "
              f"({whole / rows * 4096e6:6.2f} us a 4096-row chunk)   "
              f"{leaves} leaves {split * 1e3:9.2f} ms", flush=True)
    gap = float(np.abs(out["pallas"] - out["xla"]).max()
                / np.abs(out["xla"]).max())
    print(f"largest gap, relative to the largest bin: {gap:.2e}")
    metrics["rel_gap"] = gap
    return metrics


if __name__ == "__main__":
    rows = int(sys.argv[1]) if len(sys.argv) > 1 else 4_000_000
    bins = int(sys.argv[2]) if len(sys.argv) > 2 else 255
    leaves = int(sys.argv[3]) if len(sys.argv) > 3 else 256
    print(f"rows={rows} bins={bins} {jax.devices()}")
    from lightgbm_tpu.obs import benchio
    # one fingerprinted entry per run (aborted=true if a form dies), so
    # on-hardware rounds of this harness are regression-gated like every
    # other producer
    with benchio.abort_guard("profile_hist",
                             {"rows": rows, "bins": bins}) as guard:
        metrics = run(rows, bins, leaves)
        guard.write(dict(metrics), metrics=metrics, rows=rows)
