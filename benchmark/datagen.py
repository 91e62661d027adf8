"""The benchmark's own data: a binary-label table made from `--seed`.

`features` standard-normal f32 columns, a linear logit (x . w) * 0.5 plus
unit normal noise, label = logit > 0 (the shape of `bench.py`'s generator).
Rows are made in chunks straight into one f32 array, so no f64 copy of the
table ever exists, and every chunk has a generator of its own keyed by
(seed, chunk index): the same seed gives the same table whatever the thread
count.  The weight vector is one fixed profile of magnitudes (the normal
quantiles, norm about sqrt(features)) whose order and signs come from the
seed, so every seed poses the same problem up to a relabelling of the
features and the trees of two seeds cost the same work.
"""

import os
from concurrent.futures import ThreadPoolExecutor
from statistics import NormalDist

import numpy as np

CHUNK_ROWS = 1 << 20


def weights(seed, features):
    """The logit's weight vector: fixed magnitudes, seeded order and signs."""
    nd = NormalDist()
    mag = np.array([abs(nd.inv_cdf((i + 0.5) / features))
                    for i in range(features)], dtype=np.float64)
    rng = np.random.default_rng([int(seed), 0])
    sign = rng.choice(np.array([-1.0, 1.0]), size=features)
    return (mag[rng.permutation(features)] * sign).astype(np.float32)


def make_table(seed, rows, features, threads=None):
    """(X, y): `rows` x `features` f32 and a 0/1 f32 label, from `seed`."""
    w = weights(seed, features)
    X = np.empty((rows, features), dtype=np.float32)
    y = np.empty(rows, dtype=np.float32)

    def fill(c):
        a, b = c * CHUNK_ROWS, min(rows, (c + 1) * CHUNK_ROWS)
        rng = np.random.default_rng([int(seed), 1, c])
        rng.standard_normal(out=X[a:b], dtype=np.float32)
        noise = rng.standard_normal(b - a, dtype=np.float32)
        y[a:b] = (X[a:b] @ w) * np.float32(0.5) + noise > 0

    chunks = range(-(-rows // CHUNK_ROWS))
    threads = threads or min(8, os.cpu_count() or 1)
    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(fill, chunks))
    return X, y
