"""The Expo deployment's table: the 2009 ASA Data Expo airline on-time
record as the reference's CPU study runs it (docs/Experiments.rst: binary,
departure delayed by 15 minutes or more), its 8 native columns with the 6
categorical ones as integer codes, made from `--seed`.

Columns, in order (`categorical_feature` = 0, 1, 2, 4, 5, 6):

  0 Month          12 codes, 1-12, uniform
  1 DayofMonth     31 codes, 1-31, uniform
  2 DayOfWeek       7 codes, 1-7, uniform
  3 DepTime        hhmm, 1-2400, from a daily profile of departures
  4 UniqueCarrier  20 codes, Zipf(1.0)
  5 Origin        300 codes, Zipf(1.5)
  6 Dest          300 codes, Zipf(1.5)
  7 Distance       miles, 30-4,983, log-normal

The label is a logistic draw from the seed: per-category effects (a
seasonal profile over the months, a weekly one, normal effects of the
carriers and airports fixed by their rank of popularity, whose codes the
seed draws), an hour-of-day profile of departure delays and the distance,
through an intercept that puts about 20% of rows positive.  The real file is not at hand, so every
size above is assumed (the configuration's `assumed` lists them).

Rows are made in chunks straight into one f32 array, each chunk with a
generator of its own keyed by (seed, chunk index), as datagen.py does: the
same seed gives the same table whatever the thread count.
"""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNK_ROWS = 1 << 20
CATEGORICAL = [0, 1, 2, 4, 5, 6]
CARRIERS, AIRPORTS = 20, 300
INTERCEPT = -2.05


def _zipf_cdf(n, s):
    p = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
    return np.cumsum(p / p.sum())


def effects(seed):
    """The label's effects and the codes' order of popularity.  The effect
    of each code is fixed by its rank of popularity (drawn once, from a
    generator of its own), so every seed poses the same problem up to a
    relabelling of the codes, and the trees of two seeds cost the same
    work; the seed draws which carrier and airport codes are the popular
    ones."""
    fixed = np.random.default_rng(0)

    def centred(scale, n, s):
        # with the mean over the rows at zero
        e = fixed.normal(0.0, scale, n)
        pmf = np.diff(_zipf_cdf(n, s), prepend=0.0)
        return e - pmf @ e

    months = np.arange(1, 13)
    season = 0.25 * np.cos(2 * np.pi * (months - 7) / 12) \
        + 0.2 * (months == 12)
    rng = np.random.default_rng([int(seed), 0])
    return {
        "month": season,
        "dom": centred(0.08, 31, 0.0),
        "dow": np.array([0.15, -0.05, -0.1, 0.1, 0.2, -0.2, 0.0]),
        "carrier": centred(0.35, CARRIERS, 1.0),
        "origin": centred(0.45, AIRPORTS, 1.5),
        "dest": centred(0.35, AIRPORTS, 1.5),
        "carrier_code": rng.permutation(CARRIERS),
        "airport_code": rng.permutation(AIRPORTS),
    }


def make_table(seed, rows, threads=None):
    """(X, y): `rows` x 8 f32 columns in the module's order and a 0/1 f32
    label, from `seed`."""
    eff = effects(seed)
    carrier_cdf = _zipf_cdf(CARRIERS, 1.0)
    airport_cdf = _zipf_cdf(AIRPORTS, 1.5)
    X = np.empty((rows, 8), dtype=np.float32)
    y = np.empty(rows, dtype=np.float32)

    def fill(c):
        a, b = c * CHUNK_ROWS, min(rows, (c + 1) * CHUNK_ROWS)
        n = b - a
        rng = np.random.default_rng([int(seed), 1, c])
        month = rng.integers(1, 13, n)
        dom = rng.integers(1, 32, n)
        dow = rng.integers(1, 8, n)
        # departures: a morning and an evening bank over 5:00-23:00, a
        # few overnight; minute of the day 1-1440 as hhmm
        minute = np.where(
            rng.random(n) < 0.5, rng.normal(9.5 * 60, 150, n),
            rng.normal(17.5 * 60, 170, n))
        minute = np.clip(np.rint(minute), 1, 1440).astype(np.int64)
        dep = (minute // 60) * 100 + minute % 60
        rank_c = np.searchsorted(carrier_cdf, rng.random(n), side="right")
        rank_o = np.searchsorted(airport_cdf, rng.random(n), side="right")
        rank_d = np.searchsorted(airport_cdf, rng.random(n), side="right")
        rank_c = np.minimum(rank_c, CARRIERS - 1)
        rank_o = np.minimum(rank_o, AIRPORTS - 1)
        rank_d = np.minimum(rank_d, AIRPORTS - 1)
        dist = np.clip(np.rint(np.exp(rng.normal(6.5, 0.65, n))), 30, 4983)
        hour = minute / 60.0
        logit = (INTERCEPT + eff["month"][month - 1] + eff["dom"][dom - 1]
                 + eff["dow"][dow - 1] + eff["carrier"][rank_c]
                 + eff["origin"][rank_o] + eff["dest"][rank_d]
                 + 0.07 * np.clip(hour - 6.0, -3.0, 14.0)
                 + 0.1 * np.log(dist / 700.0))
        X[a:b, 0] = month
        X[a:b, 1] = dom
        X[a:b, 2] = dow
        X[a:b, 3] = dep
        X[a:b, 4] = eff["carrier_code"][rank_c]
        X[a:b, 5] = eff["airport_code"][rank_o]
        X[a:b, 6] = eff["airport_code"][rank_d]
        X[a:b, 7] = dist
        y[a:b] = logit + rng.logistic(0.0, 1.0, n) > 0

    chunks = range(-(-rows // CHUNK_ROWS))
    threads = threads or min(8, os.cpu_count() or 1)
    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(fill, chunks))
    return X, y


def make(seed, rows, holdout, cfg):
    """(X, y, dataset_kwargs): `rows` training rows then `holdout` held-out
    ones, and the categorical columns for `lgb.Dataset`."""
    X, y = make_table(seed, rows + holdout)
    return X, y, {"categorical_feature": list(CATEGORICAL)}
