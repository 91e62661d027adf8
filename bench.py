"""Benchmark: HIGGS-shape synthetic training throughput on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Baseline (BASELINE.md): the reference CPU learner trains HIGGS (10.5M rows x
28 features, num_leaves=255, 500 iterations) in 130.094 s on 2x E5-2690 v4.
The headline is MEASURED at the full 10.5M x 28 shape (u8-binned ~294 MB —
fits one chip's HBM with room): per-iteration wall-clock over REPEATS
timed blocks, median reported, spread recorded.  vs_baseline is
baseline_wall / (median_per_iter * 500)  (>1 means faster than the
reference CPU).

A smaller row count (BENCH_ROWS2, default 1M) adds an affine-fit
diagnostic t(N) = fixed + slope*N — diagnostics only, never the headline.

Runs only on a TPU backend (any other backend exits non-zero), in ONE
process: the kernel self-check (tpu_selfcheck.py) runs in-process first,
and any failed phase raises.
"""

import json
import os
import sys
import time

import numpy as np

ROWS = int(os.environ.get("BENCH_ROWS", 10_500_000))
ROWS2 = int(os.environ.get("BENCH_ROWS2", 1_000_000))
FEATURES = 28
NUM_LEAVES = int(os.environ.get("BENCH_LEAVES", 255))
ITERS = int(os.environ.get("BENCH_ITERS", 20))
REPEATS = int(os.environ.get("BENCH_REPEATS", 5))
BASELINE_WALL_S = 130.094
BASELINE_ROWS = 10_500_000
BASELINE_ITERS = 500


def _make_data(rows):
    rng = np.random.RandomState(7)
    X = rng.normal(size=(rows, FEATURES)).astype(np.float32)
    w = rng.normal(size=FEATURES)
    logit = X.dot(w) * 0.5
    y = (logit + rng.normal(size=rows) > 0).astype(np.float32)
    return X, y


def _train_blocks(lgb, rows, iters, repeats):
    X, y = _make_data(rows)
    params = {
        "objective": "binary",
        "num_leaves": NUM_LEAVES,
        "learning_rate": 0.1,
        "max_bin": 255,
        "verbosity": -1,
        "metric": "",
    }
    if os.environ.get("BENCH_CHUNK"):
        params["tpu_row_chunk"] = int(os.environ["BENCH_CHUNK"])
    ds = lgb.Dataset(X, label=y)
    t0 = time.time()
    ds.construct(params)
    construct_s = time.time() - t0

    import jax

    bst = lgb.Booster(params=params, train_set=ds)

    def sync():
        jax.block_until_ready(bst._gbdt.scores)

    # warmup: compile the tree builder (1 iteration)
    t0 = time.time()
    bst.update()
    sync()
    warm = time.time() - t0

    # one more untimed iteration: the first update after a scores read
    # compiles the program that resumes the physical row layout (16 s
    # cold at this shape, chip_smoke PR 21) — not part of a timed block
    bst.update()
    sync()

    blocks = []
    for _ in range(repeats):
        t0 = time.time()
        for _ in range(iters):
            bst.update()
        sync()
        blocks.append((time.time() - t0) / iters)
    return blocks, warm, construct_s


def _real_data_accuracy():
    """AUC parity on REAL data (round-4 verdict #3).  UCI HIGGS at 10.5M
    is not fetchable here (zero-egress env); the reference's bundled
    binary_classification example (7000 train / 500 test rows, a real
    HIGGS-derived sample per docs/) is the strongest real dataset
    available.  REF_* are the reference CLI's numbers measured LIVE on
    this machine (round 5: lightgbm built from /root/reference source,
    deterministic config = train.conf with sampling off)."""
    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils.textio import load_text_file

    REF_AUC = 0.828367        # live reference run, deterministic config
    REF_LOGLOSS = 0.509429
    base = None
    for root in ("/root/reference", os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            ".refbuild", "reftree")):
        cand = os.path.join(root, "examples", "binary_classification")
        if os.path.exists(os.path.join(cand, "binary.train")):
            base = cand
            break
    if base is None:
        return {"skipped": "reference example data not present"}
    tr = load_text_file(os.path.join(base, "binary.train"),
                        label_column="0")
    te = load_text_file(os.path.join(base, "binary.test"),
                        label_column="0")
    params = {"objective": "binary", "num_leaves": 63, "max_bin": 255,
              "learning_rate": 0.1, "min_data_in_leaf": 50,
              "min_sum_hessian_in_leaf": 5.0, "verbosity": -1,
              "metric": ""}
    bst = lgb.train(params, lgb.Dataset(tr.X, label=tr.label),
                    num_boost_round=100)
    p = np.asarray(bst.predict(te.X))
    y = np.asarray(te.label)
    order = np.argsort(p)
    ranks = np.empty(len(p))
    ranks[order] = np.arange(1, len(p) + 1)
    npos = y.sum()
    auc = (ranks[y > 0].sum() - npos * (npos + 1) / 2) / \
        (npos * (len(y) - npos))
    eps = 1e-12
    ll = float(-np.mean(y * np.log(p + eps)
                        + (1 - y) * np.log(1 - p + eps)))
    return {"dataset": "reference binary_classification (real HIGGS "
                       "sample, 7000/500)",
            "auc": round(float(auc), 6), "logloss": round(ll, 6),
            "ref_auc": REF_AUC, "ref_logloss": REF_LOGLOSS,
            "auc_vs_ref": round(float(auc) - REF_AUC, 6),
            "note": "500-row test; f32 summation-order variants of the "
                    "same config measured 0.8227-0.8293 here vs ref "
                    "0.8284 — deltas within that band are noise"}


def _baseline_configs_block():
    """BASELINE.md "target configs to reproduce" rows that were missing
    from the detail table (round-6 verdict ask #3): lambdarank
    (NDCG@10 + s/iter), GOSS+EFB regression, and multiclass +
    categorical — at small sizes.  Quality numbers are training-set
    diagnostics (synthetic data), not the published-dataset targets;
    they exist to catch per-config regressions in s/iter and learning
    behavior."""
    import time

    import numpy as np
    import lightgbm_tpu as lgb

    rows = int(os.environ.get("BENCH_CFG_ROWS", 40_000))
    iters = int(os.environ.get("BENCH_CFG_ITERS", 12))
    rng = np.random.RandomState(11)
    out = []

    def timed_train(params, ds):
        bst = lgb.Booster(params=params, train_set=ds)
        t0 = time.time()
        bst.update()
        warm = time.time() - t0
        t0 = time.time()
        for _ in range(iters - 1):
            bst.update()
        per = (time.time() - t0) / max(iters - 1, 1)
        return bst, round(per, 4), round(warm, 2)

    # 1) lambdarank (BASELINE.md target #3; Yahoo-LTR-shaped queries)
    qsize = 20
    nq = max(rows // qsize, 1)
    Xr = rng.normal(size=(nq * qsize, 30)).astype(np.float32)
    util = Xr[:, 0] + 0.5 * Xr[:, 1] + 0.2 * rng.normal(size=nq * qsize)
    rel = np.digitize(util, np.quantile(
        util, [0.5, 0.75, 0.9, 0.97])).astype(np.float64)
    params = {"objective": "lambdarank", "num_leaves": 63,
              "metric": "", "verbosity": -1}
    ds = lgb.Dataset(Xr, label=rel, group=np.full(nq, qsize))
    ds.construct(params)
    bst, per, warm = timed_train(params, ds)
    scores = np.asarray(bst.predict(Xr, raw_score=True))
    disc = 1.0 / np.log2(np.arange(2, 12))
    ndcg = []
    for qi in range(nq):
        sl = slice(qi * qsize, (qi + 1) * qsize)
        r = rel[sl]
        gains = (2.0 ** r[np.argsort(-scores[sl], kind="stable")][:10]
                 - 1) * disc
        ideal = (2.0 ** np.sort(r)[::-1][:10] - 1) * disc
        ndcg.append(gains.sum() / ideal.sum() if ideal.sum() > 0 else 1.0)
    out.append({"config": "lambdarank L63 (BASELINE target 3)",
                "rows": nq * qsize, "s_per_iter": per,
                "train_ndcg_at_10": round(float(np.mean(ndcg)), 5),
                "warmup_s": warm})

    # 2) GOSS + EFB regression (BASELINE.md target #2): sparse one-hot
    # blocks exercise the bundler, GOSS samples by gradient magnitude
    Xg = np.zeros((rows, 24), dtype=np.float32)
    Xg[:, :4] = rng.normal(size=(rows, 4))
    hot = rng.randint(0, 20, size=rows)
    Xg[np.arange(rows), 4 + hot] = 1.0
    yg = (Xg[:, 0] * 2 + hot * 0.1 +
          0.1 * rng.normal(size=rows)).astype(np.float64)
    params = {"objective": "regression", "num_leaves": 63,
              "data_sample_strategy": "goss", "enable_bundle": True,
              "metric": "", "verbosity": -1}
    ds = lgb.Dataset(Xg, label=yg)
    ds.construct(params)
    bst, per, warm = timed_train(params, ds)
    pred = np.asarray(bst.predict(Xg))
    out.append({"config": "GOSS+EFB regression L63 (BASELINE target 2)",
                "rows": rows, "s_per_iter": per,
                "train_l2": round(float(np.mean((pred - yg) ** 2)), 5),
                "warmup_s": warm})

    # 3) multiclass + categorical (BASELINE.md target #4)
    K = 5
    Xm = rng.normal(size=(rows, 12)).astype(np.float32)
    Xm[:, 3] = rng.randint(0, 30, size=rows)
    Xm[:, 7] = rng.randint(0, 8, size=rows)
    logits = rng.normal(size=(30, K))[Xm[:, 3].astype(int)] + \
        Xm[:, [0]] * rng.normal(size=(1, K))
    ym = np.argmax(logits + rng.gumbel(size=(rows, K)),
                   axis=1).astype(np.float64)
    params = {"objective": "multiclass", "num_class": K,
              "num_leaves": 31, "categorical_feature": [3, 7],
              "metric": "", "verbosity": -1}
    ds = lgb.Dataset(Xm, label=ym)
    ds.construct(params)
    bst, per, warm = timed_train(params, ds)
    prob = np.asarray(bst.predict(Xm))
    eps = 1e-12
    ll = float(-np.mean(np.log(
        prob[np.arange(rows), ym.astype(int)] + eps)))
    out.append({"config": "multiclass K5 + categorical (BASELINE "
                          "target 4)",
                "rows": rows, "s_per_iter": per,
                "train_multi_logloss": round(ll, 5),
                "warmup_s": warm})
    return out


def _multichip_block(n_dev):
    """Sharded fused data-parallel training over every local device:
    rows sharded on a 1-D mesh, one fused dispatch per iteration
    (models/boosting.py _setup_fused_sharded)."""
    import time as _time

    import jax
    import numpy as np
    import lightgbm_tpu as lgb

    rows = int(os.environ.get("BENCH_MC_ROWS", ROWS))
    iters = int(os.environ.get("BENCH_MC_ITERS", 10))
    X, y = _make_data(rows)
    params = {"objective": "binary", "num_leaves": NUM_LEAVES,
              "learning_rate": 0.1, "max_bin": 255, "verbosity": -1,
              "metric": "", "tree_learner": "data"}
    ds = lgb.Dataset(X, label=y)
    ds.construct(params)
    bst = lgb.Booster(params=params, train_set=ds)
    fused = bst._gbdt._fused is not None

    def sync():
        jax.block_until_ready(bst._gbdt.scores)

    bst.update()
    sync()
    t0 = _time.time()
    for _ in range(iters):
        bst.update()
    sync()
    per = (_time.time() - t0) / iters
    return {"devices": len(jax.devices()), "rows": rows, "iters": iters,
            "fused_sharded": fused,
            "s_per_iter": round(per, 4)}


def main():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import jax
    if jax.default_backend() != "tpu":
        sys.exit(f"bench.py measures the TPU; backend is "
                 f"{jax.default_backend()} — refusing to write a "
                 f"{jax.default_backend()} number under a device metric")
    import lightgbm_tpu as lgb
    from lightgbm_tpu import obs

    # telemetry at counters for the whole bench: the BENCH_obs.json
    # artifact below records compile events and memory peaks alongside
    # the headline (zero-HLO; span cost is noise at these block sizes)
    obs.get().enable("counters")

    # kernel self-check FIRST and IN-PROCESS (a chip belongs to one
    # process): the Pallas kernels' bug class (Mosaic addressing / DMA
    # windows) is invisible to the CPU suite, so the bench guards it
    if not os.environ.get("BENCH_SKIP_SELFCHECK"):
        import tpu_selfcheck
        if tpu_selfcheck.main() != 0:
            sys.exit("tpu_selfcheck failed")

    # export-on-failure guard: if the measured run dies below here, the
    # BENCH_obs artifact (and its BENCH_history.jsonl trajectory entry)
    # is still emitted with aborted=true, so a crashed round leaves
    # machine-readable evidence instead of a missing file
    from lightgbm_tpu.obs import benchio
    with benchio.abort_guard(
            "bench",
            {"rows": ROWS, "features": FEATURES, "leaves": NUM_LEAVES,
             "iters": ITERS, "repeats": REPEATS}) as obs_guard:
        _bench_body(lgb, obs_guard)


def _bench_body(lgb, obs_guard):
    blocks, warm, construct_s = _train_blocks(lgb, ROWS, ITERS, REPEATS)
    per_iter = float(np.median(blocks))

    mad = float(np.median(np.abs(np.asarray(blocks) - per_iter)))
    detail = {
        "iters_per_block": ITERS,
        "blocks_s_per_iter": [round(b, 4) for b in blocks],
        "mad_s_per_iter": round(mad, 5),
        "mad_pct": round(100.0 * mad / per_iter, 2),
        "spread_pct": round(100.0 * (max(blocks) - min(blocks))
                            / per_iter, 1),
        "warmup_compile_s": round(warm, 2),
        # dataset construction wall-clock (binning + EFB + device
        # ingest; ops/construct.py — see tools/profile_construct.py for
        # the per-stage host-loop/vectorized/device breakdown)
        "construct_s": round(construct_s, 2),
        "baseline_higgs_500iter_s": BASELINE_WALL_S,
        "per_iter_s": {str(ROWS): round(per_iter, 4)},
    }

    if ROWS == BASELINE_ROWS:
        est_500 = per_iter * BASELINE_ITERS
        detail["projection"] = "measured at the baseline row count"
    else:
        est_500 = per_iter * BASELINE_ITERS * (BASELINE_ROWS / ROWS)
        detail["projection"] = "linear in rows from one point"

    # real-data accuracy parity (round-4 verdict #3)
    if not os.environ.get("BENCH_SKIP_ACCURACY"):
        detail["real_data_accuracy"] = _real_data_accuracy()

    # BASELINE target-config rows (round-6 verdict ask #3): lambdarank,
    # GOSS+EFB, multiclass+categorical at CPU-feasible sizes
    if not os.environ.get("BENCH_SKIP_CONFIGS"):
        detail["baseline_configs"] = _baseline_configs_block()

    # on a host with more than one chip, also time the sharded fused
    # trainer over ALL local devices.  No-op on a single chip.
    import jax as _jax
    n_dev = len(_jax.devices())
    if n_dev > 1:
        detail["multichip"] = _multichip_block(n_dev)

    if ROWS2 and ROWS2 != ROWS:
        # affine-fit diagnostic from a second, smaller row count
        blocks2, _, _ = _train_blocks(lgb, ROWS2, max(ITERS, 20), 1)
        per_iter2 = float(np.median(blocks2))
        detail["per_iter_s"][str(ROWS2)] = round(per_iter2, 4)
        slope = (per_iter - per_iter2) / (ROWS - ROWS2)
        if slope < 0:       # measurement noise: don't let a negative slope
            slope = 0.0     # inflate the fixed cost past the measurements
            fixed = min(per_iter, per_iter2)
        else:
            fixed = max(per_iter2 - slope * ROWS2, 0.0)
        detail["fit"] = {"fixed_s": round(fixed, 4),
                         "slope_s_per_mrow": round(slope * 1e6, 4)}

    detail["extrapolated_higgs_500iter_s"] = round(est_500, 2)
    vs_baseline = BASELINE_WALL_S / est_500

    print(json.dumps({
        "metric": f"higgs_synth_{ROWS}x{FEATURES}_L{NUM_LEAVES}_wall_per_iter",
        "value": round(per_iter, 4),
        "unit": "s/iter",
        "vs_baseline": round(vs_baseline, 4),
        "detail": detail,
    }))

    # machine-readable perf artifact (schema: lightgbm-tpu/bench-obs/v3;
    # path overridable via BENCH_OBS_PATH) — the PERF.md round gets a
    # diffable companion with compile counts, memory peaks and a
    # fingerprinted BENCH_history.jsonl trajectory entry that
    # `tools/perfwatch.py check` gates future rounds against
    path = obs_guard.write(
        {"per_iter_s": round(per_iter, 4),
         "vs_baseline": round(vs_baseline, 4), "detail": detail},
        metrics={"per_iter_s": per_iter, "vs_baseline": vs_baseline,
                 "construct_s": construct_s, "warmup_compile_s": warm},
        rows=ROWS, features=FEATURES)
    print(f"wrote {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
