"""Interleaved A/B benchmark harness (round-5 measurement discipline).

Run-to-run variance between two processes (machine, compile, cache
state) can be larger than most single-change wins, so comparing two runs
taken at different times is blind to small deltas.  This harness removes
the between-run variance by interleaving the two arms WITHIN one process:

    settle, A, B, A, B, ... (>= 5 blocks per arm), one completion
    barrier per block

and reporting median + MAD per arm plus the paired per-position deltas
(slow drift hits adjacent A/B blocks equally, so the PAIRED delta
cancels it).

Arms differ by booster params only: land a perf change behind a config
flag, A/B it here, then flip the default.  Usage:

    python tools/ab_bench.py --rows 1000000 --iters 20 --blocks 5 \
        --b tpu_row_chunk=8192

With no --b overrides the two arms run identical code — the self-test
that the harness resolves below 2% (VERDICT round-4 ask #2).
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _parse_overrides(items):
    out = {}
    for it in items or []:
        k, v = it.split("=", 1)
        try:
            v = int(v)
        except ValueError:
            try:
                v = float(v)
            except ValueError:
                pass
        out[k] = v
    return out


def _write_obs(guard, args, tool, config, timings, health=None,
               metrics=None, rows=None, fingerprint_extra=None):
    """Drop the machine-readable BENCH_obs.json artifact (schema v3:
    hardware fingerprint + aborted flag) AND its BENCH_history.jsonl
    trajectory entry through the mode's abort guard (a lane that dies
    BEFORE writing still emits one with aborted=true): config +
    timings + the telemetry session's compile counts + memory peaks,
    so perf rounds have diffable, regression-gated artifacts, not just
    PERF.md prose.  ``metrics`` names the scalars the trajectory
    tracks per fingerprint; ``rows`` and ``fingerprint_extra`` let a
    lane fingerprint what it actually measured (the frontier/drift
    lanes do not train at the top-level --rows, and two different
    override experiments must never share a series)."""
    path = guard.write(timings, tool=tool, config=config, health=health,
                       metrics=metrics,
                       rows=rows if rows is not None
                       else getattr(args, "rows", None),
                       features=getattr(args, "features", None),
                       fingerprint_extra=fingerprint_extra)
    print(f"wrote {path}", file=sys.stderr)


def _fault_smoke(args, guard):
    """Robustness-cost smoke (`--fault`): the checkpoint guard rails
    must stay under `--max-overhead-pct` of training wall-clock at the
    bench config, and kill+resume must land.  Two interleaved full
    trainings per arm (no-checkpoint vs checkpointing) cancel slow
    drift like the A/B harness does; the report adds the resume
    wall-clock for a kill at 3/4 of the run."""
    import shutil
    import tempfile

    import lightgbm_tpu as lgb
    from lightgbm_tpu.robustness import faultinject

    rng = np.random.RandomState(7)
    X = rng.normal(size=(args.rows, args.features)).astype(np.float32)
    w = rng.normal(size=args.features)
    y = ((X.dot(w) * 0.5 + rng.normal(size=args.rows)) > 0).astype(np.float32)
    rounds = args.iters * args.blocks
    interval = args.ckpt_interval
    base = {"objective": "binary", "num_leaves": args.leaves,
            "learning_rate": 0.1, "max_bin": 255, "verbosity": -1,
            "metric": ""}
    ds = lgb.Dataset(X, label=y)
    ds.construct(base)
    work = tempfile.mkdtemp(prefix="ab-fault-")

    def run(extra=None, nbr=rounds, resume=False):
        t0 = time.time()
        bst = lgb.train({**base, **(extra or {})}, ds, num_boost_round=nbr,
                        resume=resume)
        return time.time() - t0, bst

    try:
        run(nbr=max(interval, 2))                 # compile warmup
        base_times, ckpt_times = [], []
        for rep in range(args.fault_reps):
            base_times.append(run()[0])
            ckpt_dir = os.path.join(work, f"ck{rep}")
            ckpt_times.append(run({"checkpoint_dir": ckpt_dir,
                                   "checkpoint_interval": interval})[0])
        t_base = float(np.median(base_times))
        t_ckpt = float(np.median(ckpt_times))
        overhead_pct = 100.0 * (t_ckpt - t_base) / t_base

        resume_dir = os.path.join(work, "resume")
        ck = {"checkpoint_dir": resume_dir, "checkpoint_interval": interval}
        kill_at = max((3 * rounds // 4) // interval * interval + 1, 1)
        try:
            with faultinject.injected(kill_at_iteration=kill_at):
                run(ck)
            raise SystemExit("--fault: kill injection did not fire")
        except faultinject.TrainingKilled:
            pass
        resume_s, bst = run(ck, resume=True)
        resumed_iters = rounds - (kill_at // interval) * interval
        report = {
            "fault_mode": True, "rows": args.rows, "rounds": rounds,
            "obs_artifact": args.obs_out,
            "checkpoint_interval": interval,
            "base_s": [round(t, 3) for t in base_times],
            "ckpt_s": [round(t, 3) for t in ckpt_times],
            "checkpoint_overhead_pct": round(overhead_pct, 2),
            "max_overhead_pct": args.max_overhead_pct,
            "overhead_ok": overhead_pct < args.max_overhead_pct,
            "resume_wallclock_s": round(resume_s, 3),
            "resumed_iterations": resumed_iters,
            "resumed_trees": int(bst.num_trees()),
        }
        print(json.dumps(report))
        _write_obs(guard, args, "ab_bench.fault",
                   {"rows": args.rows, "rounds": rounds,
                    "checkpoint_interval": interval},
                   report,
                   metrics={"base_train_s": t_base,
                            "ckpt_train_s": t_ckpt,
                            "resume_wallclock_s": resume_s},
                   fingerprint_extra={"rounds": rounds,
                                      "ckpt_interval": interval})
        if not report["overhead_ok"]:
            raise SystemExit(
                f"--fault: checkpoint overhead {overhead_pct:.2f}% exceeds "
                f"the {args.max_overhead_pct}% budget")
    finally:
        shutil.rmtree(work, ignore_errors=True)


def _drift_smoke(args, guard):
    """Continual-runtime smoke (`--drift`): inject a covariate shift,
    assert the rollback watchdog fires within `--rollback-within` ticks
    of a forced post-swap regression AND that the restored model serves
    bit-identically to the last-good pack; plus the full swap drill
    (detection within the window, kill-mid-retrain resumed from
    checkpoint, at most one compile per (kind, bucket) per swap); plus
    the ISSUE-9 health lane — the single-feature covariate-shift drill
    whose skew attribution must rank the planted feature #1, recorded
    in the BENCH_obs.json ``health`` section and asserted here."""
    import shutil
    import tempfile

    from lightgbm_tpu.continual import run_drift_drill
    from lightgbm_tpu.obs import benchio

    work = tempfile.mkdtemp(prefix="ab-drift-")
    try:
        swap = run_drift_drill("swap", rows=args.drift_rows, drift_at=4,
                               post_ticks=5, checkpoint_dir=work)
        roll = run_drift_drill("rollback", rows=args.drift_rows,
                               drift_at=3, post_ticks=5)
        attr = run_drift_drill("attribution", rows=args.drift_rows,
                               drift_at=4, post_ticks=6)
        rollback_delay = (None if roll.get("rollback_tick") is None else
                          roll["rollback_tick"] - roll["swap_tick"])
        health = {
            "planted_feature": attr.get("planted_feature"),
            "planted_rank": attr.get("planted_rank"),
            "skew_top": attr.get("skew_top"),
            "attribution_detect_tick": attr.get("detect_tick"),
        }
        report = {
            "drift_mode": True, "rows_per_tick": args.drift_rows,
            "detect_tick": swap.get("detect_tick"),
            "drift_at": swap.get("drift_at"),
            "detected_within_window": swap.get("detected_within_window"),
            "retrain_attempts": swap.get("retrain_attempts"),
            "swap_new_traces": swap.get("swap_new_traces"),
            "one_trace_per_key": swap.get("one_trace_per_key"),
            "swap_latency_s": round(
                float(swap.get("swap_latency_s") or 0.0), 4),
            "metric_recovered": swap.get("metric_recovered"),
            "rollback_delay_ticks": rollback_delay,
            "rollback_within": args.rollback_within,
            "rollback_ok": (rollback_delay is not None
                            and rollback_delay <= args.rollback_within),
            "post_rollback_parity": roll.get("pre_post_identical"),
            "health": health,
        }
        print(json.dumps(report))
        _write_obs(guard, args, "ab_bench.drift",
                   {"rows_per_tick": args.drift_rows,
                    "rollback_within": args.rollback_within},
                   report, health=health,
                   metrics={"swap_latency_s": report["swap_latency_s"]},
                   rows=args.drift_rows)
        problems = []
        if not report["detected_within_window"]:
            problems.append("regression not detected within the window")
        if swap.get("swap_tick") is None:
            problems.append("no hot-swap happened")
        if not report["one_trace_per_key"]:
            problems.append("swap cost more than one compile per "
                            "(kind, bucket)")
        if not report["rollback_ok"]:
            problems.append(
                f"rollback fired after {rollback_delay} tick(s), budget "
                f"{args.rollback_within}")
        if not report["post_rollback_parity"]:
            problems.append("post-rollback serving is not bit-identical "
                            "to the last-good pack")
        if health["planted_rank"] != 1:
            problems.append(
                "skew attribution ranked the planted feature "
                f"#{health['planted_rank']} (feature "
                f"{health['planted_feature']}), not #1")
        # the artifact this lane just wrote must satisfy the schema
        obs_path = args.obs_out or benchio.default_path()
        try:
            with open(obs_path) as fh:
                doc = json.load(fh)
            problems += [f"BENCH_obs: {p}"
                         for p in benchio.validate_bench_obs(doc)]
        except (OSError, ValueError) as exc:
            problems.append(f"BENCH_obs unreadable: {exc}")
        if problems:
            raise SystemExit("--drift: " + "; ".join(problems))
    finally:
        shutil.rmtree(work, ignore_errors=True)


def _frontier_smoke(args, guard):
    """Frontier-batching A/B (`--frontier`): K=1 oracle vs
    tpu_frontier_k=K at several row counts, asserting TREE BIT-IDENTITY
    between the arms after every timed iteration, and reporting per-arm
    per-iteration AFFINE FITS t(rows) = fixed + slope*rows — the
    frontier win is the FIXED (row-independent, per-split bookkeeping)
    term, so the headline number is the fixed-cost reduction.  Exits
    non-zero on any tree mismatch or when the reduction undercuts
    `--frontier-min-pct`."""
    import jax.numpy as jnp
    import lightgbm_tpu as lgb

    rows_list = [int(r) for r in args.frontier_rows.split(",") if r]
    if len(rows_list) < 2:
        raise SystemExit("--frontier needs >= 2 row counts for the "
                         "affine fit (--frontier-rows r1,r2[,...])")
    K = args.frontier_k
    base = {"objective": "binary", "num_leaves": args.frontier_leaves,
            "learning_rate": 0.1, "max_bin": 255, "verbosity": -1,
            "metric": ""}
    arms = {"A": {**base, "tpu_frontier_k": 1},
            "B": {**base, "tpu_frontier_k": K}}

    def trees(bst):
        return [ln for ln in bst.model_to_string().splitlines()
                if not ln.startswith("[")]

    def sync(bst):
        return float(jnp.sum(bst._gbdt.scores))

    per_rows = {}
    mismatch = []
    rng = np.random.RandomState(7)
    for rows in rows_list:
        X = rng.normal(size=(rows, args.features)).astype(np.float32)
        w = rng.normal(size=args.features)
        y = ((X.dot(w) * 0.5 + rng.normal(size=rows)) > 0
             ).astype(np.float32)
        ds = lgb.Dataset(X, label=y)
        ds.construct(arms["A"])
        boosters = {n: lgb.Booster(params=p, train_set=ds)
                    for n, p in arms.items()}
        for n in boosters:          # compile + settle
            boosters[n].update()
            sync(boosters[n])
        times = {"A": [], "B": []}
        for _ in range(args.frontier_blocks):
            for n in ("A", "B"):
                bst = boosters[n]
                t0 = time.time()
                for _ in range(args.frontier_iters):
                    bst.update()
                sync(bst)
                times[n].append((time.time() - t0) / args.frontier_iters)
        if trees(boosters["A"]) != trees(boosters["B"]):
            mismatch.append(rows)
        kb = boosters["B"]._gbdt.learner.plan.frontier_k
        per_rows[rows] = {
            "A_s_per_iter": round(float(np.median(times["A"])), 5),
            "B_s_per_iter": round(float(np.median(times["B"])), 5),
            "A_mad": round(float(np.median(np.abs(
                np.asarray(times["A"]) - np.median(times["A"])))), 5),
            "B_mad": round(float(np.median(np.abs(
                np.asarray(times["B"]) - np.median(times["B"])))), 5),
            "trees_identical": rows not in mismatch,
            "effective_k": int(kb),
        }

    rr = np.asarray(rows_list, np.float64)
    ta = np.asarray([per_rows[r]["A_s_per_iter"] for r in rows_list])
    tb = np.asarray([per_rows[r]["B_s_per_iter"] for r in rows_list])
    slope_a, fixed_a = np.polyfit(rr, ta, 1)
    slope_b, fixed_b = np.polyfit(rr, tb, 1)
    red = 100.0 * (1.0 - fixed_b / fixed_a) if fixed_a > 0 else 0.0
    report = {
        "frontier_mode": True, "k": K, "leaves": args.frontier_leaves,
        "features": args.features, "iters": args.frontier_iters,
        "blocks": args.frontier_blocks,
        "per_rows": per_rows,
        "fit_A": {"fixed_s_per_iter": round(float(fixed_a), 5),
                  "slope_s_per_mrow": round(float(slope_a * 1e6), 4)},
        "fit_B": {"fixed_s_per_iter": round(float(fixed_b), 5),
                  "slope_s_per_mrow": round(float(slope_b * 1e6), 4)},
        "fixed_reduction_pct": round(float(red), 2),
        "min_reduction_pct": args.frontier_min_pct,
        "trees_identical": not mismatch,
    }
    report["kernels_B"] = boosters["B"]._gbdt.kernel_plan()
    print(json.dumps(report))
    _write_obs(guard, args, "ab_bench.frontier",
               {"rows": rows_list, "k": K,
                "leaves": args.frontier_leaves,
                "iters": args.frontier_iters,
                "blocks": args.frontier_blocks}, report,
               metrics={"fixed_A_s": float(fixed_a),
                        "fixed_B_s": float(fixed_b),
                        "slope_A_s_per_mrow": float(slope_a * 1e6),
                        "slope_B_s_per_mrow": float(slope_b * 1e6)},
               rows=max(rows_list),
               fingerprint_extra={"frontier_rows": rows_list,
                                  "frontier_k": K,
                                  "num_leaves": args.frontier_leaves})
    problems = []
    if mismatch:
        problems.append(f"frontier trees NOT bit-identical to the K=1 "
                        f"oracle at rows={mismatch}")
    if args.frontier_min_pct is not None and red < args.frontier_min_pct:
        problems.append(
            f"fixed-cost reduction {red:.2f}% undercuts the "
            f"{args.frontier_min_pct}% bar")
    if problems:
        raise SystemExit("--frontier: " + "; ".join(problems))


def _chunk_smoke(args, guard):
    """Chunk-policy A/B (`--chunk`): tpu_chunk_policy=fixed vs adaptive
    at several (rows, num_leaves) regimes, asserting TREE BIT-IDENTITY
    between the arms after every timed block.  Reports per-regime
    speedups plus per-arm affine fits t(rows) = fixed + slope*rows over
    the small-leaf-heavy row counts (`--chunk-rows` at
    `--chunk-leaves`), and a separate large-uniform-leaf regime
    (`--chunk-uniform`) that must stay inside the perfwatch noise floor
    (adaptive bands are a no-op there — every leaf covers base chunks).
    Each regime also appends a `chunk_sweep` trajectory entry (winning
    base width + measured adaptive speedup under the knob-free
    host/shape fingerprint); the program reads none of them.  Exits
    non-zero on any tree mismatch, when the small-leaf speedup
    undercuts `--chunk-min-x`, or when the uniform regime regresses
    past the noise floor."""
    import jax.numpy as jnp
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import regress

    rows_list = [int(r) for r in args.chunk_rows.split(",") if r]
    if len(rows_list) < 2:
        raise SystemExit("--chunk needs >= 2 row counts for the affine "
                         "fit (--chunk-rows r1,r2[,...])")
    u_rows, u_leaves = (int(v) for v in args.chunk_uniform.split(":"))
    regimes = ([(r, args.chunk_leaves) for r in rows_list]
               + [(u_rows, u_leaves)])
    base = {"objective": "binary", "learning_rate": 0.1, "max_bin": 255,
            "verbosity": -1, "metric": ""}

    def trees(bst):
        return [ln for ln in bst.model_to_string().splitlines()
                if not ln.startswith("[")]

    def sync(bst):
        return float(jnp.sum(bst._gbdt.scores))

    per = {}
    mismatch = []
    rng = np.random.RandomState(7)
    for rows, leaves in regimes:
        X = rng.normal(size=(rows, args.features)).astype(np.float32)
        w = rng.normal(size=args.features)
        y = ((X.dot(w) * 0.5 + rng.normal(size=rows)) > 0
             ).astype(np.float32)
        p = {**base, "num_leaves": leaves}
        ds = lgb.Dataset(X, label=y)
        ds.construct(p)
        boosters = {n: lgb.Booster(params={**p, "tpu_chunk_policy": n},
                                   train_set=ds)
                    for n in ("fixed", "adaptive")}
        for n in boosters:          # compile warmup
            boosters[n].update()
            sync(boosters[n])
        for _ in range(2):          # settle (the _ab_body discipline)
            for n in boosters:
                boosters[n].update()
        for n in boosters:
            sync(boosters[n])
        times = {"fixed": [], "adaptive": []}
        for _ in range(args.chunk_blocks):
            for n in ("fixed", "adaptive"):
                bst = boosters[n]
                t0 = time.time()
                for _ in range(args.chunk_iters):
                    bst.update()
                sync(bst)
                times[n].append((time.time() - t0) / args.chunk_iters)
        key = f"{rows}x{leaves}"
        if trees(boosters["fixed"]) != trees(boosters["adaptive"]):
            mismatch.append(key)
        tf = float(np.median(times["fixed"]))
        ta = float(np.median(times["adaptive"]))
        pol = boosters["adaptive"]._gbdt.learner._chunk_policy
        per[key] = {
            "rows": rows, "leaves": leaves,
            "fixed_s_per_iter": round(tf, 5),
            "adaptive_s_per_iter": round(ta, 5),
            "speedup": round(tf / ta, 3) if ta > 0 else None,
            "menu": list(pol.sizes), "hist_menu": list(pol.hist_sizes),
            "adaptive_engaged": bool(pol.adaptive),
            "trees_identical": key not in mismatch,
        }
        # the measured verdict, keyed by the knob-free host/shape
        # fingerprint (hardware + shape band: the sweep's job is to
        # choose the knob, so the knob must not fork its series).  A
        # regime that failed bit-identity is recorded aborted
        # (evidence kept, the detector skips it).
        regress.append_entry(
            "chunk_sweep",
            {"best_row_chunk": int(pol.base),
             "adaptive_speedup": tf / ta if ta > 0 else 0.0},
            config={"rows": rows, "features": args.features,
                    "leaves": leaves},
            fingerprint_doc=regress.fingerprint(
                config={}, rows=rows, features=args.features),
            aborted=key in mismatch)

    rr = np.asarray(rows_list, np.float64)
    tf = np.asarray([per[f"{r}x{args.chunk_leaves}"]["fixed_s_per_iter"]
                     for r in rows_list])
    ta = np.asarray([per[f"{r}x{args.chunk_leaves}"]["adaptive_s_per_iter"]
                     for r in rows_list])
    slope_f, fixed_f = np.polyfit(rr, tf, 1)
    slope_a, fixed_a = np.polyfit(rr, ta, 1)
    small_speedups = [per[f"{r}x{args.chunk_leaves}"]["speedup"]
                      for r in rows_list]
    best_speedup = float(max(small_speedups))
    ukey = f"{u_rows}x{u_leaves}"
    u_ratio = (per[ukey]["adaptive_s_per_iter"]
               / per[ukey]["fixed_s_per_iter"])
    noise_floor = 1.0 + regress.FLOOR_PCT / 100.0
    report = {
        "chunk_mode": True, "features": args.features,
        "iters": args.chunk_iters, "blocks": args.chunk_blocks,
        "per_regime": per,
        "fit_fixed": {"fixed_s_per_iter": round(float(fixed_f), 5),
                      "slope_s_per_mrow": round(float(slope_f * 1e6), 4)},
        "fit_adaptive": {"fixed_s_per_iter": round(float(fixed_a), 5),
                         "slope_s_per_mrow": round(float(slope_a * 1e6),
                                                   4)},
        "small_leaf_speedups": small_speedups,
        "small_leaf_speedup_best": round(best_speedup, 3),
        "chunk_min_x": args.chunk_min_x,
        "uniform_ratio": round(float(u_ratio), 4),
        "uniform_noise_floor": round(noise_floor, 4),
        "trees_identical": not mismatch,
    }
    print(json.dumps(report))
    _write_obs(guard, args, "ab_bench.chunk",
               {"rows": rows_list, "leaves": args.chunk_leaves,
                "uniform": args.chunk_uniform,
                "iters": args.chunk_iters, "blocks": args.chunk_blocks},
               report,
               metrics={"fixed_arm_fixed_s": float(fixed_f),
                        "adaptive_arm_fixed_s": float(fixed_a),
                        "fixed_arm_slope_s_per_mrow": float(slope_f * 1e6),
                        "adaptive_arm_slope_s_per_mrow": float(
                            slope_a * 1e6),
                        "small_leaf_speedup": best_speedup,
                        "uniform_ratio": float(u_ratio)},
               rows=max(rows_list),
               fingerprint_extra={"chunk_rows": rows_list,
                                  "chunk_leaves": args.chunk_leaves,
                                  "uniform": args.chunk_uniform})
    problems = []
    if mismatch:
        problems.append(f"adaptive trees NOT bit-identical to the fixed "
                        f"grid at {mismatch}")
    if args.chunk_min_x is not None and best_speedup < args.chunk_min_x:
        problems.append(
            f"best small-leaf speedup {best_speedup:.2f}x undercuts "
            f"the {args.chunk_min_x}x bar")
    if u_ratio > noise_floor:
        problems.append(
            f"large-uniform-leaf regime regressed {100 * (u_ratio - 1):.1f}%"
            f" — past the {regress.FLOOR_PCT}% perfwatch noise floor")
    if problems:
        raise SystemExit("--chunk: " + "; ".join(problems))


def _linear_smoke(args, guard):
    """Piece-wise-linear trees A/B (`--linear`): constant leaves vs
    linear_tree refit vs linear_tree_mode=leafwise_gain (the in-search
    PL split gain) on a smooth synthetic, reporting per-arm wall clock
    and TREES-TO-TARGET-RMSE — the headline is how many fewer trees the
    linear arms need to reach the constant arm's final validation RMSE.
    Exits non-zero when the leafwise arm saves fewer than
    ``--linear-min-tree-save`` %% of the trees, or when it REGRESSES
    the constant arm's final accuracy (the PL gain must never lose to
    the model it generalizes)."""
    import time

    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import benchio

    rng = np.random.RandomState(11)
    n, f = args.linear_rows, args.linear_features
    X = rng.normal(size=(n, f)).astype(np.float32)
    # smooth target: one dominant linear direction + a nonlinearity in
    # a second feature — the regime linear_tree docs target and where
    # single-feature leaf models shine (with leafwise_gain the search
    # spends its splits on the sine because the leaf self-models
    # already carry the x0 ramp; constant trees must staircase it)
    y = (3.0 * X[:, 0] + np.sin(2.0 * X[:, 1])
         + 0.1 * rng.normal(size=n)).astype(np.float32)
    cut = int(n * 0.75)
    Xtr, Xva, ytr, yva = X[:cut], X[cut:], y[:cut], y[cut:]
    arms = {
        "constant": {},
        "refit": {"linear_tree": True, "linear_tree_mode": "refit"},
        "leafwise_gain": {"linear_tree": True,
                          "linear_tree_mode": "leafwise_gain"},
    }
    out = {}
    for name, extra in arms.items():
        p = {"objective": "regression", "metric": "rmse",
             "num_leaves": args.linear_leaves, "learning_rate": 0.1,
             "verbosity": -1, **extra}
        ds = lgb.Dataset(Xtr, label=ytr)
        vds = lgb.Dataset(Xva, label=yva, reference=ds)
        hist = {}
        t0 = time.perf_counter()
        lgb.train(p, ds, num_boost_round=args.linear_iters,
                  valid_sets=[vds], valid_names=["va"],
                  callbacks=[lgb.record_evaluation(hist)])
        wall = time.perf_counter() - t0
        curve = [float(v) for v in hist["va"]["rmse"]]
        out[name] = {"wall_s": round(wall, 3),
                     "final_rmse": round(curve[-1], 6),
                     "curve": [round(v, 6) for v in curve]}

    target = out["constant"]["final_rmse"]

    def trees_to(curve):
        for i, v in enumerate(curve):
            if v <= target:
                return i + 1
        return None

    report = {"linear_mode": True, "rows": n, "features": f,
              "leaves": args.linear_leaves, "iters": args.linear_iters,
              "target_rmse": target}
    for name in arms:
        t = trees_to(out[name]["curve"])
        out[name]["trees_to_target"] = t
        report[name] = {k: out[name][k] for k in
                        ("wall_s", "final_rmse", "trees_to_target")}
    lw = out["leafwise_gain"]["trees_to_target"]
    save_pct = (None if lw is None else
                round(100.0 * (1.0 - lw / args.linear_iters), 1))
    report["leafwise_tree_save_pct"] = save_pct
    print(json.dumps(report))
    _write_obs(guard, args, "ab_bench.linear",
               {"rows": n, "features": f, "leaves": args.linear_leaves,
                "iters": args.linear_iters},
               report,
               metrics={
                   "constant_wall_s": out["constant"]["wall_s"],
                   "refit_wall_s": out["refit"]["wall_s"],
                   "leafwise_wall_s": out["leafwise_gain"]["wall_s"],
                   "leafwise_final_rmse":
                       out["leafwise_gain"]["final_rmse"],
                   "leafwise_trees_to_target": float(lw or -1),
               },
               rows=n,
               fingerprint_extra={"lane": "linear",
                                  "linear_leaves": args.linear_leaves,
                                  "linear_iters": args.linear_iters})
    problems = []
    if lw is None:
        problems.append("leafwise_gain never reached the constant "
                        "arm's final RMSE")
    elif save_pct < args.linear_min_tree_save:
        problems.append(
            f"leafwise_gain needed {lw}/{args.linear_iters} trees "
            f"({save_pct}% saved) — under the "
            f"{args.linear_min_tree_save}% tree-save bar")
    if (out["leafwise_gain"]["final_rmse"]
            > out["constant"]["final_rmse"] * 1.001):
        problems.append(
            "accuracy regression: leafwise_gain final RMSE "
            f"{out['leafwise_gain']['final_rmse']} vs constant "
            f"{out['constant']['final_rmse']}")
    obs_path = args.obs_out or benchio.default_path()
    try:
        with open(obs_path) as fh:
            doc = json.load(fh)
        problems += [f"BENCH_obs: {p}"
                     for p in benchio.validate_bench_obs(doc)]
    except (OSError, ValueError) as exc:
        problems.append(f"BENCH_obs unreadable: {exc}")
    if problems:
        raise SystemExit("--linear: " + "; ".join(problems))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--features", type=int, default=28)
    ap.add_argument("--leaves", type=int, default=255)
    ap.add_argument("--iters", type=int, default=20,
                    help="boosting iterations per timed block")
    ap.add_argument("--blocks", type=int, default=5,
                    help="timed blocks PER ARM (interleaved)")
    ap.add_argument("--settle", type=int, default=5)
    ap.add_argument("--a", action="append", metavar="K=V",
                    help="param override for arm A (repeatable)")
    ap.add_argument("--b", action="append", metavar="K=V",
                    help="param override for arm B (repeatable)")
    ap.add_argument("--fault", action="store_true",
                    help="robustness smoke: checkpoint overhead %%, "
                    "kill+resume wall-clock (asserts the overhead budget)")
    ap.add_argument("--ckpt-interval", type=int, default=10,
                    help="--fault: checkpoint every N iterations")
    ap.add_argument("--fault-reps", type=int, default=3,
                    help="--fault: interleaved trainings per arm")
    ap.add_argument("--max-overhead-pct", type=float, default=3.0,
                    help="--fault: checkpoint overhead budget to assert")
    ap.add_argument("--drift", action="store_true",
                    help="continual-runtime smoke: drift detection, "
                    "swap compile counts, rollback-within-N + last-good "
                    "serving parity (asserts all of them)")
    ap.add_argument("--drift-rows", type=int, default=256,
                    help="--drift: rows per tick")
    ap.add_argument("--rollback-within", type=int, default=3,
                    help="--drift: ticks within which rollback must "
                    "fire after an injected post-swap regression")
    ap.add_argument("--frontier", action="store_true",
                    help="frontier-batching A/B: K=1 oracle vs "
                    "tpu_frontier_k=K across --frontier-rows, asserting "
                    "tree bit-identity and the fixed-cost reduction of "
                    "the per-iter affine fits")
    ap.add_argument("--frontier-rows", default="16384,65536",
                    metavar="R1,R2[,..]",
                    help="--frontier: row counts for the affine fit")
    ap.add_argument("--frontier-k", type=int, default=4,
                    help="--frontier: batch width of arm B")
    ap.add_argument("--frontier-leaves", type=int, default=63,
                    help="--frontier: num_leaves (own default: the "
                    "bench-wide 255 is CPU-hostile)")
    ap.add_argument("--frontier-iters", type=int, default=8,
                    help="--frontier: iterations per timed block")
    ap.add_argument("--frontier-blocks", type=int, default=3,
                    help="--frontier: timed blocks per arm (interleaved)")
    ap.add_argument("--frontier-min-pct", type=float, default=None,
                    help="--frontier: minimum fixed-cost reduction %% to "
                    "assert (exit non-zero below it; default: report "
                    "only — on CPU hosts the fixed cost is padded-chunk "
                    "compute, not the bookkeeping the batching "
                    "amortizes, see PERF.md round 12)")
    ap.add_argument("--chunk", action="store_true",
                    help="chunk-policy A/B: tpu_chunk_policy=fixed vs "
                    "adaptive across --chunk-rows at --chunk-leaves "
                    "plus the --chunk-uniform regime, asserting tree "
                    "bit-identity, the speedup bar and the uniform "
                    "noise gate; appends chunk_sweep trajectory "
                    "entries the auto modes consult")
    ap.add_argument("--chunk-rows", default="8192,16384,65536",
                    metavar="R1,R2[,..]",
                    help="--chunk: small-leaf-heavy row counts for the "
                    "affine fit")
    ap.add_argument("--chunk-leaves", type=int, default=255,
                    help="--chunk: num_leaves of the small-leaf-heavy "
                    "regimes")
    ap.add_argument("--chunk-uniform", default="262144:31",
                    metavar="ROWS:LEAVES",
                    help="--chunk: large-uniform-leaf regime that must "
                    "stay inside the perfwatch noise floor")
    ap.add_argument("--chunk-iters", type=int, default=4,
                    help="--chunk: iterations per timed block")
    ap.add_argument("--chunk-blocks", type=int, default=3,
                    help="--chunk: timed blocks per arm (interleaved)")
    ap.add_argument("--chunk-min-x", type=float, default=None,
                    help="--chunk: minimum small-leaf speedup to assert "
                    "(exit non-zero below it; default: report only)")
    ap.add_argument("--linear", action="store_true",
                    help="piece-wise-linear tree A/B: constant leaves "
                    "vs linear_tree refit vs "
                    "linear_tree_mode=leafwise_gain on a smooth "
                    "synthetic; reports per-arm wall clock and "
                    "trees-to-target-RMSE, exiting non-zero when the "
                    "leafwise arm saves fewer than "
                    "--linear-min-tree-save %% of the trees or "
                    "regresses the constant arm's accuracy")
    ap.add_argument("--linear-rows", type=int, default=24_000,
                    help="--linear: dataset rows")
    ap.add_argument("--linear-features", type=int, default=8,
                    help="--linear: dataset features")
    ap.add_argument("--linear-leaves", type=int, default=31,
                    help="--linear: num_leaves for all arms")
    ap.add_argument("--linear-iters", type=int, default=120,
                    help="--linear: boosting rounds per arm (also the "
                    "trees-to-target denominator)")
    ap.add_argument("--linear-min-tree-save", type=float, default=25.0,
                    help="--linear: minimum %% of trees the leafwise "
                    "arm must save vs the full budget to reach the "
                    "constant arm's final RMSE")
    ap.add_argument("--obs-out", default=None, metavar="PATH",
                    help="BENCH_obs.json artifact path (default: "
                    "$BENCH_OBS_PATH or ./BENCH_obs.json)")
    args = ap.parse_args(argv)

    # telemetry at counters: the artifact records the run's compile
    # events and memory peaks alongside the timings (zero-HLO, and the
    # per-iteration span cost is noise vs the timed blocks)
    from lightgbm_tpu import obs
    from lightgbm_tpu.obs import benchio
    obs.get().enable("counters")

    mode = ("ab_bench.fault" if args.fault else
            "ab_bench.drift" if args.drift else
            "ab_bench.frontier" if args.frontier else
            "ab_bench.chunk" if args.chunk else
            "ab_bench.linear" if args.linear else "ab_bench")
    # export-on-failure: a lane that dies mid-measurement still leaves
    # an aborted BENCH_obs artifact + trajectory entry; lanes that
    # wrote their artifact and THEN failed an assertion keep the real
    # (non-aborted) artifact — the measurement finished, the gate
    # didn't
    with benchio.abort_guard(mode, {"rows": args.rows,
                                    "features": args.features,
                                    "leaves": args.leaves},
                             path=args.obs_out) as guard:
        if args.fault:
            _fault_smoke(args, guard)
            return
        if args.drift:
            _drift_smoke(args, guard)
            return
        if args.frontier:
            _frontier_smoke(args, guard)
            return
        if args.chunk:
            _chunk_smoke(args, guard)
            return
        if args.linear:
            _linear_smoke(args, guard)
            return
        _ab_body(args, guard)


def _ab_body(args, guard):
    import jax.numpy as jnp
    import lightgbm_tpu as lgb

    rng = np.random.RandomState(7)
    X = rng.normal(size=(args.rows, args.features)).astype(np.float32)
    w = rng.normal(size=args.features)
    y = ((X.dot(w) * 0.5 + rng.normal(size=args.rows)) > 0).astype(np.float32)

    base = {"objective": "binary", "num_leaves": args.leaves,
            "learning_rate": 0.1, "max_bin": 255, "verbosity": -1,
            "metric": ""}
    pa = {**base, **_parse_overrides(args.a)}
    pb = {**base, **_parse_overrides(args.b)}

    # the two arms share ONE binned dataset (constructed with arm A's
    # params); overrides that change the binning itself would be
    # silently vacuous, so reject them
    _DATASET_KEYS = {"max_bin", "min_data_in_bin", "bin_construct_sample_cnt",
                     "max_bin_by_feature", "feature_pre_filter",
                     "categorical_feature", "use_missing", "zero_as_missing",
                     "enable_bundle", "min_data_per_group"}
    bad = (_DATASET_KEYS & set(_parse_overrides(args.a))) | \
          (_DATASET_KEYS & set(_parse_overrides(args.b)))
    if bad:
        raise SystemExit(f"dataset-construction params {sorted(bad)} cannot "
                         "be A/B'd here: both arms share one binned dataset")

    ds = lgb.Dataset(X, label=y)
    ds.construct(pa)
    boosters = {"A": lgb.Booster(params=pa, train_set=ds),
                "B": lgb.Booster(params=pb, train_set=ds)}

    def sync(bst):
        # host materialization: the only reliable completion barrier on
        # remote-attached TPUs (PERF.md measurement pitfalls)
        return float(jnp.sum(bst._gbdt.scores))

    # warm both compiles, then settle both arms
    for name in ("A", "B"):
        boosters[name].update()
        sync(boosters[name])
    for _ in range(args.settle):
        for name in ("A", "B"):
            boosters[name].update()
    for name in ("A", "B"):
        sync(boosters[name])

    times = {"A": [], "B": []}
    for _ in range(args.blocks):
        for name in ("A", "B"):
            bst = boosters[name]
            t0 = time.time()
            for _ in range(args.iters):
                bst.update()
            sync(bst)
            times[name].append((time.time() - t0) / args.iters)

    def stats(v):
        v = np.asarray(v)
        med = float(np.median(v))
        mad = float(np.median(np.abs(v - med)))
        return {"median_s_per_iter": round(med, 5),
                "mad_s_per_iter": round(mad, 5),
                "mad_pct": round(100 * mad / med, 2),
                "blocks": [round(x, 5) for x in v]}

    def kernel_flags(bst):
        # the resolved plan of each arm (selection is by backend and
        # shape eligibility; nothing falls back behind it)
        return bst._gbdt.kernel_plan()

    sa, sb = stats(times["A"]), stats(times["B"])
    paired = np.asarray(times["B"]) - np.asarray(times["A"])
    delta_med = float(np.median(paired))
    report = {
        "rows": args.rows, "iters_per_block": args.iters,
        "blocks_per_arm": args.blocks,
        "a_params": _parse_overrides(args.a), "b_params": _parse_overrides(args.b),
        "a_kernels": kernel_flags(boosters["A"]),
        "b_kernels": kernel_flags(boosters["B"]),
        "A": sa, "B": sb,
        "paired_delta_s_per_iter": round(delta_med, 5),
        "paired_delta_pct_of_A": round(
            100 * delta_med / sa["median_s_per_iter"], 2),
        "paired_delta_mad": round(float(np.median(np.abs(
            paired - delta_med))), 5),
    }
    print(json.dumps(report))
    _write_obs(guard, args, "ab_bench",
               {"rows": args.rows, "features": args.features,
                "leaves": args.leaves, "iters": args.iters,
                "blocks": args.blocks,
                "a_params": report["a_params"],
                "b_params": report["b_params"]},
               report,
               metrics={"A_median_s": sa["median_s_per_iter"],
                        "B_median_s": sb["median_s_per_iter"],
                        "paired_delta_s": delta_med},
               fingerprint_extra={"a": report["a_params"],
                                  "b": report["b_params"]})


if __name__ == "__main__":
    main()
