"""Chip smoke: the quickest proof that the main path still starts on the TPU.

    python chip_smoke.py                      # one chip, the driver's check
    python chip_smoke.py --warm               # second start in the same call:
                                              #   nothing >= 1 s may compile
    python chip_smoke.py --parallel data feature voting   # four-chip host
    python chip_smoke.py --rehearse [...]     # toy size on the CPU, prints
                                              #   platform=cpu, no result line

One process (a chip belongs to one process).  Trains the repo's one real
shape — 10.5M x 28 f32, binary label, bench.py's generator — through
`lgb.Dataset(...).construct` and `lgb.train` at num_leaves=255 / max_bin=255 /
default `tpu_*` settings, asserts the kernel plan that resolved, checks the
model (finite scores, falling log-loss, leaf counts, held-out AUC, device
predict vs a NumPy traversal of the reloaded model text, pred_contrib on
the device), then drives the CLI train+predict confs.  Every phase raises
on failure; nothing is caught.  Without a TPU it exits non-zero and prints
no result.  On success the last stdout line is
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.

Times printed here are smoke facts (one run, no repeats), not benchmark
results.
"""

import argparse
import gc
import importlib.metadata
import json
import os
import sys
import time

import numpy as np

T0 = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
OUT = os.path.join(HERE, "chiprun_out", "chip_smoke")

FULL = {"rows": 10_500_000, "holdout": 100_000, "leaves": 255, "timed": 5,
        "contrib_rows": 512}
TOY = {"rows": 20_000, "holdout": 5_000, "leaves": 31, "timed": 3,
       "contrib_rows": 512}

# what `auto` must resolve to for this shape (all-numerical, u8 bins, serial)
TPU_PLAN = {"partition": "pallas", "hist": "pallas", "search": "pallas",
            "mega": "pallas", "frontier_k": 1, "fused": "on",
            "tree_learner": "serial"}
CPU_PLAN = {"partition": "xla", "hist": "xla", "search": "xla",
            "mega": "off", "frontier_k": 1, "fused": "on",
            "tree_learner": "serial"}


def say(msg):
    print(f"[chip_smoke] {msg}", flush=True)


class CompileLog:
    """Counts what the process compiled, from JAX's own monitoring events:
    persistent-cache hits, persistent-cache writes (a program that took
    >= jax_persistent_cache_min_compile_time_secs and was not found) and
    the seconds spent in backend compilation or cache retrieval."""

    def __init__(self):
        import jax
        self.hits = self.writes = 0
        self.seconds = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **kw):
        self.hits += name == "/jax/compilation_cache/cache_hits"
        self.writes += name == "/jax/compilation_cache/cache_misses"

    def _duration(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def facts(self):
        return (f"cache_hits={self.hits} cache_writes={self.writes} "
                f"compile_or_fetch_s={self.seconds:.1f}")


def device_facts(rehearse):
    """Refuse anything but a TPU (or, for --rehearse, anything but the
    CPU); print what JAX reports about the device and the installation."""
    import jax
    platform = jax.default_backend()
    want = "cpu" if rehearse else "tpu"
    if platform != want:
        sys.exit(f"chip_smoke: platform={platform}, need {want}"
                 + ("" if rehearse else
                    " — no TPU, no result (use --rehearse for the CPU)"))
    import lightgbm_tpu  # noqa: F401  (places the compile cache)
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    say(f"platform={device['platform']} device_kind={device['kind']} "
        f"count={device['count']}")
    say("jax=%s jaxlib=%s libtpu=%s" % tuple(
        importlib.metadata.version(p) for p in ("jax", "jaxlib", "libtpu")))
    say(f"compile_cache_dir={jax.config.jax_compilation_cache_dir}")
    return device


def make_data(size):
    """bench.py's seeded generator; the last `holdout` rows are held out."""
    from bench import _make_data
    X, y = _make_data(size["rows"] + size["holdout"])
    n = size["rows"]
    return X[:n], y[:n], X[n:], y[n:]


def train_params(size):
    return {"objective": "binary", "num_leaves": size["leaves"],
            "max_bin": 255, "learning_rate": 0.1, "metric": ""}


def auc(y, score):
    """Rank AUC with average ranks for ties."""
    _, inv, cnt = np.unique(score, return_inverse=True, return_counts=True)
    rank = (np.cumsum(cnt) - (cnt - 1) / 2.0)[inv]
    pos = y > 0
    npos, nneg = pos.sum(), (~pos).sum()
    return float((rank[pos].sum() - npos * (npos + 1) / 2) / (npos * nneg))


def peak_bytes():
    import jax
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]


def train_phase(lgb, X, y, size, want_plan, compiles, on_chip):
    """construct + lgb.train: two warm-up iterations (the first compiles
    the fused step; the second, coming after a scores read, compiles the
    program that resumes the physical row layout), then two timed blocks
    of `timed` iterations closed by block_until_ready and by a host
    materialisation, which must contain no compilation and, on the chip,
    agree (a block_until_ready that returned early would show here)."""
    import jax
    import jax.numpy as jnp
    params = train_params(size)
    timed = size["timed"]
    t0 = time.time()
    ds = lgb.Dataset(X, label=y)
    ds.construct(params)
    say(f"construct_s={time.time() - t0:.1f} rows={len(y)} "
        f"features={X.shape[1]}")

    sign = jnp.asarray(2.0 * y - 1.0)
    barrier_at = {0: "first_iteration", 1: "second_iteration",
                  1 + timed: "block_until_ready",
                  1 + 2 * timed: "host_materialisation"}
    blocks = {}
    logloss = []
    clock = [time.time(), compiles.seconds]

    def close_block(env):
        kind = barrier_at.get(env.iteration)
        if kind is None:
            return
        scores = env.model._gbdt.scores
        if kind == "host_materialisation":
            float(jnp.sum(scores))
        else:
            jax.block_until_ready(scores)
        blocks[kind] = (time.time() - clock[0], compiles.seconds - clock[1])
        logloss.append(float(jnp.mean(jnp.logaddexp(0.0, -sign * scores))))
        clock[:] = time.time(), compiles.seconds

    bst = lgb.train(params, ds, num_boost_round=2 + 2 * timed,
                    callbacks=[close_block])
    plan = bst._gbdt.kernel_plan()
    say("plan: " + " ".join(f"{k}={v}" for k, v in plan.items()))
    got = {k: plan[k] for k in want_plan}
    assert got == want_plan, f"kernel plan {got} != expected {want_plan}"

    for kind, (secs, compile_s) in blocks.items():
        say(f"{kind}: wall_s={secs:.3f} of which compile_or_fetch_s="
            f"{compile_s:.3f}")
    bur = blocks["block_until_ready"][0] / timed
    host = blocks["host_materialisation"][0] / timed
    say(f"s_per_iter_block_until_ready={bur:.4f} "
        f"s_per_iter_host_materialisation={host:.4f} "
        f"ratio={host / bur:.3f} ({timed} iterations each)")
    assert blocks["block_until_ready"][1] == 0.0 \
        and blocks["host_materialisation"][1] == 0.0, \
        "something compiled inside a timed block"
    assert not on_chip or 1 / 1.5 < host / bur < 1.5, \
        "block_until_ready and a host materialisation disagree on the time"
    say("train_logloss=" + " ".join(f"{v:.5f}" for v in logloss))
    assert np.all(np.isfinite(logloss)) and np.all(np.diff(logloss) < 0), \
        f"train log-loss did not fall: {logloss}"
    say(f"peak_bytes_in_use={peak_bytes()}")
    return bst


def check_phase(lgb, bst, X, y, Xh, yh):
    """The model is right, not just present."""
    n = len(y)
    scores = np.asarray(bst._gbdt.scores)
    assert scores.shape == (n,) and np.isfinite(scores).all()

    path = os.path.join(OUT, "smoke_model.txt")
    bst.save_model(path)
    loaded = lgb.Booster(model_file=path)
    trees = loaded._gbdt.models
    assert len(trees) == bst.num_trees() > 0
    # every row lands in exactly one leaf of every tree: catches inexact
    # count prefix sums in the split search (ops/split.py MXU cumsums)
    for i, t in enumerate(trees):
        assert int(t.leaf_count.sum()) == n, \
            f"tree {i}: leaf counts sum to {int(t.leaf_count.sum())} != {n}"
    say(f"trees={len(trees)} leaves={[t.num_leaves for t in trees]}")

    # held-out AUC must beat what the best single feature gives — a
    # floor no model of this generator (a 28-feature linear logit) that
    # learned anything multivariate can miss
    floor = max(max(a, 1 - a) for a in
                (auc(yh, Xh[:, j]) for j in range(Xh.shape[1])))
    calls = bst._gbdt.serving.stats()["calls"]
    before = sum(v for (kind, _), v in calls.items() if kind == "raw")
    dev_raw = bst.predict(Xh, raw_score=True)
    calls = bst._gbdt.serving.stats()["calls"]
    assert sum(v for (kind, _), v in calls.items()
               if kind == "raw") > before, \
        "Booster.predict was not served by the device engine"
    got = auc(yh, dev_raw)
    say(f"holdout_auc={got:.4f} best_single_feature_auc={floor:.4f}")
    assert got > floor + 0.02, (got, floor)

    # device predict vs NumPy traversal of the reloaded model text
    host_raw = sum(t.predict(Xh) for t in trees)
    diff = float(np.abs(dev_raw - host_raw).max())
    say(f"device_predict_vs_numpy_traversal_max_abs_diff={diff:.2e}")
    assert np.allclose(dev_raw, host_raw, rtol=1e-5, atol=1e-5), diff


def contrib_phase(bst, Xh, rows):
    """pred_contrib converts f64 path matrices under x64 and runs TreeSHAP
    on the device; it must be the device engine that answers."""
    Xs = Xh[:rows]
    t0 = time.time()
    contrib = bst.predict(Xs, pred_contrib=True)
    secs = time.time() - t0
    calls = bst._gbdt.serving.stats()["calls"]
    assert any(kind == "contrib" for kind, _ in calls), \
        "pred_contrib fell to the host oracle"
    assert contrib.shape == (len(Xs), Xs.shape[1] + 1)
    assert np.isfinite(contrib).all()
    raw = bst.predict(Xs, raw_score=True)
    diff = float(np.abs(contrib.sum(axis=1) - raw).max())
    say(f"pred_contrib rows={len(Xs)} first_call_s={secs:.1f} "
        f"sum_vs_raw_max_abs_diff={diff:.2e}")
    assert diff < 1e-4, diff


def cli_phase():
    """The CLI's train.conf then predict.conf, in this process."""
    from lightgbm_tpu import cli
    from lightgbm_tpu.native import get_native
    say("text_parser=" + ("native (g++ build)" if get_native() is not None
                          else "python"))
    ex = os.path.join(HERE, "examples", "binary_classification")
    model = os.path.join(OUT, "cli_model.txt")
    pred = os.path.join(OUT, "cli_pred.txt")
    for f in (model, pred):
        if os.path.exists(f):
            os.remove(f)
    cli.main([f"config={ex}/train.conf", f"data={ex}/binary.train",
              f"valid={ex}/binary.test", f"output_model={model}",
              "metric_freq=10"])
    cli.main([f"config={ex}/predict.conf", f"data={ex}/binary.test",
              f"input_model={model}", f"output_result={pred}"])
    assert os.path.getsize(model) > 0
    p = np.loadtxt(pred)
    label = np.loadtxt(os.path.join(ex, "binary.test"), usecols=0)
    assert p.shape == label.shape and np.isfinite(p).all()
    got = auc(label, p)
    say(f"cli model={os.path.getsize(model)}B predictions={len(p)} "
        f"test_auc={got:.4f}")
    assert got > 0.75, got


def _splits_by_path(tree):
    """{root-to-node L/R path: (feature, threshold_bin)} — a numbering-
    independent view of a host Tree."""
    out, stack = {}, [(0, "")]
    while stack:
        node, path = stack.pop()
        out[path] = (int(tree.split_feature[node]),
                     int(tree.threshold_bin[node]))
        for child, side in ((tree.left_child[node], "L"),
                            (tree.right_child[node], "R")):
            if child >= 0:
                stack.append((int(child), path + side))
    return out


def parallel_phase(lgb, X, y, Xh, size, modes):
    """One iteration per parallel tree learner on a four-chip host, each
    compared with the one-chip model at the same seed by the CPU mesh
    tests' tolerance (tests/test_parallel.py: same split count, >= 85% of
    splits agree on feature and threshold within 3 bins, equal total leaf
    counts).  The serial reference trains second, so that the per-device
    peaks printed for the first mode are not its own and every later mode
    is compared as soon as it has trained."""
    import jax
    ndev = len(jax.devices())
    assert ndev == 4, f"--parallel needs a four-chip host, found {ndev}"
    params = train_params(size)
    n = len(y)
    ds = lgb.Dataset(X, label=y)
    ds.construct(params)
    models = {}

    def compare(mode):
        ref_tree, ref_raw = models["serial"]
        tree, raw = models[mode]
        ref, got = _splits_by_path(ref_tree), _splits_by_path(tree)
        same = np.mean([p in got and got[p][0] == f
                        and abs(got[p][1] - t) <= 3
                        for p, (f, t) in ref.items()])
        say(f"{mode} vs serial: leaves={tree.num_leaves}/"
            f"{ref_tree.num_leaves} splits_agree={same:.3f} "
            f"holdout_raw_max_abs_diff={np.abs(raw - ref_raw).max():.2e}")
        assert tree.num_leaves == ref_tree.num_leaves
        assert int(tree.leaf_count.sum()) == int(ref_tree.leaf_count.sum()) \
            == n
        assert same >= 0.85, same

    for mode in [modes[0], "serial", *modes[1:]]:
        t0 = time.time()
        bst = lgb.train({**params, "tree_learner": mode}, ds,
                        num_boost_round=1)
        g = bst._gbdt
        jax.block_until_ready(g.scores)
        plan = g.kernel_plan()
        say(f"{mode}: train_1_iteration_s={time.time() - t0:.1f} plan: "
            + " ".join(f"{k}={v}" for k, v in plan.items()))
        assert plan["tree_learner"] == mode and plan["fused"] == "on", plan
        if mode != "serial":
            sb = g.sharded_builder
            assert sb is not None
            rows = [s.data.shape[1]
                    for s in sb.binned_sharded.addressable_shards]
            # data/voting shard the rows, each device's block padded as
            # the learner reads it; feature-parallel replicates them
            want = n if mode == "feature" else -(-n // ndev)
            say(f"{mode}: binned shard rows per device={rows} of {n}")
            assert sb.local_n == want and len(rows) == ndev \
                and all(r == sb.learner.N_pad for r in rows), (rows, want)
        say(f"{mode}: peak_bytes_in_use per device={peak_bytes()}")
        g._flush_pending()
        models[mode] = (g.models[0], bst.predict(Xh, raw_score=True))
        del bst, g, plan
        gc.collect()
        if mode == "serial":
            compare(modes[0])
        elif "serial" in models:
            compare(mode)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--parallel", nargs="+", default=[],
                    choices=["data", "feature", "voting"],
                    help="four-chip mode: one iteration per tree learner")
    ap.add_argument("--warm", action="store_true",
                    help="fail if anything was written to the compile cache")
    ap.add_argument("--rehearse", action="store_true",
                    help="toy size on the CPU; never prints a result line")
    args = ap.parse_args(argv)
    if not __debug__:
        sys.exit("chip_smoke: the checks are asserts; run without -O")

    device = device_facts(args.rehearse)
    compiles = CompileLog()
    import lightgbm_tpu as lgb
    os.makedirs(OUT, exist_ok=True)
    size = TOY if args.rehearse else FULL
    X, y, Xh, yh = make_data(size)
    if args.parallel:
        parallel_phase(lgb, X, y, Xh, size, args.parallel)
    else:
        bst = train_phase(lgb, X, y, size,
                          CPU_PLAN if args.rehearse else TPU_PLAN, compiles,
                          on_chip=not args.rehearse)
        check_phase(lgb, bst, X, y, Xh, yh)
        contrib_phase(bst, Xh, size["contrib_rows"])
        cli_phase()
    say(compiles.facts() + f" total_wall_s={time.time() - T0:.0f}")
    if args.warm:
        assert compiles.writes == 0, \
            f"warm start compiled {compiles.writes} program(s) of >= 1 s"
    if args.rehearse:
        say("rehearsal passed on platform=cpu — not a chip result")
    else:
        print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
