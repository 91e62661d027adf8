"""One run of one cell of the benchmark.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process.  Refuses any platform but `tpu` (`--rehearse`, for the CPU
tests, shrinks the rows and allows `cpu`; its line says `platform: cpu`).
Makes the table from `--seed`, constructs the dataset, builds the booster,
warms up, measures a window of `Booster.update()` calls closed by one
barrier, then holds what the window produced against the plain reference
(reference.py) and prints one JSON object as the last line of standard
output, after checking it against the contract (contract.py).

The cell is found by name: `workloads/<name>.json` beside this file names
its configuration (`configs/<name>.json`), rows, chips and tree learner;
the metrics it reports are those `BENCHMARK.json` lists for it, each
per-layer one read by the reader its `metrics/<name>.json` names
(`readers/<reader>.py`).  Nothing here names a cell, a configuration or a
metric.
"""

import time

T0 = time.time()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import contract  # noqa: E402
import datagen  # noqa: E402
import reference  # noqa: E402
import trace_reduce  # noqa: E402


def say(msg):
    print(f"[benchmark] {msg}", flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class CompileLog:
    """What the process compiled, from JAX's own monitoring events: the
    count and the seconds of backend compilations or persistent-cache
    fetches, and the persistent cache's hits and misses (as
    chip_smoke.CompileLog)."""

    def __init__(self):
        import jax
        self.count, self.seconds, self.hits, self.misses = 0, 0.0, 0, 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **kw):
        self.hits += name == "/jax/compilation_cache/cache_hits"
        self.misses += name == "/jax/compilation_cache/cache_misses"

    def _duration(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += secs


class Cell:
    """A cell's data files, found by its name under `data`, the first of
    BENCHMARK.json's `paths` in `root`."""

    def __init__(self, root, name):
        self.bench = load_json(root, "BENCHMARK.json")
        self.data = os.path.join(root, self.bench["paths"][0])
        entry = [w for w in self.bench["workloads"] if w["name"] == name]
        if not entry:
            sys.exit(f"benchmark: BENCHMARK.json has no workload {name!r}")
        self.entry = entry[0]
        self.workload = load_json(self.data, "workloads", name + ".json")
        self.config = load_json(self.data, "configs",
                                self.entry["config"] + ".json")
        for key in ("config", "chips"):
            if self.workload[key] != self.entry[key]:
                sys.exit(f"benchmark: {name}: {key} differs between "
                         "BENCHMARK.json and the workload's file")

    def metrics(self, group):
        """{name: entry} of the `end_to_end` or `per_layer` metrics that
        this cell reports."""
        return {m["name"]: m for m in self.bench[group]
                if self.entry["name"] in m.get("workloads",
                                               [self.entry["name"]])}

    def kernels(self, name):
        return load_json(self.data, "kernels", name + ".json")

    def read(self, name, ctx):
        """A per-layer metric through the reader its own file names."""
        spec = load_json(self.data, "metrics", name + ".json")
        path = os.path.join(self.data, "readers", spec["reader"] + ".py")
        mod_spec = importlib.util.spec_from_file_location(
            "reader_" + spec["reader"], path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod.read(ctx, **spec.get("args", {}))


def find_devices(chips, rehearse):
    """The devices of the run, or exit non-zero: no accelerator, no result."""
    import jax
    platform = jax.default_backend()
    if platform != "tpu" and not (rehearse and platform == "cpu"):
        sys.exit(f"benchmark: platform={platform}, need tpu: no result")
    devices = jax.devices()
    if len(devices) < chips:
        sys.exit(f"benchmark: the cell needs {chips} chip(s), JAX finds "
                 f"{len(devices)}: no result")
    return devices


def peak_bytes(devices):
    """Highest peak_bytes_in_use over the devices; where the backend reports
    none (the CPU rehearsal) the process's peak resident bytes."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    if all(p is None for p in peaks):
        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    return max(p for p in peaks if p is not None)


def barrier(booster):
    """Wait for the device without reading `.scores`: a read drops the fused
    physical row layout, which a user's training loop never does."""
    import jax
    g = booster._gbdt
    phys = getattr(g, "_phys", None)
    jax.block_until_ready(phys if phys is not None else g.scores)


def window(booster, n, trace_dir):
    """`n` calls of update() with no barrier between them, one
    block_until_ready on the scores at the end.  Returns (seconds, failed,
    scores).  With `trace_dir` the profiler runs around it."""
    import jax
    failed = 0
    if trace_dir is None:
        profiler = contextlib.nullcontext()
    else:
        shutil.rmtree(trace_dir, ignore_errors=True)
        profiler = jax.profiler.trace(trace_dir)
    with profiler:
        t0 = time.time()
        with jax.profiler.TraceAnnotation("bench.window"):
            for i in range(n):
                with jax.profiler.TraceAnnotation(f"bench.update_{i}"):
                    try:
                        booster.update()
                    except Exception as e:  # counted, and the run goes on
                        failed += 1
                        say(f"update {i} raised {type(e).__name__}: {e}")
            with jax.profiler.TraceAnnotation("bench.final_barrier"):
                scores = booster._gbdt.scores
                jax.block_until_ready(scores)
        secs = time.time() - t0
    return secs, failed, scores


def reduce_trace(trace_dir, rehearse):
    """The trace reduction plus the window and the idle gaps' names from the
    harness's own annotations, which sit on the trace's clock."""
    devices, spans = trace_reduce.read_trace(
        trace_reduce.find_xplane(trace_dir), host_ops=rehearse,
        span_prefix="bench.")
    win = [(a, b) for n, a, b in spans if n == "bench.window"]
    out = trace_reduce.reduce_events(devices, win[0] if win else None)

    def doing(a, b):
        mid = (a + b) / 2
        inner = [n for n, s, e in spans if s <= mid <= e
                 and n != "bench.window"]
        return inner[0] if inner else "outside the harness's spans"

    gaps = {}
    for secs, a, b in out["gaps"]:
        name = doing(a, b)
        gaps[name] = gaps.get(name, 0.0) + secs
    out["idle_gaps"] = [[n, s] for n, s in sorted(
        gaps.items(), key=lambda kv: -kv[1])[:10]]
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny rows, CPU allowed: for the tests only")
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="copy the traced run's xplane there, to look at")
    ap.add_argument("--root", default=os.path.dirname(HERE),
                    help="the checkout that holds BENCHMARK.json")
    args = ap.parse_args(argv)
    root = os.path.abspath(args.root)
    sys.path.insert(0, root)

    cell = Cell(root, args.workload)
    wl, cfg = cell.workload, cell.config
    devices = find_devices(wl["chips"], args.rehearse)
    compiles = CompileLog()
    import jax
    import lightgbm_tpu as lgb
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    say(f"device {device} compile_cache={jax.config.jax_compilation_cache_dir}")
    peaks = load_json(cell.data, "peaks.json")
    if device["kind"] not in peaks and not args.rehearse:
        sys.exit(f"benchmark: no peaks for device kind {device['kind']!r} "
                 "in peaks.json")

    rows = wl["rehearse_rows"] if args.rehearse else wl["rows"]
    holdout = min(wl["holdout_rows"], rows)
    params = dict(cfg["params"], tree_learner=wl["tree_learner"])
    spans = {}

    def spanned(name, fn):
        t = time.time()
        out = fn()
        spans[name] = time.time() - t
        say(f"{name}_s={spans[name]:.3f}")
        return out

    X, y = spanned("generate", lambda: datagen.make_table(
        args.seed, rows + holdout, cfg["features"]))
    Xt, yt, yh = X[:rows], y[:rows], y[rows:]

    def construct():
        ds = lgb.Dataset(Xt, label=yt)
        ds.construct(params)
        return ds

    ds = spanned("construct", construct)
    bst = spanned("booster", lambda: lgb.Booster(params, ds))
    warm = wl["warmup_iterations"]
    for i in range(warm):
        # the first warm-up compiles the fused step and ends on a scores
        # read, which compiles the read-back that the window's final barrier
        # uses; the second compiles the resume of the physical row layout
        # that the read dropped; from then on a step finds the layout in
        # place, as the window's steps do, and the last one gives the time
        # of a warm step
        first = i == 0
        before = compiles.seconds
        spanned(f"warm_{i + 1}", lambda: (
            bst.update(), jax.block_until_ready(bst._gbdt.scores) if first
            else barrier(bst)))
        warm_step_s = spans[f"warm_{i + 1}"] - (compiles.seconds - before)
    say("plan: " + " ".join(f"{k}={v}" for k, v in
                            bst._gbdt.kernel_plan().items()))
    if args.trace:
        n = wl["traced_iterations"]
    else:
        n = max(wl["min_window_iterations"],
                math.floor(args.seconds / warm_step_s))
    compile_before = (compiles.count, compiles.seconds)
    say(f"compiles before the window: count={compiles.count} "
        f"seconds={compiles.seconds:.3f} cache_hits={compiles.hits} "
        f"cache_misses={compiles.misses}; warm_step_s={warm_step_s:.4f}; "
        f"window of {n} iterations")
    trace_dir = (os.path.join(cell.data, ".trace", args.workload)
                 if args.trace else None)

    setup_s = time.time() - T0
    window_s, failed, scores = window(bst, n, trace_dir)
    in_window = compiles.count - compile_before[0]
    say(f"window_s={window_s:.4f} iterations={n} failed={failed} "
        f"compiles_in_window={in_window}")

    memory_peak = peak_bytes(devices)
    prog_scores = np.asarray(scores, dtype=np.float64)
    model_text = bst.model_to_string()
    del bst, ds, scores
    gc.collect()

    # what the window produced, against the plain reference
    t_check = time.time()
    trees = reference.parse_model(model_text)
    # the first `recompute_trees` trees: every row, training and held-out,
    # is routed, and the reference follows the boosting loop through them
    head = max(wl["recompute_trees"], wl["heldout_trees"])
    leaf_of = reference.route(X, trees[:head])
    numbers, ref_scores, _, notes = reference.follow(
        leaf_of[:, :rows], yt, trees[:head], params, wl["recompute_trees"])
    for note in notes:
        say(note)
    # the later trees: a sample of rows drawn from the seed is routed on
    # through them, and the program's final scores are held to the
    # reference's on those rows
    sample = reference.sample_rows(args.seed, rows, wl["score_sample_rows"])
    ref_final = ref_scores[sample]
    if len(trees) > head:
        tail = reference.route(Xt[sample], trees[head:])
        ref_final = ref_final + reference.predict_raw(tail, trees[head:])
    numbers["leaf_count_sum_gap"] = max(
        abs(int(t.leaf_count.sum()) - rows) for t in trees)
    numbers["trees_missing"] = abs(warm + n - failed - len(trees))
    whole = len(prog_scores) == rows and numbers["trees_missing"] == 0
    diff = (np.abs(prog_scores[sample] - ref_final) if whole
            else np.array([1e30]))
    numbers["train_score_gap"] = float(diff.max())
    numbers["train_score_median_gap"] = float(np.median(diff))
    say("numbers: " + " ".join(f"{k}={v}" for k, v in numbers.items()))
    limits = wl["limits"]
    compared = {k: {"value": numbers[k], "limit": limits[k]}
                for k in limits}
    correct = bool(all(v["value"] <= v["limit"] for v in compared.values()))
    cut = wl["heldout_trees"]
    heldout = (reference.logloss(
        yh, reference.predict_raw(leaf_of[:cut, rows:], trees[:cut]))
        if len(trees) >= cut else 1e30)
    say(f"check_s={time.time() - t_check:.1f}")

    line = {"correct": correct, "attempted": n, "failed": failed}
    if args.trace:
        if args.keep_trace:
            os.makedirs(args.keep_trace, exist_ok=True)
            shutil.copy(trace_reduce.find_xplane(trace_dir), args.keep_trace)
        trace = reduce_trace(trace_dir, args.rehearse)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = {"spans": spans, "trace": trace, "chips": wl["chips"],
               "compile": {"in_window": in_window,
                           "before_window_s": compile_before[1]},
               "window_trees": trees[warm:], "traced_trees": trees[warm:],
               "rows": rows, "features": cfg["features"],
               "bin_bytes": 1 if cfg["params"]["max_bin"] <= 256 else 2,
               "peak": peaks.get(device["kind"], peaks["rehearsal"]),
               "kernels": cell.kernels}
        specs = cell.metrics("per_layer")
        values = {name: cell.read(name, ctx) for name in specs}
        values = {k: v for k, v in values.items() if v is not None}
        device.update(window_s=trace["window_s"], busy_s=trace["busy_s"])
        line["breakdown"] = {
            "device_ops": trace_reduce.top_ops(trace["op_seconds"]),
            "idle_gaps": trace["idle_gaps"]}
    else:
        specs = cell.metrics("end_to_end")
        values = {"train_s_per_iter": window_s / n,
                  "heldout_logloss": heldout,
                  "hbm_peak_gb": memory_peak / 1e9,
                  "setup_s": setup_s}
    line["metrics"] = {k: {"value": float(v), "unit": specs[k]["unit"]}
                       for k, v in values.items() if k in specs}
    device["memory_peak_bytes"] = int(memory_peak)
    line["device"] = device
    line["compared"] = compared

    bad = contract.problems(
        {k: v for k, v in line.items() if k != "compared"},
        {k: m["unit"] for k, m in specs.items()}, bool(args.trace))
    for k, v in compared.items():
        print(f"compared {k}={v['value']} limit={v['limit']}",
              file=sys.stderr, flush=True)
    if bad:
        for reason in bad:
            say("refused by the contract: " + reason)
        sys.exit(1)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
