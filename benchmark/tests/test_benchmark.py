"""The benchmark's own tests: on the CPU, at tiny size.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They never give a device number: every line a rehearsal prints says
`platform: cpu`.
"""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import contract  # noqa: E402
import datagen  # noqa: E402
import reference  # noqa: E402
import trace_reduce  # noqa: E402

SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in SPEC["workloads"]]


def rehearse(workload, trace, root=ROOT, devices=1, extra=(),
             program=(os.path.join(BENCH, "run.py"),)):
    """One `--rehearse` run in a process of its own; returns (rc, last
    stdout line, everything printed)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    p = subprocess.run(
        [sys.executable, *program, "--workload",
         workload, "--seed", "2147483659", "--seconds", "1", "--trace",
         str(trace), "--root", root, *extra],
        env=env, capture_output=True, text=True, timeout=600)
    lines = p.stdout.strip().splitlines()
    return p.returncode, (lines[-1] if lines else ""), p.stdout + p.stderr


def cell_metrics(spec, workload, trace):
    group = "per_layer" if trace else "end_to_end"
    return {m["name"]: m["unit"] for m in spec[group]
            if workload in m.get("workloads", [workload])}


def good_line(traced):
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
           "memory_peak_bytes": 5 * 10**9}
    if traced:
        dev.update(window_s=2.0, busy_s=1.5)
    return {"correct": True, "attempted": 3, "failed": 0, "device": dev,
            "metrics": {"a": {"value": 1.5, "unit": "s"},
                        "b": {"value": 2.0, "unit": "GB"}}}


UNITS = {"a": "s", "b": "GB"}


# ---- the contract's validator ------------------------------------------

@pytest.mark.parametrize("traced", [False, True])
def test_validator_accepts_a_sound_line(traced):
    assert contract.problems(good_line(traced), UNITS, traced) == []


def _drop_metric(line):
    del line["metrics"]["b"]


def _busy_over_window(line):
    line["device"]["busy_s"] = 2.5


def _busy_zero(line):
    line["device"]["busy_s"] = 0.0


def _no_peak(line):
    del line["device"]["memory_peak_bytes"]


def _nan_value(line):
    line["metrics"]["a"]["value"] = float("nan")


def _wrong_unit(line):
    line["metrics"]["a"]["unit"] = "ms"


def _long_breakdown(line):
    line["breakdown"] = {"device_ops": [["op", 0.1]] * 11, "idle_gaps": []}


@pytest.mark.parametrize("spoil", [
    _drop_metric, _busy_over_window, _busy_zero, _no_peak, _nan_value,
    _wrong_unit, _long_breakdown], ids=lambda f: f.__name__.strip("_"))
def test_validator_rejects(spoil):
    line = good_line(True)
    spoil(line)
    assert contract.problems(line, UNITS, True)


# ---- the generator -----------------------------------------------------

def test_generator_is_f32_repeats_one_seed_and_tells_two_apart():
    X, y = datagen.make_table(2**31 + 5, 3000, 28, threads=3)
    X2, y2 = datagen.make_table(2**31 + 5, 3000, 28, threads=1)
    X3, y3 = datagen.make_table(2**31 + 6, 3000, 28)
    assert X.dtype == np.float32 and y.dtype == np.float32
    assert np.array_equal(X, X2) and np.array_equal(y, y2)
    assert not np.array_equal(X, X3) and not np.array_equal(y, y3)
    assert set(np.unique(y)) == {0.0, 1.0} and 0.4 < y.mean() < 0.6
    # every seed poses the same problem: one profile of magnitudes
    a, b = datagen.weights(1, 28), datagen.weights(2, 28)
    assert np.allclose(np.sort(np.abs(a)), np.sort(np.abs(b)))
    assert not np.array_equal(a, b)


def test_generator_chunks_agree_across_the_chunk_boundary(monkeypatch):
    monkeypatch.setattr(datagen, "CHUNK_ROWS", 1000)
    X, y = datagen.make_table(7, 2500, 4)
    assert np.isfinite(X).all() and abs(X.std() - 1) < 0.05
    assert np.array_equal(X[:1000], datagen.make_table(7, 1000, 4)[0])


# ---- the trace reduction -----------------------------------------------

def test_trace_reduce_on_known_events():
    ms = 1_000_000
    dev = {0: [("while.1", 0, 100 * ms),          # a container: left out
               ("fusion.1", 10 * ms, 30 * ms),
               ("fusion.2", 20 * ms, 40 * ms),    # overlaps fusion.1
               ("custom-call.7", 60 * ms, 90 * ms)],
           1: [("fusion.1", 0, 50 * ms)]}
    out = trace_reduce.reduce_events(dev, (0, 100 * ms))
    assert out["window_s"] == pytest.approx(0.1)
    assert out["per_device_busy_s"] == {0: pytest.approx(0.06),
                                        1: pytest.approx(0.05)}
    assert out["busy_s"] == pytest.approx(0.055) and out["busy_s"] <= 0.1
    assert out["op_seconds"]["fusion.1"] == pytest.approx((0.02 + 0.05) / 2)
    assert "while.1" not in out["op_seconds"]
    assert out["gaps"][0][0] == pytest.approx(0.02)       # 40..60 ms on dev 0
    secs, names = trace_reduce.pattern_seconds(
        out["op_seconds"], {"match": ["^custom-call"], "except": []})
    assert secs == pytest.approx(0.015) and names == ["custom-call.7"]
    with pytest.raises(ValueError):
        trace_reduce.pattern_seconds(out["op_seconds"],
                                     {"match": ["^no_such_kernel"]})
    with pytest.raises(ValueError):
        trace_reduce.reduce_events({0: []})


def test_trace_reduce_reads_the_recorded_trace():
    """data/small_tpu.xplane.pb: three calls of one jitted 2048^2 matmul
    chain, recorded on the v5e by record_small_trace.py; the expected
    numbers beside it were read from it by hand (peek_trace.py)."""
    path = os.path.join(HERE, "data", "small_tpu.xplane.pb")
    want = json.load(open(os.path.join(HERE, "data", "small_tpu.json")))
    out = trace_reduce.reduce_events(trace_reduce.device_events(path))
    assert sorted(out["per_device_busy_s"]) == want["devices"]
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    assert out["window_s"] == pytest.approx(want["window_s"], rel=1e-6)
    assert 0 < out["busy_s"] <= out["window_s"]
    secs, names = trace_reduce.pattern_seconds(
        out["op_seconds"], want["pattern"])
    assert secs == pytest.approx(want["pattern_s"], rel=1e-6)
    assert len(names) == want["pattern_names"]


# ---- the reference and its control --------------------------------------

def toy_model(seed=11, rows=20000, leaves=8):
    """A table and a forest of two trees built by the plain reference's own
    rules, so the tests of the comparison need no program."""
    X, y = datagen.make_table(seed, rows, 6)
    text = ["tree", "version=v4", ""]
    rng = np.random.default_rng(seed)
    for i in range(2):
        n = leaves - 1
        # a left-deep chain: node k tests feature k % 6, leaf k on its right
        left = [k + 1 if k + 1 < n else -(n + 1) for k in range(n)]
        right = [-(k + 1) for k in range(n)]
        text += [f"Tree={i}", f"num_leaves={leaves}", "num_cat=0",
                 "split_feature=" + " ".join(str(k % 6) for k in range(n)),
                 "threshold=" + " ".join(
                     repr(float(t)) for t in rng.normal(size=n) * 0.5),
                 "left_child=" + " ".join(map(str, left)),
                 "right_child=" + " ".join(map(str, right)),
                 "leaf_value=" + " ".join(["0"] * leaves),
                 "leaf_count=" + " ".join(["0"] * leaves),
                 "internal_count=" + " ".join(["0"] * n), ""]
    text.append("end of trees")
    return X, y, reference.parse_model("\n".join(text))


PARAMS = {"learning_rate": 0.1}


def as_program(X, y, trees, sum_dtype):
    """The reference put in the program's place: its leaf values, counts and
    scores written into the trees, computed with per-leaf sums in
    `sum_dtype`."""
    leaf = reference.route(X, trees)
    _, scores, values, _ = reference.follow(
        leaf, y, trees, PARAMS, len(trees), sum_dtype=sum_dtype)
    for i, t in enumerate(trees):
        t.leaf_value = np.asarray(values[i], dtype=np.float64)
        t.leaf_count = np.bincount(leaf[i], minlength=t.num_leaves)
    return scores, leaf


def test_routing_agrees_with_a_row_by_row_traversal():
    X, y, trees = toy_model()
    leaf = reference.route(X, trees, block_rows=4096)
    t = trees[1]
    for r in range(0, len(X), 997):
        node = 0
        while node >= 0:
            go_left = X[r, t.split_feature[node]] <= t.threshold[node]
            node = t.left_child[node] if go_left else t.right_child[node]
        assert leaf[1, r] == -node - 1


def test_reference_passes_itself_and_the_bf16_control_fails():
    import ml_dtypes
    limits = json.load(open(os.path.join(
        BENCH, "workloads", CELLS[0] + ".json")))["limits"]
    X, y, trees = toy_model()
    scores, leaf = as_program(X, y, trees, np.float32)
    numbers, ref, _, _ = reference.follow(leaf, y, trees, PARAMS, len(trees))
    numbers["train_score_gap"] = float(np.abs(scores - ref).max())
    numbers["train_score_median_gap"] = float(np.median(np.abs(scores - ref)))
    sound = {k: numbers[k] for k in ("leaf_value_median_gap",
                                     "train_score_median_gap")}
    assert all(sound[k] <= limits[k] for k in sound), sound

    as_program(X, y, trees, ml_dtypes.bfloat16)
    control, _, _, _ = reference.follow(leaf, y, trees, PARAMS,
                                          len(trees))
    k = "leaf_value_median_gap"
    assert control[k] > limits[k] and control[k] > 3 * sound[k], control


# ---- whole runs, rehearsed ------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_ends_in_a_line_the_validator_accepts(workload, trace):
    rc, last, out = rehearse(workload, trace, extra=("--rehearse",))
    assert rc == 0, out[-3000:]
    line = json.loads(last)
    assert contract.problems(
        {k: v for k, v in line.items() if k != "compared"},
        cell_metrics(SPEC, workload, trace), bool(trace)) == []
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0, line["compared"]
    assert list(line)[-1] == "compared"
    assert "compared leaf_value_gap=" in out.strip().splitlines()[-1] \
        or "compared " in out


def test_without_an_accelerator_there_is_no_line():
    rc, last, out = rehearse(CELLS[0], 0)
    assert rc != 0 and not last.startswith("{"), out[-2000:]


def grown_root(tmp_path, chips=1, learner="serial"):
    """A copy of the benchmark in which a cell, a configuration, a metric,
    a reader and a kernel file are ADDED as files, with their entries in
    BENCHMARK.json, and nothing that was there is edited."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmark", ignore=shutil.ignore_patterns(
        ".trace", "__pycache__", "tests"))
    b = root / "benchmark"
    cfg = json.load(open(b / "configs" / "higgs-l255-b63.json"))
    cfg["name"] = "added-l31"
    cfg["params"].update(num_leaves=31, max_bin=31)
    json.dump(cfg, open(b / "configs" / "added-l31.json", "w"))
    wl = json.load(open(b / "workloads" / (CELLS[0] + ".json")))
    wl.update(name="added-l31.rows", config="added-l31", chips=chips,
              tree_learner=learner, rehearse_rows=20000)
    json.dump(wl, open(b / "workloads" / "added-l31.rows.json", "w"))
    json.dump({"match": ["dot|fusion"], "except": []},
              open(b / "kernels" / "added.json", "w"))
    json.dump({"name": "added_ms", "unit": "ms", "layer": "kernels",
               "moves": "train_s_per_iter", "source": "device_trace",
               "reader": "added_reader", "args": {"kernels": "added"}},
              open(b / "metrics" / "added_ms.json", "w"))
    (b / "readers" / "added_reader.py").write_text(
        "from trace_reduce import pattern_seconds\n"
        "def read(ctx, kernels):\n"
        "    s, _ = pattern_seconds(ctx['trace']['op_seconds'],\n"
        "                           ctx['kernels'](kernels))\n"
        "    return 1e3 * s\n")
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": "added-l31", "source": "test",
                            "file": "benchmark/configs/added-l31.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "added-l31.rows", "config": "added-l31",
                              "traffic": "rows", "chips": chips,
                              "why": "test"})
    for m in spec["per_layer"]:       # as the contract has it for new cells
        m.setdefault("workloads", CELLS + ["added-l31.rows"])
    spec["per_layer"].append({
        "name": "added_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "train_s_per_iter", "workloads": ["added-l31.rows"]})
    json.dump(spec, open(root / "BENCHMARK.json", "w"))
    return str(root), spec


@pytest.mark.parametrize("trace", [0, 1])
def test_added_files_are_found_without_an_edit(tmp_path, trace):
    root, spec = grown_root(tmp_path)
    rc, last, out = rehearse("added-l31.rows", trace, root=root,
                             extra=("--rehearse",))
    assert rc == 0, out[-3000:]
    line = json.loads(last)
    want = cell_metrics(spec, "added-l31.rows", trace)
    assert set(line["metrics"]) == set(want)
    assert ("added_ms" in line["metrics"]) == bool(trace)
    assert line["correct"] is True, line["compared"]


@pytest.mark.parametrize("trace", [0, 1])
def test_four_virtual_devices_data_parallel(tmp_path, trace):
    root, spec = grown_root(tmp_path, chips=4, learner="data")
    rc, last, out = rehearse("added-l31.rows", trace, root=root, devices=4,
                             extra=("--rehearse",))
    assert rc == 0, out[-3000:]
    assert "tree_learner=data" in out
    line = json.loads(last)
    assert line["device"]["count"] == 4
    assert contract.problems(
        {k: v for k, v in line.items() if k != "compared"},
        cell_metrics(spec, "added-l31.rows", trace), bool(trace)) == []
    if trace:
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert line["correct"] is True, line["compared"]


def test_four_chip_cell_refuses_one_device(tmp_path):
    root, _ = grown_root(tmp_path, chips=4, learner="data")
    rc, last, out = rehearse("added-l31.rows", 0, root=root,
                             extra=("--rehearse",))
    assert rc != 0 and not last.startswith("{"), out[-2000:]


# ---- the timed path broken underneath: `correct` has to come out false -----

FAULTS = ["state_unchanged", "half_the_batch", "answer_altered"]


@pytest.mark.parametrize("fault", FAULTS + ["none"])
def test_a_broken_timed_path_reads_not_correct(fault):
    rc, last, out = rehearse(
        CELLS[0], 0, extra=("--rehearse",),
        program=(os.path.join(HERE, "faulty_run.py"), fault))
    assert rc == 0, out[-3000:]
    line = json.loads(last)
    assert line["correct"] is (fault == "none"), (fault, line["compared"])
