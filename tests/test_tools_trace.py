"""Tier-1 lane for tools/trace_report.py (ISSUE-8): the --smoke
self-check must drive the continual drift drills at telemetry=trace,
export a VALID Chrome trace containing the tick/retrain/swap/rollback
spans plus runtime compile events, and exit 0 — and the summarize path
must read back what the exporters write (both formats)."""

import importlib.util
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(HERE, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trace_report_smoke(capsys):
    tool = _load_tool("trace_report")
    rc = tool.main(["--smoke", "--rows", "160"])
    out = capsys.readouterr().out.strip().splitlines()[-1]
    payload = json.loads(out)
    assert rc == 0, payload
    assert payload["ok"] is True
    assert payload["problems"] == []
    spans = payload["spans"]
    for name in ("continual.tick", "continual.retrain",
                 "continual.swap", "continual.rollback"):
        assert spans.get(name, 0) >= 1, (name, spans)
    assert payload["compiles"], "no runtime compile events in the trace"
    # the swap drill's kill+resume means the retrain span fired twice
    assert spans["continual.retrain"] >= 2


def test_trace_report_reads_both_export_formats(tmp_path, capsys):
    import lightgbm_tpu as lgb
    from lightgbm_tpu import obs

    tool = _load_tool("trace_report")
    sess = obs.get()
    sess.reset(mode="trace")
    try:
        rng = np.random.RandomState(1)
        X = rng.normal(size=(600, 5))
        y = X[:, 0] + 0.1 * rng.normal(size=600)
        lgb.train({"objective": "regression", "verbosity": -1,
                   "num_leaves": 7, "metric": ""},
                  lgb.Dataset(X, label=y), num_boost_round=3)
        paths = obs.export_session(str(tmp_path))
    finally:
        sess.reset(mode="off")

    for key in ("trace", "jsonl"):
        rc = tool.main([paths[key]])
        out = capsys.readouterr().out.strip().splitlines()[-1]
        summary = json.loads(out)
        assert rc == 0, summary
        assert summary["problems"] == []
        assert summary["spans"]["train.iteration"]["count"] == 3

    # a malformed artifact fails loudly
    bad = tmp_path / "bad.json"
    bad.write_text('{"traceEvents": [{"name": "x"}]}')
    rc = tool.main([str(bad)])
    capsys.readouterr()
    assert rc != 0


def test_trace_report_merge_distinct_pids(tmp_path, capsys):
    """`merge` combines per-rank exports into one Chrome trace with a
    distinct pid (and a process_name row) per input file."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu import obs

    tool = _load_tool("trace_report")
    sess = obs.get()
    rank_files = []
    try:
        for rank in range(3):
            sess.reset(mode="trace")
            rng = np.random.RandomState(rank)
            X = rng.normal(size=(400, 4))
            y = X[:, 0] + 0.1 * rng.normal(size=400)
            lgb.train({"objective": "regression", "verbosity": -1,
                       "num_leaves": 7, "metric": ""},
                      lgb.Dataset(X, label=y), num_boost_round=2)
            # mix the two export formats like a mixed-rank run would
            if rank % 2:
                p = str(tmp_path / f"rank{rank}.jsonl")
                obs.export_jsonl(sess, p)
            else:
                p = str(tmp_path / f"rank{rank}.json")
                obs.export_chrome_trace(sess, p)
            rank_files.append(p)
    finally:
        sess.reset(mode="off")

    out_path = str(tmp_path / "merged.json")
    rc = tool.main(["merge", "-o", out_path] + rank_files)
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0, summary
    assert summary["problems"] == []
    assert summary["pids"] == [1, 2, 3]
    # every rank's spans merged: 3 ranks x 2 iterations
    assert summary["spans"]["train.iteration"]["count"] == 6

    with open(out_path) as fh:
        doc = json.load(fh)
    names = [(e.get("pid"), e["args"]["name"])
             for e in doc["traceEvents"]
             if e.get("ph") == "M" and e.get("name") == "process_name"]
    assert len(names) == 3 and len({p for p, _ in names}) == 3
    # the merged artifact itself validates through the normal path
    rc = tool.main([out_path])
    capsys.readouterr()
    assert rc == 0


def test_trace_report_device_joins_a_tpu_trace_with_a_scope_table(
        tmp_path, capsys):
    """`device` on the trace recorded on a v5e for the benchmark's tests
    (three calls of one jitted step, a sleep between them): seconds per
    phase by the module each operation ran in, and the idle gaps put
    down to the host span that covers them."""
    trace = os.path.join(HERE, "benchmark", "tests", "data",
                         "small_tpu.xplane.pb")
    step = {"convolution_sine_fusion.2": "histogram", "fusion": "search",
            "copy.9": None}
    # a second program that gives `fusion` another phase: the trace's
    # module line says every operation ran in jit_step
    table = {"modules": {"train.fused_step": "jit_step",
                         "train.scores_read": "jit_other"},
             "tables": {"train.fused_step": step,
                        "train.scores_read": {"fusion": "scores_read"}}}
    path = tmp_path / "table.json"
    path.write_text(json.dumps(table))
    tool = _load_tool("trace_report")
    rc = tool.main(["device", trace, "--table", str(path),
                    "--span-prefix", "$time", "--json"])
    rep = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    with open(os.path.join(HERE, "benchmark", "tests", "data",
                           "small_tpu.json")) as f:
        want = json.load(f)
    assert abs(rep["busy_s"] - want["busy_s"]) < 1e-9
    assert abs(rep["window_s"] - want["window_s"]) < 1e-9
    phases = {p["phase"]: p for p in rep["phases"]}
    assert set(phases) == {"histogram", "search", "unattributed"}
    assert abs(phases["histogram"]["seconds"] + phases["search"]["seconds"]
               - want["pattern_s"]) < 1e-9
    assert phases["histogram"]["top"][0][0] == \
        "histogram/convolution_sine_fusion.2"
    assert abs(sum(p["seconds"] for p in rep["phases"])
               - rep["busy_s"]) < 1e-9
    gaps = rep["idle_gaps"]
    assert len(gaps) == 2 and all(g["ms"] > 10 for g in gaps)
    assert {g["during"] for g in gaps} == {"$time sleep"}
    # without a table every operation reads unattributed; the text form
    assert tool.main(["device", trace]) == 0
    text = capsys.readouterr().out
    assert text.startswith("device 0: window") and "unattributed" in text
