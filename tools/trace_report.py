"""Render/summarize a lightgbm_tpu telemetry trace.

Reads either artifact the obs exporters write — a Chrome-trace
``trace.json`` (the ``traceEvents`` object Perfetto loads) or a
``telemetry.jsonl`` event log — validates its structure, and prints ONE
JSON summary line: span counts + total/mean durations by name, compile
events, counter tracks, and any validation problems (non-zero exit when
the artifact is malformed).

    python tools/trace_report.py out/trace.json
    python tools/trace_report.py out/telemetry.jsonl
    python tools/trace_report.py merge -o merged.json r0.jsonl r1.jsonl
    python tools/trace_report.py device <xplane or dir> --table table.json
    python tools/trace_report.py --smoke      # tier-1 self-check

``merge`` combines multiple per-rank/per-process exports (either
format) into ONE Chrome trace with a distinct pid per input file —
multi-process mesh runs write one telemetry file per rank, and
Perfetto shows them as separate process tracks only when their pids
differ (they usually don't: every rank reports its own os.getpid).

``device`` reads a ``jax.profiler`` trace (``*.xplane.pb``) and the scope
table that ``lightgbm_tpu.obs.scopes.dump_scope_table(path)`` wrote in the
traced process, and prints, per ``lgbm.*`` phase, the device seconds, the
share of the window and the top operations as ``phase/instruction`` (on a
mesh the collectives of the tree are the phase ``hist_sync``); then
every idle gap over 1 ms, put down to the innermost host span (``train.*``
with ``LIGHTGBM_TPU_TELEMETRY=trace``) that covers it.

``--smoke`` runs the continual drift drills (swap + rollback, with
``health=counters`` so drift-attribution marks ride the trace) at
``telemetry=trace``, exports the Chrome trace, validates it, asserts
the spans an operator needs are all present — ``continual.tick`` /
``continual.retrain`` / ``continual.swap`` / ``continual.rollback`` —
plus at least one runtime compile event and the ``health.drift``
attribution mark, and validates a BENCH_obs.json v3 artifact
round-trip (schema + health section).
"""

import argparse
import json
import os
import sys
from typing import Any, Dict, List

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_KNOWN_PH = {"X", "B", "E", "C", "i", "I", "M", "s", "t", "f"}


# ---------------------------------------------------------------------------
# loading + validation
# ---------------------------------------------------------------------------
def load_events(path: str) -> List[Dict[str, Any]]:
    """Events from a Chrome-trace object or a JSONL export."""
    with open(path, encoding="utf-8") as fh:
        head = fh.read(1)
        fh.seek(0)
        if head == "{":
            first = fh.readline()
            rest = fh.read()
            if rest.strip():
                # JSONL whose first line is the report object
                events = []
                for ln in rest.splitlines():
                    if ln.strip():
                        events.append(json.loads(ln))
                json.loads(first)           # header must parse too
                return events
            doc = json.loads(first)
            return list(doc.get("traceEvents", []))
        return [json.loads(ln) for ln in fh if ln.strip()]


def validate(events: List[Dict[str, Any]]) -> List[str]:
    """Structural problems (Chrome-trace requirements the exporter
    guarantees; a regression here breaks Perfetto loading)."""
    problems = []
    if not events:
        problems.append("no events")
    for i, ev in enumerate(events):
        ph = ev.get("ph")
        if ph is None:
            problems.append(f"event {i} missing ph")
            continue
        if ph not in _KNOWN_PH:
            problems.append(f"event {i} unknown ph {ph!r}")
        if ph != "M" and "ts" not in ev:
            problems.append(f"event {i} ({ev.get('name')}) missing ts")
        if ph == "X" and (not isinstance(ev.get("dur"), int)
                          or ev["dur"] < 0):
            problems.append(f"event {i} ({ev.get('name')}) bad dur")
        if ph != "M" and "name" not in ev:
            problems.append(f"event {i} missing name")
        if len(problems) > 20:
            problems.append("... (truncated)")
            break
    return problems


def summarize(events: List[Dict[str, Any]]) -> Dict[str, Any]:
    spans: Dict[str, Dict[str, Any]] = {}
    compiles: Dict[str, int] = {}
    counters: Dict[str, float] = {}
    marks: Dict[str, int] = {}
    for ev in events:
        ph = ev.get("ph")
        name = ev.get("name", "?")
        if ph == "X":
            s = spans.setdefault(name, {"count": 0, "total_us": 0})
            s["count"] += 1
            s["total_us"] += int(ev.get("dur", 0))
        elif ph in ("i", "I") and name.startswith("compile:"):
            key = name[len("compile:"):]
            compiles[key] = compiles.get(key, 0) + 1
        elif ph in ("i", "I"):
            # non-compile instant marks (e.g. the health layer's
            # flight-recorder / skew / drift-attribution events)
            marks[name] = marks.get(name, 0) + 1
        elif ph == "C":
            args = ev.get("args") or {}
            counters[name] = args.get("value", args)
    for s in spans.values():
        s["mean_us"] = round(s["total_us"] / max(s["count"], 1), 1)
    return {"events": len(events),
            "spans": dict(sorted(spans.items())),
            "compiles": dict(sorted(compiles.items())),
            "counters": dict(sorted(counters.items())),
            "marks": dict(sorted(marks.items()))}


# ---------------------------------------------------------------------------
# merge: per-rank exports -> one Chrome trace with distinct pids
# ---------------------------------------------------------------------------
def merge_traces(inputs: List[str], out_path: str) -> Dict[str, Any]:
    """Combine per-rank/per-process telemetry exports (JSONL or Chrome
    trace) into one Chrome trace.  Every rank reports its own
    ``os.getpid()``, which collide across hosts and hide the per-rank
    structure — each input file gets its OWN pid track (1-based input
    order) plus a ``process_name`` metadata row naming the source
    file, so Perfetto renders one labeled track per rank."""
    merged: List[Dict[str, Any]] = []
    for i, path in enumerate(inputs):
        pid = i + 1
        merged.append({"ph": "M", "name": "process_name", "pid": pid,
                       "ts": 0,
                       "args": {"name": f"rank{i}:"
                                f" {os.path.basename(path)}"}})
        for ev in load_events(path):
            if ev.get("ph") == "M" and ev.get("name") == "process_name":
                continue               # replaced by the per-file row
            ev = dict(ev)
            ev["pid"] = pid
            merged.append(ev)
    doc = {
        "traceEvents": merged,
        "displayTimeUnit": "ms",
        "otherData": {"exporter": "tools/trace_report.py merge",
                      "merged_from": [os.path.basename(p)
                                      for p in inputs]},
    }
    tmp = out_path + f".tmp{os.getpid()}"
    with open(tmp, "w", encoding="utf-8") as fh:
        json.dump(doc, fh)
    os.replace(tmp, out_path)
    return doc


def merge_main(argv: List[str]) -> int:
    ap = argparse.ArgumentParser(
        prog="trace_report.py merge",
        description="merge per-rank telemetry exports into one Chrome "
                    "trace with distinct pids")
    ap.add_argument("inputs", nargs="+",
                    help="per-rank trace.json / telemetry.jsonl files")
    ap.add_argument("-o", "--out", required=True,
                    help="merged Chrome trace output path")
    args = ap.parse_args(argv)
    doc = merge_traces(args.inputs, args.out)
    events = [e for e in doc["traceEvents"] if e.get("ph") != "M"]
    problems = validate(events)
    pids = sorted({e.get("pid") for e in events})
    out = summarize(events)
    out["problems"] = problems
    out["path"] = args.out
    out["pids"] = pids
    out["inputs"] = len(args.inputs)
    if len(pids) != len(args.inputs):
        out["problems"].append(
            f"expected {len(args.inputs)} distinct pids, got {len(pids)}")
    print(json.dumps(out))
    return 1 if out["problems"] else 0


# ---------------------------------------------------------------------------
# device: a profiler trace joined with the program's scope table
# ---------------------------------------------------------------------------
def module_intervals(xplane: str) -> Dict[int, List[Any]]:
    """{device: [(HLO module name, start_ns, end_ns)]} from the ``XLA
    Modules`` line of each device plane (events are named
    ``<module>(<fingerprint>)``)."""
    import trace_reduce as tr
    from jax.profiler import ProfileData
    out: Dict[int, List[Any]] = {}
    for plane in ProfileData.from_file(xplane).planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        for line in plane.lines if m else ():
            if line.name == "XLA Modules":
                out.setdefault(int(m.group(1)), []).extend(
                    (e.name.split("(", 1)[0], e.start_ns,
                     e.start_ns + e.duration_ns) for e in line.events)
    return out


GAP_MS = 1.0     # idle gaps shorter than this are not listed


def device_report(path: str, table: Dict[str, Any], span_prefix: str,
                  top: int) -> Dict[str, Any]:
    """The ``device`` report of the trace's fullest device.  The trace is
    reduced by the benchmark's own code (benchmark/trace_reduce.py: which
    events are operations, busy time, gaps) and names get their phase by
    the benchmark reader's rule where the trace names no module."""
    import bisect
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    sys.path[:0] = [bench, os.path.join(bench, "readers")]
    import device_phase
    import trace_reduce as tr
    xplane = tr.find_xplane(path) if os.path.isdir(path) else path
    ops, spans = tr.read_trace(xplane, span_prefix=span_prefix)
    if not ops:   # a CPU run: the host events that carry an hlo_op
        ops, spans = tr.read_trace(xplane, host_ops=True,
                                   span_prefix=span_prefix)
    if not ops:
        raise SystemExit(f"{path}: the trace holds no device operation")
    dev = max(ops, key=lambda d: sum(b - a for _, a, b in ops[d]))
    red = tr.reduce_events({dev: ops[dev]})
    tables = table.get("tables", {})
    program_of = {m: p for p, m in table.get("modules", {}).items()}
    agreed = device_phase.phase_by_name(tables.values())
    mods = sorted(module_intervals(xplane).get(dev, []), key=lambda e: e[1])
    starts = [a for _, a, _ in mods]

    def phase_of(name, a):
        i = bisect.bisect_right(starts, a) - 1
        if i >= 0 and a < mods[i][2] and mods[i][0] in program_of:
            return tables[program_of[mods[i][0]]].get(name)
        return agreed.get(name)

    per_phase: Dict[str, Dict[str, float]] = {}
    for name, a, b in ops[dev]:
        if not tr.CONTAINERS.match(name):
            per = per_phase.setdefault(phase_of(name, a) or "unattributed",
                                       {})
            per[name] = per.get(name, 0.0) + (b - a) / 1e9

    def doing(a, b):
        mid = (a + b) / 2
        cover = [(e - s, n) for n, s, e in spans if s <= mid <= e]
        return min(cover)[1] if cover else "no " + span_prefix + "* span"

    idle = [{"ms": secs * 1e3, "during": doing(a, b)}
            for secs, a, b in red["gaps"] if secs * 1e3 > GAP_MS]
    by_span: Dict[str, float] = {}
    for g in idle:
        by_span[g["during"]] = by_span.get(g["during"], 0.0) + g["ms"] / 1e3
    window = red["window_s"]
    return {
        "path": path, "device": dev, "window_s": window,
        "busy_s": red["busy_s"],
        "idle_share_pct": 100.0 * (1.0 - red["busy_s"] / window),
        "phases": [
            {"phase": ph, "seconds": sum(per.values()),
             "share_pct": 100.0 * sum(per.values()) / window,
             "top": [[f"{ph}/{n}", s] for n, s in sorted(
                 per.items(), key=lambda kv: -kv[1])[:top]]}
            for ph, per in sorted(per_phase.items(),
                                  key=lambda kv: -sum(kv[1].values()))],
        "idle_gaps": idle[:20],
        "idle_s_by_span": dict(sorted(by_span.items(),
                                      key=lambda kv: -kv[1])),
    }


def device_main(argv: List[str]) -> int:
    ap = argparse.ArgumentParser(
        prog="trace_report.py device",
        description="device seconds per lgbm.* phase and idle gaps per "
                    "host span, from a profiler trace and the scope table "
                    "obs.scopes.dump_scope_table() wrote")
    ap.add_argument("trace", help="an .xplane.pb file or a directory "
                                  "that holds one")
    ap.add_argument("--table", help="the JSON file dump_scope_table wrote; "
                                    "without it every operation reads "
                                    "unattributed")
    ap.add_argument("--span-prefix", default="train.",
                    help="host spans that name the idle gaps")
    ap.add_argument("--top", type=int, default=5,
                    help="operations listed per phase")
    ap.add_argument("--json", action="store_true",
                    help="print the report as one JSON object")
    args = ap.parse_args(argv)
    table: Dict[str, Any] = {}
    if args.table:
        with open(args.table, encoding="utf-8") as fh:
            table = json.load(fh)
    rep = device_report(args.trace, table, args.span_prefix, args.top)
    if args.json:
        print(json.dumps(rep))
        return 0
    print(f"device {rep['device']}: window {rep['window_s']:.4f} s, busy "
          f"{rep['busy_s']:.4f} s, idle {rep['idle_share_pct']:.2f}%")
    for ph in rep["phases"]:
        print(f"{ph['phase']:<14}{ph['seconds']:>9.4f} s "
              f"{ph['share_pct']:>6.2f}%  " + "  ".join(
                  f"{n} {secs:.4f}" for n, secs in ph["top"]))
    print(f"idle gaps over {GAP_MS:g} ms: {len(rep['idle_gaps'])} "
          "listed, seconds by span: " + (", ".join(
              f"{n} {secs:.4f}" for n, secs in
              rep["idle_s_by_span"].items()) or "none"))
    for g in rep["idle_gaps"]:
        print(f"  {g['ms']:>9.3f} ms during {g['during']}")
    return 0


# ---------------------------------------------------------------------------
# --smoke: drive a drill at telemetry=trace and validate its trace
# ---------------------------------------------------------------------------
_REQUIRED_SPANS = ("continual.tick", "continual.retrain",
                   "continual.swap", "continual.rollback")


def smoke(rows: int) -> int:
    import shutil
    import tempfile

    from lightgbm_tpu import obs
    from lightgbm_tpu.continual import run_drift_drill
    from lightgbm_tpu.obs import benchio
    from lightgbm_tpu.obs import health as obs_health

    sess = obs.get()
    sess.reset(mode="trace")
    health_prev = obs_health.get().mode
    obs_health.get().set_mode("counters")
    work = tempfile.mkdtemp(prefix="trace-report-")
    problems: List[str] = []
    try:
        # swap drill: tick + detection + (killed-once, resumed) retrain
        # + gated swap spans; rollback drill adds the rollback span.
        # health=counters rides along so the regression tick emits its
        # drift-attribution mark onto the trace ring
        swap = run_drift_drill("swap", rows=rows, drift_at=4,
                               post_ticks=5, checkpoint_dir=work,
                               params={"health": "counters"})
        roll = run_drift_drill("rollback", rows=rows, drift_at=3,
                               post_ticks=5,
                               params={"health": "counters"})
        if swap.get("swap_tick") is None:
            problems.append("swap drill produced no hot-swap")
        if roll.get("rollback_tick") is None:
            problems.append("rollback drill never rolled back")
        detect = next((t for t in swap.get("ticks", [])
                       if t.get("drift_detected")), None)
        skew_top = (detect or {}).get("skew_top") or []
        if not skew_top:
            problems.append("swap drill's regression tick carried no "
                            "skew attribution")
        obs.memory_snapshot()
        trace_path = os.path.join(work, "trace.json")
        obs.export_chrome_trace(sess, trace_path)
        events = load_events(trace_path)
        problems += validate(events)
        summary = summarize(events)
        for name in _REQUIRED_SPANS:
            if name not in summary["spans"]:
                problems.append(f"required span missing: {name}")
        if not summary["compiles"]:
            problems.append("no runtime compile events recorded")
        if "health.drift" not in summary["marks"]:
            problems.append("health.drift attribution mark missing "
                            "from the trace")
        # BENCH_obs round trip (schema v3 since ISSUE-11): write an
        # artifact carrying the drill's health section, read it back,
        # validate the schema
        obs_path = os.path.join(work, "BENCH_obs.json")
        benchio.write_bench_obs(
            "trace_report.smoke", {"rows": rows},
            {"swap_tick": swap.get("swap_tick"),
             "rollback_tick": roll.get("rollback_tick")},
            health={"skew_top": skew_top}, path=obs_path,
            # a validation smoke is not a bench round: keep its
            # trajectory entry in the same scratch dir, never in the
            # committed BENCH_history.jsonl
            history_path=os.path.join(work, "BENCH_history.jsonl"))
        try:
            with open(obs_path) as fh:
                doc = json.load(fh)
            problems += [f"BENCH_obs: {p}"
                         for p in benchio.validate_bench_obs(doc)]
        except (OSError, ValueError) as exc:
            problems.append(f"BENCH_obs unreadable: {exc}")
        print(json.dumps({"metric": "trace_report_smoke",
                          "ok": not problems,
                          "trace_events": summary["events"],
                          "spans": {k: v["count"]
                                    for k, v in summary["spans"].items()},
                          "compiles": summary["compiles"],
                          "marks": summary["marks"],
                          "problems": problems}))
        return 1 if problems else 0
    finally:
        sess.reset(mode="off")
        obs_health.get().set_mode(health_prev)
        shutil.rmtree(work, ignore_errors=True)


def main(argv=None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    if argv and argv[0] == "merge":
        return merge_main(argv[1:])
    if argv and argv[0] == "device":
        return device_main(argv[1:])
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("trace", nargs="?", help="trace.json or telemetry.jsonl")
    ap.add_argument("--smoke", action="store_true",
                    help="run the continual drills at telemetry=trace "
                         "and validate the exported Chrome trace")
    ap.add_argument("--rows", type=int, default=192,
                    help="--smoke: rows per drill tick")
    args = ap.parse_args(argv)
    if args.smoke:
        return smoke(args.rows)
    if not args.trace:
        ap.error("give a trace file or --smoke")
    events = load_events(args.trace)
    problems = validate(events)
    out = summarize(events)
    out["problems"] = problems
    out["path"] = args.trace
    print(json.dumps(out))
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
