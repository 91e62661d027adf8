"""Test configuration: force an 8-device virtual CPU mesh before JAX import.

Mirrors the reference's distributed-without-cluster testing strategy
(tests/distributed/_test_distributed.py spawns N localhost processes); here N
virtual XLA host devices stand in for N TPU chips.
"""

import os
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
# One fixed compilation-cache directory for every process of the test
# session (pytest + the CLI subprocesses it spawns), apart from the
# package default <checkout>/.jax_cache that chip runs fill: the path is
# part of the cache key, so a directory that moves never hits.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 ".jax_cache", "tests"))
# Hermetic perf-trajectory store: tests (and every CLI subprocess they
# spawn — ab_bench/profile_* smokes inherit the env) must append their
# BENCH_obs/BENCH_history entries to a per-session scratch store, never
# to the committed repo-root BENCH_history.jsonl; real bench rounds run
# outside pytest and keep the default path.  Force-set, not setdefault:
# an operator with $BENCH_HISTORY_PATH exported for a bench round must
# not have a pytest run pollute that store with smoke-sized samples.
_OBS_SCRATCH = tempfile.mkdtemp(prefix="bench-obs-tests-")
os.environ["BENCH_HISTORY_PATH"] = os.path.join(_OBS_SCRATCH,
                                                "BENCH_history.jsonl")
os.environ["BENCH_OBS_PATH"] = os.path.join(_OBS_SCRATCH,
                                            "BENCH_obs.json")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np
import pytest

# ---------------------------------------------------------------------------
# Test-suite observability: per-file duration artifact.
#
# The full suite overruns the 870 s tier-1 window on the 2-core host
# (ROADMAP), so which lanes eat the window is operational data — every
# run drops a JSON artifact mapping test file -> {wall seconds, tests}
# so slow lanes can be found (and split/slow-marked) without rerunning
# under a profiler.  Path: $TEST_DURATIONS_OUT, else
# test_durations.json next to the rootdir (gitignored).
# ---------------------------------------------------------------------------
_DURATIONS: dict = {}
_SESSION_T0 = None


def pytest_sessionstart(session):
    global _SESSION_T0
    import time
    _SESSION_T0 = time.time()


def pytest_runtest_logreport(report):
    # setup + call + teardown all bill to the test's file: the window is
    # spent on wall-clock, not on call phases alone
    fname = report.nodeid.split("::", 1)[0]
    ent = _DURATIONS.setdefault(fname, {"seconds": 0.0, "tests": 0,
                                        "failed": 0})
    ent["seconds"] += float(getattr(report, "duration", 0.0) or 0.0)
    if report.when == "call":
        ent["tests"] += 1
        if report.failed:
            ent["failed"] += 1


def pytest_sessionfinish(session, exitstatus):
    import json
    import time
    if not _DURATIONS:
        return
    out = os.environ.get("TEST_DURATIONS_OUT")
    if out is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        out = os.path.join(root, "test_durations.json")
    doc = {
        "wall_s": round(time.time() - _SESSION_T0, 2)
        if _SESSION_T0 else None,
        "files": {f: {"seconds": round(v["seconds"], 2),
                      "tests": v["tests"], "failed": v["failed"]}
                  for f, v in sorted(_DURATIONS.items(),
                                     key=lambda kv: -kv[1]["seconds"])},
    }
    try:
        tmp = out + f".tmp{os.getpid()}"
        with open(tmp, "w") as fh:
            json.dump(doc, fh, indent=1)
            fh.write("\n")
        os.replace(tmp, out)
    except OSError:
        pass
    # the same numbers also land as one perfwatch trajectory entry —
    # in a PERSISTENT side store (gitignored, like test_durations.json
    # itself: $TEST_HISTORY_OUT, else BENCH_history_tests.jsonl at the
    # rootdir), NOT the per-session scratch BENCH_HISTORY_PATH above,
    # so the "pytest" series accumulates across sessions and
    # `perfwatch check --history BENCH_history_tests.jsonl` can gate
    # suite wall-clock and per-file lane costs (`_s`-suffixed = gated
    # time-like metrics); the test-count shape band keeps single-file
    # runs and full-suite runs in separate series
    try:
        from lightgbm_tpu.obs import regress
        hist_out = os.environ.get("TEST_HISTORY_OUT") or os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "BENCH_history_tests.jsonl")
        n_tests = sum(v["tests"] for v in _DURATIONS.values())
        metrics = {"wall_s": doc["wall_s"] or 0.0}
        metrics.update({f + "_s": v["seconds"]
                        for f, v in _DURATIONS.items()})
        # a failed or cut-short session (pytest -x, ctrl-C, collection
        # errors) has fast-but-bogus wall numbers: record it aborted so
        # the detector excludes it (regress.py contract), same as every
        # abort_guard producer
        regress.append_entry(
            "pytest", metrics,
            config={"files": len(_DURATIONS), "tests": n_tests},
            rows=n_tests, aborted=bool(exitstatus), path=hist_out)
    except Exception:
        pass                  # a failed append must never fail the run


def pytest_configure(config):
    # the tier-1 runner deselects with -m 'not slow' (ROADMAP);
    # registering the marker kills the per-test unknown-mark warning
    # and lets --strict-markers catch a typo'd trim mark that would
    # silently keep a slow test inside the 870 s window
    config.addinivalue_line(
        "markers",
        "slow: out-of-window lanes (tier-1 runs -m 'not slow'); each "
        "trim keeps a named fast in-window representative")


@pytest.fixture
def rng():
    return np.random.RandomState(42)
