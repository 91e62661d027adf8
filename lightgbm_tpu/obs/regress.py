"""Persisted benchmark trajectory + noise-aware perf-regression gate.

Twelve PERF.md rounds of honest measurements lived as hand-transcribed
prose, and every ``BENCH_obs.json`` artifact was written once and
discarded — nothing could *detect* a perf regression.  This module is
the substrate that fixes that:

* **Trajectory store** — ``BENCH_history.jsonl``, an append-only JSONL
  log at the repo root (``$BENCH_HISTORY_PATH`` overrides).  Appends go
  through :func:`lightgbm_tpu.obs.exporters._atomic_append` (one
  ``O_APPEND`` write per record, torn-tail detach), so concurrent
  writers interleave whole lines and a crash mid-write loses at most
  the torn line — readers skip unparseable lines and keep going.
* **Hardware/config fingerprint** — every entry is keyed by the things
  that legitimately shift numbers: device kind & count, CPU cores,
  jax/jaxlib versions, the x64 flag, a log2 dataset shape band, and the
  perf-relevant ``tpu_*`` knobs.  Series only ever compare
  same-fingerprint runs, so a 2-core CPU trajectory never gates a TPU
  round and a 16k-row smoke never gates a 10.5M-row headline.
* **Noise-aware change detector** — the exact statistic PERF.md rounds
  10–12 compute by hand: the latest sample vs the median/MAD of its
  same-fingerprint predecessors, flagged only past
  ``max(z * 1.4826 * MAD, floor * median)`` and only after a
  ``min_samples`` warmup, so 2-core CPU noise (measured run-to-run MAD
  ~2–6%) does not false-alarm.

``tools/perfwatch.py`` is the CLI on top (``check`` / ``report`` /
``drill``); :func:`lightgbm_tpu.obs.benchio.write_bench_obs` appends a
trajectory entry for every BENCH_obs artifact, which wires bench.py,
ab_bench.py (all modes), the profile_* tools and the conftest duration
artifact through this store.

The module is host-only by contract: no device ops, no syncs — pinned
by the jaxlint tier-B ``perfwatch.off`` budget (same zero-HLO contract
as ``telemetry.off``) and by JL001 scope covering this file.

Clock injection (``set_clock`` / ``StepClock`` / ``scaled_clock``)
exists for the ``perfwatch drill`` and tests: a planted slowdown is a
scaled clock, never a sleep, so the drill is deterministic.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import math
import os
import statistics
import time
from dataclasses import dataclass
from typing import (Any, Callable, Dict, Iterable, List, Optional,
                    Sequence, Tuple)

from .exporters import _atomic_append

SCHEMA = "lightgbm-tpu/bench-history/v1"
DEFAULT_FILENAME = "BENCH_history.jsonl"

# defaults of the change detector (CLI-overridable): warmup sample
# count before anything can regress, the MAD z multiplier, and the
# relative floor that keeps zero-MAD micro-histories from flagging on
# trivial jitter.  Floor 15% sits above the 2-core host's measured
# run-to-run spread (PERF.md: MAD ~2% train / ~6% predict) and far
# below any slowdown worth a round.
MIN_SAMPLES = 3
Z_SCORE = 4.0
FLOOR_PCT = 15.0
_MAD_TO_SIGMA = 1.4826

# booster/config knobs that legitimately shift perf numbers enough to
# split trajectories; anything else (seeds, verbosity, paths) must NOT
# fork the series
_FINGERPRINT_KNOBS = (
    "tpu_row_chunk", "tpu_chunk_policy", "tpu_frontier_k",
    "tpu_megakernel", "tpu_kernel_interpret",
    "construct_device", "tree_learner", "num_leaves", "max_bin",
    "telemetry", "health",
)
# producer-config spellings of the same knobs (bench.py/ab_bench.py
# record "leaves"): without the alias, leaf-count changes would not
# fork the series and an intentional config change would false-alarm
_KNOB_ALIASES = {"leaves": "num_leaves"}

__all__ = [
    "SCHEMA", "MIN_SAMPLES", "Z_SCORE", "FLOOR_PCT", "Finding",
    "default_path", "shape_band", "fingerprint", "fingerprint_key",
    "append_entry", "read_history", "evaluate", "regressions",
    "render_report", "metric_direction", "recording", "set_clock",
    "clock", "StepClock", "scaled_clock",
]


# ---------------------------------------------------------------------------
# store
# ---------------------------------------------------------------------------
def default_path() -> str:
    env = os.environ.get("BENCH_HISTORY_PATH")
    if env:
        return env
    repo_root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    return os.path.join(repo_root, DEFAULT_FILENAME)


def shape_band(n: Optional[int]) -> Optional[str]:
    """Log2 band of a dataset dimension (``2^17`` holds 65537..131072):
    runs only share a trajectory when their data sits in the same
    power-of-two band — fine enough to separate a smoke from a
    headline, coarse enough that a 5% row-count tweak stays in-series."""
    if n is None or n <= 0:
        return None
    return f"2^{max(int(math.ceil(math.log2(n))), 0)}"


def fingerprint(config: Optional[Dict[str, Any]] = None,
                rows: Optional[int] = None,
                features: Optional[int] = None,
                extra: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """The hardware/software/shape identity a measurement is only
    comparable within.  ``extra`` lets a producer fork its series on
    experiment parameters the knob list cannot know (e.g. ab_bench's
    per-arm overrides — two different A/B experiments must never share
    a trajectory).  jax is imported lazily and optionally so the store
    stays usable from processes that never touch a backend."""
    device_kind, device_count, backend = "none", 0, "none"
    jax_ver, jaxlib_ver, x64 = None, None, False
    try:
        import jax
        backend = jax.default_backend()
        devs = jax.devices()
        device_count = len(devs)
        device_kind = getattr(devs[0], "device_kind", backend)
        jax_ver = jax.__version__
        x64 = bool(jax.config.jax_enable_x64)
        import jaxlib
        jaxlib_ver = getattr(jaxlib, "__version__", None)
    except Exception:
        pass
    cfg = config or {}
    if rows is None:
        rows = cfg.get("rows")
    if features is None:
        features = cfg.get("features")
    knobs = {k: cfg[k] for k in _FINGERPRINT_KNOBS if k in cfg}
    for alias, canon in _KNOB_ALIASES.items():
        if canon not in knobs and alias in cfg:
            knobs[canon] = cfg[alias]
    if extra:
        knobs["extra"] = extra
    return {
        "device_kind": str(device_kind),
        "device_count": int(device_count),
        "backend": str(backend),
        "cpu_count": int(os.cpu_count() or 0),
        "jax": jax_ver,
        "jaxlib": jaxlib_ver,
        "x64": bool(x64),
        "shape_band": {"rows": shape_band(rows),
                       "features": shape_band(features)},
        "knobs": knobs,
    }


def fingerprint_key(fp: Dict[str, Any]) -> str:
    """Stable 12-hex digest of the canonicalized fingerprint — the
    grouping key of the trajectory."""
    canon = json.dumps(fp, sort_keys=True, default=str)
    return hashlib.sha1(canon.encode("utf-8")).hexdigest()[:12]


def append_entry(tool: str, metrics: Dict[str, Any],
                 config: Optional[Dict[str, Any]] = None,
                 fingerprint_doc: Optional[Dict[str, Any]] = None,
                 rows: Optional[int] = None,
                 features: Optional[int] = None,
                 aborted: bool = False,
                 path: Optional[str] = None) -> Dict[str, Any]:
    """Append one trajectory record and return it.  ``metrics`` keeps
    only finite numeric scalars; ``aborted`` records that the measured
    tool died — the detector excludes such entries, but the trajectory
    keeps the evidence."""
    fp = fingerprint_doc or fingerprint(config, rows, features)
    clean: Dict[str, float] = {}
    for k, v in (metrics or {}).items():
        if isinstance(v, bool) or not isinstance(v, (int, float)):
            continue
        v = float(v)
        if math.isfinite(v):
            clean[str(k)] = v
    entry = {
        "schema": SCHEMA,
        "unix_time": round(time.time(), 3),
        "tool": str(tool),
        "fingerprint": fp,
        "fingerprint_key": fingerprint_key(fp),
        "metrics": clean,
        "aborted": bool(aborted),
    }
    if config:
        entry["config"] = config
    _atomic_append(path or default_path(),
                   json.dumps(entry, sort_keys=True, default=str))
    return entry


def read_history(path: Optional[str] = None
                 ) -> Tuple[List[Dict[str, Any]], int]:
    """All parseable trajectory entries (append order) plus the count
    of skipped lines — torn tails, interleaving damage and foreign
    lines degrade to data loss of that one line, never a read error."""
    path = path or default_path()
    entries: List[Dict[str, Any]] = []
    skipped = 0
    if not os.path.exists(path):
        return entries, skipped
    with open(path, encoding="utf-8", errors="replace") as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                doc = json.loads(line)
            except ValueError:
                skipped += 1
                continue
            if (not isinstance(doc, dict) or doc.get("schema") != SCHEMA
                    or not isinstance(doc.get("metrics"), dict)):
                skipped += 1
                continue
            entries.append(doc)
    return entries, skipped


# ---------------------------------------------------------------------------
# injectable clock + measured recording (the drill's substrate)
# ---------------------------------------------------------------------------
_CLOCK: Callable[[], float] = time.perf_counter


def set_clock(fn: Optional[Callable[[], float]] = None) -> None:
    """Swap the wall clock the recording helper reads (faultinject
    style: process-local, explicit, tests/drills only; ``None``
    restores ``time.perf_counter``)."""
    global _CLOCK
    _CLOCK = fn or time.perf_counter


def clock() -> float:
    return _CLOCK()


class StepClock:
    """Deterministic clock: every read advances a fixed ``dt`` — a
    recorded block measures exactly ``dt`` regardless of host load, so
    drill runs are reproducible bit-for-bit."""

    def __init__(self, dt: float, start: float = 0.0):
        self.dt = float(dt)
        self.now = float(start)

    def __call__(self) -> float:
        self.now += self.dt
        return self.now


def scaled_clock(scale: float,
                 base: Optional[Callable[[], float]] = None
                 ) -> Callable[[], float]:
    """A clock running ``scale`` times faster than ``base`` — the
    planted slowdown of ``perfwatch drill``: a 3x-scaled clock makes an
    unchanged workload *measure* 3x slower, with no sleeps and no
    dependence on the host."""
    base = base or time.perf_counter
    origin = base()

    def _scaled() -> float:
        return origin + (base() - origin) * float(scale)

    return _scaled


@contextlib.contextmanager
def recording(tool: str, metric: str = "wall_s",
              config: Optional[Dict[str, Any]] = None,
              path: Optional[str] = None, **append_kw):
    """Measure the block on the (injectable) clock and append one
    trajectory entry on exit.  The yielded dict takes extra metrics;
    if the block raises, the entry is still appended with
    ``aborted: true`` (the export-on-failure contract) and the error
    propagates."""
    def _append(metrics: Dict[str, Any], aborted: bool) -> None:
        # a failed STORE write must neither sink a finished measurement
        # nor replace the measured block's own exception
        try:
            append_entry(tool, metrics, config=config, aborted=aborted,
                         path=path, **append_kw)
        except OSError as exc:
            from ..utils import log
            log.warning("could not append %s: %s",
                        path or default_path(), exc)

    extra: Dict[str, Any] = {}
    t0 = clock()
    try:
        yield extra
    except BaseException:
        extra[metric] = clock() - t0
        _append(extra, True)
        raise
    extra[metric] = clock() - t0
    _append(extra, False)


# ---------------------------------------------------------------------------
# noise-aware change detection
# ---------------------------------------------------------------------------
# direction of "worse": +1 when a higher value is a regression (time-
# like metrics), -1 when a lower value is (throughput-like).  Metrics
# matching neither are recorded and reported but never gate — gating on
# a metric whose good direction is unknown manufactures false alarms.
_WORSE_HIGH_SUFFIXES = ("_s", "_ms", "_us", "_s_per_iter", "_seconds",
                        "_s_per_mrow")
_WORSE_LOW_SUFFIXES = ("_per_s", "_per_sec", "speedup")
_WORSE_LOW_NAMES = {"vs_baseline"}
# memory metrics: peak/extra footprint is higher-worse.  Checked BEFORE
# the "delta" report-only rule — a "peak_rss_delta_mb" is a bounded
# footprint measurement (how much a phase grew RSS), not a signed
# near-zero A/B difference, so it must gate.
_WORSE_HIGH_MEM_SUFFIXES = ("_mb", "_rss", "_rss_kb", "_bytes")


def metric_direction(name: str) -> int:
    if name.endswith(_WORSE_HIGH_MEM_SUFFIXES):
        return 1
    if "delta" in name:
        # signed difference metrics (ab_bench paired_delta_s) center on
        # ~0, so the relative floor vanishes and small-n MAD alone
        # would gate sub-millisecond jitter — report, never gate
        return 0
    if name in _WORSE_LOW_NAMES or name.endswith(_WORSE_LOW_SUFFIXES):
        return -1
    if name.endswith(_WORSE_HIGH_SUFFIXES) or name == "wall_s":
        return 1
    return 0


@dataclass
class Finding:
    """One (fingerprint, tool, metric) series judged at its latest
    sample."""
    fingerprint_key: str
    tool: str
    metric: str
    value: float
    median: float           # of the prior same-fingerprint samples
    mad: float
    n_prior: int
    direction: int          # +1 higher-is-worse, -1 lower-is-worse, 0 ungated
    threshold: float        # absolute excess-over-median that would flag
    regressed: bool
    status: str             # "ok" | "warmup" | "ungated" | "REGRESSED" | "improved"

    @property
    def delta_pct(self) -> float:
        if self.median == 0:
            return 0.0
        return 100.0 * (self.value - self.median) / abs(self.median)

    def render(self) -> str:
        return (f"[{self.status}] {self.tool}/{self.metric} "
                f"@{self.fingerprint_key}: {self.value:.6g} vs median "
                f"{self.median:.6g} ±{self.mad:.2g} MAD over "
                f"{self.n_prior} run(s) ({self.delta_pct:+.1f}%)")

    def to_json(self) -> str:
        return json.dumps({
            "fingerprint_key": self.fingerprint_key, "tool": self.tool,
            "metric": self.metric, "value": self.value,
            "median": self.median, "mad": self.mad,
            "n_prior": self.n_prior, "direction": self.direction,
            "threshold": self.threshold, "regressed": self.regressed,
            "status": self.status,
            "delta_pct": round(self.delta_pct, 2)}, sort_keys=True)


def _median(values: Sequence[float]) -> float:
    return float(statistics.median(values))


def _series(entries: Iterable[Dict[str, Any]]
            ) -> Dict[Tuple[str, str, str], List[float]]:
    """(fingerprint_key, tool, metric) -> samples in append order,
    aborted entries excluded (a crashed run has no comparable number)."""
    out: Dict[Tuple[str, str, str], List[float]] = {}
    for e in entries:
        if e.get("aborted"):
            continue
        key_base = (str(e.get("fingerprint_key")), str(e.get("tool")))
        for metric, value in e.get("metrics", {}).items():
            if isinstance(value, bool) or not isinstance(value,
                                                         (int, float)):
                continue
            out.setdefault(key_base + (metric,), []).append(float(value))
    return out


def evaluate(entries: Iterable[Dict[str, Any]],
             min_samples: int = MIN_SAMPLES, z: float = Z_SCORE,
             floor_pct: float = FLOOR_PCT) -> List[Finding]:
    """Judge the LATEST sample of every series against the median/MAD
    of its predecessors — the paired statistic PERF.md rounds 10–12
    compute by hand, with an explicit warmup so thin histories never
    gate."""
    findings: List[Finding] = []
    for (fkey, tool, metric), values in sorted(_series(entries).items()):
        prior, last = values[:-1], values[-1]
        direction = metric_direction(metric)
        # even at --min-samples 0 a first-ever sample has nothing to
        # compare against: one prior is the hard floor
        if len(prior) < max(min_samples, 1):
            findings.append(Finding(fkey, tool, metric, last,
                                    _median(prior) if prior else last,
                                    0.0, len(prior), direction, 0.0,
                                    False, "warmup"))
            continue
        med = _median(prior)
        mad = _median([abs(v - med) for v in prior])
        threshold = max(z * _MAD_TO_SIGMA * mad,
                        floor_pct / 100.0 * abs(med))
        if direction == 0:
            findings.append(Finding(fkey, tool, metric, last, med, mad,
                                    len(prior), 0, threshold, False,
                                    "ungated"))
            continue
        excess = (last - med) * direction
        if excess > threshold:
            status, regressed = "REGRESSED", True
        elif excess < -threshold:
            status, regressed = "improved", False
        else:
            status, regressed = "ok", False
        findings.append(Finding(fkey, tool, metric, last, med, mad,
                                len(prior), direction, threshold,
                                regressed, status))
    return findings


def regressions(findings: Iterable[Finding]) -> List[Finding]:
    return [f for f in findings if f.regressed]


def render_report(entries: Sequence[Dict[str, Any]],
                  metric_filter: Optional[str] = None,
                  tool_filter: Optional[str] = None,
                  tail: int = 8) -> str:
    """Human-readable trajectory per metric: every series with its
    sample count, median/MAD, the last ``tail`` values and the
    detector's verdict on the latest one."""
    series = _series(entries)
    verdicts = {(f.fingerprint_key, f.tool, f.metric): f
                for f in evaluate(entries)}
    lines: List[str] = []
    for (fkey, tool, metric), values in sorted(series.items()):
        if metric_filter and metric_filter not in metric:
            continue
        if tool_filter and tool_filter not in tool:
            continue
        med = _median(values)
        mad = _median([abs(v - med) for v in values])
        f = verdicts.get((fkey, tool, metric))
        recent = ", ".join(f"{v:.6g}" for v in values[-tail:])
        lines.append(f"{tool}/{metric} @{fkey}  n={len(values)}  "
                     f"median={med:.6g} mad={mad:.2g}  "
                     f"[{f.status if f else '?'}]")
        lines.append(f"    last {min(len(values), tail)}: {recent}")
    if not lines:
        return "(empty trajectory)"
    return "\n".join(lines)
