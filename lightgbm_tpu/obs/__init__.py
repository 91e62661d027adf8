"""lightgbm_tpu.obs — runtime telemetry (spans, retrace/compile
counters, device-memory accounting, exportable traces).

See :mod:`lightgbm_tpu.obs.telemetry` for the core contract (zero-HLO,
zero-sync, off-is-free), :mod:`lightgbm_tpu.obs.memory` for HBM
attribution to named owners, :mod:`lightgbm_tpu.obs.exporters` for the
JSONL / Chrome-trace / Prometheus writers and
:mod:`lightgbm_tpu.obs.benchio` for the ``BENCH_obs.json`` benchmark
artifact.  Enabled by the ``telemetry=off|counters|trace`` parameter
(or ``LIGHTGBM_TPU_TELEMETRY``); read at runtime via
``Booster.telemetry_report()`` or the CLI's ``telemetry_out=`` export.

Device side: :mod:`lightgbm_tpu.obs.scopes` names the training step's
work (``lgbm.<phase>`` scopes, the scope table a device trace is read
with).

Model & data health rides on top: :mod:`lightgbm_tpu.obs.digest`
(on-device per-feature bin-occupancy digests with a bit-identical
NumPy oracle, PSI/chi-square skew scoring) and
:mod:`lightgbm_tpu.obs.health` (the ``health=off|counters|trace``
session, training flight recorder, training↔serving skew monitor,
drift attribution) — read via ``Booster.health_report()``.

Perf trajectory: :mod:`lightgbm_tpu.obs.regress` persists every
benchmark as a fingerprinted ``BENCH_history.jsonl`` entry and judges
new samples against same-fingerprint history (median/MAD, noise-aware)
— ``tools/perfwatch.py`` is the check/report/drill CLI on top.
"""

from . import digest, health, memory, regress, scopes
from .exporters import (export_all, export_chrome_trace, export_jsonl,
                        export_prometheus, prometheus_text)
from .telemetry import (MODES, NULL, Telemetry, compile_event,
                        configure_from_config, counter, enabled, gauge,
                        get, instant, span)

__all__ = [
    "MODES", "NULL", "Telemetry", "compile_event",
    "configure_from_config", "counter", "enabled", "gauge", "get",
    "instant", "span", "digest", "health", "memory", "regress", "scopes",
    "memory_snapshot",
    "export_all", "export_chrome_trace", "export_jsonl",
    "export_prometheus", "prometheus_text",
]


def memory_snapshot():
    """Ledger snapshot; when the session is enabled the per-owner
    byte counts also land as gauges (and, in trace mode, as counter
    tracks in the exported trace)."""
    tel = get()
    if tel.enabled:
        return memory.snapshot_to(tel)
    return memory.snapshot()


def export_session(out_dir: str):
    """Write all exporters for the process session under ``out_dir``."""
    return export_all(get(), out_dir)
