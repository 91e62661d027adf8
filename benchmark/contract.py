"""The shape the driver reads from a run's last line, checked before it is
printed.  `problems(line, metrics, traced)` returns the reasons the line
would be refused (an empty list: it is sound); run.py exits non-zero with
them on an earlier line instead of printing a line the driver cannot read."""

import math

DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")


def _number(v):
    return (isinstance(v, (int, float)) and not isinstance(v, bool)
            and math.isfinite(v))


def problems(line, metrics, traced):
    """`metrics` maps each metric this cell has to report in this trace mode
    to its unit."""
    if not isinstance(line, dict):
        return ["the last line is not a JSON object"]
    out = [f"key {k!r} is missing" for k in
           ("correct", "attempted", "failed", "metrics", "device")
           if k not in line]
    if out:
        return out
    if not isinstance(line["correct"], bool):
        out.append("correct is not true or false")
    for k in ("attempted", "failed"):
        if not (isinstance(line[k], int) and not isinstance(line[k], bool)
                and line[k] >= 0):
            out.append(f"{k} is not a count")
    got = line["metrics"]
    if not isinstance(got, dict):
        return out + ["metrics is not an object"]
    for name, unit in metrics.items():
        m = got.get(name)
        if not isinstance(m, dict) or not _number(m.get("value")):
            out.append(f"metric {name} is missing or has no finite value")
        elif m.get("unit") != unit:
            out.append(f"metric {name} has unit {m.get('unit')!r}, "
                       f"BENCHMARK.json says {unit!r}")
    for name in got:
        if name not in metrics:
            out.append(f"metric {name} is not one of this cell's in this "
                       "trace mode")
    dev = line["device"]
    if not isinstance(dev, dict):
        return out + ["device is not an object"]
    for k in DEVICE_KEYS:
        if k not in dev:
            out.append(f"device.{k} is missing")
    if not (isinstance(dev.get("count"), int) and dev.get("count", 0) >= 1):
        out.append("device.count is not a count of devices")
    if not (_number(dev.get("memory_peak_bytes"))
            and dev["memory_peak_bytes"] > 0):
        out.append("device.memory_peak_bytes is not above 0")
    if traced:
        busy, window = dev.get("busy_s"), dev.get("window_s")
        if not (_number(busy) and _number(window)):
            out.append("traced: device.busy_s and device.window_s are needed")
        elif not 0 < busy <= window:
            out.append(f"traced: 0 < busy_s <= window_s does not hold "
                       f"(busy_s={busy}, window_s={window})")
        bd = line.get("breakdown")
        if bd is not None:
            for k in ("device_ops", "idle_gaps"):
                rows = bd.get(k) if isinstance(bd, dict) else None
                if not (isinstance(rows, list) and len(rows) <= 10 and all(
                        isinstance(r, (list, tuple)) and len(r) == 2
                        and isinstance(r[0], str) and _number(r[1])
                        for r in rows)):
                    out.append(f"breakdown.{k} is not a list of at most 10 "
                               "[name, seconds]")
    elif "breakdown" in line:
        out.append("breakdown belongs to a traced run only")
    return out
