"""From a profiler trace (`*.xplane.pb`) to device busy time, idle gaps and
time per operation.  Reads the file with `jax.profiler.ProfileData` only.

A device is a plane named `/device:TPU:<n>`; its operations are the events
of the line `XLA Ops`.  Operations that only contain others (a `while`, a
`conditional`, a `call`, told by the opcode in the event's HLO text or, where
an event has only a short name, by that name) cover their bodies' idle gaps
too, so they are left out and the bodies' own events counted.  Busy time is the union of the
remaining intervals, taken on each device by itself and then averaged, so it
can never pass the window.  Where the trace has no device plane and
`host_ops=True` (the CPU rehearsal), the host events that carry an `hlo_op`
are the operations, grouped by their `device_ordinal`.
"""

import glob
import os
import re

def short_name(name):
    """An operation's name: on the TPU an event is named by its whole HLO
    instruction (`%fusion.5 = f32[...] fusion(...)`), of which the
    instruction's own name is kept."""
    return name.split(" = ", 1)[0].lstrip("%")


DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
CONTAINERS = re.compile(r"^(while|conditional|cond|call)([.\d]*)$")
CONTAINER_HLO = re.compile(r"[\])}] (while|conditional|call)\(")


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
        + glob.glob(os.path.join(trace_dir, "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no xplane under {trace_dir}")
    return found[-1]


def read_trace(path, host_ops=False, span_prefix=None):
    """(devices, spans) from one pass over an xplane: `devices` is {device
    ordinal: [(name, start_ns, end_ns), ...]}, `spans` the host events whose
    name starts with `span_prefix` (the harness's own annotations, which sit
    on the trace's clock), as (name, start_ns, end_ns)."""
    from jax.profiler import ProfileData
    devices, host, spans = {}, {}, []
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.setdefault(int(m.group(1)), []).extend(
                        (short_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events
                        if not CONTAINER_HLO.search(e.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    ev = (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    if span_prefix and e.name.startswith(span_prefix):
                        spans.append(ev)
                    elif host_ops:
                        stats = dict(e.stats)
                        if "hlo_op" in stats:
                            host.setdefault(int(stats.get(
                                "device_ordinal", 0)), []).append(ev)
    return (devices or host), spans


def device_events(path, host_ops=False):
    """{device ordinal: [(name, start_ns, end_ns), ...]} from an xplane."""
    return read_trace(path, host_ops)[0]


def _union(intervals):
    """(busy_ns, gaps) of intervals sorted by start: gaps are
    (start, end) of the stretches between them."""
    busy, gaps, end = 0.0, [], None
    for a, b in intervals:
        if end is None or a > end:
            if end is not None:
                gaps.append((end, a))
            busy += b - a
            end = b
        elif b > end:
            busy += b - end
            end = b
    return busy, gaps


def reduce_events(devices, window_ns=None):
    """The reduction proper, on {ordinal: [(name, start, end)]}.

    `window_ns`, (start, end) on the trace's clock, is the traced window;
    without it the window runs from the first operation's start to the
    last one's end over all devices.  Returns seconds: `window_s`, `busy_s`
    (mean over devices), `per_device_busy_s`, `op_seconds` ({name: mean
    over devices of the summed durations}) and `gaps` ([(seconds, start_ns,
    end_ns)] of the fullest device's longest idle stretches)."""
    if not devices or not any(devices.values()):
        raise ValueError("the trace holds no device operation")
    leaves = {d: sorted(((a, b, n) for n, a, b in evs
                         if not CONTAINERS.match(n)))
              for d, evs in devices.items()}
    if window_ns is None:
        window_ns = (min(ev[0][0] for ev in leaves.values() if ev),
                     max(max(b for _, b, _ in ev)
                         for ev in leaves.values() if ev))
    w0, w1 = window_ns
    per_device, ops, gaps_of = {}, {}, {}
    for d, evs in leaves.items():
        clipped = [(max(a, w0), min(b, w1), n) for a, b, n in evs
                   if b > w0 and a < w1]
        busy, gaps = _union([(a, b) for a, b, _ in clipped])
        if clipped:
            gaps = ([(w0, clipped[0][0])] + gaps
                    + [(max(b for _, b, _ in clipped), w1)])
        per_device[d] = busy / 1e9
        gaps_of[d] = gaps
        for a, b, n in clipped:
            ops[n] = ops.get(n, 0.0) + (b - a) / 1e9 / len(leaves)
    fullest = max(per_device, key=per_device.get)
    gaps = sorted(((b - a) / 1e9, a, b) for a, b in gaps_of[fullest]
                  if b > a)[::-1]
    return {"window_s": (w1 - w0) / 1e9,
            "busy_s": sum(per_device.values()) / len(per_device),
            "per_device_busy_s": per_device,
            "op_seconds": ops,
            "gaps": gaps}


def pattern_seconds(op_seconds, patterns):
    """Summed seconds of the operations whose name matches one of the regular
    expressions in `patterns["match"]` and none in `patterns["except"]`.
    A pattern set that matches nothing is an error, not a zero."""
    match = [re.compile(p) for p in patterns["match"]]
    skip = [re.compile(p) for p in patterns.get("except", [])]
    names = [n for n in op_seconds
             if any(p.search(n) for p in match)
             and not any(p.search(n) for p in skip)]
    if not names:
        raise ValueError(f"kernel patterns {patterns['match']} match no "
                         "operation of the trace")
    return sum(op_seconds[n] for n in names), names


def top_ops(op_seconds, k=10):
    return [[n, s] for n, s in sorted(op_seconds.items(),
                                      key=lambda kv: -kv[1])[:k]]
