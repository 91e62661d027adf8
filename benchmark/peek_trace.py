"""Look at a trace by hand: the planes, their lines, and the operations that
took most time on each device line.

    python benchmark/peek_trace.py <file.xplane.pb or trace dir> [top]
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import trace_reduce  # noqa: E402


def main(argv):
    from jax.profiler import ProfileData
    path = argv[0]
    top = int(argv[1]) if len(argv) > 1 else 25
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    print(path, os.path.getsize(path), "bytes")
    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            total, n, first, last = {}, 0, None, None
            for e in line.events:
                n += 1
                total[e.name] = total.get(e.name, 0.0) + e.duration_ns / 1e9
                first = e.start_ns if first is None else min(first, e.start_ns)
                last = max(last or 0, e.start_ns + e.duration_ns)
            span = (last - first) / 1e9 if n else 0.0
            print(f"  LINE {line.name!r}: {n} events, {len(total)} names, "
                  f"span {span:.3f} s")
            if plane.name.startswith("/device:") or "XLA" in line.name:
                for name, s in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:top]:
                    print(f"      {s:10.4f} s  {name}")


if __name__ == "__main__":
    main(sys.argv[1:])
