"""Look at one trace by hand: planes, lines, and each line's events by name
with their count and seconds (the device's operations and programs; on the
host plane the annotations and the program's Python calls).

    python3 benchmarks/tools/dump_trace.py <dir or file.xplane.pb> [regex]
"""

import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv):
    from jax.profiler import ProfileData

    from benchmarks import trace_reader

    path = argv[0]
    if os.path.isdir(path):
        path = trace_reader.find_xplane(path)
    rx = re.compile(argv[1]) if len(argv) > 1 else None
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print("plane", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  line", line.name, len(events))
            if not (trace_reader.DEVICE_PLANE.match(plane.name)
                    or plane.name == trace_reader.HOST_PLANE):
                continue
            by = {}
            for e in events:
                row = by.setdefault(e.name, [0, 0.0])
                row[0] += 1
                row[1] += e.duration_ns * 1e-9
            rows = sorted(by.items(), key=lambda kv: -kv[1][1])
            for name, (n, s) in rows[:60]:
                if rx is None or rx.search(name):
                    print(f"    {s:10.6f}s x{n:<6d} {name[:400]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
