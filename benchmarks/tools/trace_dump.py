"""Look at one trace by hand: planes, lines, event counts and the events
with most time on each line of each device plane.

    python benchmarks/tools/trace_dump.py <trace dir or .xplane.pb> [n]
"""

from __future__ import annotations

import collections
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import trace_reduce  # noqa: E402


def main() -> None:
    from jax.profiler import ProfileData

    path = sys.argv[1]
    top = int(sys.argv[2]) if len(sys.argv) > 2 else 25
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    print(path, os.path.getsize(path), "bytes")
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            if not events:
                continue
            total = collections.Counter()
            count = collections.Counter()
            for e in events:
                total[e.name] += e.duration_ns
                count[e.name] += 1
            for name, ns in total.most_common(top):
                print(f"      {ns / 1e6:10.3f} ms  x{count[name]:<6} {name[:140]}")
            e = events[len(events) // 2]
            print("      sample stats:", [(k, str(v)[:80]) for k, v in e.stats][:12])


if __name__ == "__main__":
    main()
