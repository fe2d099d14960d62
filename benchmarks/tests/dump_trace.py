"""Look at one trace by hand: planes, lines, event counts, the stats an
event carries, and the operations that took most time on each device.

    python3 benchmarks/tests/dump_trace.py <file.xplane.pb>

The names it prints are what ``benchmarks/data/trace_names.json`` maps.
"""

import sys
from collections import Counter


def main(path: str) -> None:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", plane.name, dict(plane.stats))
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", repr(line.name), len(events))
            if not events:
                continue
            first = events[len(events) // 2]
            print("    e.g.", first.name, first.start_ns, first.duration_ns,
                  {k: str(v)[:120] for k, v in first.stats})
            total = Counter()
            count = Counter()
            for e in events:
                total[e.name] += e.duration_ns
                count[e.name] += 1
            for name, ns in total.most_common(25):
                print(f"    {ns / 1e6:10.3f} ms {count[name]:7d} x {name}")


if __name__ == "__main__":
    main(sys.argv[1])
