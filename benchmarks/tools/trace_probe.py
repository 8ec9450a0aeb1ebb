"""Print what a recorded ``.xplane.pb`` holds: planes, lines, how many
events, and a sample of events with their statistics.  For looking at one
trace by hand before trusting the reduction.

    python benchmarks/tools/trace_probe.py <file.xplane.pb> [events per line]
"""

from __future__ import annotations

import collections
import sys


def main() -> int:
    from jax.profiler import ProfileData

    path = sys.argv[1]
    sample = int(sys.argv[2]) if len(sys.argv) > 2 else 6
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            names = collections.Counter(ev.name for ev in events)
            total = sum(ev.duration_ns for ev in events)
            print(f"  LINE {line.name!r}: {len(events)} events, "
                  f"{len(names)} names, {total / 1e6:.1f} ms summed")
            for name, k in names.most_common(sample):
                ev = next(e for e in events if e.name == name)
                stats = {str(a): (v if not isinstance(v, str) else v[:200])
                         for a, v in ev.stats}
                print(f"    {k:6d} x {name[:90]!r} start={ev.start_ns} "
                      f"dur={ev.duration_ns} stats={stats}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
