#!/usr/bin/env python3
"""Look at one trace by hand: planes, lines, the commonest event names and
one event's stats per line.  ``python3 benchmark/tools/trace_dump.py <dir or
.xplane.pb>``."""

import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import xtrace  # noqa: E402


def main(path):
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = xtrace.find_xplane(path)
    print("file", path, os.path.getsize(path))
    data = ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r} lines={len(lines)}")
        for line in lines[:40]:
            events = list(line.events)
            if not events:
                continue
            names = collections.Counter(e.name for e in events)
            dur = collections.Counter()
            for e in events:
                dur[e.name] += e.duration_ns
            print(f"  LINE {line.name!r} events={len(events)} "
                  f"first_start_ns={events[0].start_ns}")
            for name, ns in dur.most_common(14):
                print(f"    {ns / 1e6:10.3f} ms  x{names[name]:<6} {name[:110]}")
            try:
                print("    stats of first:", dict(list(events[0].stats)[:12]))
            except Exception as e:  # noqa: BLE001
                print("    (no stats:", e, ")")


if __name__ == "__main__":
    main(sys.argv[1])
