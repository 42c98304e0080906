#!/usr/bin/env python3
"""Print what a profiler trace holds, for reading one by hand before
code is written against it: every plane, its lines and their event
counts, and for each line of a device plane the distinct event names
that took most time, with the stats of one event of each.

    python3 benchmark/tools/trace_summary.py <dir or .xplane.pb> [names]

Make the trace with ``jax.profiler.trace(<dir>)`` around the code in
question; the harness removes its own traces once it has read them.
"""

from __future__ import annotations

import glob
import os
import sys


def main(argv) -> int:
    path = argv[1]
    names = int(argv[2]) if len(argv) > 2 else 25
    if os.path.isdir(path):
        path = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                recursive=True))[-1]
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    print("file", path, os.path.getsize(path), "bytes")
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", repr(line.name), len(events), "events")
            device = plane.name.startswith("/device:")
            if not (device or any(e.name.startswith("bench.")
                                  for e in events[:2000])):
                continue
            total: dict = {}
            sample: dict = {}
            for ev in events:
                total[ev.name] = total.get(ev.name, 0.0) + ev.duration_ns
                sample.setdefault(ev.name, ev)
            for name, ns in sorted(total.items(),
                                   key=lambda kv: -kv[1])[:names]:
                ev = sample[name]
                stats = {k: (str(v)[:120]) for k, v in ev.stats}
                print(f"    {ns / 1e6:10.3f} ms  {name[:70]!r}  "
                      f"start {ev.start_ns:.0f}  stats {stats}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
