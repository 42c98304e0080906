#!/usr/bin/env python3
"""Spreads of the end-to-end metrics over sets of runs, as the bounds
are set from them: for each metric and set the distance between the
first and third quartile (``statistics.quantiles(values, n=4)``) as a
share of the median, the wider of the sets' spreads, and five times it.

    python3 benchmark/tools/spread.py <set1 dir> <set2 dir> ...

Each directory holds one ``*.out`` file for each run of a set; the last
line of each is the run's result.
"""

from __future__ import annotations

import glob
import json
import os
import statistics
import sys


def last_line(path: str) -> dict:
    with open(path) as f:
        lines = [ln for ln in f.read().splitlines() if ln.strip()]
    return json.loads(lines[-1])


def main(argv) -> int:
    sets = []
    for directory in argv[1:]:
        runs = [last_line(p) for p in sorted(
            glob.glob(os.path.join(directory, "*.out")))]
        sets.append((directory, runs))
    names = sorted({m for _, runs in sets for r in runs
                    for m in r["metrics"]})
    for name in names:
        widest = 0.0
        for directory, runs in sets:
            values = [r["metrics"][name]["value"] for r in runs
                      if name in r["metrics"]]
            if len(values) < 2:
                continue
            q1, _, q3 = statistics.quantiles(values, n=4)
            median = statistics.median(values)
            spread = (q3 - q1) / median
            widest = max(widest, spread)
            print(json.dumps({
                "metric": name, "set": directory, "runs": len(values),
                "correct": sum(bool(r["correct"]) for r in runs),
                "median": median, "q1": q1, "q3": q3, "spread": spread,
                "first_run": values[0],
                "min": min(values), "max": max(values)}))
        print(json.dumps({"metric": name, "widest_spread": widest,
                          "five_times": 5 * widest}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
