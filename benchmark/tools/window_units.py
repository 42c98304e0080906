#!/usr/bin/env python3
"""One run of a cell, as ``benchmark/run.py`` makes it, and then the
program's own account of the window (``profiling.unit_accounts``): the
line ``slow_unit_lines`` prints for every step or batch over ``--slow``
times the median (the stage that grew, ``gc.pass`` overlaps, CPU beside
wall), beside what the readers of ``readers/program_units.py`` give.
For a run that stalled: the metrics say that it did, this says where.

    python3 benchmark/tools/window_units.py --workload <cell> --seed <n> \
        --seconds 40 --trace 0|1 [--slow 3]

Prints the run's result line last, as ``run.py`` does; before it the
slow units' lines and one line ``UNITS {...}``. Every unit's account
goes to ``chiprun_out/units/<cell>.<seed>.t<trace>.json``.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def report(run: dict, kind: str, slow: float):
    """``(brief, rows, lines)``: the readers' values over the window,
    the window's units as ``unit_accounts`` has them, and the slow
    ones' lines."""
    from benchmark.readers import program_units as units
    from raft_tpu.utils import profiling

    root, key, what, closes = (
        ("pass.batch", "batches", "batch", True) if kind == "dataset_pass"
        else ("train.step", "steps", "step", False))
    timer = profiling.host_timer()
    spans, dropped, n = timer.spans(), timer.dropped, int(run[key])
    at = (spans, dropped, root, n)
    brief = {
        "units": n, "dropped": dropped, "ring_spans": len(spans),
        "worst_over_median": units.worst_over_median(*at, closes),
        "second_worst_over_median": units.worst_over_median(
            *at, closes, 1),
        "unattributed_ms": (units.uncovered_ms_per_unit if closes
                            else units.unattributed_ms_per_unit)(*at),
        "collector_ms": units.collector_ms_per_unit(*at),
        "cpu_us": units.arg_per_unit(*at, "cpu_us", True),
        "passes": sum(s.name == units.COLLECTOR for s in spans),
        "young": timer.summary().get("gc.young")}
    window = units.window_roots(*at)
    chosen = {s.id for s in window[0]} if window else set()
    rows = [r for r in profiling.unit_accounts(spans, root)
            if r["id"] in chosen]
    return brief, rows, profiling.slow_unit_lines(rows, what, slow)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--slow", type=float, default=3.0)
    args = parser.parse_args(argv)

    from benchmark import harness
    cell = harness.load_cell(args.workload)
    devices = harness.require_devices(cell["chips"])
    kind = cell["traffic"]["kind"]
    result, compared = harness.load_driver(kind).run(
        cell, devices, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), process_start=PROCESS_START)
    brief, rows, lines = report(result["run"], kind, args.slow)
    out_dir = os.path.join(ROOT, "chiprun_out", "units")
    os.makedirs(out_dir, exist_ok=True)
    name = f"{args.workload}.{args.seed}.t{args.trace}.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump({"brief": brief, "units": rows, "slow": lines,
                   "result": result, "compared": compared.as_dict()}, f)
    for line in lines:
        print(line, flush=True)
    print("UNITS " + json.dumps(
        {"workload": args.workload, "seed": args.seed, "trace": args.trace,
         **brief}), flush=True)
    harness.emit(result, compared)
    return 0


if __name__ == "__main__":
    sys.exit(main())
