#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, one result: the last line of standard output is
the JSON object the driver reads. Without the chips the cell asks for,
or without the program beside it, it exits non-zero and prints no
result.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    from benchmark import harness
    try:
        cell = harness.load_cell(args.workload)
        try:
            import raft_tpu  # noqa: F401  the system under test
        except ImportError as e:
            raise harness.Refused(
                f"the program under test is not beside the benchmark: {e}")
        devices = harness.require_devices(cell["chips"])
    except harness.Refused as e:
        print(f"benchmark refused: {e}", file=sys.stderr)
        return 4
    driver = harness.load_driver(cell["traffic"]["kind"])
    result, compared = driver.run(
        cell, devices, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), process_start=PROCESS_START)
    harness.emit(result, compared)
    return 0


if __name__ == "__main__":
    sys.exit(main())
