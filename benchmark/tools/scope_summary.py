#!/usr/bin/env python3
"""Device time of one cell by model stage, forward and backward apart.

    python3 benchmark/tools/scope_summary.py --workload <cell> --seed <n>

Builds the cell's program as its driver does, warms it up, traces two
batches of the dataset pass or ten steps of ``train()`` with the
harness's own profiler session, and gives every device operation's self
time (``trace_reduce``: by HLO instruction name) to the innermost stage
named in that instruction's ``op_name`` in the compiled program's text:
Flax's module scopes (``fnet``, ``cnet``, ``update_block``) and the
program's own ``jax.named_scope`` (``corr_build``, ``corr_lookup``,
``coords``, ``upsample``, ``sequence_loss``, ``grad_clip``,
``optimizer_update``); what else the refinement scan does is
``scan_other``. An instruction under ``transpose(...)`` is the backward
pass's. What carries no ``op_name``, or one with no stage in it, is
listed apart, with its largest instructions.

The benchmark's readers cannot do this yet: ``load_xplane`` keeps only
an event's instruction name (PERF.md, section 7).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: innermost first: a stage named deeper in the path wins
STAGES = ("fnet", "cnet", "corr_build", "corr_lookup", "coords", "upsample",
          "update_block", "sequence_loss", "grad_clip", "optimizer_update")
#: what the refinement scan does outside its named stages: the body's
#: own arithmetic (scope ``update``) and the loop's slicing and stacking
SCAN, SCAN_PARTS = "scan_other", ("update", "while")
NO_STAGE, NO_NAME = "(op_name without a stage)", "(no op_name)"

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_WRAPPED = re.compile(r"^(?:\w+\()*([^()]*)\)*$")


def op_names(compiled_text: str) -> dict:
    """HLO instruction name to its ``op_name`` metadata ("" where the
    compiler gave it none)."""
    out = {}
    for line in compiled_text.splitlines():
        match = _INSTRUCTION.match(line)
        if match:
            out[match.group(1)] = (
                line.partition('op_name="')[2].partition('"')[0])
    return out


def stage_of(op_name: str):
    """``(stage, "forward" | "backward")`` of one ``op_name`` path such
    as ``jit(step_fn)/transpose(jvp(RAFT))/fnet/layer2_0/conv1/...``."""
    direction = "backward" if "transpose(" in op_name else "forward"
    if not op_name:
        return NO_NAME, direction
    parts = [_WRAPPED.sub(r"\1", part) for part in op_name.split("/")]
    for part in reversed(parts):
        if part in STAGES:
            return part, direction
    if any(part in SCAN_PARTS for part in parts):
        return SCAN, direction
    return NO_STAGE, direction


def by_stage(op_seconds: dict, names: dict) -> dict:
    """``{stage: {"forward": s, "backward": s}}`` and, for what has no
    stage, its largest instructions."""
    table, rest = {}, {}
    for instruction, seconds in op_seconds.items():
        stage, direction = stage_of(names.get(instruction, ""))
        row = table.setdefault(stage, {"forward": 0.0, "backward": 0.0})
        row[direction] += seconds
        if stage in (NO_STAGE, NO_NAME):
            rest[instruction] = seconds
    return {"stages": table,
            "largest_without_stage": sorted(
                rest.items(), key=lambda kv: -kv[1])[:12]}


# ------------------------------------------------------------- the two kinds

def trace_pass(cell, seed, units):
    import jax
    import jax.numpy as jnp

    from benchmark import harness
    from benchmark.drivers import dataset_pass as driver
    from raft_tpu.evaluate import _predict_dataset

    traffic = cell["traffic"]
    bs = traffic["batch_size"]
    predictor, variables = driver.build(cell, seed)
    jax.block_until_ready(variables)
    pool = driver.make_pool(seed, traffic["pool"], traffic["height"],
                            traffic["width"])

    def batches(n):
        dataset = driver.SeededPairs(pool, bs, bs * n)
        dataset.pad_mode = traffic["pad_mode"]
        return driver.run_pass(_predict_dataset, predictor, dataset,
                               seconds=float("inf"), max_batches=n,
                               keep_slots=lambda k: ())

    tracer = harness.Trace(True)
    batches(traffic["warmup_batches"])
    tracer.start()
    opened = time.perf_counter_ns()
    batches(units)
    tracer.stop([["bench.window", opened, time.perf_counter_ns() - opened]])
    tracer.read()

    top, bottom, left, right = driver.sintel_pad_widths(
        traffic["height"], traffic["width"], traffic["pad_mode"])
    shape = (bs, traffic["height"] + top + bottom,
             traffic["width"] + left + right, 3)
    image = jax.ShapeDtypeStruct(shape, jnp.float32)
    text = predictor._fn(shape, False, "float32").lower(
        predictor.variables, image, image, None).compile().as_text()
    return tracer.reduced, text


def trace_train(cell, seed, units):
    import shutil

    import jax

    from benchmark import harness
    from benchmark.drivers import train_steps as driver
    from raft_tpu.train import train

    traffic = cell["traffic"]
    tcfg, mcfg = driver.configs_of(cell, seed)
    variables = driver.seeded_variables(mcfg, seed)
    jax.block_until_ready(variables)
    pool = driver.make_batches(seed, traffic["pool"], traffic["batch_size"],
                               traffic["height"], traffic["width"])
    tracer = harness.Trace(True)
    opened = []

    def window_opens():
        tracer.start()
        opened.append(time.perf_counter_ns())

    def step_done(_since_open):
        # called as train() asks for the next batch: the step before is
        # through and its metrics are on the host
        if len(loader.fetch_s) - 1 - traffic["warmup_steps"] == units:
            tracer.stop([["bench.window", opened[0],
                          time.perf_counter_ns() - opened[0]]])
            raise driver.WindowClosed()

    loader = driver.ClockLoader(
        pool, warmup_steps=traffic["warmup_steps"], seconds=float("inf"),
        queue_depth=traffic["queue_depth"], on_window_open=window_opens,
        on_step_done=step_done)
    record = driver.Observed(0)
    out_dir = tempfile.mkdtemp(prefix="scope_summary_")
    try:
        with driver.observed(variables, record):
            try:
                train(tcfg, mcfg, ckpt_dir=out_dir + "/checkpoints",
                      log_dir=out_dir + "/runs", dataloader=loader)
            except driver.WindowClosed:
                pass
    finally:
        loader.close()
        tracer.read()
        shutil.rmtree(out_dir, ignore_errors=True)
    text = record.step.lower(*record.abstract_args).compile().as_text()
    return tracer.reduced, text


KINDS = {"dataset_pass": (trace_pass, 2, "batch"),
         "train_steps": (trace_train, 10, "step")}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"))
    args = parser.parse_args(argv)

    from benchmark import harness
    cell = harness.load_cell(args.workload)
    harness.require_devices(cell["chips"])
    harness.enable_compile_cache()
    trace_units, units, unit = KINDS[cell["traffic"]["kind"]]
    reduced, text = trace_units(cell, args.seed, units)
    summary = by_stage(reduced["ops"], op_names(text))
    busy = reduced["busy_s"]
    print(f"{args.workload}: {units} {unit}(es), window "
          f"{reduced['window_s']:.3f} s, device busy {busy:.3f} s; "
          f"device ms a {unit} by stage (share of busy)")
    rows = sorted(summary["stages"].items(),
                  key=lambda kv: -(kv[1]["forward"] + kv[1]["backward"]))
    for stage, row in rows:
        print(f"  {stage:28s} forward {1e3 * row['forward'] / units:10.3f}"
              f"  backward {1e3 * row['backward'] / units:10.3f}"
              f"  {100 * (row['forward'] + row['backward']) / busy:5.1f} %")
    for instruction, seconds in summary["largest_without_stage"]:
        print(f"    without a stage: {instruction:40s} "
              f"{1e3 * seconds / units:10.3f} ms a {unit}")
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, f"scope_summary.{args.workload}.json")
    with open(path, "w") as f:
        json.dump({"workload": args.workload, "seed": args.seed,
                   "units": units, "unit": unit,
                   "window_s": reduced["window_s"], "busy_s": busy,
                   **summary}, f, indent=1)
    print("wrote", path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
