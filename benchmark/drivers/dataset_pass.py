"""Traffic kind ``dataset_pass``: one caller runs the program's own
dataset loop, ``raft_tpu.evaluate._predict_dataset`` (what
``validate_sintel`` / ``validate_kitti`` run), over a seeded in-memory
dataset, closed loop, for the window.

Parameters (the traffic file, overridden by the cell's file): ``height``,
``width`` of the frames, ``pad_mode`` of the program's padder, ``iters``,
``batch_size`` of the predictor, ``pool`` distinct frame pairs made from
the seed and cycled, ``warmup_batches``, ``keep_per_batch`` answers kept
of each batch and ``check_pairs`` of them compared once the window has
closed.
"""

from __future__ import annotations

import time
from typing import Callable, Optional

import numpy as np

from benchmark import harness


# ------------------------------------------------------------------ traffic

def make_pool(seed: int, n: int, height: int, width: int):
    """``n`` frame pairs from the seed: blocky texture under fine noise,
    the second frame the same scene displaced by a few pixels with fresh
    noise. Integral float32 in [0, 255], as ``data/datasets.py`` yields
    frames."""
    rng = np.random.default_rng(seed)
    pairs = []
    for _ in range(n):
        coarse = rng.integers(0, 256, (height // 8 + 3, width // 8 + 3, 3))
        scene = np.kron(coarse, np.ones((8, 8, 1), np.int64))
        dy, dx = rng.integers(0, 9, 2)
        frames = []
        for oy, ox in ((8, 8), (8 + dy - 4, 8 + dx - 4)):
            crop = scene[oy:oy + height, ox:ox + width]
            noise = rng.integers(0, 77, (height, width, 3))
            frames.append(np.clip(crop * 7 // 10 + noise, 0, 255)
                          .astype(np.float32))
        pairs.append(tuple(frames))
    return pairs


class SeededPairs:
    """The dataset ``_predict_dataset`` indexes: element ``i`` is pair
    ``i mod pool``. It tells the phases when a batch's fetching starts
    and ends, which is all the benchmark can see of the pass from
    outside."""

    def __init__(self, pool, batch_size: int, length: int,
                 phases: Optional[harness.Phases] = None):
        self.pool, self.batch_size, self.length = pool, batch_size, length
        self.phases = phases

    def __len__(self):
        return self.length

    def __getitem__(self, idx: int):
        slot = idx % self.batch_size
        if self.phases is not None and slot == 0:
            self.phases.switch("fetch_pad")
        pair = self.pool[idx % len(self.pool)]
        if self.phases is not None and slot == self.batch_size - 1:
            self.phases.switch("predict_batch")
        return pair


def sintel_pad_widths(height: int, width: int, mode: str):
    """Replicate-padding to multiples of 8 as princeton-vl/RAFT
    ``core/utils/utils.py::InputPadder`` does: centred for ``sintel``,
    all at the bottom otherwise. The benchmark's own copy, for the
    reference's side of the pad and unpad round trip."""
    pad_h, pad_w = (-height) % 8, (-width) % 8
    left, right = pad_w // 2, pad_w - pad_w // 2
    top, bottom = ((pad_h // 2, pad_h - pad_h // 2) if mode == "sintel"
                   else (0, pad_h))
    return top, bottom, left, right


# ------------------------------------------------------------------- window

def run_pass(entry: Callable, predictor, dataset: SeededPairs, *,
             seconds: float, max_batches: Optional[int], keep_slots,
             phases: Optional[harness.Phases] = None) -> dict:
    """Consume ``entry(predictor, dataset, mode)`` batch by batch until a
    batch completes at or after ``seconds`` (or ``max_batches`` are
    done). Returns the facts of the window; the window ends when the
    last completed batch's last flow has reached the consumer."""
    bs = dataset.batch_size
    facts = {"pairs": 0, "batches": 0, "batch_end_s": [], "kept": [],
             "wrong_shape": 0, "order_breaks": 0}
    expect_shape = dataset.pool[0][0].shape[:2] + (2,)
    t0 = time.perf_counter()
    gen = entry(predictor, dataset, dataset.pad_mode)
    try:
        for n, (idx, _sample, flow) in enumerate(gen):
            slot = n % bs
            if phases is not None and slot == 0:
                phases.switch("consume")
            facts["order_breaks"] += int(idx != n)
            facts["wrong_shape"] += int(tuple(flow.shape) != expect_shape)
            if slot in keep_slots(n // bs):
                facts["kept"].append((n // bs, idx, np.array(flow)))
            if slot == bs - 1:
                now = time.perf_counter() - t0
                facts["pairs"] = n + 1
                facts["batches"] += 1
                facts["batch_end_s"].append(now)
                if phases is not None:
                    phases.switch(None)
                if now >= seconds or facts["batches"] == max_batches:
                    break
    finally:
        gen.close()
        if phases is not None:
            phases.switch(None)
    facts["window_s"] = facts["batch_end_s"][-1] if facts["batches"] else 0.0
    return facts


def census(compiled_text: str, names: dict) -> dict:
    """Mosaic kernels in a compiled program's text, by the program's
    kernel names (``raft_tpu.ops.layout.KERNEL_NAMES``)."""
    counts: dict = {}
    for line in compiled_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        op_name = line.partition('op_name="')[2].partition('"')[0]
        key = next((k for k, name in names.items() if name in op_name),
                   "unnamed")
        counts[key] = counts.get(key, 0) + 1
    return counts


# ---------------------------------------------------------------- reference

def reference_flow(config: dict, traffic: dict,
                   operand_name: str = "identity"):
    """``flow(variables, frame1, frame2)``: the plain reference over one
    unpadded pair, with the benchmark's own pad and unpad around it.
    ``operand_name`` ``fp8_operand`` makes it the control."""
    import functools

    import jax

    from benchmark.reference import raft as reference

    fn = jax.jit(functools.partial(
        reference.forward, iters=traffic["iters"],
        operand=getattr(reference, operand_name),
        **config["reference"]["kwargs"]))
    top, bottom, left, right = sintel_pad_widths(
        traffic["height"], traffic["width"], traffic["pad_mode"])
    widths = ((top, bottom), (left, right), (0, 0))

    def flow(variables, frame1, frame2):
        image1, image2 = (np.pad(f, widths, mode="edge")[None]
                          for f in (frame1, frame2))
        with jax.default_matmul_precision("highest"):
            out = np.asarray(fn(variables, image1, image2))[0]
        return out[top:out.shape[0] - bottom, left:out.shape[1] - right]

    return flow


def check_against_reference(kept, pool, variables, config, traffic,
                            seed) -> dict:
    """Run the plain reference over a seeded sample of the answers the
    window produced (the last batch's among them) and return each
    pair's gap."""
    rng = np.random.default_rng([seed, 0xC0FFEE])
    last = max(k for k, _, _ in kept)
    chosen = [i for i, (k, _, _) in enumerate(kept) if k == last][:1]
    rest = [i for i in range(len(kept)) if i not in chosen]
    rng.shuffle(rest)
    chosen += rest[:max(0, traffic["check_pairs"] - len(chosen))]
    reference = reference_flow(config, traffic)
    rows = []
    t0 = time.perf_counter()
    for i in chosen:
        _, idx, flow = kept[i]
        want = reference(variables, *pool[idx % len(pool)])
        rows.append(gap_of(flow, want, idx))
    return {"rows": rows, "seconds": time.perf_counter() - t0}


def gap_of(flow, want, idx) -> dict:
    """One answer against the reference's: the mean end-point gap over
    the frame in pixels, beside the reference's mean flow magnitude.
    (The gap in pixels is what is steady from seed to seed; as a share
    of the flow it swings fourfold with the seed's flow magnitude.)"""
    if flow.shape != want.shape:
        return {"idx": int(idx), "epe_px": float("nan"),
                "ref_mag_px": float("nan")}
    epe = float(np.linalg.norm(flow.astype(np.float64) - want, axis=-1)
                .mean())
    mag = float(np.linalg.norm(want.astype(np.float64), axis=-1).mean())
    return {"idx": int(idx), "epe_px": epe, "ref_mag_px": mag}


def reference_entry(config: dict, traffic: dict, operand_name: str):
    """The plain reference in the program's place, as a stand-in for
    ``_predict_dataset``, one pair at a time. Far too slow for a
    window; tests and ``tools/control.py`` use it."""
    reference = reference_flow(config, traffic, operand_name)

    def entry(predictor, dataset, mode):
        del mode
        for idx in range(len(dataset)):
            sample = dataset[idx]
            yield idx, sample, reference(predictor.variables, *sample[:2])

    return entry


# --------------------------------------------------------------------- run

def build(cell: dict, seed: int):
    """The system under test as the configuration states it, with the
    benchmark's weights."""
    from benchmark import weights
    from raft_tpu.config import RAFTConfig
    from raft_tpu.evaluate import FlowPredictor
    from raft_tpu.models.raft import RAFT

    config, traffic = cell["config"], cell["traffic"]
    model = RAFT(RAFTConfig(**config["model"]))
    variables = weights.make_variables(weights.variable_shapes(model), seed)
    predictor = FlowPredictor(model, variables, iters=traffic["iters"],
                              batch_size=traffic["batch_size"],
                              **config["predictor"])
    return predictor, variables


def compiled_program(predictor, traffic):
    """The executable of the timed shape: which Mosaic kernels it holds,
    and XLA's own figure of its temporaries in bytes. After the warm-up
    this lowers from JAX's caches and reads the executable back from
    the persistent cache."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.ops.layout import KERNEL_NAMES

    top, bottom, left, right = sintel_pad_widths(
        traffic["height"], traffic["width"], traffic["pad_mode"])
    shape = (traffic["batch_size"], traffic["height"] + top + bottom,
             traffic["width"] + left + right, 3)
    image = jax.ShapeDtypeStruct(shape, jnp.float32)
    compiled = predictor._fn(shape, False, "float32").lower(
        predictor.variables, image, image, None).compile()
    return (census(compiled.as_text(), KERNEL_NAMES),
            int(compiled.memory_analysis().temp_size_in_bytes))


def run(cell: dict, devices, *, seed: int, seconds: float, trace: bool,
        process_start: float, entry: Optional[Callable] = None):
    """Set up, warm up, measure for ``seconds``, then compare. ``entry``
    replaces the program's dataset loop in tests of ``correct``."""
    import jax

    if entry is None:
        from raft_tpu.evaluate import _predict_dataset as entry

    harness.enable_compile_cache()
    counter = harness.CompileCounter()
    traffic, config = cell["traffic"], cell["config"]
    on_chip = devices[0].platform == "tpu"
    bs = traffic["batch_size"]
    marks = [("imports_and_devices", time.perf_counter())]

    predictor, variables = build(cell, seed)
    jax.block_until_ready(variables)
    marks.append(("model_and_weights", time.perf_counter()))
    pool = make_pool(seed, traffic["pool"], traffic["height"],
                     traffic["width"])
    marks.append(("frame_pool", time.perf_counter()))
    keep_rng = np.random.default_rng([seed, 0xBEEF])
    kept_slots: dict = {}

    def keep_slots(batch_index):
        if batch_index not in kept_slots:
            kept_slots[batch_index] = set(keep_rng.choice(
                bs, size=min(bs, traffic["keep_per_batch"]),
                replace=False).tolist())
        return kept_slots[batch_index]

    def dataset(length, phases=None):
        ds = SeededPairs(pool, bs, length, phases)
        ds.pad_mode = traffic["pad_mode"]
        return ds

    tracer = harness.Trace(trace)     # compiles its marker: set-up
    # warm-up: the timed entry at the timed shape, so that the window
    # finds every program compiled and every buffer size seen
    run_pass(entry, predictor, dataset(bs * traffic["warmup_batches"]),
             seconds=float("inf"), max_batches=traffic["warmup_batches"],
             keep_slots=lambda k: ())
    marks.append(("warmup_batches", time.perf_counter()))
    kernels, temporaries = (compiled_program(predictor, traffic)
                            if on_chip else ({}, 0))
    marks.append(("kernel_census", time.perf_counter()))
    setup = counter.snapshot()

    phases = harness.Phases()
    tracer.start()
    setup_s = time.perf_counter() - process_start
    opened = time.perf_counter_ns()
    facts = run_pass(entry, predictor, dataset(10 ** 9, phases),
                     seconds=seconds, max_batches=None,
                     keep_slots=keep_slots, phases=phases)
    tracer.stop([["bench.window", opened,
                  time.perf_counter_ns() - opened]] + phases.log)
    in_window = {k: v - setup[k] for k, v in counter.snapshot().items()}
    memory = harness.memory_peak(devices)
    tracer.read()
    memory["executable_temporaries_bytes"] = temporaries

    # the program's state goes before the reference comes
    del predictor
    checked = check_against_reference(facts["kept"], pool, variables,
                                      config, traffic, seed)

    limits = cell["cell"]["limits"]
    compared = harness.Compared()
    rows = checked["rows"]
    compared.add("epe_px_worst", max((r["epe_px"] for r in rows),
                                     key=lambda v: (v != v, v)),
                 limits["epe_px_worst"])
    compared.add("wrong_shape", facts["wrong_shape"], 0)
    compared.add("order_breaks", facts["order_breaks"], 0)
    compared.add("compiles_in_window", in_window["compiles"], 0)
    expected = cell["cell"]["expected_kernels"] if on_chip else []
    compared.add("kernels_missing",
                 sum(1 for k in expected if not kernels.get(k)), 0)

    pairs_per_s = facts["pairs"] / facts["window_s"]
    device = harness.device_facts(devices)
    device["memory_peak_bytes"] = memory["memory_peak_bytes"]
    run_facts = {
        "pairs": facts["pairs"], "batches": facts["batches"],
        "window_s": facts["window_s"], "batch_end_s": facts["batch_end_s"],
        "pairs_per_s": pairs_per_s, "host_phase_s": phases.seconds,
        "host_phase_log": [[n, d / 1e9] for n, _, d in phases.log[:90]],
        "kernels": kernels, "setup_compile": setup,
        "window_compile": in_window, "checked": rows,
        "reference_s": checked["seconds"], "seed": seed,
        "memory": memory,
        "setup_phases_s": harness.durations(marks, process_start),
    }
    metrics, extra = harness.metrics_of(
        cell, tracer, run_facts, device,
        {"pairs_per_s": pairs_per_s, "setup_s": setup_s})
    result = {"correct": compared.correct, "attempted": facts["pairs"],
              "failed": facts["wrong_shape"],
              "metrics": metrics, "device": device, **extra,
              "workload": cell["name"], "run": run_facts}
    return result, compared
