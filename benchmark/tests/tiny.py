"""Cells cut to a size the CPU can hold in a test. A dataset pass: odd
frame sizes so that the padder has work, two-pair batches, three
iterations, every answer of every batch kept and checked. Training:
64x96 crops, two rows, two iterations."""

from benchmark import harness

TINY = {
    "dataset_pass": {"height": 61, "width": 90, "iters": 3, "batch_size": 2,
                     "pool": 3, "warmup_batches": 1, "keep_per_batch": 2,
                     "check_pairs": 4},
    "train_steps": {"height": 64, "width": 96, "iters": 2, "batch_size": 2,
                    "pool": 4, "warmup_steps": 4},
}


def tiny_cell(name: str) -> dict:
    cell = harness.load_cell(name)
    kind = cell["traffic"]["kind"]
    if kind == "dataset_pass":
        # the gap to the reference grows by the same step every
        # refinement iteration (0.005 px large, 0.013 px small, fp8 ten
        # times that), so the cell's limit scales with the iterations
        cell["cell"]["limits"]["epe_px_worst"] *= (
            TINY[kind]["iters"] / cell["traffic"]["iters"])
    cell["traffic"].update(TINY[kind])
    return cell


def run_tiny(name: str, seed: int = 2 ** 31 + 11, entry=None,
             max_seconds: float = 0.2):
    import time

    import jax
    cell = tiny_cell(name)
    driver = harness.load_driver(cell["traffic"]["kind"])
    return driver.run(cell, jax.devices()[:1], seed=seed,
                      seconds=max_seconds, trace=False,
                      process_start=time.perf_counter(), entry=entry)
