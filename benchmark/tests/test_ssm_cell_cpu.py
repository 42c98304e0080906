"""The cell of PR 33 rehearsed at a tiny size on the CPU, with sizes of
its own (``tests/tiny.py`` has no entry for its kind): the run reads
``correct`` true; every planted fault, under the jitted step or in the
reference that stands in the program's place (``tools/ssm_control.py``,
``tools/steps_control.py``), reads false. Nothing here is a
measurement."""

import time

import pytest

from benchmark import harness

SSM = "granite_4_0_h_micro.packed8k_ssm_train"

TINY_MODEL = {
    "hidden_size": 64, "shared_intermediate_size": 96,
    "num_hidden_layers": 3, "layer_types": ["mamba", "attention", "mamba"],
    "num_attention_heads": 4, "num_key_value_heads": 2, "mamba_n_heads": 8,
    "mamba_d_head": 16, "mamba_d_state": 16, "mamba_chunk_size": 16,
    "vocab_size": 256, "vocab_held": 64}
TINY_TRAFFIC = {"sequences": 2, "seq_len": 128, "doc_median": 24,
                "pool": 4, "warmup_steps": 4}
# bfloat16 over contractions of 64 and 256 tokens is noisier than over
# 2048 and 16384: limits of this size's own, between the tiny run's
# readings and the mildest fault's
TINY_LIMITS = {"loss_gap": 0.003, "grad_norm_gap_worst_leaf": 0.06,
               "change_norm_gap_worst_leaf": 0.02}


def tiny_ssm_cell() -> dict:
    cell = harness.load_cell(SSM)
    cell["config"]["model"].update(TINY_MODEL)
    cell["config"]["reference"]["kwargs"].update(
        {k: TINY_MODEL[k] for k in cell["config"]["reference"]["kwargs"]
         if k in TINY_MODEL})
    cell["traffic"].update(TINY_TRAFFIC)
    cell["cell"]["limits"] = dict(TINY_LIMITS)
    return cell


def run_tiny_ssm(entry=None, seed: int = 2 ** 31 + 33):
    import jax

    from benchmark.drivers import ssm_train_steps
    return ssm_train_steps.run(tiny_ssm_cell(), jax.devices()[:1],
                               seed=seed, seconds=0.2, trace=False,
                               process_start=time.perf_counter(),
                               entry=entry)


def failed_rows(compared):
    return [r["name"] for r in compared.rows if not r["ok"]]


FOLLOWED = {"loss_gap_step1", "loss_gap_step2", "loss_gap_step3",
            "grad_norm_gap_worst_leaf", "change_norm_gap_worst_leaf"}


def test_the_new_cell_finds_its_files_by_name():
    manifest = harness.read_json(harness.ROOT, "BENCHMARK.json")
    cell = harness.load_cell(SSM)
    assert SSM.split(".")[0] in {c["name"] for c in manifest["configs"]}
    assert harness.load_driver(cell["traffic"]["kind"]).run
    assert {m["name"] for m in harness.metrics_for(cell, "end_to_end")} \
        == {"samples_per_s", "setup_s"}
    per_layer = harness.metrics_for(cell, "per_layer")
    assert {m["name"] for m in per_layer} >= {
        "ssm_train_step_mfu", "ssd_time_pct.ssm_train", "ssd_roofline"}
    for entry in per_layer:
        spec = harness.read_json(harness.BENCH_DIR, "metrics",
                                 entry["name"] + ".json")
        assert {k: spec[k] for k in entry} == entry
    assert set(cell["cell"]["limits"]) == set(cell["cell"]["limits_why"])


def test_the_ssm_cell_reads_correct_and_counts():
    result, compared = run_tiny_ssm()
    assert result["correct"] is True, compared.as_dict()
    run = result["run"]
    counts = run["ssm_counts"]
    assert counts["steps"] == run["steps"] >= 1
    assert counts["tokens"] == run["steps"] * 2 * 128
    assert counts["chunks"] == run["steps"] * 2 * 128 // 16
    assert counts["ssm_resets"] == counts["document_starts"] > 0
    assert 0 < counts["loss_tokens"] < counts["tokens"]
    assert set(compared.as_dict()) == FOLLOWED | {
        "resets_missed", "compiles_in_window", "kernels_missing"}


def state_left_unchanged(step):
    import jax
    import jax.numpy as jnp

    def broken(state, batch, rng):
        keep = jax.tree.map(jnp.copy, (state.params, state.opt_state))
        new_state, metrics = step(state, batch, rng)
        return new_state.replace(params=keep[0], opt_state=keep[1]), metrics
    return broken


def resets_left_out(step):
    """Every sequence one document for the mixers, the attention and
    the loss: the state and the convolution run across the boundaries,
    and the program counts no reset."""
    import jax.numpy as jnp

    def broken(state, batch, rng):
        return step(state, dict(
            batch, segment_ids=jnp.zeros_like(batch["segment_ids"])), rng)
    return broken


def a_moved_double(step):
    import jax
    import jax.numpy as jnp

    def broken(state, batch, rng):
        before = jnp.copy(state.params["layers_0"]["mamba"]["A_log"])
        new_state, metrics = step(state, batch, rng)
        params = jax.tree.map(lambda x: x, new_state.params)
        leaf = params["layers_0"]["mamba"]
        leaf["A_log"] = before + 2.0 * (leaf["A_log"] - before)
        return new_state.replace(params=params), metrics
    return broken


def half_the_positions(step):
    """The second sequence's loss positions left out: every token there
    a document of its own has no target inside its document."""
    import jax.numpy as jnp

    def broken(state, batch, rng):
        seg = batch["segment_ids"]
        alone = jnp.broadcast_to(jnp.arange(seg.shape[1], dtype=seg.dtype),
                                 seg.shape)
        rows = jnp.arange(seg.shape[0])[:, None] >= seg.shape[0] // 2
        return step(state, dict(batch, segment_ids=jnp.where(
            rows, alone, seg)), rng)
    return broken


@pytest.mark.parametrize("fault,rows", [
    (state_left_unchanged, FOLLOWED),
    (resets_left_out, FOLLOWED | {"resets_missed"}),
    (a_moved_double, {"change_norm_gap_worst_leaf"}),
    (half_the_positions, FOLLOWED | {"resets_missed"}),
])
def test_a_fault_under_the_ssm_step_reads_not_correct(fault, rows):
    result, compared = run_tiny_ssm(entry=fault)
    assert result["correct"] is False, compared.as_dict()
    assert set(failed_rows(compared)) & rows
    if fault is resets_left_out:
        assert "resets_missed" in failed_rows(compared)


def test_the_controls_read_not_correct():
    from benchmark.tools import ssm_control, steps_control
    lines = list(ssm_control.read_cases(tiny_ssm_cell(), 7)) + list(
        steps_control.read_cases(tiny_ssm_cell(), 7))
    assert [ln["case"] for ln in lines] == [
        "control_fp8_operand", "resets_left_out", "half_the_positions",
        "control_fp8_operand", "half_the_batch", "documents_run_together"]
    for line in lines:
        assert line["correct"] is False, line
        assert set(line["compared"]) == FOLLOWED


def test_the_reference_compared_with_itself_reads_nought():
    from benchmark.drivers import ssm_train_steps as driver
    cell = tiny_ssm_cell()
    traffic, config = cell["traffic"], cell["config"]
    _, mcfg = driver.configs_of(cell, 7)
    variables = driver.seeded_variables(mcfg, 7)
    batches = driver.make_batches(7, traffic, mcfg.vocab)[
        :traffic["followed_steps"]]
    theirs = driver.follow_reference(variables, batches, traffic, config)
    same = driver.compare_steps(variables["params"], theirs, theirs)
    compared = driver.compared_followed(same, cell["cell"]["limits"],
                                        traffic["followed_steps"])
    assert compared.correct and same["grad_norm_gap_worst_leaf"] == 0.0


def test_ssm_readers_return_nothing_where_nothing_is_to_read():
    from benchmark.readers import ssm
    cell = harness.load_cell("lfm2_24b_a2b.packed8k_train")
    ctx = {"cell": cell, "run": {"steps": 3, "window_s": 3.6},
           "device": {"kind": "TPU v5e", "count": 1},
           "trace": {"ops": {"fusion.1": 0.5, "fusion.2": 0.25},
                     "busy_s": 3.5, "window_s": 3.6}}
    assert ssm.train_step_mfu(ctx) is None
    assert ssm.stage_time_pct(ctx, ["ssd_scan"]) is None
    assert ssm.ssd_roofline(ctx, ["ssd_scan"]) is None
    ctx["cell"] = harness.load_cell(SSM)
    assert ssm.train_step_mfu(ctx) is None          # no counts in the run
    assert ssm.ssd_roofline(ctx, ["ssd_scan"]) is None
    ctx["run"]["ssm_counts"] = {"tokens": 3 * 16384, "chunks": 3 * 64,
                                "causal_pairs": 3 * 2 * 10 ** 7}
    ctx["run"]["ssm_traced_counts"] = dict(ctx["run"]["ssm_counts"])
    assert 0 < ssm.train_step_mfu(ctx) < 100
    assert ssm.ssd_roofline(ctx, ["ssd_scan"]) is None   # no stage map
    ctx["run"]["stage_ops"] = {"ssd_scan": ["fusion.1"],
                               "ssm_conv": ["fusion.2"]}
    assert ssm.stage_time_pct(ctx, ["ssd_scan"]) == 100 * 0.5 / 3.5
    assert ssm.stage_time_pct(ctx, ["ssd_scan", "ssm_conv"]) == \
        100 * 0.75 / 3.5
    assert 0 < ssm.ssd_roofline(ctx, ["ssd_scan"]) < 100
