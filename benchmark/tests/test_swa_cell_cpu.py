"""The cell of PR 35 rehearsed at a tiny size on the CPU, with sizes of
its own (``tests/tiny.py`` has no entry for its kind): the run reads
``correct`` true; every planted fault, under the jitted step or in the
reference that stands in the program's place
(``tools/swa_control.py``), reads false. Nothing here is a
measurement."""

import time

import pytest

from benchmark import harness

SWA = "trinity_mini.packed16k_swa_train"

TINY_MODEL = {
    "hidden_size": 64, "intermediate_size": 160, "moe_intermediate_size": 48,
    "num_hidden_layers": 3,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "full_attention"],
    "num_dense_layers": 1, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "sliding_window": 32,
    "num_experts": 8, "num_experts_per_tok": 2, "vocab_size": 256,
    "experts_held": 2, "expert_offset": 2, "vocab_held": 64}
TINY_TRAFFIC = {"sequences": 2, "seq_len": 128, "doc_median": 60,
                "pool": 4, "warmup_steps": 4}
# bfloat16 over contractions of 64 and 256 tokens is noisier than over
# 2048 and 16384: limits of this size's own, between the tiny run's
# readings and the mildest fault's
TINY_LIMITS = {"loss_gap": 0.003, "grad_norm_gap_worst_leaf": 0.06,
               "change_norm_gap_worst_leaf": 0.03,
               "grad_diff_worst_leaf": 0.45}


def tiny_swa_cell() -> dict:
    cell = harness.load_cell(SWA)
    cell["config"]["model"].update(TINY_MODEL)
    cell["config"]["reference"]["kwargs"].update(
        {k: TINY_MODEL[k] for k in cell["config"]["reference"]["kwargs"]
         if k in TINY_MODEL})
    cell["traffic"].update(TINY_TRAFFIC)
    cell["cell"]["limits"] = dict(TINY_LIMITS)
    return cell


def run_tiny_swa(entry=None, seed: int = 2 ** 31 + 35):
    import jax

    from benchmark.drivers import swa_train_steps
    return swa_train_steps.run(tiny_swa_cell(), jax.devices()[:1],
                               seed=seed, seconds=0.2, trace=False,
                               process_start=time.perf_counter(),
                               entry=entry)


def failed_rows(compared):
    return [r["name"] for r in compared.rows if not r["ok"]]


FOLLOWED = {"loss_gap_step1", "loss_gap_step2", "loss_gap_step3",
            "grad_norm_gap_worst_leaf", "change_norm_gap_worst_leaf",
            "grad_diff_worst_leaf"}


def test_the_new_cell_finds_its_files_by_name():
    manifest = harness.read_json(harness.ROOT, "BENCHMARK.json")
    cell = harness.load_cell(SWA)
    assert SWA.split(".")[0] in {c["name"] for c in manifest["configs"]}
    assert harness.load_driver(cell["traffic"]["kind"]).run
    assert {m["name"] for m in harness.metrics_for(cell, "end_to_end")} \
        == {"samples_per_s", "setup_s"}
    per_layer = harness.metrics_for(cell, "per_layer")
    assert {m["name"] for m in per_layer} == {
        "swa_train_step_mfu", "attn_window_time_pct.swa_train",
        "attn_full_time_pct.swa_train", "attn_window_roofline",
        "expert_gmm_time_pct.swa_train", "expert_gmm_roofline.swa_train",
        "expert_load_max_over_mean.swa_train", "device_idle_pct.swa_train",
        "device_wait_ms_per_step.swa_train", "host_ms_per_step.swa_train"}
    for entry in per_layer:
        spec = harness.read_json(harness.BENCH_DIR, "metrics",
                                 entry["name"] + ".json")
        assert {k: spec[k] for k in entry} == entry
    assert set(cell["cell"]["limits"]) == set(cell["cell"]["limits_why"])


def test_the_swa_cell_reads_correct_and_counts():
    result, compared = run_tiny_swa()
    assert result["correct"] is True, compared.as_dict()
    run = result["run"]
    counts = run["swa_counts"]
    assert counts["steps"] == run["steps"] >= 1
    assert counts["tokens"] == run["steps"] * 2 * 128
    assert counts["dropped"] == 0 < counts["routed_here"]
    assert counts["window_pairs"] == counts["window_pairs_by_driver"] > 0
    assert counts["causal_pairs"] == counts["causal_pairs_by_driver"] \
        > counts["window_pairs"]
    assert 0 < counts["loss_tokens"] < counts["tokens"]
    assert "ssm_counts" not in run
    assert set(compared.as_dict()) == FOLLOWED | {
        "dropped", "window_pairs_missed", "compiles_in_window",
        "kernels_missing"}


def test_the_readers_read_the_programs_counters():
    """What the traced run's readers compute from, without a trace: the
    counters of the last steps and the required operations."""
    from benchmark import afmoe_flops
    from benchmark.readers import afmoe as readers

    result, _ = run_tiny_swa()
    run = result["run"]
    cell = tiny_swa_cell()
    ctx = {"cell": cell, "run": dict(run, ssm_traced_counts={"steps": 1}),
           "device": {"kind": "TPU v5e", "count": 1}}
    counted = readers._counted(ctx, run["steps"])
    assert {k: counted[k] for k in readers.COUNTERS} == {
        k: run["swa_counts"][k] for k in readers.COUNTERS}
    assert counted["tokens"] == run["swa_counts"]["tokens"]
    assert readers._traced(ctx)["tokens"] == 2 * 128
    assert readers.train_step_mfu(ctx) > 0
    required = afmoe_flops.train_step_flops(
        cell["config"]["model"], counted["tokens"], counted["routed_here"],
        counted["window_pairs"], counted["causal_pairs"])
    assert required["attention_window"] < required["attention_full"] * 2
    # a program without the counters: nothing to read, nothing raised
    assert readers._counted(ctx, 10 ** 6) is None
    assert readers.scope_time_pct(
        dict(ctx, trace={"ops": {}, "busy_s": 1.0}),
        ["raft_attn_window"]) is None


def state_left_unchanged(step):
    import jax
    import jax.numpy as jnp

    def broken(state, batch, rng):
        keep = jax.tree.map(jnp.copy, (state.params, state.opt_state))
        new_state, metrics = step(state, batch, rng)
        return new_state.replace(params=keep[0], opt_state=keep[1]), metrics
    return broken


def positions_run_on(step):
    """Positions that do not restart with the documents: the sliding
    layers rotate by other angles, and the program's ``window_pairs``
    no longer counts what the documents allow."""
    import jax.numpy as jnp

    def broken(state, batch, rng):
        s = batch["positions"].shape[1]
        return step(state, dict(batch, positions=jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32), batch["positions"].shape)), rng)
    return broken


def gate_moved_double(step):
    import jax.numpy as jnp

    def broken(state, batch, rng):
        before = jnp.copy(
            state.params["layers_1"]["self_attn"]["gate_proj"])
        new_state, metrics = step(state, batch, rng)
        params = new_state.params
        params["layers_1"]["self_attn"]["gate_proj"] = before + 2.0 * (
            params["layers_1"]["self_attn"]["gate_proj"] - before)
        return new_state.replace(params=params), metrics
    return broken


@pytest.mark.parametrize("fault,rows", [
    (state_left_unchanged, {"change_norm_gap_worst_leaf"}),
    (positions_run_on, {"window_pairs_missed"}),
    (gate_moved_double, {"change_norm_gap_worst_leaf"}),
])
def test_a_fault_under_the_step_reads_not_correct(fault, rows):
    result, compared = run_tiny_swa(entry=fault)
    assert result["correct"] is False
    assert rows <= set(failed_rows(compared)), compared.as_dict()


def test_the_controls_read_not_correct():
    """``tools/swa_control.py`` at the tiny size: the reference with
    something wrong in the program's place fails the rows a run
    compares."""
    from benchmark.tools import swa_control

    cases = {line["case"]: line for line in swa_control.read_cases(
        tiny_swa_cell(), 2 ** 31 + 35)}
    assert set(cases) == {"control_fp8_operand", "window_left_out",
                          "positions_on_full", "half_the_positions"}
    for name, line in cases.items():
        assert line["correct"] is False, (name, line["compared"])
