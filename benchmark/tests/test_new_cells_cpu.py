"""The cell of PR 29 rehearsed at a tiny size on the CPU, with sizes of
its own (``tests/tiny.py`` has no entry for its kind): the run reads
``correct`` true; the fp8 control and every planted fault, under the
jitted step or in the reference that stands in the program's place
(``tools/steps_control.py``), read false. Nothing here is a
measurement."""

import time

import pytest

from benchmark import harness

LM = "lfm2_24b_a2b.packed8k_train"

TINY_MODEL = {
    "hidden_size": 64, "intermediate_size": 160, "moe_intermediate_size": 48,
    "num_hidden_layers": 3,
    "layer_types": ["conv", "full_attention", "conv"], "num_dense_layers": 1,
    "num_attention_heads": 4, "num_key_value_heads": 2, "num_experts": 8,
    "num_experts_per_tok": 2, "vocab_size": 256, "experts_held": 2,
    "expert_offset": 2, "vocab_held": 64}
TINY_TRAFFIC = {"sequences": 2, "seq_len": 128, "doc_median": 40,
                "pool": 4, "warmup_steps": 4}
# bfloat16 over contractions of 64 and 256 tokens is noisier than over
# 2048 and 32768: the tiny run reads 1.0e-3 / 0.020 / 0.0054 on this seed
# and the mildest fault below 4.7e-3 / 0.080 / 0.016
TINY_LIMITS = {"loss_gap": 0.003, "grad_norm_gap_worst_leaf": 0.04,
               "change_norm_gap_worst_leaf": 0.01}


def tiny_lm_cell() -> dict:
    cell = harness.load_cell(LM)
    cell["config"]["model"].update(TINY_MODEL)
    cell["config"]["reference"]["kwargs"].update(
        {k: TINY_MODEL[k] for k in ("hidden_size", "num_hidden_layers",
                                    "num_attention_heads",
                                    "num_key_value_heads",
                                    "num_experts_per_tok", "expert_offset")})
    cell["traffic"].update(TINY_TRAFFIC)
    cell["cell"]["limits"] = dict(TINY_LIMITS)
    return cell


def run_tiny_lm(entry=None, seed: int = 2 ** 31 + 29):
    import jax

    from benchmark.drivers import lm_train_steps
    return lm_train_steps.run(tiny_lm_cell(), jax.devices()[:1], seed=seed,
                              seconds=0.2, trace=False,
                              process_start=time.perf_counter(), entry=entry)


def failed_rows(compared):
    return [r["name"] for r in compared.rows if not r["ok"]]


def test_the_new_cell_finds_its_files_by_name():
    manifest = harness.read_json(harness.ROOT, "BENCHMARK.json")
    configs = {c["name"] for c in manifest["configs"]}
    for name in (LM,):
        cell = harness.load_cell(name)
        assert name.split(".")[0] in configs
        assert harness.load_driver(cell["traffic"]["kind"]).run
        assert harness.metrics_for(cell, "per_layer")
        assert {m["name"] for m in harness.metrics_for(cell, "end_to_end")} \
            == {"samples_per_s", "setup_s"}
        for entry in harness.metrics_for(cell, "per_layer"):
            spec = harness.read_json(harness.BENCH_DIR, "metrics",
                                     entry["name"] + ".json")
            assert {k: spec[k] for k in entry} == entry


def test_the_lm_cell_reads_correct_and_counts():
    result, compared = run_tiny_lm()
    assert result["correct"] is True, compared.as_dict()
    run = result["run"]
    counts = run["lm_counts"]
    assert counts["steps"] == run["steps"] >= 1
    assert counts["dropped"] == 0
    assert counts["tokens"] == run["steps"] * 2 * 128
    # 2 expert layers x top 2 of 8, 2 held: about a quarter falls here
    assert 0 < counts["routed_here"] < counts["buffer_rows"]
    assert set(compared.as_dict()) >= {
        "loss_gap_step1", "loss_gap_step3", "grad_norm_gap_worst_leaf",
        "change_norm_gap_worst_leaf", "dropped", "compiles_in_window",
        "kernels_missing"}


def state_left_unchanged(step):
    import jax
    import jax.numpy as jnp

    def broken(state, batch, rng):
        keep = jax.tree.map(jnp.copy, (state.params, state.opt_state))
        new_state, metrics = step(state, batch, rng)
        return new_state.replace(params=keep[0], opt_state=keep[1]), metrics
    return broken


def documents_run_together(step):
    """The document mask left out: every sequence one document."""
    import jax.numpy as jnp

    def broken(state, batch, rng):
        s = batch["tokens"].shape[1]
        return step(state, dict(
            batch, segment_ids=jnp.zeros_like(batch["segment_ids"]),
            positions=jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32),
                                       batch["positions"].shape)), rng)
    return broken


def one_expert_moved_double(step):
    import jax
    import jax.numpy as jnp

    def broken(state, batch, rng):
        before = jnp.copy(state.params["layers_1"]["feed_forward"]["w2"])
        new_state, metrics = step(state, batch, rng)
        params = jax.tree.map(lambda x: x, new_state.params)
        leaf = params["layers_1"]["feed_forward"]
        leaf["w2"] = before + 2.0 * (leaf["w2"] - before)
        return new_state.replace(params=params), metrics
    return broken


@pytest.mark.parametrize("fault", [state_left_unchanged,
                                   documents_run_together,
                                   one_expert_moved_double])
def test_a_fault_under_the_lm_step_reads_not_correct(fault):
    result, compared = run_tiny_lm(entry=fault)
    assert result["correct"] is False, compared.as_dict()
    assert set(failed_rows(compared)) & {
        "loss_gap_step1", "loss_gap_step2", "loss_gap_step3",
        "grad_norm_gap_worst_leaf", "change_norm_gap_worst_leaf"}


def test_the_control_and_the_reference_side_faults_read_not_correct():
    from benchmark.tools import steps_control
    lines = list(steps_control.read_cases(tiny_lm_cell(), 7))
    assert [ln["case"] for ln in lines] == [
        "control_fp8_operand", "half_the_batch", "documents_run_together"]
    for line in lines:
        assert line["correct"] is False, line
        assert set(line["compared"]) == {
            "loss_gap_step1", "loss_gap_step2", "loss_gap_step3",
            "grad_norm_gap_worst_leaf", "change_norm_gap_worst_leaf"}


def test_the_reference_compared_with_itself_reads_nought():
    from benchmark.drivers import lm_train_steps as driver
    cell = tiny_lm_cell()
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


def test_lm_readers_return_nothing_where_nothing_is_to_read():
    from benchmark.readers import lm
    cell = harness.load_cell("raft_large.chairs_train")
    ctx = {"cell": cell, "run": {"steps": 3, "window_s": 1.0},
           "device": {"kind": "TPU v5e", "count": 1},
           "trace": {"ops": {"gmm.1": 0.5}, "busy_s": 1.0, "window_s": 1.0}}
    assert lm.train_step_mfu(ctx) is None
    assert lm.gmm_roofline(ctx, ["gmm"]) is None
    assert lm.expert_load(ctx, "train.step") is None
    ctx["cell"] = harness.load_cell(LM)
    assert lm.train_step_mfu(ctx) is None          # no counts in the run
    ctx["run"]["lm_counts"] = {"tokens": 3 * 32768, "routed_here": 3 * 65536,
                               "causal_pairs": 3 * 4 * 10 ** 7}
    ctx["run"]["lm_traced_counts"] = {"buffer_rows": 3 * 4 * 131072,
                                      "routed_here": 3 * 65536}
    assert 0 < lm.train_step_mfu(ctx) < 100
    assert 0 < lm.gmm_roofline(ctx, ["gmm"]) < 100
