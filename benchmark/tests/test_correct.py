"""``correct`` has to come out false when it should: for the control
(the reference computed in fp8, put in the program's place) and for each
fault a dataset pass can have, planted under the timed path. The chip
check is skipped; the rest of a run is driven as it is."""

import numpy as np
import pytest

from benchmark import harness
from benchmark.drivers import dataset_pass
from benchmark.tests.tiny import run_tiny, tiny_cell

CELLS = [w["name"] for w in harness.read_json(
    harness.ROOT, "BENCHMARK.json")["workloads"]
    if harness.load_cell(w["name"])["traffic"]["kind"] == "dataset_pass"]


def broken(fault):
    """The program's own dataset loop with ``fault(flow_up)`` applied to
    every batch where ``predict_batch`` produces it."""
    from raft_tpu.evaluate import _predict_dataset

    def entry(predictor, dataset, mode):
        real = predictor.predict_batch

        def predict_batch(images1, images2):
            low, up = real(images1, images2)
            up = np.array(up)
            fault(up)
            return low, up

        predictor.predict_batch = predict_batch
        try:
            yield from _predict_dataset(predictor, dataset, mode)
        finally:
            del predictor.predict_batch

    return entry


def one_answer_altered(up):
    up[0] += 1.0            # slot 0 of every batch, by one pixel


def half_the_batch_left_out(up):
    half = up.shape[0] // 2
    up[half:] = up[:half]   # the second half never computed


def failed_rows(compared):
    return [r["name"] for r in compared.rows if not r["ok"]]


@pytest.mark.parametrize("name", CELLS)
def test_the_control_reads_not_correct(name):
    cell = tiny_cell(name)
    control = dataset_pass.reference_entry(
        cell["config"], cell["traffic"],
        cell["config"]["control"]["operand"])
    result, compared = run_tiny(name, entry=control)
    assert result["correct"] is False
    assert failed_rows(compared) == ["epe_px_worst"]


@pytest.mark.parametrize("name", CELLS)
def test_the_reference_in_the_programs_place_reads_correct(name):
    cell = tiny_cell(name)
    same = dataset_pass.reference_entry(cell["config"], cell["traffic"],
                                        "identity")
    result, compared = run_tiny(name, entry=same)
    assert result["correct"] is True
    assert compared.as_dict()["epe_px_worst"]["value"] < 1e-5


@pytest.mark.parametrize("fault", [one_answer_altered,
                                   half_the_batch_left_out])
def test_a_fault_under_the_timed_path_reads_not_correct(fault):
    result, compared = run_tiny(CELLS[0], entry=broken(fault))
    assert result["correct"] is False
    assert "epe_px_worst" in failed_rows(compared)


def test_an_answer_of_the_wrong_shape_or_order_reads_not_correct():
    from raft_tpu.evaluate import _predict_dataset

    def entry(predictor, dataset, mode):
        for idx, sample, flow in _predict_dataset(predictor, dataset, mode):
            yield idx + (idx % 2), sample, flow[:-1]

    result, compared = run_tiny(CELLS[0], entry=entry)
    assert result["correct"] is False
    assert {"wrong_shape", "order_breaks"} <= set(failed_rows(compared))
    assert result["failed"] > 0


# ---------------------------------------------------------------- training

TRAIN_CELLS = [w["name"] for w in harness.read_json(
    harness.ROOT, "BENCHMARK.json")["workloads"]
    if harness.load_cell(w["name"])["traffic"]["kind"] == "train_steps"]


def state_left_unchanged(step):
    import jax
    import jax.numpy as jnp

    def broken(state, batch, rng):
        keep = jax.tree.map(jnp.copy, (state.params, state.opt_state))
        new_state, metrics = step(state, batch, rng)
        return new_state.replace(params=keep[0], opt_state=keep[1]), metrics

    return broken


def half_the_rows_left_out(step):
    import jax
    import jax.numpy as jnp

    def broken(state, batch, rng):
        def first_half_twice(x):
            half = x.shape[0] // 2
            return jnp.concatenate([x[:half], x[:half]])
        return step(state, jax.tree.map(first_half_twice, batch), rng)

    return broken


def one_leaf_moved_double(step):
    import jax
    import jax.numpy as jnp

    def broken(state, batch, rng):
        before = jnp.copy(
            state.params["update"]["update_block"]["gru"]["convq1"]["kernel"])
        new_state, metrics = step(state, batch, rng)
        params = jax.tree.map(lambda x: x, new_state.params)
        leaf = params["update"]["update_block"]["gru"]["convq1"]
        leaf["kernel"] = before + 2.0 * (leaf["kernel"] - before)
        return new_state.replace(params=params), metrics

    return broken


@pytest.mark.parametrize("fault", [state_left_unchanged,
                                   half_the_rows_left_out,
                                   one_leaf_moved_double])
@pytest.mark.parametrize("name", TRAIN_CELLS)
def test_a_fault_under_the_training_step_reads_not_correct(name, fault):
    result, compared = run_tiny(name, entry=fault)
    assert result["correct"] is False, compared.as_dict()
    assert set(failed_rows(compared)) & {
        "loss_gap_step1", "loss_gap_step2", "loss_gap_step3",
        "grad_norm_gap_worst_leaf", "change_norm_gap_worst_leaf"}


@pytest.mark.parametrize("name", TRAIN_CELLS)
def test_the_training_control_reads_not_correct(name):
    import jax

    from benchmark.drivers import train_steps
    cell = tiny_cell(name)
    traffic, config = cell["traffic"], cell["config"]
    _, mcfg = train_steps.configs_of(cell, 7)
    variables = train_steps.seeded_variables(mcfg, 7)
    batches = train_steps.make_batches(
        7, traffic["followed_steps"], traffic["batch_size"],
        traffic["height"], traffic["width"])
    theirs = train_steps.follow_reference(variables, batches, traffic,
                                          config)
    control = train_steps.follow_reference(
        variables, batches, traffic, config, config["control"]["operand"])
    numbers = train_steps.compare_steps(
        jax.device_get(variables["params"]), control, theirs)
    limits = cell["cell"]["limits"]
    over = [numbers[k] > limits[k] for k in ("grad_norm_gap_worst_leaf",
                                             "change_norm_gap_worst_leaf")]
    over += [g > limits["loss_gap"] for g in numbers["loss_gaps"]]
    assert any(over), numbers
    same = train_steps.compare_steps(
        jax.device_get(variables["params"]), theirs, theirs)
    assert same["grad_norm_gap_worst_leaf"] == 0.0
    assert same["change_norm_gap_worst_leaf"] == 0.0
