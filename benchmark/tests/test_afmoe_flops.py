"""The counts behind the sliding-window cell's metrics, against figures
worked by hand from the published widths."""

import json

import numpy as np
import pytest

from benchmark import afmoe_flops, harness

CFG = harness.read_json(harness.ROOT, "benchmark", "configs",
                        "trinity_mini.json")["model"]
TOKENS = 16384


def test_per_token_and_per_pair_counts_at_the_published_widths():
    # q, k, v, gate: 2048 -> 4096 + 512 + 512 + 4096; o: 4096 -> 2048
    assert afmoe_flops.attention_projection_flops_per_token(CFG) \
        == 2 * 2048 * 9216 + 2 * 4096 * 2048 == 54_525_952
    assert afmoe_flops.attention_flops_per_pair(CFG) == 32 * 4 * 128
    assert afmoe_flops.dense_ffn_flops_per_token(CFG) == 6 * 2048 * 6144
    assert afmoe_flops.expert_flops_per_row(CFG) \
        == afmoe_flops.shared_expert_flops_per_token(CFG) == 6 * 2048 * 1024
    assert afmoe_flops.router_flops_per_token(CFG) == 2 * 2048 * 128
    assert afmoe_flops.head_flops_per_token(CFG) == 2 * 2048 * 25024
    assert afmoe_flops.expert_layers(CFG) == 4


def test_a_step_of_one_unbroken_sequence():
    """One document of 16384 tokens: the window allows 23 % of a full
    layer's pairs; the step's parts add up and the backward pass counts
    twice the forward."""
    w = 2048
    causal = TOKENS * (TOKENS + 1) // 2
    window = w * (w + 1) // 2 + (TOKENS - w) * w
    assert abs(window / causal - 0.2343) < 1e-3
    rows = TOKENS * 8 * 4 // 8          # an eighth of every assignment
    forward = afmoe_flops.forward_flops(CFG, TOKENS, rows, window, causal)
    assert forward["total"] == sum(v for k, v in forward.items()
                                   if k != "total")
    assert forward["attention_window"] == 4 * 16384 * window
    assert forward["attention_full"] == 16384 * causal
    assert forward["attention_projections"] == 5 * TOKENS * 54_525_952
    assert forward["experts"] == rows * 6 * 2048 * 1024
    assert forward["shared_expert"] == 4 * TOKENS * 6 * 2048 * 1024
    step = afmoe_flops.train_step_flops(CFG, TOKENS, rows, window, causal)
    assert step == {k: 3 * v for k, v in forward.items()}
    # attention, projections and pairs, is most of the required work
    attention = sum(forward[k] for k in ("attention_projections",
                                         "attention_window",
                                         "attention_full"))
    assert 0.6 < attention / forward["total"] < 0.85


def test_the_windowed_attentions_required_work():
    window = 2048 * 2049 // 2 + (TOKENS - 2048) * 2048
    need = afmoe_flops.attn_window_step(CFG, TOKENS, window)
    assert need["flops"] == 3 * 4 * 16384 * window
    # forward q, o and k, v; backward q, o, dO, dq and k, v, dk, dv
    assert need["bytes"] == 4 * TOKENS * 2 * (
        (2 * 4096 + 2 * 512) + (4 * 4096 + 4 * 512))
    # compute-bound on a v5e by far
    assert need["flops"] / 197e12 > 5 * need["bytes"] / 819e9


def test_the_grouped_products_are_the_other_expert_familys():
    from benchmark import lm_flops
    need = afmoe_flops.expert_gmm_step(CFG, 0, 65536)
    assert need == lm_flops.expert_gmm_step(CFG, 0, 65536)
    assert need["products"] == 36
    assert need["flops"] == 9 * 2 * 65536 * 2048 * 1024


def test_the_drivers_count_of_allowed_pairs():
    from benchmark.drivers.swa_train_steps import allowed_pairs
    seg = np.zeros((2, 64), np.int32)
    seg[0, 10:] = 1                      # documents of 10 and 54
    seg[1, 40:] = 1                      # and of 40 and 24
    got = allowed_pairs({"segment_ids": seg}, window=16)
    lengths = [10, 54, 40, 24]
    assert got["causal_pairs"] == sum(n * (n + 1) // 2 for n in lengths)
    assert got["window_pairs"] == sum(
        sum(min(i + 1, 16) for i in range(n)) for n in lengths)


@pytest.mark.parametrize("name", [
    "swa_train_step_mfu", "attn_window_time_pct.swa_train",
    "attn_full_time_pct.swa_train", "attn_window_roofline",
    "expert_gmm_time_pct.swa_train", "expert_gmm_roofline.swa_train",
    "expert_load_max_over_mean.swa_train", "device_idle_pct.swa_train",
    "device_wait_ms_per_step.swa_train", "host_ms_per_step.swa_train"])
def test_a_metric_file_names_a_reader_that_is_there(name):
    import importlib
    with open(f"{harness.BENCH_DIR}/metrics/{name}.json") as f:
        spec = json.load(f)
    module, _, func = spec["reader"].partition(":")
    assert callable(getattr(importlib.import_module(
        f"benchmark.readers.{module}"), func))
    assert spec["workloads"] == ["trinity_mini.packed16k_swa_train"]
    assert spec["moves"] == "samples_per_s"
