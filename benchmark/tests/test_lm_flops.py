"""The token family's operation and byte counts against values worked
out by hand, at the published widths of ``lfm2_24b_a2b``."""

import numpy as np

from benchmark import harness, lm_flops

CFG = harness.load_cell("lfm2_24b_a2b.packed8k_train")["config"]["model"]


def test_per_token_counts_at_published_widths():
    # in_proj 2048 -> 6144 and out_proj 2048 -> 2048, two operations a MAC
    assert lm_flops.short_conv_flops_per_token(CFG) == \
        2 * 2048 * 6144 + 2 * 2048 * 2048 == 33_554_432
    # q 2048 -> 32 x 64, k and v 2048 -> 8 x 64 each, out 2048 -> 2048
    assert lm_flops.attention_projection_flops_per_token(CFG) == \
        2 * 2048 * 2048 * 2 + 2 * 2 * 2048 * 512 == 20_971_520
    # a pair: 32 heads x (64 MACs for the score + 64 for the value)
    assert lm_flops.attention_flops_per_pair(CFG) == 32 * 128 * 2 == 8192
    assert lm_flops.dense_ffn_flops_per_token(CFG) == \
        6 * 2048 * 11776 == 144_703_488
    assert lm_flops.router_flops_per_token(CFG) == 2 * 2048 * 64
    assert lm_flops.expert_flops_per_row(CFG) == 6 * 2048 * 1536 \
        == 18_874_368
    assert lm_flops.head_flops_per_token(CFG) == 2 * 2048 * 8192
    assert lm_flops.expert_layers(CFG) == 4


def test_causal_pairs_of_packed_documents():
    # documents of 3, 1 and 4 tokens: 6 + 1 + 10 pairs; one of 8: 36
    seg = np.array([[0, 0, 0, 1, 2, 2, 2, 2], [0] * 8])
    assert lm_flops.causal_pairs(seg) == 17 + 36


def test_a_step_of_the_cell_is_about_twelve_teraflop_forward():
    tokens = 4 * 8192
    # an eighth of 4 assignments a token in each of 4 expert layers
    routed = tokens * 4 * 4 // 8
    pairs = 4 * 8192 * 1500 // 2      # documents of ~1500 tokens
    f = lm_flops.forward_flops(CFG, tokens, routed, pairs)
    assert f["short_conv"] == 4 * 33_554_432 * tokens
    assert f["attention_projections"] == 20_971_520 * tokens
    assert f["attention"] == 8192 * pairs
    assert f["dense_ffn"] == 144_703_488 * tokens
    assert f["experts"] == 18_874_368 * routed
    assert f["lm_head"] == 33_554_432 * tokens
    assert f["total"] == sum(v for k, v in f.items() if k != "total")
    assert 12.0e12 < f["total"] < 12.6e12
    assert abs(f["experts"] / f["total"] - 0.10) < 0.01
    t = lm_flops.train_step_flops(CFG, tokens, routed, pairs)
    assert t["total"] == 3 * f["total"]


def test_grouped_products_of_a_step():
    routed = 65536
    need = lm_flops.expert_gmm_step(CFG, 4 * 131072, routed)
    assert need["products"] == 36
    assert need["flops"] == 9 * 2 * routed * 2048 * 1536
    # each product: its rows in and out (2048 + 1536 bfloat16 a row) and
    # the 8 held experts' matrices of each of the 4 layers
    assert need["bytes"] == 9 * (routed * 3584 * 2
                                 + 4 * 8 * 2048 * 1536 * 2)
    # 3.7 TFLOP against 5.9 GB: compute-bound on a v5e by 2.6 x
    assert need["flops"] / 197e12 > 2 * need["bytes"] / 819e9
