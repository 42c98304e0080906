"""``benchmark/ssm_flops.py`` at the published widths of
granite-4.0-h-micro, against counts written out by hand."""

from benchmark import harness, ssm_flops

CFG = harness.read_json(harness.BENCH_DIR, "configs",
                        "granite_4_0_h_micro.json")["model"]


def test_projections_mlp_and_head_by_hand():
    assert ssm_flops.d_inner(CFG) == 4096
    assert ssm_flops.mamba_projection_flops_per_token(CFG) == \
        2 * 2048 * 8512 + 2 * 4096 * 2048
    assert ssm_flops.conv_flops_per_token(CFG) == 2 * 4 * 4352
    assert ssm_flops.attention_projection_flops_per_token(CFG) == \
        2 * 2048 * (2048 + 512 + 512) + 2 * 2048 * 2048
    assert ssm_flops.attention_flops_per_pair(CFG) == 32 * 2 * 2 * 64
    assert ssm_flops.mlp_flops_per_token(CFG) == 2 * 3 * 2048 * 8192
    assert ssm_flops.head_flops_per_token(CFG) == 2 * 2048 * 12544


def test_the_scans_four_products_by_hand():
    parts = ssm_flops.ssd_flops_per_chunk(CFG)
    assert parts == {"cb": 2 * 256 * 256 * 128,
                     "intra": 2 * 256 * 256 * 64 * 64,
                     "states": 2 * 256 * 64 * 128 * 64,
                     "carried": 2 * 256 * 128 * 64 * 64}
    moved = ssm_flops.ssd_bytes_per_token(CFG)
    # x 8192 B, B and C 256 B each, dt 256 B (float32), y 8192 B
    assert moved["forward"] == 8192 + 512 + 256 + 8192
    assert moved["backward"] == 2 * (8192 + 512 + 256) + 8192


def test_a_step_of_two_packed_sequences():
    tokens, chunks = 2 * 8192, 2 * 32
    pairs = 2 * 8 * (1024 * 1025 // 2)       # eight documents of 1024
    forward = ssm_flops.forward_flops(CFG, tokens, chunks, pairs)
    assert forward["total"] == sum(v for k, v in forward.items()
                                   if k != "total")
    # 1.58 GFLOP a token forward, the scan a fortieth of it
    assert 1.57e9 < forward["total"] / tokens < 1.60e9
    assert 0.02 < forward["ssd_scan"] / forward["total"] < 0.03
    assert 0.62 < forward["mlp"] / forward["total"] < 0.66
    step = ssm_flops.train_step_flops(CFG, tokens, chunks, pairs)
    assert step["total"] == 3 * forward["total"]
    scan = ssm_flops.ssd_step(CFG, tokens, chunks)
    assert scan["flops"] == step["ssd_scan"]
    assert scan["bytes"] == 9 * tokens * (17152 + 26112)
    # on a v5e: compute-bound, by a little
    assert scan["flops"] / 197e12 > scan["bytes"] / 819e9
