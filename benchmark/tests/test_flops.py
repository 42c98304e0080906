"""The operation and byte counts against values worked out by hand."""

from benchmark import flops


def test_one_conv_layer_fnet_stem_at_sintel():
    # 7x7 stride-2 stem, 3 -> 64 channels, 440x1024 -> 220x512:
    # 220*512 = 112640 outputs; each 7*7*3 = 147 multiply-adds for each
    # of 64 channels: 112640 * 147 * 64 = 1059717120 MACs, twice that.
    assert flops.conv_flops(220, 512, 7, 7, 3, 64) == 2_119_434_240


def test_correlation_at_55x128_both_ways():
    n = 55 * 128                                   # 7040 queries
    assert n == 7040
    # all-pairs: 2 * 7040^2 * 256
    volume = 2 * 49_561_600 * 256
    assert volume == 25_375_539_200
    # pooled levels 27x64, 13x32, 6x16 = 1728 + 416 + 96 = 2240 cells a
    # query, 4 operations each
    assert flops.pyramid_cells(55, 128) == 7040 + 2240
    assert flops.allpairs_flops(55, 128, 256) == volume + 7040 * 2240 * 4
    # a window read from the volume: 4 levels * 81 points * 7 operations
    assert flops.lookup_from_volume_flops(55, 128, 4) == 7040 * 4 * 81 * 7
    # a window on demand: 4 levels * (100 dots of 2*256 + 81 * 7)
    assert flops.windowed_lookup_flops(55, 128, 256, 4) == \
        7040 * 4 * (100 * 512 + 567)
    c = flops.correlation_flops(55, 128, 256, 4, iters=32)
    assert c["allpairs"] == 25_949_552_640
    assert c["windowed"] == 46_648_279_040
    assert c["cheaper"] == "allpairs" and c["flops"] == c["allpairs"]
    # one iteration alone is cheaper on demand
    assert flops.correlation_flops(55, 128, 256, 4, iters=1)["cheaper"] \
        == "windowed"


def test_lookup_kernel_call_bytes():
    # bf16 features: queries 7040*256*2, targets 9280*256*2, coordinates
    # 7040*2*4, windows out 7040*324*2
    call = flops.corr_lookup_call(1, 55, 128, 256, 4, iters=32)
    assert call["bytes"] == 3_604_480 + 4_751_360 + 56_320 + 4_561_920
    assert call["flops"] == 25_949_552_640 / 32
    assert flops.corr_lookup_call(8, 55, 128, 256, 4, 32)["bytes"] == \
        8 * call["bytes"]


def test_update_block_large_per_pixel():
    # one 1/8-resolution pixel of RAFT-large's update: multiply-adds
    motion = 324 * 256 + 9 * 256 * 192 + 49 * 2 * 128 + 9 * 128 * 64 \
        + 9 * 256 * 126
    gru = 6 * 5 * 384 * 128
    head = 9 * 128 * 256 + 9 * 256 * 2
    mask = 9 * 128 * 256 + 256 * 576
    u = flops.update_flops(1, 1, small=False, radius=4)
    assert u["iteration"] == 2 * (motion + gru + head)
    assert u["mask"] == 2 * mask


def test_update_block_small_per_pixel():
    motion = 196 * 96 + 49 * 2 * 64 + 9 * 64 * 32 + 9 * 128 * 80
    gru = 3 * 9 * 242 * 96
    head = 9 * 96 * 128 + 9 * 128 * 2
    u = flops.update_flops(1, 1, small=True, radius=3)
    assert u["iteration"] == 2 * (motion + gru + head) and u["mask"] == 0


def test_encoder_large_by_stage():
    # BasicEncoder at 440x1024 -> 220x512 -> 110x256 -> 55x128
    macs = (112640 * 147 * 64
            + 112640 * 4 * 9 * 64 * 64
            + 28160 * (9 * 64 * 96 + 3 * 9 * 96 * 96 + 64 * 96)
            + 7040 * (9 * 96 * 128 + 3 * 9 * 128 * 128 + 96 * 128)
            + 7040 * 128 * 256)
    assert flops.encoder_flops(440, 1024, False, 256) == 2 * macs


def test_forward_totals_add_up():
    for small in (False, True):
        parts = flops.forward_flops(440, 1024, small, 32)
        assert parts["total"] == sum(v for k, v in parts.items()
                                     if k != "total")
    large = flops.forward_flops(440, 1024, False, 32)
    assert 1.40e12 < large["total"] < 1.44e12
    assert large["fnet"] == 2 * large["cnet"]
