"""Flow I/O round-trips, padder geometry, warm-start, and viz sanity."""

import numpy as np
import pytest

from raft_tpu.data import frame_utils
from raft_tpu.utils import InputPadder, forward_interpolate
from raft_tpu.utils.flow_viz import flow_to_image


def test_flo_roundtrip(tmp_path, rng):
    flow = rng.standard_normal((13, 17, 2)).astype(np.float32)
    p = str(tmp_path / "x.flo")
    frame_utils.write_flo(p, flow)
    back = frame_utils.read_flo(p)
    np.testing.assert_array_equal(back, flow)


def test_pfm_roundtrip(tmp_path, rng):
    img = rng.standard_normal((7, 9)).astype(np.float32)
    p = str(tmp_path / "x.pfm")
    frame_utils.write_pfm(p, img)
    back, scale = frame_utils.read_pfm(p)
    np.testing.assert_allclose(back, img, atol=1e-6)


def test_kitti_png_roundtrip(tmp_path, rng):
    pytest.importorskip("cv2")
    flow = (rng.standard_normal((6, 8, 2)) * 10).astype(np.float32)
    # KITTI encoding quantizes to 1/64 px.
    flow = np.round(flow * 64) / 64
    p = str(tmp_path / "x.png")
    frame_utils.write_flow_kitti(p, flow)
    back, valid = frame_utils.read_flow_kitti(p)
    np.testing.assert_allclose(back, flow, atol=1 / 64)
    assert valid.min() == 1


def test_padder_sintel_center():
    p = InputPadder((1, 436, 1024, 3), mode="sintel")
    assert p.padded_shape == (440, 1024)
    x = np.zeros((1, 436, 1024, 3), np.float32)
    y = p.pad(x)
    assert y.shape == (1, 440, 1024, 3)
    assert p.unpad(y).shape == x.shape


def test_padder_kitti_bottom():
    p = InputPadder((1, 375, 1242, 3), mode="kitti")
    y = p.pad(np.ones((1, 375, 1242, 3), np.float32))
    assert y.shape == (1, 376, 1248, 3)
    # reference F.pad([l, r, 0, pad_ht]): vertical padding at the bottom
    assert p._pad[2] == 0 and p._pad[3] == 1


def test_padder_noop_when_divisible():
    p = InputPadder((1, 64, 128, 3))
    x = np.random.rand(1, 64, 128, 3).astype(np.float32)
    np.testing.assert_array_equal(p.pad(x), x)


@pytest.mark.parametrize("dtype", [np.uint8, np.float32])
@pytest.mark.parametrize("mode", ["sintel", "kitti"])
@pytest.mark.parametrize("hw", [
    (64, 128),     # divisible: nothing to pad
    (436, 96),     # 4 rows: 2 + 2 in sintel mode, 4 below in kitti mode
    (61, 43),      # odd on both axes: 3 rows (1 + 2), 5 columns (2 + 3)
    (63, 47),      # one row, one column: all of it on the far side
    (58, 90),      # even: 6 rows, 6 columns
])
def test_pad_into_equals_np_pad(hw, mode, dtype):
    """``pad_into`` writes what ``np.pad(mode="edge")`` builds, bit for
    bit, whatever the destination held before, and touches nothing
    outside its slot."""
    rng = np.random.default_rng(hw[0] * 1000 + hw[1])
    x = rng.integers(0, 256, hw + (3,)).astype(dtype)
    if dtype is np.float32:
        x += rng.random(x.shape, np.float32)     # not integral
    p = InputPadder(x.shape, mode=mode)
    l, r, t, b = p._pad
    want = np.pad(x, ((t, b), (l, r), (0, 0)), mode="edge")
    np.testing.assert_array_equal(p.pad(x), want)
    stale = rng.integers(0, 256, (3,) + want.shape).astype(dtype)
    batch = stale.copy()
    out = p.pad_into(batch[1], x)
    assert out.dtype == dtype and np.shares_memory(out, batch)
    assert out.tobytes() == want.tobytes()
    np.testing.assert_array_equal(batch[0], stale[0])
    np.testing.assert_array_equal(batch[2], stale[2])
    np.testing.assert_array_equal(p.unpad(batch[1]), x)
    # a whole stack at once, as pad() takes one
    stack = np.stack([x, x[::-1]])
    dst = np.empty((2,) + want.shape, dtype)
    np.testing.assert_array_equal(p.pad_into(dst, stack), p.pad(stack))


def test_pad_into_refuses_a_wrong_destination():
    p = InputPadder((30, 44, 3), mode="sintel")
    x = np.zeros((30, 44, 3), np.float32)
    with pytest.raises(ValueError, match="pads to"):
        p.pad_into(np.empty((30, 44, 3), np.float32), x)
    with pytest.raises(ValueError, match="uint8 destination"):
        p.pad_into(np.empty((32, 48, 3), np.uint8), x)


def test_forward_interpolate_zero_flow_is_zero():
    flow = np.zeros((8, 10, 2), np.float32)
    out = forward_interpolate(flow)
    np.testing.assert_allclose(out, 0, atol=1e-6)


def test_forward_interpolate_constant_shift():
    flow = np.ones((12, 16, 2), np.float32) * 2.0
    out = forward_interpolate(flow)
    # Interior should keep the constant flow.
    np.testing.assert_allclose(out[4:-4, 4:-4], 2.0, atol=1e-5)


def test_flow_to_image_shapes(rng):
    flow = rng.standard_normal((10, 12, 2)).astype(np.float32)
    img = flow_to_image(flow)
    assert img.shape == (10, 12, 3) and img.dtype == np.uint8
    assert img.max() > 0
