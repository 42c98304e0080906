"""Parity tests: native (C++) data-layer kernels vs their numpy/cv2
references — the reference repo's kernel-testing pattern (SURVEY.md §4)
applied to the host-side pipeline."""

import numpy as np
import pytest

from raft_tpu import native

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native library unavailable")
cv2 = pytest.importorskip("cv2")


@pytest.fixture
def img(rng):
    return rng.uniform(0, 255, (37, 53, 3)).astype(np.float32)


@pytest.mark.parametrize("size", [(17, 29), (74, 106), (37, 53)])
def test_resize_bilinear_matches_cv2(img, size):
    h2, w2 = size
    got = native.resize_bilinear(img, h2, w2)
    ref = cv2.resize(img, (w2, h2), interpolation=cv2.INTER_LINEAR)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("size", [(17, 29), (74, 106)])
def test_resize_nearest_matches_cv2(img, size):
    h2, w2 = size
    got = native.resize_nearest(img, h2, w2)
    ref = cv2.resize(img, (w2, h2), interpolation=cv2.INTER_NEAREST)
    np.testing.assert_array_equal(got, ref)


def test_resize_two_channel_flow(img, rng):
    flow = rng.standard_normal((37, 53, 2)).astype(np.float32)
    got = native.resize_bilinear(flow, 20, 30)
    ref = cv2.resize(flow, (30, 20), interpolation=cv2.INTER_LINEAR)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_photometric_ops_match_numpy(img):
    before = img.copy()

    def np_brightness(x, f):
        return np.clip(x * f, 0, 255)

    def np_contrast(x, f):
        g = (0.299 * x[..., 0] + 0.587 * x[..., 1]
             + 0.114 * x[..., 2]).mean()
        return np.clip(x * f + g * (1 - f), 0, 255)

    def np_saturation(x, f):
        g = (0.299 * x[..., 0] + 0.587 * x[..., 1]
             + 0.114 * x[..., 2])[..., None]
        return np.clip(x * f + g * (1 - f), 0, 255)

    for nat, ref, f in [(native.adjust_brightness, np_brightness, 1.3),
                        (native.adjust_contrast, np_contrast, 0.7),
                        (native.adjust_saturation, np_saturation, 1.2)]:
        np.testing.assert_allclose(nat(img, f), ref(img, f),
                                   rtol=1e-4, atol=1e-3)
    # non-inplace calls must leave the input untouched
    np.testing.assert_array_equal(img, before)
    # inplace writes through
    buf = img.copy()
    out = native.adjust_brightness(buf, 1.5, inplace=True)
    assert out is buf and not np.array_equal(buf, before)


def test_erase_rect(img):
    fill = img.reshape(-1, 3).mean(0)
    got = native.erase_rect(img, 5, 7, 10, 100, fill)  # clips at borders
    ref = img.copy()
    ref[5:15, 7:107] = fill
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-6)


def test_resize_sparse_flow_matches_numpy(rng):
    from raft_tpu.data.augmentor import SparseFlowAugmentor

    h, w = 23, 31
    flow = rng.standard_normal((h, w, 2)).astype(np.float32) * 5
    valid = (rng.uniform(size=(h, w)) > 0.6).astype(np.float32)
    for fx, fy in [(1.3, 1.3), (0.7, 1.1), (1.0, 1.0)]:
        got_f, got_v = native.resize_sparse_flow(flow, valid, fx, fy)
        # numpy reference: force the pure-python path
        import raft_tpu.native as n
        saved = n._lib, n._tried
        n._lib, n._tried = None, True
        try:
            ref_f, ref_v = SparseFlowAugmentor.resize_sparse_flow_map(
                flow, valid, fx, fy)
        finally:
            n._lib, n._tried = saved
        np.testing.assert_array_equal(got_v, ref_v)
        np.testing.assert_allclose(got_f, ref_f, rtol=1e-5, atol=1e-5)


def test_augmentor_end_to_end_with_native(rng):
    """Full FlowAugmentor pass with the native backend active."""
    from raft_tpu.data.augmentor import FlowAugmentor

    aug = FlowAugmentor(crop_size=(32, 48), seed=0)
    img1 = rng.uniform(0, 255, (50, 70, 3)).astype(np.float32)
    img2 = rng.uniform(0, 255, (50, 70, 3)).astype(np.float32)
    flow = rng.standard_normal((50, 70, 2)).astype(np.float32)
    a, b, f = aug(img1, img2, flow)
    assert a.shape == (32, 48, 3) and f.shape == (32, 48, 2)
    assert np.isfinite(a).all() and np.isfinite(f).all()


@pytest.mark.parametrize("scales", [(0.83, 1.27), (1.503, 0.91)])
def test_resize_by_scale_factor_matches_cv2_fx_fy(img, scales):
    """cv2 maps coordinates by the exact fx/fy factors, not the size
    ratio; the two differ at non-round scales."""
    fx, fy = scales
    h, w = img.shape[:2]
    h2, w2 = int(round(h * fy)), int(round(w * fx))
    got = native.resize_bilinear(img, h2, w2, fx=fx, fy=fy)
    ref = cv2.resize(img, None, fx=fx, fy=fy,
                     interpolation=cv2.INTER_LINEAR)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-3)


def test_library_is_named_by_source_content_inside_the_checkout(monkeypatch):
    """A binary built from another tree's augment.cpp has another name,
    so a stale one can never be loaded; with no override the build lands
    in the git-ignored raft_tpu/native/_build/."""
    import hashlib
    import os

    from raft_tpu.native import build
    monkeypatch.delenv("RAFT_TPU_NATIVE_DIR", raising=False)
    with open(build._SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    path = build.lib_path()
    assert os.path.basename(path) == f"libraft_augment_{digest}.so"
    assert os.path.dirname(path) == os.path.join(
        os.path.dirname(build._SRC), "_build")
