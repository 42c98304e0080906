"""Uint8 wire format + staging arena suite (round 8).

Pins the PR's core contract: integral [0, 255] input serves over the
uint8 wire — detected once at submit, batched separately per dtype
against its own pre-warmed executable — with flow BIT-IDENTICAL to the
float32 path, because normalization happens inside the jitted forward
(models/normalize.py) where ``astype`` of an integral value in
[0, 255] is exact. Also covers the pure-host pieces that make the path
zero-copy and zero-compile: the per-(shape, dtype) staging arena, the
dtype-preserving InputPadder round trip, the wire-tag bucket helpers,
and the numpy ``upsample_flow`` recovery for ``low_res`` responses.

CPU-deterministic, `not slow`-eligible: random-weights RAFT-small at
iters=2 over tiny frames, same operating point as test_serving.py."""

import numpy as np
import pytest

from raft_tpu.serving import (WIRE_F32, WIRE_U8, request_wire,
                              upsample_flow, wire_cast)
from raft_tpu.serving.batcher import QueuedRequest
from raft_tpu.serving.engine import _base_of, _wire_of
from raft_tpu.utils.padder import InputPadder
from raft_tpu.utils.staging import StagingArena


# -- wire detection (pure numpy) ----------------------------------------

class TestWireCast:
    def test_uint8_passes_through_unchanged(self):
        a = np.arange(12, dtype=np.uint8).reshape(2, 2, 3)
        tag, out = wire_cast(a)
        assert tag == WIRE_U8
        assert out is a                       # no copy on the hot path

    def test_integral_float32_casts_to_uint8(self):
        f = np.array([[0.0, 1.0, 255.0], [17.0, 128.0, 42.0]],
                     np.float32)
        tag, out = wire_cast(f)
        assert tag == WIRE_U8
        assert out.dtype == np.uint8
        assert np.array_equal(out.astype(np.float32), f)

    def test_integral_int_dtype_casts_to_uint8(self):
        tag, out = wire_cast(np.array([0, 128, 255], np.int32))
        assert tag == WIRE_U8 and out.dtype == np.uint8

    @pytest.mark.parametrize("bad", [
        np.array([0.5, 1.0], np.float32),          # non-integral
        np.array([-1.0, 3.0], np.float32),         # below range (wraps)
        np.array([256.0, 3.0], np.float32),        # above range (wraps)
        np.array([np.nan, 3.0], np.float32),       # NaN
        np.array([1.0, 2.0], np.float64),          # f64 non-integral ok?
    ])
    def test_non_integral_or_out_of_range_stays_float32(self, bad):
        tag, out = wire_cast(bad)
        if np.all(np.isfinite(bad)) and np.array_equal(
                bad.astype(np.uint8).astype(bad.dtype), bad):
            # the f64-but-integral row legitimately rides the u8 wire
            assert tag == WIRE_U8
        else:
            assert tag == WIRE_F32
            assert out.dtype == np.float32

    def test_mixed_pair_falls_back_to_float32_for_both(self):
        u8 = np.full((2, 2, 3), 7, np.uint8)
        f32 = np.full((2, 2, 3), 0.5, np.float32)
        tag, a1, a2 = request_wire(u8, f32)
        assert tag == WIRE_F32
        assert a1.dtype == a2.dtype == np.float32
        assert np.array_equal(a1, u8.astype(np.float32))  # exact widen

    def test_matched_uint8_pair_stays_uint8(self):
        u8 = np.full((2, 2, 3), 7, np.uint8)
        tag, a1, a2 = request_wire(u8, u8 + 1)
        assert tag == WIRE_U8
        assert a1.dtype == a2.dtype == np.uint8


class TestBucketTagHelpers:
    @pytest.mark.parametrize("bucket,wire,base", [
        ((40, 64, "u8"), "u8", (40, 64)),
        ((40, 64, "f32"), "f32", (40, 64)),
        ((40, 64, 1, "u8"), "u8", (40, 64, 1)),          # brownout lvl
        ((64, 96, "mesh", "f32"), "f32", (64, 96, "mesh")),
        ((40, 64, "warm", 1, "u8"), "u8", (40, 64, "warm", 1)),
        ((40, 64), "f32", (40, 64)),   # untagged (hand-built) -> f32
        ((), "f32", ()),
    ])
    def test_wire_and_base_of(self, bucket, wire, base):
        assert _wire_of(bucket) == wire
        assert _base_of(bucket) == base

    def test_queued_request_low_res_defaults_false(self):
        r = QueuedRequest(None, None, None, bucket=(40, 64, "u8"),
                          t_submit=0.0)
        assert r.low_res is False
        r2 = QueuedRequest(None, None, None, bucket=(40, 64, "u8"),
                           t_submit=0.0, low_res=True)
        assert r2.low_res is True


# -- padder / normalization dtype preservation --------------------------

class TestUint8PadderRoundTrip:
    def test_pad_preserves_dtype_and_unpads_bit_exact(self):
        rng = np.random.default_rng(0)
        img = rng.integers(0, 256, (33, 57, 3), dtype=np.uint8)
        padder = InputPadder(img.shape)
        out = padder.pad(img)
        assert out.dtype == np.uint8          # np.pad edge keeps dtype
        assert out.shape[:2] == padder.padded_shape == (40, 64)
        assert np.array_equal(padder.unpad(out), img)

    def test_normalize_image_exact_across_dtypes(self):
        from raft_tpu.models.normalize import normalize_image
        rng = np.random.default_rng(1)
        u8 = rng.integers(0, 256, (8, 8, 3), dtype=np.uint8)
        a = normalize_image(u8, np.float32)
        b = normalize_image(u8.astype(np.float32), np.float32)
        assert np.array_equal(a, b)           # the bit-exactness root
        assert a.min() >= -1.0 and a.max() <= 1.0


# -- staging arena ------------------------------------------------------

class TestStagingArena:
    def test_acquire_shape_dtype_and_recycle_identity(self):
        arena = StagingArena()
        b = arena.acquire((4, 40, 64, 3), np.uint8)
        assert b.shape == (4, 40, 64, 3) and b.dtype == np.uint8
        arena.release(b)
        assert arena.pooled_buffers() == 1
        again = arena.acquire((4, 40, 64, 3), np.uint8)
        assert again is b                     # recycled, not realloc'd
        assert arena.pooled_buffers() == 0

    def test_dtype_keys_are_disjoint(self):
        arena = StagingArena()
        b = arena.acquire((2, 2), np.uint8)
        arena.release(b)
        other = arena.acquire((2, 2), np.float32)
        assert other is not b and other.dtype == np.float32
        assert arena.pooled_buffers() == 1    # u8 buffer still pooled

    def test_per_key_cap_and_none_release(self):
        arena = StagingArena()
        bufs = [arena.acquire((3, 3), np.float32) for _ in range(6)]
        arena.release(None, *bufs, None)      # None slots are no-ops
        assert arena.pooled_buffers() == StagingArena._MAX_PER_KEY


# -- upsample_flow (host-side low_res recovery) -------------------------

class TestUpsampleFlow:
    def test_constant_field_and_shape(self):
        f = np.full((3, 5, 8, 2), 3.5, np.float32)
        out = upsample_flow(f)
        assert out.shape == (3, 40, 64, 2)
        assert out.dtype == np.float32
        # a*(1-w) + a*w is constant only to rounding in float32
        assert np.max(np.abs(out - 8 * 3.5)) < 1e-4

    def test_3d_input_squeezes_and_corners_align(self):
        rng = np.random.default_rng(2)
        f = rng.normal(size=(4, 6, 2)).astype(np.float32)
        out = upsample_flow(f)
        assert out.shape == (32, 48, 2)
        # align-corners: the output corners sit exactly on input
        # samples, so the bilinear weights collapse to identity there.
        assert np.array_equal(out[0, 0], 8 * f[0, 0])
        assert np.array_equal(out[-1, -1], 8 * f[-1, -1])

    def test_padder_crops_to_raw_resolution(self):
        padder = InputPadder((36, 60, 3))     # pads to (40, 64)
        f = np.zeros((5, 8, 2), np.float32)
        out = upsample_flow(f, padder=padder)
        assert out.shape == (36, 60, 2)

    def test_custom_factor(self):
        f = np.ones((1, 2, 2, 2), np.float32)
        out = upsample_flow(f, factor=4)
        assert out.shape == (1, 8, 8, 2)
        assert np.max(np.abs(out - 4.0)) < 1e-5


# -- bit identity through the executables (real predictor, CPU) ---------

SHAPES = [(36, 60), (33, 57)]                 # both pad to (40, 64)


@pytest.fixture(scope="module")
def predictor():
    from raft_tpu.evaluate import load_predictor
    return load_predictor("random", small=True, iters=2)


@pytest.fixture(scope="module")
def u8_batch():
    rng = np.random.default_rng(11)
    i1 = rng.integers(0, 256, (2, 40, 64, 3), dtype=np.uint8)
    i2 = rng.integers(0, 256, (2, 40, 64, 3), dtype=np.uint8)
    return i1, i2


def _engine(predictor, **kw):
    from raft_tpu.serving import ServingConfig, ServingEngine
    return ServingEngine(predictor, ServingConfig(**kw))


class TestBitIdentityAcrossWires:
    def test_call_bit_identical(self, predictor, u8_batch):
        i1, i2 = u8_batch
        low_u, up_u = predictor(i1[0], i2[0])
        low_f, up_f = predictor(i1[0].astype(np.float32),
                                i2[0].astype(np.float32))
        assert np.array_equal(up_u, up_f)
        assert np.array_equal(low_u, low_f)

    def test_dispatch_batch_bit_identical(self, predictor, u8_batch):
        i1, i2 = u8_batch
        low_u, up_u = predictor.predict_batch(i1, i2)
        low_f, up_f = predictor.predict_batch(i1.astype(np.float32),
                                              i2.astype(np.float32))
        assert np.array_equal(up_u, up_f)
        assert np.array_equal(low_u, low_f)

    def test_encode_and_refine_bit_identical(self, predictor, u8_batch):
        i1, i2 = u8_batch
        f1, f2 = i1.astype(np.float32), i2.astype(np.float32)
        fm1_u = np.asarray(predictor.encode_dispatch(i1))
        fm2_u = np.asarray(predictor.encode_dispatch(i2))
        fm1_f = np.asarray(predictor.encode_dispatch(f1))
        fm2_f = np.asarray(predictor.encode_dispatch(f2))
        assert np.array_equal(fm1_u, fm1_f)
        assert np.array_equal(fm2_u, fm2_f)
        # cold refine: images1 feeds cnet, so its dtype matters too
        low_u, up_u = map(np.asarray, predictor.refine_dispatch(
            i1, fm1_u, fm2_u))
        low_f, up_f = map(np.asarray, predictor.refine_dispatch(
            f1, fm1_f, fm2_f))
        assert np.array_equal(up_u, up_f)
        # warm refine from the cold flow
        _, warm_u = map(np.asarray, predictor.refine_dispatch(
            i1, fm1_u, fm2_u, flow_init=low_u, warm=True))
        _, warm_f = map(np.asarray, predictor.refine_dispatch(
            f1, fm1_f, fm2_f, flow_init=low_f, warm=True))
        assert np.array_equal(warm_u, warm_f)

    @pytest.mark.multidevice
    def test_sharded_dispatch_bit_identical(self, predictor):
        import jax

        from raft_tpu.parallel import make_mesh
        if jax.device_count() < 4:
            pytest.skip("needs 4 devices")
        mesh = make_mesh(n_data=1, n_spatial=4,
                         devices=jax.devices()[:4])
        rng = np.random.default_rng(12)
        i1 = rng.integers(0, 256, (1, 64, 96, 3), dtype=np.uint8)
        i2 = rng.integers(0, 256, (1, 64, 96, 3), dtype=np.uint8)
        low_u, up_u = map(np.asarray, predictor.sharded_dispatch(
            i1, i2, mesh=mesh))
        low_f, up_f = map(np.asarray, predictor.sharded_dispatch(
            i1.astype(np.float32), i2.astype(np.float32), mesh=mesh))
        assert np.array_equal(up_u, up_f)
        assert np.array_equal(low_u, low_f)


class TestEngineWirePath:
    def test_mixed_dtype_traffic_zero_compiles_and_bit_equal(
            self, predictor):
        """The acceptance criterion in miniature: after dual-dtype
        warmup, uint8 / integral-float32 / non-integral-float32 traffic
        over one bucket triggers ZERO fresh compiles, and the first two
        resolve bit-identically (integral f32 auto-detects onto the u8
        wire)."""
        from raft_tpu.serving.metrics import CompileWatch
        rng = np.random.default_rng(21)
        pairs_u8 = [(rng.integers(0, 256, (h, w, 3), dtype=np.uint8),
                     rng.integers(0, 256, (h, w, 3), dtype=np.uint8))
                    for h, w in SHAPES]
        pairs_f32i = [(a.astype(np.float32), b.astype(np.float32))
                      for a, b in pairs_u8]
        pairs_f32n = [(a + 0.25, b + 0.25) for a, b in pairs_f32i]
        eng = _engine(predictor, max_batch=4, max_wait_ms=3.0,
                      buckets=(SHAPES[0],))
        eng.start()                            # dual-dtype warmup
        try:
            with CompileWatch() as watch:
                futs_u8 = [eng.submit(*p) for p in pairs_u8]
                futs_f32i = [eng.submit(*p) for p in pairs_f32i]
                futs_f32n = [eng.submit(*p) for p in pairs_f32n]
                res_u8 = [f.result(60) for f in futs_u8]
                res_f32i = [f.result(60) for f in futs_f32i]
                [f.result(60) for f in futs_f32n]
            assert watch.compiles == 0
            for a, b in zip(res_u8, res_f32i):
                assert np.array_equal(a, b)
                assert a.dtype == np.float32   # response is always f32
        finally:
            eng.close()

    def test_staged_bytes_4x_smaller_on_u8_wire(self, predictor):
        """The arena stages cap-sized (max_batch) buffers whatever the
        batch fill, so staged bytes per batch are exact: 2 frames x
        cap x padded HxW x 3 x itemsize — and the uint8 wire's itemsize
        is 1 vs float32's 4."""
        per_batch_u8 = 2 * 4 * 40 * 64 * 3    # itemsize 1
        rng = np.random.default_rng(31)
        u8 = [(rng.integers(0, 256, (36, 60, 3), dtype=np.uint8),
               rng.integers(0, 256, (36, 60, 3), dtype=np.uint8))
              for _ in range(4)]
        f32 = [(a.astype(np.float32) + 0.5, b.astype(np.float32) + 0.5)
               for a, b in u8]                # non-integral: f32 wire
        staged = {}
        for name, pairs in (("u8", u8), ("f32", f32)):
            eng = _engine(predictor, max_batch=4, max_wait_ms=20.0,
                          buckets=(SHAPES[0],))
            eng.start()
            try:
                res = [eng.submit(*p).result(60) for p in pairs]
            finally:
                eng.close()
            snap = eng.metrics.snapshot()
            batches = int(snap["serving_batches"])
            assert batches >= 1
            staged[name] = snap["serving_staged_bytes"] / batches
            # every response is an unpadded float32 (36, 60, 2) flow
            assert snap["serving_returned_bytes"] == sum(
                r.nbytes for r in res)
            assert all(r.shape == (36, 60, 2) for r in res)
        assert staged["u8"] == per_batch_u8
        assert staged["f32"] == 4 * per_batch_u8
        assert eng.arena.pooled_buffers() >= 1  # buffers were recycled

    def test_low_res_response_and_host_upsample(self, predictor):
        """``low_res=True`` resolves to the padded 1/8-grid flow —
        bit-equal to the executable's flow_low — and ``upsample_flow``
        with the stamped padder recovers raw-resolution geometry."""
        rng = np.random.default_rng(41)
        im1 = rng.integers(0, 256, (36, 60, 3), dtype=np.uint8)
        im2 = rng.integers(0, 256, (36, 60, 3), dtype=np.uint8)
        padder = InputPadder(im1.shape)
        p1, p2 = padder.pad(im1, im2)
        ref_low, ref_up = predictor.predict_batch(
            np.repeat(p1[None], 4, axis=0), np.repeat(p2[None], 4, axis=0))
        eng = _engine(predictor, max_batch=4, max_wait_ms=3.0,
                      buckets=((36, 60),))
        eng.start()
        try:
            fut = eng.submit(im1, im2, low_res=True)
            lo = fut.result(60)
            full = eng.submit(im1, im2).result(60)
        finally:
            eng.close()
        assert lo.shape == (5, 8, 2)          # padded (40, 64) / 8
        assert np.array_equal(lo, ref_low[0])
        assert np.array_equal(full, padder.unpad(ref_up[0]))
        up = upsample_flow(lo, padder=fut.padder)
        assert up.shape == (36, 60, 2)
        # documented contract: host upsample approximates, never
        # impersonates, the in-graph convex upsampling
        assert up.dtype == np.float32
