"""Serving-engine suite: bucket routing, batch closure policy,
bit-exact served outputs, warmup compile accounting, metrics, shutdown
semantics — plus regression pins for the round-5 ADVICE fixes that rode
along (logger TB-image guard, corr data-axis eligibility fold,
ProcessDataLoader pool reuse + timed drains).

All CPU-deterministic and `not slow`-eligible: the model is the random-
weights RAFT-small at iters=2 over tiny frames, and batched CPU
execution is bit-identical per sample to batch-1 (pinned here — it is
what lets the equality tests assert exact, not approximate)."""

import os
import threading
import time

import numpy as np
import pytest

from raft_tpu.serving.batcher import (BacklogFull, QueuedRequest,
                                      ShapeBucketBatcher)
from raft_tpu.serving.metrics import ServingMetrics, _percentile


def _req(bucket=(40, 64), t=0.0):
    return QueuedRequest(None, None, None, bucket=bucket, t_submit=t)


def _req_p(priority, bucket=(40, 64), t=0.0):
    return QueuedRequest(None, None, None, bucket=bucket, t_submit=t,
                         priority=priority)


class _FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


class TestBatcher:
    def test_full_bucket_closes_immediately(self):
        clock = _FakeClock()
        b = ShapeBucketBatcher(max_batch=3, max_wait_s=100.0, clock=clock)
        for _ in range(3):
            b.enqueue(_req(t=clock.t))
        batch = b.next_batch(timeout=0)
        assert len(batch) == 3
        assert b.pending() == 0

    def test_deadline_closes_partial_batch(self):
        clock = _FakeClock(10.0)
        b = ShapeBucketBatcher(max_batch=8, max_wait_s=1.0, clock=clock)
        b.enqueue(_req(t=10.0))
        b.enqueue(_req(t=10.2))
        assert b.next_batch(timeout=0) == []       # deadline not reached
        clock.t = 11.0                             # oldest hits 1.0s wait
        batch = b.next_batch(timeout=0)
        assert len(batch) == 2

    def test_bucket_routing_is_shape_homogeneous(self):
        clock = _FakeClock()
        b = ShapeBucketBatcher(max_batch=2, max_wait_s=100.0, clock=clock)
        for bucket in ((40, 64), (56, 80), (40, 64), (56, 80)):
            b.enqueue(_req(bucket=bucket, t=clock.t))
        first = b.next_batch(timeout=0)
        second = b.next_batch(timeout=0)
        assert len(first) == len(second) == 2
        for batch in (first, second):
            assert len({r.bucket for r in batch}) == 1
        assert {first[0].bucket, second[0].bucket} == {(40, 64), (56, 80)}

    def test_oldest_deadline_first_across_buckets(self):
        clock = _FakeClock(0.0)
        b = ShapeBucketBatcher(max_batch=8, max_wait_s=1.0, clock=clock)
        b.enqueue(_req(bucket=(56, 80), t=0.5))    # younger
        b.enqueue(_req(bucket=(40, 64), t=0.0))    # older
        clock.t = 2.0                              # both past deadline
        assert b.next_batch(timeout=0)[0].bucket == (40, 64)
        assert b.next_batch(timeout=0)[0].bucket == (56, 80)

    def test_backlog_cap(self):
        b = ShapeBucketBatcher(max_batch=8, max_pending=2)
        b.enqueue(_req())
        b.enqueue(_req())
        with pytest.raises(BacklogFull, match="backlog full"):
            b.enqueue(_req())

    def test_close_drains_then_none(self):
        clock = _FakeClock()
        b = ShapeBucketBatcher(max_batch=8, max_wait_s=100.0, clock=clock)
        b.enqueue(_req(t=0.0))
        b.close()
        assert len(b.next_batch(timeout=0)) == 1   # no deadline wait
        assert b.next_batch(timeout=0) is None
        with pytest.raises(RuntimeError, match="closed"):
            b.enqueue(_req())

    def test_wakes_blocked_dispatcher_on_enqueue(self):
        b = ShapeBucketBatcher(max_batch=1, max_wait_s=100.0)
        got = []
        th = threading.Thread(
            target=lambda: got.append(b.next_batch(timeout=5)))
        th.start()
        time.sleep(0.05)
        b.enqueue(_req(t=time.monotonic()))
        th.join(timeout=5)
        assert not th.is_alive() and len(got[0]) == 1


class TestMetrics:
    def test_percentile_interpolation(self):
        vals = [1.0, 2.0, 3.0, 4.0]
        assert _percentile(vals, 50) == pytest.approx(2.5)
        assert _percentile(vals, 100) == pytest.approx(4.0)
        assert _percentile([], 99) == 0.0
        assert _percentile([7.0], 99) == 7.0

    def test_counters_and_snapshot(self):
        m = ServingMetrics()
        m.record_submit(queue_depth=3)
        m.record_submit(queue_depth=1)
        m.record_batch(size=2, padded_to=4, compiles=1)
        m.record_done(0.010)
        m.record_done(0.030)
        m.record_reject()
        m.record_shed()
        snap = m.snapshot()
        assert snap["serving_requests"] == 2.0
        assert snap["serving_rejected"] == 1.0
        assert snap["serving_shed"] == 1.0
        assert snap["serving_responses"] == 2.0
        assert snap["serving_batches"] == 1.0
        assert snap["serving_padded_slots"] == 2.0
        assert snap["serving_compiles"] == 1.0
        assert snap["serving_queue_depth_peak"] == 3.0
        assert snap["serving_latency_p50_ms"] == pytest.approx(20.0)
        assert m.batch_histogram() == {2: 1}
        assert m.mean_batch_size() == 2.0
        assert "p99" in m.report() or "requests" in m.report()

    def test_snapshot_streams_through_train_logger(self, tmp_path):
        import json

        from raft_tpu.utils.logger import TrainLogger
        m = ServingMetrics()
        m.record_submit(queue_depth=1)
        m.record_done(0.005)
        logger = TrainLogger(log_dir=str(tmp_path))
        m.write_to(logger, step=7)
        logger.close()
        lines = [json.loads(l) for l in
                 open(os.path.join(str(tmp_path), "scalars.jsonl"))]
        assert any("serving_latency_p50_ms" in l and l["step"] == 7
                   for l in lines)


# -- engine integration (real FlowPredictor, CPU) ----------------------

# Two raw shapes that pad to the SAME /8 bucket (40, 64) — the bucket-
# sharing case — kept tiny so RAFT-small at iters=2 stays fast on CPU.
SHAPES = [(36, 60), (33, 57)]


@pytest.fixture(scope="module")
def predictor():
    from raft_tpu.evaluate import load_predictor
    return load_predictor("random", small=True, iters=2)


@pytest.fixture(scope="module")
def frames_and_refs(predictor):
    """Frames + bit-exact references through the SAME (max_batch=4)
    executable the engines below dispatch. (References via batch-1
    ``__call__`` are a *different* executable, and this suite's 8
    virtual CPU devices reorder float accumulation across executables —
    see test_batch_composition_independence; the single-device drill
    asserts the __call__ form of the criterion.)"""
    from raft_tpu.serving import loadgen
    frames = loadgen.make_frames(SHAPES, per_shape=2, seed=3)
    return frames, loadgen.batched_reference_flows(predictor, frames,
                                                   max_batch=4)


def _engine(predictor, **kw):
    from raft_tpu.serving import ServingConfig, ServingEngine
    return ServingEngine(predictor, ServingConfig(**kw))


class TestServingEngine:
    def test_served_bit_equal_to_direct_call(self, predictor,
                                             frames_and_refs):
        from raft_tpu.serving import loadgen
        frames, refs = frames_and_refs
        eng = _engine(predictor, max_batch=4, max_wait_ms=3.0)
        eng.start()
        try:
            res = loadgen.run_load(eng, frames, n_requests=24,
                                   concurrency=8, references=refs)
        finally:
            eng.close()
        assert res["completed"] == 24
        assert res["dropped"] == []
        # Bit-identical, not approximately equal: batching, tail-padding
        # and pipelining must be invisible to the client.
        assert res["mismatched"] == []
        assert res["ok"]
        # Everything routed through the one shared (40, 64) bucket.
        assert all(k <= 4 for k in res["batch_histogram"])
        assert sum(k * v for k, v in res["batch_histogram"].items()) == 24

    def test_batch_composition_independence(self, predictor,
                                            frames_and_refs):
        """The property the bit-equality contract rests on: a sample's
        batched result depends only on its own input — not its slot nor
        the other batch entries (so tail-pad filler can't perturb real
        samples). Also ties served values to the criterion's __call__
        wording: across executables the match is allclose-tight (exact
        on single-device hosts — asserted by scripts/serve_drill.py)."""
        from raft_tpu.serving import loadgen
        from raft_tpu.utils.padder import InputPadder
        frames, refs = frames_and_refs
        pads = []
        for im1, im2 in frames[:3]:
            p = InputPadder(im1.shape, mode="sintel")
            pads.append(p.pad(im1, im2))
        a, b, c = pads
        _, u1 = predictor.predict_batch(
            np.stack([a[0], b[0], c[0], a[0]]),
            np.stack([a[1], b[1], c[1], a[1]]))
        _, u2 = predictor.predict_batch(
            np.stack([b[0], a[0], a[0], c[0]]),
            np.stack([b[1], a[1], a[1], c[1]]))
        np.testing.assert_array_equal(u1[0], u2[1])   # A: slot/comp swap
        np.testing.assert_array_equal(u1[1], u2[0])   # B
        np.testing.assert_array_equal(u1[2], u2[3])   # C
        np.testing.assert_array_equal(u1[0], u1[3])   # within one batch
        call_refs = loadgen.reference_flows(predictor, frames[:1])
        np.testing.assert_allclose(refs[0], call_refs[0], atol=1e-4)

    def test_metrics_after_load(self, predictor, frames_and_refs):
        from raft_tpu.serving import loadgen
        frames, _ = frames_and_refs
        eng = _engine(predictor, max_batch=4, max_wait_ms=2.0)
        eng.start()
        try:
            loadgen.run_load(eng, frames, n_requests=12, concurrency=4)
        finally:
            eng.close()
        m = eng.metrics
        assert m.requests == m.responses == 12
        assert m.errors == 0 and m.rejected == 0
        assert m.batches >= 3                      # 12 reqs, max_batch 4
        assert 1.0 <= m.mean_batch_size() <= 4.0
        lat = m.latency_ms()
        assert lat["p50"] > 0 and lat["p99"] >= lat["p50"]
        assert m.throughput() > 0
        # Host-stage timer saw every pipeline stage.
        stages = eng.stages.summary()
        for name in ("pad", "stack", "dispatch", "sync", "unpad"):
            assert stages[name]["count"] > 0

    def test_clean_shutdown_resolves_inflight(self, predictor,
                                              frames_and_refs):
        frames, refs = frames_and_refs
        # Long deadline: requests are still queued when close() lands,
        # so the drain path (not the deadline path) must resolve them.
        eng = _engine(predictor, max_batch=4, max_wait_ms=10_000.0)
        eng.start()
        futs = [eng.submit(*frames[i % len(frames)]) for i in range(6)]
        eng.close(timeout=120)
        for i, f in enumerate(futs):
            flow = f.result(timeout=1)             # already resolved
            assert np.array_equal(flow, refs[i % len(frames)])
        with pytest.raises(RuntimeError, match="closed"):
            eng.submit(*frames[0])

    def test_backlog_rejection_counted(self, predictor, frames_and_refs):
        frames, _ = frames_and_refs
        eng = _engine(predictor, max_batch=4, max_wait_ms=5_000.0,
                      max_pending=1)
        eng.start()
        try:
            eng.submit(*frames[0])
            with pytest.raises(BacklogFull):
                eng.submit(*frames[1])
            assert eng.metrics.rejected == 1
            # A BacklogFull rejection is specifically a load-shed.
            assert eng.metrics.sheds == 1
            assert eng.metrics.snapshot()["serving_shed"] == 1.0
        finally:
            eng.close()

    def test_closed_engine_rejection_is_not_a_shed(self, predictor,
                                                   frames_and_refs):
        frames, _ = frames_and_refs
        eng = _engine(predictor, max_batch=8, max_wait_ms=1.0)
        eng.start()
        eng.close()
        with pytest.raises(RuntimeError, match="closed"):
            eng.submit(*frames[0])
        assert eng.metrics.sheds == 0

    def test_queue_timeout_expires_stale_requests(self, predictor,
                                                  frames_and_refs):
        """A request whose time-in-queue budget expires before dispatch
        completes with RequestTimedOut (clear, fast shedding), is
        counted in metrics, and never reaches the device."""
        from raft_tpu.serving.batcher import RequestTimedOut

        frames, _ = frames_and_refs
        # Batching deadline (300 ms) far past the per-request budget
        # (50 ms): the lone request is guaranteed expired when its
        # bucket finally closes.
        eng = _engine(predictor, max_batch=8, max_wait_ms=300.0,
                      queue_timeout_ms=50.0)
        eng.start(warmup=False)
        try:
            fut = eng.submit(*frames[0])
            with pytest.raises(RequestTimedOut, match="in queue"):
                fut.result(timeout=30)
            assert eng.metrics.timeouts == 1
            assert eng.metrics.errors == 0      # shedding is not failure
            assert eng.metrics.responses == 0
            snap = eng.metrics.snapshot()
            assert snap["serving_timeouts"] == 1.0
            assert "timeouts 1" in eng.metrics.report()
        finally:
            eng.close()

    def test_queue_timeout_spares_live_requests(self, predictor,
                                                frames_and_refs):
        """Only the expired requests in a closing batch are shed; the
        rest still serve, bit-equal to the direct call."""
        frames, refs = frames_and_refs
        eng = _engine(predictor, max_batch=4, max_wait_ms=5.0,
                      queue_timeout_ms=60_000.0)
        eng.start(warmup=False)
        try:
            fut = eng.submit(*frames[0])
            assert np.array_equal(fut.result(timeout=120), refs[0])
            assert eng.metrics.timeouts == 0
        finally:
            eng.close()

    def test_queue_timeout_disabled_by_default(self, predictor,
                                               frames_and_refs):
        frames, _ = frames_and_refs
        eng = _engine(predictor, max_batch=2, max_wait_ms=5.0)
        assert eng.config.queue_timeout_ms is None
        eng.start(warmup=False)
        try:
            fut = eng.submit(*frames[0])
            fut.result(timeout=120)             # no deadline attached
            assert eng.metrics.timeouts == 0
        finally:
            eng.close()

    def test_mismatched_frame_shapes_rejected(self, predictor,
                                              frames_and_refs):
        frames, _ = frames_and_refs
        eng = _engine(predictor, max_batch=2, max_wait_ms=1.0)
        eng.start()
        try:
            with pytest.raises(ValueError, match="shapes differ"):
                eng.submit(frames[0][0], frames[2][1])
        finally:
            eng.close()


class TestWarmup:
    def test_warmup_precompiles_then_no_request_compiles(self):
        """The acceptance-criterion probe: warmup compiles every
        configured bucket; after it, NO request triggers a fresh XLA
        compile (fresh predictor so the executable cache starts cold)."""
        from raft_tpu.evaluate import load_predictor
        from raft_tpu.serving import CompileWatch, loadgen
        pred = load_predictor("random", small=True, iters=2)
        eng = _engine(pred, max_batch=2, max_wait_ms=2.0,
                      buckets=((36, 60),))
        stats = eng.warmup()
        assert set(stats) == {(40, 64)}            # padded bucket key
        assert stats[(40, 64)]["compiles"] >= 1    # cold cache compiled
        eng.start(warmup=False)                    # already warmed
        frames = loadgen.make_frames(SHAPES, per_shape=2, seed=5)
        try:
            with CompileWatch() as w:
                res = loadgen.run_load(eng, frames, n_requests=10,
                                       concurrency=4)
        finally:
            eng.close()
        assert res["completed"] == 10
        assert w.compiles == 0                     # nothing recompiled
        assert eng.metrics.compiles == 0

    @pytest.mark.parametrize("from_env", [True, False])
    def test_persistent_cache_wiring(self, tmp_path, monkeypatch,
                                     from_env):
        """The cache directory is placed from outside: with
        JAX_COMPILATION_CACHE_DIR set the helper reports it and sets no
        directory in code; without it, <checkout>/.jax_cache."""
        import jax

        from raft_tpu.utils import compile_cache
        old = jax.config.jax_compilation_cache_dir
        try:
            if from_env:
                monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                                   str(tmp_path))
                assert compile_cache.enable_compile_cache() == \
                    str(tmp_path)
                assert jax.config.jax_compilation_cache_dir == old
            else:
                monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR",
                                   raising=False)
                want = os.path.join(os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))), ".jax_cache")
                assert compile_cache.enable_compile_cache() == want
                assert jax.config.jax_compilation_cache_dir == want
        finally:
            jax.config.update("jax_compilation_cache_dir", old)


class TestEvaluateDispatch:
    def test_dispatch_batch_is_async_and_equal(self, predictor,
                                               frames_and_refs):
        """dispatch_batch returns device arrays whose values equal the
        blocking predict_batch path bit-for-bit."""
        frames, _ = frames_and_refs
        from raft_tpu.utils.padder import InputPadder
        padder = InputPadder(frames[0][0].shape, mode="sintel")
        p1, p2 = padder.pad(*frames[0])
        i1 = np.stack([p1, p1])
        i2 = np.stack([p2, p2])
        out = predictor.dispatch_batch(i1, i2)
        assert not isinstance(out[1], np.ndarray)  # still a jax.Array
        low, up = predictor.predict_batch(i1, i2)
        np.testing.assert_array_equal(np.asarray(out[1]), up)
        np.testing.assert_array_equal(np.asarray(out[0]), low)

    def test_donation_flag_recompiles_not_corrupts(self, frames_and_refs):
        """donate_images is part of the executable cache key; on CPU
        donation is ignored (with a warning) and results are unchanged."""
        from raft_tpu.evaluate import load_predictor
        frames, _ = frames_and_refs
        pred = load_predictor("random", small=True, iters=2)
        from raft_tpu.utils.padder import InputPadder
        padder = InputPadder(frames[0][0].shape, mode="sintel")
        p1, p2 = padder.pad(*frames[0])
        i1, i2 = p1[None], p2[None]
        _, up_plain = pred.predict_batch(i1, i2)
        pred.donate_images = True
        _, up_donated = pred.predict_batch(i1.copy(), i2.copy())
        np.testing.assert_array_equal(up_plain, up_donated)
        keys = list(pred._cache)
        assert {k[3] for k in keys} == {False, True}   # two executables


# -- satellite regressions ---------------------------------------------


class TestLoggerImageGuard:
    def test_tb_add_image_failure_is_best_effort(self, tmp_path, capsys):
        """A TensorBoard image sink that raises (e.g. Pillow-free host:
        EventWriter.add_image imports PIL) must not propagate out of
        write_images — scalars and PNG sink behavior are unaffected."""
        from raft_tpu.utils.logger import TrainLogger
        logger = TrainLogger(log_dir=str(tmp_path))

        class _BrokenTB:
            def add_image(self, *a, **k):
                raise ImportError("No module named 'PIL'")

        logger._tb = _BrokenTB()
        g = np.random.default_rng(0)
        img = g.uniform(0, 255, (1, 16, 24, 3)).astype(np.float32)
        flow = g.normal(size=(1, 16, 24, 2)).astype(np.float32)
        preds = flow[None]                          # (iters=1, B, H, W, 2)
        n = logger.write_images(img, img, flow, preds, step=1)
        assert n >= 1                               # panels still produced
        assert "TensorBoard image write failed" in capsys.readouterr().out
        logger._tb = None
        logger.close()


class TestCorrDataAxisEligibility:
    def test_eligibility_folds_batch_divisibility(self):
        from raft_tpu.config import RAFTConfig
        from raft_tpu.models.corr import alternate_eval_eligible
        cfg = RAFTConfig(small=True)
        base = alternate_eval_eligible(cfg, (64, 96))
        # Divisible batch: same verdict as batch-agnostic.
        assert alternate_eval_eligible(cfg, (64, 96), batch=4,
                                       data_shards=2) == base
        # Indivisible batch over a data-sharded mesh: never eligible.
        assert alternate_eval_eligible(cfg, (64, 96), batch=3,
                                       data_shards=2) is False
        # No data sharding: batch is irrelevant.
        assert alternate_eval_eligible(cfg, (64, 96), batch=3,
                                       data_shards=1) == base

    def test_pick_engine_falls_back_on_indivisible_batch(self,
                                                         monkeypatch):
        """corr_impl='auto' must hand an indivisible-batch sharded
        config to the materialized engine, not to the shard_map wrapper
        that rejects it at lowering."""
        import jax

        from raft_tpu.evaluate import FlowPredictor, load_predictor
        from raft_tpu.models.corr import alternate_eval_eligible
        pred = load_predictor("random", small=True, iters=1)
        assert pred._engines is not None            # auto by default
        if not alternate_eval_eligible(pred.model.config, (64, 96)):
            pytest.skip("tiny shape not fused-eligible in this build")
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        ok = pred._pick_engine((4, 64, 96, 3), n_dt=2)
        bad = pred._pick_engine((3, 64, 96, 3), n_dt=2)
        assert ok.config.alternate_corr is True
        assert bad.config.alternate_corr is False   # materialized

    def test_explicit_pallas_under_indivisible_mesh_raises(self):
        """backend='pallas' + an active mesh whose axes don't divide the
        operands: a clear ValueError, not an opaque lowering failure."""
        import jax.numpy as jnp

        from raft_tpu.models.corr import (alternate_lookup,
                                          build_feature_pyramid)
        from raft_tpu.ops.corr_pallas import fused_eligible
        from raft_tpu.parallel import make_mesh
        from raft_tpu.parallel.spatial import spatial_kernel_mesh
        B, H, W, C = 1, 8, 16, 64
        pyramid2 = build_feature_pyramid(
            jnp.zeros((B, H, W, C), jnp.float32), 2)
        if not fused_eligible([f.shape[1:3] for f in pyramid2], C):
            pytest.skip("shape not fused-eligible in this build")
        fmap1 = jnp.zeros((B, H, W, C), jnp.float32)
        coords = jnp.zeros((B, H, W, 2), jnp.float32)
        mesh = make_mesh(n_data=2, n_spatial=1)     # B=1 % 2 != 0
        with spatial_kernel_mesh(mesh):
            with pytest.raises(ValueError, match="divisible"):
                alternate_lookup(fmap1, pyramid2, coords, radius=2,
                                 backend="pallas")


class _SlowDataset:
    """Picklable dataset whose reads outlast any sane worker timeout —
    stands in for an OOM-killed/hung worker process."""

    def __len__(self):
        return 4

    def reseed(self, key):
        pass

    def __getitem__(self, idx):
        time.sleep(30)
        z = np.zeros((8, 8, 3), np.float32)
        return z, z, z[..., :2], np.ones((8, 8), np.float32)


class TestProcessLoader:
    def test_pool_reused_across_epochs(self, tmp_path):
        from raft_tpu.data.datasets import ProcessDataLoader
        from test_data import _write_synthetic_sintel
        from raft_tpu.data.datasets import MpiSintel
        root = str(tmp_path / "Sintel")
        _write_synthetic_sintel(root, scenes=2, frames=3)
        ds = MpiSintel(aug_params={"crop_size": (32, 48)}, root=root,
                       dstype="clean", seed=0)
        loader = ProcessDataLoader(ds, batch_size=2, num_workers=2,
                                   shuffle=False, seed=0)
        try:
            e1 = np.stack([b["image1"] for b in loader])
            pool1 = loader._pool
            e2 = np.stack([b["image1"] for b in loader])
            pool2 = loader._pool
            assert pool1 is not None and pool1 is pool2   # no re-fork
            # Lazy per-epoch reseed still decorrelates augmentation.
            assert not np.array_equal(e1, e2)
        finally:
            loader.close()
        assert loader._pool is None                       # idempotent

    def test_dead_worker_surfaces_as_timeout_error(self):
        from raft_tpu.data.datasets import ProcessDataLoader
        loader = ProcessDataLoader(_SlowDataset(), batch_size=2,
                                   num_workers=2, shuffle=False,
                                   stall_timeout=0,
                                   worker_timeout=0.5)
        try:
            with pytest.raises(RuntimeError,
                               match=r"no result for sample \d+ "
                                     r"\(batch \d+\)"):
                next(iter(loader))
            # The timed-drain event is counted, not only raised.
            assert loader.stats.worker_timeouts == 1
            assert loader.state().worker_timeouts == 1
        finally:
            loader.close()


# -- robustness layer: priorities, breaker, isolation, health, reload --


def _save_params_ckpt(ckpt_dir, step, params, batch_stats=None):
    """Commit ``params`` under ``step`` the way a trainer would (full
    RunCheckpointer save → commit record), for the hot-reload tests."""
    import jax.numpy as jnp

    from raft_tpu.checkpoint import RunCheckpointer

    class _S:
        def __init__(self):
            self.step = jnp.asarray(step, jnp.int32)
            self.params = params
            self.batch_stats = batch_stats or {}
            self.opt_state = {"m": jnp.zeros(2, jnp.float32)}

    with RunCheckpointer(ckpt_dir) as c:
        c.save(_S())


class TestPriorities:
    def test_invalid_priority_rejected(self):
        with pytest.raises(ValueError, match="priority"):
            _req_p("urgent")

    def test_high_drains_before_low_within_bucket(self):
        clock = _FakeClock()
        b = ShapeBucketBatcher(max_batch=2, max_wait_s=100.0, clock=clock)
        b.enqueue(_req_p("low", t=0.0))
        b.enqueue(_req_p("low", t=0.1))
        b.enqueue(_req_p("high", t=0.2))
        clock.t = 200.0
        batch = b.next_batch(timeout=0)
        # The younger HIGH preempts the older LOWs in the closing batch;
        # FIFO within each class.
        assert [r.priority for r in batch] == ["high", "low"]
        assert batch[1].t_submit == 0.0

    def test_deadline_anchored_on_oldest_of_either_class(self):
        clock = _FakeClock()
        b = ShapeBucketBatcher(max_batch=8, max_wait_s=1.0, clock=clock)
        b.enqueue(_req_p("low", t=0.0))
        b.enqueue(_req_p("high", t=0.9))     # young HIGH must not reset
        clock.t = 1.1                        # the old LOW's deadline
        batch = b.next_batch(timeout=0)
        assert len(batch) == 2               # closed on the LOW's wait

    def test_high_evicts_youngest_low_under_full_backlog(self):
        b = ShapeBucketBatcher(max_batch=8, max_pending=2)
        b.enqueue(_req_p("low", t=0.0))
        victim = _req_p("low", t=5.0)        # youngest LOW
        b.enqueue(victim)
        high = _req_p("high", t=6.0)
        evicted = b.enqueue(high)
        assert evicted is victim
        assert b.pending() == 2              # HIGH took the slot
        with pytest.raises(BacklogFull):     # LOW never evicts
            b.enqueue(_req_p("low", t=7.0))

    def test_all_high_backlog_still_rejects_high(self):
        b = ShapeBucketBatcher(max_batch=8, max_pending=1)
        b.enqueue(_req_p("high"))
        with pytest.raises(BacklogFull):
            b.enqueue(_req_p("high"))

    def test_engine_counts_classes_and_evicts(self, predictor,
                                              frames_and_refs):
        from raft_tpu.serving import PRIORITY_LOW
        frames, refs = frames_and_refs
        eng = _engine(predictor, max_batch=4, max_wait_ms=5_000.0,
                      max_pending=1)
        eng.start()
        try:
            low_fut = eng.submit(*frames[0], priority=PRIORITY_LOW)
            high_fut = eng.submit(*frames[1])     # default HIGH, evicts
            with pytest.raises(BacklogFull):
                low_fut.result(timeout=5)
            eng.close(timeout=120)
            assert np.array_equal(high_fut.result(1), refs[1])
        finally:
            eng.close()
        m = eng.metrics
        assert m.requests_by_class["low"] == 1
        assert m.requests_by_class["high"] == 1
        assert m.sheds_by_class["low"] == 1 and m.sheds == 1
        snap = m.snapshot()
        assert snap["serving_requests_low"] == 1.0
        assert snap["serving_shed_low"] == 1.0


class TestCircuitBreaker:
    def test_transitions_with_fake_clock(self):
        from raft_tpu.serving import CircuitBreaker
        clock = _FakeClock()
        b = CircuitBreaker(threshold=3, cooldown_s=10.0, clock=clock)
        assert b.state == CircuitBreaker.CLOSED and b.admits()
        b.record_failure()
        b.record_failure()
        b.record_success()                     # streak resets
        assert b.consecutive_failures == 0
        for _ in range(3):
            b.record_failure()
        assert b.state == CircuitBreaker.OPEN and not b.admits()
        assert b.trips == 1
        clock.t = 9.9
        assert not b.admits()                  # cooldown still running
        clock.t = 10.0
        assert b.state == CircuitBreaker.HALF_OPEN and b.admits()
        b.record_failure()                     # failed probe
        assert b.state == CircuitBreaker.OPEN and b.trips == 2
        clock.t = 25.0
        assert b.state == CircuitBreaker.HALF_OPEN
        b.record_success()                     # healthy probe
        assert b.state == CircuitBreaker.CLOSED and b.trips == 2

    def test_validation(self):
        from raft_tpu.serving import CircuitBreaker
        with pytest.raises(ValueError, match="threshold"):
            CircuitBreaker(threshold=0)
        with pytest.raises(ValueError, match="cooldown"):
            CircuitBreaker(cooldown_s=-1.0)

    def test_engine_opens_fails_fast_and_recovers(self, predictor,
                                                  frames_and_refs):
        """Injected dispatch errors trip the breaker; submit fails fast
        with EngineUnhealthy; after the cooldown a healthy probe closes
        it and serving resumes bit-exact."""
        from raft_tpu.resilience import FaultInjector, set_injector
        from raft_tpu.serving import EngineUnhealthy
        frames, refs = frames_and_refs
        eng = _engine(predictor, max_batch=4, max_wait_ms=2.0,
                      breaker_threshold=1, breaker_cooldown_s=0.2)
        eng.start()
        try:
            set_injector(FaultInjector(serving_dispatch_errors=1))
            with pytest.raises(RuntimeError,
                               match="injected serving dispatch"):
                eng.submit(*frames[0]).result(60)
            assert eng.health()["state"] == "open"
            with pytest.raises(EngineUnhealthy, match="breaker open"):
                eng.submit(*frames[0])
            assert eng.metrics.breaker_fastfails >= 1
            time.sleep(0.25)                   # past the cooldown
            flow = eng.submit(*frames[0]).result(60)
            assert np.array_equal(flow, refs[0])
            assert eng.breaker.state == "closed"
            assert eng.breaker.trips == 1
            assert eng.health()["state"] == "ready"
        finally:
            set_injector(None)
            eng.close()


class TestBatchIsolation:
    def test_poisoned_request_fails_alone(self, predictor,
                                          frames_and_refs):
        """One poisoned input fails its own request only: batch
        neighbors are retried as singles and serve bit-exact."""
        from raft_tpu.resilience import FaultInjector, set_injector
        frames, refs = frames_and_refs
        eng = _engine(predictor, max_batch=4, max_wait_ms=60.0,
                      breaker_threshold=10)
        eng.start()
        try:
            set_injector(FaultInjector(serving_poison_nth=2))
            futs = [eng.submit(*frames[i]) for i in range(3)]
            set_injector(None)
            assert np.array_equal(futs[0].result(120), refs[0])
            assert np.array_equal(futs[2].result(120), refs[2])
            with pytest.raises(RuntimeError, match="poisoned"):
                futs[1].result(120)            # submit seq 2 = poisoned
            assert eng.metrics.isolated_retries == 2
            assert eng.metrics.errors == 1
            assert eng.metrics.responses == 2
            snap = eng.metrics.snapshot()
            assert snap["serving_isolated_retries"] == 2.0
        finally:
            set_injector(None)
            eng.close()

    def test_lone_failed_request_gets_original_error(self, predictor,
                                                     frames_and_refs):
        from raft_tpu.resilience import FaultInjector, set_injector
        frames, _ = frames_and_refs
        eng = _engine(predictor, max_batch=4, max_wait_ms=2.0,
                      breaker_threshold=10)
        eng.start()
        try:
            set_injector(FaultInjector(serving_dispatch_errors=1))
            with pytest.raises(RuntimeError,
                               match="injected serving dispatch"):
                eng.submit(*frames[0]).result(60)
            assert eng.metrics.isolated_retries == 0
        finally:
            set_injector(None)
            eng.close()


class TestHealth:
    def test_lifecycle_states(self, predictor, frames_and_refs):
        eng = _engine(predictor, max_batch=2, max_wait_ms=2.0)
        assert eng.health()["state"] == "starting"
        assert not eng.health()["ready"]
        eng.start()
        try:
            assert eng.health()["state"] == "ready"
            eng.set_degraded("canary-rollback")
            h = eng.health()
            assert h["state"] == "degraded" and h["ready"]
            assert h["degraded_reasons"] == ["canary-rollback"]
            eng.clear_degraded("canary-rollback")
            assert eng.health()["state"] == "ready"
        finally:
            eng.close()
        assert eng.health()["state"] == "closed"

    def test_gauges_stream_through_snapshot(self, predictor):
        from raft_tpu.serving.health import HEALTH_CODES
        eng = _engine(predictor, max_batch=2, max_wait_ms=2.0)
        snap = eng.metrics.snapshot()
        assert snap["serving_queue_depth"] == 0.0
        assert snap["serving_inflight_batches"] == 0.0
        assert snap["serving_breaker_trips"] == 0.0
        assert snap["serving_health_state"] == float(
            HEALTH_CODES["starting"])
        eng.start()
        try:
            assert eng.metrics.snapshot()["serving_health_state"] == \
                float(HEALTH_CODES["ready"])
        finally:
            eng.close()

    def test_gauge_source_failure_is_safe(self):
        m = ServingMetrics()
        m.set_gauge_source("broken", lambda: 1 / 0)
        assert m.snapshot()["serving_broken"] == 0.0


class TestHotReload:
    def _reload_setup(self, predictor, frames, tmp_path, **cfg_kw):
        import jax

        from raft_tpu.serving import HotReloader, ReloadConfig
        eng = _engine(predictor, max_batch=4, max_wait_ms=3.0,
                      buckets=(SHAPES[0],))
        eng.warmup()
        eng.start(warmup=False)
        reloader = HotReloader(
            eng, str(tmp_path / "ckpts"), canary_frames=[frames[0]],
            config=ReloadConfig(**{"canary_max_epe": None, **cfg_kw}))
        good = jax.tree_util.tree_map(lambda x: x * (1 + 1e-3),
                                      predictor.variables["params"])
        return eng, reloader, good

    def test_good_canary_swaps_with_zero_compiles(self, predictor,
                                                  frames_and_refs,
                                                  tmp_path):
        from raft_tpu.serving import CompileWatch
        frames, _ = frames_and_refs
        eng, reloader, good = self._reload_setup(predictor, frames,
                                                 tmp_path)
        try:
            assert reloader.poll_once()["action"] == "none"  # empty dir
            _save_params_ckpt(str(tmp_path / "ckpts"), 3, good)
            with CompileWatch() as w:
                act = reloader.poll_once()
            assert act["action"] == "swapped" and act["step"] == 3
            assert w.compiles == 0       # standby reused warmed execs
            assert reloader.current_step == 3
            assert eng.metrics.swaps == 1
            assert eng.health()["state"] == "ready"
            # The engine now serves the checkpoint's weights bit-exact.
            import jax
            for got, want in zip(
                    jax.tree_util.tree_leaves(
                        eng.predictor.variables["params"]),
                    jax.tree_util.tree_leaves(good)):
                np.testing.assert_array_equal(np.asarray(got),
                                              np.asarray(want))
            # Same step never reloads twice.
            assert reloader.poll_once()["action"] == "none"
        finally:
            reloader.stop()
            eng.close()

    def test_nan_canary_rolls_back_and_pins(self, predictor,
                                            frames_and_refs, tmp_path):
        import jax
        import jax.numpy as jnp
        frames, refs = frames_and_refs
        eng, reloader, _ = self._reload_setup(predictor, frames,
                                              tmp_path)
        bad = jax.tree_util.tree_map(
            lambda x: jnp.full_like(x, jnp.nan),
            predictor.variables["params"])
        try:
            _save_params_ckpt(str(tmp_path / "ckpts"), 5, bad)
            act = reloader.poll_once()
            assert act["action"] == "rolled_back" and act["step"] == 5
            assert "non-finite" in act["reason"]
            assert eng.metrics.rollbacks == 1
            h = eng.health()
            assert h["state"] == "degraded" and h["ready"]
            assert 5 in reloader.pinned_steps
            assert reloader.poll_once()["action"] == "none"  # pinned
            # Old model still serves, bit-exact.
            flow = eng.submit(*frames[0]).result(120)
            assert np.array_equal(flow, refs[0])
        finally:
            reloader.stop()
            eng.close()

    def test_epe_band_rolls_back(self, predictor, frames_and_refs,
                                 tmp_path):
        import jax
        frames, _ = frames_and_refs
        eng, reloader, good = self._reload_setup(
            predictor, frames, tmp_path, canary_max_epe=1e-9)
        shifted = jax.tree_util.tree_map(lambda x: x * 1.05,
                                         predictor.variables["params"])
        try:
            _save_params_ckpt(str(tmp_path / "ckpts"), 7, shifted)
            act = reloader.poll_once()
            assert act["action"] == "rolled_back"
            assert "drift band" in act["reason"]
            assert act["epe"] > 0
        finally:
            reloader.stop()
            eng.close()

    def test_newer_step_still_eligible_after_pin(self, predictor,
                                                 frames_and_refs,
                                                 tmp_path):
        """One bad export must not wedge the replica: after pinning a
        canary-failed step, the NEXT committed step swaps (and clears
        the degraded flag)."""
        import jax
        import jax.numpy as jnp
        frames, _ = frames_and_refs
        eng, reloader, good = self._reload_setup(predictor, frames,
                                                 tmp_path)
        bad = jax.tree_util.tree_map(
            lambda x: jnp.full_like(x, jnp.nan),
            predictor.variables["params"])
        try:
            _save_params_ckpt(str(tmp_path / "ckpts"), 1, bad)
            assert reloader.poll_once()["action"] == "rolled_back"
            assert eng.health()["state"] == "degraded"
            _save_params_ckpt(str(tmp_path / "ckpts"), 2, good)
            assert reloader.poll_once()["action"] == "swapped"
            assert eng.health()["state"] == "ready"   # rollback cleared
            assert eng.metrics.swaps == 1 and eng.metrics.rollbacks == 1
        finally:
            reloader.stop()
            eng.close()

    def test_swap_under_load_bit_consistent(self, predictor,
                                            frames_and_refs, tmp_path):
        """The drill's core invariant at pytest scale: every response
        during a mid-stream swap bit-matches exactly the old or the new
        model, and both models actually serve."""
        from raft_tpu.serving import loadgen
        frames, refs_old = frames_and_refs
        eng, reloader, good = self._reload_setup(predictor, frames,
                                                 tmp_path)
        refs_new = loadgen.batched_reference_flows(
            predictor.clone_with_variables(
                dict(predictor.variables, params=good)),
            frames, max_batch=4)
        out = {}

        def load():
            out.update(loadgen.run_load(
                eng, frames, n_requests=60, concurrency=8,
                references=refs_old, alt_references=refs_new,
                timeout=120.0))

        th = threading.Thread(target=load)
        try:
            th.start()
            deadline = time.monotonic() + 60
            while eng.metrics.responses < 10:
                assert time.monotonic() < deadline
                time.sleep(0.01)
            _save_params_ckpt(str(tmp_path / "ckpts"), 9, good)
            assert reloader.poll_once()["action"] == "swapped"
            th.join(120)
            assert not th.is_alive()
            # Post-swap traffic must bit-match the NEW model — issued
            # after the join so it cannot race the swap (on a slow box
            # the whole mixed load can drain before the canary ends,
            # which is why "matched_alt > 0" would be flaky here).
            post = loadgen.run_load(eng, frames, n_requests=8,
                                    concurrency=4, references=refs_new,
                                    timeout=120.0)
        finally:
            reloader.stop()
            eng.close()
        assert out["completed"] == 60
        assert out["dropped"] == [] and out["mismatched"] == []
        assert out["matched_primary"] > 0     # old model served
        assert post["completed"] == 8         # new model serves, exactly
        assert post["dropped"] == [] and post["mismatched"] == []
        assert eng.metrics.swaps == 1

    def test_watcher_thread_polls_and_swaps(self, predictor,
                                            frames_and_refs, tmp_path):
        frames, _ = frames_and_refs
        eng, reloader, good = self._reload_setup(
            predictor, frames, tmp_path, poll_interval_s=0.05)
        try:
            reloader.start()
            with pytest.raises(RuntimeError, match="already started"):
                reloader.start()
            _save_params_ckpt(str(tmp_path / "ckpts"), 11, good)
            deadline = time.monotonic() + 30
            while eng.metrics.swaps < 1:
                assert time.monotonic() < deadline, \
                    "watcher never picked up the committed step"
                time.sleep(0.02)
            assert reloader.current_step == 11
        finally:
            reloader.stop()
            eng.close()

    def test_clone_rejects_structure_change(self, predictor):
        with pytest.raises(ValueError, match="variable"):
            predictor.clone_with_variables(
                {"params": predictor.variables["params"],
                 "unexpected": {}})


class TestLoadgenAltReferences:
    def test_alt_match_counts_as_correct(self):
        """A response bit-matching the alternate reference is correct,
        one matching neither is a mismatch."""
        from concurrent.futures import Future

        from raft_tpu.serving import loadgen

        primary = [np.zeros((4, 4, 2), np.float32)]
        alt = [np.ones((4, 4, 2), np.float32)]
        frames = [(np.zeros((4, 4, 3), np.float32),) * 2]

        class _FakeEngine:
            def __init__(self, value):
                self.value = value
                self.metrics = ServingMetrics()

            def submit(self, im1, im2, priority="high"):
                f = Future()
                f.set_result(self.value)
                return f

        res = loadgen.run_load(_FakeEngine(alt[0]), frames, 4,
                               concurrency=2, references=primary,
                               alt_references=alt)
        assert res["ok"] and res["matched_alt"] == 4
        assert res["matched_primary"] == 0
        res = loadgen.run_load(
            _FakeEngine(np.full((4, 4, 2), 7.0, np.float32)), frames, 4,
            concurrency=2, references=primary, alt_references=alt)
        assert not res["ok"] and len(res["mismatched"]) == 4

    def test_per_replica_attribution(self):
        """Outcomes are attributed to the replica_id stamped on the
        resolved future; futures without one pool as unattributed."""
        from concurrent.futures import Future

        from raft_tpu.serving import loadgen

        ref = [np.zeros((4, 4, 2), np.float32)]
        frames = [(np.zeros((4, 4, 3), np.float32),) * 2]

        class _StampingEngine:
            def __init__(self):
                self.metrics = ServingMetrics()
                self._n = 0

            def submit(self, im1, im2, priority="high"):
                f = Future()
                self._n += 1
                if self._n % 2:
                    f.replica_id = "rA"
                    f.set_result(ref[0])
                else:
                    f.replica_id = "rB"
                    f.set_exception(RuntimeError("boom"))
                return f

        res = loadgen.run_load(_StampingEngine(), frames, 4,
                               concurrency=1, references=ref)
        per = res["per_replica"]
        assert per["rA"]["completed"] == 2 and per["rA"]["dropped"] == 0
        assert per["rB"]["dropped"] == 2 and per["rB"]["completed"] == 0
        assert "latency_ms" in per["rA"]
        assert "unattributed" not in per


class TestConcurrentDispatch:
    """The engine's per-bucket dispatch streams: two buckets dispatch
    concurrently (no head-of-line blocking across buckets) and the
    concurrency is invisible to clients — every response stays
    bit-exact and carries its replica attribution."""

    TWO_BUCKET_SHAPES = [(36, 60), (52, 76)]   # (40, 64) and (56, 80)

    def test_two_buckets_bit_exact_under_concurrency(self, predictor):
        from raft_tpu.serving import loadgen
        frames = loadgen.make_frames(self.TWO_BUCKET_SHAPES,
                                     per_shape=2, seed=17)
        refs = loadgen.batched_reference_flows(predictor, frames,
                                               max_batch=4)
        eng = _engine(predictor, max_batch=4, max_wait_ms=3.0)
        eng.start()
        try:
            res = loadgen.run_load(eng, frames, n_requests=24,
                                   concurrency=8, references=refs,
                                   timeout=120.0)
        finally:
            eng.close()
        assert res["ok"], res
        # One independent dispatch stream materialized per bucket —
        # keyed with the wire-dtype tag (make_frames is uint8 now, so
        # only the u8-wire streams saw traffic).
        assert set(eng._streams) == {(40, 64, "u8"), (56, 80, "u8")}

    def test_slow_bucket_does_not_block_other_bucket(self, predictor):
        """A bucket whose dispatch stalls must not delay another
        bucket's traffic: streams are per-bucket thread pairs fed by
        the router, so only the stalled bucket queues behind it."""
        from raft_tpu.serving import ServingConfig, ServingEngine, loadgen
        frames = loadgen.make_frames(self.TWO_BUCKET_SHAPES,
                                     per_shape=1, seed=19)
        # batch-1 references BEFORE the engine starts, so its dispatch
        # of the same executables is a cache hit (no compile while the
        # gate is held).
        refs = loadgen.batched_reference_flows(predictor, frames,
                                               max_batch=1)
        gate = threading.Event()

        class _GatedPredictor:
            """Blocks dispatch for one padded bucket until released."""

            def __init__(self, inner, gate_hw):
                self._inner = inner
                self._gate_hw = gate_hw

            def __getattr__(self, name):
                return getattr(self._inner, name)

            def dispatch_batch(self, i1, i2):
                if tuple(i1.shape[1:3]) == self._gate_hw:
                    assert gate.wait(60), "gate never released"
                return self._inner.dispatch_batch(i1, i2)

        eng = ServingEngine(
            _GatedPredictor(predictor, (56, 80)),
            ServingConfig(max_batch=1, max_wait_ms=1.0))
        eng.start(warmup=False)
        try:
            slow = eng.submit(*frames[1])      # (52, 76) -> gated bucket
            fast = eng.submit(*frames[0])      # (36, 60) -> free bucket
            # The free bucket completes while the gated one is still
            # stuck in its own stream's dispatch.
            assert np.array_equal(fast.result(120), refs[0])
            assert not slow.done()
            gate.set()
            assert np.array_equal(slow.result(120), refs[1])
        finally:
            gate.set()
            eng.close()

    def test_dynamic_streams_capped_and_lru_retired(self, predictor):
        """Arbitrary out-of-bucket shapes must not grow dispatch
        threads without bound: dynamic streams are capped at
        ``max_dynamic_streams`` with LRU-idle retirement, while
        configured-bucket streams are permanent. Retirement drains the
        stream's queue first, so no request is ever dropped."""
        from raft_tpu.serving import loadgen
        shapes = [(36, 60), (20, 28), (24, 36), (28, 44)]
        frames = loadgen.make_frames(shapes, per_shape=1, seed=23)
        refs = loadgen.batched_reference_flows(predictor, frames,
                                               max_batch=1)
        eng = _engine(predictor, max_batch=1, max_wait_ms=1.0,
                      buckets=((36, 60),), max_dynamic_streams=2)
        eng.start(warmup=False)
        try:
            for i, (im1, im2) in enumerate(frames):
                assert np.array_equal(
                    eng.submit(im1, im2).result(120), refs[i])
                # The dedicated bucket never retires; dynamic streams
                # stay within the cap at every step. Stream keys carry
                # the wire tag (uint8 frames ride the u8 wire).
                assert (40, 64, "u8") in eng._streams
                dynamic = [b for b in eng._streams
                           if b[:2] != (40, 64)]
                assert len(dynamic) <= 2
            assert len(eng._streams) <= 3
            # Three distinct dynamic buckets saw traffic, so at least
            # one stream was LRU-retired along the way.
            assert len(eng._retired) >= 1
        finally:
            eng.close()

    def test_replica_id_stamped_on_future(self, predictor,
                                          frames_and_refs):
        frames, refs = frames_and_refs
        eng = _engine(predictor, max_batch=4, max_wait_ms=2.0,
                      replica_id="rx")
        eng.start()
        try:
            fut = eng.submit(*frames[0])
            assert np.array_equal(fut.result(120), refs[0])
            assert fut.replica_id == "rx"
        finally:
            eng.close()

    def test_no_replica_id_without_config(self, predictor,
                                          frames_and_refs):
        frames, _ = frames_and_refs
        eng = _engine(predictor, max_batch=4, max_wait_ms=2.0)
        eng.start()
        try:
            fut = eng.submit(*frames[0])
            fut.result(120)
            assert getattr(fut, "replica_id", None) is None
        finally:
            eng.close()
