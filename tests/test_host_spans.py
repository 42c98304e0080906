"""The host spans inside the dataset pass, the predictor and the train
loop (``raft_tpu.utils.profiling.host_timer``), the benchmark reader
that turns them into per-batch and per-step milliseconds, and the
named scopes of the model stages Flax leaves unnamed."""

import contextlib
import json
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.readers import program_spans
from raft_tpu.config import RAFTConfig, TrainConfig
from raft_tpu.evaluate import FlowPredictor, _predict_dataset
from raft_tpu.models import RAFT
from raft_tpu.utils import profiling
from raft_tpu.utils.padder import InputPadder
from raft_tpu.utils.staging import StagingArena

H, W = 30, 44            # pads to 32x48 in sintel mode
BS = 3
BATCH_LEVEL = ("pass.batch", "pass.stack", "predict.h2d", "predict.dispatch",
               "predict.device_wait", "predict.d2h")
STEP_CHILDREN = ("train.loader_wait", "train.shard_batch", "train.dispatch",
                 "train.device_wait", "train.metrics_fetch", "train.log")


class Pairs:
    """Seven unpadded pairs: two full batches of three and a short one."""

    def __len__(self):
        return 7

    def __getitem__(self, i):
        r = np.random.default_rng(i)
        return tuple(r.uniform(0, 255, (H, W, 3)).astype(np.float32)
                     for _ in range(2))


@pytest.fixture(scope="module")
def predictor():
    model = RAFT(RAFTConfig(small=True, iters=2))
    key = jax.random.PRNGKey(0)
    dummy = jnp.zeros((1, 32, 48, 3))
    variables = model.init({"params": key, "dropout": key}, dummy, dummy,
                           iters=1)
    return FlowPredictor(model, variables, iters=2, batch_size=BS)


@pytest.fixture
def timer(monkeypatch):
    """A fresh process timer for one test."""
    fresh = profiling.HostStageTimer(ring=4096)
    monkeypatch.setattr(profiling, "_HOST_TIMER", fresh)
    return fresh


def run_pass(predictor):
    return [flow for _, _, flow in _predict_dataset(predictor, Pairs(),
                                                    mode="sintel")]


def by_name(spans, name):
    return [s for s in spans if s.name == name]


def staged(spans):
    """Without the collector's passes: they come when they come."""
    return [s for s in spans if s.name != "gc.pass"]


def test_pass_spans(predictor, timer, monkeypatch):
    monkeypatch.setattr(predictor, "staging", StagingArena())
    flows = run_pass(predictor)
    spans = staged(timer.spans())
    roots = by_name(spans, "pass.batch")
    assert [r.unit for r in roots] == [0, 1, 2]
    assert [r.args["pairs"] for r in roots] == [3, 3, 1]
    assert all(r.args["padded_to"] == BS and r.args["complete"] == 1
               and (r.args["height"], r.args["width"]) == (32, 48)
               and r.parent == 0 for r in roots)
    frames = BS * 32 * 48 * 3 * 4
    for root in roots:
        kids = [s for s in spans if s.parent == root.id]
        pairs = root.args["pairs"]
        for name in BATCH_LEVEL[1:]:
            assert len(by_name(kids, name)) == 1, name
        for name in ("pass.fetch", "pass.pad", "pass.unpad"):
            assert len(by_name(kids, name)) == pairs, name
        assert all(k.unit == root.unit for k in kids)
        assert all(k.start_ns >= root.start_ns
                   and k.start_ns + k.dur_ns <= root.start_ns + root.dur_ns
                   for k in kids)
        # pass.pad: each sample's one copy into its slot of the two
        # staging buffers; pass.stack: what is left of stacking (the
        # tail slots' fill and the hand-off), after the batch's last
        # pad and before the predictor has the buffers
        stack, h2d = by_name(kids, "pass.stack")[0], by_name(
            kids, "predict.h2d")[0]
        last_pad = by_name(kids, "pass.pad")[-1]
        assert (last_pad.start_ns + last_pad.dur_ns <= stack.start_ns
                and stack.start_ns + stack.dur_ns <= h2d.start_ns)
        assert stack.nbytes == h2d.nbytes == 2 * frames
        assert by_name(kids, "predict.d2h")[0].nbytes == (
            BS * 32 * 48 * 2 * 4 + BS * 4 * 6 * 2 * 4)
    assert len(spans) == len(roots) * len(BATCH_LEVEL) + 3 * 7
    assert timer.dropped == 0
    # two deep: a batch's root is still open (its flows not yet out)
    # when the next one's opens, and they close in unit order
    for a, b in zip(roots, roots[1:]):
        assert a.start_ns < b.start_ns < a.start_ns + a.dur_ns
        assert a.start_ns + a.dur_ns <= b.start_ns + b.dur_ns
    assert [r.args["ahead"] for r in roots] == [0, 1, 1]
    # the first two batches each had to allocate their pair (the second
    # was staged while the first was on the device), the third (the
    # short one) ran on the first's, and both pairs are back
    assert [r.args["arena_fresh"] for r in roots] == [2, 2, 0]
    assert predictor.staging.allocated == 4
    assert predictor.staging.pooled_buffers() == 4
    assert timer.summary()["pass.fetch"]["count"] == 7

    # a second call is a new pass: its units start again
    run_pass(predictor)
    roots = by_name(timer.spans(), "pass.batch")
    assert [r.unit for r in roots] == [0, 1, 2, 0, 1, 2]
    # and it finds the predictor's arena warm
    assert [r.args["arena_fresh"] for r in roots[3:]] == [0, 0, 0]

    # without a ring: totals only, and the same flows to the last bit
    bare = profiling.HostStageTimer(ring=0)
    monkeypatch.setattr(profiling, "_HOST_TIMER", bare)
    again = run_pass(predictor)
    assert bare.spans() == [] and bare.dropped == 0
    assert bare.summary()["pass.batch"]["count"] == 3
    assert len(again) == len(flows) == 7
    for a, b in zip(flows, again):
        assert a.shape == (H, W, 2)
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------- staging arena

SHAPES = {"a": (30, 44), "b": (22, 36)}
#: ten "a" pairs (three batches of three and one left over) with four
#: "b" pairs (a batch and one left over) in between
ORDER = "abaaabaaabaaba"


class MixedPairs:
    def __init__(self, dtype):
        self.samples = []
        for i, kind in enumerate(ORDER):
            r = np.random.default_rng(i)
            self.samples.append(tuple(
                r.integers(0, 256, SHAPES[kind] + (3,)).astype(dtype)
                for _ in range(2)))

    def __len__(self):
        return len(self.samples)

    def __getitem__(self, i):
        return self.samples[i]


class LoggingArena(StagingArena):
    def __init__(self, log):
        super().__init__()
        self.log = log

    def release(self, *buffers):
        self.log.append(("released", tuple(address(b) for b in buffers)))
        super().release(*buffers)


def address(array):
    return array.__array_interface__["data"][0]


class Recorder:
    """Stands in for a batched predictor: keeps what it is handed."""

    batch_size = BS

    def __init__(self):
        self.log, self.handed = [], []
        self.staging = LoggingArena(self.log)

    def predict_batch(self, images1, images2):
        # while the predictor has them the buffers are not in the pool
        assert self.staging.pooled_buffers() in (0, 2)   # the other shape's
        assert not any(b is images1 or b is images2
                       for pool in self.staging._pools.values()
                       for b in pool)
        self.handed.append((address(images1), address(images2),
                            images1.copy(), images2.copy()))
        up = images1[..., :2].astype(np.float32) - images2[..., :2]
        self.log.append(("returned", (address(images1), address(images2))))
        return up[:, ::8, ::8], up


def old_batches(dataset, mode, bs):
    """What ``_predict_dataset`` built before the arena: per-sample
    ``np.pad``, ``np.stack`` a full bucket, ``np.concatenate`` the
    repeated last frame onto a short one. Returns the stacks in flush
    order and the dataset indices in yield order."""
    stacks, order, buckets = [], [], {}

    def flush(items):
        i1, i2 = (np.stack([it[k] for it in items]) for k in (1, 2))
        if len(items) < bs:
            reps = bs - len(items)
            i1 = np.concatenate([i1, np.repeat(i1[-1:], reps, 0)])
            i2 = np.concatenate([i2, np.repeat(i2[-1:], reps, 0)])
        stacks.append((i1, i2))
        order.extend(it[0] for it in items)

    for idx in range(len(dataset)):
        im1, im2 = dataset[idx]
        if mode:
            im1, im2 = InputPadder(im1.shape, mode=mode).pad(im1, im2)
        buckets.setdefault(im1.shape, []).append((idx, im1, im2))
        if len(buckets[im1.shape]) == bs:
            flush(buckets.pop(im1.shape))
    for items in buckets.values():
        flush(items)
    return stacks, order


@pytest.mark.parametrize("mode,dtype", [
    ("sintel", np.float32), ("kitti", np.uint8), (None, np.float32)])
def test_pass_stages_through_the_arena(timer, mode, dtype):
    dataset, stub = MixedPairs(dtype), Recorder()
    want, order = old_batches(dataset, mode, BS)
    got = list(_predict_dataset(stub, dataset, mode))

    # the predictor was handed what pad -> stack -> concatenate built
    assert len(stub.handed) == len(want) == 6
    for (_, _, i1, i2), (w1, w2) in zip(stub.handed, want):
        assert i1.dtype == w1.dtype == dtype and i1.shape == w1.shape
        assert i1.tobytes() == w1.tobytes() and i2.tobytes() == w2.tobytes()

    # yields: the old order, the dataset's own samples, flows that are
    # the predictor's output and no arena memory
    assert [idx for idx, _, _ in got] == order
    buffers = [b for pool in stub.staging._pools.values() for b in pool]
    for idx, sample, flow in got:
        assert sample is dataset.samples[idx]
        im1, im2 = sample
        np.testing.assert_array_equal(
            flow, im1[..., :2].astype(np.float32) - im2[..., :2])
        assert not any(np.shares_memory(flow, b) for b in buffers)

    # one pair of buffers a shape: allocated for its first batch, the
    # same two from its second batch on, and idle in the pool at the end
    by_shape = {}
    for a1, a2, i1, _ in stub.handed:
        by_shape.setdefault(i1.shape, []).append({a1, a2})
    assert sorted(len(v) for v in by_shape.values()) == [2, 4]
    for pairs in by_shape.values():
        assert len(pairs[0]) == 2 and all(p == pairs[0] for p in pairs)
    assert stub.staging.allocated == 4 == len(buffers)
    roots = by_name(timer.spans(), "pass.batch")
    fresh = [r.args["arena_fresh"] for r in roots]
    assert sorted(fresh) == [0, 0, 0, 0, 2, 2]
    first = {}
    for r, (_, _, i1, _) in zip(roots, stub.handed):
        assert r.args["arena_fresh"] == (0 if i1.shape in first else 2)
        first[i1.shape] = True
        assert (r.args["height"], r.args["width"]) == i1.shape[1:3]

    # a pair goes back only once predict_batch has returned it
    assert [kind for kind, _ in stub.log] == ["returned", "released"] * 6
    assert all(a == b for (_, a), (_, b) in zip(stub.log[::2],
                                                stub.log[1::2]))

    # a second pass over the same predictor starts warm
    again = list(_predict_dataset(stub, dataset, mode))
    assert stub.staging.allocated == 4
    assert [r.args["arena_fresh"] for r in by_name(
        timer.spans(), "pass.batch")[6:]] == [0] * 6
    for (_, _, flow), (_, _, flow2) in zip(got, again):
        np.testing.assert_array_equal(flow, flow2)


def test_an_abandoned_pass_returns_its_open_buffers(timer):
    """Closed while a bucket is still filling: nothing of that bucket is
    in flight, so its pair goes back; a batch whose ``predict_batch``
    raised does not."""
    dataset, stub = MixedPairs(np.float32), Recorder()
    gen = _predict_dataset(stub, dataset, "sintel")
    next(gen)            # the first "a" batch yields; one "b" is waiting
    gen.close()
    assert stub.staging.pooled_buffers() == 4
    assert len({address(b) for pool in stub.staging._pools.values()
                for b in pool}) == 4

    class Failing(Recorder):
        def predict_batch(self, images1, images2):
            raise RuntimeError("device lost")

    failing = Failing()
    with pytest.raises(RuntimeError, match="device lost"):
        list(_predict_dataset(failing, dataset, "sintel"))
    # the failed "a" pair is dropped; the waiting "b" pair is returned
    assert failing.staging.pooled_buffers() == 2
    assert all(b.shape == (BS, 24, 40, 3)
               for pool in failing.staging._pools.values() for b in pool)


def test_reported_pass_prints_the_reuse_share(timer, capsys):
    from raft_tpu.evaluate import _reported_pass

    stub = Recorder()
    assert len(list(_reported_pass(stub, MixedPairs(np.float32),
                                   "sintel"))) == len(ORDER)
    line = capsys.readouterr().out
    assert "host stages:" in line and "pass.stack" in line
    assert "arena reuse: 67% of 12 buffers" in line


def test_pass_closed_at_a_yield(predictor, timer):
    """The benchmark closes the generator at a batch's last yield: that
    batch is complete; one closed a yield earlier is not. The batch
    dispatched ahead of it is abandoned either way, and no span stays
    open."""
    for stop_after, complete in ((BS, 1), (BS - 1, 0)):
        gen = _predict_dataset(predictor, Pairs(), mode="sintel")
        for n, _ in enumerate(gen, 1):
            if n == stop_after:
                break
        gen.close()
        roots = by_name(timer.spans(), "pass.batch")[-2:]
        assert [(r.unit, r.args["complete"]) for r in roots] == [
            (0, complete), (1, 0)]
        with timer.span("probe") as probe:
            pass
        assert probe.parent == 0


# --------------------------------------------------- two batches deep
#
# A FlowPredictor's pass dispatches batch k+1 before it collects batch
# k. The taps below are set on the instance's dispatch_batch and
# collect_batch: predict_batch stays the class's own, which is what the
# pass looks at, so it still pipelines.

class Cycled:
    """The first ``n`` elements of ``base`` repeated."""

    def __init__(self, base, n):
        self.base, self.n = base, n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        return self.base[i % len(self.base)]


DATASETS = {
    "mixed_float32": lambda: MixedPairs(np.float32),
    "mixed_uint8": lambda: MixedPairs(np.uint8),
    "short_tail": Pairs,
    "one_batch": lambda: Cycled(Pairs(), BS),
    "empty": lambda: Cycled(Pairs(), 0),
}


@contextlib.contextmanager
def wrapped_predict_batch(predictor, log):
    """``predict_batch`` wrapped on the instance, the way
    ``benchmark/tests/test_correct.py`` plants its faults."""
    real = predictor.predict_batch

    def predict_batch(images1, images2):
        log.append(("call", address(images1)))
        out = real(images1, images2)
        log.append(("returned", address(images1)))
        return out

    predictor.predict_batch = predict_batch
    try:
        yield
    finally:
        del predictor.predict_batch


@contextlib.contextmanager
def tapped(predictor, log, dispatch_fails_at=None, collect_fails_at=None,
           gates=None):
    """Log every ``dispatch_batch`` and ``collect_batch`` with the
    staging pair it is for, and hold that a pair reads at collection
    what it read at dispatch. ``gates``: one ``threading.Event`` a
    batch; its outputs cannot be collected before it is set."""
    dispatch, collect = predictor.dispatch_batch, predictor.collect_batch
    inflight = {}

    def dispatch_batch(images1, images2):
        k = sum(kind == "dispatched" for kind, _ in log)
        if k == dispatch_fails_at:
            raise RuntimeError("dispatch refused")
        flows = dispatch(images1, images2)
        inflight[id(flows[1])] = (k, images1, images2, images1.copy(),
                                  images2.copy())
        log.append(("dispatched", (address(images1), address(images2))))
        return flows

    def collect_batch(flows):
        k, images1, images2, was1, was2 = inflight.pop(id(flows[1]))
        if gates is not None:
            assert gates[k].wait(timeout=60), f"batch {k} never released"
        if k == collect_fails_at:
            raise RuntimeError("device lost")
        out = collect(flows)
        assert (images1.tobytes() == was1.tobytes()
                and images2.tobytes() == was2.tobytes()), (
            f"batch {k}'s pair was rewritten while it was in flight")
        log.append(("collected", (address(images1), address(images2))))
        return out

    predictor.dispatch_batch = dispatch_batch
    predictor.collect_batch = collect_batch
    try:
        yield
    finally:
        del predictor.dispatch_batch, predictor.collect_batch


class CycleArena(LoggingArena):
    """Logs what it hands out too."""

    def acquire(self, shape, dtype):
        buffer = super().acquire(shape, dtype)
        self.log.append(("acquired", address(buffer)))
        return buffer


@pytest.mark.parametrize("name", list(DATASETS))
def test_pipelined_pass_yields_what_the_synchronous_order_yields(
        predictor, timer, name):
    """Bit for bit and in the same order, whatever the shapes, the tail
    and the dtype on the wire; the wrapped ``predict_batch`` is called
    once a batch."""
    dataset = DATASETS[name]()
    got = list(_predict_dataset(predictor, dataset, "sintel"))
    calls = []
    with wrapped_predict_batch(predictor, calls):
        want = list(_predict_dataset(predictor, dataset, "sintel"))
    assert [idx for idx, _, _ in got] == [idx for idx, _, _ in want]
    assert len(got) == len(dataset)
    for (_, sample, flow), (_, sample2, flow2) in zip(got, want):
        assert all(a.tobytes() == b.tobytes()
                   for a, b in zip(sample, sample2))
        assert flow.shape == sample[0].shape[:2] + (2,)
        assert flow.dtype == flow2.dtype == np.float32
        assert flow.tobytes() == flow2.tobytes()
    roots = by_name(timer.spans(), "pass.batch")
    batches = len(roots) // 2
    assert batches == {"mixed_float32": 6, "mixed_uint8": 6,
                       "short_tail": 3, "one_batch": 1, "empty": 0}[name]
    assert [r.args["ahead"] for r in roots] == (
        [0] + [1] * (batches - 1) if batches else []) + [0] * batches
    assert [kind for kind, _ in calls] == ["call", "returned"] * batches


def test_pipelined_pass_overlaps(predictor, timer):
    """Batch k+1 is dispatched before batch k's outputs are read, across
    a change of shape too (the batches go a a b a b a), and every span
    lies under its own batch's root though two roots are open."""
    list(_predict_dataset(predictor, MixedPairs(np.float32), "sintel"))
    spans = staged(timer.spans())
    roots = by_name(spans, "pass.batch")
    assert [r.unit for r in roots] == list(range(6))
    assert [r.args["ahead"] for r in roots] == [0, 1, 1, 1, 1, 1]
    assert [r.args["height"] for r in roots] == [32, 32, 24, 32, 24, 32]
    assert all(r.parent == 0 and r.args["complete"] == 1 for r in roots)
    assert {s.parent for s in spans if s.name != "pass.batch"} == {
        r.id for r in roots}
    kids = {r.unit: {s.name: s for s in spans if s.parent == r.id}
            for r in roots}
    for r in roots:
        # (a leftover batch's frames were fetched under earlier roots)
        assert set(BATCH_LEVEL[1:]) | {"pass.unpad"} <= set(
            kids[r.unit]) <= set(BATCH_LEVEL[1:]) | {
                "pass.fetch", "pass.pad", "pass.unpad"}
        assert all(s.unit == r.unit for s in kids[r.unit].values())
        n = r.args["pairs"]
        assert sum(s.parent == r.id and s.name == "pass.unpad"
                   for s in spans) == n
    for a, b in zip(roots, roots[1:]):
        ahead, behind = kids[b.unit], kids[a.unit]
        assert (ahead["predict.dispatch"].start_ns
                + ahead["predict.dispatch"].dur_ns
                <= behind["predict.device_wait"].start_ns
                < behind["predict.d2h"].start_ns)
        # and a's flows are out before b's outputs are touched
        assert (a.start_ns + a.dur_ns
                <= ahead["predict.device_wait"].start_ns)
    # the reader of the six pass stages reads them over overlapping
    # roots: the whole pass, and its last three batches
    for metric in ("fetch_pad", "stack", "h2d", "dispatch", "device_wait",
                   "d2h"):
        args = json.load(open(os.path.join(
            os.path.dirname(program_spans.__file__), "..", "metrics",
            metric + "_ms_per_batch.pass.json")))["args"]
        for n in (6, 3):
            ids = {r.id for r in roots[-n:]}
            want = sum(s.dur_ns for s in spans if s.parent in ids
                       and s.name in args["stages"]) / n / 1e6
            assert program_spans.per_unit_ms(
                {"run": {"batches": n}}, args["root"], args["stages"],
                args["units"]) == pytest.approx(want)
            assert want > 0


def test_a_pair_stays_with_its_batch_until_its_outputs_are_read(
        predictor, timer, monkeypatch):
    log = []
    monkeypatch.setattr(predictor, "staging", CycleArena(log))
    with tapped(predictor, log):
        flows = list(_predict_dataset(predictor, Cycled(Pairs(), 5 * BS),
                                      "sintel"))
    assert len(flows) == 5 * BS
    # two pairs, allocated by the first two batches, used in turn
    arena = predictor.staging
    assert arena.allocated == 4 == arena.pooled_buffers()
    assert [r.args["arena_fresh"] for r in by_name(
        timer.spans(), "pass.batch")] == [2, 2, 0, 0, 0]
    pairs = [frozenset(p) for kind, p in log if kind == "dispatched"]
    assert len(set(pairs)) == 2 and all(
        a != b for a, b in zip(pairs, pairs[1:]))
    # each buffer's life: handed out, dispatched, its outputs read, back
    # in the pool, and only then handed out again
    for buffer in {a for pair in pairs for a in pair}:
        life = [kind for kind, what in log
                if what == buffer or (isinstance(what, tuple)
                                      and buffer in what)]
        assert life == ["acquired", "dispatched", "collected",
                        "released"] * (len(life) // 4)
    # and batch k+1 was dispatched before batch k was collected
    kinds = [kind for kind, _ in log if kind in ("dispatched", "collected")]
    assert kinds == (["dispatched"] + ["dispatched", "collected"] * 4
                     + ["collected"])


def test_closing_the_pass_does_not_wait_for_the_batch_in_flight(
        predictor, timer, monkeypatch):
    """The benchmark closes the pass at a batch's last yield with the
    next batch on the device. That batch is abandoned: nobody waits for
    it, its pair is not pooled, its root closes incomplete; the span
    reader still reads the completed batches."""
    import threading
    import time

    log, gates = [], [threading.Event() for _ in range(4)]
    monkeypatch.setattr(predictor, "staging", CycleArena(log))
    gates[0].set()
    gates[1].set()
    with tapped(predictor, log, gates=gates):
        gen = _predict_dataset(predictor, Cycled(Pairs(), 4 * BS), "sintel")
        got = [next(gen) for _ in range(2 * BS)]
        began = time.perf_counter()
        gen.close()          # batch 2 is dispatched; its gate stays shut
        assert time.perf_counter() - began < 5
    assert [idx for idx, _, _ in got] == list(range(2 * BS))
    kinds = [kind for kind, _ in log if kind in ("dispatched", "collected")]
    assert kinds == ["dispatched", "dispatched", "collected", "dispatched",
                     "collected"]
    # batches 0 and 1 ran on two pairs, both back; batch 2 had taken
    # batch 0's again, and that one is dropped
    arena = predictor.staging
    assert arena.allocated == 4 and arena.pooled_buffers() == 2
    roots = by_name(timer.spans(), "pass.batch")
    assert [(r.unit, r.args["complete"], r.args["ahead"])
            for r in roots] == [(0, 1, 0), (1, 1, 1), (2, 0, 1)]
    assert {s.name for s in staged(timer.spans())
            if s.parent == roots[2].id} == {
        "pass.fetch", "pass.pad", "pass.stack", "predict.h2d",
        "predict.dispatch"}
    with timer.span("probe") as probe:
        pass
    assert probe.parent == 0
    for stages in (["pass.fetch", "pass.pad"], ["pass.stack"],
                   ["predict.h2d"], ["predict.dispatch"],
                   ["predict.device_wait"], ["predict.d2h"]):
        assert program_spans.per_unit_ms(
            {"run": {"batches": 2}}, "pass.batch", stages, "batches") > 0
    # a later pass on the same predictor: the same flows
    again = list(_predict_dataset(predictor, Cycled(Pairs(), 2 * BS),
                                  "sintel"))
    for (_, _, flow), (_, _, flow2) in zip(got, again):
        np.testing.assert_array_equal(flow, flow2)


@pytest.mark.parametrize("where", ["dispatch", "collect"])
def test_a_failing_batch_surfaces_after_the_flows_before_it(
        predictor, timer, monkeypatch, where):
    """Batch 1 fails, in its dispatch or when its outputs are read:
    the consumer has batch 0's flows by then, as in the synchronous
    order, and the failed batch's pair (and that of batch 2, already in
    flight when batch 1's collection fails) is not pooled."""
    log = []
    monkeypatch.setattr(predictor, "staging", CycleArena(log))
    got = []
    with tapped(predictor, log, **{where + "_fails_at": 1}):
        with pytest.raises(RuntimeError, match="refused|lost"):
            for item in _predict_dataset(predictor, Cycled(Pairs(), 4 * BS),
                                         "sintel"):
                got.append(item)
    assert [idx for idx, _, _ in got] == list(range(BS))
    roots = by_name(timer.spans(), "pass.batch")
    if where == "dispatch":
        assert [(r.unit, r.args["complete"]) for r in roots] == [
            (0, 1), (1, 0)]
        assert predictor.staging.pooled_buffers() == 2
    else:
        assert [(r.unit, r.args["complete"]) for r in roots] == [
            (0, 1), (1, 0), (2, 0)]
        assert [kind for kind, _ in log if kind in (
            "dispatched", "collected")] == [
                "dispatched", "dispatched", "collected", "dispatched"]
        assert predictor.staging.pooled_buffers() == 0
    with timer.span("probe") as probe:
        pass
    assert probe.parent == 0
    want = list(_predict_dataset(predictor, Cycled(Pairs(), BS), "sintel"))
    for (_, _, flow), (_, _, flow2) in zip(got, want):
        np.testing.assert_array_equal(flow, flow2)


def test_a_failing_fetch_surfaces_after_the_flows_before_it(predictor,
                                                            timer):
    class Torn(Pairs):
        def __getitem__(self, i):
            if i == BS + 1:
                raise OSError("frame unreadable")
            return super().__getitem__(i)

    got = []
    with pytest.raises(OSError, match="unreadable"):
        for item in _predict_dataset(predictor, Torn(), "sintel"):
            got.append(item)
    assert [idx for idx, _, _ in got] == list(range(BS))
    assert [(r.unit, r.args["complete"]) for r in by_name(
        timer.spans(), "pass.batch")] == [(0, 1), (1, 0)]


@pytest.mark.parametrize("kind", ["stand_in", "wrapped"])
def test_any_other_predict_batch_gets_one_blocking_call_a_batch(
        predictor, timer, kind):
    """A stand-in that offers only ``predict_batch``, and a
    ``FlowPredictor`` whose ``predict_batch`` is wrapped on the
    instance: stage, call, yield, batch after batch."""
    log = []
    dataset = MixedPairs(np.float32)
    if kind == "stand_in":
        subject, context = Recorder(), contextlib.nullcontext()
        subject.staging.log = subject.log = log
    else:
        subject, context = predictor, wrapped_predict_batch(predictor, log)
    with context:
        for idx, _, _ in _predict_dataset(subject, dataset, "sintel"):
            log.append(("yield", idx))
    events = [(kind_, what) for kind_, what in log
              if kind_ in ("returned", "yield")]
    _, order = old_batches(dataset, "sintel", BS)
    want = []
    for pairs in (3, 3, 3, 3, 1, 1):
        want += ["returned"] + ["yield"] * pairs
    assert [kind_ for kind_, _ in events] == want
    assert [what for kind_, what in events if kind_ == "yield"] == order
    roots = by_name(timer.spans(), "pass.batch")
    assert [r.args["ahead"] for r in roots] == [0] * 6
    assert [r.args["pairs"] for r in roots] == [3, 3, 3, 3, 1, 1]
    for a, b in zip(roots, roots[1:]):     # one root open at a time
        assert a.start_ns + a.dur_ns <= b.start_ns


def test_reader_reads_the_newest_pass(predictor, timer):
    run_pass(predictor)
    run_pass(predictor)
    ctx = {"run": {"batches": 3}}
    spans = timer.spans()
    last = by_name(spans, "pass.batch")[-3:]
    ids = {r.id for r in last}
    for stages in (["pass.fetch", "pass.pad"], ["predict.device_wait"]):
        want = sum(s.dur_ns for s in spans
                   if s.parent in ids and s.name in stages) / 3 / 1e6
        got = program_spans.per_unit_ms(ctx, "pass.batch", stages,
                                        "batches")
        assert got == pytest.approx(want) and got > 0
    # more units than the newest pass holds: not one run's
    assert program_spans.per_unit_ms({"run": {"batches": 4}}, "pass.batch",
                                     ["pass.stack"], "batches") is None
    assert program_spans.per_unit_ms({"run": {}}, "pass.batch",
                                     ["pass.stack"], "batches") is None


@pytest.fixture
def no_collector():
    """No pass of the cyclic collector, so none takes a slot of a ring
    whose slots a test counts."""
    import gc
    gc.disable()
    yield
    gc.enable()


def test_ring_overflow_silences_the_reader(predictor, monkeypatch,
                                           no_collector):
    small = profiling.HostStageTimer(ring=40)    # a pass is 39 spans
    monkeypatch.setattr(profiling, "_HOST_TIMER", small)
    run_pass(predictor)
    ctx = {"run": {"batches": 3}}
    args = ("pass.batch", ["pass.stack"], "batches")
    assert small.dropped == 0
    assert program_spans.per_unit_ms(ctx, *args) > 0
    run_pass(predictor)
    assert small.dropped == 38 and len(small.spans()) == 40
    # the oldest span kept is the first pass's last root: the newest
    # pass is whole
    assert program_spans.per_unit_ms(ctx, *args) > 0
    with small.span("one"), small.span("two"):
        pass
    assert small.dropped == 40
    assert program_spans.per_unit_ms(ctx, *args) is None


def test_a_closed_span_leaves_no_object_for_the_collector():
    """The ring keeps columns, not objects: 390 kept objects a batch
    moved the collector's cadence, and with it when JAX frees a batch's
    staging arrays (8 % of the large pass on the chip, PERF.md PR 27)."""
    import gc

    timer = profiling.HostStageTimer(ring=256)
    with timer.span("warm", unit=0):
        pass
    gc.collect()
    gc.disable()
    try:
        before = gc.get_count()[0]
        for unit in range(1000):
            with timer.span("pass.batch", unit=unit):
                with timer.span("pass.pad"):
                    pass
        grown = gc.get_count()[0] - before
    finally:
        gc.enable()
    assert grown < 20, grown
    assert timer.dropped == 2001 - 256
    last = timer.spans()[-1]
    assert (last.name, last.unit, last.parent, last.args) == (
        "pass.batch", 999, 0, {})


def _span(name, ident, parent=0, unit=0, dur_ns=0, **args):
    return types.SimpleNamespace(name=name, id=ident, parent=parent,
                                 unit=unit, dur_ns=dur_ns, args=args)


def test_reader_on_hand_made_spans():
    spans = []
    for unit in range(4):
        root = 10 * (unit + 1)
        spans += [_span("train.loader_wait", root + 1, root, unit, 2_000_000),
                  _span("train.log", root + 2, root, unit, 500_000),
                  _span("train.loader_snapshot", root + 3, root, unit,
                        250_000),
                  _span("train.step", root, 0, unit, 9_000_000,
                        complete=int(unit != 3))]
    read = program_spans.stage_ms_per_unit
    # the step whose next() raised (unit 3) does not count
    assert read(spans, 0, "train.step", ["train.loader_wait"], 3) == 2.0
    assert read(spans, 0, "train.step",
                ["train.log", "train.loader_snapshot"], 2) == 0.75
    assert read(spans, 0, "train.step", ["train.loader_wait"], 4) is None
    assert read(spans, 0, "train.step", ["train.dispatch"], 3) is None
    assert read(spans, 0, "pass.batch", ["train.log"], 1) is None
    assert read(spans, 0, "train.step", ["train.log"], 0) is None
    # dropped spans: fine while the oldest one kept is not theirs
    assert read(spans, 7, "train.step", ["train.log"], 2) == 0.5
    assert read(spans, 7, "train.step", ["train.log"], 3) is None
    assert read(spans[3:], 7, "train.step", ["train.log"], 2) == 0.5
    # two runs' units do not follow one another
    spans[7].unit = 0
    assert read(spans, 0, "train.step", ["train.log"], 2) is None


def test_spans_reach_the_chrome_trace(predictor, timer):
    from raft_tpu.observability import disable_tracing, enable_tracing
    import time

    tracer = enable_tracing()
    try:
        before = time.perf_counter_ns()
        run_pass(predictor)
        doc = tracer.chrome_trace()
    finally:
        disable_tracing()
    host = [e for e in doc["traceEvents"] if e.get("cat") == "host"]
    # a collector pass under a span of a unit is forwarded like any
    # span of that unit
    assert {e["name"] for e in host} - {"gc.pass"} == set(BATCH_LEVEL) | {
        "pass.fetch", "pass.pad", "pass.unpad"}
    assert len(host) == len([s for s in timer.spans()
                             if s.unit is not None])
    batch = next(e for e in host if e["name"] == "pass.batch")
    assert batch["args"]["pairs"] == 3 and batch["args"]["unit"] == 0
    # on the timer's clock through the artifact's t0_ns
    t0 = doc["otherData"]["t0_ns"]
    first = by_name(timer.spans(), "pass.batch")[0]
    assert t0 + batch["ts"] * 1e3 == pytest.approx(first.start_ns, abs=2e3)
    assert first.start_ns >= before
    # tracing off again: nothing more is forwarded
    run_pass(predictor)
    assert len(tracer.events()) == len(doc["traceEvents"]) - sum(
        e["ph"] == "M" for e in doc["traceEvents"])


# ------------------------------------------------------------- train loop

TH, TW = 32, 48


class Loader:
    """``n`` batches, then either the end or an exception."""

    def __init__(self, n, raises=None):
        self.n, self.raises = n, raises

    def __iter__(self):
        rng = np.random.default_rng(0)
        for _ in range(self.n):
            image = rng.uniform(0, 255, (8, TH, TW, 3)).astype(np.float32)
            yield {"image1": image, "image2": np.roll(image, 2, axis=2),
                   "flow": np.full((8, TH, TW, 2), 2.0, np.float32),
                   "valid": np.ones((8, TH, TW), np.float32)}
        if self.raises is not None:
            raise self.raises


class Closed(Exception):
    pass


def test_train_spans(tmp_path, timer):
    """Four steps, then a loader that raises: ``train()`` finishes and
    logs step 4, in flight when the fifth fetch raised, under a root of
    its own (the root whose fetch raised began no step: `complete` 0),
    then lets the exception through (no exit checkpoint) and the open
    spans end. Two deep: root n asks for batch n+1, dispatches step n+1
    and then finishes step n; the first also fetches and dispatches
    step 1."""
    import json

    from raft_tpu.train import train
    from raft_tpu.utils.logger import TrainLogger

    tcfg = TrainConfig(name="t", num_steps=10, batch_size=8,
                       image_size=(TH, TW), iters=2, val_freq=1000,
                       sum_freq=2)
    logger = TrainLogger(str(tmp_path / "logs"), sum_freq=2,
                         tensorboard=False)
    with pytest.raises(Closed):
        train(tcfg, RAFTConfig(small=True, iters=2),
              ckpt_dir=str(tmp_path / "ckpts"),
              log_dir=str(tmp_path / "logs"),
              dataloader=Loader(4, raises=Closed()), logger=logger)
    logger.close()
    assert not (tmp_path / "ckpts" / "t").exists() or not any(
        (tmp_path / "ckpts" / "t").iterdir())
    assert _hooks() == []      # the collector's hook left with the loop

    spans = staged(timer.spans())
    steps = by_name(spans, "train.step")
    assert [(s.unit, s.args["complete"], s.args["ahead"]) for s in steps] == [
        (1, 1, 0), (2, 1, 1), (3, 1, 1), (4, 0, 0), (4, 1, 0)]
    assert all(s.parent == 0 for s in steps)
    assert steps[0].args["compiles"] >= 1
    assert not any("compiles" in s.args for s in steps[3:])
    # the roots do not overlap, and every other span lies in one of them
    for a, b in zip(steps, steps[1:]):
        assert a.start_ns + a.dur_ns <= b.start_ns
    ids = {s.id for s in steps}
    assert all(s.parent in ids for s in spans if s.id not in ids)
    kids = [[s for s in spans if s.parent == step.id] for step in steps]
    for step, under in zip(steps, kids):
        assert all(k.unit == step.unit for k in under)
        assert all(k.start_ns >= step.start_ns
                   and k.start_ns + k.dur_ns <= step.start_ns + step.dur_ns
                   for k in under)
    # step 1's fetch, shard and dispatch and step 2's under root 1
    assert sorted(k.name for k in kids[0]) == sorted(
        STEP_CHILDREN + STEP_CHILDREN[:3])
    for under in kids[1:3]:
        assert sorted(k.name for k in under) == sorted(STEP_CHILDREN)
        assert by_name(under, "train.shard_batch")[0].nbytes == (
            8 * TH * TW * (3 + 3 + 2 + 1) * 4)
        assert by_name(under, "train.metrics_fetch")[0].args["leaves"] >= 5
    # the fetch that raised, then step 4 finished under a root of its own
    assert [[k.name for k in under] for under in kids[3:]] == [
        ["train.loader_wait"],
        ["train.device_wait", "train.metrics_fetch", "train.log"]]
    with timer.span("probe") as probe:
        pass
    assert probe.parent == 0

    for stages in (["train.loader_wait"], ["train.log",
                                           "train.loader_snapshot"]):
        assert program_spans.per_unit_ms(
            {"run": {"steps": 4}}, "train.step", stages, "steps") > 0
    assert program_spans.per_unit_ms(
        {"run": {"steps": 5}}, "train.step", ["train.dispatch"],
        "steps") is None

    # the operator's line: means of the spans closed since the last
    # flush, and of the steps closed since, how many had the next step
    # dispatched ahead
    lines = [json.loads(line)
             for line in open(tmp_path / "logs" / "scalars.jsonl")]
    assert len(lines) == 2 and np.isfinite(lines[0]["loss"])
    for line in lines:
        for name in STEP_CHILDREN[:-1] + ("train.step",):
            assert line["host/" + name[len("train."):] + "_ms"] > 0
    assert [(line["host/ahead_steps"], line["host/steps"])
            for line in lines] == [(0, 1), (2, 2)]


# ------------------------------------------- the whole of a step, a batch

import gc          # noqa: E402
import threading   # noqa: E402
import time        # noqa: E402

from benchmark.readers import program_units   # noqa: E402


def test_a_full_pass_inside_a_span_is_a_span(timer):
    before = time.perf_counter_ns()
    with timer.collector_spans():
        with timer.span("train.step", unit=7) as root:
            with timer.span("train.log") as log:
                gc.collect()
    after = time.perf_counter_ns()
    (found,) = by_name(timer.spans(), "gc.pass")
    assert found.args["generation"] == 2 and found.args["main"] == 1
    assert set(found.args) == {"generation", "collected", "uncollectable",
                               "main"}
    assert (found.parent, found.unit) == (log.id, 7) and root.id != log.id
    # on the spans' clock, inside the span it ran in
    assert before <= log.start_ns <= found.start_ns
    assert (found.start_ns + found.dur_ns
            <= log.start_ns + log.dur_ns <= after)
    # children before their parent: the pass precedes the span it fell in
    names = [s.name for s in timer.spans()]
    assert names == ["gc.pass", "train.log", "train.step"]


def test_a_pass_on_another_thread_is_counted_by_overlap(timer):
    """The collector on a loader's thread holds the interpreter while
    the loop's thread waits in ``block_until_ready``: no span of the
    loop is its parent, and the step it fell into still pays for it."""
    with timer.collector_spans():
        for unit in (1, 2, 3):
            root = timer.span("train.step", unit=unit, complete=1)
            with timer.span("train.device_wait"):
                if unit == 2:
                    other = threading.Thread(target=gc.collect)
                    other.start()
                    other.join(timeout=30)
                    assert not other.is_alive()
            timer.close_root(root)
    spans = timer.spans()
    (found,) = by_name(spans, "gc.pass")
    assert found.args["main"] == 0 and found.args["generation"] == 2
    assert (found.parent, found.unit) == (0, None)
    steps = by_name(spans, "train.step")
    assert steps[1].start_ns <= found.start_ns and (
        found.start_ns + found.dur_ns <= steps[1].start_ns + steps[1].dur_ns)
    got = program_units.collector_ms_per_unit(spans, 0, "train.step", 3)
    young = (steps[2].args["young_us"] - steps[0].args["young_us"]) / 2 / 1e3
    assert got == pytest.approx(found.dur_ns / 3 / 1e6 + young)
    rows = profiling.unit_accounts(spans, "train.step")
    assert [len(r["passes"]) for r in rows] == [0, 1, 0]
    assert rows[1]["passes"][0][:2] == (2, 0)


def test_young_passes_reach_the_totals_and_never_the_ring(timer):
    gc.collect()
    with timer.collector_spans():
        with timer.span("pass.batch", unit=0):
            for _ in range(30):
                gc.collect(0)
                gc.collect(1)
    young = timer.summary()["gc.young"]
    assert young["count"] == 60 and young["total_ms"] > 0
    assert timer.young_us == int(young["total_ms"] * 1e3)
    assert [s.name for s in timer.spans()] == ["pass.batch"]
    assert timer.recorded == 1 and "gc.pass" not in timer.summary()
    # what came since a mark, as for any stage
    mark = timer.summary()
    with timer.collector_spans():
        gc.collect(0)
    assert timer.summary(since=mark)["gc.young"]["count"] == 1


def test_a_hook_installed_inside_a_pass_skips_it(timer):
    """Another ``gc.callbacks`` entry can let go of the interpreter
    between a pass's two callbacks; a hook appended then gets ``stop``
    without its ``start``: no span from time zero, no totals."""
    info = {"generation": 2, "collected": 0, "uncollectable": 0}
    timer._on_gc("stop", info)
    assert timer.spans() == [] and "gc.young" not in timer.summary()
    # and a start is used once
    timer._on_gc("start", info)
    timer._on_gc("stop", info)
    timer._on_gc("stop", info)
    (found,) = by_name(timer.spans(), "gc.pass")
    assert found.start_ns > 0 and found.dur_ns < 1e9


def _train_tiny(tmp_path, loader, num_steps=10, sum_freq=100):
    from raft_tpu.train import train
    from raft_tpu.utils.logger import TrainLogger

    tcfg = TrainConfig(name="t", num_steps=num_steps, batch_size=8,
                       image_size=(TH, TW), iters=2, val_freq=1000,
                       sum_freq=sum_freq)
    logger = TrainLogger(str(tmp_path / "logs"), sum_freq=sum_freq,
                         tensorboard=False)
    try:
        train(tcfg, RAFTConfig(small=True, iters=2),
              ckpt_dir=str(tmp_path / "ckpts"),
              log_dir=str(tmp_path / "logs"), dataloader=loader,
              logger=logger)
    finally:
        logger.close()


def _hooks():
    return [cb for cb in gc.callbacks
            if getattr(cb, "__func__", None)
            is profiling.HostStageTimer._on_gc]


@pytest.mark.parametrize("way_out", ["block_returns", "block_raises",
                                     "closed_mid_batch", "nested"])
def test_the_collector_hook_is_gone_on_every_way_out(way_out, timer,
                                                     predictor):
    """``train()``'s own ways out, a return and an exception, are held
    by ``test_train_prints_a_slow_step_and_writes_the_worst`` and
    ``test_train_spans``."""
    assert _hooks() == []
    if way_out == "block_returns":
        with timer.collector_spans():
            assert len(_hooks()) == 1
    elif way_out == "block_raises":
        with pytest.raises(Closed):
            with timer.collector_spans():
                raise Closed()
    elif way_out == "closed_mid_batch":
        gen = _predict_dataset(predictor, Pairs(), mode="sintel")
        next(gen)
        assert len(_hooks()) == 1
        gen.close()
        root = by_name(timer.spans(), "pass.batch")[0]
        assert root.args["complete"] == 0 and "cpu_us" in root.args
    else:
        # a pass inside a run (train()'s validation): one hook, the
        # outer run's, until the outer run is left
        with timer.collector_spans():
            run_pass(predictor)
            assert len(_hooks()) == 1
    assert _hooks() == []


@pytest.mark.parametrize("plant", ["sleep", "busy"])
def test_time_between_two_children_with_and_without_cpu(timer, plant):
    """Between two children of one step in four the thread sleeps for
    50 ms, or works until it has had 50 ms of a CPU: unattributed
    either way; CPU time only where it worked."""
    planted_ms = 0.0
    for unit in range(1, 5):
        root = timer.span("train.step", unit=unit, complete=1)
        with timer.span("train.dispatch"):
            pass
        if unit == 3:
            began, worked = time.perf_counter(), time.thread_time()
            if plant == "sleep":
                time.sleep(0.05)
            while plant == "busy" and time.thread_time() - worked < 0.05:
                pass
            planted_ms = (time.perf_counter() - began) * 1e3
        with timer.span("train.log"):
            pass
        timer.close_root(root)
    spans = timer.spans()
    assert planted_ms >= 50
    assert program_units.unattributed_ms_per_unit(
        spans, 0, "train.step", 4) == pytest.approx(planted_ms / 4, rel=0.1)
    # no root before the four: the growth from the first's close on
    cpu_us = program_units.arg_per_unit(spans, 0, "train.step", 4, "cpu_us",
                                        True)
    row = profiling.unit_accounts(spans, "train.step")[2]
    assert row["unattributed"] == pytest.approx(planted_ms, rel=0.1)
    assert row["stages"].keys() == {"dispatch", "log"}
    if plant == "sleep":
        assert cpu_us < 10_000 / 3 and row["cpu_ms"] < 10
    else:
        assert cpu_us == pytest.approx(50_000 / 3, rel=0.2)
        assert row["cpu_ms"] == pytest.approx(50, rel=0.2)
    assert program_units.worst_over_median(
        spans, 0, "train.step", 4, False) > 20
    # (the other three are microseconds long: one may be thrice another)
    (line,) = [line for line in profiling.slow_unit_lines(
        profiling.unit_accounts(spans, "train.step"), "step")
        if line.startswith("slow step 3: ")]
    assert "unattributed +" in line
    assert "no gc.pass" in line and "cpu " in line


def test_consume_us_is_the_consumers_time(predictor, timer, capsys):
    """The consumer dawdles 30 ms over each flow of the second batch:
    that batch's root says so, the stage spans do not grow, and the
    pass's account closes."""
    from raft_tpu.evaluate import _reported_pass

    run_pass(predictor)      # compiled: the timed pass is steady
    began = time.perf_counter_ns()
    for n, _ in enumerate(_reported_pass(predictor, Cycled(Pairs(), 15),
                                         "sintel")):
        if n // BS == 1:
            time.sleep(0.03)
    spans = [s for s in timer.spans() if s.start_ns >= began]
    roots = by_name(spans, "pass.batch")
    assert [r.args["complete"] for r in roots] == [1] * 5
    consumed = [r.args["consume_us"] for r in roots]
    # a sleep may overrun on a busy machine, never fall short
    assert 3 * 30_000 <= consumed[1] < 10 * 30_000
    assert all(c < consumed[1] / 4 for c in consumed[:1] + consumed[2:])
    assert program_units.arg_per_unit(
        spans, 0, "pass.batch", 5, "consume_us", False) == sum(consumed) / 5
    # every millisecond between the first root's start and the last
    # one's end: in a stage span, the consumer's, or uncovered
    ids = {r.id for r in roots}
    staged_ns = sum(s.dur_ns for s in staged(spans) if s.parent in ids)
    uncovered = program_units.uncovered_ms_per_unit(spans, 0, "pass.batch",
                                                    5)
    whole = roots[-1].start_ns + roots[-1].dur_ns - roots[0].start_ns
    assert staged_ns / 1e6 + sum(consumed) / 1e3 + 5 * uncovered == (
        pytest.approx(whole / 1e6, abs=0.01))
    assert 0 <= uncovered < 0.2 * whole / 5 / 1e6
    # the batch periods by the roots' closes: the dawdled one is longest
    rows = profiling.unit_accounts(spans, "pass.batch")
    assert max(rows, key=lambda r: r["ms"])["unit"] == 1
    assert rows[1]["stages"]["consume"] == consumed[1] / 1e3
    printed = capsys.readouterr().out
    assert "host stages:" in printed


def _record(name, ident, parent, unit, start_ms, dur_ms, **args):
    return profiling.SpanRecord(name, ident, parent, unit,
                                int(start_ms * 1e6), int(dur_ms * 1e6), 0,
                                args)


def _hand_made(root="train.step", overlap_ms=0.0, usage=True):
    """Five roots of 10 ms (units 0-4; unit 3 takes 40), each with two
    children of 2 and 3 ms; ``overlap_ms``: how far a root opens before
    the one before closes (a pass's roots). A generation-2 pass of 6 ms
    on another thread inside unit 3, a young pass's span of 1 ms on
    this thread inside unit 1."""
    spans, at = [], 0.0
    for unit in range(5):
        ident, dur = 10 * (unit + 1), (40.0 if unit == 3 else 10.0)
        start = at - (overlap_ms if unit else 0.0)
        spans += [_record("x.load", ident + 1, ident, unit, start + 1, 2),
                  _record("x.wait", ident + 2, ident, unit, at + 4, 3)]
        if unit == 1:
            spans.append(_record("gc.pass", ident + 3, ident + 2, unit,
                                 at + 5, 1, generation=1, main=1))
        if unit == 3:
            spans.append(_record("gc.pass", ident + 3, 0, None, at + 20, 6,
                                 generation=2, main=0))
        used = dict(cpu_us=1000 * (unit + 1) + (500 if unit >= 3 else 0),
                    young_us=200 * (unit + 1),
                    consume_us=1500) if usage else {}
        spans.append(_record(root, ident, 0, unit, start,
                             at + dur - start, complete=1, **used))
        at += dur
    return spans


# reader, arguments after n, what it reads of the last four units with
# the root before them (unit 0) in the ring, and of all five without
READERS = {
    "unattributed": (program_units.unattributed_ms_per_unit, (),
                     (70 - 4 * 5) / 4, (80 - 5 * 5) / 5),
    "collector": (program_units.collector_ms_per_unit, (),
                  (1 + 6) / 4 + 0.2, (1 + 6) / 5 + 0.2),
    "cpu": (program_units.arg_per_unit, ("cpu_us", True),
            (5500 - 1000) / 4, (5500 - 1000) / 4),
    "consume": (program_units.arg_per_unit, ("consume_us", False),
                1500, 1500),
    "worst_by_duration": (program_units.worst_over_median, (False,),
                          40 / 10, 40 / 10),
    "worst_by_closes": (program_units.worst_over_median, (True,),
                        40 / 10, 40 / 10),
    "second_worst": (program_units.worst_over_median, (False, 1),
                     10 / 10, 10 / 10),
}


@pytest.mark.parametrize("name", sorted(READERS))
def test_unit_readers_on_hand_made_spans(name):
    reader, args, with_before, without = READERS[name]
    spans = _hand_made()
    assert reader(spans, 0, "train.step", 4, *args) == pytest.approx(
        with_before)
    assert reader(spans, 0, "train.step", 5, *args) == pytest.approx(
        without)
    # as the stage reader: too few units, another root, two runs' units
    assert reader(spans, 0, "train.step", 6, *args) is None
    assert reader(spans, 0, "pass.batch", 2, *args) is None
    assert reader(spans, 0, "train.step", 0, *args) is None
    # the ring dropped spans: fine while the oldest kept is not a
    # chosen root's; silent once it is
    assert reader(spans, 9, "train.step", 4, *args) == pytest.approx(
        with_before)
    assert reader(spans[3:], 9, "train.step", 4, *args) is None
    assert reader(spans, 9, "train.step", 5, *args) is None
    # a program without the roots' integers: the readers that need them
    bare = _hand_made(usage=False)
    got = reader(bare, 0, "train.step", 4, *args)
    if name == "unattributed" or "worst" in name:
        assert got == pytest.approx(with_before)
    else:
        assert got is None


def test_uncovered_reader_on_hand_made_overlapping_roots():
    """A pass's roots: each opens 6 ms before the one before closes."""
    spans = _hand_made("pass.batch", overlap_ms=6.0)
    read = program_units.uncovered_ms_per_unit
    # units 1-4, from unit 1's start (4 ms) to unit 4's end (80): in
    # there unit 0's wait (4-7), every later child (4 x 5) and the
    # consumers of units 0-4 (5 x 1.5)
    assert read(spans, 0, "pass.batch", 4) == pytest.approx(
        (76 - 3 - 20 - 7.5) / 4)
    assert read(spans, 0, "pass.batch", 5) == pytest.approx(
        (80 - 25 - 7.5) / 5)
    assert read(spans[3:], 9, "pass.batch", 5) is None
    assert read(_hand_made("pass.batch", 6.0, usage=False), 0, "pass.batch",
                4) is None
    # batch periods by the closes: 10, 10, 40, 10 after unit 0's close
    assert program_units.worst_over_median(
        spans, 0, "pass.batch", 4, True) == pytest.approx(4.0)
    assert program_units.worst_over_median(
        spans, 0, "pass.batch", 4, False) == pytest.approx(46 / 16)
    # the second longest: of 40, 10 on a median of 25; of one, none
    assert program_units.worst_over_median(
        spans, 0, "pass.batch", 2, True) == pytest.approx(40 / 25)
    assert program_units.worst_over_median(
        spans, 0, "pass.batch", 2, True, 1) == pytest.approx(10 / 25)
    assert program_units.worst_over_median(
        spans, 0, "pass.batch", 1, True, 1) is None
    rows = profiling.unit_accounts(spans, "pass.batch")
    assert [round(r["ms"]) for r in rows] == [10, 10, 10, 40, 10]
    assert rows[3]["passes"] == [(2, 0, 6.0)]
    assert rows[3]["cpu_ms"] == 1.5 and rows[0]["cpu_ms"] is None
    (line,) = profiling.slow_unit_lines(rows, "batch")
    assert line == ("slow batch 3: 40.0 ms for a median of 10.0 | "
                    "unattributed +30.0 ms | gc.pass generation 2 on "
                    "another thread 6.0 ms | cpu 1.5 ms")
    # at a lower factor none more; passes of one kind on one thread
    # share an entry
    assert profiling.slow_unit_lines(rows, "batch", 1.1) == [line]
    rows[3]["passes"] += [(1, 1, 1.5), (1, 1, 2.0)]
    (line,) = profiling.slow_unit_lines(rows, "batch")
    assert line.endswith("| 2 x gc.pass generation 1 on this thread 3.5 ms "
                         "| gc.pass generation 2 on another thread 6.0 ms "
                         "| cpu 1.5 ms")


class SleepyLoader(Loader):
    """The loader of the train tests; its ``slow``-th batch comes three
    seconds late."""

    def __init__(self, n, slow):
        super().__init__(n)
        self.slow = slow

    def __iter__(self):
        for k, batch in enumerate(super().__iter__(), 1):
            if k == self.slow:
                time.sleep(3.0)
            yield batch


def test_train_prints_a_slow_step_and_writes_the_worst(tmp_path, timer,
                                                       capsys):
    """Nine steps flushed every four; the seventh batch comes three
    seconds late, and step 6's root is the one that asks for it (two
    deep: root n fetches batch n+1): the second flush (steps 4-7) prints
    it. Step 6 runs on the device while the loader sleeps, so the root
    is the sleep long, not the sleep and a step: three seconds keep it
    over three times the median step on a loaded machine."""
    _train_tiny(tmp_path, SleepyLoader(9, slow=7), num_steps=9, sum_freq=4)
    assert _hooks() == []      # train() returned: its hook went with it
    lines = [json.loads(line)
             for line in open(tmp_path / "logs" / "scalars.jsonl")]
    assert [line["step"] for line in lines] == [4, 8]
    for line in lines:
        for key in ("host/unattributed_ms", "host/collector_ms",
                    "host/cpu_ms", "host/worst_step_ms"):
            assert line[key] >= 0, key
    assert lines[1]["host/worst_step_ms"] > 3000
    assert lines[1]["host/cpu_ms"] < 500
    slow = [line for line in capsys.readouterr().out.splitlines()
            if line.startswith("slow step ")]
    assert any(line.startswith("slow step 6: ") and "loader_wait +" in line
               for line in slow), slow
    # the step that compiled collected and froze inside its root: a full
    # pass under it, on this thread
    first = by_name(timer.spans(), "train.step")[0]
    assert any(s.parent == first.id and s.args["generation"] == 2
               and s.args["main"] == 1
               for s in by_name(timer.spans(), "gc.pass"))


NEW_METRICS = (
    "unattributed_ms_per_step", "collector_ms_per_step",
    "host_cpu_ms_per_step", "worst_step_over_median",
    "uncovered_ms_per_batch.pass", "consume_ms_per_batch.pass",
    "collector_ms_per_batch.pass", "host_cpu_ms_per_batch.pass",
    "worst_batch_over_median.pass", "second_worst_step_over_median",
    "second_worst_batch_over_median.pass")


@pytest.mark.parametrize("name", NEW_METRICS)
def test_unit_metric_files(name, timer):
    from benchmark import harness

    manifest = harness.read_json(harness.ROOT, "BENCHMARK.json")
    spec = harness.read_json(harness.BENCH_DIR, "metrics", name + ".json")
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
    assert {k: spec[k] for k in entry} == entry
    assert entry["source"] == "program_span"
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert set(spec["workloads"]) <= set(cells)
    train = name.endswith(("_step", "step_over_median"))
    assert all(cells[w]["traffic"].endswith("_train") == train
               for w in spec["workloads"])
    assert len(spec["workloads"]) == (4 if train else 2)
    assert (entry["layer"], entry["moves"]) == (
        ("train loop", "samples_per_s") if train
        else ("dataset pass and predictor", "pairs_per_s"))
    module, _, func = spec["reader"].partition(":")
    assert module == "program_units"
    reader = getattr(program_units, func)
    # it reads the process timer: nothing there, nothing read
    units = spec["args"]["units"]
    assert reader({"run": {units: 3}}, **spec["args"]) is None
    assert reader({"run": {}}, **spec["args"]) is None
    # and a window of three made by hand
    for unit in range(4):
        root = timer.span(spec["args"]["root"], unit=unit, complete=1,
                          consume_us=0)
        with timer.span("x.stage"):
            pass
        timer.close_root(root)
    assert reader({"run": {units: 3}}, **spec["args"]) >= 0


# ----------------------------------------------------------- named scopes

def _op_names(lowered):
    return set(re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True)))


def _holds(names, scope):
    return any(re.search(rf"(^|[/(]){re.escape(scope)}($|[/)])", n)
               for n in names)


MODEL_SCOPES = ("corr_build", "corr_lookup", "coords", "upsample")


def test_forward_names_its_stages(predictor):
    image = jax.ShapeDtypeStruct((BS, 32, 48, 3), jnp.float32)
    names = _op_names(predictor._fn(image.shape, False, "float32").lower(
        predictor.variables, image, image, None))
    for scope in MODEL_SCOPES + ("RAFT/fnet", "RAFT/cnet", "update_block"):
        assert _holds(names, scope), scope


def test_train_step_names_its_stages():
    from raft_tpu.parallel import create_train_state, make_train_step

    tcfg = TrainConfig(name="t", num_steps=4, batch_size=1,
                       image_size=(TH, TW), iters=2)
    model = RAFT(RAFTConfig(small=True, iters=2))
    state = create_train_state(jax.random.PRNGKey(0), model, tcfg, (TH, TW))
    batch = {"image1": jnp.zeros((1, TH, TW, 3)),
             "image2": jnp.zeros((1, TH, TW, 3)),
             "flow": jnp.zeros((1, TH, TW, 2)),
             "valid": jnp.ones((1, TH, TW))}
    names = _op_names(make_train_step(tcfg).lower(
        state, batch, jax.random.PRNGKey(1)))
    for scope in MODEL_SCOPES + ("sequence_loss", "grad_clip",
                                 "optimizer_update", "fnet", "cnet",
                                 "update_block"):
        assert _holds(names, scope), scope
    # the backward pass keeps the forward's names
    assert any("transpose(jvp(RAFT))/corr_build" in n for n in names)
    assert any("transpose(jvp(sequence_loss))" in n for n in names)
