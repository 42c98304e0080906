"""The reduction from a trace to numbers, on a hand-made trace whose
answers can be worked out by hand (see the fixture's comment)."""

import json
import os

import pytest

from benchmark import trace_reduce as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "synthetic_trace.json")


@pytest.fixture()
def trace():
    with open(FIXTURE) as f:
        return json.load(f)


def test_busy_union_merges_overlaps_and_keeps_holes():
    events = [["a", 0, 10], ["b", 5, 10], ["c", 20, 5], ["d", 25, 5]]
    assert tr.busy_union(events) == [(0, 15), (20, 30)]


def test_idle_gaps_are_the_complement_inside_the_window():
    assert tr.idle_gaps([(0, 15), (20, 30)], 5, 40) == [(15, 20), (30, 40)]
    assert tr.idle_gaps([], 0, 10) == [(0, 10)]


def test_clip_cuts_events_to_the_window(trace):
    events = tr.clip(trace["device_ops"]["/device:TPU:0"], 1000, 11000)
    assert events[0][1:] == [1000, 200]
    assert len(events) == 9


def test_reduce_busy_idle_and_time_by_kernel(trace):
    r = tr.reduce_trace(trace)
    # busy: 200 (clipped tail) + per batch 2500-3300 and 3400-5000 = 2400
    assert r["window_s"] == pytest.approx(10000e-9)
    assert r["busy_s"] == pytest.approx((200 + 2 * 2400) * 1e-9)
    assert r["n_devices"] == 1
    assert r["ops"]["raft_corr_fwd.10"] == pytest.approx(1200e-9)
    assert r["ops"]["raft_step.10"] == pytest.approx(1800e-9)
    assert r["ops"]["fusion.1"] == pytest.approx(1600e-9)
    assert r["ops"]["while.3"] == pytest.approx(200e-9)
    assert sum(r["ops"].values()) == pytest.approx(r["busy_s"])


def test_gap_attribution_by_host_span(trace):
    g = tr.reduce_trace(trace)["gaps"]
    ns = {k: round(v * 1e9) for k, v in g.items()}
    # batch 1: fetch_pad idle 1200-2000 (the clipped tail covers 1000-1200)
    # batch 2: fetch_pad idle 6000-7000, no device work in either span...
    # but batch 1's span holds the tail, so its idle lies after the device
    assert ns["bench.fetch_pad:after_device"] == 800
    assert ns["bench.fetch_pad"] == 1000
    assert ns["bench.predict_batch:before_device"] == 2 * 500
    assert ns["bench.predict_batch:between_ops"] == 2 * 100
    assert ns["bench.predict_batch:after_device"] == 2 * 500
    assert ns["bench.consume"] == 2 * 500
    assert sum(ns.values()) == 10000 - 200 - 2 * 2400


def test_time_outside_any_span_is_named_so():
    trace = {"device_ops": {"/device:TPU:0": [["op", 100, 100]]},
             "host_spans": [["bench.window", 0, 1000],
                            ["bench.consume", 0, 300]]}
    g = tr.reduce_trace(trace)["gaps"]
    assert round(g["outside_benchmark_spans"] * 1e9) == 700
    assert round(g["bench.consume:before_device"] * 1e9) == 100
    assert round(g["bench.consume:after_device"] * 1e9) == 100


def test_two_devices_are_averaged():
    trace = {"device_ops": {"/device:TPU:0": [["op", 0, 400]],
                            "/device:TPU:1": [["op", 0, 200]]},
             "host_spans": [["bench.window", 0, 1000]]}
    r = tr.reduce_trace(trace)
    assert r["busy_s"] == pytest.approx(300e-9)
    assert r["ops"]["op"] == pytest.approx(300e-9)
    assert r["n_devices"] == 2


def test_a_trace_without_window_or_device_is_an_error(trace):
    with pytest.raises(ValueError):
        tr.reduce_trace({"device_ops": trace["device_ops"],
                         "host_spans": []})
    with pytest.raises(ValueError):
        tr.reduce_trace({"device_ops": {},
                         "host_spans": trace["host_spans"]})


def test_top_orders_by_time():
    assert tr.top({"a": 1.0, "b": 3.0, "c": 2.0}, 2) == [["b", 3.0],
                                                         ["c", 2.0]]


def test_nested_events_are_counted_once():
    # a while loop 100..1100 holding two kernels and 100 ns of its own
    events = [["%while.3 = (s32[]) while(...)", 100, 1000],
              ["%raft_step.10 = bf16[8] custom-call(...)", 100, 600],
              ["%raft_corr_fwd.10 = bf16[8] custom-call(...)", 700, 300],
              ["%fusion.1 = f32[8] fusion(...)", 1100, 50]]
    by_name = tr.time_by_name(events)
    assert by_name == {"while.3": 100, "raft_step.10": 600,
                       "raft_corr_fwd.10": 300, "fusion.1": 50}
    assert sum(by_name.values()) == sum(
        e - s for s, e in tr.busy_union(events))


def test_op_name_is_the_instruction_name():
    assert tr.op_name("%raft_step.10 = (bf16[128,7168,128]{2,1,0}) "
                      "custom-call(bf16[1] %x)") == "raft_step.10"
    assert tr.op_name("fusion.1") == "fusion.1"


def test_host_spans_move_onto_the_trace_clock():
    # host clock runs 1000 ns ahead of the trace's and 0.1 % fast
    anchors = [[1990, 2010], [12000, 12020]]        # midpoints 2000, 12010
    markers = [1000.0, 11000.0]                     # the same two programs
    spans = tr.host_to_trace_clock(
        [["bench.window", 2000, 10010], ["bench.consume", 7005, 1001]],
        anchors, markers)
    assert spans[0][1] == pytest.approx(1000) and \
        spans[0][2] == pytest.approx(10000)
    assert spans[1][1] == pytest.approx(6000) and \
        spans[1][2] == pytest.approx(1000)
    assert tr.clock_drift_us(anchors, markers) == pytest.approx(-0.01)
    with pytest.raises(ValueError):
        tr.host_to_trace_clock([], anchors[:1], markers[:1])
