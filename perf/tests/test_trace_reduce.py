"""perf/trace_reduce.py: interval arithmetic on hand-made events, and
the whole reduction on a small trace recorded on the chip
(``perf/tests/data/*.events.json``: ``load_events`` of a real
``.xplane.pb``, cut to a few steps)."""

import glob
import json
import os

import pytest

from perf import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_clip_complement():
    cover = tr.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert cover == [(0, 3), (5, 9)]
    assert tr.length(cover) == 7
    assert tr.clip(cover, 2, 6) == [(2, 3), (5, 6)]
    assert tr.complement(cover, -1, 10) == [(-1, 0), (3, 5), (9, 10)]
    assert tr.overlap((0, 4), (3, 9)) == 1


def test_self_time_takes_nested_children_out():
    ops = [["while", 0.0, 100.0, "while"], ["fusion", 10.0, 30.0, "fusion"],
           ["fusion", 50.0, 20.0, "fusion"], ["copy", 200.0, 5.0, "copy"]]
    assert tr.self_times(ops) == [50.0, 30.0, 20.0, 5.0]


def test_instruction_text_to_label_and_opcode():
    assert tr.op_kind("%fusion.123") == "fusion"
    fusion = ("%convolution_add_fusion.24 = bf16[16,1024,768]{2,1,0:T(8,128)"
              "(2,1)S(1)} fusion(bf16[16,1024,768]{2,1,0} %copy-done.77, "
              "f32[768]{0:T(1024)} %p), kind=kOutput, calls=%fused")
    assert tr.parse_instruction(fusion) == ("convolution_add_fusion",
                                            "fusion")
    kernel = ("%attn.51 = (bf16[192,1024,64]{2,1,0:T(8,128)(2,1)}, bf16[192,"
              "1024,64]{2,1,0}) custom-call(bf16[192,1024,64]{2,1,0} %b.1), "
              'custom_call_target="tpu_custom_call", operand_layout={}')
    assert tr.parse_instruction(kernel) == ("mosaic:attn", "custom-call")
    other = ('%custom-call.315 = f32[768,2304]{1,0} custom-call(f32[192,2304]'
             '{1,0} %s), custom_call_target="ConcatBitcast"')
    assert tr.parse_instruction(other) == ("custom-call", "custom-call")
    start = ("%all-reduce-start.2 = (f32[768]{0}, f32[768]{0}) "
             "all-reduce-start(f32[768]{0} %g), replica_groups={{0,1,2,3}}")
    assert tr.parse_instruction(start) == ("all-reduce-start",
                                           "all-reduce-start")


def _events():
    # one chip, window 0..1000 ns set by the harness spans
    ops = [["convolution_fusion", 100.0, 200.0, "fusion"],
           ["all-reduce-start", 300.0, 10.0, "all-reduce-start"],
           ["convolution_fusion", 320.0, 80.0, "fusion"],
           ["all-reduce-done", 400.0, 100.0, "all-reduce-done"],
           ["mosaic:attn", 700.0, 100.0, "custom-call"]]
    host = [["train.dispatch", 0.0, 90.0], ["train.readback", 90.0, 910.0]]
    return {"devices": {"/device:TPU:0": ops, "/device:TPU:1": []},
            "host": host}


def test_reduce_busy_idle_collectives_and_gap_labels():
    out = tr.reduce(_events(), chips=1)
    assert out["planes"] == 1
    assert out["window_s"] == pytest.approx(1000e-9)
    # busy: 100-300, 300-310, 320-400, 400-500, 700-800
    assert out["busy_s"] == pytest.approx(490e-9)
    # the all-reduce is in flight 300..500; fusion.2 hides 80 ns of it
    assert out["collective_s"] == pytest.approx(200e-9)
    assert out["collective_exposed_s"] == pytest.approx(120e-9)
    ops = dict(out["device_ops"])
    assert ops["convolution_fusion"] == pytest.approx(280e-9)
    assert ops["mosaic:attn"] == pytest.approx(100e-9)
    gaps = dict(out["idle_gaps"])
    # 0-100 idle: 90 under dispatch -> "train.dispatch"; the rest
    # (310-320, 500-700, 800-1000) under the read-back
    assert gaps["train.dispatch"] == pytest.approx(100e-9)
    assert gaps["train.readback"] == pytest.approx(410e-9)
    assert sum(gaps.values()) == pytest.approx(
        out["window_s"] - out["busy_s"])


def test_a_gap_goes_to_the_innermost_span_that_covers_it():
    events = {"devices": {"/device:TPU:0": [["fusion", 0.0, 10.0, "fusion"],
                                            ["fusion", 60.0, 40.0, "fusion"]]},
              "host": [["serve.step", 0.0, 100.0],
                       ["serve.readback", 5.0, 70.0]]}
    assert dict(tr.reduce(events, chips=1)["idle_gaps"]) == {
        "serve.readback": pytest.approx(50e-9)}


def test_reduce_averages_over_the_chips_used():
    events = _events()
    events["devices"]["/device:TPU:1"] = [["fusion", 100.0, 100.0, "fusion"]]
    out = tr.reduce(events, chips=2)
    assert out["planes"] == 2
    assert out["busy_s"] == pytest.approx((490e-9 + 100e-9) / 2)


def test_no_device_plane_reduces_to_nothing():
    assert tr.reduce({"devices": {}, "host": []}, chips=1) == {}


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(DATA, "*.events.json"))) or [None])
def test_recorded_trace(path):
    if path is None:
        pytest.skip("no recorded trace under perf/tests/data")
    with open(path) as f:
        recorded = json.load(f)
    out = tr.reduce(recorded["events"], chips=recorded["chips"])
    want = recorded["expect"]
    assert out["planes"] == recorded["chips"]
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert out["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert out["device_ops"][0][0] == want["top_op"]
    assert [name for name, _s in out["idle_gaps"]][0] == want["top_gap"]
    # self times never add up to more than the busy union
    assert sum(out["ops"].values()) <= out["busy_s"] * (1 + 1e-9)
