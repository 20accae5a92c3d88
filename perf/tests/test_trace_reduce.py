"""perf/trace_reduce.py: interval arithmetic on hand-made events, the
whole reduction on small traces recorded on the chip
(``perf/tests/data/*.events.json``: ``load_events`` of a real
``.xplane.pb``, cut to a few steps), the sweep that labels idle gaps
against the loop it replaced (kept here as the reference), the device's
programs, and the names the program metrics match pinned to the
engine's jitted functions."""

import glob
import json
import os
import random
import re
import statistics

import pytest

from perf import harness, readers
from perf import trace_reduce as tr
from perf.spans import Recording

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
    # self times never add up to more than the busy union, but by what
    # the fixture's own ``expect`` names: the serving tail holds ONE
    # operation the profiler lets start before the conditional it
    # follows has ended, and states that overlap to the nanosecond
    assert (sum(out["ops"].values()) - want.get("self_time_overlap_s", 0.0)
            <= out["busy_s"] * (1 + 1e-9))


# ------------------------------- the sweep against the loop it replaced

def _reduce_by_the_old_loop(events: dict, chips: int, top: int = 10) -> dict:
    """``trace_reduce.reduce`` as it stood before the sweep (PR 37's
    tree, word for word): every host span looked at for every gap. The
    reference the sweep is held to, key for key."""
    planes = sorted(events["devices"].items(),
                    key=lambda kv: -sum(op[2] for op in kv[1]))[:chips]
    planes = [(name, ops) for name, ops in planes if ops]
    if not planes:
        return {}
    host = events["host"]
    if host:
        lo = min(s for _n, s, _d in host)
        hi = max(s + d for _n, s, d in host)
    else:
        lo = min(op[1] for _p, ops in planes for op in ops)
        hi = max(op[1] + op[2] for _p, ops in planes for op in ops)
    window_ns = hi - lo
    busy_ns = coll_ns = exposed_ns = 0.0
    by_label = {}
    gaps_by_span = {}
    for _plane, ops in planes:
        cover = tr.clip(tr.union([(op[1], op[1] + op[2]) for op in ops]),
                        lo, hi)
        busy_ns += tr.length(cover)
        for op, own in zip(ops, tr.self_times(ops)):
            by_label[op[0]] = by_label.get(op[0], 0.0) + own
        coll = tr.clip(tr.union(tr.collective_intervals(ops)), lo, hi)
        coll_ns += tr.length(coll)
        others = tr.union([(op[1], op[1] + op[2]) for op in ops
                           if not tr.COLLECTIVE.match(op[3])])
        exposed_ns += sum(tr.length(tr.complement(others, s, e))
                          for s, e in coll)
        for gap in tr.complement(cover, lo, hi):
            best, best_key = "no harness span", (0.0, 0.0)
            for name, s, d in host:
                key = (tr.overlap(gap, (s, s + d)), -d)
                if key[0] > 0.0 and key > best_key:
                    best, best_key = name, key
            gaps_by_span[best] = (gaps_by_span.get(best, 0.0)
                                  + gap[1] - gap[0])
    n = len(planes)
    ranked = sorted(by_label.items(), key=lambda kv: -kv[1])
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "collective_s": coll_ns / n / 1e9,
        "collective_exposed_s": exposed_ns / n / 1e9,
        "planes": n,
        "ops": {label: ns / n / 1e9 for label, ns in ranked},
        "device_ops": [[label, ns / n / 1e9] for label, ns in ranked[:top]],
        "idle_gaps": [[name, ns / n / 1e9] for name, ns in sorted(
            gaps_by_span.items(), key=lambda kv: -kv[1])[:top]],
    }


def _same_as_the_old_loop(events: dict, chips: int) -> None:
    """Equal, not approximately: the same gaps go to the same spans
    and are added up in the same order. Lists compare their order."""
    want = _reduce_by_the_old_loop(events, chips, top=10 ** 6)
    got = tr.reduce(events, chips, top=10 ** 6)
    assert set(got) - set(want) == {"programs"}
    for key, value in want.items():
        assert got[key] == value, key
    assert list(got["ops"]) == list(want["ops"])


def _random_events(seed: int) -> dict:
    """Nested host spans and device operations on a grid of whole
    nanoseconds, coarse enough that equal overlaps, equal lengths and
    whole duplicates (the three ties of the rule) all occur."""
    rng = random.Random(seed)
    grid = rng.choice((1, 5, 25))
    end = rng.randrange(40, 400) * grid
    host = []

    def nest(lo, hi, depth):
        at = lo
        while at < hi and len(host) < 120:
            start = at + grid * rng.randrange(0, 4)
            stop = min(hi, start + grid * rng.randrange(0, 40))
            if start >= hi:
                break
            host.append([f"span{depth}.{rng.randrange(3)}",
                         float(start), float(stop - start)])
            if rng.random() < 0.2:      # a twin: same interval, any name
                host.append([f"twin{rng.randrange(3)}", float(start),
                             float(stop - start)])
            if depth < 6 and stop - start > 2 * grid and rng.random() < 0.7:
                nest(start, stop, depth + 1)
            at = stop + grid * rng.randrange(0, 3)

    if rng.random() < 0.9:
        nest(0, end, 0)
        rng.shuffle(host)               # recorded thread by thread, unsorted
    devices = {}
    for chip in range(rng.choice((1, 1, 2))):
        ops, at = [], -grid * rng.randrange(0, 10)
        while at < end + 10 * grid:
            dur = grid * rng.randrange(1, 12)
            kind = rng.choice(("fusion", "fusion", "copy", "all-reduce",
                               "all-reduce-start", "all-reduce-done"))
            ops.append([kind, float(at), float(dur), kind])
            if rng.random() < 0.3:      # a child inside, as a while's body
                ops.append(["fusion", float(at), float(dur // 2 or 1),
                            "fusion"])
            at += dur + grid * rng.choice((0, 0, 1, 2, 7, 30))
        devices[f"/device:TPU:{chip}"] = ops
    return {"devices": devices, "host": host}


@pytest.mark.parametrize("block", range(8))
def test_the_sweep_labels_gaps_as_the_old_loop_did_on_random_nests(block):
    for seed in range(block * 40, block * 40 + 40):
        events = _random_events(seed)
        _same_as_the_old_loop(events, chips=len(events["devices"]))


def test_the_sweep_labels_gaps_as_the_old_loop_did_on_hand_made_events():
    _same_as_the_old_loop(_events(), chips=1)
    # three ties: equal overlap and length, twice; the earlier in the
    # list takes the gap, whatever the order of starts
    events = {"devices": {"/device:TPU:0": [["fusion", 0.0, 10.0, "fusion"],
                                            ["fusion", 60.0, 40.0, "fusion"]]},
              "host": [["b", 10.0, 60.0], ["a", 5.0, 60.0],
                       ["c", 5.0, 60.0], ["outer", 0.0, 100.0]]}
    _same_as_the_old_loop(events, chips=1)
    assert tr.reduce(events, chips=1)["idle_gaps"] == [["b", 50e-9]]
    # a gap that no span touches and a zero-length span sits in
    events["host"] = [["late", 95.0, 5.0], ["point", 30.0, 0.0]]
    _same_as_the_old_loop(events, chips=1)
    assert tr.reduce(events, chips=1)["idle_gaps"] == [
        [tr.NO_SPAN, 30e-9]]            # the window is 30..100
    events["host"].append(["early", 0.0, 5.0])
    _same_as_the_old_loop(events, chips=1)
    assert tr.reduce(events, chips=1)["idle_gaps"] == [
        [tr.NO_SPAN, 50e-9]]


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(DATA, "*.events.json"))))
def test_the_sweep_labels_gaps_as_the_old_loop_did_on_recorded_traces(path):
    with open(path) as f:
        recorded = json.load(f)
    _same_as_the_old_loop(recorded["events"], recorded["chips"])


# ------------------------------------------------ the device's programs

def _program_events():
    # two decode programs, one chunk, and an operation outside all three
    ops = [["fusion", 100.0, 50.0, "fusion"],
           ["mosaic:ragged-dot-none", 150.0, 40.0, "custom-call"],
           ["while", 300.0, 200.0, "while"],
           ["mosaic:ragged-dot-none", 310.0, 100.0, "custom-call"],
           ["fusion", 420.0, 30.0, "fusion"],
           ["fusion", 600.0, 60.0, "fusion"],
           ["mosaic:ragged-dot-none", 660.0, 30.0, "custom-call"],
           ["copy", 900.0, 10.0, "copy"]]
    programs = [["jit_paged_horizon_step", 95.0, 100.0],
                ["jit_chunk", 295.0, 210.0],
                ["jit_paged_horizon_step", 598.0, 96.0]]
    return {"devices": {"/device:TPU:0": ops}, "host": [],
            "programs": {"/device:TPU:0": programs}}


def test_a_program_name_loses_its_fingerprint():
    assert tr.program_name("jit_chunk(18047190439146499223)") == "jit_chunk"
    assert tr.program_name("jit__ring_insert_fn(7)") == "jit__ring_insert_fn"
    assert tr.program_name("jit_f(x)(12)") == "jit_f(x)"
    assert tr.program_name("no_fingerprint") == "no_fingerprint"


def test_operations_go_to_the_program_that_holds_them():
    out = tr.reduce(_program_events(), chips=1)
    programs = out["programs"]
    assert list(programs) == ["jit_chunk", "jit_paged_horizon_step"]
    decode, chunk = programs["jit_paged_horizon_step"], programs["jit_chunk"]
    assert (decode["calls"], chunk["calls"]) == (2, 1)
    assert decode["seconds"] == pytest.approx(196e-9)
    assert sorted(decode["call_s"]) == pytest.approx([96e-9, 100e-9])
    assert decode["ops"] == {"fusion": pytest.approx(110e-9),
                             "mosaic:ragged-dot-none": pytest.approx(70e-9)}
    # the while's own time is what its body does not cover
    assert chunk["ops"] == {"while": pytest.approx(70e-9),
                            "mosaic:ragged-dot-none": pytest.approx(100e-9),
                            "fusion": pytest.approx(30e-9)}
    # label by label the programs add up to the whole trace, over the
    # operations that lie inside one (the copy at 900 lies in none)
    for label, seconds in out["ops"].items():
        inside = sum(p["ops"].get(label, 0.0) for p in programs.values())
        assert inside == pytest.approx(0.0 if label == "copy" else seconds)
    # and nothing else of the reduction moved
    _same_as_the_old_loop(_program_events(), chips=1)


def test_a_call_the_trace_cut_in_two_is_no_call_of_the_window():
    events = _program_events()
    # the harness's spans open at 290: the first decode program began
    # before them, as did its operations, which busy_s leaves out too
    events["host"] = [["serve.step", 290.0, 310.0],
                      ["serve.step", 600.0, 100.0]]
    programs = tr.reduce(events, chips=1)["programs"]
    assert programs["jit_paged_horizon_step"]["calls"] == 1
    assert programs["jit_paged_horizon_step"]["ops"] == {
        "fusion": pytest.approx(60e-9),
        "mosaic:ragged-dot-none": pytest.approx(30e-9)}
    assert programs["jit_chunk"]["calls"] == 1
    _same_as_the_old_loop(events, chips=1)


def test_programs_are_a_chips_mean_like_ops():
    events = _program_events()
    events["devices"]["/device:TPU:1"] = events["devices"]["/device:TPU:0"]
    events["programs"]["/device:TPU:1"] = events["programs"]["/device:TPU:0"]
    one, two = tr.reduce(_program_events(), 1), tr.reduce(events, 2)
    for name, p in one["programs"].items():
        q = two["programs"][name]
        assert q["calls"] == p["calls"]
        assert sorted(q["call_s"]) == sorted(2 * p["call_s"])
        assert q["seconds"] == pytest.approx(p["seconds"])
        assert q["ops"] == pytest.approx(p["ops"])


PROGRAM_SPECS = {
    "median": {"reads": "trace", "reducer": "program_call_median",
               "args": {"program": "^jit_paged_horizon_step$"},
               "scale": 1000},
    "inside": {"reads": "trace", "reducer": "program_ops_per_call",
               "args": {"program": "^jit_paged_horizon_step$",
                        "match": "^mosaic:ragged-dot"}},
    "ratio": {"reads": "trace", "reducer": "program_ratio",
              "args": {"field": "calls", "program": "^jit_(chunk|prefill)$",
                       "over": "^jit_paged_horizon_step$"}},
    "share": {"reads": "trace", "reducer": "program_ratio",
              "args": {"field": "seconds",
                       "program": "^(?!jit_paged_horizon_step$)",
                       "over": "", "beside": "^jit_paged_horizon_step$"},
              "scale": 100},
}


def test_the_program_reducers():
    rec = Recording()
    rec.trace = tr.reduce(_program_events(), chips=1)
    read = {k: readers.read(spec, rec) for k, spec in PROGRAM_SPECS.items()}
    assert read["median"] == pytest.approx(98e-6)
    assert read["inside"] == pytest.approx(35e-9)
    assert read["ratio"] == pytest.approx(0.5)
    assert read["share"] == pytest.approx(100 * 210 / 406)
    # the two ratios are one reducer over two fields
    seconds = {**PROGRAM_SPECS["ratio"], "args": {
        **PROGRAM_SPECS["ratio"]["args"], "field": "seconds"}}
    assert readers.read(seconds, rec) == pytest.approx(210 / 196)
    # a program or a label that is not there reads nothing, never 0;
    # no call of it beside calls of the others is a count of 0; a
    # program named as ``beside`` that is not there reads nothing
    for key, arg, want in (("median", "program", None),
                           ("inside", "program", None),
                           ("inside", "match", None),
                           ("ratio", "over", None),
                           ("share", "over", None),
                           ("share", "beside", None),
                           ("ratio", "program", 0.0),
                           ("share", "program", 0.0)):
        spec = dict(PROGRAM_SPECS[key])
        spec["args"] = {**spec["args"], arg: "^jit_nothing_of_the_kind$"}
        assert readers.read(spec, rec) == want, (key, arg)


def test_a_trace_without_a_modules_line_names_no_program():
    events = _program_events()
    del events["programs"]
    for events in (events, _events()):
        rec = Recording()
        rec.trace = tr.reduce(events, chips=1)
        assert rec.trace["programs"] == {}
        for key, spec in PROGRAM_SPECS.items():
            assert readers.read(spec, rec) is None, key
    # nor does a run that took no trace
    for key, spec in PROGRAM_SPECS.items():
        assert readers.read(spec, Recording()) is None, key


def test_the_recorded_serving_tail_names_its_programs():
    with open(os.path.join(DATA, "serve_tail_few_steps.events.json")) as f:
        recorded = json.load(f)
    out = tr.reduce(recorded["events"], chips=1)
    programs = out["programs"]
    assert {"jit_paged_horizon_step", "jit_chunk", "jit_tok0_fn",
            "jit__ring_insert_fn"} <= set(programs)
    assert programs["jit_paged_horizon_step"]["calls"] >= 2
    for label, seconds in out["ops"].items():
        inside = sum(p["ops"].get(label, 0.0) for p in programs.values())
        assert inside <= seconds * (1 + 1e-9)
    # nearly every operation of a serving tail lies inside a program
    assert (sum(s for p in programs.values() for s in p["ops"].values())
            >= 0.98 * sum(out["ops"].values()))
    # inside the decode program: the grouped kernel and the experts'
    decode = programs["jit_paged_horizon_step"]["ops"]
    assert any(k.startswith("mosaic:gqa_paged_decode_attention")
               for k in decode)
    assert decode["mosaic:ragged-dot-none"] > 0
    for key, want in recorded["expect"]["programs"].items():
        assert programs[key]["calls"] == want["calls"]
        assert statistics.median(programs[key]["call_s"]) == pytest.approx(
            want["median_s"])


# ------------------- the names the metric files match, held to the engine

def test_the_program_metrics_match_the_names_the_engine_jits():
    """A rename in ``serving/engine.py`` fails here and not a metric:
    the trace's "XLA Modules" line shows a jitted function as
    ``jit_<__name__>``."""
    from perf.drivers import serve
    from perf.tests import tiny
    from pytorch_multiprocessing_distributed_tpu.serving import (
        ServingEngine)

    family, model, _opts, make = serve.build_engine(
        tiny.tiny_serve_cell(), "cpu")
    engine = make(family.init_params(model, 0))
    names = {"decode": engine._decode.__name__,
             "prefill": engine._prefill_jit.__name__,
             "chunk": engine._chunk_jit.__name__,
             "tok0": engine._tok0_jit.__name__,
             "insert": engine._insert_jit.__name__}
    assert names == {"decode": "paged_horizon_step", "prefill": "prefill",
                     "chunk": "chunk", "tok0": "tok0_fn",
                     "insert": "_paged_insert_fn"}
    assert ServingEngine._ring_insert_fn.__name__ == "_ring_insert_fn"
    on_the_line = {k: "jit_" + v for k, v in names.items()}
    on_the_line["ring_insert"] = "jit__ring_insert_fn"

    def hits(pattern):
        return {k for k, v in on_the_line.items() if re.search(pattern, v)}

    specs = [harness.load_layer_metric(m["name"])
             for m in harness.load_manifest()["per_layer"]]
    specs = [m for m in specs if m["reducer"].startswith("program_")]
    assert len(specs) == 5
    for spec in specs:
        args = spec["args"]
        if "beside" in args:            # "every program but" the decode
            want = set(on_the_line) - {"decode"}
            assert hits(args["beside"]) == {"decode"}, spec["name"]
            assert hits(args["over"]) == set(on_the_line), spec["name"]
        elif spec["name"].startswith("prefill_"):
            want = {"prefill", "chunk"}
            if "over" in args:
                assert hits(args["over"]) == {"decode"}, spec["name"]
        else:
            want = {"decode"}
        assert hits(args["program"]) == want, spec["name"]


def test_a_renamed_decode_program_drops_the_share_and_reads_no_100():
    """The committed metric files over a tail whose decode program goes
    by another name, as after a rename in ``serving/engine.py`` that no
    tier-1 test catches: every program metric is left out of the line;
    none reads 100 % or 0."""
    events = _program_events()
    for call in events["programs"]["/device:TPU:0"]:
        call[0] = call[0].replace("paged_horizon_step", "renamed_step")
    rec = Recording()
    rec.trace = tr.reduce(events, chips=1)
    assert "jit_renamed_step" in rec.trace["programs"]
    specs = [harness.load_layer_metric(m["name"])
             for m in harness.load_manifest()["per_layer"]]
    read = {m["name"]: readers.read(m, rec) for m in specs
            if m["reducer"].startswith("program_")}
    assert read.pop("prefill_program_ms.serve") == pytest.approx(210e-6)
    assert len(read) == 4 and set(read.values()) == {None}
