"""The benchmark's trace reduction, in tier-1.

``perf/tests/test_trace_reduce.py`` (interval arithmetic, the sweep
that labels idle gaps against the loop it replaced, the recorded
traces, the device's programs, and the pin of the program names the
metric files match to the functions ``serving/engine.py`` jits) is run
by hand with ``python -m pytest perf/tests``; its cases are collected
here too, so that a rename in the engine fails tier-1. Beside them, the
rule an idle gap is labelled by, as the program's ``perf:host.gc``
spans rely on it (kept here: the benchmark's own tests are a
``benchmark`` PR's to change).
"""

import pytest

from perf import trace_reduce as tr
# the reduction's own cases, collected under this file's name
from perf.tests.test_trace_reduce import *  # noqa: F401,F403


def test_a_collector_pause_takes_the_gap_it_covers_and_no_other():
    """A collection nested in ``serve.step`` takes a gap it covers whole
    (a tie with the step, which the shorter span wins), and not one that
    the step covers more of."""
    events = {"devices": {"/device:TPU:0": [["fusion", 0.0, 10.0, "fusion"],
                                            ["fusion", 40.0, 20.0, "fusion"],
                                            ["fusion", 90.0, 10.0, "fusion"]]},
              "host": [["serve.step", 0.0, 100.0],
                       ["host.gc", 20.0, 10.0],     # 10 of the gap 10-40
                       ["host.gc", 55.0, 40.0]]}    # all of the gap 60-90
    assert dict(tr.reduce(events, chips=1)["idle_gaps"]) == {
        "serve.step": pytest.approx(30e-9), "host.gc": pytest.approx(30e-9)}


# The two cases below replace the same-named cases of
# ``perf/tests/test_trace_reduce.py`` in tier-1: those count the
# manifest's program metrics, and ``chunk_attn_ms.serve`` (the grouped
# chunk kernel's seconds in a prompt program) and
# ``short_conv_decode_ms.serve`` (the conv kernel's in the decode
# program) are two more. The benchmark's own file takes the count in a
# ``benchmark`` PR.
from perf import harness, readers  # noqa: E402
from perf.spans import Recording  # noqa: E402
from perf.tests.test_trace_reduce import _program_events  # noqa: E402

# the program metrics that read the prompt programs, chunk and prefill
_PROMPT_METRICS = ("prefill_", "chunk_attn_")


def test_the_program_metrics_match_the_names_the_engine_jits():
    """A rename in ``serving/engine.py`` fails here and not a metric:
    the trace's "XLA Modules" line shows a jitted function as
    ``jit_<__name__>``."""
    import re

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
    assert len(specs) == 7
    for spec in specs:
        args = spec["args"]
        if "beside" in args:            # "every program but" the decode
            want = set(on_the_line) - {"decode"}
            assert hits(args["beside"]) == {"decode"}, spec["name"]
            assert hits(args["over"]) == set(on_the_line), spec["name"]
        elif spec["name"].startswith(_PROMPT_METRICS):
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
    none reads 100 % or 0. (The chunk kernel's seconds read nothing
    here: the recorded tail holds no ``mosaic:gqa_chunk_attention``.)"""
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
    assert len(read) == 6 and set(read.values()) == {None}
