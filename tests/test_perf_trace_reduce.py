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
