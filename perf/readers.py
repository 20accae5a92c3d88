"""Per-layer metrics: each is ``perf/layer_metrics/<name>.json``, read
here.

A metric file names what it ``reads`` out of a run's ``Recording`` and
one ``reducer`` out of the fixed set below; ``scale`` turns the result
into the metric's unit (1000 for seconds to ms, 100 for a share to %).
A reader that finds nothing to read returns None and the harness
leaves the metric out of the line.

    reads                     reducer      args
    "series:<name>"           median | mean | max | sum | percentile(q)
    "counter:<name>"          value
    ["counter:a","counter:b"] ratio                      a / b
    "trace"                   busy_share   match: regex over op labels
    "trace"                   per_step     match: regex, or key: a
                                           number of the reduction
                                           (collective_s, ...)
    "trace"                   value        key
    "trace"                   roofline_share
                                           match: regex over op labels;
                                           kernel: the counters
                                           work.<kernel>.ops|bytes;
                                           peak_ops, peak_bytes: keys
                                           of perf/peaks.json
    "trace"                   program_call_median
                                           program: regex over the names
                                           of the device's programs
    "trace"                   program_ops_per_call
                                           program; match: regex over
                                           the op labels INSIDE them
    "trace"                   program_ratio
                                           field: "calls" or "seconds";
                                           program, over: two regexes;
                                           beside (optional): a third

The three ``program_*`` reducers read the reduction's ``programs`` (the
trace's "XLA Modules" line: one entry a jitted function, under the name
it was jitted with): the median seconds of ONE call of the programs
whose name matches; the seconds under matching labels inside them, a
call of them; their ``field`` (calls, or seconds) over that of the
programs matching ``over`` (every program where ``over`` is ""). Where
the trace names no program they read nothing. Where it names some and
none matches ``program``, the first two read nothing and the ratio
reads 0, which is a count (a tail that drew no prefill), not a share of
a peak; where none matches ``over``, or none matches ``beside``, the
ratio reads nothing: a metric that counts "every program but the
decode program" names the decode program under ``beside``, so that a
rename in the engine drops the metric off the line and does not turn
it into 100 %.

``roofline_share`` is the least time the chip could take for the work
over the time it took: max(ops / peak_ops, bytes / peak_bytes_per_s)
over the traced device seconds under ``match``. The work is what the
family's ``kernel_work`` says the mathematics requires over the same
traced steps (the drivers record it); the peaks are the found
device's. It is never clipped: a share over 100 says the work is
counted too high or the time leaves part of it out, and the driver
refuses it.
"""

from __future__ import annotations

import math
import re
from typing import Optional

from . import stats
from .spans import Recording


def _series(rec: Recording, reads: str):
    kind, _, name = reads.partition(":")
    if kind != "series":
        raise ValueError(f"{reads!r} is not a series")
    return rec.series.get(name) or None


def _counter(rec: Recording, reads: str) -> Optional[float]:
    kind, _, name = reads.partition(":")
    if kind != "counter":
        raise ValueError(f"{reads!r} is not a counter")
    return rec.counters.get(name)


def _matching_seconds(rec: Recording, pattern: str) -> Optional[float]:
    ops = rec.trace.get("ops")
    if ops is None:
        return None
    rx = re.compile(pattern)
    matched = [s for label, s in ops.items() if rx.search(label)]
    # a program that runs no such operation has nothing to read: the
    # metric is left out of the line, never reported as 0
    return sum(matched) if matched else None


def _programs(rec: Recording, pattern: str = "") -> list:
    """The entries of the reduction's ``programs`` whose name matches
    (all of them without a pattern)."""
    rx = re.compile(pattern)
    return [p for name, p in rec.trace.get("programs", {}).items()
            if rx.search(name)]


def read(spec: dict, rec: Recording) -> Optional[float]:
    """The metric's value in its own unit, or None."""
    reducer, reads = spec["reducer"], spec["reads"]
    args = spec.get("args", {})
    value: Optional[float]
    if reducer in ("median", "mean", "max", "sum", "percentile"):
        values = _series(rec, reads)
        if not values:
            return None
        if reducer == "median":
            value = stats.median(values)
        elif reducer == "mean":
            value = sum(values) / len(values)
        elif reducer == "max":
            value = max(values)
        elif reducer == "sum":
            value = sum(values)
        else:
            value = stats.supported_percentile(values, float(args["q"]))
    elif reducer == "value" and reads != "trace":
        value = _counter(rec, reads)
    elif reducer == "ratio":
        top, bottom = (_counter(rec, r) for r in reads)
        value = (None if top is None or not bottom else top / bottom)
    elif reducer == "busy_share":
        seconds = _matching_seconds(rec, args["match"])
        busy = rec.trace.get("busy_s")
        value = None if seconds is None or not busy else seconds / busy
    elif reducer == "per_step":
        steps = rec.trace.get("steps")
        seconds = (_matching_seconds(rec, args["match"])
                   if "match" in args else rec.trace.get(args["key"]))
        value = (None if seconds is None or not steps
                 else seconds / steps)
    elif reducer == "roofline_share":
        seconds = _matching_seconds(rec, args["match"])
        work = [rec.counters.get(f"work.{args['kernel']}.{k}")
                for k in ("ops", "bytes")]
        peaks = [rec.counters.get("peak." + args[k])
                 for k in ("peak_ops", "peak_bytes")]
        value = (None if not seconds or None in work or not all(peaks)
                 else max(w / p for w, p in zip(work, peaks)) / seconds)
    elif reducer == "value":
        value = rec.trace.get(args["key"])
    elif reducer == "program_call_median":
        calls = [s for p in _programs(rec, args["program"])
                 for s in p["call_s"]]
        value = stats.median(calls) if calls else None
    elif reducer == "program_ops_per_call":
        mine = _programs(rec, args["program"])
        rx = re.compile(args["match"])
        matched = [s for p in mine for label, s in p["ops"].items()
                   if rx.search(label)]
        value = (sum(matched) / sum(p["calls"] for p in mine)
                 if matched else None)
    elif reducer == "program_ratio":
        top, bottom = (sum(p[args["field"]] for p in _programs(rec, args[k]))
                       for k in ("program", "over"))
        # no call of ``program`` beside calls of ``over`` is a count of
        # 0, not a thing that could not be read; a ``beside`` that is
        # not in the trace is a name that changed
        value = (top / bottom if bottom
                 and _programs(rec, args.get("beside", "")) else None)
    else:
        raise ValueError(f"unknown reducer {reducer!r}")
    if value is None or not math.isfinite(value):
        return None
    return value * float(spec.get("scale", 1.0))
