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
    return sum(s for label, s in ops.items() if rx.search(label))


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
    elif reducer == "value":
        value = rec.trace.get(args["key"])
    else:
        raise ValueError(f"unknown reducer {reducer!r}")
    if value is None or not math.isfinite(value):
        return None
    return value * float(spec.get("scale", 1.0))
