"""The benchmark's own spans and counters.

Spans are recorded from the harness, around its calls into each layer
of the program (the program's graftscope spans sit inside CLI loops the
harness does not run). Each span is a host-clock interval AND a
``jax.profiler.TraceAnnotation`` named ``perf:<name>``, so in a traced
run the same interval sits on the profiler's clock beside the device
operations, where ``trace_reduce`` labels idle gaps with it.

A ``Recording`` is what the per-layer readers see: span durations,
sample series the drivers copy out of the program's own meters, and
plain counters.
"""

from __future__ import annotations

import contextlib
import time
from collections import defaultdict
from typing import Dict, List

import jax

ANNOTATION_PREFIX = "perf:"


class Recording:
    def __init__(self) -> None:
        # name -> seconds, one entry per span or sample
        self.series: Dict[str, List[float]] = defaultdict(list)
        self.counters: Dict[str, float] = {}
        # filled by a traced run: what trace_reduce made of the trace
        self.trace: dict = {}
        self.enabled = True

    @contextlib.contextmanager
    def span(self, name: str):
        """Time the enclosed call into a layer under ``name``."""
        if not self.enabled:
            yield
            return
        with jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.series[name].append(time.perf_counter() - t0)

    def sample(self, name: str, value: float) -> None:
        self.series[name].append(float(value))

    def count(self, name: str, value: float) -> None:
        self.counters[name] = float(value)

    def add(self, name: str, value: float = 1.0) -> None:
        self.counters[name] = self.counters.get(name, 0.0) + value

    def reset(self) -> None:
        """Drop what warm-up recorded; the window starts clean."""
        self.series.clear()
