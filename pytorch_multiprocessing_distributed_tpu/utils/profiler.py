"""Tracing / profiling hooks (SURVEY.md §5 "Tracing / profiling").

The reference's only instrumentation is wall-clock meters
(``main.py:88-89,94,99,117-118``) — which on an async-dispatch runtime
measure nothing unless steps are synchronized. This module provides:

- :func:`trace` — context manager around ``jax.profiler`` emitting a
  TensorBoard-loadable trace (XLA op-level timeline, HBM usage);
- :class:`StepTimer` — step timing whose tick boundary is a REAL
  device-to-host readback, with warmup discard — the same measurement
  discipline as ``bench.py``;
- :func:`annotate` — named trace regions (``jax.profiler.TraceAnnotation``)
  so host-side phases (data, H2D, step) are visible in the timeline.
"""

from __future__ import annotations

import contextlib
import time
from typing import List, Optional

import jax
import numpy as np


def sync(step_output) -> None:
    """Force completion of ``step_output``'s computation, for real.

    A timing boundary must end in work the device cannot defer: a
    device->host transfer of one leaf (``np.asarray``) returns only
    once the program that produced it has run, whatever a backend's
    ``block_until_ready`` does. It is cheap, so every timing boundary
    in the framework goes through here.
    """
    jax.block_until_ready(step_output)
    leaves = jax.tree.leaves(step_output)
    if leaves:
        # Transfer the smallest leaf (a scalar metric in every trainer
        # path): completion of one output of a program implies the whole
        # program ran, and a scalar keeps the D2H cost fixed and small
        # instead of shipping parameters to host.
        np.asarray(min(leaves, key=lambda l: getattr(l, "size", 1)))


@contextlib.contextmanager
def trace(logdir: str, *, host_tracer_level: int = 2):
    """Capture a profiler trace for the enclosed region into ``logdir``."""
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = host_tracer_level
    jax.profiler.start_trace(
        logdir, create_perfetto_link=False, profiler_options=options
    )
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str):
    """Named region visible in the profiler timeline."""
    return jax.profiler.TraceAnnotation(name)


class StepTimer:
    """Measures per-step wall time honestly under async dispatch.

    Call :meth:`tick` with the step's output (any pytree); it blocks on
    the output before reading the clock. The first ``warmup`` ticks
    (compilation, autotuning) are recorded separately.
    """

    def __init__(self, warmup: int = 2):
        self.warmup = warmup
        self.times: List[float] = []
        self.warmup_times: List[float] = []
        self._last: Optional[float] = None

    def start(self) -> None:
        self._last = time.perf_counter()

    def tick(self, step_output) -> float:
        sync(step_output)
        now = time.perf_counter()
        if self._last is None:
            self._last = now
            return 0.0
        dt = now - self._last
        self._last = now
        if len(self.warmup_times) < self.warmup:
            self.warmup_times.append(dt)
        else:
            self.times.append(dt)
        return dt

    @property
    def mean(self) -> float:
        return sum(self.times) / len(self.times) if self.times else 0.0

    def images_per_sec(self, batch_size: int) -> float:
        return batch_size / self.mean if self.mean else 0.0
