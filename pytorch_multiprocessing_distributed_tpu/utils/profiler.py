"""Tracing / profiling hooks (SURVEY.md §5 "Tracing / profiling").

The reference's only instrumentation is wall-clock meters
(``main.py:88-89,94,99,117-118``) — which on an async-dispatch runtime
measure nothing unless steps are synchronized. This module provides:

- :func:`trace` — context manager around ``jax.profiler`` emitting a
  TensorBoard-loadable trace (XLA op-level timeline, HBM usage);
- :func:`sync` — the framework's one timing boundary: a REAL
  device-to-host readback;
- :func:`annotate` — named trace regions (``jax.profiler.TraceAnnotation``)
  so host-side phases are visible in the timeline. Importing this
  module makes it graftscope's annotator: every ``scope.span()`` of the
  program is then a ``perf:<name>`` region of any profiler trace
  (``runtime/scope.py`` says what does and does not reach the trace).
"""

from __future__ import annotations

import contextlib

import jax
import numpy as np

from ..runtime import scope as graftscope


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


graftscope.set_annotator(annotate)
