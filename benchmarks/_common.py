"""Shared helpers for the standalone benchmark scripts."""

import os
import time


def timeit(fn, args, min_window=0.5):
    """ms-accurate adaptive timing: drain the queue, grow the window to
    >= ``min_window`` seconds, end every window on a real D2H readback
    (``utils.profiler.sync`` — same discipline as bench.py)."""
    from pytorch_multiprocessing_distributed_tpu.utils.profiler import sync

    out = fn(*args)
    sync(out)  # compile + drain
    n = 2
    while True:
        sync(fn(*args))  # drain boundary
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*args)
        sync(out)
        dt = time.perf_counter() - t0
        if dt >= min_window or n >= 10_000:
            return dt / n
        n = min(10_000, max(n + 1, int(n * 1.3 * min_window / dt)))


def enable_compile_cache() -> None:
    """Persistent XLA executable cache, where ``utils.compile_cache``
    places it (``JAX_COMPILATION_CACHE_DIR``, else the checkout's
    ``.jax_cache``): the first script pays each TPU compile once and
    every later harness invocation reuses it. The platform itself is
    jax's own business (``JAX_PLATFORMS``)."""
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from pytorch_multiprocessing_distributed_tpu.utils.compile_cache import (
        enable_compilation_cache)

    enable_compilation_cache()
