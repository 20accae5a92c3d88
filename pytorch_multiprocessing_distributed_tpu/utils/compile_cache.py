"""Persistent XLA compilation cache, and what the process compiled.

Every jitted program in this framework is traced and compiled once per
process; on TPU a cold ResNet-50/GPT compile costs 20-50 s. JAX can
persist compiled executables keyed by (HLO, platform, flags, cache
path); with the cache on, every re-run of the same program — across
processes — skips straight to execution.

Where it lives is decided from OUTSIDE the program:

* ``JAX_COMPILATION_CACHE_DIR`` set — jax already uses that directory;
  this module then sets no cache directory in code at all;
* unset — the one fixed path ``<checkout>/.jax_cache`` (git-ignored).
  Never ``~/.cache``, a temp name, a pid or a time: the path is part of
  the cache key, so a directory that moves never hits.

jax's own variables cover the rest (``JAX_ENABLE_COMPILATION_CACHE=0``
turns it off). All four CLIs, ``bench.py`` and ``benchmarks/_common``
go through :func:`enable_compilation_cache`.

The reference has no analogue (cuDNN autotune caches live inside the
driver); this is the XLA-native equivalent of "warm starts".
"""

from __future__ import annotations

import os
import re
import weakref
from typing import Optional, Tuple

# <checkout>/.jax_cache: this file is <checkout>/<package>/utils/...
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def jit_cache_size(fn) -> int:
    """Number of distinct programs a ``jax.jit``-wrapped function has
    traced (and hence compiled) so far — the per-function compile
    counter the serving engine's "one decode signature" guarantee is
    asserted against (``tests/test_serving.py``).

    A slot-based continuous-batching engine exists to keep this at 1:
    requests joining and leaving must never change the jitted decode
    step's (shape, dtype, static-arg) signature. Returns -1 when the
    counter is unavailable (not a jitted function, or a jax without
    ``_cache_size``) so callers can skip the assertion rather than
    crash.
    """
    try:
        return int(fn._cache_size())
    except Exception:  # noqa: BLE001  # graftlint: disable=GL111 counter is diagnostic-only; -1 = unavailable
        return -1


# ---- per-function compile-key log ------------------------------------
# jax's trace cache exposes a SIZE (``_cache_size``) but not its keys,
# so "how many programs" is answerable and "WHICH shapes" is not. The
# serving engine's length-bucketed decode needs the latter: its
# acceptance test pins not just "compiles <= len(buckets)" but that the
# compiled set is exactly the buckets the traffic touched. Call sites
# that own a jitted function call :func:`record_jit_key` right after
# each invocation with a descriptive key (e.g. ``("decode", window)``);
# the key is logged iff the trace cache grew during that call.
_jit_keys: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
# fallback for non-weakrefable callables, keyed by id. Each tracked fn
# is also pinned with a STRONG reference deliberately — ids are only
# unique among live objects, so the pin is what stops a recycled id
# from inheriting a dead function's key log / size baseline. The leak
# is bounded by the number of distinct tracked jits (a handful per
# engine) and only exists on jax builds whose jit wrapper refuses
# weakrefs.
_jit_keys_by_id: dict = {}
_jit_pins: list = []


def _key_slot(fn):
    try:
        return _jit_keys.setdefault(fn, [0, []])
    except TypeError:  # fn doesn't support weakrefs
        slot = _jit_keys_by_id.get(id(fn))
        if slot is None:
            slot = _jit_keys_by_id[id(fn)] = [0, []]
            _jit_pins.append(fn)
        return slot


def record_jit_key(fn, key) -> bool:
    """Attribute ``fn``'s newest compiled program(s) to ``key``.

    Call immediately after invoking the jitted ``fn``: if its trace
    cache grew since the previous ``record_jit_key`` call, ``key`` is
    appended to the function's key log (once per growth — an unchanged
    cache size records nothing, so steady-state calls are free).
    Returns True when a (re)trace was detected. With a jax whose
    ``_cache_size`` counter is unavailable, falls back to logging each
    distinct key once (an upper-bound approximation).
    """
    slot = _key_slot(fn)
    size = jit_cache_size(fn)
    if size < 0:
        if key not in slot[1]:
            slot[1].append(key)
            return True
        return False
    if size > slot[0]:
        slot[0] = size
        slot[1].append(key)
        return True
    slot[0] = size
    return False


def jit_cache_keys(fn) -> Tuple:
    """Keys recorded (in first-compile order) for ``fn`` via
    :func:`record_jit_key` — the answer to *which* bucket shapes
    compiled, where :func:`jit_cache_size` only answers how many."""
    return tuple(_key_slot(fn)[1])


def lowered_cost_analysis(fn, *args, **kwargs):
    """AOT-lower and compile a jitted ``fn`` once; returns
    ``(compiled, cost)`` where ``cost`` is XLA's own per-program cost
    dict (``flops`` etc., normalized across 0.4.x's list-shaped return
    by ``utils.compat.cost_analysis_dict``) or None when unavailable.

    The ONE lowering path shared by the benchmark harness
    (``bench.compile_step`` drives its MFU math off the ``flops``
    entry) and the graftcheck auditor (``analysis/programs.py`` reads
    the compiled module's HLO text for GSPMD-inserted collectives) —
    so the program the auditor inspects can never drift from the one
    the bench times. Compiles but never executes; raises whatever
    ``lower``/``compile`` raise (callers own the fallback policy).
    """
    compiled, cost, _memory = lowered_program_analysis(fn, *args,
                                                       **kwargs)
    return compiled, cost


def lowered_program_analysis(fn, *args, **kwargs):
    """The graftmeter extension of :func:`lowered_cost_analysis`:
    ``(compiled, cost, memory)`` where ``memory`` is XLA's own
    compiled-memory breakdown (argument/output/temp/generated-code
    bytes + the donation-aliased overlap, normalized across jax 0.4.x
    shapes by ``utils.compat.memory_analysis_dict``) or None when the
    backend exposes no memory model. Same lowering, same executable —
    the static memory budget in ``analysis/costs.json``, the bench's
    roofline stamp, and the auditor's HLO all read ONE program.

    The compile is a ``compile.lower`` graftscope span (cat
    ``compile``) — the goodput ledger's compile category; host-side
    only, and a no-op when no scope is armed."""
    from ..runtime import scope as graftscope
    from .compat import cost_analysis_dict, memory_analysis_dict

    with graftscope.span("compile.lower", cat="compile",
                         what=getattr(fn, "__name__",
                                      type(fn).__name__)):
        compiled = fn.lower(*args, **kwargs).compile()
    return (compiled, cost_analysis_dict(compiled),
            memory_analysis_dict(compiled))


def program_facts(fn, *args) -> dict:
    """What the compiler put into the program ``fn`` runs on ``args``:
    Mosaic kernels, all-reduces, and XLA's memory model of it — the
    evidence that a step really uses the kernel and really spans the
    mesh. One more lowering of the same program (a persistent-cache
    hit where the cache is on), so callers ask only while a graftscope
    is armed."""
    compiled, _cost, memory = lowered_program_analysis(fn, *args)
    text = compiled.as_text()
    return {
        "tpu_custom_calls": text.count('custom_call_target="tpu_custom_call"'),
        "all_reduces": len(re.findall(r"= \S+ all-reduce(?:-start)?\(",
                                      text)),
        "memory": memory,
    }


def _platform() -> str:
    """The platform jax runs on: pinned by config/env when it is, else
    the backend's own answer (which initializes it — every caller uses
    devices moments later)."""
    import jax

    pinned = (jax.config.jax_platforms
              or os.environ.get("JAX_PLATFORMS", ""))
    if pinned:
        return pinned.split(",")[0].strip().lower()
    return jax.default_backend()


def enable_compilation_cache() -> Optional[str]:
    """Turn on JAX's persistent compilation cache where the module
    docstring says it lives. Returns the directory in use, or None on
    the CPU platform.

    CPU runs skip the cache: XLA:CPU AOT results embed exact host
    machine features, and reloading across processes logs
    feature-mismatch errors warning of SIGILL — while CPU compiles are
    cheap anyway. The cache's purpose is the 20-50 s TPU compiles.

    Safe to call any time before the first compile; idempotent.
    """
    import jax

    if _platform() == "cpu":
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        from jax.experimental.compilation_cache import (
            compilation_cache as cc)

        path = CACHE_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
        # jax memoizes its is-cache-used decision at the FIRST compile
        # of the process; if anything jitted before this call the new
        # dir would be silently ignored. Resetting makes the next
        # compile re-read the config.
        cc.reset_cache()
    # jax's default admission gate (1 s) drops the many 0.1-1 s programs
    # a serving engine compiles per bucket; the sub-ms jits stay out
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    return path


class CompileLog:
    """What this process compiled, from jax's own monitoring events:
    one entry per backend compile (program name, seconds, and whether
    the persistent cache answered it). The CLIs print
    :meth:`summary` when they finish, so a run shows its compile cost
    apart from its run time and whether a warm cache was hit.

    A cache hit is reported INSIDE the compile it answers, so a hit
    event marks the next compile-duration event."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring

        self.programs = []   # [name, seconds, cache_hit] per compile
        self._hit = False
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def close(self) -> None:
        """Stop listening (a supervised restart builds a new log)."""
        import jax.monitoring

        jax.monitoring.unregister_event_listener(self._on_event)
        jax.monitoring.unregister_event_duration_listener(
            self._on_duration)

    def _on_event(self, event, **_):
        if event == self._HIT:
            self._hit = True

    def _on_duration(self, event, seconds, fun_name=None, **_):
        if event == self._COMPILE:
            self.programs.append([str(fun_name), float(seconds),
                                  self._hit])
            self._hit = False

    def summary(self, top: int = 3) -> dict:
        """Totals plus the ``top`` longest compiles (the train step or
        the serving programs — what a warm cache is for)."""
        longest = sorted(self.programs, key=lambda p: -p[1])[:top]
        return {
            "compiles": len(self.programs),
            "compile_s": round(sum(p[1] for p in self.programs), 3),
            "cache_hits": sum(1 for p in self.programs if p[2]),
            "longest": [{"name": n, "seconds": round(s, 3),
                         "cache_hit": h} for n, s, h in longest],
        }
