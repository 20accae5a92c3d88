"""graftscope: structured tracing, percentile telemetry plumbing, and a
flight recorder for serving + training.

The stack's only operational signals used to be run-total averages
(``utils.metrics``) and the raw XLA profiler (``utils.profiler``) —
no per-request timelines, no per-phase attribution, and when something
died the only artifact was a stack trace. This module is the
observability sibling of graftlint/graftcheck/graftfault: a
**zero-host-sync structured event bus**. Spans and instant events carry
monotonic host timestamps and are emitted ONLY at boundaries where the
host already synchronizes (horizon drain, admission, checkpoint,
retry/quarantine, windowed metric fetch) — instrumentation never adds
a device round-trip, a compile, or a transfer to any hot path (the
transfer/recompile sentinels pin this with the scope ARMED).

Arming discipline is ``runtime.faults``'s: one module global. Disarmed,
every emit helper is a single global read + ``is None`` check —
:func:`emit` returns immediately, :func:`span` hands back a shared
no-op context manager. No allocation, no clock read, nothing.

**What reaches the profiler.** A second module global, the
*annotator* (:func:`set_annotator`: a callable ``name -> context
manager``), puts :func:`span` on the profiler's clock.
``utils/profiler.py`` sets it to ``jax.profiler.TraceAnnotation`` when
it is imported, which the serving engine and the trainer modules do.
From then on every ``span()`` — armed or not — is entered as an
annotation named :data:`ANNOTATION_PREFIX` + the span's name
(``perf:decode.readback``) for exactly its duration, so a profiler
trace shows the program's spans beside the device operations and
``perf/trace_reduce.py`` can lay each idle gap of the device at one of
them. Attributes stay on the :class:`Event`; the annotation carries the
name alone. Outside a profiler session a ``TraceAnnotation`` is a flag
check: nothing is recorded, and that is what "off" means — no arming
call is needed to trace. Instants (:func:`emit`) and retroactive spans
(:func:`emit_span`) are NOT annotated: an annotation has to be open
while the work runs. Without an annotator (this module imported
alone) the disarmed ``span()`` still returns the shared no-op.

Pieces:

- :class:`Event` / :class:`Scope` — the bus. A ``Scope`` keeps the
  full event log (``keep=True``, the export mode the CLIs arm) and
  ALWAYS keeps a bounded ring of the most recent events — the
  **flight recorder**. On an engine-fatal error
  (``PoolPoisonedError``, a watchdog fail-fast, an unhandled exception
  in ``serve()``/the trainer loop) the ring is dumped to disk
  (:func:`flight_dump`), so the postmortem starts with the last
  seconds of truth instead of a bare traceback.
- :func:`emit` / :func:`span` / :func:`emit_span` — module-level
  emission against the armed scope. ``span`` is a context manager
  (Chrome-trace "X" complete event, duration measured here on the
  host); ``emit_span`` records a span RETROACTIVELY from a duration
  the caller already measured (the trainer's data-wait meter).
  Attribution convention for transports (graftlink): ``wire.rpc``
  spans carry the stream id (``sid``), lane name, and the lane's
  queue depth at submit, and the router's ``route.splice`` instants
  carry per-transfer ``handoff_s``/``resident``/``nbytes`` — a slow
  disaggregated handoff is attributable to queueing vs transfer from
  the trace alone.
- Exporters: :func:`to_chrome_trace` / :func:`write_chrome_trace`
  (Perfetto/``chrome://tracing``-loadable JSON, sits next to the XLA
  trace from ``utils.profiler.trace``), :func:`write_jsonl` /
  :func:`events_from_jsonl` (the event log the timeline plot reads),
  and :func:`prometheus_text` + :func:`start_stats_server` (text
  exposition over stdlib ``http.server`` — ``serve_lm.py
  --stats_port``; no new dependencies).

Timestamps are ``time.perf_counter`` seconds — the same clock every
``Request`` lifecycle stamp and engine meter already uses, so scope
events and ``ServingMetrics`` percentiles line up exactly. A ``Scope``
also notes an ``anchor``, one ``(perf_counter(), time.time_ns())``
pair read at its construction: the profiler stamps its host events on
the wall clock in nanoseconds, so :func:`to_chrome_trace` given the
anchor (``--trace_out`` passes it) writes the events where the XLA
trace has them, and a flight dump's header carries it.

**The collector's pauses** (:func:`host_pauses`). One ``gc.callbacks``
hook, installed when this module is imported, times every collection
of Python's cyclic garbage collector with a ``perf_counter`` pair and
keeps process-wide totals; with an annotator set it opens
``perf:host.gc`` for the collection's length, on whichever thread ran
it, and with a scope armed it files a ``host.gc`` Event (cat
``"host"``, attrs ``generation`` and ``collected``). A collection
starts at any allocation, so it may start inside :meth:`Scope.record`
with the scope's lock held on the same thread: the hook takes no lock,
leaves the Event's fields in a lock-free pending deque, and the next
``record`` (or read of the log) files them. It never raises.

Env hook: ``PMDT_SCOPE=1`` (or ``PMDT_SCOPE=/path/for/flight.jsonl``)
arms a scope at import for chaos drills on a live CLI, the same shape
as ``PMDT_FAULT_PLAN``.

This module is stdlib-only (no jax, no numpy): it must be importable
from the fault layer and the schedulers without dragging a runtime in.
"""

from __future__ import annotations

import gc
import itertools
import json
import os
import sys
import threading
import time
from collections import deque
from typing import (Callable, ContextManager, Deque, Dict, List, Optional,
                    Sequence, Tuple)

__all__ = [
    "Event", "Scope", "arm", "disarm", "active_scope", "scoped",
    "set_identity", "get_identity",
    "ANNOTATION_PREFIX", "set_annotator", "host_pauses",
    "emit", "span", "emit_span", "flight_dump",
    "to_chrome_trace", "write_chrome_trace", "write_jsonl",
    "events_from_jsonl", "prometheus_text", "scope_events_fn",
    "start_stats_server",
    "flight_recorder", "add_cli_args", "arm_from_args",
    "export_from_args",
]


class Event:
    """One structured event: a span (``ph="X"``, has a duration) or an
    instant (``ph="i"``). ``ts`` is ``time.perf_counter`` seconds (the
    span's START for ``X``); ``seq`` is a process-wide monotone — two
    events with equal timestamps still have a total order."""

    __slots__ = ("name", "cat", "ph", "ts", "dur", "tid", "seq", "attrs")

    def __init__(self, name: str, cat: str, ph: str, ts: float,
                 dur: float, tid: int, seq: int, attrs: Dict):
        self.name = name
        self.cat = cat
        self.ph = ph
        self.ts = ts
        self.dur = dur
        self.tid = tid
        self.seq = seq
        self.attrs = attrs

    @property
    def end(self) -> float:
        return self.ts + self.dur

    def to_dict(self) -> Dict:
        d = {"name": self.name, "cat": self.cat, "ph": self.ph,
             "ts": self.ts, "tid": self.tid, "seq": self.seq}
        if self.ph == "X":
            d["dur"] = self.dur
        if self.attrs:
            d.update(self.attrs)
        return d

    def __repr__(self) -> str:
        return (f"Event({self.name!r}, cat={self.cat!r}, ph={self.ph!r}"
                f", ts={self.ts:.6f}, dur={self.dur:.6f}, "
                f"seq={self.seq})")


_SEQ = itertools.count()

# host.gc Events the collector hook could not file itself (it takes no
# lock): (ts, dur, tid, seq, generation, collected), filed by the next
# Scope.record or read of the log
_GC_PENDING: Deque[tuple] = deque(maxlen=4096)
GC_SPAN = "host.gc"

# graftfleet: process-wide identity tags ((host, rank, run_uid) — set
# by runtime.fleet.arm) merged into every RECORDED event's attrs, so a
# fleet collector can lane-split a merged timeline by rank. One module
# global; None (the default) adds nothing anywhere — and the merge
# only runs inside Scope.record, which a disarmed process never
# reaches, so the disarmed hot-path cost contract is untouched.
_IDENTITY: Optional[Dict] = None


def set_identity(identity: Optional[Dict]) -> None:
    """Install (or with None clear) the identity tags every recorded
    event carries from here on. Existing attrs win on collision —
    an event that explicitly names a rank keeps its own."""
    global _IDENTITY
    _IDENTITY = dict(identity) if identity else None


def get_identity() -> Optional[Dict]:
    return dict(_IDENTITY) if _IDENTITY is not None else None


class Scope:
    """An armed event sink.

    Args:
      keep: keep the FULL event log (export mode — the CLIs' choice;
        memory grows with the run). False = ring-only (always-on
        production mode: bounded memory, flight recorder still whole).
      flight_capacity: ring size — how many recent events a fatal
        dump preserves.
      flight_path: where :func:`flight_dump` writes when the caller
        passes no explicit path (None = dumps are skipped unless a
        path is given at dump time).
    """

    def __init__(self, keep: bool = True, flight_capacity: int = 2048,
                 flight_path: Optional[str] = None):
        if flight_capacity < 1:
            raise ValueError(
                f"flight_capacity must be >= 1, got {flight_capacity}")
        self.keep = bool(keep)
        self.flight_path = flight_path
        self.t0 = time.perf_counter()
        # the same instant on the profiler's host clock (wall ns)
        self.anchor: Tuple[float, int] = (self.t0, time.time_ns())
        self.ring: Deque[Event] = deque(maxlen=int(flight_capacity))
        self.log: List[Event] = []
        self.dropped = 0  # events that exist only in (or fell off) the ring
        self._mu = threading.Lock()

    def record(self, event: Event) -> None:
        identity = _IDENTITY
        if identity is not None:
            for key, value in identity.items():
                event.attrs.setdefault(key, value)
        with self._mu:
            self._file_pending()
            self._append(event)

    def _append(self, event: Event) -> None:
        # the caller holds _mu
        if self.keep:
            self.log.append(event)
        elif len(self.ring) == self.ring.maxlen:
            self.dropped += 1  # oldest ring entry evicted for good
        self.ring.append(event)

    def _file_pending(self) -> None:
        """File the collector's pauses the hook left pending, which
        belong to the ARMED scope (the caller holds _mu; a collection
        during this loop only appends to the deque, which the loop
        drains too)."""
        while _GC_PENDING and self is _SCOPE:
            try:
                ts, dur, tid, seq, gen, collected = _GC_PENDING.popleft()
            except IndexError:  # another thread filed it first
                break
            attrs = {"generation": gen, "collected": collected}
            if _IDENTITY is not None:
                attrs.update((k, v) for k, v in _IDENTITY.items()
                             if k not in attrs)
            self._append(Event(GC_SPAN, "host", "X", ts, dur, tid, seq,
                               attrs))

    def events(self) -> List[Event]:
        """Snapshot of the recorded events (full log, or the ring when
        ``keep=False``), in record order."""
        with self._mu:
            self._file_pending()
            return list(self.log) if self.keep else list(self.ring)

    def events_since(self, start: int):
        """Incremental read: ``(events, next_start)`` — the retained
        events whose STREAM index (count of events ever recorded) is
        ``>= start``, plus the cursor to pass next time. A periodic
        consumer (graftfleet's goodput scrape) stays O(new events) per
        call instead of re-copying the whole log. In ring mode events
        older than the ring are gone — a too-old ``start`` yields what
        is left (downstream seq cursors make that a visible
        undercount, never a double count)."""
        with self._mu:
            self._file_pending()
            if self.keep:
                return self.log[start:], len(self.log)
            base = self.dropped
            items = list(self.ring)[max(0, start - base):]
            return items, base + len(self.ring)

    def tail(self) -> List[Event]:
        """The flight-recorder window: the most recent events."""
        with self._mu:
            self._file_pending()
            return list(self.ring)

    def counts(self) -> Dict[str, int]:
        """``{event name: occurrences}`` over :meth:`events`."""
        out: Dict[str, int] = {}
        for ev in self.events():
            out[ev.name] = out.get(ev.name, 0) + 1
        return out


_SCOPE: Optional[Scope] = None


def _file_pending_into_armed() -> None:
    """The collector's pauses still pending belong to the scope armed
    when they happened: file them there before it is swapped out."""
    s = _SCOPE
    if s is not None and _GC_PENDING:
        with s._mu:
            s._file_pending()
    _GC_PENDING.clear()


def arm(scope: Scope) -> Scope:
    global _SCOPE
    _file_pending_into_armed()
    _SCOPE = scope
    return scope


def disarm() -> None:
    global _SCOPE
    _file_pending_into_armed()
    _SCOPE = None


def active_scope() -> Optional[Scope]:
    return _SCOPE


# The prefix perf/trace_reduce.py collects host annotations by
# (perf/spans.py::ANNOTATION_PREFIX; this module imports nothing of the
# benchmark's, so it keeps its own copy and a test holds the two equal).
ANNOTATION_PREFIX = "perf:"

_ANNOTATOR: Optional[Callable[[str], ContextManager]] = None


def set_annotator(
        annotator: Optional[Callable[[str], ContextManager]]) -> None:
    """Install (or with None remove) the callable that opens a
    profiler annotation for a name; see the module docstring."""
    global _ANNOTATOR
    _ANNOTATOR = annotator


# ------------------------------------------------- the collector's pauses

# process-wide totals since import: collections by generation, their
# seconds, the longest one (read through host_pauses())
_gc_collections = [0, 0, 0]
_gc_pause_s = 0.0
_gc_longest_s = 0.0
_gc_t0 = 0.0
_gc_annotation = None
# the last error the hook kept from the collector (None: none yet)
gc_hook_error: Optional[BaseException] = None


def _on_gc(phase: str, info: Dict) -> None:
    """The ``gc.callbacks`` hook (module docstring). Takes no lock,
    never raises, and allocates nothing but the annotation and the
    clock's floats (and, armed, the pending tuple)."""
    global _gc_t0, _gc_annotation, _gc_pause_s, _gc_longest_s
    global gc_hook_error
    try:
        if phase == "start":
            # the clock first: a pause is counted even where the
            # annotation fails
            _gc_t0 = time.perf_counter()
            annotator = _ANNOTATOR
            if annotator is not None:
                _gc_annotation = annotator(ANNOTATION_PREFIX + GC_SPAN)
                _gc_annotation.__enter__()
            return
        dur = time.perf_counter() - _gc_t0
        gen = info["generation"]
        _gc_collections[gen] += 1
        _gc_pause_s += dur
        if dur > _gc_longest_s:
            _gc_longest_s = dur
        annotation, _gc_annotation = _gc_annotation, None
        if annotation is not None:
            annotation.__exit__(None, None, None)
        if _SCOPE is not None:
            _GC_PENDING.append((_gc_t0, dur, threading.get_ident(),
                                next(_SEQ), gen, info["collected"]))
    except Exception as e:  # noqa: BLE001 — a collection must never fail
        gc_hook_error = e


def host_pauses() -> Tuple[int, int, int, float, float]:
    """``(generation-0, -1, -2 collections, pause seconds, longest
    pause seconds)`` of this process's cyclic collector since this
    module was imported: cumulative, so a reader takes deltas
    (``ServingMetrics`` at each engine step)."""
    n0, n1, n2 = _gc_collections
    return n0, n1, n2, _gc_pause_s, _gc_longest_s


def _install_gc_hook() -> None:
    """Once per process: a re-import replaces its own earlier hook."""
    gc.callbacks[:] = [cb for cb in gc.callbacks
                       if not (getattr(cb, "__module__", None) == __name__
                               and getattr(cb, "__name__", None)
                               == "_on_gc")]
    gc.callbacks.append(_on_gc)


_install_gc_hook()


class scoped:
    """``with scoped(Scope()) as s: ...`` — arm for the block, always
    disarm (test/bench hygiene, mirrors ``faults.armed``)."""

    def __init__(self, scope: Optional[Scope] = None):
        self.scope = scope if scope is not None else Scope()

    def __enter__(self) -> Scope:
        return arm(self.scope)

    def __exit__(self, *exc) -> None:
        disarm()


# --------------------------------------------------------------- emission

def emit(name: str, cat: str = "run", **attrs) -> None:
    """Record an instant event. Disarmed cost: one global read + an
    ``is None`` check (the kwargs the CALLER evaluated are discarded —
    keep hot-path attrs to values already at hand; never compute, and
    never sync, to feed an event)."""
    s = _SCOPE
    if s is None:
        return
    s.record(Event(name, cat, "i", time.perf_counter(), 0.0,
                   threading.get_ident(), next(_SEQ), attrs))


def emit_span(name: str, dur: float, cat: str = "run",
              t_start: Optional[float] = None, **attrs) -> None:
    """Record a span RETROACTIVELY from a duration the caller already
    measured (e.g. the trainer's per-batch data-wait): the span ends
    now (or at ``t_start + dur`` when given) and started ``dur``
    seconds earlier."""
    s = _SCOPE
    if s is None:
        return
    ts = (time.perf_counter() - dur) if t_start is None else t_start
    s.record(Event(name, cat, "X", ts, max(0.0, dur),
                   threading.get_ident(), next(_SEQ), attrs))


class _NullSpan:
    """The disarmed ``span()`` result: a shared, allocation-free no-op."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def note(self, **attrs) -> None:
        """No-op twin of :meth:`_LiveSpan.note`."""


_NULL_SPAN = _NullSpan()


class _LiveSpan:
    """A span that records into ``scope`` (when armed), sits inside
    ``annotation`` (when an annotator is set), or both."""

    __slots__ = ("scope", "name", "cat", "attrs", "t_start", "annotation")

    def __init__(self, scope: Optional[Scope], name: str, cat: str,
                 attrs: Dict, annotation=None):
        self.scope = scope
        self.name = name
        self.cat = cat
        self.attrs = attrs
        self.t_start = 0.0
        self.annotation = annotation

    def __enter__(self) -> "_LiveSpan":
        if self.annotation is not None:
            self.annotation.__enter__()
        if self.scope is not None:
            self.t_start = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self.scope is not None:
            now = time.perf_counter()
            if exc_type is not None:
                # a span that died names its killer — the flight
                # recorder's most valuable line
                self.attrs.setdefault("error", exc_type.__name__)
            self.scope.record(Event(
                self.name, self.cat, "X", self.t_start,
                now - self.t_start, threading.get_ident(), next(_SEQ),
                self.attrs))
        if self.annotation is not None:
            self.annotation.__exit__(exc_type, exc, tb)
        return False

    def note(self, **attrs) -> None:
        """Attach attrs discovered mid-span (e.g. tokens realized by a
        drain, known only after the readback)."""
        self.attrs.update(attrs)


def span(name: str, cat: str = "run", **attrs):
    """Context manager recording one complete span (begin at
    ``__enter__``, duration at ``__exit__``), inside a profiler
    annotation ``perf:<name>`` when an annotator is set. Disarmed and
    without an annotator: returns a shared no-op — one global read
    each, no allocation, no clock read."""
    s = _SCOPE
    annotator = _ANNOTATOR
    if annotator is None:
        if s is None:
            return _NULL_SPAN
        return _LiveSpan(s, name, cat, attrs)
    return _LiveSpan(s, name, cat, attrs,
                     annotator(ANNOTATION_PREFIX + name))


# ---------------------------------------------------------- flight recorder

def flight_dump(reason: str, path: Optional[str] = None
                ) -> Optional[str]:
    """Dump the armed scope's ring buffer (the most recent events) as
    JSONL — the crash-grade artifact engine-fatal paths write before
    propagating. First line is a header naming the reason; events
    follow oldest-first. Returns the path written, or None when no
    scope is armed / no path is configured (a disarmed process keeps
    its zero-cost contract even while crashing).

    Best-effort BY CONTRACT: every caller sits on a raise path (an
    engine-fatal error is about to propagate), so a dump failure — a
    typo'd directory, a full disk, an unserializable attr — must
    never replace the real error with its own. It is reported to
    stderr and swallowed; the original exception stays the one the
    process dies with."""
    s = _SCOPE
    if s is None:
        return None
    target = path if path is not None else s.flight_path
    if not target:
        return None
    tail = s.tail()
    before_window = (max(0, len(s.log) - len(tail)) if s.keep
                     else s.dropped)
    header = {"graftscope_flight": reason,
              "events": len(tail),
              "events_before_window": before_window,
              "t0": s.t0,
              "anchor": list(s.anchor),
              "wall_time": time.time()}
    tmp = f"{target}.tmp.{os.getpid()}"
    try:
        with open(tmp, "w") as fh:
            fh.write(json.dumps(header, sort_keys=True) + "\n")
            for ev in tail:
                fh.write(json.dumps(ev.to_dict(), sort_keys=True,
                                    default=repr) + "\n")
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, target)
    except Exception as e:
        # the dump is diagnostics for a crash already in flight —
        # failing to write it must not mask that crash
        print(f"graftscope: flight dump to {target!r} failed "
              f"({type(e).__name__}: {e}); continuing with the "
              "original error", file=sys.stderr)
        return None
    return target


class flight_recorder:
    """``with flight_recorder("serve loop"): ...`` — on ANY exception
    escaping the block, dump the flight ring (named after the block +
    the exception) and re-raise. The graftfault-era loops wrap their
    drive bodies in this so a crash always leaves a timeline behind."""

    def __init__(self, what: str, path: Optional[str] = None):
        self.what = what
        self.path = path

    def __enter__(self) -> "flight_recorder":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is not None and not issubclass(
                exc_type, (GeneratorExit, KeyboardInterrupt, SystemExit)):
            emit("engine.fatal", cat="fault", what=self.what,
                 error=exc_type.__name__)
            flight_dump(f"{self.what}: {exc_type.__name__}: {exc}",
                        self.path)
        return False


# --------------------------------------------------------------- exporters

def to_chrome_trace(events: Sequence[Event],
                    t0: Optional[float] = None,
                    pid: Optional[int] = None,
                    anchor: Optional[Tuple[float, int]] = None) -> Dict:
    """Chrome-trace/Perfetto JSON object from events.

    Timestamps are shifted to start at 0 (``t0`` defaults to the
    earliest event, or the armed/arming scope's ``t0``) and converted
    to microseconds — load the file in ``chrome://tracing`` or
    https://ui.perfetto.dev next to the XLA trace from
    ``utils.profiler.trace``. Given a scope's ``anchor`` instead, they
    are microseconds on the profiler's host clock (the wall clock),
    where the XLA trace has the same instants.
    """
    base_us = 0.0
    if anchor is not None:
        t0, base_us = anchor[0], anchor[1] / 1e3
    elif t0 is None:
        t0 = min((ev.ts for ev in events),
                 default=_SCOPE.t0 if _SCOPE is not None else 0.0)
    if pid is None:
        pid = os.getpid()
    out = []
    for ev in events:
        entry = {
            "name": ev.name,
            "cat": ev.cat,
            "ph": ev.ph,
            "ts": base_us + (ev.ts - t0) * 1e6,
            "pid": pid,
            "tid": ev.tid,
        }
        if ev.ph == "X":
            entry["dur"] = ev.dur * 1e6
        else:
            entry["s"] = "t"  # thread-scoped instant
        if ev.attrs:
            entry["args"] = ev.attrs
        out.append(entry)
    return {"traceEvents": out, "displayTimeUnit": "ms"}


def write_chrome_trace(path: str, events: Sequence[Event],
                       t0: Optional[float] = None,
                       anchor: Optional[Tuple[float, int]] = None) -> str:
    with open(path, "w") as fh:
        json.dump(to_chrome_trace(events, t0, anchor=anchor), fh)
    return path


def write_jsonl(path: str, events: Sequence[Event]) -> str:
    """The raw event log, one JSON object per line (the format
    :func:`events_from_jsonl` and the timeline plot read, and the same
    schema :func:`flight_dump` writes after its header line)."""
    with open(path, "w") as fh:
        for ev in events:
            fh.write(json.dumps(ev.to_dict(), sort_keys=True) + "\n")
    return path


def events_from_jsonl(path: str) -> List[Dict]:
    """Parse a JSONL event log (or a flight dump — header lines
    without a ``name`` field are skipped) into plain dicts."""
    out = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            obj = json.loads(line)
            if "name" in obj and "ph" in obj:
                out.append(obj)
    return out


def _prom_name(key: str, prefix: str) -> str:
    safe = "".join(c if (c.isalnum() or c == "_") else "_"
                   for c in key)
    if safe and safe[0].isdigit():
        safe = "_" + safe
    return f"{prefix}_{safe}"


def prometheus_text(snapshot: Dict, prefix: str = "pmdt_serving"
                    ) -> str:
    """Prometheus text exposition (0.0.4) of a flat metrics snapshot.

    Every numeric value becomes a gauge named
    ``<prefix>_<sanitized key>``; non-numeric values (program lists,
    strings) are skipped — the snapshot stays the one source of truth
    and this stays a dependency-free projection of it."""
    lines = []
    for key in sorted(snapshot):
        value = snapshot[key]
        if isinstance(value, bool) or not isinstance(value,
                                                     (int, float)):
            continue
        name = _prom_name(key, prefix)
        lines.append(f"# TYPE {name} gauge")
        lines.append(f"{name} {float(value):g}")
    return "\n".join(lines) + "\n"


def scope_events_fn(since: int = 0) -> List[Dict]:
    """The standard ``events_fn`` for :func:`start_stats_server`: the
    ARMED scope's retained events from stream index ``since`` as
    ``to_dict`` rows ([] when disarmed). Reading through the module
    global — not a captured Scope — means a re-armed scope (a
    supervised restart) is served live, never a dead incarnation's
    log; the ``since`` cursor keeps periodic scrapes O(new events)."""
    s = _SCOPE
    if s is None:
        return []
    events, _ = s.events_since(max(0, int(since)))
    return [e.to_dict() for e in events]


def start_stats_server(snapshot_fn: Callable[[], Dict], port: int = 0,
                       host: str = "127.0.0.1",
                       prefix: str = "pmdt_serving",
                       health_fn: Optional[Callable[[], Dict]] = None,
                       events_fn: Optional[Callable[[int], List[Dict]]]
                       = None):
    """Serve live telemetry over stdlib ``http.server`` (daemon
    thread): ``/metrics`` is the Prometheus text exposition of
    ``snapshot_fn()``, ``/snapshot.json`` the raw JSON snapshot.
    ``port=0`` binds an ephemeral port — read it back from
    ``server.server_address[1]``. Call ``server.shutdown()`` to stop.

    ``health_fn`` (graftheal) adds ``/healthz``: the JSON payload of
    ``health_fn()`` (``runtime.heal.healthz`` — health-machine state +
    last-beat ages), status **200 only when** ``state == "ready"``,
    503 otherwise — the liveness/readiness probe a replica router
    consumes (a DRAINING engine stops receiving traffic the moment it
    flips, without racing its queue). Without ``health_fn`` the path
    404s like any other.

    ``events_fn`` (graftfleet) adds ``/events.json``: called as
    ``events_fn(since)`` where ``since`` is the stream cursor from
    the optional ``?since=N`` query (0 without one); returns the
    recorded event dicts from that point (``Event.to_dict`` rows —
    the JSONL schema as one JSON array). :func:`scope_events_fn` is
    the standard source (the ARMED scope, re-arms followed live); a
    :class:`~.fleet.FleetCollector` scrapes the full array for the
    merged per-rank timeline, while a periodic consumer passes the
    count it already holds to stay O(new events) per scrape.
    """
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    class Handler(BaseHTTPRequestHandler):
        def do_GET(self):  # noqa: N802 (http.server API)
            code = 200
            try:
                if self.path.startswith("/metrics"):
                    body = prometheus_text(snapshot_fn(), prefix)
                    ctype = "text/plain; version=0.0.4"
                elif self.path.startswith("/snapshot.json"):
                    body = json.dumps(snapshot_fn(), sort_keys=True)
                    ctype = "application/json"
                elif (self.path.startswith("/events.json")
                        and events_fn is not None):
                    since = 0
                    if "?" in self.path:
                        from urllib.parse import parse_qs, urlsplit

                        query = parse_qs(urlsplit(self.path).query)
                        try:
                            since = int(query.get("since", ["0"])[0])
                        except ValueError:
                            since = 0
                    body = json.dumps(events_fn(since), default=repr)
                    ctype = "application/json"
                elif (self.path.startswith("/healthz")
                        and health_fn is not None):
                    payload = health_fn()
                    body = json.dumps(payload, sort_keys=True)
                    ctype = "application/json"
                    if payload.get("state") != "ready":
                        code = 503  # router: stop sending traffic
                else:
                    self.send_error(404)
                    return
            except Exception as e:  # a broken snapshot_fn must surface
                self.send_error(500, f"{type(e).__name__}: {e}")
                return
            data = body.encode("utf-8")
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def log_message(self, *args):  # stats scrapes are not stdout news
            pass

    server = ThreadingHTTPServer((host, port), Handler)
    thread = threading.Thread(target=server.serve_forever, daemon=True,
                              name="pmdt-stats-server")
    thread.start()
    return server


# ------------------------------------------------------------ CLI glue

def add_cli_args(parser, stats_port: bool = False) -> None:
    """The shared graftscope flag set (``serve_lm.py`` /
    ``train_lm.py`` / ``main.py`` all take the same three and all
    opt into ``--stats_port`` — live serving/training gauges plus the
    graftmeter ``hbm_*`` ledger). Any one of them arms a full-log
    scope for the run."""
    g = parser.add_argument_group("graftscope")
    g.add_argument("--trace_out", default="", type=str, metavar="JSON",
                   help="write a Chrome-trace/Perfetto JSON timeline "
                        "of the run (load in chrome://tracing or "
                        "ui.perfetto.dev, beside the XLA trace from "
                        "--profile)")
    g.add_argument("--events_out", default="", type=str,
                   metavar="JSONL",
                   help="write the raw graftscope event log, one JSON "
                        "object per line (the timeline plot's and the "
                        "postmortem tooling's input)")
    g.add_argument("--flight_path", default="", type=str,
                   metavar="JSONL",
                   help="flight-recorder dump destination on fatal "
                        "errors (default: derived from --events_out/"
                        "--trace_out, else graftscope_flight.jsonl)")
    if stats_port:
        g.add_argument("--stats_port", default=0, type=int,
                       help="serve live telemetry over stdlib "
                            "http.server on this port: /metrics is "
                            "the Prometheus text exposition of the "
                            "metrics snapshot, /snapshot.json the "
                            "raw JSON (0 = off)")


def arm_from_args(args) -> Optional[Scope]:
    """Arm a scope when any graftscope flag asks for one (None — and
    zero cost — otherwise). Full-log only when an export artifact
    (``--trace_out``/``--events_out``) will actually consume it;
    ``--stats_port``/``--flight_path`` alone arm ring-only — bounded
    memory on a long-running server, flight recorder still whole."""
    export = args.trace_out or args.events_out
    if not (export or args.flight_path
            or getattr(args, "stats_port", 0)):
        return None
    flight = args.flight_path
    if not flight:
        flight = (os.path.splitext(export)[0] + ".flight.jsonl"
                  if export else "graftscope_flight.jsonl")
    return arm(Scope(keep=bool(export), flight_path=flight))


def export_from_args(args, echo=print) -> None:
    """End-of-run artifact writes for :func:`arm_from_args` CLIs."""
    s = _SCOPE
    if s is None:
        return
    events = s.events()
    if args.trace_out:
        # on the profiler's clock: beside an XLA trace of the same run
        write_chrome_trace(args.trace_out, events, anchor=s.anchor)
        echo(f"graftscope trace: {args.trace_out} "
             f"({len(events)} events)")
    if args.events_out:
        write_jsonl(args.events_out, events)
        echo(f"graftscope events: {args.events_out}")


# env hook: arm a scope for the whole process (live-CLI drills, the
# PMDT_FAULT_PLAN shape). "1"/"on" arms ring-only with the default
# flight path — the ring's ONLY consumer is the crash dump, so a mode
# that could never write one would be pure overhead; any other value
# is the flight-dump path (full log kept for export).
_ENV_SCOPE = os.environ.get("PMDT_SCOPE")
if _ENV_SCOPE:
    if _ENV_SCOPE.lower() in ("1", "on", "true"):
        arm(Scope(keep=False, flight_path="graftscope_flight.jsonl"))
    else:
        arm(Scope(keep=True, flight_path=_ENV_SCOPE))
