"""From a profiler trace to numbers: busy and idle time, the operations
that took most of it, collectives, and what the host was doing in each
idle gap.

The profiler's ``.xplane.pb`` is read with ``jax.profiler.ProfileData``
(nothing but jax) into a plain, JSON-serialisable ``events`` dict, and
every number is computed from that dict, so the arithmetic is tested on
a small recorded trace kept under ``perf/tests/data``:

    {"devices":  {"/device:TPU:0": [[label, start_ns, dur_ns, opcode], ...]},
     "host":     [[span_name, start_ns, dur_ns], ...],
     "programs": {"/device:TPU:0": [[name, start_ns, dur_ns], ...]}}

``devices`` holds each chip's "XLA Ops" line: one event per executed
HLO instruction (control flow nests its body's events inside its own),
whose name in the trace is the instruction's whole text. ``label`` is
the instruction's name without its number (``%convolution_add_fusion.24``
-> ``convolution_add_fusion``, so the twelve layers' copies of one
fusion add up under one name), with ``mosaic:`` before it where the
instruction is a Mosaic (Pallas) kernel, a ``custom-call`` whose target
is ``tpu_custom_call``; ``opcode`` is the HLO opcode. ``host`` holds
the harness's own ``perf:<name>`` annotations (``perf/spans.py``), which
the profiler puts on the same clock. ``programs`` holds each chip's
"XLA Modules" line: one event per executed program, a jitted function
under the name it was jitted with (``jit_paged_horizon_step(1804...)``
-> ``jit_paged_horizon_step``: the fingerprint in brackets changes with
the program's text, the name does not), on the clock of the same
plane's operations, which lie inside it. A trace without that line (a
recording made before it was read) has no ``programs`` key.
"""

from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
import shutil
import tempfile
from typing import Dict, List, Optional, Tuple

from .spans import ANNOTATION_PREFIX

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NO_SPAN = "no harness span"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)")
MOSAIC = "mosaic:"
_INSTRUCTION = re.compile(
    r"^%?(?P<name>[^ ]+) = .*? (?P<opcode>[a-z][\w\-]*)\(")
# set by run.py --keep-trace: the raw .xplane.pb is copied there
KEEP_DIR: Optional[str] = None

Interval = Tuple[float, float]


@contextlib.contextmanager
def traced():
    """Profile the enclosed window into a fresh directory under
    ``TMPDIR`` (yielded; ``reduce_dir`` removes it)."""
    import jax

    trace_dir = tempfile.mkdtemp(prefix="perf_trace_")
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 2
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        yield trace_dir
    finally:
        jax.profiler.stop_trace()


def load_events(xplane_path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    events: dict = {"devices": {}, "host": [], "programs": {}}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops, programs = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        label, opcode = parse_instruction(e.name)
                        ops.append([label, float(e.start_ns),
                                    float(e.duration_ns), opcode])
                elif line.name == MODULES_LINE:
                    programs += [[program_name(e.name), float(e.start_ns),
                                  float(e.duration_ns)]
                                 for e in line.events]
            events["devices"][plane.name] = ops
            events["programs"][plane.name] = programs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(ANNOTATION_PREFIX):
                        events["host"].append(
                            [e.name[len(ANNOTATION_PREFIX):],
                             float(e.start_ns), float(e.duration_ns)])
    return events


def reduce_dir(trace_dir: str, chips: int) -> dict:
    """Reduce the one trace under ``trace_dir`` and remove it."""
    try:
        paths = glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
        if not paths:
            return {}
        if KEEP_DIR:
            os.makedirs(KEEP_DIR, exist_ok=True)
            shutil.copy(paths[0], KEEP_DIR)
        return reduce(load_events(paths[0]), chips)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


# ------------------------------------------------------------ intervals

def union(intervals: List[Interval]) -> List[Interval]:
    """Disjoint sorted cover of ``intervals``."""
    out: List[Interval] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def length(intervals: List[Interval]) -> float:
    return sum(end - start for start, end in intervals)


def clip(intervals: List[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def complement(cover: List[Interval], lo: float, hi: float
               ) -> List[Interval]:
    """The gaps of a disjoint sorted ``cover`` inside ``[lo, hi]``."""
    gaps, at = [], lo
    for start, end in clip(cover, lo, hi):
        if start > at:
            gaps.append((at, start))
        at = max(at, end)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def self_times(ops: List[list]) -> List[float]:
    """Each event's duration minus what its nested children cover (a
    ``while`` holds its body's operations inside its own interval)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [op[2] for op in ops]
    stack: List[int] = []
    for i in order:
        start, end = ops[i][1], ops[i][1] + ops[i][2]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= start:
            stack.pop()
        if stack and end <= ops[stack[-1]][1] + ops[stack[-1]][2]:
            own[stack[-1]] -= ops[i][2]
        stack.append(i)
    return [max(0.0, t) for t in own]


# ------------------------------------------------------------ reduction

def op_kind(name: str) -> str:
    """An instruction's name without its instance number:
    ``%fusion.123`` -> ``fusion``."""
    return re.sub(r"[.\-_]?\d+$", "", name.lstrip("%"))


def parse_instruction(text: str) -> Tuple[str, str]:
    """(label, opcode) of one "XLA Ops" event, whose name is the HLO
    instruction's text: ``%attn.51 = (...) custom-call(...),
    custom_call_target="tpu_custom_call", ...``."""
    m = _INSTRUCTION.match(text)
    if m is None:
        return op_kind(text.split(" ")[0]), ""
    label, opcode = op_kind(m.group("name")), m.group("opcode")
    if (opcode == "custom-call"
            and 'custom_call_target="tpu_custom_call"' in text):
        label = MOSAIC + label
    return label, opcode


def program_name(text: str) -> str:
    """An "XLA Modules" event's name without its fingerprint:
    ``jit_chunk(18047190439146499223)`` -> ``jit_chunk``."""
    return re.sub(r"\(\d+\)$", "", text)


def collective_intervals(ops: List[list]) -> List[Interval]:
    """In-flight intervals of collectives: a synchronous one is its own
    event; an asynchronous one runs from its ``-start`` event's start
    to the matching ``-done`` event's end (matched in order per name
    stem)."""
    out: List[Interval] = []
    open_starts: Dict[str, List[float]] = {}
    for _label, start, dur, kind in sorted(ops, key=lambda op: op[1]):
        if not COLLECTIVE.match(kind):
            continue
        if kind.endswith("-start"):
            open_starts.setdefault(kind[:-6], []).append(start)
        elif kind.endswith("-done"):
            begun = open_starts.get(kind[:-5])
            out.append((begun.pop(0) if begun else start, start + dur))
        else:
            out.append((start, start + dur))
    return out


def label_gaps(gaps: List[Interval], host: List[list]) -> List[str]:
    """For each of ``gaps`` (disjoint, sorted by start) the ONE host
    span it goes to, whole: the span that overlaps most of it; of spans
    that overlap it alike the shortest (the innermost of a nest); of
    those the earlier in ``host``; ``NO_SPAN`` where none overlaps it.

    A sweep over gaps and spans, both sorted by start, that keeps the
    spans still open between gaps (as many as the nest is deep), in
    place of every span looked at for every gap."""
    by_start = sorted(range(len(host)), key=lambda i: host[i][1])
    labels: List[str] = []
    open_spans: List[int] = []
    at = 0
    for gap in gaps:
        while at < len(by_start) and host[by_start[at]][1] < gap[1]:
            open_spans.append(by_start[at])
            at += 1
        # a span that ended before this gap ended before every later one
        open_spans = [i for i in open_spans
                      if host[i][1] + host[i][2] > gap[0]]
        best, best_key = NO_SPAN, None
        for i in open_spans:
            name, s, d = host[i]
            key = (overlap(gap, (s, s + d)), -d, -i)
            if key[0] > 0.0 and (best_key is None or key > best_key):
                best, best_key = name, key
        labels.append(best)
    return labels


def add_programs(programs: Dict[str, dict], calls: List[list],
                 ops: List[list], own: List[float]) -> None:
    """One plane's "XLA Modules" events into ``programs``: each call's
    duration under its name, and each operation's self time (``own``)
    under the call whose interval holds the operation's start (one
    program runs on a chip at a time)."""
    calls = sorted(calls, key=lambda call: call[1])
    starts = [call[1] for call in calls]
    for name, _start, dur in calls:
        programs.setdefault(name, {"call_ns": [], "ops": {}})[
            "call_ns"].append(dur)
    for op, own_ns in zip(ops, own):
        k = bisect.bisect_right(starts, op[1]) - 1
        if k >= 0 and op[1] < calls[k][1] + calls[k][2]:
            inside = programs[calls[k][0]]["ops"]
            inside[op[0]] = inside.get(op[0], 0.0) + own_ns


def reduce(events: dict, chips: int, top: int = 10) -> dict:
    """Busy/idle, top operations, collectives, labelled idle gaps and
    the device's programs.

    The window is the span of the harness's annotations (first start to
    last end) where there are any, else of the device events. Per-chip
    numbers are averaged over the ``chips`` device planes with most
    work (a one-chip cell on a four-chip host leaves three planes
    empty).

    ``programs``: for every name on the planes' "XLA Modules" lines its
    ``calls`` and the ``seconds`` inside it (a chip's, like ``ops``:
    the planes' sum over their number, which ``call_s`` alone does not
    carry), each call's own seconds (``call_s``, every plane's; their
    median is a reader's to take), and ``ops``: the self times of the
    operations that start inside one of its calls, by label, so that
    over those operations the programs' sums are the whole trace's
    ``ops``. Where
    the harness annotated the window, the calls that start inside it.
    Empty where the trace has no such line."""
    planes = sorted(events["devices"].items(),
                    key=lambda kv: -sum(op[2] for op in kv[1]))[:chips]
    planes = [(name, ops) for name, ops in planes if ops]
    if not planes:
        return {}
    host = events["host"]
    if host:
        lo = min(s for _n, s, _d in host)
        hi = max(s + d for _n, s, d in host)
    else:
        lo = min(op[1] for _p, ops in planes for op in ops)
        hi = max(op[1] + op[2] for _p, ops in planes for op in ops)
    window_ns = hi - lo
    busy_ns = coll_ns = exposed_ns = 0.0
    by_label: Dict[str, float] = {}
    gaps_by_span: Dict[str, float] = {}
    programs: Dict[str, dict] = {}
    for plane, ops in planes:
        cover = clip(union([(op[1], op[1] + op[2]) for op in ops]), lo, hi)
        busy_ns += length(cover)
        own = self_times(ops)
        for op, own_ns in zip(ops, own):
            by_label[op[0]] = by_label.get(op[0], 0.0) + own_ns
        # a call that began before the harness's first span is the end
        # of a program the trace cut in two: like the operations out
        # there, which ``busy_s`` leaves out, it is no call of the window
        add_programs(programs,
                     [call for call in events.get("programs", {}).get(
                         plane, []) if not host or lo <= call[1] <= hi],
                     ops, own)
        coll = clip(union(collective_intervals(ops)), lo, hi)
        coll_ns += length(coll)
        others = union([(op[1], op[1] + op[2]) for op in ops
                        if not COLLECTIVE.match(op[3])])
        exposed_ns += sum(length(complement(others, s, e))
                          for s, e in coll)
        gaps = complement(cover, lo, hi)
        for gap, span in zip(gaps, label_gaps(gaps, host)):
            gaps_by_span[span] = (gaps_by_span.get(span, 0.0)
                                  + gap[1] - gap[0])
    n = len(planes)
    ranked = sorted(by_label.items(), key=lambda kv: -kv[1])
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "collective_s": coll_ns / n / 1e9,
        "collective_exposed_s": exposed_ns / n / 1e9,
        "planes": n,
        # seconds per operation label, averaged over the chips
        "ops": {label: ns / n / 1e9 for label, ns in ranked},
        "device_ops": [[label, ns / n / 1e9] for label, ns in ranked[:top]],
        "idle_gaps": [[name, ns / n / 1e9] for name, ns in sorted(
            gaps_by_span.items(), key=lambda kv: -kv[1])[:top]],
        # calls and seconds a chip, like ``ops``; a call's own seconds
        "programs": {
            name: {"calls": len(p["call_ns"]) / n,
                   "seconds": sum(p["call_ns"]) / n / 1e9,
                   "call_s": [ns / 1e9 for ns in p["call_ns"]],
                   "ops": {label: ns / n / 1e9
                           for label, ns in p["ops"].items()}}
            for name, p in sorted(programs.items(),
                                  key=lambda kv: -sum(kv[1]["call_ns"]))},
    }
