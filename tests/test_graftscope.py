"""graftscope: the structured event bus, percentile telemetry, the
exporters, and the flight recorder.

What must stay true:

- **zero disarmed cost**: emission helpers reduce to one global read;
  ``span()`` disarmed returns a SHARED no-op object (no allocation);
- **zero armed cost on device paths**: the serving engine's sentinel
  pins (0 compiles / 0 transfers / 0 extra host syncs in steady
  state) hold with a scope ARMED — instrumentation lives strictly at
  boundaries where the host already synchronizes;
- **exact percentiles**: ``PercentileMeter`` agrees with
  ``np.percentile`` to the float, including weighted updates and
  windowed views;
- **honest accounting**: ``decode_tokens`` comes from drained blocks
  (an explicit counter), never re-derived as
  ``tokens_generated - ttft.count`` — the derivation that breaks the
  moment TTFT-family samples decouple from first tokens;
- **loadable artifacts**: the Chrome-trace export carries the schema
  Perfetto requires, the JSONL log round-trips, the Prometheus text
  exposition parses, the stats endpoint serves both live;
- **crash truth**: engine-fatal paths (an injected
  ``PoolPoisonedError`` included) leave the flight ring on disk, with
  the events leading into the failure;
- **the collector named**: every collection of Python's cyclic
  collector is counted (``host_pauses``), annotated ``perf:host.gc``
  and, armed, filed as a ``host.gc`` Event without a lock; the engine
  meters its own step, the caller's time between steps and the pauses
  inside both.
"""

import contextlib
import gc
import importlib.util
import json
import math
import time
import os
import subprocess
import sys
import threading
import urllib.request

import numpy as np
import pytest

from pytorch_multiprocessing_distributed_tpu import models
from pytorch_multiprocessing_distributed_tpu.analysis.sentinels import (
    guard_transfers, recompile_budget)
from pytorch_multiprocessing_distributed_tpu.runtime import (
    scope as graftscope)
from pytorch_multiprocessing_distributed_tpu.runtime.faults import (
    FaultPlan, FaultRule, PoolPoisonedError, armed)
from pytorch_multiprocessing_distributed_tpu.runtime.scope import (
    Event, Scope, events_from_jsonl, prometheus_text, scoped,
    start_stats_server, to_chrome_trace, write_chrome_trace,
    write_jsonl)
from pytorch_multiprocessing_distributed_tpu.serving import (
    DONE, FAILED, ServingEngine, init_params)
from pytorch_multiprocessing_distributed_tpu.utils.meters import (
    AverageMeter, PercentileMeter, exact_percentile)
from pytorch_multiprocessing_distributed_tpu.utils.metrics import (
    ServingMetrics)


@pytest.fixture(autouse=True)
def no_automatic_collections():
    """The collector's hook annotates every collection and files it
    into an armed scope, so a collection that happened to fall inside
    a test would add ``host.gc`` to the logs these tests compare
    whole: automatic collection is held off for each test (an explicit
    ``gc.collect()`` still runs, and the hook with it)."""
    was = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was:
            gc.enable()


def _tiny(**kw):
    return models.GPT(vocab_size=61, max_seq_len=64, hidden_size=32,
                      num_layers=2, num_heads=2, mlp_dim=64,
                      attn_impl="xla", **kw)


class FakeAnnotator:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs
    ``(what, name, thread)`` at every enter and exit."""

    def __init__(self):
        self.log = []

    @contextlib.contextmanager
    def __call__(self, name):
        self.log.append(("enter", name, threading.get_ident()))
        try:
            yield
        finally:
            self.log.append(("exit", name, threading.get_ident()))

    def names(self):
        return [name for what, name, _tid in self.log if what == "enter"]

    def parents(self):
        """``[(name, parent name or None)]`` in enter order; asserts
        the log is properly bracketed on one thread."""
        assert len({tid for _w, _n, tid in self.log}) <= 1
        stack, out = [], []
        for what, name, _tid in self.log:
            if what == "enter":
                out.append((name, stack[-1] if stack else None))
                stack.append(name)
            else:
                assert stack and stack.pop() == name, (name, self.log)
        assert not stack, stack
        return out


@contextlib.contextmanager
def annotator(fn):
    """Swap graftscope's annotator for the block, then put back what
    the imports had set (``utils.profiler.annotate``)."""
    was = graftscope._ANNOTATOR
    graftscope.set_annotator(fn)
    try:
        yield fn
    finally:
        graftscope.set_annotator(was)


# ------------------------------------------------------------ event bus

class TestEventBus:
    def test_disarmed_is_a_shared_noop(self):
        """Disarmed cost contract: emit returns immediately, span()
        hands back the SAME object every time (no allocation), and
        nothing is recorded anywhere."""
        graftscope.disarm()
        assert graftscope.active_scope() is None
        graftscope.emit("never", cat="x", huge=list(range(3)))
        # the engine's import set the profiler annotator; the shared
        # no-op is what span() returns WITHOUT one (scope.py alone)
        with annotator(None):
            s1 = graftscope.span("a")
            s2 = graftscope.span("b", cat="y", k=1)
        assert s1 is s2  # the shared _NULL_SPAN singleton
        assert s1 is graftscope._NULL_SPAN
        with s1 as live:
            live.note(tokens=5)  # no-op twin keeps caller code unconditional
        assert graftscope.flight_dump("nothing armed") is None

    def test_emit_span_ordering_and_nesting(self):
        with scoped() as s:
            graftscope.emit("run.start", cat="run", n=3)
            with graftscope.span("outer", cat="run") as outer:
                graftscope.emit("inner.mark", cat="run")
                with graftscope.span("inner", cat="run"):
                    pass
                outer.note(tokens=7)
            graftscope.emit("run.end")
        assert graftscope.active_scope() is None  # scoped() disarms
        events = s.events()
        names = [e.name for e in events]
        # spans record at EXIT: inner closes before outer
        assert names == ["run.start", "inner.mark", "inner", "outer",
                         "run.end"]
        # seq is a process-wide total order even under equal timestamps
        assert [e.seq for e in events] == sorted(e.seq for e in events)
        outer_ev = events[names.index("outer")]
        inner_ev = events[names.index("inner")]
        mark = events[names.index("inner.mark")]
        # temporal nesting: the outer span contains its children
        assert outer_ev.ts <= inner_ev.ts
        assert inner_ev.end <= outer_ev.end + 1e-9
        assert outer_ev.ts <= mark.ts <= outer_ev.end
        # mid-span note landed before the span closed
        assert outer_ev.attrs["tokens"] == 7
        assert outer_ev.ph == "X" and mark.ph == "i"

    def test_span_records_its_killer(self):
        with scoped() as s:
            with pytest.raises(ValueError):
                with graftscope.span("doomed", cat="run"):
                    raise ValueError("boom")
        (ev,) = s.events()
        assert ev.attrs["error"] == "ValueError"

    def test_emit_span_retroactive(self):
        with scoped() as s:
            graftscope.emit_span("data.wait", 0.25, cat="train", batch=3)
        (ev,) = s.events()
        assert ev.ph == "X"
        assert ev.dur == pytest.approx(0.25)
        assert ev.attrs == {"batch": 3}

    def test_ring_only_scope_bounds_memory(self):
        s = Scope(keep=False, flight_capacity=4)
        with scoped(s):
            for i in range(10):
                graftscope.emit("tick", i=i)
        assert len(s.events()) == 4
        assert [e.attrs["i"] for e in s.tail()] == [6, 7, 8, 9]
        assert s.dropped == 6
        assert s.counts() == {"tick": 4}
        with pytest.raises(ValueError, match="flight_capacity"):
            Scope(flight_capacity=0)

    def test_counts_and_keep_mode(self):
        with scoped() as s:
            for _ in range(3):
                graftscope.emit("a")
            graftscope.emit("b")
        assert s.counts() == {"a": 3, "b": 1}
        assert len(s.events()) == 4  # keep=True: full log


# ------------------------------------------------- profiler annotations

PREFIX = graftscope.ANNOTATION_PREFIX


class TestAnnotator:
    def test_prefix_is_the_one_trace_reduce_collects(self):
        from perf.spans import ANNOTATION_PREFIX

        assert graftscope.ANNOTATION_PREFIX == ANNOTATION_PREFIX == "perf:"

    def test_engine_import_sets_the_profiler_annotator(self):
        import jax

        from pytorch_multiprocessing_distributed_tpu.utils import profiler

        assert graftscope._ANNOTATOR is profiler.annotate
        assert isinstance(profiler.annotate("x"),
                          jax.profiler.TraceAnnotation)
        # the real thing, outside any profiler session: enter, note, exit
        graftscope.disarm()
        with graftscope.span("decode.readback", cat="serving") as live:
            live.note(tokens=1)

    def test_scope_alone_imports_without_jax(self):
        code = (
            "import sys\n"
            "from pytorch_multiprocessing_distributed_tpu.runtime "
            "import scope\n"
            "assert 'jax' not in sys.modules, 'scope.py pulled jax in'\n"
            "assert scope._ANNOTATOR is None\n"
            "assert scope.span('a') is scope.span('b')\n")
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        done = subprocess.run([sys.executable, "-c", code], cwd=root,
                              capture_output=True, text=True, timeout=120)
        assert done.returncode == 0, done.stderr

    def test_disarmed_span_enters_and_leaves_the_annotation(self):
        graftscope.disarm()
        with annotator(FakeAnnotator()) as fake:
            with graftscope.span("outer", cat="run", k=1) as outer:
                outer.note(tokens=3)   # attrs go nowhere, and harmlessly
                with graftscope.span("inner"):
                    pass
        me = threading.get_ident()
        assert fake.log == [
            ("enter", PREFIX + "outer", me), ("enter", PREFIX + "inner", me),
            ("exit", PREFIX + "inner", me), ("exit", PREFIX + "outer", me)]

    def test_armed_span_records_the_event_and_annotates(self):
        with annotator(FakeAnnotator()) as fake, scoped() as s:
            with graftscope.span("work", cat="run", k=1) as live:
                live.note(tokens=3)
        (event,) = s.events()
        assert (event.name, event.ph) == ("work", "X")
        assert event.attrs == {"k": 1, "tokens": 3}
        assert fake.names() == [PREFIX + "work"]
        assert [what for what, _n, _t in fake.log] == ["enter", "exit"]

    def test_instants_and_retroactive_spans_are_not_annotated(self):
        with annotator(FakeAnnotator()) as fake, scoped() as s:
            graftscope.emit("request.submit", cat="request")
            graftscope.emit_span("train.data", 0.01, cat="train")
        assert [e.name for e in s.events()] == ["request.submit",
                                                "train.data"]
        assert fake.log == []

    def test_a_dying_span_closes_its_annotation_and_names_its_killer(self):
        with annotator(FakeAnnotator()) as fake, scoped() as s:
            with pytest.raises(ZeroDivisionError):
                with graftscope.span("doomed"):
                    1 / 0
        assert [what for what, _n, _t in fake.log] == ["enter", "exit"]
        assert s.events()[0].attrs["error"] == "ZeroDivisionError"


# ------------------------------------------------- the collector's pauses

class TestCollectorPauses:
    def test_a_forced_collection_is_counted_with_its_generation(self):
        before = graftscope.host_pauses()
        gc.collect()
        gc.collect(0)
        after = graftscope.host_pauses()
        assert [b - a for a, b in zip(before[:3], after[:3])] == [1, 0, 1]
        paused = after[3] - before[3]
        assert paused > 0.0
        assert after[4] >= before[4] and after[4] > 0.0
        # the hook is in gc.callbacks once, however often it is put in
        graftscope._install_gc_hook()
        graftscope._install_gc_hook()
        assert sum(cb is graftscope._on_gc for cb in gc.callbacks) == 1

    def test_a_collection_is_annotated_on_the_thread_that_ran_it(self):
        with annotator(FakeAnnotator()) as fake:
            gc.collect()
            t = threading.Thread(target=gc.collect)
            t.start()
            t.join()
        name = PREFIX + graftscope.GC_SPAN
        assert name == "perf:host.gc"
        assert [(what, n) for what, n, _t in fake.log] == [
            ("enter", name), ("exit", name)] * 2
        assert fake.log[0][2] == fake.log[1][2] == threading.get_ident()
        assert fake.log[2][2] == fake.log[3][2] == t.ident

    def test_a_collection_inside_record_neither_deadlocks_nor_is_lost(self):
        """A collection starts at an allocation, so it can start inside
        ``Scope.record`` with the scope's lock held on the same thread:
        the hook must take no lock, and the Event must still land."""

        class CollectingLog(list):
            collected = False

            def append(self, item):
                if not CollectingLog.collected:
                    CollectingLog.collected = True
                    gc.collect()          # inside record, _mu held
                super().append(item)

        s = Scope()
        s.log = CollectingLog()
        with scoped(s):
            t = threading.Thread(target=graftscope.emit, args=("first",),
                                 daemon=True)
            t.start()
            t.join(timeout=60)
            assert not t.is_alive(), "the collector hook took the lock"
            graftscope.emit("second")
        names = [e.name for e in s.events()]
        assert names == ["first", "host.gc", "second"]
        (pause,) = [e for e in s.events() if e.name == "host.gc"]
        assert (pause.cat, pause.ph, pause.tid) == ("host", "X", t.ident)
        assert pause.attrs["generation"] == 2
        assert isinstance(pause.attrs["collected"], int)
        assert pause.dur > 0.0
        first = s.events()[0]
        assert first.ts <= pause.ts <= first.ts + first.dur + 1.0

    def test_a_failing_annotator_never_fails_a_collection(self):
        def broken(name):
            raise RuntimeError("no profiler here")

        graftscope.gc_hook_error = None
        before = graftscope.host_pauses()
        with annotator(broken):
            gc.collect()
        assert isinstance(graftscope.gc_hook_error, RuntimeError)
        graftscope.gc_hook_error = None
        # the pause of a collection whose annotation failed still counts
        after = graftscope.host_pauses()
        assert after[2] == before[2] + 1
        assert 0.0 < after[3] - before[3] < 60.0

    def test_disarmed_without_an_annotator_a_collection_records_nothing(
            self):
        graftscope.disarm()
        with annotator(None):
            gc.collect()
            assert graftscope.span("a") is graftscope._NULL_SPAN
        assert not graftscope._GC_PENDING
        # a scope armed later does not inherit pauses from before it
        with scoped() as s:
            graftscope.emit("x")
        assert [e.name for e in s.events()] == ["x"]


# every span one engine step can open, and what it must sit inside
ENGINE_SPANS = {
    "engine.step": None,
    "engine.admit": "engine.step",
    "serving.prefill": "engine.admit",
    "serving.prefill_chunk": "engine.admit",
    "serving.prefill_tok0": "engine.admit",
    "serving.slot_insert": "engine.admit",
    "serving.prefix_hit": "engine.admit",
    "decode.dispatch": "engine.step",
    "pages.table_upload": "decode.dispatch",
    "decode.drain": "engine.step",
    "decode.readback": "decode.drain",
}


class TestEngineSpans:
    def _paged_engine(self):
        model = models.get_model("gpt_tiny", attn_impl="xla")
        params = init_params(model, 3)
        rng = np.random.default_rng(3)
        first = rng.integers(0, model.vocab_size, (19,)).tolist()
        # shares its first two pages with `first`: a partial prefix hit
        second = first[:16] + rng.integers(0, model.vocab_size,
                                           (5,)).tolist()
        engine = ServingEngine(model, params, max_slots=2, s_max=64,
                               min_bucket=8, kv_layout="paged",
                               page_size=8, prefix_cache=4)
        return engine, first, second

    def test_one_paged_run_shows_every_span_properly_nested(self):
        """A miss, then a partial prefix hit two steps later, then the
        first request finishes three steps before the second: every
        span of an engine step is annotated, each inside its parent,
        and the page table is uploaded on exactly the steps that follow
        an admission or a release. The step is pipelined one block
        deep and admission is two stages a step apart: a step that
        dispatches a prefill reads nothing; the NEXT step reads the
        first token, splices, and (the table now changed) uploads; the
        first decoding step dispatches twice, every later one
        dispatches before it drains the OLDER block, and the last step
        only drains."""
        engine, first, second = self._paged_engine()
        with annotator(FakeAnnotator()) as fake, scoped() as s:
            engine.submit(first, 6)
            steps = 0
            while engine.in_flight:
                engine.step()
                steps += 1
                if steps == 2:
                    engine.submit(second, 6)
        assert steps == 9
        parents = fake.parents()          # also: properly bracketed
        assert ({name for name, _p in parents}
                == {PREFIX + name for name in ENGINE_SPANS})
        for name, parent in parents:
            want = ENGINE_SPANS[name[len(PREFIX):]]
            assert parent == (want and PREFIX + want), (name, parent)
        # the same spans, with their attrs, on the armed scope
        spans = [e for e in s.events() if e.ph == "X"]
        assert ([PREFIX + e.name for e in spans]
                == [name for what, name, _t in fake.log if what == "exit"])
        assert all("req" in e.attrs for e in spans
                   if e.name.startswith("serving."))
        dispatch = next(e for e in spans if e.name == "decode.dispatch")
        assert set(dispatch.attrs) == {"window", "horizon", "draft_k",
                                       "overlapped", "occupancy",
                                       "kv_pages_live", "kv_pages_window"}
        # the first dispatch: one slot holds the 19-token prompt (its
        # next write is column 19, the third page of 8), and the grid
        # the old kernel walked was both slots' whole window
        assert dispatch.attrs["kv_pages_live"] == 19 // 8 + 1
        assert (dispatch.attrs["kv_pages_window"]
                == 2 * -(-dispatch.attrs["window"] // 8))
        # one group of events per step; engine.step is recorded last
        groups, group = [], []
        for e in s.events():
            group.append(e)
            if e.name == "engine.step":
                groups.append(group)
                group = []
        uploaded = [any(e.name == "pages.table_upload" for e in g)
                    for g in groups]
        admitted = [next(e for e in g if e.name == "engine.admit")
                    .attrs["admitted"] for g in groups]
        released = [any(e.name == "request.done" for e in g)
                    for g in groups]
        assert admitted == [1, 0, 1, 0, 0, 0, 0, 0, 0]
        assert released == [False] * 5 + [True, False, False, True]
        assert uploaded == [i > 0 and bool(admitted[i - 1]
                                           or released[i - 1])
                            for i in range(steps)]
        assert uploaded == [False, True, False, True, False, False, True,
                            False, False]
        dispatches = [[e.attrs["overlapped"] for e in g
                       if e.name == "decode.dispatch"] for g in groups]
        assert dispatches == [[], [False, True]] + [[True]] * 6 + [[]]

    def test_annotated_steady_state_adds_no_compile_or_transfer(self):
        """With the profiler's annotator set and no scope armed — the
        state every run is in now — a warmed paged engine re-serving
        its mix makes no fresh compile and no transfer beyond the
        expected ones: an annotation is host-only."""
        from pytorch_multiprocessing_distributed_tpu.utils import profiler

        engine, first, second = self._paged_engine()
        work = [(first, 5), (second, 5), (first[:7], 5)]
        engine.serve(work)                # warm every bucket
        compiles = engine.decode_step_compiles
        graftscope.disarm()
        with annotator(profiler.annotate):
            with guard_transfers():
                with recompile_budget(engine._decode, 0,
                                      label="annotated steady state"):
                    finished = engine.serve(work)
        assert all(r.state == DONE for r in finished)
        assert engine.decode_step_compiles == compiles


# ------------------------------------------------------- exact meters

class TestPercentileMeter:
    def test_exact_against_numpy(self):
        rng = np.random.default_rng(0)
        values = rng.lognormal(0.0, 1.5, size=257).tolist()
        m = PercentileMeter()
        for v in values:
            m.update(v)
        for q in (0, 10, 50, 90, 95, 99, 99.9, 100):
            assert m.percentile(q) == pytest.approx(
                float(np.percentile(values, q)), rel=0, abs=1e-12), q
        assert m.avg == pytest.approx(float(np.mean(values)))
        assert m.max == max(values)
        snap = m.percentiles((50, 95, 99))
        assert set(snap) == {"p50", "p95", "p99"}

    def test_weighted_update_matches_population(self):
        """update(v, n) records v n times — the percentile population
        and the inherited weighted average stay consistent."""
        m = PercentileMeter()
        m.update(1.0, 3)
        m.update(5.0, 1)
        assert m.count == 4 and len(m.values) == 4
        assert m.percentile(50) == pytest.approx(
            float(np.percentile([1.0, 1.0, 1.0, 5.0], 50)))
        assert m.avg == pytest.approx(2.0)

    def test_empty_and_single(self):
        m = PercentileMeter()
        assert m.percentile(99) == 0.0 and m.max == 0.0
        m.update(2.5)
        assert m.percentile(1) == 2.5 and m.percentile(99) == 2.5

    def test_exact_percentile_interpolates(self):
        assert exact_percentile([0.0, 10.0], 50) == pytest.approx(5.0)
        assert exact_percentile([0.0, 10.0], 25) == pytest.approx(2.5)

    def test_window_stats(self):
        m = PercentileMeter()
        for v in (10.0, 20.0):
            m.update(v)
        m.advance_window()
        for v in (1.0, 2.0, 3.0):
            m.update(v)
        win = m.window_stats((50,))
        assert win["count"] == 3.0
        assert win["avg"] == pytest.approx(2.0)
        assert win["max"] == 3.0
        assert win["p50"] == pytest.approx(
            float(np.percentile([1.0, 2.0, 3.0], 50)))
        # run-total view still covers everything
        assert m.count == 5
        assert m.percentile(100) == 20.0

    def test_reset_clears_samples(self):
        m = PercentileMeter()
        m.update(3.0)
        m.advance_window()
        m.reset()
        assert m.values == [] and m.window_values() == []
        assert isinstance(m, AverageMeter)  # drop-in contract


# -------------------------------------------------- serving telemetry

class TestServingMetrics:
    def test_snapshot_has_percentiles(self):
        m = ServingMetrics()
        for t in (0.1, 0.2, 0.9):
            m.record_first_token(t)
        m.record_admission(0.05)
        m.record_decode_step(0.01, 4, 2, 0, 16)
        snap = m.snapshot()
        for name in ("ttft", "queue_wait", "decode_step"):
            for q in ("p50", "p90", "p95", "p99"):
                assert f"{name}_{q}_s" in snap
        assert snap["ttft_p99_s"] == pytest.approx(
            float(np.percentile([0.1, 0.2, 0.9], 99)))
        m.record_completion(12)
        snap = m.snapshot()
        assert snap["tokens_per_request_p50"] == 12.0
        assert snap["tokens_per_request_avg"] == 12.0

    def test_decode_tokens_from_drained_blocks(self):
        """Regression (the satellite fix): decode_tokens is the
        explicit drained-block counter. The old derivation
        ``tokens_generated - ttft.count`` silently undercounts the
        moment a TTFT-family sample exists without a first token
        behind it (a request failed before its first token, its
        latency-to-failure recorded)."""
        m = ServingMetrics()
        m.record_first_token(0.05)          # request A: real tok0
        m.record_decode_step(0.01, 4, 1, 0, 16)  # 4 drained tokens
        m.ttft.update(0.5)   # request B: latency to FAILURE, no token
        m.record_failure()
        snap = m.snapshot()
        assert snap["decode_tokens"] == 4
        old_derivation = m.tokens_generated - m.ttft.count
        assert old_derivation == 3  # the silent undercount, pinned
        assert snap["decode_tokens_per_sec"] == pytest.approx(4 / 0.01)

    def test_engine_decode_tokens_exact_under_quarantine(self):
        """Engine-level: with one request quarantined before its first
        token, decode_tokens still equals the survivors' post-first
        tokens exactly."""
        model = _tiny()
        engine = ServingEngine(model, init_params(model, 5),
                               max_slots=2, s_max=32, min_bucket=8,
                               retry_backoff_s=0.0, dispatch_retries=2)
        prompts = [list(range(2, 7)), list(range(3, 9)),
                   list(range(1, 4))]
        plan = FaultPlan([FaultRule("serving.prefill", "error",
                                    times=2)])
        with armed(plan):
            reqs = [engine.submit(p, 4) for p in prompts]
            for _ in engine.run():
                pass
        assert reqs[0].state == FAILED and not reqs[0].tokens
        assert [r.state for r in reqs[1:]] == [DONE, DONE]
        snap = engine.metrics.snapshot()
        survivors = sum(len(r.tokens) for r in reqs[1:])
        assert snap["tokens_generated"] == survivors
        # 1 prefill token each; the rest drained from decode blocks
        assert snap["decode_tokens"] == survivors - 2

    @pytest.mark.parametrize("prefill_chunk", [None, 8])
    def test_the_engine_meters_its_own_loop(self, prefill_chunk):
        """A fresh ``ServingMetrics`` over a few steps of a tiny CPU
        engine, one forced collection between two steps: the loop is
        the steps plus the gaps between them, the collection is in
        the collector's totals, every prompt program dispatched is
        counted, and the five metric files that read these counters
        each give a number from a recording filled the serve driver's
        way (``engine.<key>`` for every numeric snapshot item)."""
        from perf import harness, readers
        from perf.spans import Recording

        model = _tiny()
        engine = ServingEngine(model, init_params(model, 5), max_slots=2,
                               s_max=64, min_bucket=8, page_size=8,
                               prefill_chunk=prefill_chunk)
        rng = np.random.default_rng(1)
        with scoped() as s:
            for n in (11, 19, 6):
                engine.submit(rng.integers(0, 61, (n,)).tolist(), 5)
            engine.metrics = fresh = ServingMetrics()
            engine.step()
            engine.step()
            gc.collect()
            while engine.in_flight:
                engine.step()
        snap = fresh.snapshot()
        assert snap["steps"] >= 3
        assert snap["loop_s"] == pytest.approx(
            snap["step_wall_s"] + snap["step_gap_s"], abs=1e-6)
        assert snap["gc_gen2_collections"] >= 1
        assert snap["gc_collections"] >= snap["gc_gen2_collections"]
        assert 0.0 < snap["gc_pause_s"] <= snap["loop_s"]
        assert snap["step_gap_max_s"] >= snap["gc_pause_max_s"] > 0.0
        assert snap["step_max_s"] >= snap["step_p50_s"] > 0.0
        assert 0.0 <= snap["step_max_gc_s"] <= snap["step_max_s"]
        assert snap["step_max_cpu_s"] >= 0.0
        prompts = [e for e in s.events()
                   if e.name in ("serving.prefill", "serving.prefill_chunk")]
        assert snap["prompt_dispatches"] == len(prompts) >= 3
        assert snap["prompt_dispatches"] == (snap["prefill_dispatches"]
                                             + snap["chunk_dispatches"])
        assert (snap["chunk_dispatches"] > 0) == (prefill_chunk is not None)
        # the deltas are taken at step exits: a snapshot later on does
        # not stretch the loop
        time.sleep(0.01)
        assert fresh.snapshot()["loop_s"] == snap["loop_s"]

        rec = Recording()
        for key, value in snap.items():
            if isinstance(value, (int, float)):
                rec.count(f"engine.{key}", value)
        for name in ("step_ms_p50.serve", "step_ms_max.serve",
                     "gc_pause_share.serve", "between_steps_share.serve",
                     "prompt_dispatches_per_decode.serve"):
            value = readers.read(harness.load_layer_metric(name), rec)
            assert value is not None and math.isfinite(value), name
        assert readers.read(harness.load_layer_metric(
            "step_ms_p50.serve"), rec) == pytest.approx(
                snap["step_p50_s"] * 1000)

    def test_snapshot_delta_windows(self):
        m = ServingMetrics()
        m.record_first_token(0.1)
        m.record_decode_step(0.5, 10, 1, 0, 16)
        d1 = m.snapshot_delta()
        assert d1["window_decode_tokens"] == 10
        assert d1["window_ttft_count"] == 1.0
        assert d1["window_decode_tokens_per_sec"] == pytest.approx(20.0)
        # second window: only NEW activity
        m.record_first_token(0.3)
        m.record_first_token(0.5)
        m.record_decode_step(0.5, 4, 1, 0, 16)
        d2 = m.snapshot_delta()
        assert d2["window_decode_tokens"] == 4
        assert d2["window_ttft_count"] == 2.0
        assert d2["window_ttft_p50_s"] == pytest.approx(
            float(np.percentile([0.3, 0.5], 50)))
        # run-total snapshot is untouched by the windowing
        assert m.snapshot()["decode_tokens"] == 14
        # idle window: zero deltas, zero rates (no division blowup)
        d3 = m.snapshot_delta()
        assert d3["window_decode_tokens"] == 0
        assert d3["window_decode_tokens_per_sec"] == 0.0


# ----------------------------------------------------------- exporters

class TestExporters:
    def _sample_scope(self):
        with scoped() as s:
            with graftscope.span("phase", cat="serving", req=1):
                graftscope.emit("mark", cat="fault", site="x")
        return s

    def test_chrome_trace_schema(self, tmp_path):
        s = self._sample_scope()
        path = tmp_path / "trace.json"
        write_chrome_trace(str(path), s.events(), t0=s.t0)
        doc = json.loads(path.read_text())
        assert doc["displayTimeUnit"] == "ms"
        evs = doc["traceEvents"]
        assert len(evs) == 2
        for e in evs:
            # the Perfetto/chrome://tracing required keys
            assert {"name", "ph", "ts", "pid", "tid"} <= set(e)
            assert isinstance(e["ts"], float) and e["ts"] >= 0.0
        span_ev = next(e for e in evs if e["ph"] == "X")
        inst = next(e for e in evs if e["ph"] == "i")
        assert span_ev["dur"] >= 0.0
        assert inst["s"] == "t"  # instant scope marker
        assert inst["args"]["site"] == "x"
        assert span_ev["args"]["req"] == 1

    def test_chrome_trace_on_the_profilers_clock(self, tmp_path):
        """Given the scope's anchor, timestamps are microseconds on the
        wall clock, which the profiler stamps its host events on; the
        zero-based form above is what ``t0`` still gives."""
        s = Scope()
        with scoped(s):
            wall_us = time.time_ns() / 1e3
            graftscope.emit("mark")
        assert s.anchor[0] == s.t0 and isinstance(s.anchor[1], int)
        (ev,) = to_chrome_trace(s.events(), anchor=s.anchor)["traceEvents"]
        assert ev["ts"] == pytest.approx(wall_us, abs=5e3)
        (zero,) = to_chrome_trace(s.events(), t0=s.t0)["traceEvents"]
        assert 0.0 <= zero["ts"] < 5e3
        assert ev["ts"] - zero["ts"] == pytest.approx(s.anchor[1] / 1e3,
                                                      abs=1.0)

    def test_trace_out_writes_on_the_profilers_clock(self, tmp_path):
        import argparse

        parser = argparse.ArgumentParser()
        graftscope.add_cli_args(parser)
        path = tmp_path / "t.json"
        args = parser.parse_args(["--trace_out", str(path)])
        try:
            graftscope.arm_from_args(args)
            graftscope.emit("mark")
            wall_us = time.time_ns() / 1e3
            graftscope.export_from_args(args, echo=lambda *_a: None)
        finally:
            graftscope.disarm()
        (ev,) = json.loads(path.read_text())["traceEvents"]
        assert ev["ts"] == pytest.approx(wall_us, abs=5e3)

    def test_jsonl_roundtrip(self, tmp_path):
        s = self._sample_scope()
        path = tmp_path / "events.jsonl"
        write_jsonl(str(path), s.events())
        back = events_from_jsonl(str(path))
        assert [e["name"] for e in back] == ["mark", "phase"]
        assert back[1]["ph"] == "X" and "dur" in back[1]
        assert back[0]["seq"] < back[1]["seq"]

    def test_prometheus_text(self):
        text = prometheus_text(
            {"ttft_p99_s": 0.25, "decode_tokens": 40,
             "decode_programs": [[32, 1]], "mode": "steady",
             "armed": True, "99weird key": 1.5},
            prefix="pmdt_serving")
        lines = [ln for ln in text.splitlines() if ln]
        # every gauge: one TYPE line + one sample line, parseable
        samples = {}
        for ln in lines:
            if ln.startswith("# TYPE "):
                assert ln.endswith(" gauge")
                continue
            name, value = ln.rsplit(" ", 1)
            samples[name] = float(value)
        assert samples["pmdt_serving_ttft_p99_s"] == 0.25
        assert samples["pmdt_serving_decode_tokens"] == 40.0
        assert samples["pmdt_serving__99weird_key"] == 1.5
        # non-numeric values (and bools) never become gauges
        assert not any("programs" in k or "mode" in k or "armed" in k
                       for k in samples)

    def test_timeline_plot_from_jsonl(self, tmp_path):
        """The plot_curves.py parity artifact, now for serving: a
        JSONL event log renders to a timeline PNG (flight dumps render
        too — the header line is skipped by the parser)."""
        from pytorch_multiprocessing_distributed_tpu.utils.plotting import (
            draw_timeline)

        with scoped() as s:
            with graftscope.span("serving.prefill", cat="serving",
                                 req=0):
                pass
            graftscope.emit("fault.injected", cat="fault", site="x")
            graftscope.emit_span("decode.drain", 0.01, cat="serving")
        path = tmp_path / "run.jsonl"
        write_jsonl(str(path), s.events())
        out = draw_timeline(str(path))
        assert out == str(tmp_path / "run.png")
        assert (tmp_path / "run.png").stat().st_size > 0
        with pytest.raises(ValueError, match="no graftscope events"):
            empty = tmp_path / "empty.jsonl"
            empty.write_text("")
            draw_timeline(str(empty))

    def test_stats_server_serves_metrics_and_snapshot(self):
        m = ServingMetrics()
        m.record_first_token(0.125)
        server = start_stats_server(m.snapshot, port=0)
        try:
            port = server.server_address[1]
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics") as resp:
                body = resp.read().decode()
                assert resp.headers["Content-Type"].startswith(
                    "text/plain")
            assert "pmdt_serving_ttft_avg_s 0.125" in body
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/snapshot.json") as resp:
                snap = json.loads(resp.read())
            assert snap["ttft_avg_s"] == 0.125
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/nope")
            assert err.value.code == 404
        finally:
            server.shutdown()

    def test_stats_server_is_live_not_cached(self):
        """The endpoint re-reads the snapshot per scrape — live
        telemetry, not a boot-time copy."""
        m = ServingMetrics()
        server = start_stats_server(m.snapshot, port=0)
        try:
            port = server.server_address[1]

            def scrape():
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/snapshot.json") as r:
                    return json.loads(r.read())

            assert scrape()["requests_completed"] == 0
            m.record_completion(3)
            assert scrape()["requests_completed"] == 1
        finally:
            server.shutdown()


# ------------------------------------------------------ flight recorder

class TestFlightRecorder:
    def test_flight_dump_writes_header_and_tail(self, tmp_path):
        target = tmp_path / "flight.jsonl"
        with scoped(Scope(keep=False, flight_capacity=3,
                          flight_path=str(target))):
            for i in range(7):
                graftscope.emit("tick", i=i)
            out = graftscope.flight_dump("test reason")
        assert out == str(target)
        lines = [json.loads(ln) for ln in
                 target.read_text().splitlines()]
        header, events = lines[0], lines[1:]
        assert header["graftscope_flight"] == "test reason"
        # the scope's instant on perf_counter and on the profiler's clock
        assert len(header["anchor"]) == 2
        assert header["events"] == 3
        assert header["events_before_window"] == 4
        assert [e["i"] for e in events] == [4, 5, 6]  # oldest-first
        # a dump parses through the standard JSONL reader (header
        # skipped)
        assert len(events_from_jsonl(str(target))) == 3

    def test_flight_recorder_context_dumps_on_crash(self, tmp_path):
        target = tmp_path / "crash.jsonl"
        with scoped(Scope(flight_path=str(target))) as s:
            with pytest.raises(RuntimeError):
                with graftscope.flight_recorder("drive loop"):
                    graftscope.emit("work", step=1)
                    raise RuntimeError("boom")
        assert target.exists()
        names = [e["name"] for e in events_from_jsonl(str(target))]
        assert names == ["work", "engine.fatal"]
        fatal = s.events()[-1]
        assert fatal.attrs == {"what": "drive loop",
                               "error": "RuntimeError"}

    def test_flight_recorder_passes_clean_exit(self, tmp_path):
        target = tmp_path / "clean.jsonl"
        with scoped(Scope(flight_path=str(target))):
            with graftscope.flight_recorder("drive loop"):
                graftscope.emit("work")
        assert not target.exists()  # no crash, no dump

    def test_dump_failure_never_masks_the_crash(self, tmp_path):
        """flight_dump sits on raise paths by contract: a typo'd
        directory (or any write failure) is reported and swallowed —
        the engine-fatal error stays the one that propagates."""
        bad = str(tmp_path / "no_such_dir" / "f.jsonl")
        with scoped(Scope(flight_path=bad)):
            graftscope.emit("work")
            assert graftscope.flight_dump("typo'd dir") is None
            # the context-manager path: the ORIGINAL error survives
            with pytest.raises(RuntimeError, match="the real crash"):
                with graftscope.flight_recorder("drive", path=bad):
                    raise RuntimeError("the real crash")
        # unserializable attrs fall back to repr, never a TypeError
        target = tmp_path / "weird.jsonl"
        with scoped(Scope(flight_path=str(target))):
            graftscope.emit("odd", payload=object())
            assert graftscope.flight_dump("repr fallback") == str(
                target)
        (ev,) = events_from_jsonl(str(target))
        assert "object object" in ev["payload"]

    def test_arm_from_args_keep_mode(self):
        """Full log only when an export artifact will consume it;
        --stats_port/--flight_path alone arm the bounded ring (a
        long-running server must not grow memory for a log nothing
        reads)."""
        import argparse

        parser = argparse.ArgumentParser()
        graftscope.add_cli_args(parser, stats_port=True)
        try:
            s = graftscope.arm_from_args(
                parser.parse_args(["--stats_port", "1"]))
            assert s.keep is False
            assert s.flight_path == "graftscope_flight.jsonl"
            s = graftscope.arm_from_args(
                parser.parse_args(["--trace_out", "/tmp/t.json"]))
            assert s.keep is True
            assert s.flight_path == "/tmp/t.flight.jsonl"
            assert graftscope.arm_from_args(
                parser.parse_args([])) is None
        finally:
            graftscope.disarm()

    def test_env_hook_ring_mode_can_dump(self, tmp_path):
        """PMDT_SCOPE=1 (ring-only drills) arms WITH the default
        flight path — the ring's only consumer is the crash dump, so
        the mode must be able to write one."""
        import subprocess
        import sys as _sys

        code = (
            "from pytorch_multiprocessing_distributed_tpu.runtime "
            "import scope\n"
            "s = scope.active_scope()\n"
            "assert s is not None and s.keep is False\n"
            "assert s.flight_path == 'graftscope_flight.jsonl'\n"
            "print('env hook OK')\n")
        env = dict(os.environ, PMDT_SCOPE="1")
        proc = subprocess.run([_sys.executable, "-c", code], env=env,
                              capture_output=True, text=True,
                              timeout=120,
                              cwd=os.path.dirname(os.path.dirname(
                                  os.path.abspath(__file__))))
        assert proc.returncode == 0, proc.stderr
        assert "env hook OK" in proc.stdout

    def test_engine_fatal_pool_poison_dumps_flight(self, tmp_path):
        """The acceptance scenario: an injected engine-fatal
        ``PoolPoisonedError`` (mid-execution failure of a pool-
        donating program, graftfault's harness) leaves the flight
        ring on disk — the dispatch/drain events leading into the
        poisoned launch, then the fatal marker."""
        target = tmp_path / "poisoned.jsonl"
        model = _tiny()
        engine = ServingEngine(model, init_params(model, 1),
                               max_slots=1, s_max=32, min_bucket=8,
                               decode_buckets=(), retry_backoff_s=0.0)
        with scoped(Scope(flight_path=str(target))):
            engine.submit(list(range(5)), 4)
            engine._donate_cache = True  # CPU never donates; simulate

            def exploding_decode(*a, **k):
                raise RuntimeError("simulated XlaRuntimeError mid-exec")

            engine._decode = exploding_decode
            with pytest.raises(PoolPoisonedError, match="pool-donating"):
                for _ in engine.run():
                    pass
        events = events_from_jsonl(str(target))
        names = [e["name"] for e in events]
        # the lifecycle that led in is present, then the fatal marker
        assert "request.submit" in names
        assert "serving.prefill" in names
        assert names[-1] == "engine.fatal"
        fatal = events[-1]
        assert fatal["error"] == "PoolPoisonedError"
        assert fatal["cause"] == "RuntimeError"

    def test_generic_step_fatal_dumps_once(self, tmp_path):
        """A non-poison fatal escaping step() dumps too (watchdog
        fail-fast class), via the step()-level recorder."""
        target = tmp_path / "fatal.jsonl"
        model = _tiny()
        engine = ServingEngine(model, init_params(model, 1),
                               max_slots=1, s_max=32, min_bucket=8,
                               decode_buckets=(), retry_backoff_s=0.0,
                               dispatch_retries=1)
        with scoped(Scope(flight_path=str(target))):
            engine.submit(list(range(4)), 3)
            plan = FaultPlan([FaultRule("serving.decode_dispatch",
                                        "error", times=5)])
            with armed(plan):
                with pytest.raises(Exception,
                                   match="serving.decode_dispatch"):
                    for _ in engine.run():
                        pass
        events = events_from_jsonl(str(target))
        names = [e["name"] for e in events]
        assert names[-1] == "engine.fatal"
        assert "fault.injected" in names  # the injection is on the tape


# ------------------------------------------- armed-cost sentinel pins

class TestArmedCost:
    def test_engine_steady_state_sentinels_with_scope_armed(self):
        """The tentpole's hard criterion: arming graftscope adds ZERO
        compiles, ZERO transfers, and ZERO host syncs to the serving
        hot path. Same pin as tests/test_sentinels.py's steady-state
        engine test — now with the scope ARMED and recording."""
        model = _tiny()
        params = init_params(model, 7)
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, model.vocab_size, (n,))
                   for n in (3, 9, 12)]
        engine = ServingEngine(model, params, max_slots=2, s_max=32,
                               min_bucket=8)
        engine.serve([(p, 4) for p in prompts])  # warm, disarmed
        compiles = engine.decode_step_compiles
        syncs_before = engine.metrics.snapshot()["decode_host_syncs"]

        with scoped() as s:
            with guard_transfers():
                with recompile_budget(engine._decode, 0,
                                      label="armed steady state"):
                    finished = engine.serve([(p, 4) for p in prompts])
        assert all(r.state == DONE for r in finished)
        assert engine.decode_step_compiles == compiles
        # the armed pass produced a full timeline...
        counts = s.counts()
        assert counts["request.done"] == 3
        assert counts["decode.dispatch"] >= 1
        assert counts["decode.drain"] == counts["decode.dispatch"]
        # ...and EXACTLY the disarmed pass's host syncs: one per drain
        syncs = (engine.metrics.snapshot()["decode_host_syncs"]
                 - syncs_before)
        assert syncs == counts["decode.drain"]

    def test_trainer_window_fetch_only_sync(self):
        """LM train loop shape: spans ride the windowed metric fetch
        the loop already pays — emitting them adds no device work
        (the step's program is untouched; pinned by the sentinel
        suite's train-step test plus this armed smoke)."""
        import jax
        import jax.numpy as jnp

        from pytorch_multiprocessing_distributed_tpu.parallel import (
            make_mesh)
        from pytorch_multiprocessing_distributed_tpu.train.lm import (
            create_lm_train_state, make_lm_train_step)
        from pytorch_multiprocessing_distributed_tpu.train.optim import (
            sgd)
        from pytorch_multiprocessing_distributed_tpu.train.step import (
            shard_batch)

        model = _tiny()
        mesh = make_mesh(8, 1)
        opt = sgd(learning_rate=0.1)
        tokens = jnp.asarray(np.random.default_rng(0).integers(
            0, model.vocab_size, (16, 32)))
        state = create_lm_train_state(model, jax.random.PRNGKey(0),
                                      tokens[:2], opt)
        step = make_lm_train_step(model, opt, mesh)
        (tok,) = shard_batch((tokens,), mesh)
        state, _ = step(state, tok)
        state, _ = step(state, tok)  # placement fixed point (see
        # tests/test_sentinels.py)

        with scoped() as s:
            with guard_transfers():
                with recompile_budget(step, 0, label="armed train"):
                    for i in range(3):
                        state, metrics = step(state, tok)
                        graftscope.emit_span("train.data", 0.0,
                                             cat="train", batch=i)
                    with graftscope.span("train.metrics_fetch",
                                         cat="train"):
                        fetched = jax.device_get(metrics)
        assert np.isfinite(float(np.asarray(fetched["loss"])))
        assert s.counts() == {"train.data": 3,
                              "train.metrics_fetch": 1}


# ----------------------------------------------------- fault timeline

class TestFaultTimeline:
    def test_injected_fault_and_retry_are_events(self):
        """Every injected fault and every retry is a visible,
        site-named event — a chaos drill's timeline shows where the
        faults landed."""
        from pytorch_multiprocessing_distributed_tpu.runtime.faults import (
            maybe_fault, register_site, retry_with_backoff)

        register_site("test.scope_site",
                      "synthetic site for the timeline test")
        plan = FaultPlan([FaultRule("test.scope_site", "error",
                                    times=2)])
        with scoped() as s:
            with armed(plan):
                retry_with_backoff(
                    lambda: maybe_fault("test.scope_site", "ok"),
                    attempts=3, base_delay_s=0.0)
        counts = s.counts()
        assert counts["fault.injected"] == 2
        assert counts["fault.retry"] == 2
        injected = [e for e in s.events()
                    if e.name == "fault.injected"]
        assert all(e.attrs["site"] == "test.scope_site"
                   for e in injected)
        assert injected[0].cat == "fault"

    def test_request_timeline_record(self):
        """Request.timeline(): latencies for exactly the phases the
        request reached."""
        from pytorch_multiprocessing_distributed_tpu.serving.scheduler import (
            Request)

        r = Request([1, 2, 3], 4, None)
        t = r.timeline()
        assert t["prompt_len"] == 3 and "queue_wait_s" not in t
        r.submit_time = 100.0
        r.admit_time = 100.5
        r.first_token_time = 101.0
        r.finish_time = 103.0
        r.tokens = [7, 8, 9]
        r.state = DONE
        r.finish_reason = "length"
        t = r.timeline()
        assert t["queue_wait_s"] == pytest.approx(0.5)
        assert t["ttft_s"] == pytest.approx(1.0)
        assert t["decode_s"] == pytest.approx(2.0)
        assert t["total_s"] == pytest.approx(3.0)
        assert t["tokens"] == 3 and t["state"] == DONE

    def test_thread_ids_separate_lanes(self):
        """Events carry the emitting thread id — concurrent lanes
        (engine loop vs stats thread) stay separable in the trace."""
        with scoped() as s:
            graftscope.emit("main.lane")
            t = threading.Thread(
                target=lambda: graftscope.emit("other.lane"))
            t.start()
            t.join()
        a, b = s.events()
        assert a.tid != b.tid
        trace = to_chrome_trace(s.events())
        tids = {e["tid"] for e in trace["traceEvents"]}
        assert len(tids) == 2


# ------------------------------------------------ trainer loop, armed

@pytest.mark.slow
def test_trainer_fit_timeline(tmp_path):
    """Trainer.fit with a scope armed (the main.py --trace_out path):
    the whole epoch timeline lands — data waits, windowed metric
    fetches, window spans, validation, checkpoint (with the
    checkpoint.write byte count) — and the run itself is unchanged
    (artifacts written, no crash, flight ring never dumped). Slow
    (full vit fit); the armed-cost CRITERION stays tier-1 via
    TestArmedCost."""
    import jax
    import jax.numpy as jnp

    from pytorch_multiprocessing_distributed_tpu.data.pipeline import (
        ShardedLoader)
    from pytorch_multiprocessing_distributed_tpu.parallel import (
        make_mesh)
    from pytorch_multiprocessing_distributed_tpu.train import (
        create_train_state)
    from pytorch_multiprocessing_distributed_tpu.train.optim import sgd
    from pytorch_multiprocessing_distributed_tpu.train.trainer import (
        Trainer)

    rng = np.random.default_rng(0)
    images = rng.integers(0, 255, (64, 32, 32, 3), dtype=np.uint8)
    labels = rng.integers(0, 10, (64,)).astype(np.int64)
    loader = lambda train: ShardedLoader(  # noqa: E731
        images, labels, batch_size=16, world_size=8, train=train,
        shuffle=False, with_valid=not train)
    model = models.get_model("vit_tiny", num_classes=10)
    opt = sgd(learning_rate=0.1)
    state = create_train_state(
        model, jax.random.PRNGKey(0), jnp.zeros((2, 32, 32, 3)), opt)
    trainer = Trainer(
        model=model, optimizer=opt, mesh=make_mesh(), state=state,
        train_loader=loader(True), test_loader=loader(False),
        save_path=str(tmp_path), epochs=1, print_freq=2)

    flight = tmp_path / "flight.jsonl"
    with scoped(Scope(flight_path=str(flight))) as s:
        trainer.fit()
    counts = s.counts()
    assert counts["train.data"] == 4  # 64 imgs / (16-batch) steps
    assert counts["train.metrics_fetch"] >= 1
    assert counts["train.window"] == counts["train.metrics_fetch"]
    assert counts["train.eval_fetch"] >= 1
    assert counts["train.checkpoint"] == 1  # final epoch
    write = next(e for e in s.events()
                 if e.name == "checkpoint.write")
    assert write.attrs["bytes"] > 0
    assert write.attrs["epoch"] == 1
    # clean run: artifact exists, flight ring never dumped
    assert (tmp_path / "model_1.pth").exists()
    assert not flight.exists()
    # every window span's step attribution is coherent
    for ev in s.events():
        if ev.name == "train.window":
            assert ev.attrs["steps"] >= 1
            assert ev.dur >= 0.0


# --------------------------------------------------- make-scope smoke

def test_scope_smoke_end_to_end(tmp_path):
    """The ``make scope`` body, in-process: a synthetic engine run
    emits a Perfetto-loadable Chrome trace, a JSONL log with complete
    per-request lifecycles, and a parseable Prometheus exposition
    (live endpoint scraped once) — every assertion lives in
    benchmarks/scope_smoke.py so the CI target and this tier-1 test
    can never drift apart."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "scope_smoke", os.path.join(repo, "benchmarks",
                                    "scope_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = mod.run(str(tmp_path))
    assert out["snapshot"]["requests_completed"] == 4
    assert graftscope.active_scope() is None  # smoke disarms
