"""graftfleet: cross-host observability — rank-tagged events, fleet
collection, collective/straggler attribution, the goodput ledger, and
the bounded percentile meters.

What must stay true:

- **zero disarmed cost**: ``note_arrival``/``publish_endpoint``/
  ``goodput_gauges`` reduce to one module-global read when no monitor
  is armed;
- **zero armed device cost**: the serving engine's sentinel pins (0
  compiles / 0 transfers / 0 extra host syncs in steady state) hold
  with a fleet monitor AND a scope armed — everything graftfleet does
  is host-side bookkeeping at boundaries the host already owns;
- **clock-aligned lanes**: the published monotonic-offset handshake
  puts every rank's events on one axis; the merged Chrome trace has
  exactly one lane (pid) per rank;
- **named stragglers**: with injectable clocks, the artificially
  slowed rank is NAMED, with exact lag percentiles (pinned against
  ``np.percentile``);
- **honest goodput**: restart backoff and retry delays land in lost
  categories; window-nested waits never count as productive; the
  fraction is bounded by [0, 1]; re-ingesting a scope never
  double-counts (the seq cursor);
- **bounded meters**: capped ``PercentileMeter``s stay EXACT over the
  retained window and bit-identical to uncapped while under the cap.
"""

import gc
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from pytorch_multiprocessing_distributed_tpu.analysis.sentinels import (
    guard_transfers, recompile_budget)
from pytorch_multiprocessing_distributed_tpu.runtime import fleet
from pytorch_multiprocessing_distributed_tpu.runtime import (
    scope as graftscope)
from pytorch_multiprocessing_distributed_tpu.runtime.scope import (
    Event, Scope, scoped, start_stats_server)
from pytorch_multiprocessing_distributed_tpu.runtime.store import (
    MemStore)
from pytorch_multiprocessing_distributed_tpu.utils.meters import (
    PercentileMeter, exact_percentile)


@pytest.fixture(autouse=True)
def no_automatic_collections():
    """An armed scope files every collection of Python's cyclic
    collector as a ``host.gc`` Event; these tests compare whole event
    logs, so automatic collection is held off for each of them."""
    was = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was:
            gc.enable()


# --------------------------------------------------- harness helpers

def _mk_monitors(store, world, *, bases=None, clock=None,
                 run_uid="t"):
    """World-size monitors over one store with injectable per-rank
    perf clocks: rank r's perf reads ``clock() + bases[r]`` while wall
    reads ``clock()`` — so the published handshake must cancel the
    bases exactly for aligned stamps to agree."""
    bases = bases or [0.0] * world
    clock = clock or (lambda: 0.0)
    return [fleet.FleetMonitor(
        store, f"host{r}", r, world, run_uid=run_uid,
        perf=(lambda b=bases[r]: clock() + b), wall=clock)
        for r in range(world)]


def _span_dict(seq, name, dur, ts=0.0, cat="train", **attrs):
    d = {"name": name, "cat": cat, "ph": "X", "ts": ts, "dur": dur,
         "seq": seq}
    d.update(attrs)
    return d


def _instant_dict(seq, name, ts=0.0, cat="fault", **attrs):
    d = {"name": name, "cat": cat, "ph": "i", "ts": ts, "seq": seq}
    d.update(attrs)
    return d


# ------------------------------------------------- identity tagging

class TestIdentityTagging:
    def test_armed_fleet_tags_every_event(self):
        store = MemStore()
        (monitor,) = _mk_monitors(store, 1)
        with scoped() as s:
            with fleet.scoped_fleet(monitor):
                graftscope.emit("inner", cat="t")
                with graftscope.span("inner.span", cat="t"):
                    pass
            graftscope.emit("outer", cat="t")
        inner, inner_span, outer = s.events()
        for ev in (inner, inner_span):
            assert ev.attrs["host"] == "host0"
            assert ev.attrs["rank"] == 0
            assert ev.attrs["run_uid"] == "t"
        assert "rank" not in outer.attrs  # disarm cleared identity

    def test_explicit_attrs_win_over_identity(self):
        store = MemStore()
        (monitor,) = _mk_monitors(store, 1)
        with scoped() as s:
            with fleet.scoped_fleet(monitor):
                graftscope.emit("x", cat="t", rank=99)
        assert s.events()[0].attrs["rank"] == 99

    def test_disarmed_module_helpers_are_noops(self):
        """The arming-discipline pin: nothing armed, the module
        helpers return immediately — no store, no scope, no error."""
        assert fleet.active_fleet() is None
        fleet.note_arrival("dist.gate")
        fleet.publish_endpoint("127.0.0.1:1")
        assert fleet.goodput_gauges() == {}


# ---------------------------------------------- clock-aligned lanes

class TestClockAlignment:
    def test_offsets_cancel_per_rank_perf_bases(self):
        store = MemStore()
        clock = {"t": 1000.0}
        _mk_monitors(store, 3, bases=[0.0, 77.0, -13.0],
                     clock=lambda: clock["t"])
        offsets = fleet.FleetCollector(store, run_uid="t").clock_offsets()
        assert offsets[0] == pytest.approx(0.0)
        assert offsets[1] == pytest.approx(-77.0)
        assert offsets[2] == pytest.approx(13.0)

    def test_merged_timeline_one_lane_per_rank_aligned(self):
        store = MemStore()
        clock = {"t": 50.0}
        _mk_monitors(store, 2, bases=[0.0, 30.0],
                     clock=lambda: clock["t"])
        collector = fleet.FleetCollector(store, run_uid="t")
        # the same wall instant reads perf 60 on rank 0, 90 on rank 1
        events = {0: [{"name": "a", "cat": "t", "ph": "X", "ts": 60.0,
                       "dur": 1.0, "tid": 1, "seq": 0}],
                  1: [{"name": "b", "cat": "t", "ph": "X", "ts": 90.0,
                       "dur": 2.0, "tid": 2, "seq": 1}]}
        trace = collector.merged_timeline(events,
                                          hosts={0: "h0", 1: "h1"})
        rows = trace["traceEvents"]
        meta = [r for r in rows if r["ph"] == "M"]
        assert {m["pid"] for m in meta} == {0, 1}
        assert {m["args"]["name"] for m in meta} == \
            {"rank 0 (h0)", "rank 1 (h1)"}
        spans = {r["pid"]: r for r in rows if r["ph"] == "X"}
        # aligned to the SAME instant -> both start at t0 == 0
        assert spans[0]["ts"] == pytest.approx(0.0)
        assert spans[1]["ts"] == pytest.approx(0.0)
        assert spans[1]["dur"] == pytest.approx(2e6)
        json.dumps(trace)  # schema must serialize

    def test_merged_gauges_rank_labels_and_percentiles(self):
        snaps = {0: {"tps": 10.0, "note": "str-skipped", "ok": True},
                 1: {"tps": 30.0}, 2: {"tps": 20.0}, 3: None}
        merged = fleet.FleetCollector.merged_gauges(snaps)
        assert set(merged) == {"tps"}
        g = merged["tps"]
        assert g["by_rank"] == {0: 10.0, 1: 30.0, 2: 20.0}
        vals = [10.0, 30.0, 20.0]
        for q in (50, 95, 99):
            assert g[f"p{q}"] == pytest.approx(
                float(np.percentile(vals, q)))
        assert (g["min"], g["max"]) == (10.0, 30.0)


# ------------------------------------------ straggler attribution

class TestStragglerAttribution:
    def test_injected_clock_names_the_slow_rank_exactly(self):
        """The headline pin: rank 2 arrives exactly 0.5 s late at
        every boundary; the report names it with lag percentiles
        pinned to the injected constant."""
        store = MemStore()
        clock = {"t": 0.0}
        m0, m1, m2 = _mk_monitors(store, 3, bases=[5.0, -3.0, 11.0],
                                  clock=lambda: clock["t"])
        for k in range(5):
            clock["t"] = 100.0 + k
            m0.note_arrival("dist.gate")
            m1.note_arrival("dist.gate")
            clock["t"] = 100.5 + k
            m2.note_arrival("dist.gate")
        report = fleet.FleetCollector(store,
                                      run_uid="t").straggler_report()
        assert report["collectives"] == 5
        assert report["straggler_rank"] == 2
        by2 = report["by_rank"][2]
        assert by2["slowest_count"] == 5
        assert by2["lag_p50_s"] == pytest.approx(0.5)
        assert by2["lag_p95_s"] == pytest.approx(0.5)
        assert report["by_rank"][0]["lag_p50_s"] == pytest.approx(0.0)
        assert report["skew_p50_s"] == pytest.approx(0.5)
        assert report["by_name"]["dist.gate"]["slowest_rank"] == 2

    def test_axis_and_bytes_ride_the_stamp(self):
        store = MemStore()
        (m,) = _mk_monitors(store, 1)
        m.note_arrival("all_reduce@data", axis="data", nbytes=64)
        stamps = fleet.FleetCollector(store, run_uid="t").arrivals()
        assert stamps[0]["axis"] == "data"
        assert stamps[0]["nbytes"] == 64

    def test_single_rank_yields_no_verdict(self):
        store = MemStore()
        (m,) = _mk_monitors(store, 1)
        m.note_arrival("dist.gate")
        report = fleet.FleetCollector(store,
                                      run_uid="t").straggler_report()
        assert report["collectives"] == 0
        assert report["straggler_rank"] is None
        assert report["straggler_lag_p95_s"] is None

    def test_store_outage_drops_stamps_never_raises(self):
        """Observability must never kill the run: a dead store makes
        stamps drop COUNTED, with the workload unharmed."""
        class DeadStore:
            def set(self, key, value):
                raise ConnectionError("store down")

            def get(self, key):
                return None

        monitor = fleet.FleetMonitor(DeadStore(), "h", 0, 2,
                                     run_uid="t")
        monitor.note_arrival("dist.gate")
        monitor.publish_endpoint("127.0.0.1:1")
        # construction publishes world+clock (2 drops), then the
        # arrival and the endpoint
        assert monitor.dropped_stamps >= 4

    def test_dist_gate_and_barrier_stamp_arrivals(self):
        """The wired boundaries: gate_collectives and barrier stamp
        the armed monitor (and stay no-ops disarmed)."""
        from pytorch_multiprocessing_distributed_tpu.parallel import (
            dist)

        store = MemStore()
        (monitor,) = _mk_monitors(store, 1)
        with fleet.scoped_fleet(monitor):
            dist.gate_collectives()
            dist.barrier("fleet-test")
        dist.gate_collectives()  # disarmed: no-op
        names = [s["name"] for s in fleet.FleetCollector(
            store, run_uid="t").arrivals()]
        assert names == ["dist.gate", "dist.gate",
                         "barrier:fleet-test"]

    def test_all_reduce_stamps_static_bytes(self):
        """The host-level collective stamps its per-member payload
        bytes from HOST metadata — and on the audit geometry the
        number must equal the committed graftcheck budget
        (fingerprints.json), the no-device-read join."""
        import jax
        import jax.numpy as jnp

        from pytorch_multiprocessing_distributed_tpu.parallel import (
            collectives, make_mesh)

        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-device CPU mesh")
        mesh = make_mesh(4, 2)
        store = MemStore()
        (monitor,) = _mk_monitors(store, 1)
        stacked = jnp.ones((4, 16), jnp.float32)
        with scoped() as s:
            with fleet.scoped_fleet(monitor):
                out = collectives.all_reduce(stacked, mesh, "data")
        assert float(out[0]) == 4.0
        (stamp,) = fleet.FleetCollector(store, run_uid="t").arrivals()
        assert stamp["name"] == "all_reduce@data"
        committed = fleet.static_collective_bytes(
            "collectives_all_reduce")
        assert stamp["nbytes"] == committed["psum@data"] == 64
        (ev,) = [e for e in s.events()
                 if e.name == "collective.all_reduce"]
        assert ev.ph == "i"  # dispatch-only: an instant, NOT a span
        assert ev.attrs["nbytes"] == 64


def test_straggler_over_real_tcp_store():
    """The multi-client harness on the REAL C++ store (the
    tests/test_graftheal.py pattern): three 'hosts' stamp arrivals
    through their own TCP clients in their own threads, one host
    sleeping before every boundary; a FOURTH client (the collector's
    seat) names it."""
    import shutil

    if shutil.which("g++") is None and shutil.which("make") is None:
        pytest.skip("no C++ toolchain")
    from pytorch_multiprocessing_distributed_tpu.runtime import (
        TCPStore, TCPStoreServer)

    rounds, slow_rank = 4, 1
    with TCPStoreServer(port=0) as srv:
        clients = [TCPStore(port=srv.port, backoff_s=0.0)
                   for _ in range(4)]
        try:
            monitors = [fleet.FleetMonitor(
                clients[r], f"host{r}", r, 3, run_uid="tcp")
                for r in range(3)]

            def worker(rank):
                for _ in range(rounds):
                    if rank == slow_rank:
                        time.sleep(0.05)
                    monitors[rank].note_arrival("dist.gate")

            threads = [threading.Thread(target=worker, args=(r,))
                       for r in range(3)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30.0)
            assert not any(t.is_alive() for t in threads)
            report = fleet.FleetCollector(
                clients[3], run_uid="tcp").straggler_report()
            assert report["collectives"] == rounds
            assert report["straggler_rank"] == slow_rank
            assert report["by_rank"][slow_rank]["lag_p50_s"] > 0.0
        finally:
            for c in clients:
                c.close()


# ------------------------------------------------- goodput ledger

class TestGoodputLedger:
    def test_window_minus_nested_waits(self):
        led = fleet.GoodputLedger.from_events([
            _span_dict(0, "train.window", 10.0, ts=0.0),
            _span_dict(1, "train.data", 2.0, ts=1.0),
            _span_dict(2, "train.metrics_fetch", 1.0, ts=5.0),
        ])
        g = led.gauges()
        assert g["goodput_wall_s"] == pytest.approx(10.0)
        assert g["goodput_productive_s"] == pytest.approx(7.0)
        assert g["goodput_frac"] == pytest.approx(0.7)
        assert g["goodput_data_wait_s"] == pytest.approx(2.0)
        assert g["goodput_metrics_sync_s"] == pytest.approx(1.0)

    def test_restart_and_retry_land_in_lost_categories(self):
        """The satellite pin: supervised-restart backoff and
        fault-retry delays are LOST time, named as such."""
        led = fleet.GoodputLedger.from_events([
            _span_dict(0, "train.window", 4.0, ts=0.0),
            _instant_dict(1, "heal.restart", ts=4.0, backoff_s=3.0),
            _instant_dict(2, "fault.retry", ts=7.0, delay_s=0.5),
            _instant_dict(3, "fault.retry", ts=8.0, delay_s=1.0),
            _span_dict(4, "end.marker", 0.0, ts=10.0, cat="t"),
        ])
        g = led.gauges()
        assert g["goodput_restart_backoff_s"] == pytest.approx(3.0)
        assert g["goodput_fault_retry_s"] == pytest.approx(1.5)
        assert g["goodput_productive_s"] == pytest.approx(4.0)
        assert g["goodput_frac"] == pytest.approx(0.4)
        assert g["goodput_lost_s"] == pytest.approx(6.0)

    def test_serving_spans_are_productive_drain_is_lost(self):
        led = fleet.GoodputLedger.from_events([
            _span_dict(0, "serving.prefill", 1.0, ts=0.0,
                       cat="serving"),
            _span_dict(1, "decode.drain", 3.0, ts=1.0, cat="serving"),
            _span_dict(2, "engine.drain", 6.0, ts=4.0, cat="serving"),
        ])
        g = led.gauges()
        assert g["goodput_productive_s"] == pytest.approx(4.0)
        assert g["goodput_drain_s"] == pytest.approx(6.0)
        assert g["goodput_frac"] == pytest.approx(0.4)

    def test_compile_and_checkpoint_categories(self):
        led = fleet.GoodputLedger.from_events([
            _span_dict(0, "compile.lower", 5.0, ts=0.0,
                       cat="compile"),
            _span_dict(1, "train.checkpoint", 2.0, ts=5.0),
            _span_dict(2, "checkpoint.write", 1.5, ts=5.2),
            _span_dict(3, "train.window", 3.0, ts=7.0),
        ])
        g = led.gauges()
        assert g["goodput_compile_s"] == pytest.approx(5.0)
        assert g["goodput_checkpoint_s"] == pytest.approx(2.0)
        # the nested write is tracked APART — never double-counted
        # into the checkpoint category
        assert g["goodput_checkpoint_write_s"] == pytest.approx(1.5)
        assert g["goodput_frac"] == pytest.approx(0.3)

    def test_seq_cursor_never_double_counts(self):
        led = fleet.GoodputLedger()
        events = [_span_dict(0, "train.window", 2.0, ts=0.0),
                  _span_dict(1, "train.window", 3.0, ts=2.0)]
        assert led.ingest(events) == 2
        assert led.ingest(events) == 0  # replay: cursor holds
        assert led.ingest(events + [
            _span_dict(2, "train.window", 1.0, ts=5.0)]) == 1
        assert led.gauges()["goodput_productive_s"] == \
            pytest.approx(6.0)

    def test_event_objects_and_dicts_agree(self):
        ev = Event("train.window", "train", "X", 0.0, 2.0, 0, 0, {})
        from_obj = fleet.GoodputLedger.from_events([ev]).gauges()
        from_dict = fleet.GoodputLedger.from_events(
            [ev.to_dict()]).gauges()
        assert from_obj == from_dict

    def test_frac_clamped_to_one(self):
        """Overlapping productive spans can sum past the wall (two
        threads draining at once); the fraction is still bounded."""
        led = fleet.GoodputLedger.from_events([
            _span_dict(0, "decode.drain", 2.0, ts=0.0),
            _span_dict(1, "decode.drain", 2.0, ts=0.0),
        ])
        assert led.gauges()["goodput_frac"] == pytest.approx(1.0)

    def test_empty_ledger_reports_zero_not_nan(self):
        g = fleet.GoodputLedger().gauges()
        assert g["goodput_frac"] == 0.0
        assert g["goodput_wall_s"] == 0.0

    def test_ingest_scope_is_incremental(self):
        """Review fix: a scrape loop must stay O(new events) — the
        ledger reads the scope through ``events_since`` (cursor), so
        a second pull with nothing new ingests NOTHING, and a
        re-armed scope (supervised restart) resets the cursor without
        double-counting."""
        ledger = fleet.arm_goodput()
        try:
            with scoped() as s1:
                graftscope.emit_span("train.window", 1.0, cat="train")
                assert ledger.ingest_scope() == 1
                assert ledger.ingest_scope() == 0  # nothing new
                graftscope.emit_span("train.window", 2.0, cat="train")
                assert ledger.ingest_scope() == 1  # only the new one
                assert ledger._scope is s1
            with scoped():  # a fresh scope: cursor resets, seq guards
                graftscope.emit_span("train.window", 4.0, cat="train")
                assert ledger.ingest_scope() == 1
            # every span accumulated exactly once across both scopes
            # (gauges() would clamp to the wall here: the retroactive
            # spans overlap on the real clock)
            assert ledger.seconds["train_window"] == pytest.approx(7.0)
        finally:
            fleet.disarm_goodput()

    def test_scope_events_since_ring_mode(self):
        """The incremental read across ring eviction: a too-old
        cursor yields what is retained — an undercount, never a
        double count."""
        s = Scope(keep=False, flight_capacity=4)
        for i in range(3):
            s.record(Event(f"e{i}", "t", "i", float(i), 0.0, 0, i, {}))
        events, cursor = s.events_since(0)
        assert [e.name for e in events] == ["e0", "e1", "e2"]
        assert s.events_since(cursor) == ([], 3)
        for i in range(3, 9):  # evicts e0..e4 (ring of 4 keeps e5..e8)
            s.record(Event(f"e{i}", "t", "i", float(i), 0.0, 0, i, {}))
        events, cursor = s.events_since(cursor)
        assert [e.name for e in events] == ["e5", "e6", "e7", "e8"]
        assert cursor == 9

    def test_goodput_gauges_pull_the_armed_scope(self):
        fleet.arm_goodput()
        try:
            with scoped():
                graftscope.emit_span("train.window", 2.0, cat="train")
                graftscope.emit_span("train.data", 0.5, cat="train")
                g1 = fleet.goodput_gauges()
                g2 = fleet.goodput_gauges()  # cursor: no double count
            assert g1["goodput_productive_s"] == pytest.approx(1.5)
            assert g2["goodput_productive_s"] == \
                g1["goodput_productive_s"]
            assert 0.0 < g1["goodput_frac"] <= 1.0
        finally:
            fleet.disarm_goodput()
        assert fleet.goodput_gauges() == {}


# ------------------------------------- bounded percentile meters

class TestPercentileMeterCap:
    def test_capped_exact_while_under_the_cap(self):
        """Regression pin for BOTH modes: under the cap, a capped
        meter is bit-identical to the uncapped default (and both to
        np.percentile)."""
        rng = np.random.default_rng(0)
        vals = rng.exponential(1.0, size=200)
        capped = PercentileMeter(max_samples=512)
        free = PercentileMeter()
        for v in vals:
            capped.update(float(v))
            free.update(float(v))
        for q in (50, 90, 95, 99):
            expect = float(np.percentile(vals, q))
            assert capped.percentile(q) == free.percentile(q) == \
                pytest.approx(expect, abs=0, rel=0)

    def test_over_cap_keeps_exact_recent_window(self):
        rng = np.random.default_rng(1)
        vals = rng.normal(size=1000)
        m = PercentileMeter(max_samples=128)
        for v in vals:
            m.update(float(v))
        assert len(m.values) == 128  # bounded — the satellite's point
        recent = vals[-128:]
        for q in (50, 95, 99):
            assert m.percentile(q) == pytest.approx(
                float(np.percentile(recent, q)))
        # averages/counters stay RUN-TOTAL (the meter surface)
        assert m.count == 1000
        assert m.avg == pytest.approx(float(np.mean(vals)))

    def test_windowed_view_survives_trimming(self):
        m = PercentileMeter(max_samples=8)
        for v in range(5):
            m.update(float(v))
        m.advance_window()
        for v in range(100, 110):  # trims well past the old window
            m.update(float(v))
        win = m.window_stats((50,))
        assert win["count"] == 8.0  # capped retention bounds the window
        assert win["p50"] == pytest.approx(
            float(np.percentile(np.arange(102, 110), 50)))

    def test_bound_arms_and_tightens_a_live_meter(self):
        m = PercentileMeter()
        for v in range(100):
            m.update(float(v))
        m.bound(16)
        assert len(m.values) == 16 and m.values[0] == 84.0
        m.bound(64)  # loosening is refused: the cap only ratchets down
        assert m.max_samples == 16
        with pytest.raises(ValueError):
            m.bound(1)
        with pytest.raises(ValueError):
            PercentileMeter(max_samples=1)

    def test_serving_metrics_bound_samples_caps_the_live_meters(self):
        from pytorch_multiprocessing_distributed_tpu.utils.metrics \
            import ServingMetrics

        metrics = ServingMetrics()
        for i in range(50):
            metrics.record_first_token(0.01 * i)
            metrics.record_admission(0.001 * i)
        metrics.bound_samples(8)
        assert len(metrics.ttft.values) == 8
        assert len(metrics.queue_wait.values) == 8
        snap = metrics.snapshot()  # percentiles still served, capped
        assert snap["ttft_p50_s"] == pytest.approx(
            float(np.percentile([0.01 * i for i in range(42, 50)],
                                50)))
        assert snap["tokens_generated"] == 50  # counters run-total


# ------------------------------------------------ armed-cost pins

class TestArmedCost:
    def test_engine_steady_state_sentinels_with_fleet_armed(self):
        """The tentpole's hard criterion: arming graftfleet (identity
        tagging + an armed scope recording rank-tagged events) adds
        ZERO compiles, ZERO transfers, ZERO host syncs to the serving
        hot path — same pin as graftscope's, one layer higher."""
        from pytorch_multiprocessing_distributed_tpu import models
        from pytorch_multiprocessing_distributed_tpu.serving import (
            DONE, ServingEngine, init_params)

        model = models.GPT(vocab_size=61, max_seq_len=64,
                           hidden_size=32, num_layers=2, num_heads=2,
                           mlp_dim=64, attn_impl="xla")
        params = init_params(model, 7)
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, model.vocab_size, (n,))
                   for n in (3, 9, 12)]
        engine = ServingEngine(model, params, max_slots=2, s_max=32,
                               min_bucket=8)
        engine.serve([(p, 4) for p in prompts])  # warm, disarmed
        compiles = engine.decode_step_compiles

        store = MemStore()
        (monitor,) = _mk_monitors(store, 1, run_uid="cost")
        with scoped() as s:
            with fleet.scoped_fleet(monitor):
                with guard_transfers():
                    with recompile_budget(engine._decode, 0,
                                          label="fleet armed"):
                        finished = engine.serve(
                            [(p, 4) for p in prompts])
        assert all(r.state == DONE for r in finished)
        assert engine.decode_step_compiles == compiles
        # every recorded event carries the rank identity
        for ev in s.events():
            assert ev.attrs["rank"] == 0, ev
        assert s.counts()["request.done"] == 3


# ------------------------------------------------- live endpoints

class TestLiveEndpoints:
    def test_events_json_route_serves_the_armed_scope(self):
        """The default events_fn reads the ARMED scope (so a re-arm
        is followed live) and honors the ?since= cursor — a periodic
        scrape stays O(new events)."""
        server = start_stats_server(
            lambda: {"ok": 1},
            events_fn=graftscope.scope_events_fn)
        try:
            port = server.server_address[1]

            def fetch(path):
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}{path}") as resp:
                    return json.loads(resp.read())

            assert fetch("/events.json") == []  # disarmed: empty
            with scoped():
                graftscope.emit("x", cat="t", k=1)
                rows = fetch("/events.json")
                assert [r["name"] for r in rows] == ["x"]
                assert rows[0]["k"] == 1
                graftscope.emit("y", cat="t")
                # incremental: cursor skips what we already hold
                assert [r["name"] for r in
                        fetch("/events.json?since=1")] == ["y"]
                assert fetch("/events.json?since=2") == []
            # without events_fn the route stays a 404 (no accidental
            # surface)
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/no_such")
        finally:
            server.shutdown()

    def test_endpoint_publication_roundtrip(self):
        store = MemStore()
        monitors = _mk_monitors(store, 2)
        monitors[0].publish_endpoint("127.0.0.1:9100")
        monitors[1].publish_endpoint("127.0.0.1:9101")
        eps = fleet.FleetCollector(store, run_uid="t").endpoints()
        assert eps[0]["address"] == "127.0.0.1:9100"
        assert eps[1]["host"] == "host1"

    def test_collector_requires_a_published_world(self):
        with pytest.raises(KeyError, match="no fleet world"):
            _ = fleet.FleetCollector(MemStore(),
                                     run_uid="absent").world


# --------------------------------------------------- fleet smoke

def test_fleet_smoke_end_to_end():
    """`make fleet`'s body, in-process: the 2-rank synthetic run
    produces a merged per-rank timeline, a straggler report naming
    the injected-slow rank with skew percentiles, and a goodput
    fraction on a live /snapshot.json scrape."""
    import benchmarks.fleet_smoke as smoke

    out = smoke.run()
    assert out["report"]["straggler_rank"] == smoke.SLOW_RANK
    assert out["report"]["straggler_lag_p95_s"] > 0.0
    assert 0.0 < out["live_snapshot"]["goodput_frac"] <= 1.0
    lanes = {ev["pid"] for ev in out["timeline"]["traceEvents"]}
    assert lanes == {0, 1}
