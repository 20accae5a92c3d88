"""Serving-engine throughput: offered load, sequence-length cost, and
decode-horizon dispatch overhead.

Three sweeps over the continuous-batching :class:`ServingEngine`:

1. **Load sweep** (``--sweep load``, the original): an open-loop
   request stream (arrival times fixed in advance — the load does NOT
   slow down when the server lags, which is what "heavy traffic"
   means) at several slot counts; per point: delivered tokens/sec,
   TTFT mean/p95 (submit -> first token, queueing included), queue
   wait p95, mean occupancy and queue depth.

2. **Length sweep** (``--sweep length``): short / long / mixed prompt
   length distributions, each served twice — length-bucketed decode
   (``decode_buckets=auto``) vs the full-``s_max`` window
   (``decode_buckets=off``, the pre-bucketing engine). The point of
   record: ``decode_step_avg_s`` tracking ``decode_window_avg``
   instead of staying flat at ``s_max`` — serving cost following the
   ACTIVE sequences. Chunked prefill is exercised on the long/mixed
   distributions (``--prefill_chunk``).

3. **Horizon sweep** (``--sweep horizon``): a slot-saturating,
   queue-empty steady state (requests == slots, long budgets) served
   at each ``--horizons`` value. The point of record: steady-state
   decode tokens/sec vs H, with ``host_syncs_per_token`` collapsing
   toward 1/H — the evidence that per-step dispatch + readback
   latency, not TPU compute, bounded the H=1 engine (on the CPU
   dispatch-bound config the speedup target is >= 2x at H=8).

4. **Chaos sweep** (``--sweep chaos``): the same steady state served
   fault-free and then under a BACKGROUND fault rate (a graftfault
   ``every=K`` rule injecting a transient dispatch error every K-th
   dispatch — every one recovered by bounded retry, with the
   post-fault H=1 cooldown engaged). The point of record: the
   throughput degradation budget — tok/s under faults vs fault-free,
   with the injected/retry/collapse counts printed beside it, so the
   cost of surviving a given fault rate is RECORDED, never silently
   eaten.

5. **Paged sweep** (``--sweep paged``, graftpage): dense slots vs the
   paged KV cache at a FIXED HBM budget (the dense pool's own KV
   bytes), across short/long/mixed length distributions and prefix-
   hit rates {0, 0.5, 0.9}. Two points of record per cell: (a)
   **resident requests at fixed HBM** — peak concurrent occupancy
   when the backlog saturates the pool, dense vs paged (the paged
   pool holds MORE requests in the same bytes because a request pins
   ``ceil(total / page_size)`` pages, not ``s_max`` columns; the
   planner's prediction is pinned byte-exact against the real
   allocation); (b) **TTFT under prefix hits** — closed-loop
   single-request serves at each hit rate, TTFT split hit vs miss (a
   full hit skips prefill entirely: state splice + at most one COW
   page fork). Paged streams are asserted token-exact vs dense.

6. **Spec sweep** (``--sweep spec``, graftspec): speculative decode —
   accepted-tokens/target-step, TTFT and decode tok/s at draft length
   k ∈ {0, 2, 4, 8} x draft source {self-draft n-gram, draft model}
   on REPETITIVE vs RANDOM prompt families. The repetitive family's
   target is briefly trained on the motif stream (a few seconds of
   SGD) so its greedy continuation genuinely continues the pattern —
   acceptance is then structural, not luck; the random family is the
   adversarial floor (acceptance ~0, and the adaptive
   ``pick_draft_k`` ladder collapses k so throughput holds). Points
   of record: ``spec_accepted_per_target_step`` > 1.0 on the
   repetitive config (more tokens per weight stream — THE speculative
   claim), accept_len p50/p95/p99 in the JSON, and k=0 reproducing
   the non-speculative engine exactly (no spec passes, same program
   ladder — disarmed costs nothing). Off-TPU the draft model is the
   target itself (structural full acceptance — the mode's smoke);
   on TPU pass ``--draft_model`` for a real small-drafts-big setup.

7. **Drain sweep** (``--sweep drain``, graftheal): the elastic-
   lifecycle latencies. Point one: **drain latency** — a loaded
   engine flips to DRAINING mid-serve (the SIGTERM path) and the
   clock runs until every in-flight request finished (admission
   closed throughout). Point two: **recovery time-to-first-token**
   after a supervised restart — an engine with a request-redelivery
   journal is abandoned mid-run (the crash shape), a fresh engine
   replays the WAL ON THE CLOCK (journal load + redelivery + prefill)
   until the first redelivered token lands, and the redelivered
   streams are asserted token-exact vs the pre-crash prefix. The
   recorded numbers are the two SLOs a replica router needs: how long
   a drain holds a slot hostage, and how long a restarted replica
   takes to resume visible progress.

8. **Fleet sweep** (``--sweep fleet``, graftroute): the
   disaggregated-fleet evidence. Point one: a 2-replica router's
   streams are BYTE-IDENTICAL to the single-engine baseline —
   aggregate tok/s vs one engine, per-replica ``goodput_frac`` with
   the straggler named, work steals counted. Point two:
   prefill/decode disaggregation (one prefill replica handing KV
   blocks to a decode replica over the host round-trip) is
   token-exact vs monolithic, transfer bytes per request recorded.
   Point three: one injected replica death mid-run — the dead
   replica's journal redelivers to the peer, every stream still
   byte-exact, fleet ``tokens_generated`` dedup-verified, and the
   **redelivery recovery TTFT** (death detection to the first
   redelivered token) wall-clocked.

9. **Wire sweep** (``--sweep wire``, graftwire): the socket-transport
   cost, measured against the in-process fleet it must be
   semantically identical to. Point one: the SAME 2-replica fleet
   served in-process and then over localhost sockets (thread-hosted
   ``ReplicaServer``\\ s — real TCP, zero subprocess noise): tok/s
   side by side with the **per-RPC overhead p50/p95** from the
   client's own call clock, streams asserted BYTE-IDENTICAL. Point
   two: prefill→decode disaggregation over the wire — the KV block
   rides as raw framed numpy, **transfer bytes/request** recorded at
   both layers (PageTransfer payload and the framed wire meter).
   Point three: a socket-level replica kill mid-run (the SIGKILL
   shape the smoke does to a real process) — WAL redelivery to the
   peer, **kill→recovery TTFT** wall-clocked, streams exact, fleet
   metrics dedup-verified.

10. **Autoscale sweep** (``--sweep autoscale``, graftscale): the
    elastic fleet under time-varying load. A **bursty** (square-wave)
    and a **diurnal** (ramp) arrival trace each drive the
    :class:`FleetAutoscaler` over a 1..3-replica in-process fleet —
    **replicas-over-time** (change-points), **shed rate**, and **TTFT
    p50/p99 across the scale events** per point, every admitted
    request asserted complete. Then a **rolling v1→v2 rollout** under
    steady load: wall-clock **rollout duration**, zero failed
    requests, every stream byte-exact to exactly one weight version.

11. **Quant sweep** (``--sweep quant``, graftquant): int8 KV + f32
    per-page-per-head scales vs model-dtype KV at **FIXED HBM**.
    Point one: the planner inversion in both modes, pinned byte-exact
    against real pools, with the per-slot KV byte ratio gated at its
    own geometry floor (>= **1.8x** wherever ``head_dim >= 64`` —
    every TPU registry model). Point two: ``run_point`` model-dtype
    at the budget's dense slot count vs int8 at the planned quantized
    count — resident requests and tok/s side by side at the same
    byte budget. Point three: greedy transcripts asserted EQUAL on a
    canonical subset and the max-abs teacher-forced **logit delta**
    vs the model-dtype cache recorded and gated (audited, not
    asserted away — int8 KV is not token-exact by construction).

``offered=inf`` is the closed-loop limit: every request submitted
up front, measuring peak engine throughput. Needs a TPU; ``--platform
cpu`` is the explicit rehearsal mode (gpt_tiny, float32, clamped shapes
— counts and correctness, never a speed).
``--json_out`` records every point (plus the compiled window set per
engine) for the round's evidence JSON.

Run: ``python benchmarks/serving_bench.py [--model gpt_small]
[--sweep load,length,horizon] [--slots 2,4,8] [--offered inf,8]
[--horizons 1,4,8] [--json_out benchmarks/serving_bench_tpu.json]``
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

import benchmarks._common as _common  # noqa: E402


def _percentile(values, q):
    return float(np.percentile(np.asarray(values), q)) if values else 0.0


def run_point(model, params, prompts, new_tokens, slots, offered_rps,
              s_max, warmup=False, arm_plan=None, **engine_kwargs):
    from pytorch_multiprocessing_distributed_tpu.runtime import (
        hbm as hbm_ledger)
    from pytorch_multiprocessing_distributed_tpu.runtime import (
        faults, fleet, life)
    from pytorch_multiprocessing_distributed_tpu.runtime import (
        scope as graftscope)
    from pytorch_multiprocessing_distributed_tpu.serving import (
        ServingEngine)
    from pytorch_multiprocessing_distributed_tpu.utils.metrics import (
        ServingMetrics)

    # graftmeter: one fresh ledger per point, armed BEFORE the engine
    # so the pool/params registrations land — every sweep point then
    # records its resident HBM beside its throughput. Armed inside
    # the try: a failed engine construction must still disarm (a
    # stale process-wide ledger would silently absorb later points'
    # registrations).
    # graftfleet: one fresh full-log scope per point — the engine's
    # prefill/drain spans feed the point's goodput fraction.
    ledger = hbm_ledger.arm(hbm_ledger.HbmLedger())
    point_scope = graftscope.arm(graftscope.Scope(keep=True))
    # graftlife: a fresh ownership ledger per point — the leaked_*
    # numbers below must all be 0 (a bench point that strands slots
    # or pages is measuring a leak, not throughput)
    life_led = life.arm(life.OwnershipLedger())
    try:
        engine = ServingEngine(model, params, max_slots=slots,
                               s_max=s_max, **engine_kwargs)
        if arm_plan is not None:
            # chaos sweep: arm BEFORE the warm-up pass so the
            # degraded-mode programs (collapsed-horizon windows) also
            # compile before the clock; ``injected`` below counts the
            # measured window only
            faults.arm(arm_plan)
        if warmup:
            # steady-state sweeps: pay every compile before the clock,
            # then measure on fresh meters (the horizon sweep compiles
            # up to 2x the programs of H=1 — charging compiles to the
            # point would invert the comparison)
            engine.serve([(p, new_tokens) for p in prompts])
            engine.metrics = ServingMetrics()
        injected_base = (arm_plan.triggered() if arm_plan is not None
                         else 0)
        # arrival schedule: evenly spaced at the offered rate (inf =
        # all at t=0). Open loop — lateness accumulates if the engine
        # can't keep up
        arrivals = ([0.0] * len(prompts) if offered_rps == float("inf")
                    else [i / offered_rps for i in range(len(prompts))])
        t_start = time.perf_counter()
        pending = list(zip(prompts, arrivals))
        finished = []
        while pending or engine.in_flight:
            now = time.perf_counter() - t_start
            while pending and pending[0][1] <= now:
                prompt, _ = pending.pop(0)
                engine.submit(prompt, new_tokens)
            if engine.in_flight:
                for request, _, done in engine.step():
                    if done:
                        finished.append(request)
            elif pending:
                time.sleep(min(0.005, pending[0][1] - now))
    finally:
        if arm_plan is not None:
            faults.disarm()
        hbm_ledger.disarm()
        graftscope.disarm()
        life.disarm()
    wall = time.perf_counter() - t_start
    # graftfleet: goodput over the point's own timeline (engine
    # prefill + drain spans vs the point's wall); collective skew only
    # when a fleet monitor is armed (multi-rank run) — None-safe
    # off-TPU and single-host, never a fake number
    goodput = fleet.GoodputLedger.from_events(point_scope.events())
    goodput_frac = (round(goodput.gauges()["goodput_frac"], 4)
                    if goodput.wall_s > 0 else None)
    collective_skew_p95_s = None
    collective_straggler_rank = None
    monitor = fleet.active_fleet()
    if monitor is not None:
        report = fleet.FleetCollector(
            monitor.store, run_uid=monitor.run_uid,
            prefix=monitor.prefix).straggler_report()
        if report["collectives"]:
            collective_skew_p95_s = report["skew_p95_s"]
            collective_straggler_rank = report["straggler_rank"]
    ttfts = [r.first_token_time - r.submit_time for r in finished]
    waits = [r.admit_time - r.submit_time for r in finished]
    total_tokens = sum(len(r.tokens) for r in finished)
    snap = engine.metrics.snapshot()
    # graftmeter efficiency attribution: decode MFU charges the run's
    # total DISPATCHED scan steps (the horizon meter's sum — collapsed
    # H=1 dispatches in the chaos sweep's cooldowns count as 1, not
    # H_max) at the steady-state program's per-step static FLOPs; the
    # chip does that work regardless of occupancy, so this IS the
    # utilization (window variation across buckets is the remaining
    # approximation). Null off-TPU (no peak) — never a fake number.
    mfu = None
    decode_flops = None
    if engine.decode_programs:
        import bench

        w, h = max(engine.decode_programs, key=lambda p: (p[1], p[0]))
        decode_flops = engine.decode_program_analysis(w, h).get("flops")
        peak = bench.chip_peak_flops(jax.devices()[0])
        if decode_flops and peak and wall > 0:
            steps_dispatched = engine.metrics.horizon.sum
            mfu = round((decode_flops / h) * steps_dispatched
                        / wall / peak, 4)
    return {
        "hbm_resident_bytes": ledger.total_bytes,
        "hbm_per_slot_bytes": engine.pool.per_slot_bytes,
        # graftlife: the drained point must hold NOTHING (0s, pinned)
        "leaked_slots": life_led.live("slot"),
        "leaked_pages": life_led.live("page"),
        "leaked_threads": life_led.live("thread"),
        "decode_flops_per_dispatch": decode_flops,
        "mfu": mfu,
        # graftfleet: wall-time accounting + cross-rank attribution
        # for EVERY sweep point (None-safe single-host/off-TPU)
        "goodput_frac": goodput_frac,
        "collective_skew_p95_s": collective_skew_p95_s,
        "collective_straggler_rank": collective_straggler_rank,
        "completed": len(finished),
        "wall_s": wall,
        "tokens_per_sec": total_tokens / wall,
        "ttft_avg_ms": 1e3 * float(np.mean(ttfts)),
        # tail latencies for EVERY sweep point (graftscope): a change
        # that keeps the mean but breaks the p99 is bench-visible
        "ttft_p50_ms": 1e3 * _percentile(ttfts, 50),
        "ttft_p95_ms": 1e3 * _percentile(ttfts, 95),
        "ttft_p99_ms": 1e3 * _percentile(ttfts, 99),
        "queue_wait_p95_ms": 1e3 * _percentile(waits, 95),
        "queue_wait_p99_ms": 1e3 * _percentile(waits, 99),
        "decode_step_avg_s": snap["decode_step_avg_s"],
        "decode_step_p50_s": snap["decode_step_p50_s"],
        "decode_step_p95_s": snap["decode_step_p95_s"],
        "decode_step_p99_s": snap["decode_step_p99_s"],
        "decode_window_avg": snap["decode_window_avg"],
        "decode_tokens_per_sec": snap["decode_tokens_per_sec"],
        "decode_horizon_avg": snap["decode_horizon_avg"],
        "decode_dispatches": snap["decode_dispatches"],
        "host_syncs_per_token": snap["host_syncs_per_token"],
        "overlapped_dispatches": snap["overlapped_dispatches"],
        "occupancy_avg": engine.metrics.occupancy.avg,
        "occupancy_max": snap["occupancy_max"],
        "queue_depth_avg": engine.metrics.queue_depth.avg,
        "decode_compiles": engine.decode_step_compiles,
        "decode_windows": list(engine.decode_windows),
        "decode_programs": [list(p) for p in engine.decode_programs],
        "dispatch_retries": snap["dispatch_retries"],
        "requests_failed": snap["requests_failed"],
        "horizon_collapses": snap["horizon_collapses"],
        # graftspec telemetry (all zero when spec is disarmed)
        "spec_tokens_drafted": snap["spec_tokens_drafted"],
        "spec_tokens_accepted": snap["spec_tokens_accepted"],
        "spec_verify_passes": snap["spec_verify_passes"],
        "spec_accept_rate": snap["spec_accept_rate"],
        "spec_accepted_per_target_step":
            snap["spec_accepted_per_target_step"],
        "accept_len_p50": snap["accept_len_p50"],
        "accept_len_p95": snap["accept_len_p95"],
        "accept_len_p99": snap["accept_len_p99"],
        "spec_programs": [list(p) for p in engine.spec_programs],
        "injected": (arm_plan.triggered() - injected_base
                     if arm_plan is not None else 0),
    }


def _draw_lengths(rng, dist, n, lo, hi):
    """Prompt lengths for one distribution family. ``short`` exercises
    the small decode buckets, ``long`` pins near ``s_max``, ``mixed``
    interleaves both — the case where per-step bucketing (cost follows
    the longest ACTIVE sequence as long requests retire) shows up."""
    short = (max(1, lo), max(1, hi // 4))
    long_ = (max(1, (3 * hi) // 4), hi)
    if dist == "short":
        bands = [short] * n
    elif dist == "long":
        bands = [long_] * n
    else:  # mixed: alternate so both kinds are resident together
        bands = [short if i % 2 == 0 else long_ for i in range(n)]
    return [int(rng.integers(a, b + 1)) for a, b in bands]


def run_length_sweep(model, params, args, s_max, prompt_hi, rng):
    """short/long/mixed x (bucketed | full-window) grid; the JSON
    evidence that decode step time scales with the active bucket."""
    results = []
    chunk = args.prefill_chunk or None
    for dist in args.len_dist.split(","):
        lengths = _draw_lengths(rng, dist, args.requests,
                                prompt_hi // 8, prompt_hi)
        prompts = [rng.integers(0, model.vocab_size, (n,)).tolist()
                   for n in lengths]
        for label, buckets in (("auto", None), ("off", ())):
            r = run_point(model, params, prompts, args.new_tokens,
                          int(args.slots.split(",")[0]), float("inf"),
                          s_max, decode_buckets=buckets,
                          prefill_chunk=chunk)
            r.update(dist=dist, buckets=label,
                     prompt_len_min=min(lengths),
                     prompt_len_max=max(lengths),
                     prefill_chunk=chunk or 0)
            results.append(r)
            print(f"dist={dist:6s} buckets={label:4s}  "
                  f"{r['tokens_per_sec']:9.1f} tok/s  "
                  f"step={1e3 * r['decode_step_avg_s']:7.2f} ms  "
                  f"window={r['decode_window_avg']:6.1f}/{s_max}  "
                  f"ttft p95={r['ttft_p95_ms']:8.1f} ms  "
                  f"(compiles={r['decode_compiles']} "
                  f"windows={r['decode_windows']})", flush=True)
    return results


def run_horizon_sweep(model, params, args, rng):
    """Steady-state dispatch-overhead grid: requests == slots (queue
    drains at admission, so the adaptive horizon is not forced to 1)
    with budgets of several horizons, served at each --horizons value.
    The record: decode tokens/sec vs H and syncs/token -> 1/H."""
    horizons = [int(x) for x in args.horizons.split(",")]
    # ONE slot: the most dispatch-bound shape (per-dispatch compute is
    # minimal, per-dispatch overhead is constant), and syncs/token
    # reads exactly 1/H — the README cost-model term, measured
    slots = 1
    # budgets long enough that most dispatches run at full H (the
    # CPU-clamped --new_tokens would leave every budget below H_max,
    # and a budget of a few H leaves the H=1 tail dominating the mean);
    # +1: the prefill token, so the DECODE budget divides every horizon
    # exactly and no point pays a remainder of single-step dispatches
    new_tokens = max(args.new_tokens, 16 * max(horizons) + 1)
    prompt_hi = max(2, min(args.prompt_max,
                           model.max_seq_len - new_tokens) - 1)
    s_max = min(model.max_seq_len, prompt_hi + new_tokens)
    lengths = [int(rng.integers(max(1, prompt_hi // 2), prompt_hi + 1))
               for _ in range(slots)]
    prompts = [rng.integers(0, model.vocab_size, (n,)).tolist()
               for n in lengths]
    results = []
    for h in horizons:
        # full s_max window: the sweep isolates dispatch+readback
        # overhead (the length sweep owns the bucketing evidence), so
        # boundary-forced H=1 stretches would only blur the comparison.
        # Best-of-N: the point is a latency floor, and host scheduling
        # noise only ever ADDS time — the max is the honest estimator
        r = max((run_point(model, params, prompts, new_tokens, slots,
                           float("inf"), s_max, warmup=True,
                           decode_buckets=(), decode_horizon=h)
                 for _ in range(args.horizon_repeats)),
                key=lambda p: p["decode_tokens_per_sec"])
        r.update(horizon=h, slots=slots, new_tokens=new_tokens,
                 s_max=s_max)
        results.append(r)
        print(f"H={h:3d}  decode {r['decode_tokens_per_sec']:9.1f} "
              f"tok/s  syncs/tok={r['host_syncs_per_token']:6.3f}  "
              f"h_avg={r['decode_horizon_avg']:5.2f}  "
              f"overlapped={r['overlapped_dispatches']:4d}  "
              f"(programs={r['decode_programs']})", flush=True)
    if len(results) > 1 and results[0]["decode_tokens_per_sec"] > 0:
        speedup = (results[-1]["decode_tokens_per_sec"]
                   / results[0]["decode_tokens_per_sec"])
        print(f"# steady-state decode speedup H={horizons[-1]} vs "
              f"H={horizons[0]}: {speedup:.2f}x", flush=True)
    return results


def run_chaos_sweep(model, params, args, rng):
    """Fault-free vs background-fault-rate steady state: the recorded
    degradation budget. One transient error every --chaos_every
    decode-dispatch ATTEMPTS (seeded, deterministic; each recovered
    fault adds one retry attempt, so the realized per-dispatch rate is
    1/(chaos_every - 1)), every one recovered by the engine's bounded
    retry + cooldown — the sweep measures what that survival COSTS in
    tok/s."""
    from pytorch_multiprocessing_distributed_tpu.runtime.faults import (
        FaultPlan, FaultRule)

    if args.chaos_every < 2:
        # every=1 would fault every attempt INCLUDING the retries —
        # retries exhaust and the run dies instead of measuring
        raise SystemExit("--chaos_every must be >= 2 (every attempt "
                         "faulting leaves no attempt to recover on)")

    new_tokens = max(args.new_tokens, 65)
    prompt_hi = max(2, min(args.prompt_max,
                           model.max_seq_len - new_tokens) - 1)
    s_max = min(model.max_seq_len, prompt_hi + new_tokens)
    slots = int(args.slots.split(",")[0])
    lengths = [int(rng.integers(max(1, prompt_hi // 2), prompt_hi + 1))
               for _ in range(slots)]
    prompts = [rng.integers(0, model.vocab_size, (n,)).tolist()
               for n in lengths]
    point = dict(decode_buckets=(), decode_horizon=4,
                 retry_backoff_s=0.0)
    base = run_point(model, params, prompts, new_tokens, slots,
                     float("inf"), s_max, warmup=True, **point)
    plan = FaultPlan([FaultRule("serving.decode_dispatch", "error",
                                times=0, every=args.chaos_every)],
                     seed=7)
    fault = run_point(model, params, prompts, new_tokens, slots,
                      float("inf"), s_max, warmup=True, arm_plan=plan,
                      **point)
    base_tps = base["decode_tokens_per_sec"]
    fault_tps = fault["decode_tokens_per_sec"]
    degradation = (0.0 if base_tps == 0
                   else 1.0 - fault_tps / base_tps)
    results = []
    for label, r in (("fault-free", base), ("faulted", fault)):
        r.update(mode=label, chaos_every=args.chaos_every)
        results.append(r)
        print(f"chaos {label:10s}  {r['decode_tokens_per_sec']:9.1f} "
              f"decode tok/s  injected={r['injected']:3d}  "
              f"retries={r['dispatch_retries']:3d}  "
              f"collapses={r['horizon_collapses']:3d}  "
              f"failed={r['requests_failed']}", flush=True)
    # dispatch_retries counts retries from EVERY engine fault domain;
    # equality holds here because the sweep injects only dispatch
    # faults and the local CPU run has no real transients to add
    assert fault["dispatch_retries"] == fault["injected"], (
        "every injected fault must be VISIBLY retried, none eaten")
    assert fault["requests_failed"] == 0, (
        "a background transient rate must be fully recovered")
    print(f"# degradation budget at 1/{args.chaos_every - 1} "
          f"per-dispatch fault rate: {100 * degradation:.1f}% "
          f"({base_tps:.1f} -> {fault_tps:.1f} tok/s)", flush=True)
    results.append({"mode": "budget", "chaos_every": args.chaos_every,
                    "degradation_frac": degradation})
    return results


def _hit_prompts(rng, model, dist, n, lo, hi, hit_rate):
    """Request stream at a prefix-hit rate: ``hit_rate`` of the
    requests re-use one of two "popular" prompts (identical full
    prompts — FULL hits once cached), the rest are unique."""
    lengths = _draw_lengths(rng, dist, n + 2, lo, hi)
    popular = [rng.integers(0, model.vocab_size, (lengths[i],)).tolist()
               for i in range(2)]
    prompts = []
    for i in range(n):
        if rng.random() < hit_rate:
            prompts.append(list(popular[i % 2]))
        else:
            prompts.append(rng.integers(
                0, model.vocab_size, (lengths[2 + i],)).tolist())
    return prompts


def run_paged_sweep(model, params, args, rng):
    """Dense vs paged at fixed HBM x length dist x prefix-hit rate.
    See the module docstring (sweep 5); CPU-runnable, TPU-ready."""
    from pytorch_multiprocessing_distributed_tpu.analysis.meter import (
        plan_capacity)
    from pytorch_multiprocessing_distributed_tpu.runtime import (
        hbm as hbm_ledger)
    from pytorch_multiprocessing_distributed_tpu.serving import (
        PagePool, ServingEngine)

    new_tokens = args.new_tokens
    # the pool must ADMIT up to the model's own max length (that is
    # what s_max is for); traffic runs mostly shorter — exactly the
    # gap dense slots pay worst-case for and pages do not
    s_max = model.max_seq_len
    prompt_hi = max(2, min(args.prompt_max, s_max - new_tokens) - 1)
    slots_dense = int(args.slots.split(",")[0])
    page_size = max(4, args.page_size)
    # FIXED budget: params + exactly the dense pool's worst-case KV
    # bytes — the planner charges params first, so the page pool gets
    # precisely the bytes the dense slots occupied
    kv_budget = slots_dense * PagePool.per_slot_kv_bytes(model, s_max)
    budget = hbm_ledger.tree_nbytes(params) + kv_budget
    results = []
    for dist in args.len_dist.split(","):
        lengths = _draw_lengths(rng, dist, args.requests,
                                max(1, prompt_hi // 8), prompt_hi)
        prompts = [rng.integers(0, model.vocab_size, (n,)).tolist()
                   for n in lengths]
        plan = plan_capacity(
            model, s_max, budget, params=params, page_size=page_size,
            length_dist=[n + new_tokens for n in lengths])
        num_pages = plan["max_pages"] + 1  # + scratch
        paged_slots = max(slots_dense + 1,
                          min(plan["expected_resident_requests"] + 2,
                              args.requests))

        # ---- point (a): resident requests at the fixed budget
        dense = run_point(model, params, prompts, new_tokens,
                          slots_dense, float("inf"), s_max)
        paged = run_point(model, params, prompts, new_tokens,
                          paged_slots, float("inf"), s_max,
                          kv_layout="paged", page_size=page_size,
                          num_pages=num_pages)
        # planner-vs-allocation byte-exactness pin (the graftmeter
        # contract): a real pool of the planned page count holds
        # exactly the planned KV bytes
        with hbm_ledger.scoped_ledger() as ledger:
            from pytorch_multiprocessing_distributed_tpu.serving import (
                PagePool)

            pool = PagePool(model, paged_slots, s_max,
                            page_size=page_size, num_pages=num_pages)
            kv_entry = ledger.entries()["serving.kv_pages"]
        assert kv_entry[1] == plan["paged_kv_bytes_at_max"], (
            "planner and PagePool disagree on the page bytes")
        del pool
        for mode, r, eng_slots in (("dense", dense, slots_dense),
                                   ("paged", paged, paged_slots)):
            r.update(mode=mode, dist=dist, slots=eng_slots,
                     hbm_budget_bytes=budget,
                     hbm_kv_budget_bytes=kv_budget, s_max=s_max,
                     page_size=(page_size if mode == "paged" else 0),
                     num_pages=(num_pages if mode == "paged" else 0),
                     resident_requests=r["occupancy_max"],
                     planner_expected_resident=plan[
                         "expected_resident_requests"])
            results.append(r)
        gain = (paged["occupancy_max"] / dense["occupancy_max"]
                if dense["occupancy_max"] else 0.0)
        print(f"paged dist={dist:6s}  resident dense="
              f"{dense['occupancy_max']:3d} paged="
              f"{paged['occupancy_max']:3d} ({gain:.1f}x at "
              f"{budget / (1 << 20):.1f} MiB KV)  "
              f"planner={plan['expected_resident_requests']}",
              flush=True)

        # ---- point (b): TTFT at prefix-hit rates (closed loop: one
        # request in flight, so TTFT is the prefill-side latency the
        # prefix cache actually removes)
        for hit_rate in (0.0, 0.5, 0.9):
            prompts_h = _hit_prompts(rng, model, dist, args.requests,
                                     max(1, prompt_hi // 8), prompt_hi,
                                     hit_rate)
            engine = ServingEngine(
                model, params, max_slots=paged_slots, s_max=s_max,
                kv_layout="paged", page_size=page_size,
                num_pages=num_pages, prefix_cache=16)
            ref = ServingEngine(model, params, max_slots=slots_dense,
                                s_max=s_max)
            # warm compiles off the clock (one throwaway miss)
            engine.serve([(prompts_h[0], new_tokens)])
            finished = []
            for p in prompts_h:
                finished.append(engine.serve([(p, new_tokens)])[0])
            ttft = {"hit": [], "miss": []}
            for r in finished:
                key = "hit" if r.prefix_hit == "full" else "miss"
                ttft[key].append(r.first_token_time - r.submit_time)
            # token-exactness vs the dense engine, per unique prompt
            for p, r in list(zip(prompts_h, finished))[:4]:
                (d,) = ref.serve([(p, new_tokens)])
                assert r.tokens == d.tokens, (
                    "paged stream diverged from dense")
            snap = engine.metrics.snapshot()
            point = {
                "mode": "ttft", "dist": dist, "hit_rate": hit_rate,
                "page_size": page_size,
                "requests": len(prompts_h),
                "prefix_hits": snap["prefix_hits"],
                "prefix_partial_hits": snap["prefix_partial_hits"],
                "prefix_misses": snap["prefix_misses"],
                # None, not 0, when a rate produced no samples of a
                # kind (e.g. every popular prompt shorter than one
                # page -> no hits; hit_rate ~1 -> possibly no misses)
                "ttft_hit_p50_ms": (1e3 * _percentile(ttft["hit"], 50)
                                    if ttft["hit"] else None),
                "ttft_hit_p95_ms": (1e3 * _percentile(ttft["hit"], 95)
                                    if ttft["hit"] else None),
                "ttft_miss_p50_ms": (1e3 * _percentile(ttft["miss"], 50)
                                     if ttft["miss"] else None),
                "ttft_miss_p95_ms": (1e3 * _percentile(ttft["miss"], 95)
                                     if ttft["miss"] else None),
                "hbm_per_slot_bytes": engine.pool.per_slot_bytes,
            }
            ratio = (point["ttft_hit_p50_ms"]
                     / point["ttft_miss_p50_ms"]
                     if point["ttft_hit_p50_ms"] is not None
                     and point["ttft_miss_p50_ms"] else None)
            point["ttft_hit_over_miss_p50"] = ratio

            def ms(v):
                return "     n/a" if v is None else f"{v:8.2f}"

            results.append(point)
            print(f"paged dist={dist:6s} hit={hit_rate:.1f}  "
                  f"ttft p50 hit={ms(point['ttft_hit_p50_ms'])} ms "
                  f"miss={ms(point['ttft_miss_p50_ms'])} ms  "
                  f"(ratio={ratio if ratio is None else round(ratio, 3)}"
                  f", hits={snap['prefix_hits']})", flush=True)
    return results


def run_quant_sweep(model, params, args, rng):
    """graftquant (sweep 11): int8 KV at fixed HBM — residency gain
    (planner, byte-exact vs real pools), measured occupancy + tok/s
    both modes, transcript equality on a canonical subset, and the
    teacher-forced logit-delta audit. See module docstring."""
    from pytorch_multiprocessing_distributed_tpu.analysis.meter import (
        plan_capacity)
    from pytorch_multiprocessing_distributed_tpu.inference import (
        teacher_forced_logits)
    from pytorch_multiprocessing_distributed_tpu.runtime import (
        hbm as hbm_ledger)
    from pytorch_multiprocessing_distributed_tpu.serving import (
        PagePool, ServingEngine)

    new_tokens = args.new_tokens
    s_max = model.max_seq_len
    prompt_hi = max(2, min(args.prompt_max, s_max - new_tokens) - 1)
    lengths = _draw_lengths(rng, "mixed", args.requests,
                            max(1, prompt_hi // 8), prompt_hi)
    prompts = [rng.integers(0, model.vocab_size, (n,)).tolist()
               for n in lengths]
    results = []

    # ---- point (a): the byte claim, planner == allocator both modes
    kv_model = PagePool.per_slot_kv_bytes(model, s_max)
    kv_int8 = PagePool.per_slot_kv_bytes(model, s_max, "int8")
    kv_ratio = kv_model / kv_int8
    head_dim = model.hidden_size // model.num_heads
    itemsize = jnp.dtype(model.dtype).itemsize
    # int8 stores head_dim 1-byte lanes + one f32 scale per group:
    # the achievable ratio IS itemsize*Dh/(Dh+4). Gate at that floor,
    # clamped to the 1.8x headline it clears at head_dim >= 64
    # (gpt_small/gpt_medium) for bf16 and at any registry geometry
    # for f32 — a layout regression (fatter sidecar, padding) trips
    # this before it ships
    ratio_floor = min(1.8, itemsize * head_dim / (head_dim + 4)
                      * 0.999)
    assert kv_ratio >= ratio_floor, (
        f"int8 per-slot KV ratio {kv_ratio:.3f} under the geometry "
        f"floor {ratio_floor:.3f}")
    # FIXED budget: params + exactly N model-dtype slots (KV + scalar
    # state) — plan_ref inverts it back to N, plan_q to what int8
    # fits in the same bytes. N >= 5 so integer slot-count floors
    # cannot mask the gain at small --slots
    slots_dense = max(int(args.slots.split(",")[0]), 5)
    per_slot_full = kv_model + PagePool.per_slot_state_bytes()
    budget = (hbm_ledger.tree_nbytes(params)
              + slots_dense * per_slot_full)
    plan_ref = plan_capacity(model, s_max, budget, params=params)
    plan_q = plan_capacity(model, s_max, budget, params=params,
                           kv_dtype="int8")
    assert plan_ref["max_slots"] == slots_dense
    planned_gain = plan_q["max_slots"] / plan_ref["max_slots"]
    assert planned_gain >= min(1.8, ratio_floor), (
        f"planned residency gain {planned_gain:.2f}x under the floor "
        f"at a {slots_dense}-slot budget")
    # planner-vs-allocation byte-exactness pin (the graftmeter
    # contract, quantized mode): a real int8 pool of the planned slot
    # count (one page of s_max rows a slot) registers exactly the
    # planned KV bytes plus the scratch page
    with hbm_ledger.scoped_ledger() as ledger:
        pool = PagePool(model, plan_q["max_slots"], s_max,
                        page_size=s_max, kv_dtype="int8")
        kv_entry = ledger.entries()["serving.kv_pages"]
    assert kv_entry[1] == (plan_q["max_slots"] + 1) * kv_int8, (
        "planner and quantized PagePool disagree on the KV bytes")
    del pool

    # ---- point (b): measured residency + throughput at the budget
    quant_slots = max(slots_dense + 1,
                      min(plan_q["max_slots"], args.requests))
    ref = run_point(model, params, prompts, new_tokens, slots_dense,
                    float("inf"), s_max)
    quant = run_point(model, params, prompts, new_tokens, quant_slots,
                      float("inf"), s_max, kv_dtype="int8")
    for mode, r, eng_slots in (("model", ref, slots_dense),
                               ("int8", quant, quant_slots)):
        r.update(mode=mode, kv_dtype=mode, slots=eng_slots,
                 hbm_budget_bytes=budget, s_max=s_max,
                 per_slot_kv_bytes=(kv_int8 if mode == "int8"
                                    else kv_model),
                 per_slot_kv_ratio=kv_ratio,
                 resident_requests=r["occupancy_max"],
                 planner_max_slots=(plan_q if mode == "int8"
                                    else plan_ref)["max_slots"],
                 planned_residency_gain=planned_gain)
        results.append(r)
    print(f"quant    KV/slot {kv_model} -> {kv_int8} B "
          f"({kv_ratio:.2f}x, head_dim={head_dim})  planned slots "
          f"{plan_ref['max_slots']} -> {plan_q['max_slots']} "
          f"({planned_gain:.2f}x at {budget / (1 << 20):.1f} MiB)  "
          f"resident {ref['occupancy_max']} -> "
          f"{quant['occupancy_max']}  "
          f"{ref['tokens_per_sec']:.1f} -> "
          f"{quant['tokens_per_sec']:.1f} tok/s", flush=True)

    # ---- point (c): quality audit — transcripts + logit delta.
    # int8 KV is NOT token-exact by construction; the bench pins the
    # canonical subset byte-equal and puts the honest logit delta on
    # the record (gated at the committed tolerance per dtype)
    eng_ref = ServingEngine(model, params, max_slots=2, s_max=s_max)
    eng_q = ServingEngine(model, params, max_slots=2, s_max=s_max,
                          kv_dtype="int8")
    canon = prompts[:4]
    out_ref = eng_ref.serve([(p, new_tokens) for p in canon])
    out_q = eng_q.serve([(p, new_tokens) for p in canon])
    for i, (a, b) in enumerate(zip(out_q, out_ref)):
        assert list(a.tokens) == list(b.tokens), (
            f"int8 stream {i} diverged from the model-dtype engine")
    full = jnp.asarray(list(canon[0])
                       + list(out_ref[0].tokens))[None, :]
    lg_ref = teacher_forced_logits(model, params, full, len(canon[0]))
    lg_q = teacher_forced_logits(model, params, full, len(canon[0]),
                                 kv_dtype="int8")
    delta = float(np.max(np.abs(np.asarray(lg_ref)
                                - np.asarray(lg_q))))
    tol = 5e-3 if itemsize >= 4 else 6e-2
    assert 0.0 < delta < tol, (
        f"teacher-forced logit delta {delta:.2e} outside (0, {tol})")
    point = {
        "mode": "quant_quality", "kv_dtype": "int8",
        "requests": len(canon), "transcripts_equal": True,
        "logit_delta_max": delta, "logit_delta_tol": tol,
    }
    results.append(point)
    print(f"quant    {len(canon)} canonical streams byte-equal, "
          f"max |logit delta| = {delta:.2e} (tol {tol:.0e})",
          flush=True)
    return results


def train_repetitive(model, params, motif, steps=60, lr=0.1,
                     seq=64, batch=8, seed=0):
    """Quick plain-SGD fit of ``model`` on the cyclic ``motif``
    stream (a few seconds on CPU for the tiny geometry): repetition is
    the easiest structure a LM learns, so the trained target's greedy
    continuation genuinely loops — the spec sweep's repetitive family
    then measures STRUCTURAL acceptance (the model really continues
    the pattern the n-gram drafter indexes), not random-params luck."""
    rng = np.random.default_rng(seed)

    def make_batch():
        rows = []
        for _ in range(batch):
            off = int(rng.integers(0, len(motif)))
            rows.append([motif[(off + i) % len(motif)]
                         for i in range(seq)])
        return jnp.asarray(rows, jnp.int32)

    def loss_fn(p, toks):
        logits = model.apply({"params": p}, toks, train=False)
        lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), -1)
        return -jnp.mean(jnp.take_along_axis(
            lp, toks[:, 1:][..., None], -1))

    step = jax.jit(lambda p, t: jax.tree.map(
        lambda a, g: (a - lr * g).astype(a.dtype), p,
        jax.grad(loss_fn)(p, t)))
    for _ in range(steps):
        params = step(params, make_batch())
    return params


def run_spec_sweep(model, params, args, rng):
    """Speculative-decode grid (graftspec): {repetitive, random}
    prompts x {self-draft, draft-model} x k. See the module docstring
    (sweep 6). Asserted invariants: k=0 runs ZERO spec passes with
    the non-spec program ladder (disarmed reproduces the plain
    engine), and the repetitive family's best k>0 point clears >1.0
    accepted tokens per target step."""
    from pytorch_multiprocessing_distributed_tpu import models

    platform = jax.devices()[0].platform
    ks = [int(x) for x in args.spec_ks.split(",")]
    new_tokens = max(args.new_tokens, 48)
    motif = [7, 19, 3, 42, 11, 58, 23, 5]
    motif = [t % model.vocab_size for t in motif]
    n_req = min(args.requests, 4 if platform != "tpu" else args.requests)
    s_max = min(model.max_seq_len, 32 + new_tokens)
    prompt_len = min(30, s_max - new_tokens - 1)

    # draft model: a REAL registry model on TPU (--draft_model), the
    # target itself off-TPU (structural acceptance — the mode's smoke)
    if args.draft_model:
        draft_model = models.get_model(
            args.draft_model, dtype=model.dtype,
            vocab_size=model.vocab_size, attn_impl="xla")
        from pytorch_multiprocessing_distributed_tpu.serving import (
            init_params)

        draft_params = init_params(draft_model, 7)
    else:
        draft_model, draft_params = model, None  # filled per family

    # the repetitive family's target: briefly trained on the motif
    rep_params = train_repetitive(model, params, motif)
    families = {
        "repetitive": (rep_params,
                       [[motif[i % len(motif)] for i in range(prompt_len)]
                        for _ in range(n_req)]),
        "random": (params,
                   [rng.integers(0, model.vocab_size,
                                 (prompt_len,)).tolist()
                    for _ in range(n_req)]),
    }
    results = []
    best_rep = 0.0
    for family, (fam_params, prompts) in families.items():
        for mode in args.spec_modes.split(","):
            for k in ks:
                kwargs = dict(decode_buckets=(), decode_horizon=4,
                              draft_k=k)
                if k and mode == "model":
                    kwargs.update(
                        draft_model=draft_model,
                        draft_params=(draft_params if draft_params
                                      is not None else fam_params))
                elif k == 0 and mode == "model":
                    continue  # k=0 is mode-less; keep one baseline row
                r = run_point(model, fam_params, prompts, new_tokens,
                              min(4, n_req), float("inf"), s_max,
                              warmup=True, **kwargs)
                r.update(family=family, mode=(mode if k else "off"),
                         draft_k=k, new_tokens=new_tokens)
                results.append(r)
                if k == 0:
                    assert r["spec_verify_passes"] == 0, (
                        "k=0 must run ZERO speculative passes")
                    assert not r["spec_programs"], (
                        "k=0 must not compile spec programs")
                if family == "repetitive" and k:
                    best_rep = max(
                        best_rep, r["spec_accepted_per_target_step"])
                print(f"spec {family:10s} {r['mode']:5s} k={k}  "
                      f"acc/step={r['spec_accepted_per_target_step']:5.2f}  "
                      f"rate={r['spec_accept_rate']:4.2f}  "
                      f"accept_len p50/p95="
                      f"{r['accept_len_p50']:.1f}/"
                      f"{r['accept_len_p95']:.1f}  "
                      f"{r['decode_tokens_per_sec']:8.1f} decode tok/s  "
                      f"ttft p95={r['ttft_p95_ms']:7.1f} ms", flush=True)
    assert best_rep > 1.0, (
        f"repetitive-prompt config must clear >1.0 accepted tokens "
        f"per target step, got {best_rep:.3f} — the speculative claim "
        "is the whole point")
    print(f"# spec: repetitive best accepted/target-step = "
          f"{best_rep:.2f}", flush=True)
    return results


def run_drain_sweep(model, params, args, rng):
    """Drain latency + post-restart recovery TTFT (graftheal), both
    wall-clocked on a loaded engine; the redelivered streams are
    verified token-exact against the pre-crash prefixes."""
    import tempfile

    from pytorch_multiprocessing_distributed_tpu.runtime import heal
    from pytorch_multiprocessing_distributed_tpu.serving import (
        ServingEngine)

    new_tokens = max(args.new_tokens, 8)
    prompt_hi = max(2, min(args.prompt_max,
                           model.max_seq_len - new_tokens) - 1)
    s_max = min(model.max_seq_len, prompt_hi + new_tokens)
    slots = int(args.slots.split(",")[0])
    prompts = [rng.integers(0, model.vocab_size, (int(rng.integers(
        max(1, prompt_hi // 2), prompt_hi + 1)),)).tolist()
        for _ in range(2 * slots)]
    tmpdir = tempfile.mkdtemp(prefix="pmdt_drain_bench_")

    def mk(journal=None):
        return ServingEngine(model, params, max_slots=slots,
                             s_max=s_max, decode_horizon=4,
                             decode_buckets=(), retry_backoff_s=0.0,
                             journal=journal)

    # ---- point 1: drain latency (the SIGTERM path, no deadline)
    engine = mk()
    engine.serve([(prompts[0], 2)])  # compiles off the clock
    reqs = [engine.submit(p, new_tokens) for p in prompts]
    engine.step()  # mid-serve: slots resident, queue non-empty
    engine.begin_drain("bench")
    t0 = time.perf_counter()
    engine.drain(None)
    drain_latency = time.perf_counter() - t0
    drained = sum(r.state == "done" for r in reqs)
    point = {
        "mode": "drain",
        "slots": slots,
        "requests": len(prompts),
        "drain_latency_s": drain_latency,
        "drained_completed": drained,
        "drained_failed": sum(r.state == "failed" for r in reqs),
        "drain_tokens": sum(len(r.tokens) for r in reqs),
    }
    print(f"drain    latency={drain_latency:8.3f} s  "
          f"completed={drained}/{len(prompts)}  "
          f"tokens={point['drain_tokens']}", flush=True)
    results = [point]

    # ---- point 2: recovery TTFT after a supervised restart
    wal = os.path.join(tmpdir, "wal.jsonl")
    journal = heal.RequestJournal(wal)
    crashed = mk(journal)
    pre = [crashed.submit(p, new_tokens) for p in prompts]
    for _ in range(3):
        crashed.step()  # partial progress into the WAL
    prefix = {r.uid: list(r.tokens) for r in pre}
    del crashed  # abandoned mid-run: the crash shape (WAL not closed)

    t0 = time.perf_counter()  # journal replay ON the clock
    journal2 = heal.RequestJournal(wal)
    unfinished = journal2.unfinished()
    # snapshot NOW: the live entries grow as the fresh engine re-serves
    replayed_tokens = sum(len(e.tokens) for e in unfinished)
    fresh = mk(journal2)
    redelivered = fresh.redeliver(unfinished)
    t_first = None
    while fresh.in_flight and t_first is None:
        for request, _tok, _done in fresh.step():
            t_first = time.perf_counter()
            break
    recovery_ttft = (t_first - t0) if t_first is not None else None
    fresh.drain(None)
    # redelivery is token-exact: every pre-crash prefix is a prefix
    # of the recovered stream (greedy determinism, bench-asserted)
    for r in redelivered:
        want = prefix.get(r.uid, [])
        assert r.tokens[:len(want)] == want, (
            f"redelivered request {r.uid} diverged from its "
            "pre-crash prefix")
    point = {
        "mode": "recovery",
        "slots": slots,
        "redelivered": len(redelivered),
        "replayed_tokens": replayed_tokens,
        "recovery_ttft_s": recovery_ttft,
        "recovered_completed": sum(r.state == "done"
                                   for r in redelivered),
    }
    print(f"recovery ttft={recovery_ttft:8.3f} s  "
          f"redelivered={len(redelivered)}  "
          f"replayed_tokens={point['replayed_tokens']}", flush=True)
    results.append(point)
    import shutil

    shutil.rmtree(tmpdir, ignore_errors=True)
    return results


def run_fleet_sweep(model, params, args, rng):
    """graftroute (sweep 8): the fleet evidence — (1) a 2-replica
    router's streams are BYTE-IDENTICAL to the single-engine baseline
    at higher aggregate tok/s, with per-replica goodput_frac and the
    straggler named; (2) prefill/decode disaggregation is token-exact
    vs monolithic, transfer bytes recorded; (3) one injected replica
    death mid-run -> journal redelivery to the peer, every stream
    still exact, recovery TTFT wall-clocked."""
    import tempfile

    from pytorch_multiprocessing_distributed_tpu.runtime import (
        faults, fleet as graftfleet, heal)
    from pytorch_multiprocessing_distributed_tpu.serving import (
        Router, ServingEngine, ServingReplica)

    new_tokens = max(4, min(args.new_tokens, 16))
    prompt_hi = max(2, min(args.prompt_max,
                           model.max_seq_len - new_tokens) - 1)
    s_max = min(model.max_seq_len, prompt_hi + new_tokens)
    slots = int(args.slots.split(",")[0])
    n_req = max(2 * slots + 2, min(args.requests, 12))
    prompts = [rng.integers(0, model.vocab_size, (int(rng.integers(
        max(1, prompt_hi // 2), prompt_hi + 1)),)).tolist()
        for _ in range(n_req)]

    def mk(journal=None, dispatch_retries=3):
        return ServingEngine(model, params, max_slots=slots,
                             s_max=s_max, decode_buckets=(),
                             retry_backoff_s=0.0, journal=journal,
                             dispatch_retries=dispatch_retries)

    # ---- baseline: ONE engine, same request set
    base = mk()
    base.serve([(prompts[0], 2)])  # compiles off the clock
    t0 = time.perf_counter()
    ref = base.serve([(p, new_tokens) for p in prompts])
    base_s = time.perf_counter() - t0
    ref_tokens = {i: list(r.tokens) for i, r in enumerate(ref)}
    total_tokens = sum(len(t) for t in ref_tokens.values())
    results = []

    # ---- point 1: 2-replica fleet, byte-identical + aggregate tok/s
    router = Router([ServingReplica("r0", mk()),
                     ServingReplica("r1", mk())])
    for replica in router.replicas:  # compiles off the clock, like
        replica.engine.serve([(prompts[0], 2)])  # the baseline's
    t0 = time.perf_counter()
    out = router.serve([(p, new_tokens) for p in prompts])
    fleet_s = time.perf_counter() - t0
    for i, r in enumerate(out):
        assert r.state == "done" and list(r.tokens) == ref_tokens[i], (
            f"fleet stream {i} diverged from the single-engine "
            "baseline")
    merged = router.merged_metrics()
    report = graftfleet.fleet_serving_report(merged["per_replica"])
    point = {
        "mode": "fleet", "replicas": 2, "slots": slots,
        "requests": n_req,
        "baseline_tokens_per_sec": total_tokens / base_s,
        "tokens_per_sec": total_tokens / fleet_s,
        "speedup": base_s / fleet_s,
        "steals": router.steals,
        "goodput_frac_per_replica":
            report.get("goodput_frac_per_replica", {}),
        "straggler": report.get("straggler"),
        "byte_identical": True,
    }
    print(f"fleet    2 replicas  {point['tokens_per_sec']:9.1f} tok/s "
          f"(1 engine: {point['baseline_tokens_per_sec']:9.1f})  "
          f"speedup={point['speedup']:5.2f}x  steals={router.steals}",
          flush=True)
    results.append(point)

    # ---- point 2: prefill/decode split vs monolithic (token-exact)
    router = Router([ServingReplica("pf", mk(), role="prefill"),
                     ServingReplica("dc", mk(), role="decode")])
    router.serve([(prompts[0], 2)])  # both halves' compiles off-clock
    t0 = time.perf_counter()
    out = router.serve([(p, new_tokens) for p in prompts])
    disagg_s = time.perf_counter() - t0
    for i, r in enumerate(out):
        assert r.state == "done" and list(r.tokens) == ref_tokens[i], (
            f"disaggregated stream {i} diverged from monolithic")
    pf = router._by_rid["pf"]
    point = {
        "mode": "disagg", "slots": slots, "requests": n_req,
        "tokens_per_sec": total_tokens / disagg_s,
        "transfers": router.transfers_routed,
        "transfer_bytes": router.transfer_bytes,
        "transfer_bytes_per_request":
            router.transfer_bytes // max(1, router.transfers_routed),
        "prefill_transfers": pf.transfers_out,
        "token_exact": True,
    }
    print(f"disagg   prefill->decode  "
          f"{point['tokens_per_sec']:9.1f} tok/s  "
          f"transfers={router.transfers_routed} (token-exact)",
          flush=True)
    results.append(point)

    # ---- point 3: injected replica death -> redelivery recovery TTFT
    tmpdir = tempfile.mkdtemp(prefix="pmdt_fleet_bench_")

    def mkrep(i):
        journal = heal.RequestJournal(
            os.path.join(tmpdir, f"wal{i}.jsonl"))
        return ServingReplica(f"r{i}", mk(journal, dispatch_retries=1),
                              journal=journal)

    router = Router([mkrep(0), mkrep(1)])
    reqs = [router.submit(p, new_tokens, uid=f"u{i}")
            for i, p in enumerate(prompts)]
    for _ in range(3):
        router.step()  # tokens into both WALs before the kill
    plan = faults.FaultPlan(seed=7, rules=[faults.FaultRule(
        "serving.decode_dispatch", "fatal", times=1)])
    faults.arm(plan)
    t_death = None
    t_recover = None
    try:
        while router.in_flight:
            before = router.requests_redelivered
            t_pre = time.perf_counter()
            events = router.step()
            if router.requests_redelivered > before and t_death is None:
                # the dying dispatch, the reap AND the journal replay
                # all happen inside this one step — clock recovery
                # from the step's START, or the interval measures the
                # microseconds between two post-step reads
                t_death = t_pre
            if t_death is not None and t_recover is None:
                redelivered = set(router.redelivered_uids)
                for request, _tok, _done in events:
                    if request.uid in redelivered:
                        t_recover = time.perf_counter()
                        break
    finally:
        faults.disarm()
    recs = router.records()
    for i in range(n_req):
        r = recs[f"u{i}"]
        assert r.state == "done" and list(r.tokens) == ref_tokens[i], (
            f"post-death stream u{i} diverged")
    merged = router.merged_metrics()
    assert merged["tokens_generated"] == total_tokens, (
        "redelivery dedup broke the fleet token count")
    point = {
        "mode": "redelivery", "slots": slots, "requests": n_req,
        "redelivered": router.requests_redelivered,
        "replayed_tokens": router.redelivery_replayed_tokens,
        "recovery_ttft_s": (t_recover - t_death
                            if t_recover and t_death else None),
        "replicas_dead": merged["fleet_replicas_dead"],
        "token_exact": True,
    }
    print(f"redeliver dead=1  redelivered={point['redelivered']}  "
          f"recovery_ttft="
          f"{point['recovery_ttft_s'] if point['recovery_ttft_s'] is None else round(point['recovery_ttft_s'], 4)} s",
          flush=True)
    results.append(point)
    import shutil

    shutil.rmtree(tmpdir, ignore_errors=True)
    return results


def run_wire_sweep(model, params, args, rng):
    """graftwire + graftlink (sweep 9): the socket transport vs the
    in-process seam it mirrors — (1) same fleet, THREE transports
    (in-process, blocking wire, pipelined wire): tok/s side by side,
    streams byte-identical, per-RPC overhead p50/p95, and a scraper
    thread hammering the snapshot verb through the timed run so the
    sweep records snapshot p99 with a long engine verb in flight (the
    head-of-line headline: blocking queues the scrape behind every
    step RPC, pipelined answers it on the obs lane); (2)
    disaggregation over the wire: PageTransfer bytes/request at the
    payload and framing layers (wire bytes ~ payload bytes — the
    zero-copy scatter-gather claim) plus prefill->decode handoff
    latency — then the SAME split with int8 KV (graftquant),
    bytes/request halved vs the model-dtype run; (3) socket-level
    kill -> WAL redelivery with the recovery TTFT on the clock."""
    import tempfile
    import threading

    from pytorch_multiprocessing_distributed_tpu.runtime import (
        heal, wire)
    from pytorch_multiprocessing_distributed_tpu.serving import (
        RemoteReplica, ReplicaServer, Router, ServingEngine,
        ServingReplica)

    new_tokens = max(4, min(args.new_tokens, 16))
    prompt_hi = max(2, min(args.prompt_max,
                           model.max_seq_len - new_tokens) - 1)
    s_max = min(model.max_seq_len, prompt_hi + new_tokens)
    slots = int(args.slots.split(",")[0])
    n_req = max(2 * slots + 2, min(args.requests, 12))
    prompts = [rng.integers(0, model.vocab_size, (int(rng.integers(
        max(1, prompt_hi // 2), prompt_hi + 1)),)).tolist()
        for _ in range(n_req)]

    def mk(journal=None, dispatch_retries=3, kv_dtype="model"):
        return ServingEngine(model, params, max_slots=slots,
                             s_max=s_max, decode_buckets=(),
                             retry_backoff_s=0.0, journal=journal,
                             dispatch_retries=dispatch_retries,
                             kv_dtype=kv_dtype)

    def socket_fleet(journals=None, roles=("both", "both"),
                     kv_dtype="model", pipelined=True):
        servers = []
        for i, role in enumerate(roles):
            journal = journals[i] if journals else None
            servers.append(ReplicaServer(
                mk(journal, dispatch_retries=1 if journals else 3,
                   kv_dtype=kv_dtype),
                rid=f"r{i}", role=role).start())
        replicas = [RemoteReplica(s.address, backoff_s=0.0,
                                  pipelined=pipelined)
                    for s in servers]
        return Router(replicas), servers, replicas

    def rpc_stats(replicas):
        samples = [s for r in replicas for s in r._client.rpc_s]
        if not samples:
            return {"rpcs": 0}
        return {"rpcs": len(samples),
                "rpc_p50_ms": _percentile(samples, 50) * 1e3,
                "rpc_p95_ms": _percentile(samples, 95) * 1e3}

    results = []

    # ---- point 1: one fleet, two transports (byte-identical)
    router = Router([ServingReplica("r0", mk()),
                     ServingReplica("r1", mk())])
    # full warm pass off the clock (every prefill bucket + decode
    # program compiled) so the timed runs compare TRANSPORT, not
    # compile order — the socket fleet gets the identical warmup
    router.serve([(p, new_tokens) for p in prompts])
    t0 = time.perf_counter()
    ref = router.serve([(p, new_tokens) for p in prompts])
    inproc_s = time.perf_counter() - t0
    ref_tokens = {i: list(r.tokens) for i, r in enumerate(ref)}
    total_tokens = sum(len(t) for t in ref_tokens.values())

    # the SAME socket fleet twice: blocking (pipelined=False — the
    # pre-graftlink wire, one exchange at a time) then pipelined (the
    # default). A scraper thread hits replica 0's snapshot verb
    # through the timed run: blocking queues each scrape behind the
    # in-flight step RPC (head-of-line), pipelined answers it from
    # the obs lane — snapshot p99 under load is the HOL headline.
    by_transport = {}
    for transport, pipelined in (("blocking", False),
                                 ("pipelined", True)):
        router, servers, replicas = socket_fleet(pipelined=pipelined)
        stop = threading.Event()
        scrape_s = []

        def scrape_loop(replica=replicas[0], samples=scrape_s):
            while not stop.is_set():
                t_s = time.perf_counter()
                try:
                    replica.scrape()
                except Exception:
                    return
                samples.append(time.perf_counter() - t_s)
                stop.wait(0.002)

        try:
            router.serve([(p, new_tokens) for p in prompts])  # warmup
            for replica in replicas:
                replica._client.rpc_s.clear()
            scraper = threading.Thread(target=scrape_loop, daemon=True)
            scraper.start()
            t0 = time.perf_counter()
            out = router.serve([(p, new_tokens) for p in prompts])
            socket_s = time.perf_counter() - t0
            stop.set()
            scraper.join(timeout=10.0)
            for i, r in enumerate(out):
                assert r.state == "done" and \
                    list(r.tokens) == ref_tokens[i], (
                        f"{transport} socket-fleet stream {i} "
                        "diverged from the in-process fleet")
            point = {
                "mode": "wire_fleet", "transport": transport,
                "replicas": 2, "slots": slots, "requests": n_req,
                "inproc_tokens_per_sec": total_tokens / inproc_s,
                "tokens_per_sec": total_tokens / socket_s,
                "wire_overhead_frac": socket_s / inproc_s - 1.0,
                "byte_identical": True,
                "snapshot_scrapes": len(scrape_s),
            }
            if scrape_s:
                point["snapshot_p50_ms"] = \
                    _percentile(scrape_s, 50) * 1e3
                point["snapshot_p99_ms"] = \
                    _percentile(scrape_s, 99) * 1e3
            point.update(rpc_stats(replicas))
            by_transport[transport] = point
            print(f"wire     2 replicas {transport:9s} "
                  f"{point['tokens_per_sec']:9.1f} tok/s "
                  f"(in-process: "
                  f"{point['inproc_tokens_per_sec']:9.1f})  "
                  f"overhead="
                  f"{point['wire_overhead_frac'] * 100:5.1f}%  "
                  f"rpc p50={point.get('rpc_p50_ms', 0):6.2f} ms "
                  f"p95={point.get('rpc_p95_ms', 0):6.2f} ms  "
                  f"snapshot p99="
                  f"{point.get('snapshot_p99_ms', 0):7.2f} ms "
                  f"({point['snapshot_scrapes']} scrapes)",
                  flush=True)
            results.append(point)
        finally:
            stop.set()
            for server in servers:
                server.stop()
    pipe = by_transport["pipelined"]
    blk = by_transport["blocking"]
    pipe["speedup_vs_blocking"] = (pipe["tokens_per_sec"]
                                   / blk["tokens_per_sec"])
    if "snapshot_p99_ms" in pipe and "snapshot_p99_ms" in blk:
        pipe["snapshot_p99_vs_blocking"] = (pipe["snapshot_p99_ms"]
                                            / blk["snapshot_p99_ms"])
    print(f"wire     pipelined vs blocking  "
          f"{pipe['speedup_vs_blocking']:.2f}x tok/s, snapshot p99 "
          f"{pipe.get('snapshot_p99_vs_blocking', float('nan')):.2f}x",
          flush=True)

    # ---- point 2: disaggregation over the wire (PageTransfer bytes)
    meter0 = wire.wire_meter()["wire_bytes_sent"]
    router, servers, replicas = socket_fleet(
        roles=("prefill", "decode"))
    try:
        router.serve([(prompts[0], 2)])
        t0 = time.perf_counter()
        out = router.serve([(p, new_tokens) for p in prompts])
        disagg_s = time.perf_counter() - t0
        for i, r in enumerate(out):
            assert r.state == "done" and \
                list(r.tokens) == ref_tokens[i], (
                    f"wire-disagg stream {i} diverged from the "
                    "in-process fleet")
        wire_sent = wire.wire_meter()["wire_bytes_sent"] - meter0
        point = {
            "mode": "wire_disagg", "slots": slots, "requests": n_req,
            "tokens_per_sec": total_tokens / disagg_s,
            "transfers": router.transfers_routed,
            "transfer_bytes": router.transfer_bytes,
            "transfer_bytes_per_request":
                router.transfer_bytes // max(1,
                                             router.transfers_routed),
            "wire_bytes_sent": wire_sent,
            # payload bytes as a fraction of EVERYTHING that hit the
            # socket (transfers + every verb header + token events):
            # the zero-copy scatter-gather claim is wire ~ payload,
            # so this should sit near 1.0 — recorded, not asserted
            # (tiny bench models inflate the verb-header share)
            "wire_payload_frac":
                router.transfer_bytes / max(1, wire_sent),
            "token_exact": True,
        }
        if router.transfer_handoff_s:
            point["handoff_p50_ms"] = \
                _percentile(router.transfer_handoff_s, 50) * 1e3
            point["handoff_p95_ms"] = \
                _percentile(router.transfer_handoff_s, 95) * 1e3
        assert wire_sent >= router.transfer_bytes
        print(f"wire     prefill->decode  "
              f"{point['tokens_per_sec']:9.1f} tok/s  "
              f"{point['transfer_bytes_per_request']} KV B/req over "
              f"{router.transfers_routed} transfers  payload/wire="
              f"{point['wire_payload_frac']:.3f}  handoff p95="
              f"{point.get('handoff_p95_ms', 0):6.2f} ms "
              "(token-exact)", flush=True)
        results.append(point)
        model_bytes_per_request = point["transfer_bytes_per_request"]
    finally:
        for server in servers:
            server.stop()

    # ---- point 2b: the SAME disaggregation, int8 KV on the wire
    # (graftquant): the PageTransfer rides as int8 blocks + f32
    # scales (4 raw segments). int8 is not token-exact vs the
    # model-dtype fleet, so the reference is an in-process int8
    # engine — transport must not change ONE token of it — and the
    # headline is transfer bytes/request against point 2's run
    eng_q = mk(kv_dtype="int8")
    ref_q = eng_q.serve([(p, new_tokens) for p in prompts])
    ref_q_tokens = {i: list(r.tokens) for i, r in enumerate(ref_q)}
    q_tokens = sum(len(t) for t in ref_q_tokens.values())
    meter0 = wire.wire_meter()["wire_bytes_sent"]
    router, servers, replicas = socket_fleet(
        roles=("prefill", "decode"), kv_dtype="int8")
    try:
        router.serve([(prompts[0], 2)])
        t0 = time.perf_counter()
        out = router.serve([(p, new_tokens) for p in prompts])
        quant_s = time.perf_counter() - t0
        for i, r in enumerate(out):
            assert r.state == "done" and \
                list(r.tokens) == ref_q_tokens[i], (
                    f"quantized wire-disagg stream {i} diverged from "
                    "the in-process int8 engine")
        wire_sent = wire.wire_meter()["wire_bytes_sent"] - meter0
        bpr = router.transfer_bytes // max(1, router.transfers_routed)
        point = {
            "mode": "wire_disagg_quant", "kv_dtype": "int8",
            "slots": slots, "requests": n_req,
            "tokens_per_sec": q_tokens / quant_s,
            "transfers": router.transfers_routed,
            "transfer_bytes": router.transfer_bytes,
            "transfer_bytes_per_request": bpr,
            "model_dtype_bytes_per_request": model_bytes_per_request,
            "transfer_bytes_ratio": bpr / model_bytes_per_request,
            "wire_bytes_sent": wire_sent,
            "wire_payload_frac":
                router.transfer_bytes / max(1, wire_sent),
            "token_exact_vs_int8_engine": True,
        }
        if router.transfer_handoff_s:
            point["handoff_p50_ms"] = \
                _percentile(router.transfer_handoff_s, 50) * 1e3
            point["handoff_p95_ms"] = \
                _percentile(router.transfer_handoff_s, 95) * 1e3
        assert wire_sent >= router.transfer_bytes
        # the halving claim: int8 lanes + f32 scales vs model-dtype
        # blocks over the SAME prompt set — (Dh+4)/(itemsize*Dh),
        # < 0.6 for bf16 at head_dim >= 16 and any f32 geometry
        assert bpr < 0.6 * model_bytes_per_request, (
            f"quantized transfer {bpr} B/req is not < 0.6x the "
            f"model-dtype {model_bytes_per_request} B/req")
        print(f"wire     prefill->decode int8  "
              f"{point['tokens_per_sec']:9.1f} tok/s  "
              f"{bpr} KV B/req vs {model_bytes_per_request} "
              f"model-dtype ({point['transfer_bytes_ratio']:.2f}x, "
              f"token-exact vs int8 engine)", flush=True)
        results.append(point)
    finally:
        for server in servers:
            server.stop()

    # ---- point 3: kill -> WAL redelivery, recovery TTFT
    tmpdir = tempfile.mkdtemp(prefix="pmdt_wire_bench_")
    journals = [heal.RequestJournal(
        os.path.join(tmpdir, f"wal{i}.jsonl")) for i in range(2)]
    router, servers, replicas = socket_fleet(journals=journals)
    t_death = None
    t_recover = None
    try:
        for i, p in enumerate(prompts):
            router.submit(p, new_tokens, uid=f"u{i}")
        for _ in range(3):
            router.step()  # tokens into both WALs before the kill
        victim = max(replicas, key=lambda r: r.in_flight)
        servers[replicas.index(victim)].kill()
        while router.in_flight:
            before = router.requests_redelivered
            t_pre = time.perf_counter()
            events = router.step()
            if (router.requests_redelivered > before
                    and t_death is None):
                # reap + WAL read + replay happen inside this one
                # step: clock recovery from the step's start
                t_death = t_pre
            if t_death is not None and t_recover is None:
                redelivered = set(router.redelivered_uids)
                for request, _tok, _done in events:
                    if request.uid in redelivered:
                        t_recover = time.perf_counter()
                        break
        recs = router.records()
        for i in range(n_req):
            r = recs[f"u{i}"]
            assert r.state == "done" and \
                list(r.tokens) == ref_tokens[i], (
                    f"post-kill stream u{i} diverged")
        merged = router.merged_metrics()
        assert merged["tokens_generated"] == total_tokens, (
            "redelivery dedup broke the fleet token count")
        point = {
            "mode": "wire_kill", "slots": slots, "requests": n_req,
            "redelivered": router.requests_redelivered,
            "replayed_tokens": router.redelivery_replayed_tokens,
            "recovery_ttft_s": (t_recover - t_death
                                if t_recover and t_death else None),
            "token_exact": True,
        }
        rec_s = point["recovery_ttft_s"]
        print(f"wire     kill dead=1  "
              f"redelivered={point['redelivered']}  recovery_ttft="
              f"{rec_s if rec_s is None else round(rec_s, 4)} s",
              flush=True)
        results.append(point)
    finally:
        for server in servers:
            server.stop()
        import shutil

        shutil.rmtree(tmpdir, ignore_errors=True)
    return results


def run_autoscale_sweep(model, params, args, rng):
    """graftscale (sweep 10): the elastic-fleet evidence — (1) a
    BURSTY arrival trace (square-wave offered load) and (2) a
    DIURNAL one (ramp up, plateau, ramp down) each drive the
    autoscaler over a 1..3-replica fleet: replicas-over-time, shed
    rate, and TTFT p50/p99 ACROSS the scale events land in the
    record; (3) a rolling v1->v2 weight rollout under steady load:
    duration on the clock, zero failed requests, every stream
    byte-exact to one version."""
    from pytorch_multiprocessing_distributed_tpu.serving import (
        EngineReplicaSpawner, FleetAutoscaler, FleetSaturated,
        RollingRollout, Router, ServingEngine, ServingReplica,
        init_params)

    new_tokens = max(4, min(args.new_tokens, 8))
    prompt_hi = max(2, min(args.prompt_max,
                           model.max_seq_len - new_tokens) - 1)
    s_max = min(model.max_seq_len, prompt_hi + new_tokens)
    slots = int(args.slots.split(",")[0])
    prompts = [rng.integers(0, model.vocab_size, (int(rng.integers(
        max(1, prompt_hi // 2), prompt_hi + 1)),)).tolist()
        for _ in range(8)]
    versions = {"v1": params, "v2": init_params(model, 2)}

    def mk(tag="v1"):
        return ServingEngine(model, versions[tag], max_slots=slots,
                             s_max=s_max, decode_buckets=(),
                             retry_backoff_s=0.0)

    def mk_fleet(n=1, **scale_kw):
        router = Router(
            [ServingReplica(f"r{i}", mk(), model_tag="v1")
             for i in range(n)], max_pending=4)
        scale_kw.setdefault("min_replicas", n)
        scale_kw.setdefault("max_replicas", 3)
        scale_kw.setdefault("up_after", 2)
        scale_kw.setdefault("down_after", 8)
        scale_kw.setdefault("cooldown", 4)
        scaler = FleetAutoscaler(
            router, EngineReplicaSpawner(
                lambda tag, journal: mk(tag or "v1")),
            model_tag="v1", sleep=lambda s: None, **scale_kw)
        return router, scaler

    # arrival traces: offered requests per tick
    def bursty(t):
        return 3 if (t // 20) % 2 == 0 else 0  # square wave

    def diurnal(t):
        # ramp 0 -> peak -> 0 over the trace (the day curve)
        period = 80
        phase = (t % period) / period
        return round(3 * min(phase, 1 - phase) * 2)

    results = []
    for trace_name, trace in (("bursty", bursty),
                              ("diurnal", diurnal)):
        router, scaler = mk_fleet(1)
        router.submit(list(prompts[0]), 2, uid="warm0")
        while router.in_flight:  # compiles off the clock
            router.step()
        uid, shed = 0, 0
        replicas_over_time = [(0, 1)]
        t0 = time.perf_counter()
        for t in range(80):
            for _ in range(trace(t)):
                try:
                    router.submit(
                        list(prompts[uid % len(prompts)]),
                        new_tokens, uid=f"u{uid}")
                    uid += 1
                except FleetSaturated:
                    shed += 1
            router.step()
            scaler.tick()
            if replicas_over_time[-1][1] != len(router.replicas):
                replicas_over_time.append(
                    (t + 1, len(router.replicas)))
        steps, idle_tail = 80, 0
        while (router.in_flight or router.pending_depth
               or idle_tail < 30):  # tail: let scale-down fire too
            if not (router.in_flight or router.pending_depth):
                idle_tail += 1
            router.step()
            scaler.tick()
            steps += 1
            if replicas_over_time[-1][1] != len(router.replicas):
                replicas_over_time.append(
                    (steps, len(router.replicas)))
        wall_s = time.perf_counter() - t0
        finished = [r for u, r in router.records().items()
                    if not str(u).startswith("warm")
                    and r.state == "done"
                    and r.first_token_time is not None]
        ttfts = [r.first_token_time - r.submit_time
                 for r in finished]
        point = {
            "mode": "autoscale", "trace": trace_name,
            "slots": slots, "offered": uid + shed,
            "completed": len(finished),
            "shed": shed,
            "shed_rate": shed / max(1, uid + shed),
            "scale_ups": scaler.scale_ups,
            "scale_downs": scaler.scale_downs,
            "peak_replicas": max(n for _, n in replicas_over_time),
            "replicas_over_time": replicas_over_time,
            "scale_events": [e.to_dict() for e in scaler.events],
            "ttft_p50_ms": 1e3 * _percentile(ttfts, 50),
            "ttft_p99_ms": 1e3 * _percentile(ttfts, 99),
            "wall_s": wall_s,
        }
        assert len(finished) == uid, (
            f"{trace_name}: {uid - len(finished)} admitted "
            "request(s) never completed")
        print(f"autoscale {trace_name:8s}  peak={point['peak_replicas']} "
              f"replicas  ups={scaler.scale_ups} "
              f"downs={scaler.scale_downs}  "
              f"shed={100 * point['shed_rate']:4.1f}%  "
              f"ttft p99={point['ttft_p99_ms']:7.1f} ms", flush=True)
        results.append(point)

    # ---- rolling rollout under steady load, duration on the clock
    router, scaler = mk_fleet(2, cooldown=0, down_after=50)
    router.submit(list(prompts[0]), 2, uid="warm0")
    while router.in_flight:
        router.step()
    ref = {}
    for tag in ("v1", "v2"):
        out = mk(tag).serve([(list(p), new_tokens) for p in prompts])
        ref[tag] = {tuple(prompts[i]): list(r.tokens)
                    for i, r in enumerate(out)}
    rollout = RollingRollout(scaler, "v2")
    uid = 0
    total = 3 * len(prompts)
    for _ in range(5000):
        if uid < total:
            try:
                router.submit(list(prompts[uid % len(prompts)]),
                              new_tokens, uid=f"u{uid}")
                uid += 1
            except FleetSaturated:
                pass
        router.step()
        scaler.tick()
        rollout.tick()
        if (rollout.done and uid >= total and not router.in_flight
                and not router.pending_depth):
            break
    recs = {u: r for u, r in router.records().items()
            if not u.startswith("warm")}
    failed = [u for u, r in recs.items() if r.state != "done"]
    mixed = [u for u, r in recs.items()
             if list(r.tokens) not in (
                 ref["v1"].get(tuple(r.prompt)),
                 ref["v2"].get(tuple(r.prompt)))]
    assert rollout.done and not failed and not mixed, (
        f"rollout: done={rollout.done} failed={failed} "
        f"mixed-version={mixed}")
    point = {
        "mode": "rollout", "slots": slots, "requests": len(recs),
        "replaced": rollout.replaced,
        "duration_s": rollout.duration_s,
        "failed": 0, "version_exact": True,
    }
    print(f"rollout  v1->v2  {len(rollout.replaced)} replica(s) in "
          f"{rollout.duration_s:6.2f}s under load  "
          f"({len(recs)} streams, 0 failed, version-exact)",
          flush=True)
    results.append(point)
    return results


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="gpt_small")
    p.add_argument("--requests", default=32, type=int)
    p.add_argument("--prompt_max", default=96, type=int,
                   help="ragged prompt lengths drawn in "
                        "[prompt_max//4, prompt_max]")
    p.add_argument("--new_tokens", default=64, type=int)
    p.add_argument("--slots", default="2,4,8", type=str)
    p.add_argument("--offered", default="inf,8", type=str,
                   help="offered loads in requests/sec ('inf' = all "
                        "submitted up front)")
    p.add_argument("--sweep", default="load,length,horizon", type=str,
                   help="which sweeps to run: load, length, horizon, "
                        "chaos, drain, paged, spec, fleet, wire, "
                        "autoscale, quant, or "
                        "any comma list")
    p.add_argument("--chaos_every", default=5, type=int,
                   help="chaos sweep: inject one transient fault every "
                        "K-th dispatch ATTEMPT, K >= 2 (realized "
                        "per-dispatch rate 1/(K-1): each recovered "
                        "fault adds one retry attempt)")
    p.add_argument("--len_dist", default="short,long,mixed", type=str,
                   help="length-sweep prompt distributions")
    p.add_argument("--prefill_chunk", default=32, type=int,
                   help="length sweep: admit prompts in chunks of N "
                        "(0 = whole-prompt)")
    p.add_argument("--horizons", default="1,4,8", type=str,
                   help="horizon-sweep decode_horizon values")
    p.add_argument("--page_size", default=8, type=int,
                   help="paged sweep: KV page size (columns per page)")
    p.add_argument("--horizon_repeats", default=3, type=int,
                   help="horizon sweep: best-of-N runs per point "
                        "(host-noise suppression)")
    p.add_argument("--spec_ks", default="0,2,4,8", type=str,
                   help="spec sweep: draft lengths k (0 = the "
                        "non-speculative baseline the k>0 points "
                        "must not regress when disarmed)")
    p.add_argument("--spec_modes", default="self,model", type=str,
                   help="spec sweep: draft sources (self = n-gram "
                        "self-drafting, model = draft model)")
    p.add_argument("--draft_model", default="", type=str,
                   help="spec sweep: registry name of the draft "
                        "model ('' = off-TPU smoke uses the target "
                        "as its own draft)")
    p.add_argument("--json_out", default="", type=str,
                   help="record every sweep point as JSON")
    p.add_argument("--dtype", default="bfloat16",
                   choices=["float32", "bfloat16"])
    p.add_argument("--platform", default="tpu", choices=["tpu", "cpu"],
                   help="cpu = rehearsal mode: gpt_tiny, float32 and "
                        "capped sizes on the host platform — counts and "
                        "correctness, never a speed. The default fails "
                        "without a TPU")
    args = p.parse_args()

    from pytorch_multiprocessing_distributed_tpu import models
    from pytorch_multiprocessing_distributed_tpu.serving import (
        init_params)

    import bench

    platform = bench.require_platform(args.platform)[0].platform
    _common.enable_compile_cache()
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    if platform == "cpu":
        args.model = "gpt_tiny"
        args.requests = min(args.requests, 8)
        args.prompt_max = min(args.prompt_max, 24)
        args.new_tokens = min(args.new_tokens, 8)
        args.prefill_chunk = min(args.prefill_chunk, 8)
        dtype = jnp.float32
    model = models.get_model(
        args.model, dtype=dtype,
        attn_impl="flash" if platform == "tpu" else "xla")
    params = init_params(model)
    rng = np.random.default_rng(0)
    s_max = min(model.max_seq_len, args.prompt_max + args.new_tokens)
    # prompts must pass static-fit admission: len + new_tokens <= s_max
    prompt_hi = s_max - args.new_tokens
    if prompt_hi < 1:
        raise SystemExit(
            f"--new_tokens {args.new_tokens} leaves no room for a "
            f"prompt within s_max={s_max} "
            f"(max_seq_len={model.max_seq_len})")
    print(f"# platform={platform} model={args.model} "
          f"requests={args.requests} prompt<= {args.prompt_max} "
          f"new={args.new_tokens} s_max={s_max}")

    record = {"platform": platform, "model": args.model,
              "requests": args.requests, "new_tokens": args.new_tokens,
              "s_max": s_max, "load_sweep": [], "length_sweep": [],
              "horizon_sweep": [], "chaos_sweep": [], "drain_sweep": [],
              "paged_sweep": [], "spec_sweep": [], "fleet_sweep": [],
              "wire_sweep": [], "autoscale_sweep": [],
              "quant_sweep": []}
    sweeps = args.sweep.split(",")

    if "load" in sweeps:
        prompts = [
            rng.integers(0, model.vocab_size,
                         (int(rng.integers(max(1, prompt_hi // 4),
                                           prompt_hi + 1)),)).tolist()
            for _ in range(args.requests)]
        for slots in [int(x) for x in args.slots.split(",")]:
            for load in args.offered.split(","):
                rps = float("inf") if load == "inf" else float(load)
                r = run_point(model, params, prompts, args.new_tokens,
                              slots, rps, s_max)
                r.update(slots=slots, offered=load)
                record["load_sweep"].append(r)
                print(f"slots={slots:3d} offered={load:>5s} req/s  "
                      f"completed={r['completed']:3d}  "
                      f"{r['tokens_per_sec']:9.1f} tok/s  "
                      f"ttft avg={r['ttft_avg_ms']:8.1f} ms "
                      f"p95={r['ttft_p95_ms']:8.1f} ms  "
                      f"occ={r['occupancy_avg']:5.2f} "
                      f"queue={r['queue_depth_avg']:5.2f} "
                      f"(compiles={r['decode_compiles']})", flush=True)

    if "length" in sweeps:
        record["length_sweep"] = run_length_sweep(
            model, params, args, s_max, prompt_hi, rng)

    if "horizon" in sweeps:
        record["horizon_sweep"] = run_horizon_sweep(
            model, params, args, rng)

    if "paged" in sweeps:
        record["paged_sweep"] = run_paged_sweep(model, params, args,
                                                rng)

    if "spec" in sweeps:
        record["spec_sweep"] = run_spec_sweep(model, params, args,
                                              rng)

    if "chaos" in sweeps:
        record["chaos_sweep"] = run_chaos_sweep(model, params, args,
                                                rng)

    if "drain" in sweeps:
        record["drain_sweep"] = run_drain_sweep(model, params, args,
                                                rng)

    if "fleet" in sweeps:
        record["fleet_sweep"] = run_fleet_sweep(model, params, args,
                                                rng)

    if "wire" in sweeps:
        record["wire_sweep"] = run_wire_sweep(model, params, args,
                                              rng)

    if "autoscale" in sweeps:
        record["autoscale_sweep"] = run_autoscale_sweep(
            model, params, args, rng)

    if "quant" in sweeps:
        record["quant_sweep"] = run_quant_sweep(model, params, args,
                                                rng)

    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(record, f, indent=2, sort_keys=True)
        print(f"# wrote {args.json_out}", flush=True)


if __name__ == "__main__":
    main()
