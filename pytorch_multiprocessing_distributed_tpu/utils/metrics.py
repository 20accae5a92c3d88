"""Training/eval metrics (pure JAX) + serving metrics (host meters).

Behavioral parity target for the classification half: ``accuracy`` in
reference ``utils.py:64-77``: returns ``(precision@1 as a percentage,
per-sample correctness mask)`` computed via top-k prediction sets. Here
the computation is a pure jittable function of ``(logits, targets)`` so
it can live *inside* the compiled train step (no host round-trip per
batch, unlike the reference's ``.item()`` calls at ``main.py:113-115``).

:class:`ServingMetrics` is the inference-side counterpart: the serving
engine's per-request latency (TTFT) and per-step throughput/occupancy
aggregation. Host-side by necessity — wall-clock spans host scheduling,
not just device compute — built on the same ``AverageMeter`` the
training loops report through.
"""

from __future__ import annotations

import time
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from ..runtime.scope import host_pauses
from .meters import AverageMeter, PercentileMeter


def topk_accuracy(
    logits: jax.Array, targets: jax.Array, topk: Sequence[int] = (1,)
) -> Tuple[list, jax.Array]:
    """Precision@k for each k in ``topk``.

    Args:
      logits: ``[batch, num_classes]`` raw scores.
      targets: ``[batch]`` integer class labels.
      topk: which k's to report.

    Returns:
      ``(precs, correct)`` where ``precs[i]`` is a scalar percentage for
      ``topk[i]`` and ``correct`` is the ``[maxk, batch]`` bool matrix of
      "prediction j matches the target", mirroring the reference's
      ``correct`` tensor layout (``utils.py:71-72``).
    """
    maxk = max(topk)
    batch_size = targets.shape[0]
    _, pred = jax.lax.top_k(logits, maxk)  # [batch, maxk]
    pred = pred.T  # [maxk, batch] — reference's pred.t()
    correct = pred == targets[None, :]

    precs = []
    for k in topk:
        correct_k = jnp.sum(correct[:k].astype(jnp.float32))
        precs.append(correct_k * (100.0 / batch_size))
    return precs, correct


def accuracy(
    logits: jax.Array, targets: jax.Array, topk: Sequence[int] = (1,)
) -> Tuple[jax.Array, jax.Array]:
    """Reference-shaped ``accuracy``: ``(prec@topk[0] %, squeezed mask)``.

    Mirrors reference ``utils.py:64-77`` which returns ``res[0]`` and
    ``correct.squeeze()``.
    """
    precs, correct = topk_accuracy(logits, targets, topk)
    return precs[0], jnp.squeeze(correct)


def correct_count(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Number of argmax-correct samples in the batch.

    Parity target: the eval accumulation at reference ``main.py:150-151``
    (``pred.eq(target).sum()``). A pure scalar so it can be ``psum``-reduced
    across the data axis — fixing the reference's missing cross-rank
    reduction (its ``reduce_tensor`` at ``main.py:173-177`` is dead code).
    """
    pred = jnp.argmax(logits, axis=-1)
    return jnp.sum((pred == targets).astype(jnp.int32))


class ServingMetrics:
    """Aggregates the serving engine's operational metrics.

    - ``ttft``: seconds from SUBMIT to first token, per request — the
      user-visible latency, so it deliberately includes time spent
      queued behind other requests, not just prefill compute;
    - ``queue_wait``: seconds from submit to admission (the moment
      prefill work starts), per request — ``ttft - queue_wait`` is the
      prefill-side latency, so the pair splits "the pool was busy"
      from "the prompt was long" when tuning slot counts;
    - ``decode_step``: wall seconds per engine decode iteration (one
      drained token block);
    - ``decode_window``: the attention window (in cache columns) each
      decode step ran over — under length-bucketed decode this tracks
      the longest ACTIVE sequence's bucket, and the bench plots step
      time against it;
    - ``horizon`` / ``dispatches`` / ``host_syncs`` /
      ``overlapped_dispatches``: the dispatch-overhead meters. Each
      fused decode horizon is ONE device dispatch and (at drain) ONE
      host sync for H emitted tokens, so ``host_syncs_per_token``
      collapses from 1 toward 1/H — the whole point of horizon decode;
      ``overlapped_dispatches`` counts horizons launched BEFORE the
      previous block's readback (the engine's one-block-deep
      pipeline: in steady state every dispatch but a cold start's, at
      every horizon);
    - ``occupancy``: live slots at each decode step (the utilization
      the slot count should be tuned against);
    - ``queue_depth``: queued requests at each decode step (sustained
      > 0 means the pool, not the arrival rate, is the bottleneck);
    - token/request counters for end-to-end tokens/sec;
    - fault-domain counters (graftfault): ``dispatch_retries``
      (transient errors recovered by bounded retry across EVERY
      engine fault domain — dispatch, readback, prefill, chunk, tok0,
      insert — one counter because they share one retry policy; the
      name keeps the stable metrics surface),
      ``requests_failed`` (poisoned/deadline-evicted requests
      quarantined with their error), ``requests_shed`` (submissions
      rejected at the queue bound — the load-shed half of the
      degradation ladder), ``watchdog_trips`` (hung horizon readbacks
      detected and failed fast), ``horizon_collapses`` (dispatches
      forced to H=1 during a post-fault cooldown). A fault that is
      absorbed must still be VISIBLE — silent recovery is how fleets
      rot;
    - the engine's own loop, metered from inside ``step()`` at its
      entry and exit (:meth:`record_step`): ``step`` (the whole call's
      wall seconds, a percentile meter), ``step_wall_s`` and
      ``step_max_s`` with the longest step's ``step_max_cpu_s`` (this
      thread's CPU seconds) and ``step_max_gc_s`` (collector seconds):
      of the longest step, whether it was the collector, host work, or
      waiting (on the device, a lock, I/O or the machine);
      ``step_gap_s`` / ``step_gap_max_s``, the caller's time from the
      previous step's exit (or this object's construction) to the
      next entry; ``loop_s`` from construction to the last step's exit
      (= ``step_wall_s + step_gap_s``); and the process's collector
      activity over that same interval (``gc_pause_s``,
      ``gc_collections``, ``gc_gen2_collections``, and
      ``gc_pause_max_s``: the most collector seconds inside one step
      or one gap, one long collection where there was one), from
      ``runtime.scope.host_pauses()``, based at construction and
      advanced at step exits only, never at ``snapshot()``. The
      collector is the PROCESS's: several engines in one process each
      book the pauses that fell inside their own loop, so their sums
      may count one pause more than once;
    - ``prefill_dispatches`` / ``chunk_dispatches``: the prompt
      programs dispatched (a whole prompt; one chunk), in admission
      and in ``prefill_detached``; ``prompt_dispatches`` their sum.

    The latency meters (``ttft``/``queue_wait``/``decode_step``, plus
    per-request generated-token counts) are
    :class:`~.meters.PercentileMeter`\\ s (graftscope): ``snapshot()``
    reports p50/p90/p95/p99 beside the averages — p95/p99 TTFT is THE
    serving SLO, and an average actively hides a broken tail — and
    :meth:`snapshot_delta` reports the same stats over just the window
    since the previous delta (steady-state dashboards; run-total
    averages smear warm-up over everything). ``snapshot()`` flattens
    everything into the plain dict the CLI prints, the stats endpoint
    exposes, and the benchmark records.
    """

    def __init__(self) -> None:
        self.ttft = PercentileMeter()
        self.queue_wait = PercentileMeter()
        self.decode_step = PercentileMeter()
        self.request_tokens = PercentileMeter()
        self.decode_window = AverageMeter()
        self.horizon = AverageMeter()
        self.occupancy = AverageMeter()
        self.queue_depth = AverageMeter()
        self.tokens_generated = 0
        # decode (post-first) tokens, accumulated from DRAINED blocks —
        # the authoritative decode-token count. The old derivation
        # ``tokens_generated - ttft.count`` silently miscounts the
        # moment first-token samples and TTFT samples decouple (e.g. a
        # latency recorded for a request that failed before its first
        # token); an explicit counter cannot.
        self.decode_tokens = 0
        self.requests_completed = 0
        self.dispatches = 0
        self.host_syncs = 0
        self.overlapped_dispatches = 0
        self.dispatch_retries = 0
        self.requests_failed = 0
        self.requests_shed = 0
        self.requests_redelivered = 0
        self.watchdog_trips = 0
        self.horizon_collapses = 0
        # graftpage counters: prefix-cache outcomes per admission and
        # admissions deferred for page pressure (the head HELD queued
        # — never failed — until running work frees pages)
        self.prefix_hits = 0
        self.prefix_partial_hits = 0
        self.prefix_misses = 0
        self.page_holds = 0
        # graftspec counters: draft tokens proposed vs accepted by the
        # batched verify pass, and the per-pass accepted-length
        # percentiles (accept_len p50/p95/p99 — the distribution the
        # draft source's quality shows up in; tokens/target-step =
        # 1 + accept_len mean)
        self.tokens_drafted = 0
        self.tokens_accepted = 0
        self.accept_len = PercentileMeter()
        # routed-expert load of the decode steps (families with
        # experts): assignments computed by the experts held here,
        # assignments routed to experts this chip does not hold (a
        # share of an expert-parallel deployment; 0 when every expert
        # is held), the busiest held expert's load over the held
        # experts' mean load, averaged over layers and blocks, the rows
        # the grouped matmuls were given (ops/moe.py::row_ladder: a
        # static bound over the held rows), and how many (layer, block)
        # entries there were and how many of them ran at full width
        # (every assignment given, held or not)
        self.moe_assignments = 0
        self.moe_assignments_elsewhere = 0
        self.moe_load = AverageMeter()
        self.moe_rows_given = 0
        self.moe_layer_blocks = 0
        self.moe_full_width = 0
        # two kinds of layer in one cache manager (a family with
        # sliding-window layers): pages held by kind, the bytes both
        # pools hold over what ONE pool of every layer would hold for
        # the same requests, and ring pages written over since the
        # first sample (each a page that fell out of the window's
        # reach), sampled once a drained block
        self.kv_pages_full = AverageMeter()
        self.kv_pages_sliding = AverageMeter()
        self.kv_bytes_held = AverageMeter()
        self.kv_bytes_undivided = AverageMeter()
        self.kv_ring_pages_overwritten = 0
        self._ring_base = None
        # the engine's own loop (record_step)
        self.step = PercentileMeter()
        self.step_wall_s = 0.0
        self.step_max_s = 0.0
        self.step_max_cpu_s = 0.0
        self.step_max_gc_s = 0.0
        self.step_gap_s = 0.0
        self.step_gap_max_s = 0.0
        self.gc_pause_max_s = 0.0
        self.prefill_dispatches = 0
        self.chunk_dispatches = 0
        self._t_base = self._t_exit = time.perf_counter()
        self._gc_base = self._gc_exit = host_pauses()
        self._elapsed = 0.0
        self._occupancy_max = 0
        self._queue_wait_max = 0.0
        self._delta_base: dict = {}

    def bound_samples(self, max_samples: int) -> None:
        """Cap every percentile meter's sample retention (graftfleet):
        a LIVE server scraped forever must not grow one float per
        request without bound. Percentiles stay exact over the most
        recent ``max_samples``; counters and averages stay run-total.
        The CLIs arm this whenever ``--stats_port`` puts these meters
        behind a long-running stats server; tests and short benches
        keep the uncapped default."""
        for meter in (self.ttft, self.queue_wait, self.decode_step,
                      self.request_tokens, self.accept_len, self.step):
            meter.bound(max_samples)

    def record_step(self, t_in: float, gc_in: tuple, cpu_s: float,
                    t_out: float, gc_out: tuple) -> None:
        """One ``ServingEngine.step()`` call: its entry and exit on
        ``time.perf_counter``, its thread's CPU seconds, and
        :func:`~..runtime.scope.host_pauses` read at entry and exit."""
        gap = t_in - self._t_exit
        self.step_gap_s += gap
        self.step_gap_max_s = max(self.step_gap_max_s, gap)
        wall = t_out - t_in
        gc_step = gc_out[3] - gc_in[3]
        self.step.update(wall)
        self.step_wall_s += wall
        if wall > self.step_max_s:
            self.step_max_s, self.step_max_cpu_s, self.step_max_gc_s = (
                wall, cpu_s, gc_step)
        self.gc_pause_max_s = max(self.gc_pause_max_s, gc_step,
                                  gc_in[3] - self._gc_exit[3])
        self._t_exit, self._gc_exit = t_out, gc_out

    def record_prompt_dispatch(self, chunk: bool) -> None:
        """One prompt program dispatched: a chunk, or a whole prompt."""
        if chunk:
            self.chunk_dispatches += 1
        else:
            self.prefill_dispatches += 1

    def record_first_token(self, ttft_seconds: float) -> None:
        self.ttft.update(ttft_seconds)
        self.tokens_generated += 1

    def record_admission(self, queue_wait_seconds: float) -> None:
        """Stamp when a request leaves the queue and its prefill work
        begins — the queue-wait half of TTFT."""
        self.queue_wait.update(queue_wait_seconds)
        self._queue_wait_max = max(self._queue_wait_max,
                                   queue_wait_seconds)

    def record_dispatch(self, horizon: int,
                        overlapped: bool = False) -> None:
        """One device dispatch of a fused ``horizon``-step decode
        program; ``overlapped`` = launched before the previous block's
        readback (no host sync sat between the two programs)."""
        self.dispatches += 1
        self.horizon.update(horizon)
        if overlapped:
            self.overlapped_dispatches += 1

    def record_decode_step(self, seconds: float, tokens: int,
                           occupancy: int, queue_depth: int,
                           window: int = 0) -> None:
        """One drained token block: ``seconds`` of engine decode wall
        (dispatch + drain), ``tokens`` realized tokens, and the block's
        ONE host sync."""
        self.decode_step.update(seconds)
        self.host_syncs += 1
        if window:
            self.decode_window.update(window)
        self.occupancy.update(occupancy)
        self._occupancy_max = max(self._occupancy_max, occupancy)
        self.queue_depth.update(queue_depth)
        self.tokens_generated += tokens
        self.decode_tokens += tokens
        self._elapsed += seconds

    @property
    def decode_elapsed_s(self) -> float:
        """Accumulated decode wall seconds (the productive-time
        numerator graftroute's per-replica goodput fraction uses)."""
        return self._elapsed

    def record_completion(self, tokens: int = 0) -> None:
        """``tokens`` = the finished request's generated-token count
        (tokens/request is a percentile the capacity planner reads)."""
        self.requests_completed += 1
        if tokens:
            self.request_tokens.update(tokens)

    # ---- fault-domain counters (graftfault) ----
    def record_retry(self) -> None:
        """One transient error absorbed by bounded retry, in ANY of
        the engine's fault domains (dispatch, readback, prefill,
        chunk, tok0, insert — all share the one retry policy)."""
        self.dispatch_retries += 1

    def record_failure(self) -> None:
        """One request quarantined (poisoned prefill/insert, or its
        deadline expired) — evicted as FAILED, engine kept serving."""
        self.requests_failed += 1

    def record_shed(self) -> None:
        """One submission rejected at the queue bound (QueueFull) or
        at a closed (DRAINING/DEAD) admission door."""
        self.requests_shed += 1

    def record_redelivery(self) -> None:
        """One journaled unfinished request re-submitted after a
        supervised restart (graftheal) — recovery work is visible,
        never mistaken for fresh traffic."""
        self.requests_redelivered += 1

    def record_watchdog_trip(self) -> None:
        """One hung horizon readback detected and failed fast."""
        self.watchdog_trips += 1

    def record_horizon_collapse(self) -> None:
        """One dispatch degraded to H=1 during a post-fault cooldown."""
        self.horizon_collapses += 1

    # ---- paged-KV / prefix-cache counters (graftpage) ----
    def record_prefix_outcome(self, hit) -> None:
        """One paged admission's prefix-cache outcome: ``"full"``
        (prompt fully cached — no prefill compute), ``"partial"``
        (leading pages reused, suffix prefilled), or None (miss)."""
        if hit == "full":
            self.prefix_hits += 1
        elif hit == "partial":
            self.prefix_partial_hits += 1
        else:
            self.prefix_misses += 1

    # ---- speculative-decode counters (graftspec) ----
    def record_spec(self, drafted: int, accept_lens) -> None:
        """One drained speculative block: ``drafted`` draft tokens
        proposed across its active verify passes, ``accept_lens`` the
        per-(pass, slot) accepted-draft counts (each in
        ``[0, draft_k]``; emitted tokens per pass = accepted + 1)."""
        self.tokens_drafted += int(drafted)
        for a in accept_lens:
            self.tokens_accepted += int(a)
            self.accept_len.update(float(a))

    def record_moe(self, counts) -> None:
        """One drained block's per-layer expert assignment counts
        (``[layers, held + 2]``: a column a held expert, then the
        assignments routed to experts held elsewhere, last the rows
        the grouped matmuls were given; they came back in the token
        block's own readback). Dropless: a layer's assignments sum to
        tokens x top-k, and a layer that was given that many rows ran
        at full width in every step of the block."""
        counts, elsewhere, given = (counts[:, :-2], counts[:, -2],
                                    counts[:, -1])
        self.moe_assignments += int(counts.sum())
        self.moe_assignments_elsewhere += int(elsewhere.sum())
        self.moe_rows_given += int(given.sum())
        self.moe_layer_blocks += len(given)
        self.moe_full_width += int(
            (given == counts.sum(axis=1) + elsewhere).sum())
        means = counts.mean(axis=1)
        live = means > 0
        if live.any():
            self.moe_load.update(
                float((counts.max(axis=1)[live] / means[live]).mean()))

    def record_kv_pages(self, pool) -> None:
        """One drained block's sample of a two-kind ``PagePool``
        (host mirror only: no device read)."""
        usage = pool.kv_usage()
        self.kv_pages_full.update(usage["full"])
        self.kv_pages_sliding.update(usage["sliding"])
        self.kv_bytes_held.update(usage["bytes_held"])
        self.kv_bytes_undivided.update(usage["bytes_undivided"])
        if self._ring_base is None:
            self._ring_base = pool.ring_pages_overwritten
        self.kv_ring_pages_overwritten = (pool.ring_pages_overwritten
                                          - self._ring_base)

    def record_page_hold(self) -> None:
        """One admission deferred because the page pool could not
        cover the FIFO head's demand — the head stays QUEUED (held,
        not failed) until running work frees pages. Counted at the
        TRANSITION into the held state: one deferred admission is one
        hold, however many steps the wait lasts."""
        self.page_holds += 1

    def snapshot(self) -> dict:
        # decode tokens come from DRAINED blocks (the explicit
        # counter), never re-derived as tokens_generated - ttft.count:
        # that subtraction breaks the moment a TTFT-family sample
        # exists without a first token behind it (a request failed
        # before its first token whose latency-to-failure is recorded)
        decode_tokens = self.decode_tokens
        decode_tps = (0.0 if self._elapsed == 0
                      else decode_tokens / self._elapsed)
        snap = {
            "requests_completed": self.requests_completed,
            "tokens_generated": self.tokens_generated,
            "decode_tokens": decode_tokens,
            "ttft_avg_s": self.ttft.avg,
            "ttft_last_s": self.ttft.val,
            "queue_wait_avg_s": self.queue_wait.avg,
            "queue_wait_max_s": self._queue_wait_max,
            "decode_step_avg_s": self.decode_step.avg,
            "decode_window_avg": self.decode_window.avg,
            "decode_horizon_avg": self.horizon.avg,
            "decode_dispatches": self.dispatches,
            "decode_host_syncs": self.host_syncs,
            "host_syncs_per_token": (0.0 if decode_tokens <= 0 else
                                     self.host_syncs / decode_tokens),
            "overlapped_dispatches": self.overlapped_dispatches,
            "decode_tokens_per_sec": decode_tps,
            "occupancy_avg": self.occupancy.avg,
            "occupancy_max": self._occupancy_max,
            "queue_depth_avg": self.queue_depth.avg,
            "decode_steps": self.decode_step.count,
            "dispatch_retries": self.dispatch_retries,
            "requests_failed": self.requests_failed,
            "requests_shed": self.requests_shed,
            "requests_redelivered": self.requests_redelivered,
            "watchdog_trips": self.watchdog_trips,
            "horizon_collapses": self.horizon_collapses,
            "prefix_hits": self.prefix_hits,
            "prefix_partial_hits": self.prefix_partial_hits,
            "prefix_misses": self.prefix_misses,
            "page_holds": self.page_holds,
            # graftspec: verify passes = accept_len samples; tokens
            # per target-model step is THE speculative headline (1.0
            # = non-speculative; every point above it is a token the
            # bandwidth-bound weight stream yielded for free)
            "spec_tokens_drafted": self.tokens_drafted,
            "spec_tokens_accepted": self.tokens_accepted,
            "spec_verify_passes": self.accept_len.count,
            "spec_accept_rate": (
                0.0 if self.tokens_drafted == 0
                else self.tokens_accepted / self.tokens_drafted),
            "spec_accepted_per_target_step": (
                0.0 if self.accept_len.count == 0
                else 1.0 + self.accept_len.avg),
            "moe_assignments": self.moe_assignments,
            "moe_assignments_elsewhere": self.moe_assignments_elsewhere,
            "moe_held_share": (
                0.0 if self.moe_assignments == 0
                else self.moe_assignments
                / (self.moe_assignments + self.moe_assignments_elsewhere)),
            "moe_load_max_over_mean": self.moe_load.avg,
            "moe_rows_given": self.moe_rows_given,
            "moe_rows_given_over_held": (
                0.0 if self.moe_assignments == 0
                else self.moe_rows_given / self.moe_assignments),
            "moe_full_width_share": (
                0.0 if self.moe_layer_blocks == 0
                else self.moe_full_width / self.moe_layer_blocks),
            "kv_pages_held_full": self.kv_pages_full.avg,
            "kv_pages_held_sliding": self.kv_pages_sliding.avg,
            "kv_bytes_held_over_undivided": (
                0.0 if self.kv_bytes_undivided.avg == 0
                else self.kv_bytes_held.avg / self.kv_bytes_undivided.avg),
            "kv_ring_pages_overwritten": self.kv_ring_pages_overwritten,
            "steps": self.step.count,
            "step_wall_s": self.step_wall_s,
            "step_max_s": self.step_max_s,
            "step_max_cpu_s": self.step_max_cpu_s,
            "step_max_gc_s": self.step_max_gc_s,
            "step_gap_s": self.step_gap_s,
            "step_gap_max_s": self.step_gap_max_s,
            "loop_s": self._t_exit - self._t_base,
            "gc_pause_s": self._gc_exit[3] - self._gc_base[3],
            "gc_pause_max_s": self.gc_pause_max_s,
            "gc_collections": (sum(self._gc_exit[:3])
                               - sum(self._gc_base[:3])),
            "gc_gen2_collections": self._gc_exit[2] - self._gc_base[2],
            "prefill_dispatches": self.prefill_dispatches,
            "chunk_dispatches": self.chunk_dispatches,
            "prompt_dispatches": (self.prefill_dispatches
                                  + self.chunk_dispatches),
        }
        # graftscope percentile telemetry: the tail IS the SLO
        for name, meter in (("ttft", self.ttft),
                            ("queue_wait", self.queue_wait),
                            ("decode_step", self.decode_step),
                            ("step", self.step)):
            for q, v in meter.percentiles((50, 90, 95, 99)).items():
                snap[f"{name}_{q}_s"] = v
        for q, v in self.request_tokens.percentiles((50, 95)).items():
            snap[f"tokens_per_request_{q}"] = v
        snap["tokens_per_request_avg"] = self.request_tokens.avg
        for q, v in self.accept_len.percentiles((50, 95, 99)).items():
            snap[f"accept_len_{q}"] = v
        return snap

    # counters whose deltas snapshot_delta reports
    _DELTA_COUNTERS = (
        "tokens_generated", "decode_tokens", "requests_completed",
        "requests_failed", "requests_shed", "requests_redelivered",
        "dispatches", "host_syncs",
        "dispatch_retries", "horizon_collapses", "watchdog_trips",
        "tokens_drafted", "tokens_accepted",
    )

    def snapshot_delta(self) -> dict:
        """Steady-state window: counter deltas and latency percentiles
        over ONLY the activity since the previous ``snapshot_delta``
        call (the first call's window starts at construction). This is
        the stats a dashboard scrapes — run-total averages smear
        warm-up compiles over the steady state; a window does not."""
        out = {}
        elapsed = self._elapsed - self._delta_base.get("_elapsed", 0.0)
        for key in self._DELTA_COUNTERS:
            cur = getattr(self, key)
            out[f"window_{key}"] = cur - self._delta_base.get(key, 0)
            self._delta_base[key] = cur
        self._delta_base["_elapsed"] = self._elapsed
        out["window_elapsed_s"] = elapsed
        out["window_decode_tokens_per_sec"] = (
            0.0 if elapsed == 0
            else out["window_decode_tokens"] / elapsed)
        for name, meter in (("ttft", self.ttft),
                            ("queue_wait", self.queue_wait),
                            ("decode_step", self.decode_step)):
            for stat, v in meter.window_stats((50, 95, 99)).items():
                key = (f"window_{name}_count" if stat == "count"
                       else f"window_{name}_{stat}_s")
                out[key] = v
            meter.advance_window()
        return out
