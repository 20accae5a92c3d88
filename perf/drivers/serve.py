"""Serving cells: one ``ServingEngine`` replica under generated load.

The engine is built as ``serve_lm.py --random_init`` builds it, every
option written out in the cell file. The harness is the caller of
``engine.step()``: it offers requests (closed loop: each client submits
its next request the moment its last one finishes; open loop: on a
schedule, timed from when each request was due), receives token events,
and stamps each with the host clock as it gets them.
"""

from __future__ import annotations

import gc
import time
from typing import Dict, List, Optional

import numpy as np

from .. import families, harness, stats, trace_reduce
from ..spans import Recording
from ..traffic import OpenLoop, ServeTraffic

OPTIONS = {
    "dtype": "bfloat16",
    "max_slots": None,
    "s_max": 1024,
    "kv_layout": "paged",
    "kv_dtype": "model",
    "page_size": 16,
    "num_pages": None,          # None = dense worst-case parity
    "decode_horizon": 1,
    "decode_attn": "auto",
    "decode_buckets": None,     # None = powers of two up to s_max
    "prefill_chunk": None,
    "prefix_cache": 0,
    "draft_k": 0,
    "max_queue": None,
    "temperature": 0.0,
    "top_k": 0,
    "top_p": 0.0,
    "eos_id": None,
    "trace_seconds": 2.0,       # the traced tail of a --trace 1 run
}
TRAFFIC_KEYS = {"kind", "loop", "clients", "stagger_per_step", "first_turn",
                "warmup_completions", "warmup_seconds", "arrivals",
                "prompt_len", "output_len", "size_seed", "pool_requests",
                "what"}
CHECK_REQUESTS = 8


class Client:
    """One in-flight request as the harness sees it."""

    __slots__ = ("request", "due", "last_token_t", "seen")

    def __init__(self, request, due: Optional[float]):
        self.request = request
        self.due = due              # open loop: absolute due time
        self.last_token_t: Optional[float] = None
        self.seen = 0               # token events received so far


def build_engine(cell: harness.Cell, platform: str):
    """(family, model, options, make): ``make(params)`` is
    ``serve_lm.py``'s ``ServingEngine(...)`` call with the cell file's
    options, the family's own passed as keywords beside the driver's."""
    from pytorch_multiprocessing_distributed_tpu.serving import (
        ServingEngine)

    family = families.load(cell.config)
    opts = harness.take_options(
        cell.options, OPTIONS, f"workloads/{cell.name}",
        family.ENGINE_OPTIONS)
    model = family.build_model(cell.config, opts["dtype"], platform)
    paged = opts["kv_layout"] == "paged"

    def make(params):
        return ServingEngine(
            model, params, max_slots=int(opts["max_slots"]),
            s_max=opts["s_max"], max_queue=opts["max_queue"],
            temperature=opts["temperature"], top_k=opts["top_k"],
            top_p=opts["top_p"], eos_id=opts["eos_id"],
            decode_buckets=opts["decode_buckets"],
            prefill_chunk=opts["prefill_chunk"],
            decode_horizon=opts["decode_horizon"],
            decode_attn=opts["decode_attn"], kv_layout=opts["kv_layout"],
            kv_dtype=opts["kv_dtype"],
            page_size=opts["page_size"] if paged else None,
            num_pages=opts["num_pages"] if paged else None,
            prefix_cache=opts["prefix_cache"] if paged else 0,
            draft_k=opts["draft_k"],
            **{k: opts[k] for k in family.ENGINE_OPTIONS})
    return family, model, opts, make


def warmup_requests(engine, mix: dict) -> List[tuple]:
    """(prompt_len, max_new_tokens) of solo requests that between them
    compile every program the mix's lengths can reach: one per prefill
    bucket, one per decode window."""
    from pytorch_multiprocessing_distributed_tpu.serving.scheduler import (
        bucket_length)

    s_max = engine.pool.s_max
    lo, hi = (int(mix["prompt_len"][k]) for k in ("min", "max")) \
        if mix["prompt_len"]["dist"] != "fixed" else \
        (int(mix["prompt_len"]["value"]),) * 2
    out_hi = (int(mix["output_len"]["value"])
              if mix["output_len"]["dist"] == "fixed"
              else int(mix["output_len"]["max"]))
    longest = min(s_max, hi + out_hi)
    plan = []
    for bucket in sorted({bucket_length(n, engine.min_bucket, s_max)
                          for n in range(lo, hi + 1)}):
        plan.append((min(bucket, hi), 2))
    prev = 0
    for window in engine.decode_buckets:
        # a decode step at position p runs the smallest window > p
        if prev < longest and window > lo:
            prompt = min(max(prev, lo), hi)
            plan.append((prompt, min(max(2, prev - prompt + 2),
                                     s_max - prompt)))
        prev = window
    return plan


def run(cell: harness.Cell, *, seed: int, seconds: float, trace: bool,
        t_process: float, rec: Recording, allow_cpu: bool = False
        ) -> dict:
    from pytorch_multiprocessing_distributed_tpu.serving import QueueFull
    from pytorch_multiprocessing_distributed_tpu.serving.scheduler import (
        DONE, FAILED)
    from pytorch_multiprocessing_distributed_tpu.utils.compile_cache import (
        CompileLog, enable_compilation_cache)
    from pytorch_multiprocessing_distributed_tpu.utils.metrics import (
        ServingMetrics)

    enable_compilation_cache()
    compile_log = CompileLog()
    devices = harness.require_devices(cell.chips, allow_cpu)
    mix = cell.traffic
    unknown = set(mix) - TRAFFIC_KEYS
    if unknown:
        raise harness.ManifestError(
            f"serve traffic has unknown keys {sorted(unknown)}")
    family, model, opts, make = build_engine(cell, devices[0].platform)
    params = family.init_params(model, seed)
    engine = make(params)
    pool = engine.pool
    work_dtypes = {"dtype": opts["dtype"],
                   "kv_dtype": (opts["dtype"] if opts["kv_dtype"] == "model"
                                else opts["kv_dtype"])}
    traffic = ServeTraffic(mix, model.vocab_size, pool.s_max, seed)
    closed = mix["loop"] == "closed"
    n_clients = (int(opts["max_slots"]) if mix.get("clients") == "max_slots"
                 else int(mix.get("clients") or 0))

    # where spans and samples go: the run's recording, or (the traced
    # tail) one that is thrown away
    sink = {"rec": rec, "work": _no_work()}
    if trace:
        _wrap_engine(engine, sink)

    live: Dict[object, Client] = {}
    finished: List[Client] = []
    state = {"t0": None, "rejected": 0, "submitted": 0, "tokens": 0,
             "pages_peak": 0}

    def submit(spec, due=None):
        try:
            request = engine.submit(spec.prompt.tolist(),
                                    spec.max_new_tokens)
        except (QueueFull, ValueError):
            state["rejected"] += 1
            return
        live[request.uid] = Client(request, due)
        state["submitted"] += 1

    def step():
        with sink["rec"].span("serve.step"):
            events = engine.step()
        now = time.perf_counter()
        in_window = state["t0"] is not None
        for request, _token, done in events:
            client = live[request.uid]
            if (in_window and client.last_token_t is not None
                    and client.last_token_t >= state["t0"]):
                sink["rec"].sample("serve.itl",
                                   now - client.last_token_t)
            client.last_token_t = now
            if in_window:
                state["tokens"] += 1
                # what this token required: the prefill samples a
                # request's first, a decode step each later one, its
                # query attending the prompt and every token so far
                if client.seen == 0:
                    sink["work"]["prompt_lens"].append(
                        len(request.prompt))
                else:
                    sink["work"]["context_lens"].append(
                        len(request.prompt) + client.seen)
            client.seen += 1
            if done:
                finished.append(live.pop(request.uid))
        for uid in [u for u, c in live.items()
                    if c.request.state == FAILED]:
            finished.append(live.pop(uid))   # quarantined: no event
        state["pages_peak"] = max(state["pages_peak"],
                                  getattr(pool, "pages_in_use", 0))
        return now

    # ---- warm-up 1: every program the mix's lengths reach, solo --------
    for prompt_len, new in warmup_requests(engine, mix):
        spec = traffic.take()
        prompt = np.resize(spec.prompt, prompt_len)
        submit(spec._replace(prompt=prompt, max_new_tokens=new))
        while engine.in_flight:
            step()

    # the allocator's peak does not see a running program's
    # temporaries, and the decode program's are the largest thing on
    # the chip: take them from the engine's own analysis of the widest
    # window's program (one more lowering, a compile-cache hit)
    t_analysis = time.perf_counter()
    memory = engine.decode_program_analysis(
        engine.decode_buckets[-1], 1).get("memory") or {}
    program_peak = memory.get("peak_bytes", 0)
    analysis_s = time.perf_counter() - t_analysis

    # ---- warm-up 2: bring the loop to its steady mix --------------------
    def top_up():
        """Closed loop: every idle client submits its next request."""
        with sink["rec"].span("serve.submit"):
            while len(live) < target["clients"]:
                spec = traffic.take()
                if target["started"] < n_clients:
                    if mix.get("first_turn") == "uniform_age":
                        k = target["started"] % traffic.n
                        spec = spec._replace(max_new_tokens=max(2, round(
                            spec.max_new_tokens
                            * traffic.first_turn_fraction[k])))
                    target["started"] += 1
                submit(spec)

    target = {"clients": 0, "started": 0}
    open_loop = None
    if closed:
        done_before = len(finished)
        while (target["clients"] < n_clients
               or len(finished) - done_before
               < int(mix["warmup_completions"])):
            # clients start staggered, so the window opens on a batch
            # of mixed ages, not on one cohort in lockstep
            target["clients"] = min(
                n_clients,
                target["clients"] + int(mix["stagger_per_step"]))
            top_up()
            step()
    else:
        open_loop = OpenLoop(traffic)
        t_loop = time.perf_counter()
        while time.perf_counter() - t_loop < float(mix["warmup_seconds"]):
            _offer(open_loop, t_loop, submit, sink)
            step()

    # ---- the measured window ---------------------------------------------
    rec.reset()
    sink["work"] = window_work = _no_work()
    engine.metrics = ServingMetrics()
    finished_before = len(finished)
    compiles_before = len(compile_log.programs)
    submitted_before, rejected_before = (state["submitted"],
                                         state["rejected"])
    state["pages_peak"] = 0
    t_start = state["t0"] = time.perf_counter()
    setup_s = time.time() - t_process
    now = t_start
    while now - t_start < seconds:
        if closed:
            top_up()
        else:
            _offer(open_loop, t_loop, submit, sink)
        now = step()
    t_end, elapsed = now, now - t_start
    window_metrics = engine.metrics
    compiles_in_window = len(compile_log.programs) - compiles_before
    attempted = state["submitted"] - submitted_before
    rejected = state["rejected"] - rejected_before
    window_tokens = state["tokens"]
    pages_peak = state["pages_peak"]
    done_in_window = finished[finished_before:]

    # ---- the traced tail ---------------------------------------------------
    if trace:
        engine.metrics = ServingMetrics()
        sink["rec"] = Recording()
        sink["work"] = tail_work = _no_work()
        tail_start = time.perf_counter()
        steps = 0
        with trace_reduce.traced() as trace_dir:
            while (time.perf_counter() - tail_start
                   < float(opts["trace_seconds"])):
                if closed:
                    top_up()
                else:
                    _offer(open_loop, t_loop, submit, sink)
                step()
                steps += 1
        rec.trace = trace_reduce.reduce_dir(trace_dir, chips=1)
        rec.trace["steps"] = steps
        # what the traced steps required of each kernel a metric asks
        # about, from the lengths of the slots that were live in them
        harness.record_kernel_work(
            rec, cell, family, {**tail_work, **work_dtypes})

    # ---- what the window's requests saw -------------------------------------
    state["t0"] = None
    first_tokens = [
        c for c in list(live.values()) + finished
        if c.request.first_token_time is not None
        and t_start <= c.request.submit_time
        and c.request.first_token_time <= t_end]
    for c in first_tokens:
        r = c.request
        origin = c.due if c.due is not None else r.submit_time
        rec.sample("serve.ttft", r.first_token_time - origin)
        rec.sample("serve.queue_wait", r.admit_time - origin)
    failed_requests = [c for c in done_in_window
                       if c.request.state != DONE]
    wrong_length = [c for c in done_in_window if c.request.state == DONE
                    and len(c.request.tokens) != c.request.max_new_tokens]
    rec.series["serve.decode_step"] = list(
        window_metrics.decode_step.values)
    rec.count("serve.occupancy_avg_share",
              window_metrics.occupancy.avg / int(opts["max_slots"]))
    rec.count("serve.queue_depth_avg", window_metrics.queue_depth.avg)
    rec.count("serve.host_syncs", window_metrics.host_syncs)
    rec.count("serve.decode_tokens", window_metrics.decode_tokens)
    rec.count("serve.dispatches", window_metrics.dispatches)
    # and every number the engine kept over the window, as engine.<key>
    for key, value in window_metrics.snapshot().items():
        if isinstance(value, (int, float)):
            rec.count(f"engine.{key}", value)
    if hasattr(pool, "num_pages"):
        rec.count("serve.pages_peak_share",
                  pages_peak / (pool.num_pages - 1))
    if open_loop is not None and open_loop.lateness_s:
        rec.series["serve.generator_lateness"] = open_loop.lateness_s
    # the whole model's required operations over the window, for the
    # step's share of the chip's peak beside the kernels' rooflines
    model_ops = [family.kernel_work(cell.config, part,
                                    {**window_work, **work_dtypes})
                 for part in ("forward.prefill", "forward.decode")]
    if all(model_ops):
        rec.count("serve.model_flops_per_s",
                  sum(w["ops"] for w in model_ops) / elapsed)
    harness.record_peaks(rec, devices)
    summary = compile_log.summary()
    compile_log.close()
    rec.count("compile.seconds", summary["compile_s"])
    rec.count("compile.programs", summary["compiles"])
    rec.count("compile.cache_hits", summary["cache_hits"])

    # ---- what the reference will be given: a sample of the streams -----
    ok = [c.request for c in done_in_window if c.request.state == DONE]
    picks = (np.random.default_rng(int(seed) & 0xFFFFFFFF).choice(
        len(ok), size=min(CHECK_REQUESTS, len(ok)), replace=False)
        if ok else [])
    n_failed = len(failed_requests) + rejected
    compared = [
        {"what": "wrong_length_streams", "value": len(wrong_length),
         "limit": 0},
        {"what": "requests_failed_or_rejected", "value": n_failed,
         "limit": 0},
        {"what": "compiles_in_window", "value": compiles_in_window,
         "limit": 0},
        {"what": "window_finished_no_request", "value": int(not ok),
         "limit": 0}]

    itl = rec.series.get("serve.itl", [])
    ttft = rec.series.get("serve.ttft", [])
    end_to_end = {"serve_tokens_per_s": window_tokens / elapsed,
                  "setup_s": setup_s}
    for name, values in (("ttft_ms_p95", ttft), ("itl_ms_p95", itl)):
        p95 = stats.supported_percentile(values, 95)
        if p95 is not None:
            end_to_end[name] = p95 * 1000.0
    checks = {
        "decode_program_memory": memory,
        "decode_program_analysis_s": analysis_s,
        "checked_requests": len(picks),
        "wrong_length_streams": len(wrong_length),
        "requests_failed": len(failed_requests),
        "requests_rejected": rejected,
        "requests_finished": len(ok),
        "compiles_in_window": compiles_in_window,
        "window_s": elapsed, "tokens": window_tokens,
        "ttft_samples": len(ttft), "itl_samples": len(itl),
        "decode_steps": len(rec.series["serve.decode_step"]),
        "max_slots": int(opts["max_slots"]),
        "decode_attn": engine.decode_attn,
        "prefill_attn": model.attn_impl,
        "decode_windows": list(engine.decode_windows),
        "prefill_compiles": engine.prefill_compiles,
        "compile": summary,
    }

    # ---- the comparison, with the engine, its pool and its programs gone
    # and the chip's peak read: the reference has the chip less the weights.
    # (Not a function of its own: one more frame under the engine's
    # tracing cost the six decode programs 0.8-1.5 s each, PERF.md.)
    allocator_peak = harness.allocator_peak_bytes(devices)
    sampled, s_max = [ok[i] for i in picks], pool.s_max
    del engine, pool        # the closures above held them; not used again
    gc.collect()            # a traced run's wrapped engine is a cycle
    judged = family.compare_streams(cell.config, params, sampled, s_max)
    compared = judged["compared"] + compared
    return {
        "correct": harness.all_within(compared),
        "attempted": attempted,
        "failed": n_failed,
        "end_to_end": end_to_end,
        "devices": devices,
        "program_peak_bytes": program_peak,
        "allocator_peak_bytes": allocator_peak,
        "checks": {**judged["checks"], **checks,
                   "reference": families.reference_path(family),
                   "compared": compared},
    }


def _no_work() -> dict:
    """Lengths of what was served, for the family's ``kernel_work``:
    one prompt length a prefill, one context length a decoded token."""
    return {"prompt_lens": [], "context_lens": []}


def _offer(open_loop: OpenLoop, t_loop: float, submit, sink: dict):
    with sink["rec"].span("serve.submit"):
        for spec in open_loop.due(time.perf_counter() - t_loop):
            submit(spec, due=t_loop + spec.due_s)


def _wrap_engine(engine, sink: dict) -> None:
    """Traced runs only: spans around the engine's own phases of a
    step, so an idle gap can be laid at scheduling and prefill, at
    dispatch, or at the read-back. Wrapped from here, on this one
    instance; a phase the engine no longer has is simply not wrapped
    (its gaps then fall under ``serve.step``)."""
    for attr, name in (("_admit", "serve.admit_prefill"),
                       ("_dispatch", "serve.dispatch"),
                       ("_drain_one", "serve.readback")):
        inner = getattr(engine, attr, None)
        if inner is None:
            continue

        def wrapped(*args, _inner=inner, _name=name, **kwargs):
            with sink["rec"].span(_name):
                return _inner(*args, **kwargs)

        setattr(engine, attr, wrapped)
