"""Benchmark harness — prints ONE JSON line for the driver.

The reference publishes no numbers (BASELINE.md), so this harness IS the
benchmark the framework is judged on. Configs mirror BASELINE.json:
``resnet50_imagenet`` (config #2, THE NORTH STAR and the default: global
batch 256, 224x224, bf16), ``resnet18_cifar`` (config #1),
``resnet152_imagenet`` (config #3), ``vit_b16_imagenet`` (config #4) and
``convnext_lamb`` (config #5, large-batch LAMB stress); ``gpt_lm``
(beyond BASELINE's five) measures the GPT/flash-attention LM path in
tokens/sec/chip.

No fallback hides the device: ``jax.devices()`` is called once,
in-process, and a platform other than ``tpu`` is an error unless
``--platform cpu`` asked for a CPU rehearsal — which times nothing under
a device metric's name and never touches the baseline record. Every
failure exits non-zero (after printing an ``error`` JSON line).

Measurement discipline (``block_until_ready`` alone is not trusted as a
timing boundary — a backend that returns from it early once produced a
physically impossible MFU of 11.6 here):

1. every window boundary is a REAL D2H readback of a scalar metric
   (``np.asarray``), which cannot return before the program has run;
2. the queue is drained (one step + readback) before each clock start,
   so a window never absorbs previously enqueued async work;
3. the window is grown until it spans >= ``--min_window`` seconds
   (default 1.0 s) of real wall time — never a 9 ms blip;
4. a linearity self-check times N steps and 2N steps; if t(2N)/t(N) is
   not ~2 (within [1.6, 2.6], tolerance for the fixed per-window
   readback latency), the run FAILS with an ``error`` field instead of
   emitting a number;
4b. the reported step time is the two-window SLOPE
   ``(t(2N) - t(N)) / N``: each window is ``fixed_readback + n * step``,
   so the difference cancels the fixed device->host readback latency
   exactly, leaving the steady-state step time the chip actually
   sustains. The conservative whole-window quotient ``t(2N) / 2N``
   (which charges the readback to the workload) is kept in
   ``extra.step_ms_conservative``; both are linearity- and MFU-gated;
5. hard physical sanity gates: computed MFU must be <= 1.0 and the loss
   finite, else ``error`` — this harness cannot print a number that
   exceeds the hardware's peak.

MFU: the compiled step's own XLA cost analysis gives FLOPs per program
(per chip); ``mfu = flops/sec / chip peak`` using a per-generation peak
table (bf16 MXU numbers). A TPU missing from the table is an error.

``vs_baseline``: the first VALID TPU run of each metric writes
``benchmarks/baseline_record.json``; later runs report against it.
Before a record exists (or on error / mismatched config) it is null —
a non-comparison must never read as "on par".
"""

import argparse
import json
import math
import os
import sys
import time
import traceback

# bf16 peak FLOPs/s per chip by device_kind substring (first match wins;
# more specific generations first). Sources: public TPU spec sheets.
PEAK_FLOPS = [
    ("v6e", 918e12),
    ("v6 lite", 918e12),
    ("v5p", 459e12),
    ("v5e", 197e12),
    ("v5 lite", 197e12),
    ("v5", 459e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
]

# HBM bandwidth per chip (bytes/s) by the same device_kind substrings —
# the roofline's second axis (graftmeter): a step whose arithmetic
# intensity sits below peak_flops/peak_bw is bandwidth-bound and no
# kernel fusion will reach MXU peak. Sources: public TPU spec sheets.
PEAK_HBM_BW = [
    ("v6e", 1640e9),
    ("v6 lite", 1640e9),
    ("v5p", 2765e9),
    ("v5e", 819e9),
    ("v5 lite", 819e9),
    ("v5", 2765e9),
    ("v4", 1228e9),
    ("v3", 900e9),
    ("v2", 700e9),
]

CONFIGS = {
    "resnet18_cifar": dict(
        model="res", image_size=32, batch=512, num_classes=10, stem="cifar",
    ),
    "resnet50_imagenet": dict(
        model="resnet50", image_size=224, batch=256, num_classes=1000,
        stem="imagenet",
    ),
    "resnet152_imagenet": dict(
        model="resnet152", image_size=224, batch=128, num_classes=1000,
        stem="imagenet",
    ),
    "vit_b16_imagenet": dict(
        model="vit_b16", image_size=224, batch=256, num_classes=1000,
        stem=None,
    ),
    # BASELINE config #5: large-batch LAMB stress (ConvNeXt, 21k-way head).
    "convnext_lamb": dict(
        model="convnext_t", image_size=224, batch=256, num_classes=21841,
        stem=None, optimizer="lamb",
    ),
    # LM / long-context flagship (beyond BASELINE's five): GPT-2 small
    # through the Pallas causal flash kernel; tokens/sec/chip.
    "gpt_lm": dict(
        lm=True, model="gpt_small", seq_len=1024, batch=8,
    ),
    # long-context variant: 4x the sequence — the [S, S] attention
    # never materializes (flash kernel), so this measures what the
    # long-context stack actually sustains. batch 2 = same tokens/step
    # as gpt_lm ON THE SINGLE-CHIP canonical geometry (build_workload
    # rounds the global batch up to the data-axis size on wider meshes,
    # where per-chip tokens/step then differ).
    "gpt_lm_long": dict(
        lm=True, model="gpt_small", seq_len=4096, batch=2,
    ),
}


def metric_for(config: str):
    """(metric_name, unit) for a config — the ONE place the naming
    lives; the success and error paths must emit the same strings (the
    baseline record is keyed by them)."""
    if CONFIGS.get(config, {}).get("lm"):
        return f"{config}_train_tokens_per_sec_per_chip", "tokens/sec/chip"
    return f"{config}_train_images_per_sec_per_chip", "images/sec/chip"


def _log(msg: str) -> None:
    """Diagnostics go to stderr; stdout carries exactly one JSON line."""
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def require_platform(platform: str):
    """``jax.devices()``, once and in-process — on the platform that
    was asked for, or an error: a number comes only from the chip, and
    ``cpu`` is the explicit rehearsal that reports no device metric.
    Shared with ``benchmarks/serving_bench.py``."""
    import jax

    if platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    devices = jax.devices()
    if devices[0].platform != platform:
        raise RuntimeError(
            f"jax found platform {devices[0].platform!r}, not "
            f"{platform!r}; a benchmark number comes only from the "
            "chip (--platform cpu rehearses the control flow and "
            "reports no device metric)")
    return devices


def _chip_peak(device, table):
    """First device_kind-substring match in an ordered peak table
    (more specific generations first) — the ONE lookup both the FLOPs
    and HBM-bandwidth axes use. None off-TPU (a CPU rehearsal has no
    roofline); a TPU that is not in the table is an error, not a
    default."""
    if device.platform != "tpu":
        return None
    kind = device.device_kind.lower()
    for key, peak in table:
        if key in kind:
            return peak
    raise ValueError(
        f"device_kind {device.device_kind!r} is not in bench.py's peak "
        "tables; add its published peaks before benchmarking on it")


def chip_peak_flops(device) -> float:
    return _chip_peak(device, PEAK_FLOPS)


def chip_peak_hbm_bw(device) -> float:
    return _chip_peak(device, PEAK_HBM_BW)


def compile_step(step, *args):
    """AOT-compile the step ONCE; return ``(callable, costs)`` where
    ``costs`` is the graftmeter record for the exact executable
    (``{flops, bytes_accessed, arithmetic_intensity, memory}`` —
    ``analysis.meter.costs_record``).

    The compiled executable drives the warmup/timed loops directly (AOT
    compiles don't populate jit's cache, so lowering for cost analysis
    and then calling the jitted wrapper would compile the same program
    twice). Lowering + cost/memory analysis go
    through the shared ``utils.compile_cache.lowered_program_analysis``
    path (the same one the graftcheck/graftmeter auditors inspect, so
    the benched program, the budgeted program and the audited program
    cannot drift).
    """
    from pytorch_multiprocessing_distributed_tpu.analysis.meter import (
        costs_record)
    from pytorch_multiprocessing_distributed_tpu.utils.compile_cache import (
        lowered_program_analysis)

    compiled, cost, memory = lowered_program_analysis(step, *args)
    if cost is None:
        _log("cost_analysis unavailable (backend returned no usable "
             "cost model)")
    return compiled, costs_record(cost, memory)


def build_workload(config: str, dtype_name: str, batch_size: int,
                   devices, remat: bool = False, vocab_chunks: int = 0,
                   zero: bool = False, zero_overlap: bool = True):
    """Construct the EXACT program a config benches: the jitted train
    step, its initialized state, the resident device batch, and the
    item count per step. The ONE place this lives: every timed
    variant in this file builds its program here.

    Returns ``(step, state, batch_args, items_per_step, batch)`` with
    ``batch`` after the data-axis divisibility rounding.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_multiprocessing_distributed_tpu import models
    from pytorch_multiprocessing_distributed_tpu.parallel import make_mesh
    from pytorch_multiprocessing_distributed_tpu.train import (
        create_train_state, make_train_step)
    from pytorch_multiprocessing_distributed_tpu.train.lamb import lamb
    from pytorch_multiprocessing_distributed_tpu.train.optim import sgd
    from pytorch_multiprocessing_distributed_tpu.train.step import shard_batch

    cfg = CONFIGS[config]
    n_dev = len(devices)
    is_tpu = devices[0].platform == "tpu"
    mesh = make_mesh(n_dev, devices=devices)
    dtype = jnp.bfloat16 if dtype_name == "bfloat16" else jnp.float32
    batch = batch_size or cfg["batch"]
    is_lm = bool(cfg.get("lm"))
    if vocab_chunks and not is_lm:
        raise ValueError(
            f"--vocab_chunks streams the LM head; {config} is not an "
            "LM config"
        )
    if not is_tpu:
        # --platform cpu is a rehearsal of the control flow, not a
        # measurement — shrink so it ends in bounded time.
        batch = min(batch, (1 if is_lm else 4) * n_dev)
    if batch % n_dev:
        batch += n_dev - batch % n_dev  # keep the data axis even
    rng = np.random.default_rng(0)

    if is_lm:
        from pytorch_multiprocessing_distributed_tpu.train.lm import (
            create_lm_train_state, make_lm_train_step)

        s = cfg["seq_len"]
        if not is_tpu:
            s = min(s, 64)  # interpret-mode flash kernel: liveness only
        model = models.get_model(cfg["model"], dtype=dtype,
                                 max_seq_len=max(s, 1024))
        opt = sgd(learning_rate=0.1)
        tokens = jnp.asarray(
            rng.integers(0, model.vocab_size, (batch, s))
        )
        state = create_lm_train_state(
            model, jax.random.PRNGKey(0), tokens[:2], opt
        )
        step = make_lm_train_step(model, opt, mesh, remat=remat,
                                  vocab_chunks=vocab_chunks, zero=zero,
                                  zero_overlap=zero_overlap)
        batch_args = shard_batch((tokens,), mesh)
        items_per_step = batch * s  # tokens
    else:
        s = cfg["image_size"]
        model = models.get_model(
            cfg["model"], dtype=dtype, bn_axis="data",
            num_classes=cfg["num_classes"], stem=cfg["stem"],
        )
        opt = (lamb(learning_rate=1e-3) if cfg.get("optimizer") == "lamb"
               else sgd(learning_rate=0.1))
        state = create_train_state(
            model, jax.random.PRNGKey(0), jnp.zeros((2, s, s, 3)), opt
        )
        step = make_train_step(model, opt, mesh, remat=remat, zero=zero,
                               zero_overlap=zero_overlap)
        x = jnp.asarray(rng.normal(size=(batch, s, s, 3)), jnp.float32)
        y = jnp.asarray(rng.integers(0, cfg["num_classes"], (batch,)))
        batch_args = shard_batch((x, y), mesh)
        items_per_step = batch  # images

    if zero:
        # graftzero: moments sharded from step one (the replicated
        # tree never materializes); the step binds on this structure
        from pytorch_multiprocessing_distributed_tpu.parallel.zero import (
            zeroify_state)

        state = zeroify_state(state, mesh)
    return step, state, batch_args, items_per_step, batch


def run_bench(config: str, dtype_name: str, batch_size: int,
              min_window: float, warmup: int, devices,
              remat: bool = False, vocab_chunks: int = 0,
              zero: bool = False) -> dict:
    import numpy as np

    n_dev = len(devices)
    platform = devices[0].platform
    is_tpu = platform == "tpu"
    if not is_tpu:
        min_window, warmup = min(min_window, 0.2), min(warmup, 1)
    step, state, batch_args, items_per_step, batch = build_workload(
        config, dtype_name, batch_size, devices, remat=remat,
        vocab_chunks=vocab_chunks, zero=zero,
    )
    zero_plan = state.opt_state.plan if zero else None
    if zero:
        # the lazy zero wrapper has no .lower — hand the AOT path the
        # bound jit program for this state structure (the exact
        # program the loop runs)
        step = step.jit_program(state)
    # graftfleet goodput accounting for the bench run itself: compile
    # seconds vs measured-window seconds vs everything else (warmup,
    # queue drains, window growth) over the run's wall clock
    t_run0 = time.perf_counter()
    step, costs = compile_step(step, state, *batch_args)
    compile_s = time.perf_counter() - t_run0
    timed_windows = []  # seconds of MEASURED stepping (the goodput)
    flops = float(costs["flops"]) if costs and costs["flops"] else None
    bytes_accessed = (float(costs["bytes_accessed"])
                      if costs and costs["bytes_accessed"] else None)

    from pytorch_multiprocessing_distributed_tpu.utils.profiler import sync

    def readback(metrics) -> float:
        # The window boundary: profiler.sync is the framework's single
        # D2H-forcing sync (block_until_ready ALONE is not trusted as
        # a timing boundary — see the module docstring).
        sync(metrics)
        return float(np.asarray(metrics["loss"]))

    def window(state, n: int):
        """Drain the queue, then time n steps ending in a D2H readback."""
        state, m = step(state, *batch_args)
        readback(m)  # queue now empty: the clock can't absorb old work
        t0 = time.perf_counter()
        for _ in range(n):
            state, m = step(state, *batch_args)
        loss = readback(m)
        t = time.perf_counter() - t0
        timed_windows.append(t)
        return t, state, loss

    _log(f"warmup x{warmup}")
    for _ in range(max(1, warmup)):
        state, metrics = step(state, *batch_args)
    readback(metrics)

    # Grow the window until it spans >= min_window seconds of real wall
    # time (a 9 ms total window measures nothing). Growth is
    # capped at 10x per iteration and the whole measurement at a wall
    # deadline, so a broken readback (windows reading ~0) degrades to an
    # error line in bounded time, never an hours-long queue drain.
    deadline = time.monotonic() + float(
        os.environ.get("PMDT_BENCH_DEADLINE", 420))
    n1 = 4 if is_tpu else 2
    max_steps = 20_000
    for _ in range(8):
        t1, state, loss = window(state, n1)
        _log(f"window n={n1}: {t1 * 1000:.1f} ms ({1000 * t1 / n1:.3f} ms/step)")
        if t1 >= min_window or n1 >= max_steps:
            break
        if time.monotonic() + 3 * max(t1, 0.001) > deadline:
            raise RuntimeError(
                f"bench deadline exceeded while growing the timed window "
                f"(n={n1} still only {t1 * 1000:.0f} ms) — timing is not "
                "converging; refusing to emit a number"
            )
        n1 = min(max_steps, 10 * n1,
                 max(n1 + 1, math.ceil(n1 * 1.25 * min_window / t1)))

    # Linearity self-check: 2N steps must take ~2x the time of N steps.
    # A fixed per-window readback latency plus timing jitter keeps the
    # honest ratio just under 2; anything far
    # from 2 means some async/caching artifact ate the measurement.
    if time.monotonic() + 2.5 * t1 > deadline:
        raise RuntimeError(
            "bench deadline would be exceeded by the linearity window — "
            "refusing to emit an unverified number"
        )
    t2, state, loss2 = window(state, 2 * n1)
    ratio = t2 / t1
    _log(f"window n={2 * n1}: {t2 * 1000:.1f} ms (linearity ratio {ratio:.3f})")

    # Two-window slope: t(n) = fixed_readback + n*step, so the difference
    # cancels the fixed D2H latency exactly. Guarded below: the
    # linearity gate already bounds ratio in [1.6, 2.6], which bounds the
    # slope within a sane band of the conservative quotient; the MFU gate
    # applies to the slope (the number actually reported).
    step_s_conservative = t2 / (2 * n1)
    step_s = (t2 - t1) / n1
    if step_s <= 0 or not is_tpu:
        # slope <= 0: the linear model collapsed (and on TPU the
        # linearity gate below rejects the run). Off TPU the gates that
        # guard the slope (linearity, MFU) are inactive and the windows
        # are deliberately short liveness probes, so the conservative
        # whole-window quotient — which can only OVERstate step time —
        # is the only safe estimate there.
        step_s = step_s_conservative
    # NOTE: when slope > conservative (steps DEcelerating, e.g. thermal
    # throttling — fixed_readback would be negative) the slope is the
    # PESSIMISTIC estimate and is kept; the fallback never swaps in the
    # smaller number.
    per_chip = items_per_step / step_s / n_dev
    peak = chip_peak_flops(devices[0])
    peak_bw = chip_peak_hbm_bw(devices[0])
    # measured-vs-roofline join (graftmeter): achieved FLOP/s, bytes/s
    # and the intensity-limited ceiling, from the SAME static model the
    # committed cost budgets pin. Null-safe on CPU/unknown chips.
    from pytorch_multiprocessing_distributed_tpu.analysis.meter import (
        roofline)

    eff = roofline(flops, bytes_accessed, step_s, peak, peak_bw)
    mfu = eff["mfu"]

    # ---- graftzero comparison sweep (--zero): the replicated twin,
    # the serialized (overlap-off) twin and a comm-only probe, each a
    # short drained window — honest syncs, never a dispatch stopwatch.
    # overlap_frac = (t_serialized - t_zero) / t_comm: the fraction of
    # the standalone grad-comm wall the bucketed dependency chain
    # hides under compute. hbm_opt_state_bytes is the measured
    # per-chip ledger delta (sharded vs replicated moments).
    zero_extra = {}
    if zero:
        import jax.numpy as _jnp

        from pytorch_multiprocessing_distributed_tpu.parallel import (
            zero as zero_mod)
        from pytorch_multiprocessing_distributed_tpu.runtime import hbm
        from pytorch_multiprocessing_distributed_tpu.runtime import (
            scope as graftscope)
        from pytorch_multiprocessing_distributed_tpu.train.step import (
            register_state_hbm)

        def timed_steps(fn, st, bargs, n):
            st, m = fn(st, *bargs)
            sync(m)  # drain: the clock cannot absorb queued work
            t0 = time.perf_counter()
            for _ in range(n):
                st, m = fn(st, *bargs)
            sync(m)
            return (time.perf_counter() - t0) / n

        n_cmp = max(2, n1 // 2) if is_tpu else 2
        rep_step, rep_state, rep_args, _, _ = build_workload(
            config, dtype_name, batch, devices, remat=remat,
            vocab_chunks=vocab_chunks, zero=False)
        with hbm.scoped_ledger() as rep_ledger:
            register_state_hbm(rep_state)
            rep_opt_bytes = rep_ledger.snapshot().get(
                "hbm_opt_state_bytes", 0)
        rep_s = timed_steps(rep_step, rep_state, rep_args, n_cmp)

        ser_step, ser_state, ser_args, _, _ = build_workload(
            config, dtype_name, batch, devices, remat=remat,
            vocab_chunks=vocab_chunks, zero=True, zero_overlap=False)
        with hbm.scoped_ledger() as z_ledger:
            register_state_hbm(ser_state)
            zero_opt_bytes = z_ledger.snapshot().get(
                "hbm_opt_state_bytes", 0)
        ser_s = timed_steps(ser_step, ser_state, ser_args, n_cmp)

        mesh = rep_args[0].sharding.mesh
        comm_fn = zero_mod.comm_probe(zero_plan, mesh)
        dummies = [_jnp.zeros((b.padded,), _jnp.dtype(b.dtype))
                   for b in zero_plan.buckets]

        def comm_once(_st, *a):
            out = comm_fn(list(a))
            return _st, out

        comm_s = timed_steps(comm_once, None, tuple(dummies), n_cmp)
        comm_bytes = zero_mod.static_comm_bytes(zero_plan)
        total_comm_bytes = (comm_bytes["reduce_scatter"]
                            + comm_bytes["all_gather"])
        # the measured grad-comm span on the bus (static bytes rider —
        # the fleet.static_collective_bytes discipline), feeding the
        # goodput ledger below like every other bench span
        graftscope.emit_span("train.grad_comm", comm_s, cat="train",
                             nbytes=total_comm_bytes,
                             buckets=len(zero_plan.buckets))
        overlap_frac = None
        if comm_s > 0:
            overlap_frac = max(0.0, min(1.0, (ser_s - step_s) / comm_s))
        zero_extra = {
            "zero": True,
            "zero_shards": zero_plan.num_shards,
            "zero_buckets": len(zero_plan.buckets),
            "replicated_step_ms": round(1000 * rep_s, 3),
            "serialized_step_ms": round(1000 * ser_s, 3),
            "grad_comm_ms": round(1000 * comm_s, 3),
            "grad_comm_bytes": total_comm_bytes,
            "grad_comm_frac_of_step": (round(comm_s / step_s, 4)
                                       if step_s > 0 else None),
            "overlap_frac": (round(overlap_frac, 4)
                             if overlap_frac is not None else None),
            "hbm_opt_state_bytes": zero_opt_bytes,
            "hbm_opt_state_bytes_replicated": rep_opt_bytes,
        }
        del rep_step, rep_state, ser_step, ser_state

    # graftfleet: goodput over this bench run (classified through the
    # same ledger the CLIs serve) + collective skew when a fleet
    # monitor is armed — None-safe on a single host, never a fake 0
    from pytorch_multiprocessing_distributed_tpu.runtime import fleet

    run_wall = time.perf_counter() - t_run0
    gp_events = [
        {"name": "bench.run", "ph": "X", "ts": t_run0,
         "dur": run_wall, "seq": 0},
        {"name": "compile.lower", "ph": "X", "cat": "compile",
         "ts": t_run0, "dur": compile_s, "seq": 1},
    ]
    gp_events += [
        {"name": "train.window", "ph": "X", "ts": t_run0, "dur": t,
         "seq": 2 + i} for i, t in enumerate(timed_windows)]
    goodput = fleet.GoodputLedger.from_events(gp_events).gauges()
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

    result = {
        "metric": metric_for(config)[0],
        "value": round(per_chip, 2),
        "unit": metric_for(config)[1],
        "mfu": mfu,
        "extra": {
            "config": config,
            "dtype": dtype_name,
            "global_batch": batch,
            "devices": n_dev,
            "platform": platform,
            "device_kind": getattr(devices[0], "device_kind", "unknown"),
            "steps_timed": 2 * n1,
            "step_ms": round(1000 * step_s, 3),
            "step_ms_conservative": round(1000 * step_s_conservative, 3),
            "window1_s": round(t1, 4),
            "window2_s": round(t2, 4),
            "linearity_ratio": round(ratio, 4),
            # NaN/Inf are not legal JSON; stringify so the output line
            # always parses even when training diverged
            "final_loss": loss2 if math.isfinite(loss2) else repr(loss2),
            # canonical = the config's own batch/dtype (what the baseline
            # record may be written from; ad-hoc flag runs never claim
            # it). Keyed on the REQUEST (batch_size==0), not the final
            # batch: mesh-alignment rounding of the config's own batch
            # must not bar a config from ever recording a baseline.
            "canonical": (batch_size == 0 and dtype_name == "bfloat16"
                          and is_tpu and not remat
                          and vocab_chunks == 0 and not zero),
            "remat": remat,
            "vocab_chunks": vocab_chunks,
            **zero_extra,
            "flops_per_step_per_chip": flops,
            "peak_flops_per_chip": peak,
            # ---- graftmeter efficiency attribution: every record
            # carries WHERE the time went, not just how much of it
            "bytes_accessed_per_step_per_chip": bytes_accessed,
            "peak_hbm_bw_per_chip": peak_bw,
            "arithmetic_intensity": eff["arithmetic_intensity"],
            "achieved_flops_per_sec": eff["achieved_flops_per_sec"],
            "achieved_bytes_per_sec": eff["achieved_bytes_per_sec"],
            "roofline_flops_per_sec": eff["roofline_flops_per_sec"],
            "roofline_bound": eff["roofline_bound"],
            "roofline_frac": eff["roofline_frac"],
            "hbm_memory": (costs or {}).get("memory"),
            # ---- graftfleet: where the RUN's wall went (compile vs
            # measured stepping vs overhead) + cross-rank skew
            "goodput_frac": round(goodput["goodput_frac"], 4),
            "goodput_compile_s": round(goodput["goodput_compile_s"], 3),
            "goodput_wall_s": round(goodput["goodput_wall_s"], 3),
            "collective_skew_p95_s": collective_skew_p95_s,
            "collective_straggler_rank": collective_straggler_rank,
        },
    }
    # ---- hard sanity gates: never print a physically impossible number.
    errors = []
    if not math.isfinite(loss2):
        errors.append(f"non-finite loss {loss2}")
    fastest = min(step_s, step_s_conservative)
    if flops and peak and flops / fastest > peak:
        # BOTH estimators must be physically possible (equivalently:
        # per-chip images/sec above the ceiling peak*(batch/n_dev)/flops)
        errors.append(
            f"implied {flops / fastest / 1e12:.1f} TFLOP/s "
            f"({'conservative' if fastest < step_s else 'slope'} estimator)"
            f" exceeds the chip's {peak / 1e12:.0f} TFLOP/s peak "
            f"(worst-case mfu {flops / fastest / peak:.3f}) — "
            "measurement invalid"
        )
    if is_tpu:
        if t2 < min_window:
            errors.append(
                f"timed window {t2 * 1000:.0f} ms < required "
                f"{min_window * 1000:.0f} ms even at n={2 * n1} steps"
            )
        if not (1.6 <= ratio <= 2.6):
            errors.append(
                f"non-linear timing: t(2N)/t(N) = {ratio:.3f}, expected ~2 "
                "— async artifact, number rejected"
            )
    if errors:
        result["error"] = "; ".join(errors)
        result["value"] = 0.0
        result["mfu"] = None
    return result


def build_parser():
    p = argparse.ArgumentParser()
    p.add_argument("--config", default="resnet50_imagenet",
                   choices=sorted(CONFIGS),
                   help="default = the BASELINE.md north-star workload")
    p.add_argument("--dtype", default="bfloat16",
                   choices=["float32", "bfloat16"])
    p.add_argument("--batch_size", default=0, type=int,
                   help="global batch (0 = config default)")
    p.add_argument("--min_window", default=1.0, type=float,
                   help="minimum timed-window span in seconds")
    p.add_argument("--warmup", default=5, type=int)
    p.add_argument("--platform", default="tpu", choices=["tpu", "cpu"],
                   help="cpu = rehearse the control flow on the host "
                        "platform (shrunk shapes, no device metric, no "
                        "baseline record); the default fails without a "
                        "TPU")
    p.add_argument("--remat", action="store_true",
                   help="rematerialize activations (jax.checkpoint) — "
                        "trades ~1.3x step time for the activation HBM")
    p.add_argument("--vocab_chunks", default=0, type=int,
                   help="LM configs: stream the head+CE over N vocab "
                        "slices (logits never materialize); 0 = dense. "
                        "Non-canonical probe knob like --remat")
    p.add_argument("--zero", action="store_true",
                   help="graftzero sweep: bench the sharded-update "
                        "step AND its replicated/serialized twins + a "
                        "comm-only probe — records replicated vs "
                        "sharded step time, grad-comm bytes/wall, "
                        "overlap_frac and the per-chip "
                        "hbm_opt_state_bytes delta (~1/N). "
                        "Non-canonical probe knob like --remat")
    return p


def main():
    args = build_parser().parse_args()
    metric, unit = metric_for(args.config)

    try:
        devices = require_platform(args.platform)
        _log(f"devices: {len(devices)} x {devices[0].device_kind}")
        from pytorch_multiprocessing_distributed_tpu.utils.compile_cache import (  # noqa: E501
            enable_compilation_cache)

        _log(f"compilation cache: {enable_compilation_cache()}")
        result = run_bench(args.config, args.dtype, args.batch_size,
                           args.min_window, args.warmup, devices,
                           remat=args.remat,
                           vocab_chunks=args.vocab_chunks,
                           zero=args.zero)
    except Exception as e:  # noqa: BLE001 — report why, then fail
        _log(traceback.format_exc())
        print(json.dumps({"metric": metric, "value": 0.0, "unit": unit,
                          "mfu": None,
                          "error": f"{type(e).__name__}: {e}"}))
        sys.exit(1)

    if args.platform == "cpu":
        # a rehearsal: what the run COUNTED, under no device metric's
        # name, and no baseline read or write
        extra = result["extra"]
        print(json.dumps({
            "rehearsal": "cpu", "config": args.config,
            "error": result.get("error"),
            **{k: extra[k] for k in (
                "platform", "device_kind", "devices", "global_batch",
                "dtype", "steps_timed", "final_loss")}}))
        sys.exit(1 if "error" in result else 0)

    record_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "benchmarks", "baseline_record.json",
    )
    rec = {}
    if os.path.exists(record_path):
        with open(record_path) as f:
            rec = json.load(f)
    # null (not 1.0) when no valid comparison happened: an error line
    # must never read as "on par with baseline".
    vs = None
    base = rec.get(metric)
    if isinstance(base, (int, float)):  # legacy scalar format
        base = {"value": base}
    extra = result["extra"]
    comparable = (
        isinstance(base, dict)
        and base.get("value")
        and "error" not in result
        and result["value"] > 0
        # apples-to-apples only: a different batch/dtype/chip is a
        # different experiment, not a regression/speedup
        and all(
            base.get(k) is None or base.get(k) == extra.get(k)
            for k in ("global_batch", "dtype", "device_kind")
        )
        # legacy records lack the remat key; treat them as non-remat
        and bool(base.get("remat", False)) == bool(extra.get("remat"))
        # same rationale for the streamed-CE knob: a chunked probe
        # is a different experiment than the dense canonical run
        and int(base.get("vocab_chunks", 0) or 0)
        == int(extra.get("vocab_chunks", 0) or 0)
        # a record written under a different step-time estimator is a
        # different measurement, not a baseline (the slope estimator
        # reads faster than the whole-window quotient purely because
        # it cancels the fixed readback latency)
        and base.get("estimator", "whole_window") == "two_window_slope"
    )
    if comparable:
        vs = round(result["value"] / base["value"], 4)
    result["vs_baseline"] = vs

    # The first VALID number for each metric becomes the baseline record
    # later runs compare against (gated so an error can never pollute
    # it; only a canonical-config run — the config's own batch, bf16 —
    # may claim the slot, never an ad-hoc --batch_size probe).
    valid = ("error" not in result and result["value"] > 0
             and extra["canonical"])
    prior = rec.get(metric)
    prior_legacy = (
        isinstance(prior, dict)
        and prior.get("estimator", "whole_window") != "two_window_slope"
    )
    if valid and (metric not in rec or prior_legacy):
        rec[metric] = {
            "value": result["value"],
            "unit": result["unit"],
            "mfu": result["mfu"],
            "device_kind": extra["device_kind"],
            "global_batch": extra["global_batch"],
            "dtype": extra["dtype"],
            "remat": bool(extra.get("remat")),
            "estimator": "two_window_slope",
        }
        with open(record_path, "w") as f:
            json.dump(rec, f, indent=2, sort_keys=True)
        _log(f"recorded baseline for {metric} -> {record_path}")

    print(json.dumps(result))
    if "error" in result:
        sys.exit(1)


if __name__ == "__main__":
    main()
