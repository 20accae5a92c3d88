"""Training cells: the job ``train_lm.py --parallel dp`` runs, measured.

The driver builds what the CLI builds — the registry model in bf16,
``train/optim.sgd``, ``create_lm_train_state``, ``make_lm_train_step``
on a ``(data,)`` mesh of the cell's chips, ``TokenLoader`` through
``prefetch_to_device`` — and runs the CLI's loop without its printing:
fetch a batch, dispatch the step, read the loss back once a window.
The loader runs inside the measured window.
"""

from __future__ import annotations

import math
import re
import time
from typing import Iterator

import numpy as np

from .. import flops, harness, trace_reduce
from ..reference import gpt2 as reference
from ..spans import Recording
from ..traffic import train_tokens

OPTIONS = {
    "dtype": "bfloat16",
    "per_chip_batch": None,         # sequences a chip holds per step
    "optimizer": "sgd",             # train/optim.sgd, the CLI's
    "lr": 0.01,                     # 0.1 diverges at this width
    "remat": False,
    "grad_accum": 1,
    "vocab_chunks": 0,
    "zero": False,
    "prefetch": 2,                  # prefetch_to_device's depth
    # windows alternate N and 2N steps, each ending in a read-back of
    # the loss; their difference over N is the step time with the
    # fixed read-back latency cancelled (bench.py's slope rule)
    "readback_every": 4,
    "warmup_steps": 3,
    "trace_windows": 1,             # traced pairs (N + 2N steps) at the end
}
TRAFFIC_KEYS = {"kind", "seq_len", "corpus", "corpus_batches", "shuffle",
                "replicas", "what"}

# Initial-parameter loss, system (bf16 activations, flash kernel) against
# the float32 reference on the same batch. Both average >= 16k
# predictions, so bf16 rounding (about 3 significant digits per logit,
# unbiased) averages out: the two agree to ~1e-3 of a loss near 11.
# What the tolerance must catch moves the loss by far more: at these
# widths with normal(0, 0.02) weights, dropping one block or attending
# without the causal mask shifts the reference's own loss by 2.6e-2
# (measured once at full width, PERF.md section 6). 5e-3 sits between.
LOSS_TOLERANCE = 5e-3

_SEED_MASK = 0x7FFFFFFF         # TokenLoader adds the epoch to its seed


def batches_forever(loader) -> Iterator[np.ndarray]:
    """The CLI's epoch loop without its end: reshuffle, go round."""
    epoch = 1
    while True:
        loader.set_epoch(epoch)
        yield from loader
        epoch += 1


class TrainJob:
    """Everything the cell builds, by the CLI's own calls. Built from
    shapes alone by ``perf/rehearse.py`` (described devices), and with
    real arrays by :func:`run`."""

    def __init__(self, cell: harness.Cell, devices):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from pytorch_multiprocessing_distributed_tpu.parallel import (
            make_mesh)
        from pytorch_multiprocessing_distributed_tpu.train.lm import (
            create_lm_train_state, make_lm_train_step)
        from pytorch_multiprocessing_distributed_tpu.train import optim

        opts = harness.take_options(cell.options, OPTIONS,
                                    f"workloads/{cell.name}")
        unknown = set(cell.traffic) - TRAFFIC_KEYS
        if unknown:
            raise harness.ManifestError(
                f"train traffic has unknown keys {sorted(unknown)}")
        if cell.traffic["replicas"] != len(devices):
            raise harness.ManifestError(
                f"traffic spreads the batch over "
                f"{cell.traffic['replicas']} replicas, the cell has "
                f"{len(devices)} chips")
        if opts["optimizer"] != "sgd":
            raise harness.ManifestError(
                f"unknown optimizer {opts['optimizer']!r}")
        self.opts = opts
        self.chips = len(devices)
        self.seq_len = int(cell.traffic["seq_len"])
        self.global_batch = int(opts["per_chip_batch"]) * self.chips
        self.model = harness.build_model(cell.config, opts["dtype"])
        self.mesh = make_mesh(self.chips, devices=list(devices))
        self.optimizer = optim.sgd(learning_rate=opts["lr"])
        self.replicated = NamedSharding(self.mesh, P())
        self.split = NamedSharding(self.mesh, P("data"))
        self.step = make_lm_train_step(
            self.model, self.optimizer, self.mesh, remat=opts["remat"],
            grad_accum=opts["grad_accum"],
            vocab_chunks=opts["vocab_chunks"], zero=opts["zero"])
        sample = np.zeros((2, self.seq_len), np.int32)

        def init(key):
            return create_lm_train_state(self.model, key, sample,
                                         self.optimizer)

        # one jitted call makes the whole state on the device(s)
        self.init = jax.jit(init, out_shardings=self.replicated)

    def abstract_args(self):
        """(state, tokens) as shapes with their shardings — what the
        step is compiled for."""
        import jax
        import jax.numpy as jnp

        state = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        state = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=self.replicated),
            state)
        tokens = jax.ShapeDtypeStruct(
            (self.global_batch, self.seq_len), jnp.int32,
            sharding=self.split)
        return state, tokens


def program_facts(compiled) -> dict:
    text = compiled.as_text()
    return {
        "mosaic_calls": text.count('custom_call_target="tpu_custom_call"'),
        "all_reduces": len(re.findall(r"= \S+ all-reduce(?:-start)?\(",
                                      text)),
        "peak_bytes": harness.program_peak_bytes(compiled),
    }


def run(cell: harness.Cell, *, seed: int, seconds: float, trace: bool,
        t_process: float, rec: Recording, allow_cpu: bool = False
        ) -> dict:
    import jax

    from pytorch_multiprocessing_distributed_tpu.data.lm import TokenLoader
    from pytorch_multiprocessing_distributed_tpu.data.pipeline import (
        prefetch_to_device)
    from pytorch_multiprocessing_distributed_tpu.utils.compile_cache import (
        CompileLog, enable_compilation_cache)

    enable_compilation_cache()
    compile_log = CompileLog()
    devices = harness.require_devices(cell.chips, allow_cpu)
    job = TrainJob(cell, devices)
    opts, mix = job.opts, cell.traffic

    # ---- set-up: weights, data, the compiled step ---------------------
    state = job.init(harness.prng_key(seed))
    n_tokens = int(mix["corpus_batches"]) * job.global_batch * job.seq_len
    vocab = cell.config["vocab_size"]
    loader = TokenLoader(
        train_tokens(mix, vocab, n_tokens, seed),
        batch_size=job.global_batch, seq_len=job.seq_len,
        world_size=job.chips, shuffle=bool(mix["shuffle"]),
        seed=int(seed) & _SEED_MASK)
    batches = prefetch_to_device(batches_forever(loader), job.mesh,
                                 size=int(opts["prefetch"]))
    first = next(batches)
    # ahead-of-time, so the program that runs is the one whose memory
    # analysis and collectives are reported (one compile, not two)
    compiled = job.step.lower(state, first).compile()
    facts = program_facts(compiled)
    placed = (all(len(leaf.sharding.device_set) == job.chips
                  for leaf in jax.tree.leaves(state.params))
              and len(first.sharding.device_set) == job.chips)

    # correctness (a): the reference's loss of the initial parameters
    # on the first batch, before the step donates them
    ref_loss = float(reference.make_loss_fn(cell.config)(
        state.params, np.asarray(first)))
    state, metrics = compiled(state, first)
    loss0 = float(np.asarray(metrics["loss"]))
    loss_gap = abs(loss0 - ref_loss)
    for _ in range(int(opts["warmup_steps"])):
        state, metrics = compiled(state, next(batches))
    float(np.asarray(metrics["loss"]))      # drain: the queue is empty

    # ---- the measured window -------------------------------------------
    rec.reset()
    compiles_before = len(compile_log.programs)
    every = int(opts["readback_every"])
    losses, skipped, windows = [], [], []
    steps = 0

    def window(n: int, into: Recording):
        nonlocal state, steps
        for _ in range(n):
            with into.span("train.data"):
                batch = next(batches)
            with into.span("train.dispatch"):
                state, m = compiled(state, batch)
            losses.append(m["loss"])
            skipped.append(m["skipped"])
            steps += 1
        with into.span("train.readback"):
            # a real device-to-host read of a scalar: cannot return
            # before every step enqueued so far has run
            float(np.asarray(m["loss"]))

    t_start = time.perf_counter()
    setup_s = time.time() - t_process
    t_prev, elapsed, k = t_start, 0.0, 0
    while elapsed < seconds:
        n = every * (1 + k % 2)
        window(n, rec)
        now = time.perf_counter()
        windows.append((n, now - t_prev))
        t_prev, elapsed, k = now, now - t_start, k + 1
    measured_steps = steps
    compiles_in_window = len(compile_log.programs) - compiles_before

    # ---- the traced tail (its own short window, profiler on) -----------
    if trace:
        tail = Recording()
        with trace_reduce.traced() as trace_dir:
            for _ in range(int(opts["trace_windows"])):
                window(every, tail)
                window(2 * every, tail)
        rec.trace = trace_reduce.reduce_dir(trace_dir, chips=job.chips)
        rec.trace["steps"] = steps - measured_steps

    # ---- correctness (b) and the numbers --------------------------------
    losses = [float(x) for x in jax.device_get(losses)]
    n_skipped = int(sum(int(x) for x in jax.device_get(skipped)))
    finite = all(math.isfinite(x) for x in losses)
    fell = losses[measured_steps - 1] < losses[0]
    correct = (loss_gap <= LOSS_TOLERANCE and finite and n_skipped == 0
               and fell and placed and compiles_in_window == 0)

    tokens_per_step = job.global_batch * job.seq_len
    rate_chip = measured_steps * tokens_per_step / elapsed / job.chips
    flops_token = flops.train_flops_per_token(cell.config, job.seq_len)
    kind = devices[0].device_kind
    peak = (harness.load_peaks(kind)["bf16_flops_per_s"]
            if devices[0].platform == "tpu" else float("nan"))
    # the slope of consecutive (N, 2N) window pairs: per-step time with
    # the fixed per-window read-back cancelled
    slopes = [(b[1] - a[1]) / (b[0] - a[0])
              for a, b in zip(windows[0::2], windows[1::2])]
    rec.series["train.step_slope"] = slopes
    rec.series["train.window_step"] = [t / n for n, t in windows]
    summary = compile_log.summary()
    compile_log.close()
    rec.count("train.steps", measured_steps)
    rec.count("train.model_flops_per_s_chip", rate_chip * flops_token)
    rec.count("device.peak_flops_per_s", peak)
    rec.count("program.all_reduces", facts["all_reduces"])
    rec.count("program.mosaic_calls", facts["mosaic_calls"])
    rec.count("compile.seconds", summary["compile_s"])
    rec.count("compile.programs", summary["compiles"])
    rec.count("compile.cache_hits", summary["cache_hits"])

    return {
        "correct": bool(correct),
        "attempted": measured_steps,
        "failed": n_skipped,
        "end_to_end": {"train_tokens_per_s_chip": rate_chip,
                       "setup_s": setup_s},
        "devices": devices,
        "program_peak_bytes": facts["peak_bytes"],
        "checks": {
            "loss_initial_system": loss0, "loss_initial_reference": ref_loss,
            "loss_gap": loss_gap, "loss_tolerance": LOSS_TOLERANCE,
            "loss_first": losses[0], "loss_last": losses[measured_steps - 1],
            "losses_finite": finite, "steps_skipped": n_skipped,
            "state_and_batch_on_every_chip": placed,
            "compiles_in_window": compiles_in_window,
            "window_s": elapsed, "steps": measured_steps,
            "global_batch": job.global_batch,
            "step_ms_slope_samples": len(slopes),
            "program": facts, "compile": summary,
        },
    }
