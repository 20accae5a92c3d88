"""Epoch-level train/validate loops.

Behavioral parity with the reference's ``train`` (``main.py:87-131``) and
``validate`` (``main.py:134-171``): same meters, same stdout line formats,
same ``[epoch, loss.avg, acc]`` log rows, primary-host gating everywhere
the reference gates on rank 0.

Two deliberate fixes of record (SURVEY.md §3.5):
- eval accuracy uses the globally ``psum``-ed correct count (the
  reference divides a per-rank count by the full dataset size,
  ``main.py:151,168`` — wrong by ~world_size);
- the LR schedule is a pure function of the epoch evaluated on every
  replica (the reference steps it on rank 0 only, ``main.py:69-70``).

Timing note: XLA dispatch is asynchronous — ``time.time()`` around the
step call measures nothing (SURVEY.md §5 "Tracing"). The hot loop
therefore keeps the step's scalar metrics ON DEVICE and fetches them only
at ``print_freq`` boundaries (and at epoch end): between fetches the
steps pipeline freely (async dispatch overlaps H2D, compute and the next
dispatch), and each fetch is a real synchronization point, so the
window's wall-clock divided by its step count is honest per-step time.
The reference pays a device->host sync EVERY iteration for ``.item()``
(``main.py:113-115``); VERDICT r1 measured that pattern costing real
throughput here, so the meters take the same values in windowed batches
instead (identical averages, identical printed lines).
"""

from __future__ import annotations

import os
import time
from typing import Optional

import jax
import jax.numpy as jnp

from ..data.pipeline import ShardedLoader, prefetch_to_device
from ..parallel import dist
from ..runtime import scope as graftscope
from ..parallel.mesh import MODEL_AXIS
from ..utils import AverageMeter, Logger
from ..utils import profiler  # noqa: F401 (sets graftscope's annotator)
from ..utils.plotting import draw_plot
from .checkpoint import prune_checkpoints, save_checkpoint
from .state import TrainState
from .step import (
    make_eval_step,
    make_eval_step_tp,
    make_train_step,
    make_train_step_tp,
    register_state_hbm,
    shard_state,
)


_HANDLER_NOT_INSTALLED = object()  # signal handler sentinel (see fit)


class Trainer:
    """Drives the compiled steps over epochs, reproducing the reference CLI
    trainer's observable behavior (``main.py:32-84``)."""

    def __init__(
        self,
        *,
        model,
        optimizer,
        mesh,
        state: TrainState,
        train_loader: ShardedLoader,
        test_loader: ShardedLoader,
        save_path: str,
        epochs: int,
        print_freq: int = 10,
        start_epoch: int = 1,
        zero: bool = False,
        zero1: bool = False,
        fsdp: bool = False,
        remat: bool = False,
        grad_accum: int = 1,
        loss_fn=None,
        clip_grad_norm=None,
        ema_decay=None,
        save_every: int = 0,
        keep_checkpoints: int = 0,
        ckpt_backend: str = "msgpack",
        ckpt_async: bool = False,
    ):
        self.mesh = mesh
        self.state = state
        self.train_loader = train_loader
        self.test_loader = test_loader
        self.save_path = save_path
        self.epochs = epochs
        self.print_freq = print_freq
        # resume continues the epoch series (and thus the LR schedule and
        # the log-row numbering) instead of restarting at 1 — the resume
        # path the reference lacks entirely.
        self.start_epoch = start_epoch
        # periodic checkpointing (0 = reference behavior: final epoch
        # only, main.py:75-77) with optional keep-K retention
        self.save_every = save_every
        self.keep_checkpoints = keep_checkpoints
        # "msgpack" = reference-parity model_{epoch}.pth (host-gathered,
        # torch-interoperable); "orbax" = sharded per-host OCDBT writes
        # under {save_path}/orbax/ — no gather, scales with the model
        # (requires save_path on SHARED storage across hosts)
        if ckpt_backend == "orbax":
            from .orbax_ckpt import OrbaxCheckpointer

            self._orbax = OrbaxCheckpointer(
                save_path, keep=keep_checkpoints or None,
                async_=ckpt_async,
            )
        elif ckpt_async:
            raise ValueError(
                "ckpt_async requires ckpt_backend='orbax' (the msgpack "
                "writer is synchronous by design)"
            )
        elif ckpt_backend != "msgpack":
            raise ValueError(
                f"ckpt_backend must be 'msgpack' or 'orbax', "
                f"got {ckpt_backend!r}"
            )
        self.ckpt_backend = ckpt_backend
        # evaluate/checkpoint with EMA weights when tracking is on
        self.ema_decay = ema_decay
        from ..ops.losses import cross_entropy_loss

        loss_fn = loss_fn or cross_entropy_loss
        # graftzero: the shard_map-DP sharded weight update. Distinct
        # from --zero1 (the GSPMD zero1 placement): this mode rewrites
        # the explicit DP step's communication schedule, so it
        # composes with pure DP only.
        self._zero = zero
        if zero:
            if dict(mesh.shape).get(MODEL_AXIS, 1) > 1 or zero1 or fsdp:
                raise ValueError(
                    "zero=True is the explicit shard_map-DP sharded "
                    "update; under --model_parallel/--zero1/--fsdp the "
                    "GSPMD path already owns the state placement — use "
                    "zero1/fsdp there instead")
            if ckpt_backend == "orbax":
                raise ValueError(
                    "zero=True checkpoints via the msgpack "
                    "gather-on-save path (mode-portable artifacts); "
                    "--ckpt_backend orbax would persist the sharded "
                    "layout and break --resume round-trips — use "
                    "msgpack with --zero")
        if dict(mesh.shape).get(MODEL_AXIS, 1) > 1 or zero1 or fsdp:
            # the GSPMD step: real tensor parallelism (params sharded
            # over the model axis), ZeRO-1 (optimizer moments sharded
            # over the data axis) and/or FSDP/ZeRO-3 (params + stats +
            # moments all sharded over data). The model must carry
            # ``bn_axis=None`` — BN stats are global by construction
            # there; main.py builds it accordingly.
            self.state = shard_state(state, mesh, zero1=zero1, fsdp=fsdp)
            self.train_step = make_train_step_tp(
                model, optimizer, mesh, zero1=zero1, fsdp=fsdp,
                remat=remat, grad_accum=grad_accum, loss_fn=loss_fn,
                clip_grad_norm=clip_grad_norm, ema_decay=ema_decay,
            )
            self.eval_step = make_eval_step_tp(
                model, mesh, zero1=zero1, fsdp=fsdp, loss_fn=loss_fn
            )
        else:
            self.train_step = make_train_step(
                model, optimizer, mesh, remat=remat, grad_accum=grad_accum,
                loss_fn=loss_fn, clip_grad_norm=clip_grad_norm,
                ema_decay=ema_decay, zero=zero,
            )
            self.eval_step = make_eval_step(model, mesh, loss_fn=loss_fn)
            if zero:
                # moments sharded from step one: the replicated tree
                # (fresh init or a restored checkpoint) is flattened
                # into P(data) buckets and never materializes again
                from ..parallel.zero import zeroify_state

                self.state = zeroify_state(self.state, mesh)
        self.train_logger = Logger(os.path.join(save_path, "train.log"))
        self.test_logger = Logger(os.path.join(save_path, "test.log"))
        # graftmeter: resident-state footprint on the armed ledger
        # (the GSPMD branch already registered inside shard_state —
        # same entries, same bytes; the DP branch registers here), and
        # the live throughput gauges main.py --stats_port serves —
        # updated at the windowed fetch the loop already pays
        register_state_hbm(self.state)
        self.live = {}

    # ------------------------------------------------------------- epochs

    def _install_preemption_handler(self):
        """SIGTERM -> checkpoint-and-exit at the next metrics window.

        TPU preemptions/maintenance deliver SIGTERM to every host of the
        slice; instead of dying mid-step, the hot loop notices the flag
        at its next fetch boundary, saves a checkpoint that resumes at
        the INTERRUPTED epoch (the partial epoch is redone — its steps
        are not individually recoverable), and exits cleanly. Installed
        only in the main thread of the main interpreter; a prior handler
        is chained so external supervisors still see the signal.
        """
        import signal
        import threading

        if threading.current_thread() is not threading.main_thread():
            return _HANDLER_NOT_INSTALLED
        self._preempted = False
        # NB getsignal() returns None for a handler installed from C —
        # still a value we must RESTORE (hence the distinct sentinel
        # for the not-installed case above)
        prev = signal.getsignal(signal.SIGTERM)

        def handler(signum, frame):
            self._preempted = True
            if callable(prev) and prev not in (
                signal.SIG_IGN, signal.SIG_DFL, handler
            ):
                prev(signum, frame)

        signal.signal(signal.SIGTERM, handler)
        return prev

    def _checkpoint_if_preempted(self, epoch: int) -> None:
        """Called at metrics-window boundaries inside the hot loop.

        Multi-host: the local SIGTERM flag is AGREED across hosts first
        (signal delivery skews by milliseconds; a host branching into
        the save collectives while another dispatches the next train
        step would deadlock the slice — the exact failure this feature
        exists to avoid). Any host's flag preempts everyone.
        """
        preempted = bool(getattr(self, "_preempted", False))
        if jax.process_count() > 1:
            import numpy as _np
            from jax.experimental import multihost_utils

            flags = multihost_utils.process_allgather(
                _np.int32(preempted)
            )
            preempted = bool(flags.max())
        if not preempted:
            return
        graftscope.emit("train.preempted", cat="train", epoch=epoch)
        if dist.is_primary():
            print(
                f"SIGTERM received: checkpointing at epoch {epoch} "
                f"(resume redoes the interrupted epoch) and exiting"
            )
        # resume continues AT `epoch`: load_checkpoint restores
        # state.epoch and main.py starts from state.epoch + 1. If a
        # REAL end-of-epoch checkpoint for epoch-1 already exists
        # (--save_every), keep it: overwriting it with mid-epoch state
        # would destroy a clean artifact for zero resume benefit.
        from .checkpoint import checkpoint_path

        if self.ckpt_backend == "orbax":
            target = os.path.join(self._orbax.directory, str(epoch - 1))
            exists = self._orbax.has_epoch(epoch - 1)
        else:
            target = checkpoint_path(self.save_path, epoch - 1)
            exists = os.path.exists(target)
        # The skip-vs-save decision must be UNIFORM across hosts: only
        # the primary writes msgpack checkpoints, so with a non-shared
        # save_path the file exists only there — a per-host
        # os.path.exists would send the primary down the skip branch
        # while workers enter save_checkpoint's gather collective,
        # deadlocking the slice. The primary's verdict is broadcast
        # (same pattern as resolve_auto_resume).
        if jax.process_count() > 1:
            import numpy as _np
            from jax.experimental import multihost_utils

            exists = bool(
                multihost_utils.broadcast_one_to_all(_np.int32(exists))
            )
        if exists:
            if dist.is_primary():
                print(f"keeping existing {target} (same resume point)")
        else:
            self._save_state(
                self.state.replace(epoch=jnp.asarray(epoch - 1, jnp.int32)),
                epoch - 1,
            )
        raise SystemExit(0)

    def _save_state(self, state: TrainState, epoch: int,
                    wait: bool = True) -> None:
        """One checkpoint write through the configured backend. EVERY
        host calls this: the msgpack path's sharded-leaf gather is a
        collective (the write itself is primary-gated inside), and the
        orbax path has every host writing its own shards.

        ``wait=False`` (async orbax) lets a periodic mid-training save
        overlap serialization with the next epochs; callers that rely
        on the artifact existing when they move on (final epoch,
        preemption exit) keep the default."""
        with graftscope.span("train.checkpoint", cat="train",
                             epoch=epoch, backend=self.ckpt_backend,
                             wait=wait):
            if self.ckpt_backend == "orbax":
                self._orbax.save(state, epoch)
                if wait:
                    self._orbax.wait()
            else:
                save_checkpoint(self.save_path, state, epoch)
                if dist.is_primary():
                    prune_checkpoints(self.save_path,
                                      self.keep_checkpoints)

    def fit(self) -> TrainState:
        """The reference's epoch loop (``main.py:67-82``)."""
        prev_handler = self._install_preemption_handler()
        try:
            # an unhandled exception unwinding the epoch loop dumps
            # the flight ring first (preemption's SystemExit is exempt
            # — that exit is the graceful path, not a crash)
            with graftscope.flight_recorder("trainer loop"):
                self._fit_epochs()
        finally:
            try:
                if self.ckpt_backend == "orbax":
                    # an async periodic save may still be in flight
                    # (e.g. when an exception unwinds the epoch loop) —
                    # make it durable before the process can exit
                    self._orbax.wait()
            finally:
                # a caller's process must not permanently swallow
                # SIGTERM after training ends — restore EVEN IF the
                # wait above raises (failed async commit)
                self._restore_handler(prev_handler)
        if dist.is_primary():
            draw_plot(self.save_path)
        return self.state

    def _fit_epochs(self) -> None:
        for epoch in range(self.start_epoch, self.epochs + 1):
            # LR schedule is a function of the epoch carried in the
            # state (uniform across replicas — fixed vs reference
            # main.py:69-70).
            self.state = self.state.replace(
                epoch=jnp.asarray(epoch, jnp.int32)
            )
            self.train_epoch(epoch)
            self.validate(epoch, mode="test")
            periodic = self.save_every and epoch % self.save_every == 0
            if epoch == self.epochs or periodic:
                # mid-training periodic saves may overlap with the
                # next epochs (async orbax); the final one is durable
                # before fit returns
                self._save_state(self.state, epoch,
                                 wait=epoch == self.epochs)

    @staticmethod
    def _restore_handler(prev_handler) -> None:
        if prev_handler is not _HANDLER_NOT_INSTALLED:
            import signal

            # None = prior handler lives in C and is invisible to
            # Python; SIG_DFL at least lets TERM terminate again
            signal.signal(
                signal.SIGTERM,
                signal.SIG_DFL if prev_handler is None else prev_handler,
            )

    # -------------------------------------------------------------- train

    def train_epoch(self, epoch: int) -> None:
        batch_time = AverageMeter()
        data_time = AverageMeter()
        losses = AverageMeter()
        top1 = AverageMeter()

        self.train_loader.set_epoch(epoch)
        n_batches = len(self.train_loader)
        skipped = 0  # steps the NaN/inf grad guard refused to apply
        pending = []  # device-resident metric dicts since the last fetch
        window_start = time.time()
        end = time.time()
        for i, (images, labels) in enumerate(
            prefetch_to_device(self.train_loader, self.mesh)
        ):
            data_time.update(time.time() - end)
            # data-wait span, recorded retroactively from the meter's
            # own measurement — graftscope adds NO clock reads or
            # syncs to the hot loop, only an append when armed
            graftscope.emit_span("train.data", data_time.val,
                                 cat="train", batch=i)
            self.state, metrics = self.train_step(self.state, images, labels)
            # NO host sync here: the scalars stay on device and the next
            # step's dispatch overlaps this one's execution.
            pending.append(metrics)
            if i % self.print_freq == 0 or i == n_batches - 1:
                # graftheal: the liveness gate sits at the SAME window
                # boundary as the preemption check — a dead peer
                # raises a named PeerLostError here, before this host
                # dispatches more steps whose collectives would hang
                # on it (one global read when no monitor is armed)
                dist.gate_collectives()
                self._checkpoint_if_preempted(epoch)
                with graftscope.span("train.metrics_fetch", cat="train",
                                     epoch=epoch, steps=len(pending)):
                    fetched = jax.device_get(pending)  # the sync point
                for m in fetched:
                    # the guard's skip indicator rides the same windowed
                    # fetch — a skipped step is VISIBLE, never silent,
                    # and its metrics (the poisoned batch's, possibly
                    # NaN) stay out of every meter
                    if int(m.get("skipped", 0)):
                        skipped += 1
                        continue
                    losses.update(float(m["loss"]), int(m["count"]))
                    top1.update(float(m["prec1"]), int(m["count"]))
                now = time.time()
                batch_time.update(
                    (now - window_start) / len(pending), len(pending)
                )
                # the fetch boundary is the ONE honest per-window
                # timing point under async dispatch: the window span
                # covers its steps' wall clock, attributed here
                graftscope.emit_span(
                    "train.window", now - window_start, cat="train",
                    epoch=epoch, steps=len(pending),
                    step_avg_s=batch_time.val)
                window_start = now
                # live gauges for --stats_port: host values already in
                # hand at this (the loop's one) sync boundary
                global_batch = getattr(self.train_loader,
                                       "batch_size", 0)
                self.live.update(
                    epoch=epoch, batch=i, loss=losses.avg,
                    prec1=top1.avg, step_time_s=batch_time.val,
                    images_per_sec=(0.0 if not batch_time.val else
                                    global_batch / batch_time.val),
                    steps_skipped=skipped)
                pending = []
                if dist.is_primary() and i % self.print_freq == 0:
                    print(
                        "Epoch: [{0}][{1}/{2}]\t"
                        "Time {batch_time.val:.3f} ({batch_time.avg:.3f})\t"
                        "Data {data_time.val:.3f} ({data_time.avg:.3f})\t"
                        "Loss {loss.val:.4f} ({loss.avg:.4f})\t"
                        "Prec {top1.val:.3f}% ({top1.avg:.3f}%)".format(
                            epoch, i, n_batches,
                            batch_time=batch_time, data_time=data_time,
                            loss=losses, top1=top1,
                        )
                    )
            end = time.time()
        if dist.is_primary():
            if skipped:
                print(
                    f"Epoch [{epoch}]: NaN/inf grad guard skipped "
                    f"{skipped}/{n_batches} step(s) (params carried "
                    "through unchanged)"
                )
            self.train_logger.write([epoch, losses.avg, top1.avg])

    # ---------------------------------------------------------------- eval

    def validate(self, epoch: int, mode: str = "test") -> float:
        batch_time = AverageMeter()
        losses = AverageMeter()
        total_correct = 0

        self.test_loader.set_epoch(epoch)
        # EMA evaluation: swap the averaged weights in (standard EMA
        # practice; BN running stats are already their own EMA).
        eval_state = self.state
        if self.ema_decay and getattr(self.state, "ema_params", None):
            eval_state = self.state.replace(params=self.state.ema_params)
        if self._zero:
            # the eval step reads params/stats only; its replicated
            # state spec would silently all-gather the sharded moment
            # buckets per batch — hand it a state without them
            eval_state = eval_state.replace(opt_state={})
        n_batches = len(self.test_loader)
        pending = []
        window_start = time.time()
        for i, batch in enumerate(
            prefetch_to_device(self.test_loader, self.mesh)
        ):
            if len(batch) == 3:
                images, labels, valid = batch
            else:  # loader without validity info: everything counts
                images, labels = batch
                valid = jnp.ones(labels.shape, bool)
            pending.append(self.eval_step(eval_state, images, labels, valid))
            if i % self.print_freq == 0 or i == n_batches - 1:
                with graftscope.span("train.eval_fetch", cat="train",
                                     epoch=epoch, steps=len(pending)):
                    fetched = jax.device_get(pending)
                for m in fetched:
                    losses.update(float(m["loss"]), int(m["count"]))
                    total_correct += int(m["correct"])  # GLOBAL (psum-ed)
                now = time.time()
                batch_time.update(
                    (now - window_start) / len(pending), len(pending)
                )
                window_start = now
                pending = []
                if dist.is_primary() and i % self.print_freq == 0:
                    print(
                        mode,
                        ": [{0}/{1}]\t"
                        "Time {batch_time.val:.3f} ({batch_time.avg:.3f})\t"
                        "Loss {loss.val:.4f} ({loss.avg:.4f})".format(
                            i, n_batches, batch_time=batch_time, loss=losses
                        ),
                    )
        total_acc = 100.0 * total_correct / self.test_loader.dataset_size
        if dist.is_primary():
            print("Accuracy {:.2f}".format(total_acc))
            self.test_logger.write([epoch, losses.avg, float(total_acc)])
        return total_acc
