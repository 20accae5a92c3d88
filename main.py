"""CLI entrypoint — same seven flags as the reference (``main.py:21-30``).

What changes underneath (the TPU-native design, SURVEY.md §7): no
``mp.spawn`` — ONE process per host drives all local chips via a named
``(data, model)`` mesh; ``--world_size`` sets the data-axis (DP) degree
the way it set the number of spawned GPU processes in the reference
(``main.py:28,185-188``); the NCCL rendezvous on ``127.0.0.1:20080``
(``main.py:190-193``) becomes ``jax.distributed`` pod init (multi-host)
or nothing (single host).

Extension flags (all optional, defaults reproduce the reference):
``--data_root``, ``--synthetic``, ``--dtype``, ``--model_parallel``,
``--seed``, ``--resume``.

Testing without chips: PMDT_FORCE_CPU_DEVICES=8 virtualizes 8 CPU
devices (same mechanism as the test suite).
"""

import argparse
import os
import shutil

from pytorch_multiprocessing_distributed_tpu.runtime import (
    scope as graftscope)

parser = argparse.ArgumentParser(description="Confidence Aware Learning")
parser.add_argument('--batch_size', default=64, type=int, help='Batch size')
parser.add_argument('--epochs', default=20, type=int, help='Total number of epochs to run')
parser.add_argument('--model', default='res', type=str, help='Models name to use [res, dense, vgg]')
parser.add_argument('--save_path', default='./test/', type=str,
                    help='Savefiles directory: logs, checkpoints, plots AND a\n'
                         'main.py snapshot land here (run_model). The default\n'
                         './test/ is a run artifact, gitignored — not the\n'
                         'tests/ suite')
parser.add_argument('--gpu', default='7', type=str, help='GPU id to use')
parser.add_argument('--print-freq', '-p', default=10, type=int, metavar='N', help='print frequency (default: 10)')
parser.add_argument('--world_size', default=2, type=int, help='Gpu use number')
# --- TPU-native extensions (not in the reference CLI) ---
parser.add_argument('--dataset', default='cifar', choices=['cifar', 'imagenet'],
                    help='dataset family: cifar (reference parity) or imagenet '
                         '(BASELINE configs #2/#3 — ImageFolder tree or --synthetic)')
parser.add_argument('--data_root', default='', type=str,
                    help='dataset root (cifar: cifar-10-batches-py inside; '
                         'imagenet: train/ + val/ ImageFolder tree)')
parser.add_argument('--synthetic', action='store_true',
                    help='use a deterministic synthetic dataset (no files needed)')
parser.add_argument('--num_classes', default=0, type=int,
                    help='label count (0 = auto: 10 cifar / 1000 imagenet)')
parser.add_argument('--image_size', default=0, type=int,
                    help='square input size (0 = auto: 32 cifar / 224 imagenet)')
parser.add_argument('--dtype', default='float32', choices=['float32', 'bfloat16'],
                    help='compute dtype for conv/matmul (params stay f32)')
parser.add_argument('--model_parallel', default=1, type=int,
                    help='model-axis size of the mesh (1 = pure DP, reference mode)')
parser.add_argument('--zero', action='store_true',
                    help='graftzero: sharded weight update on the '
                         'explicit shard_map-DP step — grads reduce-'
                         'scatter into per-rank bucket shards, the '
                         'optimizer updates only the local shard '
                         '(moments sharded from step one, ~1/world '
                         'optimizer HBM per chip), params all-gather '
                         'back. Bit-identical trajectory; checkpoints '
                         'stay mode-portable (gather-on-save). Pure DP '
                         'only — see --zero1/--fsdp for the GSPMD path')
parser.add_argument('--zero1', action='store_true',
                    help='ZeRO-1: shard optimizer moments over the data '
                         'axis (each replica stores 1/world of them; '
                         'GSPMD inserts the reduce-scatter/all-gather)')
parser.add_argument('--fsdp', action='store_true',
                    help='FSDP/ZeRO-3: shard params, BN stats AND '
                         'optimizer moments over the data axis (each '
                         'replica stores ~1/world of the model; GSPMD '
                         'all-gathers params per layer and reduce-'
                         'scatters grads). For models bigger than chip '
                         'HBM; pure DP is faster when the model fits')
parser.add_argument('--grad_accum', default=1, type=int,
                    help='accumulate gradients over N sequential '
                         'microbatches per optimizer step (activation '
                         'memory of one microbatch, one weight update) — '
                         'the per-device batch must divide by N')
parser.add_argument('--clip_grad_norm', default=0.0, type=float,
                    help='clip the global gradient norm to this bound '
                         'before the update (0 = off); applied to the '
                         'already-averaged gradients, torch '
                         'clip_grad_norm_ semantics')
parser.add_argument('--label_smoothing', default=0.0, type=float,
                    help='cross-entropy label smoothing epsilon '
                         '(torch CrossEntropyLoss(label_smoothing=e))')
parser.add_argument('--ema', default=0.0, type=float, metavar='DECAY',
                    help='track an exponential moving average of the '
                         'params with this decay (e.g. 0.999) and use '
                         'it for evaluation; 0 = off')
parser.add_argument('--remat', action='store_true',
                    help='rematerialize activations in the backward '
                         '(jax.checkpoint): ~1.3x step time for a much '
                         'smaller HBM footprint — buys batch sizes the '
                         'chip could not otherwise hold')
parser.add_argument('--seed', default=0, type=int, help='init/seed for params and shuffling')
parser.add_argument('--resume', default='', type=str,
                    help="checkpoint path to resume from, or 'auto' = "
                         "latest model_*.pth in --save_path (reference "
                         "has no resume)")
parser.add_argument('--save_every', default=0, type=int,
                    help='checkpoint every N epochs (0 = reference '
                         'behavior: final epoch only)')
parser.add_argument('--keep_checkpoints', default=0, type=int,
                    help='retain only the K newest periodic checkpoints '
                         '(0 = keep all)')
parser.add_argument('--ckpt_backend', default='msgpack',
                    choices=['msgpack', 'orbax'],
                    help='msgpack = reference-parity model_{epoch}.pth '
                         '(one host-gathered file, torch-interoperable); '
                         'orbax = sharded per-host writes under '
                         '{save_path}/orbax/ — no gather, scales with '
                         'the model; needs shared storage across hosts. '
                         "With orbax, --resume takes 'auto' or an epoch "
                         'number')
parser.add_argument('--ckpt_async', action='store_true',
                    help='overlap checkpoint serialization with training '
                         '(orbax backend only); the final-epoch and '
                         'preemption saves are always durable before '
                         'exit')
parser.add_argument('--lr', default=0.0, type=float,
                    help='base learning rate (0 = optimizer default: '
                         '0.1 sgd / 1e-3 lamb, the reference values)')
parser.add_argument('--lr_schedule', default='multistep',
                    choices=['multistep', 'cosine'],
                    help='multistep = reference MultiStepLR([60,80], 0.1); '
                         'cosine = cosine decay to 0 over --epochs with '
                         '--warmup_epochs linear warmup')
parser.add_argument('--warmup_epochs', default=0, type=int,
                    help='linear LR warmup epochs (cosine schedule only)')
parser.add_argument('--optimizer', default='sgd',
                    choices=['sgd', 'lamb', 'sgd_fused'],
                    help='sgd = reference config (main.py:51-55); lamb = '
                         'large-batch layerwise-adaptive (BASELINE #5); '
                         'sgd_fused = same SGD trajectory via the fused '
                         'single-pass Pallas update kernel')
parser.add_argument('--profile', default='', type=str, metavar='LOGDIR',
                    help='capture a jax.profiler trace of the run into '
                         'LOGDIR (TensorBoard-loadable; off when empty)')
parser.add_argument('--torch_export', action='store_true',
                    help='additionally export the final weights as a '
                         'torch-loadable state_dict '
                         '(model_{epoch}.torch.pth, reference model '
                         'naming; ResNet family only)')
parser.add_argument('--max_restarts', default=0, type=int,
                    help='graftheal supervised restart: catch named-'
                         'fatal errors (GraftFaultError family — lost '
                         'peer, poisoned pool, exhausted retries), '
                         're-run rendezvous, and restart the run '
                         'resuming from the newest digest-valid '
                         'checkpoint (--resume auto semantics) — at '
                         'most N times with exponential backoff '
                         '(0 = die on first fatal, the old behavior)')
parser.add_argument('--restart_backoff', default=1.0, type=float,
                    help='first-restart delay in seconds (doubles per '
                         'restart, capped at 30s)')
graftscope.add_cli_args(parser, stats_port=True)


def main(args):
    if args.torch_export and not (
        args.model == "res" or args.model.startswith("resnet")
    ):
        # Fail BEFORE the training run, not after hours of work: the
        # torch state_dict mapping covers the ResNet family only.
        raise SystemExit(
            f"--torch_export supports the ResNet family only "
            f"(got --model {args.model})"
        )
    # arm before any jax work: compile/placement phases belong on the
    # timeline too (zero cost when no graftscope flag is set; the
    # Trainer's spans and the flight recorder attach automatically)
    graftscope.arm_from_args(args)
    from pytorch_multiprocessing_distributed_tpu.runtime import hbm

    if args.stats_port:
        # graftmeter: arm the HBM ledger before any state is placed so
        # the Trainer's params/opt-state registrations land on it
        hbm.arm()
    # Backend selection must happen before device queries.
    from pytorch_multiprocessing_distributed_tpu.utils.hostenv import (
        announce_done, announce_run, force_cpu_devices_from_env)

    force_cpu_devices_from_env()
    from pytorch_multiprocessing_distributed_tpu.utils.compile_cache import (
        CompileLog, enable_compilation_cache)

    cache_dir = enable_compilation_cache()
    compile_log = CompileLog()

    import jax
    import jax.numpy as jnp

    from pytorch_multiprocessing_distributed_tpu import data as datamod
    from pytorch_multiprocessing_distributed_tpu import models
    from pytorch_multiprocessing_distributed_tpu.parallel import (
        dist, make_mesh)
    from pytorch_multiprocessing_distributed_tpu.train import (
        create_train_state, load_checkpoint)
    from pytorch_multiprocessing_distributed_tpu.train.optim import (
        cosine_lr, multistep_lr, sgd)
    from pytorch_multiprocessing_distributed_tpu.train.trainer import Trainer

    # Every pure-flag validation BEFORE dist/device/data work (the
    # repo-wide convention train_lm.py states explicitly: an invalid
    # combo must not cost a backend bring-up or a dataset read, and
    # must never surface as an unrelated crash later).
    if args.model in models.LM_MODELS:
        raise ValueError(
            f"--model {args.model} is a language model: it trains on "
            "token sequences via pytorch_multiprocessing_distributed_tpu"
            ".train.lm (make_lm_train_step), not through this image-"
            "classification CLI. See MIGRATION.md."
        )
    if args.optimizer == "sgd_fused" and (
        args.zero1 or args.fsdp or args.model_parallel > 1
    ):
        raise ValueError(
            "--optimizer sgd_fused is the explicit shard_map-DP "
            "path's fused kernel; under --zero1/--fsdp/--model_parallel "
            "the GSPMD partitioner cannot shard through the opaque "
            "Pallas call (it would replicate the moment buffers, "
            "defeating the sharding). Use --optimizer sgd there."
        )
    if args.zero and (args.zero1 or args.fsdp or args.model_parallel > 1):
        raise ValueError(
            "--zero is the explicit shard_map-DP sharded update; "
            "--zero1/--fsdp/--model_parallel run the GSPMD path, which "
            "shards state via placement instead — pick one family."
        )
    if args.zero and args.optimizer == "sgd_fused":
        raise ValueError(
            "--zero shards the update through the transform's "
            "update()/shard_update() path; the fused Pallas whole-"
            "update kernel cannot run on shards. Use --optimizer sgd "
            "or lamb with --zero."
        )
    if args.zero and args.ckpt_backend == "orbax":
        raise ValueError(
            "--zero checkpoints via msgpack gather-on-save (the "
            "artifact round-trips between --zero and plain runs); "
            "--ckpt_backend orbax would persist the sharded layout."
        )
    if args.warmup_epochs and args.lr_schedule != "cosine":
        raise ValueError(
            "--warmup_epochs applies to --lr_schedule cosine (the "
            "reference's MultiStepLR has no warmup)"
        )
    # dataset-derived geometry (the reference hardcodes 32x32/10-way,
    # data.py:11 + model/resnet.py:86; here the imagenet route widens it)
    is_imagenet = args.dataset == "imagenet"
    image_size = args.image_size or (224 if is_imagenet else 32)
    if not is_imagenet and image_size != 32:
        raise ValueError(
            "--dataset cifar is fixed at 32x32 (the reference resizes to "
            "32, data.py:11); --image_size applies to --dataset imagenet"
        )

    dist.init_process()
    if dist.is_primary():
        announce_run(cache_dir)

    mesh = make_mesh(args.world_size, args.model_parallel)
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32

    if not args.data_root:
        args.data_root = "./imagenet" if is_imagenet else "./cifar10_data"
    args.image_size = image_size

    # loaders first (reference order: main.py:36 -> data.py:6-59), so the
    # model head can size itself from what the dataset actually contains
    # (a FolderImageNet tree derives its own class count).
    train_loader, test_loader = datamod.get_loader(args, mesh)
    num_classes = (
        args.num_classes
        or getattr(getattr(train_loader, "dataset", None), "num_classes", None)
        or (1000 if is_imagenet else 10)
    )
    args.num_classes = num_classes

    # model (reference main.py:39-40 — only 'res' didn't crash there).
    # Pure DP binds the data axis into BN for the explicit pmean stat
    # sync; the TP path (model_parallel > 1) runs under global-semantics
    # GSPMD jit where batch stats are global by construction, so BN must
    # NOT carry an axis name there (train/step.py make_train_step_tp).
    use_gspmd = args.model_parallel > 1 or args.zero1 or args.fsdp
    model = models.get_model(
        args.model, dtype=dtype,
        bn_axis=None if use_gspmd else "data",
        num_classes=num_classes,
        stem="imagenet" if is_imagenet else "cifar",
    )

    # optimizer + schedule — default is the exact reference config
    # (main.py:51-59); the alternatives are the model-layer extension
    # seam BASELINE configs #4/#5 train through
    def make_schedule(base_default):
        base = args.lr or base_default
        if args.lr_schedule == "cosine":
            return cosine_lr(base, args.epochs,
                             warmup_epochs=args.warmup_epochs)
        # warmup x non-cosine is rejected in the flag-validation block
        return multistep_lr(base, milestones=[60, 80], gamma=0.1)

    if args.optimizer == "lamb":
        from pytorch_multiprocessing_distributed_tpu.train.lamb import lamb

        optimizer = lamb(
            learning_rate=make_schedule(1e-3),
            weight_decay=0.0001,
        )
    elif args.optimizer == "sgd_fused":
        # GSPMD combos rejected up in the flag-validation block
        from pytorch_multiprocessing_distributed_tpu.ops.pallas.fused_update import (
            sgd_pallas)

        optimizer = sgd_pallas(
            learning_rate=make_schedule(0.1),
            momentum=0.9,
            weight_decay=0.0001,
            nesterov=True,
        )
    else:
        optimizer = sgd(
            learning_rate=make_schedule(0.1),
            momentum=0.9,
            weight_decay=0.0001,
            nesterov=True,
        )

    state = create_train_state(
        model,
        jax.random.PRNGKey(args.seed),
        jnp.zeros((2, image_size, image_size, 3), jnp.float32),
        optimizer,
        ema=args.ema > 0,
    )
    start_epoch = 1
    if args.ckpt_backend == "orbax" and args.resume:
        from pytorch_multiprocessing_distributed_tpu.train.orbax_ckpt import (
            OrbaxCheckpointer)

        ck = OrbaxCheckpointer(args.save_path)
        if args.resume == "auto":
            # latest_epoch broadcasts the primary's verdict itself
            epoch = ck.latest_epoch()
        else:
            try:
                epoch = int(args.resume)
            except ValueError:
                raise SystemExit(
                    f"--ckpt_backend orbax: --resume must be 'auto' or "
                    f"an epoch number (orbax checkpoints are epoch-keyed "
                    f"directories under {{save_path}}/orbax/), got "
                    f"{args.resume!r}"
                )
        if epoch is None:
            if dist.is_primary():
                print(f"--resume auto: no orbax checkpoint under "
                      f"{args.save_path}; starting fresh")
        else:
            # device_get: the restore lands committed on the template's
            # (single-device, pre-shard_state) placement; committed
            # leaves would then fight the mesh sharding inside the
            # jitted step. Host arrays are placement-free — the trainer
            # re-shards them exactly like a fresh init (shard_state for
            # zero1/fsdp/TP, jit replication for plain DP).
            state = jax.device_get(ck.restore(state, epoch))
            start_epoch = int(state.epoch) + 1
            if dist.is_primary():
                print(f"Resumed from {ck.directory}/{epoch} "
                      f"(continuing at epoch {start_epoch})")
        ck.close()
    auto_resume = False
    if args.ckpt_backend != "orbax" and args.resume == "auto":
        from pytorch_multiprocessing_distributed_tpu.train.checkpoint import (
            resolve_auto_resume)

        args.resume = resolve_auto_resume(args.save_path) or ""
        auto_resume = bool(args.resume)
        if not args.resume and dist.is_primary():
            print(f"--resume auto: no checkpoint under {args.save_path}; "
                  "starting fresh")
    if args.ckpt_backend != "orbax" and args.resume:
        if auto_resume:
            # auto picks the checkpoint, so it also owns the recovery:
            # a corrupt newest checkpoint (digest mismatch) is reported
            # and the previous valid epoch restores instead. An
            # EXPLICIT --resume path still fails loudly — the user
            # named that file.
            from pytorch_multiprocessing_distributed_tpu.train.checkpoint import (
                checkpoint_epoch, load_with_fallback)

            # anchor the fallback walk at the primary-resolved epoch:
            # a stale EXTRA checkpoint on one host (newer than what the
            # primary resolved) must not shift that host's walk and get
            # misdiagnosed as cross-host divergence
            state, args.resume = load_with_fallback(
                args.save_path, state,
                anchor=checkpoint_epoch(args.resume))
        else:
            state = load_checkpoint(args.resume, state)
        # continue the epoch series (LR schedule + log numbering) from
        # where the checkpoint left off
        start_epoch = int(state.epoch) + 1
        if dist.is_primary():
            print(f"Resumed from {args.resume} (continuing at epoch {start_epoch})")

    from pytorch_multiprocessing_distributed_tpu.ops.losses import (
        smooth_cross_entropy_loss)

    loss_fn = smooth_cross_entropy_loss(args.label_smoothing)
    trainer = Trainer(
        model=model,
        optimizer=optimizer,
        mesh=mesh,
        state=state,
        train_loader=train_loader,
        test_loader=test_loader,
        save_path=args.save_path,
        epochs=args.epochs,
        print_freq=args.print_freq,
        start_epoch=start_epoch,
        zero=args.zero,
        zero1=args.zero1,
        fsdp=args.fsdp,
        remat=args.remat,
        grad_accum=args.grad_accum,
        loss_fn=loss_fn,
        clip_grad_norm=args.clip_grad_norm or None,
        ema_decay=args.ema or None,
        save_every=args.save_every,
        keep_checkpoints=args.keep_checkpoints,
        ckpt_backend=args.ckpt_backend,
        ckpt_async=args.ckpt_async,
    )
    stats_server = None
    health = None
    if args.stats_port:
        # live trainer telemetry: hbm_* capacity gauges (graftmeter
        # ledger) + the loop's windowed loss/throughput, on /metrics
        # and /snapshot.json over stdlib http.server — plus /healthz
        # (graftheal): 200 only while the run is up, with last-beat
        # ages when a PMDT_HEARTBEAT monitor is armed
        from pytorch_multiprocessing_distributed_tpu.runtime import (
            fleet, heal)

        health = heal.HealthState()
        # graftfleet: goodput_* gauges classified from the Trainer's
        # own spans (train.window/data/metrics_fetch/checkpoint)
        fleet.arm_goodput()

        def live_snapshot():
            snap = dict(trainer.live)
            ledger = hbm.active_ledger()
            if ledger is not None:
                snap.update(ledger.snapshot())
            snap.update(fleet.goodput_gauges())
            return snap

        stats_server = graftscope.start_stats_server(
            live_snapshot, port=args.stats_port, prefix="pmdt",
            health_fn=lambda: heal.healthz(health,
                                           heal.active_monitor()),
            # /events.json (graftfleet): the armed scope, served
            # live, ?since= cursor for incremental scrapes
            events_fn=graftscope.scope_events_fn)
        print(f"stats: http://127.0.0.1:"
              f"{stats_server.server_address[1]}/metrics "
              f"(+ /healthz)", flush=True)
        # announce this rank's scrape address to the fleet store
        # (no-op unless PMDT_FLEET armed a monitor at rendezvous)
        fleet.publish_endpoint(
            f"127.0.0.1:{stats_server.server_address[1]}")
        health.to_ready("training")

    try:
        if args.profile:
            from pytorch_multiprocessing_distributed_tpu.utils.profiler import trace

            with trace(args.profile):
                trainer.fit()
        else:
            trainer.fit()
    except BaseException:
        # the supervised-restart path (--max_restarts) re-enters
        # main() on the SAME fixed --stats_port: a listener left
        # behind by the dying run would turn every restart into
        # EADDRINUSE — release it before the named fatal propagates
        if stats_server is not None:
            stats_server.shutdown()
        raise

    if args.torch_export:
        from pytorch_multiprocessing_distributed_tpu.train.checkpoint import (
            _gather_for_host)
        from pytorch_multiprocessing_distributed_tpu.utils.torch_interop import (
            save_torch_checkpoint)

        # COLLECTIVE gather first — under --zero1/--fsdp/--model_parallel
        # the state is sharded across hosts, so every host must
        # participate before the primary-only write (same contract as
        # save_checkpoint).
        params, batch_stats = _gather_for_host(
            (trainer.state.params, trainer.state.batch_stats))
        if dist.is_primary():
            out = os.path.join(
                args.save_path, f"model_{args.epochs}.torch.pth")
            save_torch_checkpoint(
                out, jax.device_get(params), jax.device_get(batch_stats))
            print(f"Exported torch state_dict -> {out}")

    if dist.is_primary():
        graftscope.export_from_args(args)
        announce_done(compile_log)
    if stats_server is not None:
        if health is not None:
            health.to_dead("run complete")
        stats_server.shutdown()
    dist.destroy_process_group()


def run_model(args):
    """Experiment bring-up (reference ``run_model``, ``main.py:180-188``):
    create the save dir, snapshot this script into it, run —
    optionally under graftheal's bounded-restart supervisor
    (``--max_restarts``): a named fatal (lost peer, poisoned engine
    state, exhausted retries) tears the pod down, backs off, re-runs
    rendezvous, and restarts the run with ``--resume auto`` — so every
    restart resumes from the newest digest-valid checkpoint through
    ``load_with_fallback``. Restart budget exhaustion fails loudly
    (``RestartBudgetExhausted``)."""
    if not os.path.exists(args.save_path):
        os.makedirs(args.save_path)
    shutil.copy(__file__, os.path.join(args.save_path, 'main.py'))
    if not args.max_restarts:
        main(args)
        return
    from pytorch_multiprocessing_distributed_tpu.runtime import heal

    def target(attempt):
        if attempt:
            # resume from the newest digest-valid checkpoint (auto
            # owns corrupt-artifact fallback; main() re-resolves it)
            args.resume = "auto"
        return main(args)

    def rerendezvous():
        # tear the pod down so the restarted run re-runs bring-up
        # (init_process is idempotent only while initialized)
        from pytorch_multiprocessing_distributed_tpu.parallel import (
            dist)

        dist.destroy_process_group()

    heal.Supervisor(target, max_restarts=args.max_restarts,
                    backoff_s=args.restart_backoff,
                    rendezvous=rerendezvous).run()


if __name__ == "__main__":
    run_model(parser.parse_args())
