"""Language-model training CLI — the LM counterpart of ``main.py``.

The reference trains ConvNets only; this CLI is the framework-native
entry point for the GPT family, surfacing every LM parallelism strategy
through flags on ONE mesh abstraction:

    --parallel dp               pure data parallelism (shard_map + psum)
    --parallel sp --degree 4    sequence parallelism over a (data, seq)
                                mesh; --sp_mode ring|zigzag|ulysses
    --parallel tp --degree 2    GSPMD tensor parallelism (Megatron-style
                                trailing-dim sharding, zero1/fsdp-ready)
    --parallel pp --degree 4    pipelined training (GPipe schedule,
                                vocab-parallel embed/head, per-stage
                                block residency)

plus ``--n_experts`` for Switch-MoE feed-forwards (trained against the
load-balancing aux + router z losses). Artifacts mirror ``main.py``:
``train.log`` rows (``{epoch:04d} {loss:.6f} {ppl:.6f}``), a final
``model_{epoch}.pth`` checkpoint, and (dense models) a greedy sample
from ``inference.generate`` as a smoke signal.

Data-free by construction: ``--corpus_tokens`` synthesizes a
deterministic Zipf stream (``data.synthetic_tokens``); pass
``--corpus`` with an ``np.save``-format int32 token file (detected by
magic bytes) or ANY text file / directory (byte-level tokens,
``data.text``).

Run on the CPU mesh:  PMDT_FORCE_CPU_DEVICES=8 python train_lm.py \\
    --model gpt_tiny --parallel sp --degree 4 --sp_mode zigzag \\
    --epochs 2 --save_path /tmp/lm
"""

import argparse
import math
import os
import time

from pytorch_multiprocessing_distributed_tpu.runtime import (
    scope as graftscope)

parser = argparse.ArgumentParser(
    description="TPU-native GPT training (LM counterpart of main.py)")
parser.add_argument('--model', default='gpt_tiny', type=str,
                    help='gpt_tiny | gpt_small | gpt_medium')
parser.add_argument('--batch_size', default=32, type=int,
                    help='global batch (sequences per step)')
parser.add_argument('--seq_len', default=128, type=int)
parser.add_argument('--epochs', default=2, type=int)
parser.add_argument('--lr', default=0.1, type=float)
parser.add_argument('--lr_schedule', default='constant',
                    choices=['constant', 'cosine'],
                    help='cosine = decay to 0 over --epochs with '
                         '--warmup_epochs linear warmup')
parser.add_argument('--warmup_epochs', default=0, type=int)
parser.add_argument('--save_path', default='./lm_run/', type=str)
parser.add_argument('--resume', default='', type=str,
                    help="checkpoint path to resume from, or 'auto' = "
                         "latest model_<epoch>.pth under --save_path "
                         "(same semantics as main.py)")
parser.add_argument('--save_every', default=0, type=int,
                    help='also checkpoint every N epochs (0 = final '
                         'epoch only)')
parser.add_argument('--keep_checkpoints', default=0, type=int,
                    help='retain only the newest K checkpoints of the '
                         '--save_every series (0 = keep all)')
parser.add_argument('--ckpt_backend', default='msgpack',
                    choices=['msgpack', 'orbax'],
                    help='msgpack = single-file model_<epoch>.pth; '
                         'orbax = sharded per-host OCDBT writes under '
                         '{save_path}/orbax/ (multi-host scale; '
                         '--resume takes auto or an epoch number)')
parser.add_argument('--ckpt_async', action='store_true',
                    help='orbax only: overlap periodic saves with '
                         'training (final save stays durable-before-'
                         'exit)')
parser.add_argument('--print_freq', default=10, type=int)
parser.add_argument('--seed', default=0, type=int)
parser.add_argument('--corpus', default='', type=str,
                    help='token source: a .npy int32 file, OR any text '
                         'file / directory of text files (byte-level '
                         'tokens, ids 0..255 + 256 as doc separator — '
                         'fits gpt_tiny\'s 257 vocab out of the box); '
                         'empty = synthetic stream')
parser.add_argument('--corpus_tokens', default=200_000, type=int,
                    help='synthetic stream length when --corpus is empty')
parser.add_argument('--dtype', default='float32',
                    choices=['float32', 'bfloat16'])
parser.add_argument('--parallel', default='dp',
                    choices=['dp', 'sp', 'tp', 'pp'])
parser.add_argument('--pp_schedule', default='gpipe',
                    choices=['gpipe', '1f1b'],
                    help='pipeline schedule: gpipe (autodiff through '
                         'the forward schedule) or 1f1b (interleaved '
                         'fwd/bwd, O(stages) activation residency)')
parser.add_argument('--degree', default=1, type=int,
                    help='size of the sp/tp/pp axis (data axis gets the '
                         'rest of the devices)')
parser.add_argument('--sp_mode', default='ring',
                    choices=['ring', 'zigzag', 'ulysses'])
parser.add_argument('--n_experts', default=0, type=int,
                    help='> 0: Switch-MoE feed-forward in every block')
parser.add_argument('--moe_top_k', default=1, type=int,
                    help='experts per token: 1 = Switch (raw top prob), '
                         '>= 2 = GShard (renormalized top-k weights)')
parser.add_argument('--moe_aux_weight', default=0.01, type=float)
parser.add_argument('--remat', action='store_true')
parser.add_argument('--vocab_chunks', default=0, type=int,
                    help='stream the LM head + cross-entropy over N '
                         'vocab slices so [B,S,V] logits never '
                         'materialize (big-vocab memory knob; exact '
                         'same objective). dp/sp paths; 0 = dense')
parser.add_argument('--grad_accum', default=1, type=int,
                    help='microbatches per update (dp/sp paths)')
parser.add_argument('--zero', action='store_true',
                    help='graftzero sharded weight update (dp path '
                         'only): grads reduce-scatter into per-rank '
                         'bucket shards, the optimizer updates the '
                         'local shard (moments sharded — ~1/world '
                         'optimizer HBM per chip), params all-gather '
                         'back. Bit-identical trajectory; msgpack '
                         'checkpoints stay mode-portable '
                         '(gather-on-save)')
parser.add_argument('--zero1', action='store_true',
                    help='ZeRO-1 optimizer sharding (tp path only)')
parser.add_argument('--fsdp', action='store_true',
                    help='ZeRO-3 param sharding (tp path only)')
parser.add_argument('--val_frac', default=0.0, type=float,
                    help='hold out this fraction of the token stream '
                         'and log per-epoch val loss/ppl to test.log')
parser.add_argument('--hf_init', default='', type=str, metavar='PATH',
                    help='initialize from an HF-format GPT-2 state_dict '
                         '(torch .pth/.bin); geometry must match --model. '
                         'Builds the GPT-2 configuration (ln_eps=1e-5, '
                         'biasless head) so re-export stays exact')
parser.add_argument('--hf_export', action='store_true',
                    help='after training, also write the weights as an '
                         'HF-loadable GPT-2 state_dict '
                         '(model_{epochs}.hf.pth). Trains with a '
                         'biasless head (GPT-2 has no head-bias slot); '
                         'dense dp/sp/tp models only')
parser.add_argument('--sample', default=0, type=int,
                    help='after training, print N decoded continuation '
                         'tokens (any --parallel; greedy unless '
                         '--sample_beams)')
parser.add_argument('--sample_beams', default=0, type=int,
                    help='> 1: decode --sample tokens with beam search '
                         'of this width instead of greedy (prints the '
                         'best beam)')
parser.add_argument('--max_restarts', default=0, type=int,
                    help='graftheal supervised restart: catch named-'
                         'fatal errors (GraftFaultError family), '
                         're-run rendezvous, restart the run with '
                         '--resume auto (newest digest-valid '
                         'checkpoint) — at most N times with '
                         'exponential backoff (0 = die on first '
                         'fatal)')
parser.add_argument('--restart_backoff', default=1.0, type=float,
                    help='first-restart delay in seconds (doubles per '
                         'restart, capped at 30s)')
graftscope.add_cli_args(parser, stats_port=True)


def main(args):
    """Run the training CLI — under graftheal's bounded-restart
    supervisor when ``--max_restarts`` is set (restarts resume from
    the newest digest-valid checkpoint via ``--resume auto``; budget
    exhaustion raises the named ``RestartBudgetExhausted``)."""
    if not args.max_restarts:
        return _run(args)
    from pytorch_multiprocessing_distributed_tpu.runtime import heal

    def target(attempt):
        if attempt:
            args.resume = 'auto'
        return _run(args)

    def rerendezvous():
        from pytorch_multiprocessing_distributed_tpu.parallel import (
            dist)

        dist.destroy_process_group()

    return heal.Supervisor(target, max_restarts=args.max_restarts,
                           backoff_s=args.restart_backoff,
                           rendezvous=rerendezvous).run()


def _run(args):
    # arm before any jax work: compile/placement phases belong on the
    # timeline too (zero cost when no graftscope flag is set)
    graftscope.arm_from_args(args)
    from pytorch_multiprocessing_distributed_tpu.runtime import hbm

    if args.stats_port:
        # graftmeter: live trainer HBM/throughput gauges are scrapeable
        # while the run is hot — arm the ledger before any state lands
        hbm.arm()
    from pytorch_multiprocessing_distributed_tpu.utils.hostenv import (
        announce_done, announce_run, device_memory,
        force_cpu_devices_from_env)

    force_cpu_devices_from_env()
    from pytorch_multiprocessing_distributed_tpu.utils.compile_cache import (
        CompileLog, enable_compilation_cache, program_facts)

    cache_dir = enable_compilation_cache()
    compile_log = CompileLog()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_multiprocessing_distributed_tpu import models
    from pytorch_multiprocessing_distributed_tpu.data.lm import (
        TokenLoader, synthetic_tokens)
    from pytorch_multiprocessing_distributed_tpu.parallel import (
        dist, make_mesh)
    from pytorch_multiprocessing_distributed_tpu.train.checkpoint import (
        checkpoint_epoch, load_checkpoint, load_with_fallback,
        prune_checkpoints, resolve_auto_resume, save_checkpoint)
    from pytorch_multiprocessing_distributed_tpu.train.lm import (
        create_lm_train_state, make_lm_train_step, make_lm_train_step_tp)
    from pytorch_multiprocessing_distributed_tpu.train.optim import sgd
    from pytorch_multiprocessing_distributed_tpu.train.step import (
        shard_batch, shard_state)
    from pytorch_multiprocessing_distributed_tpu.utils import Logger

    dtype = jnp.bfloat16 if args.dtype == 'bfloat16' else jnp.float32

    model_kw = dict(dtype=dtype, n_experts=args.n_experts)
    if args.moe_top_k != 1:
        if not args.n_experts:
            raise SystemExit('--moe_top_k needs --n_experts > 0')
        model_kw.update(moe_top_k=args.moe_top_k)
    if args.parallel == 'sp':
        model_kw.update(seq_axis='seq', sp_mode=args.sp_mode)
    if args.parallel in ('tp', 'pp'):
        # Pallas kernels cannot run under the pp step's check_vma
        # shard_map; for tp the XLA path avoids interpret-mode cost off
        # TPU while staying exact
        model_kw.update(attn_impl='xla')
    if args.hf_init or args.hf_export:
        if args.n_experts:
            raise SystemExit(
                '--hf_init/--hf_export cover dense GPTs (MoE blocks '
                'have no GPT-2 representation)')
        # GPT-2 configuration: its LN eps, and no head-bias slot — the
        # export must not have to drop a trained parameter
        model_kw.update(ln_eps=1e-5, head_bias=False)
    if args.resume and args.hf_init:
        raise SystemExit(
            '--resume restores a full TrainState; --hf_init seeds '
            'fresh initial weights — pick one')
    if args.save_every < 0:
        raise SystemExit(f'--save_every must be >= 0, got {args.save_every}')
    if args.ckpt_async and args.ckpt_backend != 'orbax':
        raise SystemExit('--ckpt_async applies to --ckpt_backend orbax')
    if args.ckpt_backend == 'orbax' and args.resume not in ('', 'auto'):
        try:
            int(args.resume)
        except ValueError:
            raise SystemExit(
                f"--ckpt_backend orbax: --resume must be 'auto' or an "
                f"epoch number (orbax checkpoints are epoch-keyed "
                f"directories under {{save_path}}/orbax/), got "
                f"{args.resume!r}")
    model = models.get_model(args.model, **model_kw)
    hf_params = None
    if args.hf_init:
        from pytorch_multiprocessing_distributed_tpu.utils.gpt_interop import (
            load_gpt2_checkpoint)

        hf_model, hf_params = load_gpt2_checkpoint(
            args.hf_init, model.num_heads, **model_kw)
        mine = {k: getattr(model, k) for k in (
            'vocab_size', 'max_seq_len', 'hidden_size', 'num_layers',
            'mlp_dim')}
        theirs = {k: getattr(hf_model, k) for k in mine}
        if mine != theirs:
            raise SystemExit(
                f'--hf_init geometry {theirs} does not match '
                f'--model {args.model} {mine}')
    # Every inapplicable/oversized flag combo fails BEFORE the run (the
    # main.py convention: a dropped flag or a post-training crash after
    # hours of work is worse than an immediate error).
    if args.seq_len > model.max_seq_len:
        raise SystemExit(
            f"--seq_len {args.seq_len} exceeds the model's "
            f"max_seq_len {model.max_seq_len}")
    if (args.zero1 or args.fsdp) and args.parallel != 'tp':
        raise SystemExit(
            "--zero1/--fsdp shard state through the GSPMD path; use "
            f"--parallel tp (got --parallel {args.parallel})")
    if args.zero and args.parallel != 'dp':
        raise SystemExit(
            "--zero rewrites the explicit DP step's grad exchange "
            "(reduce-scatter -> sharded update -> all-gather); use "
            f"--parallel dp (got --parallel {args.parallel}; the tp "
            "path's --zero1/--fsdp shard via GSPMD placement instead)")
    if args.zero and args.ckpt_backend == 'orbax':
        raise SystemExit(
            "--zero checkpoints via msgpack gather-on-save (artifacts "
            "round-trip between --zero and plain runs); --ckpt_backend "
            "orbax would persist the sharded layout")
    if args.pp_schedule != 'gpipe' and args.parallel != 'pp':
        raise SystemExit(
            f"--pp_schedule {args.pp_schedule} only applies to "
            f"--parallel pp (got --parallel {args.parallel})")
    if args.remat and args.parallel == 'pp':
        raise SystemExit(
            "--remat is not wired into the pipelined step (gpipe bounds "
            "live activations to the in-flight microbatches; 1f1b "
            "already rematerializes each stage backward internally)")
    if args.vocab_chunks > 1 and args.parallel in ('tp', 'pp'):
        raise SystemExit(
            '--vocab_chunks streams the head inside the dp/sp step '
            '(tp shards the head over the model axis; pp computes a '
            'vocab-parallel LSE already)')
    if args.grad_accum > 1 and args.parallel in ('tp', 'pp'):
        raise SystemExit(
            "--grad_accum is wired into the dp/sp step (pp microbatches "
            "already; for tp use a smaller global batch)")
    if args.val_frac and not 0.0 < args.val_frac < 1.0:
        raise SystemExit(
            f"--val_frac must be in (0, 1), got {args.val_frac}")
    if args.sample_beams and not args.sample:
        raise SystemExit('--sample_beams needs --sample N')
    if args.sample_beams and not (
            1 <= args.sample_beams <= model.vocab_size):
        # fail BEFORE the training run, not at decode time after it
        raise SystemExit(
            f'--sample_beams must be in [1, vocab_size='
            f'{model.vocab_size}], got {args.sample_beams}')
    if args.sample:
        if args.seq_len + args.sample > model.max_seq_len:
            raise SystemExit(
                f"--seq_len {args.seq_len} + --sample {args.sample} "
                f"exceeds max_seq_len {model.max_seq_len}")

    if args.lr_schedule == 'cosine':
        from pytorch_multiprocessing_distributed_tpu.train.optim import (
            cosine_lr)

        lr = cosine_lr(args.lr, args.epochs,
                       warmup_epochs=args.warmup_epochs)
    else:
        if args.warmup_epochs:
            raise SystemExit(
                "--warmup_epochs applies to --lr_schedule cosine")
        lr = args.lr

    # backend/devices touched only AFTER every pure-flag validation —
    # an invalid combo must not cost a (possibly slow) TPU bring-up
    dist.init_process()
    if dist.is_primary():
        announce_run(cache_dir, attn_impl=model.attn_impl)
    n_dev = len(jax.devices())
    deg = args.degree if args.parallel != 'dp' else 1
    if n_dev % max(1, deg):
        raise SystemExit(f"{n_dev} devices not divisible by --degree {deg}")
    dp = n_dev // max(1, deg)

    corpus_is_text = False
    if args.corpus:
        from pytorch_multiprocessing_distributed_tpu.data.text import (
            sniff_bytes)

        def _sniff(path):
            # magic bytes, not extension (see data.text.sniff_bytes);
            # directories defer to load_text_corpus's per-file sniff
            if os.path.isdir(path):
                return 'text'
            with open(path, 'rb') as f:
                return sniff_bytes(f.read(6))

        kind = _sniff(args.corpus)
        if kind == 'npz':
            raise SystemExit(
                f"--corpus {args.corpus} is an npz/zip archive — pass "
                "the np.save (.npy) array itself, or a text file")
        if kind == 'npy':
            tokens = np.load(args.corpus).astype(np.int32)
        else:
            # anything else is raw text: byte-level tokens (ids 0..255,
            # 256 = document separator) — no vocab files needed
            from pytorch_multiprocessing_distributed_tpu.data.text import (
                load_text_corpus)

            try:
                tokens = load_text_corpus(args.corpus)
            except ValueError as e:
                # e.g. a .npy dropped inside a corpus directory: same
                # clean one-line exit as the sibling misuse paths
                raise SystemExit(str(e))
            corpus_is_text = True
        if len(tokens) == 0:
            raise SystemExit(f"--corpus {args.corpus} contains no tokens")
        if tokens.max() >= model.vocab_size or tokens.min() < 0:
            # jit CLAMPS out-of-range gathers silently — without this
            # check an oversized-vocab corpus trains on garbage
            raise SystemExit(
                f"--corpus token ids span [{tokens.min()}, "
                f"{tokens.max()}] but --model {args.model} has "
                f"vocab_size {model.vocab_size}")
    else:
        tokens = synthetic_tokens(
            args.corpus_tokens, vocab_size=model.vocab_size,
            seed=args.seed)
    val_loader = None
    if args.val_frac:
        n_val = int(len(tokens) * args.val_frac)
        min_val = args.batch_size * args.seq_len
        if n_val < min_val:
            raise SystemExit(
                f"--val_frac {args.val_frac} holds out {n_val} tokens "
                f"but one eval batch needs {min_val} — grow the corpus "
                f"or the fraction")
        tokens, val_tokens = tokens[:-n_val], tokens[-n_val:]
        val_loader = TokenLoader(
            val_tokens, batch_size=args.batch_size,
            seq_len=args.seq_len, world_size=dp, shuffle=False,
            seed=args.seed)
    loader = TokenLoader(
        tokens, batch_size=args.batch_size, seq_len=args.seq_len,
        world_size=dp, seed=args.seed)

    opt = sgd(learning_rate=lr)
    rng = jax.random.PRNGKey(args.seed)
    sample_tok = jnp.zeros((2, args.seq_len), jnp.int32)

    def init_state():
        st = create_lm_train_state(model, rng, sample_tok, opt)
        if hf_params is not None:
            # same tree structure by construction (geometry checked
            # above, head_bias/ln_eps already in model_kw)
            st = st.replace(
                params=jax.tree.map(jnp.asarray, hf_params))
        return st

    # --resume: same main.py semantics (auto = primary host's latest
    # checkpoint broadcast to everyone; resolve AFTER dist init). The
    # template the checkpoint restores into is each branch's
    # freshly-built state — incl. the pipe-stacked tree for pp — so the
    # round trip is structural, BEFORE any GSPMD placement.
    ck = None
    resume_path = args.resume
    resume_epoch = None
    if args.ckpt_backend == 'orbax':
        from pytorch_multiprocessing_distributed_tpu.train.orbax_ckpt import (
            OrbaxCheckpointer)

        ck = OrbaxCheckpointer(args.save_path, async_=args.ckpt_async,
                               keep=args.keep_checkpoints or None)
        if args.resume == 'auto':
            resume_epoch = ck.latest_epoch()
            if resume_epoch is None and dist.is_primary():
                print(f"--resume auto: no orbax checkpoint under "
                      f"{ck.directory}; starting fresh", flush=True)
        elif args.resume:
            resume_epoch = int(args.resume)
    auto_msgpack = False
    if args.ckpt_backend != 'orbax' and resume_path == 'auto':
        resume_path = resolve_auto_resume(args.save_path) or ''
        auto_msgpack = bool(resume_path)
        if not resume_path and dist.is_primary():
            print(f"--resume auto: no checkpoint under "
                  f"{args.save_path}; starting fresh", flush=True)
    start_epoch = 1

    def maybe_resume(st):
        nonlocal start_epoch
        if ck is not None and resume_epoch is not None:
            st = jax.device_get(ck.restore(st, resume_epoch))
            start_epoch = int(st.epoch) + 1
            if dist.is_primary():
                print(f"Resumed from {ck.directory}/{resume_epoch} "
                      f"(continuing at epoch {start_epoch})", flush=True)
        elif ck is None and resume_path:
            if auto_msgpack:
                # auto picked the checkpoint, so it owns the recovery:
                # a corrupt newest checkpoint falls back to the
                # previous valid epoch (an explicit path fails loudly);
                # the walk is anchored at the primary-resolved epoch so
                # a stale extra checkpoint on one host cannot shift it
                st, used = load_with_fallback(
                    args.save_path, st,
                    anchor=checkpoint_epoch(resume_path))
            else:
                st, used = load_checkpoint(resume_path, st), resume_path
            start_epoch = int(st.epoch) + 1
            if dist.is_primary():
                print(f"Resumed from {used} (continuing at "
                      f"epoch {start_epoch})", flush=True)
        return st

    if args.parallel == 'pp':
        from pytorch_multiprocessing_distributed_tpu.parallel import (
            create_pipelined_lm_state, make_pipelined_lm_train_step)

        mesh = make_mesh(dp, deg, axis_names=('data', 'pipe'))
        state = create_pipelined_lm_state(
            model, rng, sample_tok, opt, n_stages=deg,
            params=hf_params)
        state = maybe_resume(state)
        step = make_pipelined_lm_train_step(
            model, opt, mesh, schedule=args.pp_schedule,
            moe_aux_weight=args.moe_aux_weight)
    elif args.parallel == 'tp':
        mesh = make_mesh(dp, deg)
        state = maybe_resume(init_state())
        state = shard_state(state, mesh, zero1=args.zero1, fsdp=args.fsdp)
        step = make_lm_train_step_tp(
            model, opt, mesh, zero1=args.zero1, fsdp=args.fsdp,
            remat=args.remat, moe_aux_weight=args.moe_aux_weight)
    else:
        axes = ('data', 'seq') if args.parallel == 'sp' else ('data',)
        mesh = (make_mesh(dp, deg, axis_names=axes)
                if args.parallel == 'sp' else make_mesh(dp))
        state = maybe_resume(init_state())
        if args.zero:
            # moments sharded from step one — the replicated tree
            # (fresh init or the restored checkpoint) flattens into
            # P(data) buckets; save_checkpoint gathers back on save
            from pytorch_multiprocessing_distributed_tpu.parallel.zero import (
                zeroify_state)

            state = zeroify_state(state, mesh)
        step = make_lm_train_step(
            model, opt, mesh,
            seq_axis='seq' if args.parallel == 'sp' else None,
            remat=args.remat, grad_accum=args.grad_accum,
            moe_aux_weight=args.moe_aux_weight,
            vocab_chunks=args.vocab_chunks, zero=args.zero)

    eval_step = None
    if val_loader is not None:
        from pytorch_multiprocessing_distributed_tpu.train.lm import (
            make_lm_eval_step, make_lm_eval_step_tp)

        if args.parallel == 'pp':
            from pytorch_multiprocessing_distributed_tpu.parallel import (
                make_pipelined_lm_eval_step)

            eval_step = make_pipelined_lm_eval_step(model, mesh)
        elif args.parallel == 'tp':
            eval_step = make_lm_eval_step_tp(
                model, mesh, zero1=args.zero1, fsdp=args.fsdp)
        else:
            eval_step = make_lm_eval_step(
                model, mesh,
                seq_axis='seq' if args.parallel == 'sp' else None,
                vocab_chunks=args.vocab_chunks)

    # graftmeter: trainer state residency on the armed ledger (the tp
    # path already registered inside shard_state — same entry names,
    # same bytes; dp/sp/pp register here). No-op when disarmed.
    from pytorch_multiprocessing_distributed_tpu.train.step import (
        register_state_hbm)

    register_state_hbm(state)

    # live gauges for --stats_port: updated at the print boundary (the
    # loop's one deliberate host sync — no extra fetches), merged with
    # the hbm_* ledger gauges on /metrics + /snapshot.json; /healthz
    # (graftheal) serves 200 only while the run is up, with last-beat
    # ages when a PMDT_HEARTBEAT monitor is armed
    from pytorch_multiprocessing_distributed_tpu.runtime import heal

    live = {}
    stats_server = None
    health = None
    if args.stats_port:
        health = heal.HealthState()
        # graftfleet: goodput_* gauges beside the loss/throughput and
        # hbm_* gauges — classified from the spans the loop already
        # emits (window/data/fetch/checkpoint/restart)
        from pytorch_multiprocessing_distributed_tpu.runtime import (
            fleet)

        fleet.arm_goodput()

        def live_snapshot():
            snap = dict(live)
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

    os.makedirs(args.save_path, exist_ok=True)
    logger = Logger(os.path.join(args.save_path, 'train.log'))
    test_logger = (Logger(os.path.join(args.save_path, 'test.log'))
                   if val_loader is not None else None)
    from pytorch_multiprocessing_distributed_tpu.data.pipeline import (
        prefetch_to_device)

    # dp/sp single-host: double-buffered async H2D (the image Trainer's
    # discipline) — the NEXT batch's transfer is enqueued while the
    # current step computes. Multi-host keeps shard_batch: TokenLoader
    # yields the GLOBAL batch on every host, which is exactly what
    # device_put slices (prefetch's multihost path expects per-host
    # local rows instead). tp/pp steps take the host array directly.
    use_prefetch = (args.parallel in ('dp', 'sp')
                    and jax.process_count() == 1)

    def train_epochs():
        nonlocal state
        # the clock reads below are graftscope's only per-step host
        # cost — taken ONLY while a scope is armed (disarmed, the loop
        # is byte-for-byte the old one)
        armed = graftscope.active_scope() is not None
        for epoch in range(start_epoch, args.epochs + 1):
            state = state.replace(epoch=jnp.asarray(epoch, jnp.int32))
            loader.set_epoch(epoch)
            t0, losses, seen = time.time(), 0.0, 0
            batches = (prefetch_to_device(loader, mesh) if use_prefetch
                       else loader)
            t_ready = time.perf_counter() if armed else 0.0
            t_window = t_ready  # window wall anchor (armed only)
            for i, batch in enumerate(batches):
                if armed:
                    # data wait: time from step dispatch to the next
                    # batch being in hand (prefetch hides H2D here)
                    graftscope.emit_span(
                        "train.data", time.perf_counter() - t_ready,
                        cat="train", epoch=epoch, batch=i)
                if use_prefetch:
                    first = armed and epoch == start_epoch and i == 0
                    if first and hasattr(step, 'lower'):
                        graftscope.emit("train.program", cat="compile",
                                        **program_facts(step, state, batch))
                    state, metrics = step(state, batch)
                    if first:
                        # where the state and the batch really live
                        graftscope.emit(
                            "train.placement", cat="train",
                            param_devices=min(
                                len(leaf.sharding.device_set) for leaf
                                in jax.tree.leaves(state.params)),
                            batch_devices=len(batch.sharding.device_set),
                            bytes_in_use=device_memory("bytes_in_use"))
                elif args.parallel in ('tp', 'pp'):
                    with graftscope.span("train.h2d", cat="train",
                                         batch=i):
                        tok = jnp.asarray(batch)
                    state, metrics = step(state, tok)
                else:
                    with graftscope.span("train.h2d", cat="train",
                                         batch=i):
                        (tok_sharded,) = shard_batch(
                            (jnp.asarray(batch),), mesh)
                    state, metrics = step(state, tok_sharded)
                if i % args.print_freq == 0 or i == len(loader) - 1:
                    # graftheal liveness gate at the window boundary
                    # (one global read unless a monitor is armed): a
                    # dead peer raises a named PeerLostError before
                    # this host dispatches more collective-bearing
                    # steps that would hang on it
                    dist.gate_collectives()
                    # the print boundary is the loop's ONE deliberate
                    # host sync — the same boundary graftscope stamps
                    with graftscope.span("train.metrics_fetch",
                                         cat="train", epoch=epoch,
                                         batch=i) as mspan:
                        skipped = int(
                            np.asarray(metrics.get('skipped', 0)))
                        loss = (None if skipped
                                else float(np.asarray(metrics['loss'])))
                    if armed:
                        # the window span: this fetch boundary is the
                        # one honest per-window timing point under
                        # async dispatch — and the PRODUCTIVE span the
                        # goodput ledger classifies (its nested
                        # train.data waits are subtracted there)
                        now = time.perf_counter()
                        graftscope.emit_span(
                            "train.window", now - t_window,
                            cat="train", epoch=epoch, batch=i)
                        t_window = now
                    if skipped:
                        # NaN/inf grad guard refused this step — its
                        # loss is the poisoned batch's (possibly NaN);
                        # keep it out of the printed line and the
                        # epoch average
                        mspan.note(skipped=True)
                        graftscope.emit("train.step_skipped",
                                        cat="train", epoch=epoch,
                                        batch=i)
                        if dist.is_primary():
                            print(f"Epoch: [{epoch}][{i}/{len(loader)}]\t"
                                  "step skipped (non-finite grads)",
                                  flush=True)
                        t_ready = time.perf_counter() if armed else 0.0
                        continue
                    losses, seen = losses + loss, seen + 1
                    live.update(
                        epoch=epoch, batch=i, loss=loss,
                        tokens_per_sec=(args.batch_size * args.seq_len
                                        * (i + 1)
                                        / (time.time() - t0)))
                    if dist.is_primary():
                        extra = ''
                        if 'moe_aux' in metrics:
                            extra = (f"\tAux "
                                     f"{float(np.asarray(metrics['moe_aux'])):.3f}")
                        print(f"Epoch: [{epoch}][{i}/{len(loader)}]\t"
                              f"Loss {loss:.4f}\t"
                              f"Tok/s {args.batch_size * args.seq_len * (i + 1) / (time.time() - t0):.0f}"
                              f"{extra}", flush=True)
                t_ready = time.perf_counter() if armed else 0.0
            avg = losses / max(1, seen)
            if dist.is_primary():
                logger.write([epoch, avg, math.exp(min(avg, 20.0))])
            if eval_step is not None:
                with graftscope.span("train.validate", cat="train",
                                     epoch=epoch):
                    tot, cnt = 0.0, 0.0
                    # graftzero: the eval step reads params only; its
                    # replicated state spec would all-gather the
                    # sharded moment buckets per batch — strip them
                    eval_state = (state.replace(opt_state={})
                                  if args.zero else state)
                    for batch in val_loader:
                        tok = jnp.asarray(batch)
                        if args.parallel not in ('tp', 'pp'):
                            (tok,) = shard_batch((tok,), mesh)
                        m = eval_step(eval_state, tok)
                        c = float(np.asarray(m['count']))
                        tot = tot + float(np.asarray(m['loss'])) * c
                        cnt = cnt + c
                    vloss = tot / max(1.0, cnt)
                if dist.is_primary():
                    print(f"Val: [{epoch}]\tLoss {vloss:.4f}\t"
                          f"PPL {math.exp(min(vloss, 20.0)):.2f}",
                          flush=True)
                    test_logger.write(
                        [epoch, vloss, math.exp(min(vloss, 20.0))])
            if (args.save_every and epoch % args.save_every == 0
                    and epoch < args.epochs):
                # periodic checkpoint (collective; the final epoch is
                # saved once below)
                with graftscope.span("train.checkpoint", cat="train",
                                     epoch=epoch,
                                     backend=args.ckpt_backend):
                    if ck is not None:
                        ck.save(state, epoch)  # retention inside
                    else:
                        save_checkpoint(args.save_path, state, epoch)
                        if args.keep_checkpoints and dist.is_primary():
                            prune_checkpoints(args.save_path,
                                              args.keep_checkpoints)

    # a crash unwinding the epoch loop dumps the flight ring first —
    # the postmortem starts with the last windows' spans, not a bare
    # stack trace
    try:
        with graftscope.flight_recorder("train_lm epoch loop"):
            train_epochs()
    except BaseException:
        # --max_restarts re-enters _run on the SAME --stats_port: a
        # listener surviving the dying run = EADDRINUSE on restart
        if stats_server is not None:
            stats_server.shutdown()
        raise
    if args.hf_export:
        from pytorch_multiprocessing_distributed_tpu.train.checkpoint import (
            _gather_for_host)

        # ONE collective gather serves both writes below: gathered
        # leaves are fully addressable, so save_checkpoint's internal
        # gather becomes a no-op pass-through
        state = _gather_for_host(state)
    if start_epoch <= args.epochs:
        with graftscope.span("train.checkpoint", cat="train",
                             epoch=args.epochs,
                             backend=args.ckpt_backend, final=True):
            if ck is not None:
                ck.save(state, args.epochs)
                ck.wait()  # final save durable before exit
            else:
                save_checkpoint(args.save_path, state, args.epochs)
                # prune after EVERY save (Trainer semantics): retention
                # means "newest K overall", identically on both
                # backends (orbax's max_to_keep counts the final save)
                if args.keep_checkpoints and dist.is_primary():
                    prune_checkpoints(args.save_path,
                                      args.keep_checkpoints)
    elif dist.is_primary():
        # resume landed past --epochs: nothing trained, and rewriting
        # model_{epochs}.pth would relabel a LATER-epoch state
        print(f"--resume: checkpoint already at epoch "
              f"{start_epoch - 1} >= --epochs {args.epochs}; "
              "nothing to train", flush=True)
    if args.hf_export:
        from pytorch_multiprocessing_distributed_tpu.utils.gpt_interop import (
            save_gpt2_checkpoint)

        if dist.is_primary():
            export_params = state.params
            if args.parallel == 'pp':
                from pytorch_multiprocessing_distributed_tpu.parallel import (
                    unstack_pipeline_params)

                export_params = unstack_pipeline_params(
                    jax.device_get(state.params), model.vocab_size)
            out = os.path.join(args.save_path,
                               f"model_{args.epochs}.hf.pth")
            save_gpt2_checkpoint(out, export_params)
            print(f"HF export: {out}", flush=True)

    if args.sample:
        from pytorch_multiprocessing_distributed_tpu.inference import (
            beam_search, generate)
        from pytorch_multiprocessing_distributed_tpu.inference.generate import (
            register_generate_hbm)

        dense = model.clone(seq_axis=None)
        # graftmeter: the decode's KV residency on the ledger (host
        # boundary — generate itself is jitted); disarmed = no-op
        register_generate_hbm(dense, 1, args.seq_len + args.sample)
        prompt = jnp.asarray(tokens[: args.seq_len][None, :])

        def decode(params, **kw):
            if args.sample_beams > 1:
                toks, _ = beam_search(dense, params, prompt,
                                      max_new_tokens=args.sample,
                                      beam_size=args.sample_beams)
                return toks[:, 0]  # best beam
            return generate(dense, params, prompt,
                            max_new_tokens=args.sample, **kw)

        if (args.parallel == 'tp' and not (args.zero1 or args.fsdp)
                and model.num_heads % deg == 0 and not args.n_experts
                and args.sample_beams <= 1
                and jax.process_count() == 1):
            # decode the GSPMD-sharded params where they live: TP
            # decode shards heads/KV-cache/vocab over the model axis
            # (greedy only — beam search decodes gathered params below;
            # multi-host TP output spans non-addressable shards, so it
            # takes the _gather_for_host branch like every other case)
            out = decode(state.params, mesh=mesh)
        else:
            # every other trained state decodes single-shard: sp params
            # are already the dense tree (replicated), pp restacks, MoE
            # decodes droplessly (inference/generate.py). Gather first —
            # pipe/model-sharded leaves span hosts in multi-host runs and
            # a bare device_get would crash AFTER the whole training run
            # (collective: every host calls it, like save_checkpoint)
            from pytorch_multiprocessing_distributed_tpu.train.checkpoint import (
                _gather_for_host)

            params = jax.device_get(_gather_for_host(state.params))
            if args.parallel == 'pp':
                from pytorch_multiprocessing_distributed_tpu.parallel import (
                    unstack_pipeline_params)

                params = unstack_pipeline_params(
                    params, model.vocab_size)
            out = decode(params)
        if dist.is_primary():
            ids = np.asarray(out[0, -args.sample:]).tolist()
            print("sample:", ids)
            if corpus_is_text:
                from pytorch_multiprocessing_distributed_tpu.data.text import (
                    detokenize)

                print("sample text:", repr(detokenize(ids)), flush=True)

    if ck is not None:
        ck.close()
    if dist.is_primary():
        graftscope.export_from_args(args)
        announce_done(compile_log)
    if stats_server is not None:
        if health is not None:
            health.to_dead("run complete")
        stats_server.shutdown()
    dist.destroy_process_group()


if __name__ == '__main__':
    main(parser.parse_args())
