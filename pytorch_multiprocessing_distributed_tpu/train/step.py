"""The compiled SPMD train/eval step.

This is the parity moment for the reference's hot loop (``main.py:
101-110``): H2D copy, DDP forward (with SyncBatchNorm stat exchange),
cross-entropy, backward with bucketed NCCL all-reduce, SGD step. Here the
entire iteration is ONE jitted ``shard_map`` program over the mesh:

- the global batch arrives sharded over the ``data`` axis (per-replica
  slice = ``batch // world_size``, reference ``data.py:39``);
- params/optimizer state are replicated; the model's BatchNorm binds the
  ``data`` axis name, so batch statistics are ``pmean``-synced in-step
  (== SyncBatchNorm, reference ``main.py:43``);
- gradients are ``pmean``-ed over ``data`` — DDP averages gradients by
  world size, and XLA lowers this to the same ring all-reduce NCCL would
  run, but fused into the step and riding ICI;
- loss / prec@1 / correct counts are reduced in-step, so the host reads
  back three scalars instead of shipping logits (the reference pays a
  device->host sync per batch for ``.item()`` at ``main.py:113-115``).

State is donated: params are updated in place in HBM.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.losses import cross_entropy_loss, cross_entropy_per_sample
from ..runtime import hbm
from ..utils.metrics import topk_accuracy
from ..parallel.mesh import DATA_AXIS, MODEL_AXIS
from .optim import Transform, apply_updates
from .state import TrainState


def _train_body(model, optimizer: Transform, loss_fn: Callable,
                axis_name: Optional[str], remat: bool = False,
                grad_accum: int = 1, dp_size: int = 1,
                clip_grad_norm: Optional[float] = None,
                ema_decay: Optional[float] = None,
                zero_plan=None, zero_overlap: bool = True):
    """The one train-step body both parallelism paths share.

    ``axis_name`` set: per-shard view under ``shard_map`` — grads/metrics
    are explicitly ``pmean``/``psum``-ed over the data axis (the DDP
    analogue). ``axis_name=None``: global view under GSPMD jit — the loss
    is already a global mean, so autodiff produces the reduction and the
    collective calls drop out.

    ``remat``: wrap the forward in ``jax.checkpoint`` so the backward
    recomputes activations instead of keeping them resident in HBM —
    the standard TPU memory/FLOPs trade that buys batch sizes the chip
    could not otherwise hold (~1.3x step time for ~the forward's
    activation footprint back).

    ``grad_accum``: split the batch into this many microbatches and run
    them sequentially under ``lax.scan``, summing gradients, before the
    ONE optimizer step — the standard large-global-batch trade (activation
    memory of one microbatch, one all-reduce, one weight update). The
    microbatch split is STRIDED (sample ``i`` goes to microbatch
    ``i % grad_accum``) so that under GSPMD the batch dimension's
    data-axis sharding stays device-local through the reshape — a
    contiguous split would gather each microbatch from a subset of
    devices (an all-to-all). BatchNorm statistics are computed per
    microbatch and the running stats see ``grad_accum`` momentum updates
    per step (torch grad-accumulation semantics: N small forwards).
    """

    if grad_accum < 1:
        raise ValueError(
            f"grad_accum must be >= 1, got {grad_accum} (1 = no "
            "accumulation; 0/negative would silently disable it)"
        )
    if clip_grad_norm is not None and not clip_grad_norm > 0:
        raise ValueError(
            f"clip_grad_norm must be > 0, got {clip_grad_norm} (a "
            "negative bound would NEGATE gradients; pass None to disable)"
        )
    if ema_decay is not None and not 0.0 < ema_decay < 1.0:
        raise ValueError(
            f"ema_decay must be in (0, 1), got {ema_decay} (>= 1 "
            "diverges exponentially; pass None to disable)"
        )

    def grad_of(params, stats, images, labels):
        def compute_loss(p):
            logits, mutated = model.apply(
                {"params": p, "batch_stats": stats},
                images,
                train=True,
                mutable=["batch_stats"],
            )
            return loss_fn(logits, labels), (logits, mutated["batch_stats"])

        if remat:
            compute_loss = jax.checkpoint(compute_loss)
        return jax.value_and_grad(compute_loss, has_aux=True)(params)

    def body(state: TrainState, images, labels):
        if grad_accum > 1:
            b = images.shape[0]
            # Under shard_map ``b`` IS the per-device batch; under GSPMD
            # it is global, and the PER-DEVICE batch (b / dp) must still
            # divide by grad_accum or the strided microbatch reshape
            # loses its device-locality (GSPMD would silently insert an
            # all-to-all per microbatch — the cost this split avoids).
            if b % (grad_accum * dp_size):
                if axis_name is None:
                    detail = (f"global batch {b}, data-parallel degree "
                              f"{dp_size}")
                    per_dev = b // dp_size
                else:
                    # shard_map body: b is already the PER-DEVICE batch
                    detail = (f"per-device batch {b} as seen inside "
                              f"shard_map; the global batch is b x "
                              f"world_size")
                    per_dev = b
                raise ValueError(
                    f"per-device batch {per_dev} is not divisible by "
                    f"grad_accum={grad_accum} ({detail})"
                )

            def to_micro(x):
                return strided_microbatches(x, grad_accum)

            def micro(carry, mb):
                stats, gsum, lsum, csum = carry
                imgs, labs = mb
                (loss, (logits, new_stats)), grads = grad_of(
                    state.params, stats, imgs, labs
                )
                pred = jnp.argmax(logits, axis=-1)
                corr = jnp.sum((pred == labs).astype(jnp.int32))
                gsum = jax.tree.map(jnp.add, gsum, grads)
                return (new_stats, gsum, lsum + loss, csum + corr), None

            carry0 = (
                state.batch_stats,
                jax.tree.map(jnp.zeros_like, state.params),
                jnp.zeros((), jnp.float32),
                jnp.zeros((), jnp.int32),
            )
            (new_stats, gsum, lsum, correct), _ = jax.lax.scan(
                micro, carry0, (to_micro(images), to_micro(labels))
            )
            # equal-sized microbatches: mean of means == global mean
            grads = jax.tree.map(lambda g: g / grad_accum, gsum)
            loss = lsum / grad_accum
        else:
            (loss, (logits, new_stats)), grads = grad_of(
                state.params, state.batch_stats, images, labels
            )
            pred = jnp.argmax(logits, axis=-1)
            correct = jnp.sum((pred == labels).astype(jnp.int32))

        if zero_plan is not None:
            # graftzero (parallel/zero.py): the grad psum + replicated
            # update becomes reduce-scatter -> sharded update ->
            # all-gather; the guard predicate moves to the scattered
            # shards (same values, partitioned) with ONE summed scalar
            # psum, still BEFORE clipping
            from ..parallel import zero as zero_mod

            g_shards = zero_mod.reduce_scatter_grads(
                grads, zero_plan, axis_name, mean=True,
                overlap=zero_overlap)
            finite = zero_mod.finite_shards(g_shards, axis_name)
            if clip_grad_norm is not None:
                g_shards = zero_mod.clip_shards_by_global_norm(
                    g_shards, axis_name, clip_grad_norm)
            new_params, new_opt = zero_mod.apply_sharded_update(
                optimizer, state.opt_state, g_shards, state.params,
                axis_name, lr_step=state.epoch, overlap=zero_overlap)
        else:
            if axis_name is not None:
                # The DDP all-reduce moment (reference main.py:109):
                # average gradients across the data axis. BN stats were
                # already pmean-ed inside the forward (axis bound by
                # shard_map).
                grads = jax.lax.pmean(grads, axis_name)

            # NaN/inf guard predicate off the AVERAGED grads
            # (replicated, so every shard agrees) and BEFORE clipping —
            # a non-finite norm would poison the clip scale itself
            finite = finite_grads(grads)

            if clip_grad_norm is not None:
                # Global-norm clipping of the ALREADY-averaged
                # gradients (torch.nn.utils.clip_grad_norm_ semantics:
                # one norm over every leaf; scale only when the norm
                # exceeds the bound).
                gnorm = jnp.sqrt(sum(
                    jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in jax.tree.leaves(grads)
                ))
                scale = jnp.minimum(1.0, clip_grad_norm / (gnorm + 1e-6))
                grads = jax.tree.map(lambda g: g * scale, grads)

            if getattr(optimizer, "apply", None) is not None:
                # fused whole-update path (the Pallas single-pass SGD)
                new_params, new_opt = optimizer.apply(
                    grads, state.opt_state, state.params,
                    lr_step=state.epoch
                )
            else:
                updates, new_opt = optimizer.update(
                    grads, state.opt_state, state.params,
                    lr_step=state.epoch
                )
                new_params = apply_updates(state.params, updates)

        count = jnp.asarray(labels.shape[0], jnp.int32)
        if axis_name is not None:
            loss = jax.lax.pmean(loss, axis_name)
            correct = jax.lax.psum(correct, axis_name)
            count = jax.lax.psum(count, axis_name)
        metrics = {"loss": loss, "correct": correct, "count": count}
        metrics["prec1"] = 100.0 * correct / count

        new_state = state.replace(
            params=new_params, batch_stats=new_stats, opt_state=new_opt
        )
        if ema_decay is not None and state.ema_params:
            new_state = new_state.replace(
                ema_params=jax.tree.map(
                    lambda e, p: ema_decay * e + (1.0 - ema_decay) * p,
                    state.ema_params, new_params,
                )
            )
        new_state, metrics = guard_nonfinite(finite, new_state, state,
                                             metrics)
        return new_state, metrics

    return body


def make_train_step(
    model,
    optimizer: Transform,
    mesh: Mesh,
    *,
    loss_fn: Callable = cross_entropy_loss,
    axis_name: str = DATA_AXIS,
    remat: bool = False,
    grad_accum: int = 1,
    clip_grad_norm=None,
    ema_decay=None,
    zero: bool = False,
    zero_overlap: bool = True,
):
    """Build the jitted DP train step.

    Returns ``step(state, images, labels) -> (state, metrics)`` where
    ``metrics = {loss, prec1, correct, count}`` are already globally
    reduced (scalars, replicated).

    ``zero=True`` (graftzero, ``parallel/zero.py``): gradients are
    reduce-scattered along the data axis into per-rank flat shards, the
    optimizer update runs on the local shard only (moments sharded —
    the state must carry a :class:`..parallel.zero.ZeroOptState`, build
    it with ``zero.zeroify_state``), and updated params are
    all-gathered back. Same trajectory bit-for-bit (test-pinned;
    exception: ``clip_grad_norm``, whose global norm is necessarily a
    psum of per-shard partial sums — a different summation order than
    the replicated leafwise norm, so clipped runs agree to float
    reassociation tolerance rather than bitwise). Optimizer HBM drops
    ~1/N per chip. ``zero_overlap=False`` serializes the bucketed
    collectives behind the full backward (the bench's overlap
    baseline).
    """
    if not zero:
        sharded = shard_map(
            _train_body(model, optimizer, loss_fn, axis_name,
                        remat=remat, grad_accum=grad_accum,
                        clip_grad_norm=clip_grad_norm,
                        ema_decay=ema_decay),
            mesh=mesh,
            in_specs=(P(), P(axis_name), P(axis_name)),
            out_specs=(P(), P()),
            check_vma=False,
        )
        return jax.jit(sharded, donate_argnums=(0,))
    return _lazy_zero_step(
        lambda plan: _train_body(
            model, optimizer, loss_fn, axis_name, remat=remat,
            grad_accum=grad_accum, clip_grad_norm=clip_grad_norm,
            ema_decay=ema_decay, zero_plan=plan,
            zero_overlap=zero_overlap),
        mesh, axis_name, n_batch_args=2)


def _lazy_zero_step(make_body, mesh: Mesh, axis_name: str,
                    n_batch_args: int, entry=None):
    """Lazily-bound graftzero jit: shard_map in/out specs depend on the
    state's bucket layout (``ZeroOptState.plan``), so the program binds
    on first call keyed on the state's pytree structure — the shard_map
    twin of :func:`lazy_gspmd_jit`, shared by the image and LM DP
    steps. ``entry(step_fn) -> step_fn`` optionally wraps the jitted
    callee (the LM path's trace-time shape validation).

    The returned step also emits the ``train.grad_comm`` instant on the
    graftscope bus and a fleet arrival stamp per dispatch — the STATIC
    per-step collective bytes from the plan (the
    ``fleet.static_collective_bytes`` discipline: never a device read,
    never a dispatch-only stopwatch), feeding the straggler report's
    byte join. Disarmed cost: two module-global reads.
    """
    from ..parallel import zero as zero_mod
    from ..runtime import fleet as graftfleet
    from ..runtime import scope as graftscope

    compiled = {}

    def _bind(state):
        if not isinstance(state.opt_state, zero_mod.ZeroOptState):
            raise ValueError(
                "zero=True needs a zero-sharded state — build it with "
                "parallel.zero.zeroify_state(state, mesh) after init/"
                "resume")
        key = jax.tree.structure(state)
        if key not in compiled:
            spec = zero_mod.train_state_specs(state, axis_name)
            sharded = shard_map(
                make_body(state.opt_state.plan),
                mesh=mesh,
                in_specs=(spec,) + (P(axis_name),) * n_batch_args,
                out_specs=(spec, P()),
                check_vma=False,
            )
            if entry is not None:
                sharded = entry(sharded)
            compiled[key] = jax.jit(sharded, donate_argnums=(0,))
        return compiled[key]

    def step(state, *args):
        fn = _bind(state)
        if (graftscope.active_scope() is not None
                or graftfleet.active_fleet() is not None):
            plan = state.opt_state.plan
            comm = zero_mod.static_comm_bytes(plan)
            nbytes = comm["reduce_scatter"] + comm["all_gather"]
            graftscope.emit(
                "train.grad_comm", cat="train", nbytes=nbytes,
                buckets=len(plan.buckets), axis=axis_name,
                bucket_bytes=[
                    b.padded * jnp.dtype(b.dtype).itemsize
                    for b in plan.buckets])
            graftfleet.note_arrival("train.grad_comm", axis=axis_name,
                                    nbytes=nbytes)
        return fn(state, *args)

    # graftcheck's lowering handle (the lazy_gspmd_jit contract): the
    # underlying jax.jit program for a given state structure, so the
    # donation/HLO audits interrogate the EXACT program the trainer
    # runs (abstract states work — structure + plan are all it reads)
    step.jit_program = _bind
    return step


def make_eval_step(
    model,
    mesh: Mesh,
    *,
    axis_name: str = DATA_AXIS,
    loss_fn: Callable = cross_entropy_loss,
):
    """Build the jitted eval step (reference ``validate`` inner loop,
    ``main.py:144-151``): forward in eval mode (running BN stats), loss +
    correct-count, globally reduced.

    Two fixes over the reference's eval semantics:
    - the correct count is ``psum``-ed across the data axis (the
      reference divides a per-rank count by the FULL dataset size,
      ``main.py:151,168`` — wrong by ~world_size; its ``reduce_tensor``
      fix is dead code);
    - a per-sample validity mask excludes the sampler's wraparound-
      padding duplicates, so accuracy is exact even when the dataset
      size is not divisible by world_size (SURVEY.md §3.5.3).

    Returns ``step(state, images, labels, valid) -> metrics`` with
    ``metrics = {loss, loss_sum, correct, correct5, count, prec1,
    prec5}``; the sums/counts are masked sums over REAL samples only
    (``correct5``/``prec5`` = top-5, the metric the reference's README
    quotes but never computes — the trainer's stdout/log formats ignore
    it for reference parity; library callers read it from the dict).
    """

    sharded = shard_map(
        _eval_body(model, axis_name, loss_fn=loss_fn),
        mesh=mesh,
        in_specs=(P(), P(axis_name), P(axis_name), P(axis_name)),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(sharded)


def _eval_body(model, axis_name: Optional[str],
               loss_fn: Callable = cross_entropy_loss):
    """Shared eval body (masked-validity accounting) for both paths —
    explicit ``psum`` under ``shard_map`` when ``axis_name`` is set,
    global sums under GSPMD jit when it is ``None``.

    The per-sample criterion mirrors the TRAIN loss (``loss_fn``'s
    ``.per_sample`` companion when it has one — e.g. label smoothing —
    plain cross-entropy otherwise), so train/test losses stay
    comparable, like the reference's shared ``criterion`` (main.py:48).
    """
    per_sample = getattr(loss_fn, "per_sample", cross_entropy_per_sample)

    def body(state: TrainState, images, labels, valid):
        logits = model.apply(
            {"params": state.params, "batch_stats": state.batch_stats},
            images,
            train=False,
        )
        w = valid.astype(jnp.float32)
        per_sample_loss = per_sample(logits, labels)
        pred = jnp.argmax(logits, axis=-1)
        correct = jnp.sum((pred == labels).astype(jnp.float32) * w)
        # top-5: the metric the reference's README quotes but its code
        # never computes (README.md:13-17 vs main.py:129-130); provided
        # at the metrics level, stdout/log formats stay reference-exact.
        # The [maxk, batch] correctness matrix comes from the SAME
        # jittable helper the meters use (utils/metrics.topk_accuracy).
        k = min(5, logits.shape[-1])
        _, correct_mat = topk_accuracy(logits, labels, topk=(k,))
        in_top5 = jnp.any(correct_mat, axis=0)
        correct5 = jnp.sum(in_top5.astype(jnp.float32) * w)
        loss_sum = jnp.sum(per_sample_loss * w)
        count = jnp.sum(w)
        if axis_name is not None:
            loss_sum, correct, correct5, count = jax.lax.psum(
                (loss_sum, correct, correct5, count), axis_name
            )
        metrics = {
            "loss_sum": loss_sum,
            "correct": correct.astype(jnp.int32),
            "correct5": correct5.astype(jnp.int32),
            "count": count.astype(jnp.int32),
        }
        safe = jnp.maximum(metrics["count"], 1)
        metrics["loss"] = loss_sum / safe
        metrics["prec1"] = 100.0 * metrics["correct"] / safe
        metrics["prec5"] = 100.0 * metrics["correct5"] / safe
        return metrics

    return body


def _check_tp_model(model) -> None:
    """The GSPMD path's one model contract, enforced where it matters.

    Under global-semantics jit there is no bound mesh axis, so a model
    built with ``bn_axis="data"`` would crash deep inside BatchNorm at
    trace time with an unbound-axis error and no pointer here. (BN stats
    are global by construction on this path — ``bn_axis=None`` IS
    sync-BN.)
    """
    if getattr(model, "bn_axis", None) is not None:
        raise ValueError(
            "make_*_step_tp requires a model built with bn_axis=None: "
            "under GSPMD jit batch statistics are computed over the "
            f"global batch (= sync-BN); got bn_axis={model.bn_axis!r}. "
            "Build the model with bn_axis=None for model_parallel > 1 "
            "(see main.py)."
        )


def finite_grads(grads):
    """On-device all-finite predicate over a gradient tree — the
    NaN/inf skip-and-count guard's ONE scalar bool. No host sync: the
    step SELECTS between updated and carried state with it, and the
    skip indicator rides the metrics dict (``skipped``) into the
    trainer's existing windowed metric fetches like every other
    scalar. A single poisoned batch (loss overflow, corrupt record)
    then costs one skipped step instead of NaN'd params and momenta
    forever.

    The reduction SHAPE matters under GSPMD: a per-leaf
    ``all(isfinite)`` AND-chain lowers to one tiny pred all-reduce PER
    LEAF on a sharded step (~+38 serialized collective launches per
    step for the FSDP/TP LM steps, each paying fixed launch latency
    on a pod). Summing per-leaf non-finite COUNTS keeps every
    cross-leaf combine an ADD, the one form XLA's AllReduceReassociate
    pass folds into a single fused all-reduce (``AR(a)+AR(b) ->
    AR(a+b)``, applied transitively down the chain) — AND-combines
    have no such pass. That fold happens in the TPU/GPU compiler
    pipelines where collective launch latency is real; the committed
    CPU-lowered fingerprints still count one all-reduce per leaf (the
    CPU pipeline skips collective-optimization passes — its
    "collectives" are shared-memory copies with no launch cost).
    int32 counts are exact (no float rounding), and a total of 0 is
    equivalent to every leaf all-finite. On replicated grads (the
    shard_map DP paths guard AFTER the psum) the whole reduction is
    local either way."""
    bad = jnp.asarray(0, jnp.int32)
    for g in jax.tree.leaves(grads):
        bad = bad + jnp.sum(
            jnp.logical_not(jnp.isfinite(g)).astype(jnp.int32))
    return bad == 0


def guard_nonfinite(finite, new_state, state, metrics):
    """Skip-and-count: keep ``new_state`` when ``finite``, carry the
    OLD state through otherwise (params, stats, momenta and EMA all
    selected — a non-finite grad must not leak into ANY buffer), and
    record the skip in ``metrics['skipped']``. Pure ``jnp.where`` on
    a scalar predicate: no branch, no host sync, donation-friendly."""
    guarded = jax.tree.map(lambda a, b: jnp.where(finite, a, b),
                           new_state, state)
    metrics["skipped"] = (~finite).astype(jnp.int32)
    return guarded, metrics


def strided_microbatches(x, accum: int):
    """``[b, ...] -> [accum, b//accum, ...]``, STRIDED (sample ``i`` to
    microbatch ``i % accum``): under GSPMD the batch dim's data-axis
    sharding stays device-local through the reshape — a contiguous
    split would gather each microbatch from a device subset (an
    all-to-all). The ONE copy of the convention (image + LM steps)."""
    b = x.shape[0]
    return x.reshape(b // accum, accum, *x.shape[1:]).swapaxes(0, 1)


def tp_param_spec(leaf, tp: int) -> P:
    """Partition rule for tensor parallelism over the ``model`` axis.

    Shard the trailing dimension — the output-feature dim of every Dense
    kernel ``(in, out)`` and Conv kernel ``(H, W, Cin, Cout)``, and the
    channel dim of BN scale/bias/stats — when it divides evenly;
    replicate everything else (scalars, odd-sized leaves). Keeping ALL
    channel-indexed leaves sharded the same way means layer outputs,
    their BN parameters and their optimizer moments line up with no
    resharding between layers; XLA/GSPMD propagates the specs and
    inserts the (all-gather / reduce-scatter) collectives.
    """
    shape = getattr(leaf, "shape", ())
    if tp > 1 and len(shape) >= 1 and shape[-1] % tp == 0 and shape[-1] >= tp:
        return P(*([None] * (len(shape) - 1)), MODEL_AXIS)
    return P()


def zero1_opt_spec(leaf, dp: int, tp: int) -> P:
    """Partition rule for ZeRO-1 optimizer-state sharding.

    Starts from the TP trailing-dim rule (moments must line up with
    their params on the ``model`` axis), then additionally shards the
    LARGEST remaining divisible dimension over ``data`` — each DP
    replica then stores only 1/dp of every moment buffer, and GSPMD
    turns the weight update into reduce-scatter(grads) -> sharded
    update -> all-gather(params), the ZeRO-1 schedule (cf. SURVEY §2.3
    "sharded optimizer: optional optimization").
    """
    spec = list(tp_param_spec(leaf, tp))
    shape = getattr(leaf, "shape", ())
    spec += [None] * (len(shape) - len(spec))
    best, best_size = None, 0
    for i, n in enumerate(shape):
        if spec[i] is None and n % dp == 0 and n >= dp and n > best_size:
            best, best_size = i, n
    if best is not None:
        spec[best] = DATA_AXIS
    return P(*spec)


def state_shardings(state, mesh: Mesh, *, zero1: bool = False,
                    fsdp: bool = False):
    """NamedSharding pytree for a :class:`TrainState` under TP (and,
    optionally, ZeRO sharding over ``data``).

    Optimizer moments mirror parameter shapes, so the trailing-dim TP
    rule covers params, batch_stats and opt_state uniformly.

    ``zero1`` spreads each optimizer moment buffer across the data axis
    (params stay replicated per DP rank — the ZeRO-1 memory point).

    ``fsdp`` is the ZeRO-3 point: params, batch_stats AND moments are
    all sharded over ``data`` (largest divisible dim,
    :func:`zero1_opt_spec`), so each replica stores ~1/dp of the whole
    model. GSPMD then materializes full params layer-by-layer at use
    (all-gather in the forward/backward) and reduce-scatters gradients —
    the FSDP schedule — instead of keeping a resident replica. This is
    the trade that fits models bigger than chip HBM; for HBM-resident
    models pure DP is faster (no per-layer gathers).
    """
    tp = mesh.shape[MODEL_AXIS]
    dp = mesh.shape[DATA_AXIS]

    def tp_sh(l):
        return NamedSharding(mesh, tp_param_spec(l, tp))

    def dp_sh(l):
        return NamedSharding(mesh, zero1_opt_spec(l, dp, tp))

    param_sh = dp_sh if fsdp else tp_sh
    opt_sh = dp_sh if (zero1 or fsdp) else tp_sh

    return state.replace(
        params=jax.tree.map(param_sh, state.params),
        batch_stats=jax.tree.map(param_sh, state.batch_stats),
        opt_state=jax.tree.map(opt_sh, state.opt_state),
        epoch=NamedSharding(mesh, P()),
        ema_params=jax.tree.map(param_sh, state.ema_params),
    )


def shard_state(state, mesh: Mesh, *, zero1: bool = False,
                fsdp: bool = False):
    """Place a replicated state onto the mesh with TP/ZeRO shardings."""
    placed = jax.tree.map(
        lambda l, s: jax.device_put(l, s),
        state,
        state_shardings(state, mesh, zero1=zero1, fsdp=fsdp),
    )
    # graftmeter: this is the moment trainer state lands on the mesh —
    # ledger the residency here (disarmed: one global read)
    register_state_hbm(placed)
    return placed


def register_state_hbm(state, prefix: str = "train") -> None:
    """Put a :class:`TrainState`'s resident footprint on the armed
    graftmeter HBM ledger (no-op when disarmed — one global read):
    parameters, optimizer moments, batch stats and the EMA shadow,
    each its own gauge. Bytes are PER-CHIP, from host sharding
    metadata only (``hbm.tree_shard_nbytes`` — a replicated leaf
    charges its full size, a ``P(data)``-sharded leaf its
    ``1/data``-slice), so under graftzero/ZeRO-1/FSDP the
    ``hbm_opt_state_bytes`` gauge on ``/metrics`` IS the measured
    ~1/N saving the sharded-update schedule claims — a live delta,
    not a divided-by-hand estimate."""
    if hbm.active_ledger() is None:
        return
    hbm.register(f"{prefix}.params",
                 hbm.tree_shard_nbytes(state.params),
                 category="params")
    hbm.register(f"{prefix}.opt_state",
                 hbm.tree_shard_nbytes(state.opt_state),
                 category="opt_state")
    stats = getattr(state, "batch_stats", None)
    if stats:
        hbm.register(f"{prefix}.batch_stats",
                     hbm.tree_shard_nbytes(stats),
                     category="params")
    ema = getattr(state, "ema_params", None)
    if ema:
        hbm.register(f"{prefix}.ema_params",
                     hbm.tree_shard_nbytes(ema),
                     category="params")


def make_train_step_tp(
    model,
    optimizer: Transform,
    mesh: Mesh,
    *,
    loss_fn: Callable = cross_entropy_loss,
    zero1: bool = False,
    fsdp: bool = False,
    remat: bool = False,
    grad_accum: int = 1,
    clip_grad_norm=None,
    ema_decay=None,
):
    """Build the jitted DP x TP train step (GSPMD path).

    Where :func:`make_train_step` expresses data parallelism explicitly
    (``shard_map`` + ``pmean`` — the DDP analogue), tensor parallelism is
    expressed the idiomatic XLA way: the step body is written with GLOBAL
    semantics and the *shardings* carry the parallelism — params'
    trailing (output-feature) dims live on the ``model`` axis
    (:func:`tp_param_spec`), the batch lives on ``data``, and GSPMD
    inserts the collectives. Consequences:

    - gradient averaging over ``data`` needs no explicit ``pmean``: the
      loss is a global mean, so autodiff produces the reduction;
    - sync-BN needs no axis name: batch statistics are means over the
      globally-sharded batch, which IS the cross-replica statistic
      (build the model with ``bn_axis=None`` for this path);
    - the chip-count math of the reference's ``--model_parallel`` flag
      becomes real: passing 2 halves each chip's parameter/optimizer
      footprint instead of silently replicating work (round-2 VERDICT
      weak #2).

    Returns ``step(state, images, labels) -> (state, metrics)``;
    ``state`` must be placed with :func:`shard_state` first.
    """
    _check_tp_model(model)
    body = _train_body(model, optimizer, loss_fn, axis_name=None,
                       remat=remat, grad_accum=grad_accum,
                       dp_size=mesh.shape[DATA_AXIS],
                       clip_grad_norm=clip_grad_norm, ema_decay=ema_decay)

    return lazy_gspmd_jit(
        body, mesh,
        arg_specs=(P(DATA_AXIS, None, None, None), P(DATA_AXIS)),
        returns_state=True, zero1=zero1, fsdp=fsdp,
    )


def lazy_gspmd_jit(body, mesh: Mesh, *, arg_specs, returns_state: bool,
                   zero1: bool = False, fsdp: bool = False):
    """Lazily-bound GSPMD jit: the ONE place the 'cache the jitted
    program keyed on the state's pytree structure, build in/out
    shardings from state_shardings on first call' idiom lives
    (train/eval image TP steps and the LM TP step all bind through
    here — a future change to the caching key applies everywhere).

    ``body(state, *args)``; ``arg_specs`` are the PartitionSpecs of the
    non-state args; metrics outputs are replicated.
    """
    compiled = {}

    def _bind(state):
        key = jax.tree.structure(state)
        if key not in compiled:
            state_sh = state_shardings(state, mesh, zero1=zero1,
                                       fsdp=fsdp)
            in_sh = (state_sh,) + tuple(
                NamedSharding(mesh, s) for s in arg_specs)
            repl = NamedSharding(mesh, P())
            compiled[key] = jax.jit(
                body,
                in_shardings=in_sh,
                out_shardings=(state_sh, repl) if returns_state else repl,
                donate_argnums=(0,) if returns_state else (),
            )
        return compiled[key]

    def step(state, *args):
        # in_shardings depend on the state pytree structure; bind
        # lazily on first call (and on structure change, e.g. resume)
        return _bind(state)(state, *args)

    # graftcheck's lowering handle: the underlying jax.jit program for
    # a given state structure (abstract states work — only the pytree
    # structure is read), so the donation/HLO audits interrogate the
    # EXACT program the trainer runs instead of a reconstruction
    step.jit_program = _bind
    return step


def make_eval_step_tp(model, mesh: Mesh, *, zero1: bool = False,
                      fsdp: bool = False,
                      loss_fn: Callable = cross_entropy_loss):
    """Eval twin of :func:`make_train_step_tp` (global semantics; same
    masked-validity accounting as :func:`make_eval_step`). ``zero1``
    must match the train step's so in_shardings agree with where the
    state actually lives (a mismatch would silently reshard per call).
    """
    _check_tp_model(model)
    body = _eval_body(model, axis_name=None, loss_fn=loss_fn)
    return lazy_gspmd_jit(
        body, mesh,
        arg_specs=(P(DATA_AXIS, None, None, None), P(DATA_AXIS),
                   P(DATA_AXIS)),
        returns_state=False, zero1=zero1, fsdp=fsdp,
    )


def audit_programs():
    """graftcheck registration hook (``analysis/programs.py``): the
    canonical image DP train step — the parity moment for the
    reference's DDP loop, and the program whose communication contract
    IS the design: gradients cross the wire exactly once per step:
    the psums move ONE parameter tree of bytes (the BN statistic
    pmeans beside it are channel-sized). ``expect_grad_psums`` pins
    that inline; dropping the ``pmean(grads)`` or reducing twice
    moves it. The donation audit
    (``min_donated``) pins that ``donate_argnums=(0,)`` still reaches
    the lowered module — deleting it doubles resident state HBM
    without failing a single numeric test.

    The TP/FSDP GSPMD twins register from ``train/lm.py`` on the tiny
    GPT, where compiling the partitioned HLO is cheap enough for
    tier-1."""
    def build_dp():
        import numpy as np

        from ..models import get_model
        from ..parallel.mesh import audit_mesh
        from .optim import sgd
        from .state import create_train_state

        mesh = audit_mesh(data=8)
        model = get_model("res", stem="cifar", num_classes=10,
                          bn_axis=DATA_AXIS)
        opt = sgd(learning_rate=0.1)
        state = jax.eval_shape(
            lambda: create_train_state(
                model, jax.random.PRNGKey(0),
                jnp.zeros((2, 32, 32, 3)), opt))
        step = make_train_step(model, opt, mesh)
        images = jax.ShapeDtypeStruct((16, 32, 32, 3), jnp.float32)
        labels = jax.ShapeDtypeStruct((16,), jnp.int32)
        params_bytes = sum(
            int(np.prod(leaf.shape)) * leaf.dtype.itemsize
            for leaf in jax.tree.leaves(state.params))
        return {
            "fn": step,
            "args": (state, images, labels),
            "mesh": mesh,
            "lower_fn": step,
            "params_bytes": params_bytes,
            "expect_grad_psums": 1,
            "min_donated": len(jax.tree.leaves(state.params)),
        }

    def build_dp_zero():
        """The graftzero twin: SAME model/mesh/batch as build_dp, but
        the committed communication contract is FLIPPED — zero psums
        sized like the parameter tree; the gradient exchange is
        exactly one reduce-scatter (the full padded flat buckets) plus
        one all-gather (the per-rank shard) on the data axis, byte
        volumes pinned inline AND committed. The NaN-guard's summed
        non-finite scalar psum stays (pinned separately:
        ``max_psum_bytes`` bounds every remaining psum at the BN
        statistic size — a grad-sized one reappearing fails here, not
        just in the refreshable budget)."""
        import numpy as np

        from ..models import get_model
        from ..parallel import zero as zero_mod
        from ..parallel.mesh import audit_mesh
        from .optim import sgd
        from .state import create_train_state

        mesh = audit_mesh(data=8)
        model = get_model("res", stem="cifar", num_classes=10,
                          bn_axis=DATA_AXIS)
        opt = sgd(learning_rate=0.1)
        state = jax.eval_shape(
            lambda: create_train_state(
                model, jax.random.PRNGKey(0),
                jnp.zeros((2, 32, 32, 3)), opt))
        state = zero_mod.zeroify_state(state, mesh)
        step = make_train_step(model, opt, mesh, zero=True)
        jit_fn = step.jit_program(state)
        images = jax.ShapeDtypeStruct((16, 32, 32, 3), jnp.float32)
        labels = jax.ShapeDtypeStruct((16,), jnp.int32)
        params_bytes = sum(
            int(np.prod(leaf.shape)) * leaf.dtype.itemsize
            for leaf in jax.tree.leaves(state.params))
        comm = zero_mod.static_comm_bytes(state.opt_state.plan)
        # largest surviving psum: sync-BN pmeans its batch mean AND
        # var in ONE tupled eqn, so the cap is 2x the widest [C]
        # statistic leaf — everything else (loss/correct/count
        # scalars, the guard's int32) sits far under it, and a
        # grad-sized psum creeping back is ~3 orders over
        max_bn = 2 * max(
            (int(np.prod(leaf.shape)) * leaf.dtype.itemsize
             for leaf in jax.tree.leaves(state.batch_stats)),
            default=4)
        return {
            "fn": jit_fn,
            "args": (state, images, labels),
            "mesh": mesh,
            "lower_fn": jit_fn,
            "params_bytes": params_bytes,
            "expect_grad_psums": 0,
            "expect_collective_subset": {
                "reduce_scatter@data": {"count": 1,
                                      "bytes": comm["reduce_scatter"]},
                "all_gather@data": {"count": 1,
                                    "bytes": comm["all_gather"]},
            },
            "max_psum_bytes": max_bn,
            "min_donated": len(jax.tree.leaves(state.params)),
        }

    return [{"name": "train_step_dp_resnet18", "min_devices": 8,
             "build": build_dp},
            {"name": "train_step_dp_resnet18_zero", "min_devices": 8,
             "build": build_dp_zero}]


def shard_batch(batch, mesh: Mesh, axis_name: str = DATA_AXIS):
    """Place a host array as a device array sharded over the data axis.

    The H2D boundary (reference ``input.cuda(rank)``, ``main.py:101``) —
    one call distributing per-replica slices across all local chips.
    """
    return jax.tree.map(
        lambda x: jax.device_put(
            x, NamedSharding(mesh, P(axis_name, *([None] * (x.ndim - 1))))
        ),
        batch,
    )
