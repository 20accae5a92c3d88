"""Language-model training step: next-token loss, DP x SP sharding.

The image trainer's step (``train/step.py``) is classification-shaped
(``[B, C]`` logits, ``[B]`` labels); LM training needs the next-token
objective over ``[B, S, V]`` logits, and — under sequence parallelism —
a label shift that CROSSES shard boundaries: with contiguous sequence
sharding, the target for shard ``i``'s last position is the FIRST token
of shard ``i+1``. :func:`make_lm_train_step` handles both:

- DP only (1-D ``data`` mesh): standard shift, final position masked;
- DP x SP (``(data, seq)`` mesh): tokens arrive ``P(data, seq)``;
  each shard ``ppermute``s its first token column back to its left
  neighbor to complete the shift locally, and only the GLOBAL final
  position is masked. Attention is the causal ring; grads are
  ``pmean``-ed over both axes via the exact masked-sum/count ratio.

No reference counterpart (the reference trains ConvNets only); built to
the same conventions as ``train/step.py``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from flax.traverse_util import flatten_dict
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.losses import cross_entropy_per_sample
from ..parallel.mesh import DATA_AXIS
from .optim import Transform, apply_updates
from .state import TrainState


def _next_token_targets(tokens, seq_axis: Optional[str],
                        zigzag: bool = False):
    """(targets, valid) for the next-token objective.

    ``targets[:, j]`` is the token following position ``j`` (globally);
    ``valid`` masks the one global position with no successor.
    """
    b, s = tokens.shape
    if seq_axis is None:
        targets = jnp.concatenate(
            [tokens[:, 1:], jnp.zeros((b, 1), tokens.dtype)], axis=1
        )
        valid = jnp.concatenate(
            [jnp.ones((b, s - 1), bool), jnp.zeros((b, 1), bool)], axis=1
        )
        return targets, valid

    axis_size = jax.lax.psum(1, seq_axis)
    idx = jax.lax.axis_index(seq_axis)
    if zigzag:
        # shard i holds chunks (i, 2N-1-i): chunk-internal positions
        # shift locally; each chunk's LAST position needs the first
        # token of the globally-next chunk:
        # - chunk i's successor is chunk i+1 = shard i+1's first half
        #   (except i = N-1, whose successor chunk N is this shard's
        #   OWN second half);
        # - chunk 2N-1-i's successor is chunk 2N-i = shard i-1's second
        #   half (except i = 0, whose chunk 2N-1 ends the sequence).
        c = s // 2
        ta, tb = tokens[:, :c], tokens[:, c:]
        recv_a = jax.lax.ppermute(  # shard i <- shard i+1's ta[:, 0]
            ta[:, 0], seq_axis,
            [((i + 1) % axis_size, i) for i in range(axis_size)],
        )
        recv_b = jax.lax.ppermute(  # shard i <- shard i-1's tb[:, 0]
            tb[:, 0], seq_axis,
            [(i, (i + 1) % axis_size) for i in range(axis_size)],
        )
        next_a = jnp.where(idx == axis_size - 1, tb[:, 0], recv_a)
        targets = jnp.concatenate(
            [ta[:, 1:], next_a[:, None], tb[:, 1:], recv_b[:, None]],
            axis=1,
        )
        valid = jnp.ones((b, s), bool)
        # global last position = chunk 2N-1's last col = shard 0's tb end
        valid = valid.at[:, -1].set(idx != 0)
        return targets, valid
    # contiguous: right neighbor's first column completes the shift
    # (perm sends shard i+1's value to shard i)
    perm = [((i + 1) % axis_size, i) for i in range(axis_size)]
    next_first = jax.lax.ppermute(tokens[:, 0], seq_axis, perm)
    targets = jnp.concatenate(
        [tokens[:, 1:], next_first[:, None]], axis=1
    )
    # only the global last position (last shard's last column) is invalid
    valid = jnp.ones((b, s), bool)
    valid = valid.at[:, -1].set(idx != axis_size - 1)
    return targets, valid


def _collect_moe_losses(mut):
    """(aux, z) layer-means from a ``mutable=['losses']`` apply result.

    sow appends ``(scalar,)`` tuples keyed moe_aux/moe_z, one path per
    MoE layer; the mean over layers keeps the loss weights
    geometry-independent. Zeros when the model has no MoE blocks.
    """
    flat = flatten_dict(mut.get("losses", {}))
    aux_terms = [v for path, vals in flat.items()
                 if path[-1] == "moe_aux"
                 for v in jax.tree_util.tree_leaves(vals)]
    z_terms = [v for path, vals in flat.items()
               if path[-1] == "moe_z"
               for v in jax.tree_util.tree_leaves(vals)]
    aux = (sum(aux_terms) / len(aux_terms)
           if aux_terms else jnp.zeros((), jnp.float32))
    z = (sum(z_terms) / len(z_terms)
         if z_terms else jnp.zeros((), jnp.float32))
    return aux, z


def _checked_token_entry(sharded, mesh, axis_name, seq_axis, zigzag,
                         grad_accum: int = 1):
    """Shared train/eval entry wrapper: trace-time shape validation (a
    mismatched global batch must raise a framework-style error, not an
    opaque shard_map sharding failure — mirrors the image path's and
    TokenLoader's checks) plus the transparent zigzag token permutation
    (callers keep passing natural-order global tokens; the loss is a
    masked mean — permutation-invariant)."""
    dp = int(mesh.shape[axis_name])
    sp = int(mesh.shape[seq_axis]) if seq_axis is not None else 1

    def checked(state, tokens):
        b, s = tokens.shape
        if b % (dp * grad_accum):
            need = (f"data-axis size x grad_accum = {dp} x {grad_accum}"
                    if grad_accum > 1 else f"data-axis size {dp}")
            raise ValueError(
                f"global batch {b} must divide by {need} "
                f"(mesh axis {axis_name!r})"
            )
        if seq_axis is not None and s % sp:
            raise ValueError(
                f"seq_len {s} is not divisible by the sequence-axis "
                f"size {sp} (mesh axis {seq_axis!r})"
            )
        if zigzag:
            from ..parallel.ring_attention import zigzag_indices

            perm = zigzag_indices(s, sp).reshape(-1)
            tokens = tokens[:, perm]
        return sharded(state, tokens)

    return checked


def make_lm_train_step(
    model,
    optimizer: Transform,
    mesh: Mesh,
    *,
    axis_name: str = DATA_AXIS,
    seq_axis: Optional[str] = None,
    remat: bool = False,
    grad_accum: int = 1,
    moe_aux_weight: float = 0.01,
    moe_z_weight: float = 1e-3,
    vocab_chunks: int = 0,
    zero: bool = False,
    zero_overlap: bool = True,
):
    """Build the jitted LM train step.

    Args:
      model: a :class:`..models.gpt.GPT`-like module (``[B, S] ->
        [B, S, V]``), built with the SAME ``seq_axis``.
      mesh: 1-D ``(data,)`` mesh, or 2-D ``(data, seq)`` when
        ``seq_axis`` is set.
      grad_accum: microbatches per update over the batch dim (activation
        memory of one microbatch — the long-context memory knob beside
        ``remat``); exact same update as the single-shot step.
      vocab_chunks: > 1 streams the head matmul + CE over this many
        vocab slices (:func:`..ops.losses.chunked_lm_ce`): the
        ``[B, S, V]`` logits never materialize in either pass — the
        big-vocab memory knob. Exactly the dense objective (parity
        test-pinned); 0/1 = dense path.

    Returns ``step(state, tokens) -> (state, metrics)``; ``tokens`` is
    the global ``[B, S]`` int array, ``metrics = {loss, count}`` (loss =
    exact mean next-token CE over all predictable positions). MoE models
    (``n_experts > 0``) additionally train against the Switch
    load-balancing aux loss and the ST-MoE router z-loss the layer sows
    into its ``losses`` collection (``moe_aux_weight`` /
    ``moe_z_weight``; metrics gain ``moe_aux``).

    ``zero=True`` (graftzero): the per-leaf grad psums become one
    bucketed reduce-scatter, the update runs on local shards (moments
    sharded — the state must carry a
    :class:`..parallel.zero.ZeroOptState`; build it with
    ``zero.zeroify_state``), params all-gather back. DP only
    (``seq_axis`` must be None — the cross-shard label shift lives on
    the SP path).
    """
    if grad_accum < 1:
        raise ValueError(
            f"grad_accum must be >= 1, got {grad_accum} (1 = no "
            "accumulation; 0/negative would silently disable it)"
        )
    if zero and seq_axis is not None:
        raise ValueError(
            "zero=True shards the update over the data axis only; "
            "combine it with DP (seq_axis=None), not sequence "
            "parallelism")
    axes = (axis_name,) if seq_axis is None else (axis_name, seq_axis)
    is_moe = getattr(model, "n_experts", 0) > 0
    # zigzag SP: the model was built with sp_mode="zigzag", so tokens
    # must arrive in the zigzag_indices layout (handled transparently
    # below — callers keep passing natural-order global tokens) and the
    # label shift crosses chunk boundaries instead of shard boundaries
    zigzag = (seq_axis is not None
              and getattr(model, "sp_mode", "ring") == "zigzag")

    def make_body(zero_plan=None):
        def body(state: TrainState, tokens):
            return _body(state, tokens, zero_plan)
        return body

    def _body(state: TrainState, tokens, zero_plan):
        targets, valid = _next_token_targets(tokens, seq_axis, zigzag)
        w = valid.astype(jnp.float32)
        # Constants wrt params, computed before differentiation: global
        # predictable-position count and shard count (for layer-mean
        # normalization of the per-shard aux losses).
        count = jax.lax.psum(jnp.sum(w), axes)
        world = jax.lax.psum(1, axes)

        # Differentiate a LOCAL objective — deliberately no collective
        # inside the differentiated function (transposing through psum
        # under shard_map is a notorious factor-of-N trap; ring
        # attention's own custom VJP handles its internal comms). The
        # local objective is pre-normalized (CE by the global count, aux
        # by shard count x microbatch count) so ONE psum of the summed
        # local grads outside is exactly the global-mean gradient.
        def local_obj(params, tok, tgt, ww):
            if vocab_chunks > 1:
                from ..ops.losses import chunked_lm_ce

                hidden, mut = model.apply(
                    {"params": params}, tok, train=True,
                    return_hidden=True, mutable=["losses"]
                )
                ce_sum = chunked_lm_ce(
                    hidden, params["head"]["kernel"],
                    params["head"].get("bias"), tgt, ww, vocab_chunks,
                )
            else:
                logits, mut = model.apply(
                    {"params": params}, tok, train=True,
                    mutable=["losses"]
                )
                flat_ce = cross_entropy_per_sample(
                    logits.reshape(-1, logits.shape[-1]), tgt.reshape(-1)
                ).reshape(tgt.shape)
                ce_sum = jnp.sum(flat_ce * ww)
            aux, z = _collect_moe_losses(mut)
            obj = ce_sum / count + (
                moe_aux_weight * aux + moe_z_weight * z
            ) / (world * grad_accum)
            return obj, (ce_sum, aux)

        if remat:
            local_obj = jax.checkpoint(local_obj)

        if grad_accum == 1:
            (_, (loss_sum, aux)), grads = jax.value_and_grad(
                local_obj, has_aux=True
            )(state.params, tokens, targets, w)
        else:
            b = tokens.shape[0]
            if b % grad_accum:
                raise ValueError(
                    f"per-device batch {b} is not divisible by "
                    f"grad_accum={grad_accum}"
                )

            from .step import strided_microbatches

            def to_micro(x):
                return strided_microbatches(x, grad_accum)

            def micro(carry, mb):
                gsum, lsum, asum = carry
                (_, (ce, aux_mb)), g = jax.value_and_grad(
                    local_obj, has_aux=True
                )(state.params, *mb)
                return (jax.tree.map(jnp.add, gsum, g),
                        lsum + ce, asum + aux_mb), None

            carry0 = (
                jax.tree.map(jnp.zeros_like, state.params),
                jnp.zeros((), jnp.float32),
                jnp.zeros((), jnp.float32),
            )
            (grads, loss_sum, aux_sum), _ = jax.lax.scan(
                micro, carry0,
                (to_micro(tokens), to_micro(targets), to_micro(w)),
            )
            aux = aux_sum / grad_accum
        loss = jax.lax.psum(loss_sum, axes) / count
        from .step import finite_grads, guard_nonfinite

        if zero_plan is not None:
            # graftzero: the per-leaf grad psums become ONE bucketed
            # reduce-scatter (sum semantics — the local objective is
            # already globally pre-normalized), the update runs on
            # local shards, params all-gather back; the guard counts
            # non-finites on the scattered shards with one summed
            # scalar psum
            from ..parallel import zero as zero_mod

            g_shards = zero_mod.reduce_scatter_grads(
                grads, zero_plan, axis_name, mean=False,
                overlap=zero_overlap)
            finite = zero_mod.finite_shards(g_shards, axis_name)
            new_params, new_opt = zero_mod.apply_sharded_update(
                optimizer, state.opt_state, g_shards, state.params,
                axis_name, lr_step=state.epoch, overlap=zero_overlap)
        else:
            grads = jax.tree.map(lambda g: jax.lax.psum(g, axes), grads)

            # NaN/inf skip-and-count guard off the globally-summed
            # grads (replicated — every shard agrees): see
            # step.guard_nonfinite
            finite = finite_grads(grads)
            updates, new_opt = optimizer.update(
                grads, state.opt_state, state.params, lr_step=state.epoch
            )
            new_params = apply_updates(state.params, updates)
        new_state = state.replace(params=new_params, opt_state=new_opt)
        metrics = {"loss": loss, "count": count}
        if is_moe:
            metrics["moe_aux"] = jax.lax.psum(aux, axes) / world
        new_state, metrics = guard_nonfinite(finite, new_state, state,
                                             metrics)
        return new_state, metrics

    if zero:
        from .step import _lazy_zero_step

        return _lazy_zero_step(
            make_body, mesh, axis_name, n_batch_args=1,
            entry=lambda sharded: _checked_token_entry(
                sharded, mesh, axis_name, None, False, grad_accum))

    if seq_axis is None:
        in_specs = (P(), P(axis_name))
    else:
        in_specs = (P(), P(axis_name, seq_axis))
    sharded = shard_map(
        make_body(),
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(
        _checked_token_entry(sharded, mesh, axis_name, seq_axis, zigzag,
                             grad_accum),
        donate_argnums=(0,),
    )


def make_lm_train_step_tp(
    model,
    optimizer: Transform,
    mesh: Mesh,
    *,
    zero1: bool = False,
    fsdp: bool = False,
    remat: bool = False,
    moe_aux_weight: float = 0.01,
    moe_z_weight: float = 1e-3,
):
    """Build the jitted DP x TP LM train step (GSPMD path).

    The LM twin of :func:`..train.step.make_train_step_tp`: the body is
    written with GLOBAL semantics and the shardings carry the
    parallelism — the generic trailing-dim rule
    (:func:`..train.step.tp_param_spec`) puts every Dense output-feature
    dim (wqkv/fc1 columns, wo/fc2 via their own trailing dims, the
    vocab head) and the embedding hidden dim on the ``model`` axis,
    tokens live on ``data``, and GSPMD inserts the Megatron-style
    collectives. ``zero1``/``fsdp`` compose exactly as on the image
    path. ``state`` must be placed with
    :func:`..train.step.shard_state` first.

    Requires a model built WITHOUT ``seq_axis`` (TP x SP composition
    runs through the shard_map path, not GSPMD).
    """
    if getattr(model, "seq_axis", None) is not None:
        raise ValueError(
            "make_lm_train_step_tp requires a model built with "
            "seq_axis=None: under GSPMD the sequence stays unsharded "
            "(use make_lm_train_step(seq_axis=...) for SP)"
        )
    is_moe = getattr(model, "n_experts", 0) > 0

    def body(state: TrainState, tokens):
        targets, valid = _next_token_targets(tokens, None)
        w = valid.astype(jnp.float32)
        count = jnp.sum(w)

        def obj(params):
            logits, mut = model.apply(
                {"params": params}, tokens, train=True, mutable=["losses"]
            )
            flat_ce = cross_entropy_per_sample(
                logits.reshape(-1, logits.shape[-1]), targets.reshape(-1)
            ).reshape(targets.shape)
            ce_mean = jnp.sum(flat_ce * w) / count
            aux, z = _collect_moe_losses(mut)
            total = ce_mean + moe_aux_weight * aux + moe_z_weight * z
            return total, (ce_mean, aux)

        if remat:
            obj = jax.checkpoint(obj)
        (_, (loss, aux)), grads = jax.value_and_grad(
            obj, has_aux=True
        )(state.params)
        from .step import finite_grads, guard_nonfinite

        finite = finite_grads(grads)
        updates, new_opt = optimizer.update(
            grads, state.opt_state, state.params, lr_step=state.epoch
        )
        new_state = state.replace(
            params=apply_updates(state.params, updates), opt_state=new_opt
        )
        metrics = {"loss": loss, "count": count}
        if is_moe:
            metrics["moe_aux"] = aux
        new_state, metrics = guard_nonfinite(finite, new_state, state,
                                             metrics)
        return new_state, metrics

    from .step import lazy_gspmd_jit

    return lazy_gspmd_jit(
        body, mesh, arg_specs=(P(DATA_AXIS),), returns_state=True,
        zero1=zero1, fsdp=fsdp,
    )


def make_lm_eval_step(
    model,
    mesh: Mesh,
    *,
    axis_name: str = DATA_AXIS,
    seq_axis: Optional[str] = None,
    vocab_chunks: int = 0,
):
    """Forward-only next-token CE over held-out tokens (DP x SP paths).

    The LM twin of the image :func:`..train.step.make_eval_step`: same
    mesh/axis conventions as :func:`make_lm_train_step` (including the
    zigzag token permutation and the cross-shard label shift), eval-mode
    apply (MoE aux sows are discarded — flax drops non-mutable
    collections), exact masked-mean accounting via a psum-ed global
    count. Returns ``eval_step(state, tokens) -> {loss, count}``.

    ``vocab_chunks`` streams the head+CE exactly like the train step —
    a run that only fits BECAUSE of chunking must not OOM at its first
    validation pass.
    """
    axes = (axis_name,) if seq_axis is None else (axis_name, seq_axis)
    zigzag = (seq_axis is not None
              and getattr(model, "sp_mode", "ring") == "zigzag")

    def body(state: TrainState, tokens):
        targets, valid = _next_token_targets(tokens, seq_axis, zigzag)
        w = valid.astype(jnp.float32)
        count = jax.lax.psum(jnp.sum(w), axes)
        if vocab_chunks > 1:
            from ..ops.losses import chunked_lm_ce

            hidden = model.apply({"params": state.params}, tokens,
                                 train=False, return_hidden=True)
            ce_sum = chunked_lm_ce(
                hidden, state.params["head"]["kernel"],
                state.params["head"].get("bias"), targets, w,
                vocab_chunks,
            )
        else:
            logits = model.apply({"params": state.params}, tokens,
                                 train=False)
            flat_ce = cross_entropy_per_sample(
                logits.reshape(-1, logits.shape[-1]), targets.reshape(-1)
            ).reshape(targets.shape)
            ce_sum = jnp.sum(flat_ce * w)
        loss = jax.lax.psum(ce_sum, axes) / count
        return {"loss": loss, "count": count}

    if seq_axis is None:
        in_specs = (P(), P(axis_name))
    else:
        in_specs = (P(), P(axis_name, seq_axis))
    sharded = shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=P(),
        check_vma=False,
    )
    return jax.jit(
        _checked_token_entry(sharded, mesh, axis_name, seq_axis, zigzag)
    )


def make_lm_eval_step_tp(model, mesh: Mesh, *, zero1: bool = False,
                         fsdp: bool = False):
    """Eval twin of :func:`make_lm_train_step_tp` (GSPMD path).

    ``zero1``/``fsdp`` must match the train step's so in_shardings
    agree with where the state actually lives.
    """
    if getattr(model, "seq_axis", None) is not None:
        raise ValueError(
            "make_lm_eval_step_tp requires a model built with "
            "seq_axis=None (use make_lm_eval_step(seq_axis=...) for SP)"
        )

    def body(state: TrainState, tokens):
        targets, valid = _next_token_targets(tokens, None)
        w = valid.astype(jnp.float32)
        count = jnp.sum(w)
        logits = model.apply({"params": state.params}, tokens,
                             train=False)
        flat_ce = cross_entropy_per_sample(
            logits.reshape(-1, logits.shape[-1]), targets.reshape(-1)
        ).reshape(targets.shape)
        return {"loss": jnp.sum(flat_ce * w) / count, "count": count}

    from .step import lazy_gspmd_jit

    return lazy_gspmd_jit(
        body, mesh, arg_specs=(P(DATA_AXIS),), returns_state=False,
        zero1=zero1, fsdp=fsdp,
    )


def create_lm_train_state(model, rng, sample_tokens,
                          optimizer: Transform) -> TrainState:
    """LM twin of :func:`..train.create_train_state` (no batch stats).

    Accepts a sequence-parallel model directly: ``seq_axis`` changes no
    parameter shapes but DOES make the forward call collectives
    (``axis_index``/``psum``) that have no bound axis at init time, so
    initialization runs on an axis-free clone. ``sample_tokens`` is the
    GLOBAL ``[B, S]`` batch either way.
    """
    if getattr(model, "seq_axis", None) is not None:
        model = model.clone(seq_axis=None)
    variables = model.init(rng, sample_tokens, train=False)
    params = variables["params"]
    return TrainState(
        params=params,
        batch_stats={},
        opt_state=optimizer.init(params),
        epoch=jnp.ones((), jnp.int32),
    )


# ----------------------------------------------------------- graftcheck

def _audit_gpt(**kw):
    """The shared tiny audit GPT (ONE geometry across the LM-family
    hooks — see :func:`...analysis.programs.audit_tiny_gpt`)."""
    from ..analysis.programs import audit_tiny_gpt

    return audit_tiny_gpt(**kw)


def _audit_lm_pieces(model, mesh_data=1, mesh_model=1):
    """(mesh, abstract state, abstract tokens, optimizer) for one LM
    audit program."""
    from ..parallel.mesh import audit_mesh
    from .optim import sgd

    mesh = audit_mesh(data=mesh_data, model=mesh_model)
    opt = sgd(learning_rate=0.1)
    state = jax.eval_shape(
        lambda: create_lm_train_state(
            model, jax.random.PRNGKey(0),
            jnp.zeros((2, 16), jnp.int32), opt))
    tokens = jax.ShapeDtypeStruct((8, 16), jnp.int32)
    return mesh, state, tokens, opt


def audit_programs():
    """graftcheck registration hook: the LM train steps across the
    parallelism modes whose communication the compiler owns.

    - ``lm_step_dp``: shard_map DP — grads psum per leaf (the LM body
      deliberately reduces OUTSIDE the differentiated function); the
      committed budget pins total psum volume = params + metrics.
    - ``lm_step_tp`` / ``lm_step_fsdp``: GSPMD — the jaxpr shows only
      sharding constraints, so these compile (CPU, partitioned) and
      pin the HLO collective set: TP must all-reduce, FSDP must
      all-gather params and reduce-scatter grads (``require_hlo``) —
      the ZeRO-3 schedule as a checkable artifact, per
      arXiv:2004.13336's framing of the weight-update sharding.
    - ``lm_step_moe``: the MoE objective through the DP step (aux/z
      losses included) — fingerprint + budget over the routed FFN.
    """
    def build_dp():
        model = _audit_gpt()
        mesh, state, tokens, opt = _audit_lm_pieces(model, mesh_data=8)
        step = make_lm_train_step(model, opt, mesh)
        return {
            "fn": step, "args": (state, tokens), "mesh": mesh,
            "lower_fn": step,
            "min_donated": len(jax.tree.leaves(state.params)),
        }

    def build_tp(fsdp=False):
        model = _audit_gpt()
        mesh, state, tokens, opt = _audit_lm_pieces(
            model, mesh_data=2, mesh_model=2)
        step = make_lm_train_step_tp(model, opt, mesh, fsdp=fsdp)
        jit_fn = step.jit_program(state)
        spec = {
            "fn": jit_fn, "args": (state, tokens), "mesh": mesh,
            "lower_fn": jit_fn, "compile": True,
            "min_donated": len(jax.tree.leaves(state.params)),
            # FSDP's defining exchange is all-gather(params) +
            # reduce-scatter(grads); XLA:CPU's partitioner lowers the
            # reduce-scatter half as all-reduce(+slice), so the
            # portable requirement is gather + reduce — the committed
            # HLO budget pins the exact op set this jax emits
            "require_hlo": (("all-gather", "all-reduce") if fsdp
                            else ("all-reduce",)),
        }
        return spec

    def build_moe():
        model = _audit_gpt(n_experts=4, moe_capacity_factor=4.0)
        mesh, state, tokens, opt = _audit_lm_pieces(model, mesh_data=8)
        step = make_lm_train_step(model, opt, mesh)
        return {
            "fn": step, "args": (state, tokens), "mesh": mesh,
            "lower_fn": step,
            "min_donated": len(jax.tree.leaves(state.params)),
        }

    def build_dp_zero():
        """graftzero twin of ``lm_step_dp``: the ~30 per-leaf grad
        psums collapse into ONE bucketed reduce-scatter + ONE
        all-gather on the data axis (byte volumes pinned inline and
        committed); the only psums left are the loss/count scalars and
        the NaN-guard's summed non-finite int32 — ``max_psum_bytes=4``
        pins them separately (any grad-sized psum creeping back fails
        live, no refresh can launder it)."""
        import numpy as np

        from ..parallel import zero as zero_mod

        model = _audit_gpt()
        mesh, state, tokens, opt = _audit_lm_pieces(model, mesh_data=8)
        state = zero_mod.zeroify_state(state, mesh)
        step = make_lm_train_step(model, opt, mesh, zero=True)
        jit_fn = step.jit_program(state)
        comm = zero_mod.static_comm_bytes(state.opt_state.plan)
        params_bytes = sum(
            int(np.prod(leaf.shape)) * leaf.dtype.itemsize
            for leaf in jax.tree.leaves(state.params))
        return {
            "fn": jit_fn, "args": (state, tokens), "mesh": mesh,
            "lower_fn": jit_fn,
            "params_bytes": params_bytes,
            "expect_grad_psums": 0,
            "expect_collective_subset": {
                "reduce_scatter@data": {"count": 1,
                                      "bytes": comm["reduce_scatter"]},
                "all_gather@data": {"count": 1,
                                    "bytes": comm["all_gather"]},
            },
            "max_psum_bytes": 4,
            "min_donated": len(jax.tree.leaves(state.params)),
        }

    return [
        {"name": "lm_step_dp", "min_devices": 8, "build": build_dp},
        {"name": "lm_step_tp", "min_devices": 4, "build": build_tp},
        {"name": "lm_step_fsdp", "min_devices": 4,
         "build": lambda: build_tp(fsdp=True)},
        {"name": "lm_step_moe", "min_devices": 8, "build": build_moe},
        {"name": "lm_step_dp_zero", "min_devices": 8,
         "build": build_dp_zero},
    ]
