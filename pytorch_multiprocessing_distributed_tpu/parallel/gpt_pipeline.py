"""Pipelined GPT training: heterogeneous stages over a ``pipe`` mesh axis.

:mod:`.pipeline` provides the homogeneous GPipe primitive; a real LM is
NOT homogeneous — it is embed -> N blocks -> head, and the embedding /
head tables are among the largest tensors in the model. The torch way
to pipeline this is per-stage ``nn.Module``\\ s with different code on
different ranks. The TPU-native way, used here, keeps ONE SPMD program
and makes every stage-heterogeneous tensor *sharded* over the pipe axis
instead:

- **embedding**: the vocab dimension is sharded over ``pipe``
  (Megatron-style vocab-parallel lookup: each shard gathers the rows it
  owns, one ``psum`` materializes the activation);
- **blocks**: stacked ``[n_stages, layers_per_stage, ...]`` and sharded
  over ``pipe`` — stage *s* holds only its own layers; microbatches flow
  through :func:`.pipeline.pipeline_apply` (``ppermute`` ring, GPipe
  schedule, differentiable scan);
- **head**: output-vocab sharded over ``pipe``; under the default
  ``schedule="gpipe"`` the next-token loss is computed vocab-parallel
  (local partial logits, ``pmax``/``psum`` log-sum-exp) so the full
  ``[B, S, V]`` logits tensor never materializes anywhere. The
  ``"1f1b"`` schedule instead weight-GATHERS the head for the step and
  evaluates a dense per-microbatch CE where the last stage's output
  lands (``[mb, s, V]`` only — params stay vocab-sharded at rest; the
  trade buys O(n_stages) activation residency, see ``body_1f1b``).

Every parameter therefore has exactly one resident shard per pipe
stage (embed/head rows live where their slice lives), composing with
data parallelism over the ``data`` axis — all in one jitted
``shard_map`` with ``check_vma=True`` (required for correct collective
AD transposes, see :mod:`.pipeline`).

No reference counterpart (the reference is single-stage DDP,
SURVEY.md §2.3); geometry validation mirrors :func:`.mesh.make_mesh`.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .mesh import DATA_AXIS

# NB: ..train imports stay function-local — parallel/__init__ re-exports
# this module and ..train imports ..parallel, so a top-level import here
# would cycle.

PIPE_AXIS = "pipe"
_LN_EPS = 1e-6  # flax nn.LayerNorm default, as used by the GPT family


def _num_layers(params) -> int:
    n = 0
    while f"block_{n}" in params:
        n += 1
    return n


def stack_pipeline_params(params, n_stages: int):
    """GPT ``init`` params -> the pipe-shardable tree.

    Returns a dict whose pipe-sharded leaves carry a leading
    ``n_stages`` dim: ``embed`` ``[S, ceil(V/S), D]`` (vocab
    row-sharded, zero-padded), ``blocks`` ``[S, L/S, ...]``, ``head_k``
    ``[S, D, ceil(V/S)]`` / ``head_b`` ``[S, ceil(V/S)]`` (vocab
    col-sharded, zero-padded). ``head_b`` is present only when the GPT
    has a head bias — a ``head_bias=False`` model (the HF-GPT-2 interop
    configuration) simply has no such leaf. Padded vocab slots are NOT
    masked here: the forward passes mask them explicitly from the true
    ``vocab_size`` (``slot_id >= vocab_size -> -1e9``), so masking
    never depends on a bias slot existing. ``pos`` and ``ln_f`` are
    small and replicated.
    """
    num_layers = _num_layers(params)
    if num_layers == 0:
        raise ValueError("params has no block_<i> entries — not a GPT tree")
    if num_layers % n_stages:
        raise ValueError(
            f"{num_layers} layers not divisible by n_stages={n_stages}"
        )
    per = num_layers // n_stages
    blocks = jax.tree.map(
        lambda *ls: jnp.stack(ls),
        *[params[f"block_{i}"] for i in range(num_layers)],
    )
    blocks = jax.tree.map(
        lambda l: l.reshape(n_stages, per, *l.shape[1:]), blocks
    )

    embed = params["embed"]  # [V, D]
    vocab, d = embed.shape
    vs = -(-vocab // n_stages)  # ceil
    pad = n_stages * vs - vocab
    embed = jnp.pad(embed, ((0, pad), (0, 0))).reshape(n_stages, vs, d)
    head_k = params["head"]["kernel"]  # [D, V]
    head_k = jnp.pad(head_k, ((0, 0), (0, pad)))
    head_k = head_k.reshape(d, n_stages, vs).transpose(1, 0, 2)

    out = {
        "embed": embed,
        # copy pass-through leaves: sharing buffers with the source tree
        # would let a donating step on the SOURCE state delete them
        "pos": jnp.array(params["pos_embed"], copy=True),
        "blocks": blocks,
        "ln_f": jax.tree.map(lambda l: jnp.array(l, copy=True),
                             params["ln_final"]),
        "head_k": head_k,
    }
    if "bias" in params["head"]:
        out["head_b"] = jnp.pad(
            params["head"]["bias"], (0, pad)).reshape(n_stages, vs)
    return out


def unstack_pipeline_params(pipe_params, vocab_size: int):
    """Inverse of :func:`stack_pipeline_params` (checkpoint interop)."""
    n_stages, vs, d = pipe_params["embed"].shape
    blocks = pipe_params["blocks"]
    any_leaf = jax.tree_util.tree_leaves(blocks)[0]
    per = any_leaf.shape[1]
    head = {
        "kernel": pipe_params["head_k"].transpose(1, 0, 2).reshape(
            d, n_stages * vs)[:, :vocab_size],
    }
    if "head_b" in pipe_params:
        head["bias"] = pipe_params["head_b"].reshape(
            n_stages * vs)[:vocab_size]
    out = {
        "embed": pipe_params["embed"].reshape(n_stages * vs, d)[:vocab_size],
        "pos_embed": pipe_params["pos"],
        "ln_final": pipe_params["ln_f"],
        "head": head,
    }
    for s in range(n_stages):
        for j in range(per):
            out[f"block_{s * per + j}"] = jax.tree.map(
                lambda l: l[s, j], blocks
            )
    return out


def pipeline_specs(pipe_params, pipe_axis: str = PIPE_AXIS):
    """PartitionSpec tree matching :func:`stack_pipeline_params` output."""
    specs = {
        "embed": P(pipe_axis),
        "pos": P(),
        "blocks": jax.tree.map(lambda _: P(pipe_axis),
                               pipe_params["blocks"]),
        "ln_f": jax.tree.map(lambda _: P(), pipe_params["ln_f"]),
        "head_k": P(pipe_axis),
    }
    if "head_b" in pipe_params:
        specs["head_b"] = P(pipe_axis)
    return specs


def create_pipelined_lm_state(model, rng, sample_tokens,
                              optimizer: "Transform",
                              n_stages: int,
                              params=None) -> "TrainState":
    """Init the GPT normally, restack for the pipe axis, init optimizer
    buffers on the stacked tree (so they shard identically). Pass
    ``params`` (a dense GPT param tree, e.g. imported HF-GPT-2 weights
    from :func:`..utils.gpt_interop.from_gpt2_state_dict`) to stack
    those instead of a fresh init."""
    from ..train.state import TrainState

    if getattr(model, "seq_axis", None) is not None:
        model = model.clone(seq_axis=None)
    if params is None:
        params = model.init(rng, sample_tokens, train=False)["params"]
    params = stack_pipeline_params(
        jax.tree.map(jnp.asarray, params), n_stages)
    return TrainState(
        params=params,
        batch_stats={},
        opt_state=optimizer.init(params),
        epoch=jnp.ones((), jnp.int32),
    )


def _shared_parts(model, pipe_axis):
    """Closures shared by every pipelined body (gpipe train, 1f1b
    train, eval) — ONE copy so the execution paths cannot drift
    numerically."""
    from ..models.gpt import Block
    from ..train.lm import _collect_moe_losses
    from .pipeline import _zeros_vma

    # attn_impl="xla": the Pallas flash kernel cannot declare vma for
    # the check_vma=True shard_map these steps REQUIRE (collective AD
    # correctness, see .pipeline); plain masked attention is the same
    # exact math.
    ln_eps = getattr(model, "ln_eps", _LN_EPS)
    is_moe = getattr(model, "n_experts", 0) > 0
    block = Block(model.num_heads, model.mlp_dim, model.dtype,
                  attn_impl="xla", ln_eps=ln_eps,
                  n_experts=getattr(model, "n_experts", 0),
                  moe_top_k=getattr(model, "moe_top_k", 1),
                  moe_capacity_factor=getattr(
                      model, "moe_capacity_factor", 1.0))

    if is_moe:
        def stage_fn(stage_params, x):
            # MoE contract: (y, [aux_sum, z_sum]) — this stage's LAYER
            # SUM of the sown balance/z losses (the bodies normalize to
            # the layer-mean the dense step uses)
            def layer(carry, lp):
                h, acc = carry
                y, mut = block.apply({"params": lp}, h,
                                     mutable=["losses"])
                a, zl = _collect_moe_losses(mut)
                return (y, acc + jnp.stack([a, zl])), None

            acc0 = _zeros_vma((2,), jnp.float32, x)
            (y, acc), _ = jax.lax.scan(layer, (x, acc0), stage_params)
            return y, acc
    else:
        def stage_fn(stage_params, x):
            # stage_params leaves [L/S, ...]: scan this stage's layers
            def layer(carry, lp):
                return block.apply({"params": lp}, carry), None

            y, _ = jax.lax.scan(layer, x, stage_params)
            return y

    def vocab_parallel_embed(emb, pos, tokens, i):
        """Gather the locally-owned rows, psum to materialize [B, S, D]."""
        emb0 = emb[0]  # [Vs, D]
        vs = emb0.shape[0]
        start = i * vs
        idx = tokens - start
        mine = jnp.logical_and(idx >= 0, idx < vs)
        h = emb0[jnp.clip(idx, 0, vs - 1)] * mine[..., None]
        h = jax.lax.psum(h, pipe_axis)
        return (h + pos[: tokens.shape[1]]).astype(model.dtype)

    def final_ln(h, lnf):
        mu = jnp.mean(h, -1, keepdims=True)
        var = jnp.var(h, -1, keepdims=True)
        h = (h - mu) * jax.lax.rsqrt(var + ln_eps)
        return h * lnf["scale"] + lnf["bias"]

    return stage_fn, vocab_parallel_embed, final_ln


def _make_forward_ce(model, axis_name, pipe_axis, m,
                     moe_aux_weight=0.01, moe_z_weight=1e-3):
    """The GPipe forward objective shared by the gpipe train body and
    the eval step: vocab-parallel embed -> pipelined blocks -> final LN
    -> vocab-parallel log-sum-exp CE (the [B, S, V] logits never
    materialize). For MoE models the pipelined stages also accumulate
    the sown balance/z losses (valid ticks only) and the objective adds
    them layer-mean-normalized, mirroring the dense step. Returns
    ``forward_ce(p, tokens) -> (obj, (ce_sum, count, moe_aux))`` with
    ``obj`` normalized for differentiation."""
    from ..train.lm import _next_token_targets
    from .pipeline import pipeline_apply

    stage_fn, vocab_parallel_embed, final_ln = _shared_parts(
        model, pipe_axis
    )
    is_moe = getattr(model, "n_experts", 0) > 0
    n_layers = model.num_layers

    def forward_ce(p, tokens):
        targets, valid = _next_token_targets(tokens, None)
        w = valid.astype(jnp.float32)
        count = jax.lax.psum(jnp.sum(w), axis_name)
        b, s = tokens.shape
        if b % m:
            raise ValueError(
                f"per-replica batch {b} is not divisible by "
                f"n_microbatches={m}"
            )
        i = jax.lax.axis_index(pipe_axis)

        vs = p["embed"].shape[1]
        start = i * vs
        h = vocab_parallel_embed(p["embed"], p["pos"], tokens, i)

        micro = h.reshape(m, b // m, s, h.shape[-1])
        out = pipeline_apply(
            stage_fn, p["blocks"], micro, axis_name=pipe_axis,
            with_stage_aux=is_moe
        )
        if is_moe:
            out, aux_local = out
            # layer-mean x microbatch-mean, matching the dense step's
            # _collect_moe_losses normalization
            aux_vec = jax.lax.psum(aux_local, pipe_axis) / (
                n_layers * m)
        else:
            aux_vec = jnp.zeros((2,), jnp.float32)
        h = out.reshape(b, s, -1).astype(jnp.float32)
        h = final_ln(h, p["ln_f"])

        # ---- vocab-parallel head + log-sum-exp CE: each stage scores
        # its vocab slice; padded slots are masked to -1e9 (zero softmax
        # mass) from the TRUE vocab size — explicit, so it works with or
        # without a head bias (head_bias=False is the HF-GPT-2 interop
        # configuration). The matmul stays f32: the plain GPT head is
        # f32-pinned (models/gpt.py nn.Dense(dtype=f32)) and trajectory
        # parity must hold for bf16 models too.
        logits = h @ p["head_k"][0]
        if "head_b" in p:
            logits = logits + p["head_b"][0]
        slot_valid = start + jnp.arange(vs) < model.vocab_size
        logits = jnp.where(slot_valid, logits, -1e9)
        # stop_gradient BEFORE pmax: the max-shift is numerical
        # stabilization only (lse is shift-invariant) and pmax has
        # no AD rule — its input must already carry a zero tangent
        gmax = jax.lax.pmax(
            jax.lax.stop_gradient(jnp.max(logits, -1)), pipe_axis
        )
        lse = jnp.log(jax.lax.psum(
            jnp.sum(jnp.exp(logits - gmax[..., None]), -1), pipe_axis
        )) + gmax
        tidx = targets - start
        tmine = jnp.logical_and(tidx >= 0, tidx < vs)
        tlogit = jnp.take_along_axis(
            logits, jnp.clip(tidx, 0, vs - 1)[..., None], -1
        )[..., 0] * tmine
        tlogit = jax.lax.psum(tlogit, pipe_axis)
        ce_sum = jnp.sum((lse - tlogit) * w)
        # /dp_world: grads come back data-summed under check_vma AD,
        # so the local aux objective pre-divides (dense-step convention)
        dp_world = jax.lax.psum(1, axis_name)
        obj = ce_sum / count + (
            moe_aux_weight * aux_vec[0] + moe_z_weight * aux_vec[1]
        ) / dp_world
        return obj, (ce_sum, count, aux_vec[0])

    return forward_ce


def _state_specs(state, pipe_axis):
    """ONE source of truth for the pipelined state layout
    (pipeline_specs), mirrored onto the full TrainState pytree."""
    from ..train.optim import OptState
    from ..train.state import TrainState

    ps = pipeline_specs(state.params, pipe_axis)
    return TrainState(
        params=ps,
        batch_stats={},
        opt_state=OptState(momentum=ps, count=P(), initialized=P()),
        epoch=P(),
    )


def make_pipelined_lm_train_step(
    model,
    optimizer: "Transform",
    mesh: Mesh,
    *,
    axis_name: str = DATA_AXIS,
    pipe_axis: str = PIPE_AXIS,
    n_microbatches: Optional[int] = None,
    schedule: str = "gpipe",
    moe_aux_weight: float = 0.01,
    moe_z_weight: float = 1e-3,
):
    """Build the jitted DP x PP LM train step.

    Args:
      model: a ``GPT`` (provides block geometry and dtype) — dense or
        MoE (``n_experts > 0``: the pipelined stages accumulate the
        sown balance/z losses on valid ticks and both schedules train
        against them with the dense step's layer-mean normalization;
        the reported ``moe_aux`` is a per-microbatch estimator of the
        same statistic, like every sharded batch view).
      mesh: 2-D ``(data, pipe)`` mesh (either axis may be 1).
      n_microbatches: microbatches per step (default: the pipe axis
        size — the minimum that keeps every stage busy; more shrinks
        the bubble fraction further).
      schedule: ``"gpipe"`` (autodiff through the forward schedule —
        simplest, but the reversed scan stashes residuals for all M
        microbatches) or ``"1f1b"`` (:func:`.pipeline.pipeline_1f1b` —
        each microbatch's backward starts as soon as its forward leaves
        the last stage, O(n_stages) activation residency independent of
        M, rematerialized stage backward). Same math either way — the
        trajectory-parity test pins gpipe == 1f1b == plain DP.

    Returns ``step(state, tokens) -> (state, metrics)`` with ``state``
    from :func:`create_pipelined_lm_state`; ``tokens`` is the global
    ``[B, S]`` int array and ``metrics = {loss, count}`` matches
    :func:`..train.lm.make_lm_train_step` (exact mean next-token CE).
    """
    from ..train.lm import _next_token_targets
    from ..train.optim import apply_updates
    from ..train.state import TrainState
    from .pipeline import pipeline_1f1b

    if schedule not in ("gpipe", "1f1b"):
        raise ValueError(
            f"schedule must be 'gpipe' or '1f1b', got {schedule!r}"
        )
    n_stages = int(mesh.shape[pipe_axis])
    dp = int(mesh.shape[axis_name])
    m = n_microbatches or n_stages
    is_moe = getattr(model, "n_experts", 0) > 0
    n_layers = model.num_layers
    stage_fn, vocab_parallel_embed, final_ln = _shared_parts(
        model, pipe_axis
    )
    forward_ce = _make_forward_ce(model, axis_name, pipe_axis, m,
                                  moe_aux_weight, moe_z_weight)

    def _metrics(loss, count, moe_aux):
        out = {"loss": loss, "count": count}
        if is_moe:
            out["moe_aux"] = jax.lax.pmean(moe_aux, axis_name)
        return out

    def body(state: TrainState, tokens):
        (_, (ce_sum, count, moe_aux)), grads = jax.value_and_grad(
            forward_ce, has_aux=True
        )(state.params, tokens)
        # NO explicit grad psums here. Under check_vma=True the vma-aware
        # AD transposes already reduce each cotangent over every mesh
        # axis its parameter is INVARIANT along: pipe-sharded leaves come
        # back data-summed, replicated leaves (pos, ln_f) come back
        # summed over BOTH axes. An explicit psum on top multiplies the
        # gradient by the axis size (verified empirically: 2x/8x updates
        # on a (2, 4) mesh). This is the opposite convention from the
        # check_vma=False steps elsewhere in train/, which must psum
        # their local grads themselves.

        updates, new_opt = optimizer.update(
            grads, state.opt_state, state.params, lr_step=state.epoch
        )
        new_state = state.replace(
            params=apply_updates(state.params, updates), opt_state=new_opt
        )
        loss = jax.lax.psum(ce_sum, axis_name) / count
        return new_state, _metrics(loss, count, moe_aux)

    def body_1f1b(state: TrainState, tokens):
        """Manual-VJP twin of ``body`` built on :func:`pipeline_1f1b`.

        Differences from the GPipe body, both standard 1F1B structure:
        the per-microbatch loss must be computable where the last
        stage's output lands, so (a) the head is weight-GATHERED over
        ``pipe`` for the step (Megatron-style: gather the [D, V/S]
        slices, grads return through the all_gather transpose as a
        psum_scatter — params stay vocab-sharded at rest), and (b) the
        final-LN + CE run per-microbatch inside the schedule rather
        than once over the full batch.
        """
        targets, valid = _next_token_targets(tokens, None)
        w = valid.astype(jnp.float32)
        count = jax.lax.psum(jnp.sum(w), axis_name)
        b, s = tokens.shape
        if b % m:
            raise ValueError(
                f"per-replica batch {b} is not divisible by "
                f"n_microbatches={m}"
            )
        i = jax.lax.axis_index(pipe_axis)
        p = state.params
        mb = b // m

        # ---- vocab-parallel embedding, differentiated via vjp so the
        # schedule's input cotangent flows back to the embed rows
        def embed_fn(emb, pos):
            h = vocab_parallel_embed(emb, pos, tokens, i)
            return h.reshape(m, mb, s, h.shape[-1])

        micro, embed_vjp = jax.vjp(embed_fn, p["embed"], p["pos"])

        # ---- gather the vocab-sharded head for the last-stage loss.
        # Padded vocab slots are masked inside mb_loss from the true
        # vocab size — no bias slot needed to carry the mask, so a
        # biasless (head_bias=False, HF-interop) head gathers only its
        # kernel.
        has_bias = "head_b" in p
        head_leaves = (
            (p["head_k"], p["head_b"]) if has_bias else (p["head_k"],)
        )

        def gather_fn(*hs):
            full_k = jax.lax.all_gather(
                hs[0][0], pipe_axis, axis=1, tiled=True
            )  # [D, S*Vs]
            full_b = (jax.lax.all_gather(
                hs[1][0], pipe_axis, axis=0, tiled=True
            ) if has_bias else None)  # [S*Vs]
            return full_k, full_b

        (full_k, full_b), gather_vjp = jax.vjp(gather_fn, *head_leaves)
        loss_params = (full_k, full_b, p["ln_f"])
        aux = (
            targets.reshape(m, mb, s),
            w.reshape(m, mb, s),
        )

        def mb_loss(lp, y, aux_j):
            fk, fb, lnf = lp
            tj, wj = aux_j
            h = final_ln(y.astype(jnp.float32), lnf)
            logits = h @ fk  # [mb, s, Vpad] f32
            if fb is not None:
                logits = logits + fb
            logits = jnp.where(
                jnp.arange(fk.shape[1]) < model.vocab_size, logits, -1e9
            )
            gmax = jax.lax.stop_gradient(jnp.max(logits, -1))
            lse = jnp.log(jnp.sum(
                jnp.exp(logits - gmax[..., None]), -1
            )) + gmax
            tlogit = jnp.take_along_axis(
                logits, tj[..., None], -1
            )[..., 0]
            return jnp.sum((lse - tlogit) * wj) / count

        dp_world = jax.lax.psum(1, axis_name)
        if is_moe:
            # objective adds (w_aux*A + w_z*Z) / (L*M*dp): the constant
            # aux cotangent the schedule seeds on every backward tick
            aux_ct = jnp.asarray(
                [moe_aux_weight, moe_z_weight], jnp.float32
            ) / (n_layers * m * dp_world)
            (loss_local, d_blocks, d_lp, d_micro,
             aux_local) = pipeline_1f1b(
                stage_fn, p["blocks"], micro, mb_loss, loss_params,
                aux, axis_name=pipe_axis, with_stage_aux=True,
                stage_aux_cotangent=aux_ct,
            )
            moe_aux = jax.lax.psum(aux_local, pipe_axis)[0] / (
                n_layers * m)
        else:
            loss_local, d_blocks, d_lp, d_micro = pipeline_1f1b(
                stage_fn, p["blocks"], micro, mb_loss, loss_params,
                aux, axis_name=pipe_axis,
            )
            moe_aux = jnp.zeros((), jnp.float32)
        d_fk, d_fb, d_lnf = d_lp
        # gather_vjp's psum_scatter SUMS the per-shard partials itself —
        # feed them unreduced (a pre-psum would overcount by n_stages)
        d_head = gather_vjp((d_fk, d_fb))
        d_emb, d_pos = embed_vjp(d_micro)
        grads = {
            "embed": d_emb,
            "pos": d_pos,
            "blocks": d_blocks,
            # ln_f is replicated over pipe; its partials need the psum
            "ln_f": jax.tree.map(
                lambda g: jax.lax.psum(g, pipe_axis), d_lnf
            ),
            "head_k": d_head[0],
        }
        if has_bias:
            grads["head_b"] = d_head[1]
        updates, new_opt = optimizer.update(
            grads, state.opt_state, state.params, lr_step=state.epoch
        )
        new_state = state.replace(
            params=apply_updates(state.params, updates), opt_state=new_opt
        )
        loss = jax.lax.psum(loss_local, axis_name)
        return new_state, _metrics(loss, count, moe_aux)

    def step(state, tokens):
        if state.params["embed"].shape[0] != n_stages:
            raise ValueError(
                f"state was stacked for "
                f"{state.params['embed'].shape[0]} stages but the mesh "
                f"{pipe_axis!r} axis has {n_stages} — create the state "
                f"with n_stages matching the mesh"
            )
        if tokens.shape[0] % (dp * m):
            raise ValueError(
                f"global batch {tokens.shape[0]} must divide by "
                f"data axis x n_microbatches = {dp} x {m}"
            )
        sspec = _state_specs(state, pipe_axis)
        mspec = {"loss": P(), "count": P()}
        if is_moe:
            mspec["moe_aux"] = P()
        sharded = shard_map(
            body_1f1b if schedule == "1f1b" else body,
            mesh=mesh,
            in_specs=(sspec, P(axis_name)),
            out_specs=(sspec, mspec),
        )
        return sharded(state, tokens)

    return jax.jit(step, donate_argnums=(0,))


def make_pipelined_lm_eval_step(
    model,
    mesh: Mesh,
    *,
    axis_name: str = DATA_AXIS,
    pipe_axis: str = PIPE_AXIS,
    n_microbatches: Optional[int] = None,
):
    """Forward-only pipelined eval: exact mean next-token CE through the
    same GPipe forward (vocab-parallel embed/head, per-stage blocks) as
    the train step — `eval(state, tokens) -> {loss, count}` matching
    :func:`..train.lm.make_lm_eval_step`'s contract. ``state`` is the
    full pipelined TrainState (opt buffers ride along untouched)."""
    n_stages = int(mesh.shape[pipe_axis])
    dp = int(mesh.shape[axis_name])
    m = n_microbatches or n_stages
    forward_ce = _make_forward_ce(model, axis_name, pipe_axis, m)

    def body(state, tokens):
        _, (ce_sum, count, _aux) = forward_ce(state.params, tokens)
        loss = jax.lax.psum(ce_sum, axis_name) / count
        return {"loss": loss, "count": count}

    def step(state, tokens):
        if state.params["embed"].shape[0] != n_stages:
            raise ValueError(
                f"state was stacked for "
                f"{state.params['embed'].shape[0]} stages but the mesh "
                f"{pipe_axis!r} axis has {n_stages} — create the state "
                f"with n_stages matching the mesh"
            )
        if tokens.shape[0] % (dp * m):
            raise ValueError(
                f"global batch {tokens.shape[0]} must divide by "
                f"data axis x n_microbatches = {dp} x {m}"
            )
        sharded = shard_map(
            body,
            mesh=mesh,
            in_specs=(_state_specs(state, pipe_axis), P(axis_name)),
            out_specs={"loss": P(), "count": P()},
        )
        return sharded(state, tokens)

    return jax.jit(step)
