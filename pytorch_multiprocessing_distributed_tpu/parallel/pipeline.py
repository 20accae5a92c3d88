"""Pipeline parallelism: GPipe-style microbatched execution over a mesh
axis, expressed as a shard_map collective pipeline.

The reference is single-stage (SURVEY.md §2.3 marks PP absent); this is
the framework's PP primitive. Stage s of a homogeneous S-stage network
lives on mesh shard s of the ``pipe`` axis. Microbatches enter stage 0,
activations hop to the next stage each tick via ``lax.ppermute`` (ICI
neighbor exchange, overlapped with the current tick's compute by XLA),
and after ``M + S - 1`` ticks every microbatch has flowed through every
stage — the classic GPipe schedule with its (S-1)-tick bubble.

Differentiable by construction: the schedule is a ``lax.scan`` over
ticks and autodiff reverses it (backward microbatches flow the ring the
other way), so ``jax.grad`` of a loss on the pipeline output yields
per-stage parameter gradients on the shard that owns the stage — a
pipelined training step with no hand-written backward schedule.

Use INSIDE ``shard_map`` with the stage-stacked params sharded over the
pipe axis (leading dim S -> per-shard 1, see tests):

    jax.shard_map(
        lambda p, x: pipeline_apply(stage_fn, p, x, axis_name="pipe"),
        mesh=mesh,
        in_specs=(P("pipe"), P()),
        out_specs=P(),
    )

Keep ``check_vma`` at its default (True): the replication checker is
what makes the AD transpose of the final ``psum`` correct — under
``check_vma=False`` gradients through the pipeline silently come back
scaled by the number of stages.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp



def _vary(x, axis_name):
    """Mark ``x`` device-varying over ``axis_name`` if it isn't already
    (check_vma bookkeeping for values entering the per-shard schedule)."""
    if axis_name in jax.typeof(x).vma:
        return x
    return jax.lax.pcast(x, axis_name, to="varying")


def _match_vma(x, vma_of):
    """Widen ``x``'s device-varying axes to ``vma_of``'s (cotangents
    must carry the exact vma of the output they seed)."""
    want = jax.typeof(vma_of).vma
    have = jax.typeof(x).vma
    for ax in want - have:
        x = jax.lax.pcast(x, ax, to="varying")
    return x


def _zeros_vma(shape, dtype, vma_of):
    """Zeros carrying ``vma_of``'s device-varying axes — fresh constants
    are replication-invariant, which would make a scan carry's vma
    narrower than the values written into it (jax.vjp then rejects the
    cotangents as type-mismatched)."""
    return _match_vma(jnp.zeros(shape, dtype), vma_of)


def _zeros_like_tree_vma(tree):
    return jax.tree.map(
        lambda l: _zeros_vma(jnp.shape(l), jnp.result_type(l), l), tree
    )


def _stage_aux_zeros(stage_fn, params, x, vma_of):
    """Zero accumulator matching ``stage_fn``'s aux output structure
    (shared by both schedules so their aux bookkeeping cannot drift)."""
    aux_shapes = jax.eval_shape(lambda p, xx: stage_fn(p, xx)[1],
                                params, x)
    return jax.tree.map(
        lambda s: _zeros_vma(s.shape, s.dtype, vma_of), aux_shapes)


def _masked_aux_add(acc, aux_t, valid):
    """Accumulate a stage-aux pytree for VALID (non-bubble) ticks only."""
    return jax.tree.map(
        lambda a, g: a + jnp.where(valid, g, 0.0), acc, aux_t)


def pipeline_apply(
    stage_fn: Callable,
    stage_params,
    microbatches: jax.Array,
    *,
    axis_name: str,
    with_stage_aux: bool = False,
):
    """Run the S-stage pipeline on ``M`` microbatches.

    Args:
      stage_fn: ``stage_fn(params, x) -> y`` with ``y.shape == x.shape``
        (homogeneous stages — the standard PP regime). With
        ``with_stage_aux=True`` the contract is ``stage_fn(params, x) ->
        (y, aux)`` where ``aux`` is a pytree of per-invocation scalars
        (e.g. MoE balance losses).
      stage_params: THIS shard's stage parameters (pytree; leaves carry
        a leading stage dim of 1 from the ``P(axis_name)`` in_spec,
        squeezed here).
      microbatches: ``[M, mb, ...]`` replicated input microbatches.
      axis_name: the bound pipe mesh axis.
      with_stage_aux: accumulate the aux outputs of VALID (non-bubble) stage
        invocations. The schedule is a plain scan, so differentiating
        the caller's objective through the accumulated aux flows
        gradients into routing params (and upstream activations)
        automatically.

    Returns:
      ``[M, mb, ...]`` pipeline outputs, replicated across the axis.
      With ``with_stage_aux``: ``(outputs, aux_sum)`` where ``aux_sum`` is
      THIS shard's sum over its valid invocations (device-varying —
      ``psum`` over the axis for the global sum).
    """
    n = jax.lax.psum(1, axis_name)  # static python int under shard_map
    i = jax.lax.axis_index(axis_name)
    m = microbatches.shape[0]
    params = jax.tree.map(lambda l: jnp.squeeze(l, axis=0), stage_params)
    perm = [(j, (j + 1) % n) for j in range(n)]
    # Run under check_vma=True (shard_map's default): correct psum/
    # ppermute AD transposes REQUIRE the replication checker — with
    # check_vma=False the transpose of the final psum over-counts
    # gradients by the axis size. Mark the device-varying values
    # explicitly so the checker accepts the scan carries.
    microbatches = _vary(microbatches, axis_name)

    def tick(carry, t):
        act, out, aux_acc = carry
        # stage 0 injects microbatch t (clipped reads feed the bubble
        # ticks; their results are masked out of `out` below)
        inj = microbatches[jnp.clip(t, 0, m - 1)]
        x = jnp.where(i == 0, inj, act)
        if with_stage_aux:
            y, aux_t = stage_fn(params, x)
            # this stage computes microbatch t - i at tick t; bubble
            # ticks process clipped garbage whose aux must not count
            f_valid = jnp.logical_and(t - i >= 0, t - i < m)
            aux_acc = _masked_aux_add(aux_acc, aux_t, f_valid)
        else:
            y = stage_fn(params, x)
        # the last stage banks finished microbatch t - (n - 1)
        slot = t - (n - 1)
        valid = jnp.logical_and(
            i == n - 1, jnp.logical_and(slot >= 0, slot < m)
        )
        sc = jnp.clip(slot, 0, m - 1)
        out = out.at[sc].set(jnp.where(valid, y, out[sc]))
        # rotate activations one stage forward around the ring
        act = jax.lax.ppermute(y, axis_name, perm)
        return (act, out, aux_acc), None

    act0 = jnp.zeros_like(microbatches[0])  # inherits varying-ness
    out0 = jnp.zeros_like(microbatches)
    if with_stage_aux:
        aux0 = _stage_aux_zeros(stage_fn, params, microbatches[0],
                                microbatches)
    else:
        aux0 = ()
    (act, out, aux_acc), _ = jax.lax.scan(
        tick, (act0, out0, aux0), jnp.arange(m + n - 1)
    )
    # `out` is populated only on the last shard; replicate it
    mask = (i == n - 1).astype(out.dtype)
    out = jax.lax.psum(out * mask, axis_name)
    return (out, aux_acc) if with_stage_aux else out


def pipeline_1f1b(
    stage_fn: Callable,
    stage_params,
    microbatches: jax.Array,
    loss_fn: Callable,
    loss_params,
    aux,
    *,
    axis_name: str,
    with_stage_aux: bool = False,
    stage_aux_cotangent=None,
):
    """1F1B pipelined training pass: loss + grads in one schedule.

    :func:`pipeline_apply` + autodiff is GPipe: ALL forwards run before
    any backward, so the reversed scan stashes per-tick residuals for
    every one of the ``M`` microbatches — activation memory grows with
    ``M``, which defeats the point of microbatching. 1F1B starts each
    microbatch's backward as soon as its forward leaves the last stage;
    at any instant a stage holds at most ``2S - 1`` stage-INPUTS (a
    rolling buffer, independent of ``M``) and rematerializes the stage
    forward inside the backward tick (the classic remat trade: one extra
    stage-forward per backward buys O(S) instead of O(M) residency).

    Schedule (tick ``t``, stage ``s`` of ``S``, microbatch ``j``):
    forward of ``j`` runs at ``t = j + s``; the last stage computes the
    per-microbatch loss and its output cotangent immediately; backward
    of ``j`` runs at ``t = j + 2S - 1 - s``. Every steady-state tick is
    exactly one-forward-one-backward per stage. Activations hop +1 on
    the ``ppermute`` ring, cotangents hop -1, both overlapped with
    compute by XLA. Total ``M + 2S - 1`` ticks.

    Args:
      stage_fn: ``stage_fn(params, x) -> y`` with ``y.shape == x.shape``
        (pure local compute — no collectives; it runs under ``jax.vjp``
        inside the schedule).
      stage_params: THIS shard's stage parameters (leaves carry the
        leading stage dim of 1 from a ``P(axis_name)`` in_spec).
      microbatches: ``[M, mb, ...]`` input microbatches.
      loss_fn: ``loss_fn(loss_params, y, aux_j) -> scalar`` per-
        microbatch loss, evaluated where the LAST stage's output lands.
        Local ops only — it executes on every stage every tick (masked),
        so a collective inside it would change meaning.
      loss_params: parameters of the loss head (e.g. final-LN / head
        weights). Grads come back as per-shard PARTIAL sums (nonzero
        only where the last stage contributed): ``psum`` them for
        replicated params, or feed them raw to the transpose of the
        collective that built them (e.g. an ``all_gather``'s vjp).
      aux: pytree of ``[M, ...]`` per-microbatch loss inputs (targets,
        weights); no gradients flow to it.
      axis_name: the bound pipe mesh axis.
      with_stage_aux: ``stage_fn(params, x) -> (y, stage_aux)`` where
        ``stage_aux`` is a pytree of scalars (e.g. MoE balance losses).
        The schedule then optimizes ``sum_j loss_j + <stage_aux_cotangent,
        sum_valid stage_aux>``: on each backward tick the aux
        cotangent is seeded alongside the activation cotangent, so its
        gradient reaches this stage's params AND flows upstream
        through the cotangent ring (routing depends on the stage
        input).
      stage_aux_cotangent: pytree matching ``stage_aux`` — the constant
        d(objective)/d(stage_aux) weights (required iff ``with_stage_aux``).

    Returns:
      ``(loss_sum, dstage_params, dloss_params, dmicrobatches)``:
      summed loss over microbatches (replicated over the axis), grads
      for this shard's stage params (same leading-1 shape), UNREDUCED
      per-shard loss-param grads (see above), and the ``[M, mb, ...]``
      input cotangent (replicated over the axis). With ``with_stage_aux`` a
      fifth element: THIS shard's valid-invocation aux sum
      (device-varying — ``psum`` over the axis for the global sum).
    """
    if with_stage_aux and stage_aux_cotangent is None:
        raise ValueError("with_stage_aux=True requires stage_aux_cotangent")
    n = jax.lax.psum(1, axis_name)  # static python int under shard_map
    i = jax.lax.axis_index(axis_name)
    m = microbatches.shape[0]
    buf = 2 * n - 1  # max in-flight stage-inputs (stage 0's lifetime)
    params = jax.tree.map(lambda l: jnp.squeeze(l, axis=0), stage_params)
    perm_fwd = [(j, (j + 1) % n) for j in range(n)]
    perm_bwd = [(j, (j - 1) % n) for j in range(n)]

    microbatches = _vary(microbatches, axis_name)
    aux = jax.tree.map(lambda l: _vary(l, axis_name), aux)
    loss_params = jax.tree.map(lambda l: _vary(l, axis_name), loss_params)
    if with_stage_aux:
        # the stage-aux outputs inherit the microbatches' full vma (the
        # activations they are computed from); the constant cotangent
        # seeded into their vjp must carry the same
        stage_aux_cotangent = jax.tree.map(
            lambda l: _match_vma(l, microbatches), stage_aux_cotangent)

    def masked_add(acc, g, mask):
        return jax.tree.map(
            lambda a, gg: a + gg * mask.astype(gg.dtype), acc, g
        )

    def tick(carry, t):
        (act_in, cot_in, resid, dy_buf, dps, dlps, dmb, loss_acc,
         aux_acc) = carry

        # ---- forward: microbatch j_f = t - i flows through this stage
        j_f = t - i
        f_valid = jnp.logical_and(j_f >= 0, j_f < m)
        inj = microbatches[jnp.clip(t, 0, m - 1)]
        x_in = jnp.where(i == 0, inj, act_in)
        if with_stage_aux:
            y, aux_t = stage_fn(params, x_in)
            aux_acc = _masked_aux_add(aux_acc, aux_t, f_valid)
        else:
            y = stage_fn(params, x_in)

        # last stage: per-microbatch loss + output cotangent for j_f,
        # banked one tick (its backward runs at t + 1)
        aux_j = jax.tree.map(lambda l: l[jnp.clip(j_f, 0, m - 1)], aux)
        loss_j, loss_vjp = jax.vjp(
            lambda lp, yy: loss_fn(lp, yy, aux_j), loss_params, y
        )
        dlp_j, dy_j = loss_vjp(jnp.ones_like(loss_j))
        l_valid = jnp.logical_and(f_valid, i == n - 1)
        loss_acc = loss_acc + jnp.where(l_valid, loss_j, 0.0)
        dlps = masked_add(dlps, dlp_j, l_valid)
        new_dy = jnp.where(l_valid, dy_j, jnp.zeros_like(dy_j))

        # ---- backward: microbatch j_b = t - (2S-1) + i, rematerialized
        # from the stored stage input. Residual READ happens before the
        # forward WRITE below: at stage 0 the two share a slot on the
        # very tick j_b's storage is retired (j_f - j_b == buf).
        j_b = t - (2 * n - 1) + i
        b_valid = jnp.logical_and(j_b >= 0, j_b < m)
        x_saved = resid[jnp.mod(j_b, buf)]
        g_in = jnp.where(i == n - 1, dy_buf, cot_in)
        _, stage_vjp = jax.vjp(stage_fn, params, x_saved)
        if with_stage_aux:
            # seed the constant aux cotangent with the activation one:
            # the vjp routes it into this stage's params (dp_j) and
            # upstream through dx_j. Invalid-tick contributions follow
            # the same masking as everything else (dp masked here, dx
            # masked at the j_b chain's accumulation points).
            dp_j, dx_j = stage_vjp((g_in, stage_aux_cotangent))
        else:
            dp_j, dx_j = stage_vjp(g_in)
        dps = masked_add(dps, dp_j, b_valid)
        sb = jnp.clip(j_b, 0, m - 1)
        take = jnp.logical_and(b_valid, i == 0)
        dmb = dmb.at[sb].set(jnp.where(take, dx_j, dmb[sb]))

        # now bank this tick's forward input
        sf = jnp.mod(j_f, buf)
        resid = resid.at[sf].set(jnp.where(f_valid, x_in, resid[sf]))

        act_out = jax.lax.ppermute(y, axis_name, perm_fwd)
        cot_out = jax.lax.ppermute(dx_j, axis_name, perm_bwd)
        return (
            act_out, cot_out, resid, new_dy, dps, dlps, dmb, loss_acc,
            aux_acc
        ), None

    mb0 = microbatches[0]
    z = _zeros_vma(mb0.shape, mb0.dtype, mb0)
    if with_stage_aux:
        aux0 = _stage_aux_zeros(stage_fn, params, mb0, mb0)
    else:
        aux0 = ()
    carry0 = (
        z,                                                # fwd ring
        z,                                                # bwd ring
        _zeros_vma((buf,) + z.shape, z.dtype, mb0),       # input residuals
        z,                                        # banked loss cotangent
        _zeros_like_tree_vma(params),             # stage-param grads
        _zeros_like_tree_vma(loss_params),
        _zeros_vma(microbatches.shape, microbatches.dtype, mb0),
        _zeros_vma((), jnp.float32, mb0),         # loss accumulator
        aux0,                                     # stage-aux accumulator
    )
    (_, _, _, _, dps, dlps, dmb, loss_acc, aux_acc), _ = jax.lax.scan(
        tick, carry0, jnp.arange(m + 2 * n - 1)
    )

    loss_sum = jax.lax.psum(loss_acc, axis_name)  # last stage holds it
    dmb = jax.lax.psum(dmb, axis_name)            # stage 0 holds it
    dstage = jax.tree.map(lambda g: jnp.expand_dims(g, 0), dps)
    if with_stage_aux:
        return loss_sum, dstage, dlps, dmb, aux_acc
    return loss_sum, dstage, dlps, dmb
