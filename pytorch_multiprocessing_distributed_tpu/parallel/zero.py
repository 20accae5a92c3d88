"""graftzero: cross-replica sharded weight update (ZeRO-1) with
bucketed, overlapped gradient communication.

The DP train steps' reference semantics move gradients as ONE
grad-sized ``psum`` and then run a fully replicated optimizer update on
every rank: optimizer moments are N-way redundant in HBM and the
all-reduce serializes behind the backward pass. arXiv:2004.13336
(PAPERS.md) replaces that schedule with

    reduce-scatter(grads) -> sharded optimizer update -> all-gather

so each DP rank stores and updates only ``1/N`` of every moment buffer
and the two collectives move the same total bytes as the one all-reduce
(ring cost: ``2 (N-1)/N P`` either way) — the freed ``(N-1)/N`` of the
optimizer state is what ``plan_capacity(zero_shards=N)`` re-spends.

Mechanics (all under ``shard_map``, the explicit-collective DP path):

- the grad tree is flattened into **dtype-homogeneous flat buckets**
  (:func:`plan_buckets`): shard boundaries land in flat index space, so
  they never have to split a leaf across ragged shapes, and elementwise
  optimizer math runs on bare 1-D shards;
- each bucket is ``lax.psum_scatter``-ed along the DP axis as its own
  collective, chained bucket-to-bucket through
  ``lax.optimization_barrier`` — a pure dependency chain that fixes the
  ISSUE order (bucket 0's scatter can start while later buckets' grads
  are still being computed) without adding ops;
- the optimizer update runs on the local shard only. BOTH shipped
  transforms (:func:`..train.optim.sgd`, :func:`..train.lamb.lamb`)
  provide the ``Transform.shard_update`` / ``Transform.shard_finish``
  split: the elementwise phase runs on the flat shards, the update
  direction is all-gathered, and the finish phase (LR scale; LAMB's
  per-leaf trust ratio) is applied on full leaves with the exact
  replicated math — bit-identical to the replicated baseline by
  construction. A custom transform without the seam falls back to its
  unmodified ``update`` on the shard pytrees, which is only correct
  (and only bitwise-stable) if that update is purely elementwise —
  the seam is the supported path;
- updated params are all-gathered back (per bucket, same chaining), so
  params stay replicated (the ZeRO-1 point: moments shard, params
  don't) and donation still aliases the full state.

Optimizer moments are allocated sharded FROM STEP ONE: a
:class:`ZeroOptState` holds per-bucket flat arrays of GLOBAL shape
``[padded]`` placed ``P(data)`` on the mesh — each rank materializes
only its ``padded/N`` slice, and the replicated tree never exists.
Checkpoints stay portable: ``save_checkpoint`` gathers a
:class:`ZeroOptState` back to the inner (replicated-format) state, so
``--resume auto`` round-trips between ``--zero`` and plain runs.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import flax.struct
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import DATA_AXIS

# Default bucket granularity. Big enough that the tiny audit/test
# models land in ONE bucket per dtype (the committed budget's "exactly
# one reduce-scatter + one all-gather"); small enough that real models
# split into several buckets whose scatters overlap the backward.
DEFAULT_BUCKET_MB = 32.0


@dataclass(frozen=True)
class Bucket:
    """One dtype-homogeneous flat bucket: which param-tree leaves it
    holds (indices into the flattened leaf list), where each starts in
    flat space, and the pad/shard geometry over ``num_shards``."""

    dtype: str
    leaf_idx: Tuple[int, ...]
    sizes: Tuple[int, ...]
    offsets: Tuple[int, ...]
    total: int
    padded: int
    shard: int


@dataclass(frozen=True)
class ZeroPlan:
    """The static bucket layout for one (param tree, num_shards) pair.

    Hashable/frozen by construction: it rides the jit cache key as a
    ``ZeroOptState`` static field, and two states built from the same
    params + shard count compare equal. ``leaf_shapes``/``leaf_dtypes``
    record the flattened param-leaf geometry so gather-on-save can
    unflatten without the original tree."""

    num_shards: int
    buckets: Tuple[Bucket, ...]
    leaf_shapes: Tuple[Tuple[int, ...], ...]
    leaf_dtypes: Tuple[str, ...]

    @property
    def padded_bytes(self) -> int:
        """Total flat bytes across buckets (incl. padding) — the
        reduce-scatter operand volume per step."""
        return sum(b.padded * jnp.dtype(b.dtype).itemsize
                   for b in self.buckets)

    @property
    def shard_bytes(self) -> int:
        """Per-rank flat bytes across buckets — what ONE moment buffer
        costs per chip under zero (= padded_bytes / num_shards), and
        the all-gather operand volume per step."""
        return sum(b.shard * jnp.dtype(b.dtype).itemsize
                   for b in self.buckets)


def plan_buckets(params, num_shards: int, *,
                 bucket_bytes: Optional[int] = None) -> ZeroPlan:
    """Lay the param tree's leaves into dtype-homogeneous flat buckets.

    Leaves keep tree-flattening order within their dtype group; a group
    splits into multiple buckets once it exceeds ``bucket_bytes`` (a
    single oversized leaf gets its own bucket — leaves are never split
    ACROSS buckets; shard boundaries inside one bucket land in flat
    index space instead). Every bucket pads to a multiple of
    ``num_shards`` so ``psum_scatter`` tiles evenly.
    """
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    if bucket_bytes is None:
        bucket_bytes = int(DEFAULT_BUCKET_MB * 2 ** 20)
    leaves = jax.tree.leaves(params)
    if not leaves:
        raise ValueError("plan_buckets: empty parameter tree")
    by_dtype: Dict[str, List[int]] = {}
    for i, leaf in enumerate(leaves):
        by_dtype.setdefault(str(jnp.dtype(leaf.dtype)), []).append(i)

    buckets: List[Bucket] = []
    for dtype, idxs in by_dtype.items():
        itemsize = jnp.dtype(dtype).itemsize
        cur: List[int] = []
        cur_bytes = 0
        groups: List[List[int]] = []
        for i in idxs:
            n = int(math.prod(leaves[i].shape)) * itemsize
            if cur and cur_bytes + n > bucket_bytes:
                groups.append(cur)
                cur, cur_bytes = [], 0
            cur.append(i)
            cur_bytes += n
        if cur:
            groups.append(cur)
        for group in groups:
            sizes = tuple(int(math.prod(leaves[i].shape))
                          for i in group)
            offsets, off = [], 0
            for s in sizes:
                offsets.append(off)
                off += s
            total = off
            padded = -(-total // num_shards) * num_shards
            buckets.append(Bucket(
                dtype=dtype, leaf_idx=tuple(group), sizes=sizes,
                offsets=tuple(offsets), total=total, padded=padded,
                shard=padded // num_shards))
    covered = sorted(i for b in buckets for i in b.leaf_idx)
    assert covered == list(range(len(leaves)))
    return ZeroPlan(
        num_shards=num_shards,
        buckets=tuple(buckets),
        leaf_shapes=tuple(tuple(int(d) for d in leaf.shape)
                          for leaf in leaves),
        leaf_dtypes=tuple(str(jnp.dtype(leaf.dtype))
                          for leaf in leaves),
    )


def static_comm_bytes(plan: ZeroPlan) -> Dict[str, int]:
    """Per-step collective byte volumes as the committed jaxpr budget
    counts them (operand avals): the reduce-scatter sees the full
    padded bucket, the all-gather sees the per-rank shard. These are
    the static bytes the ``train.grad_comm`` events carry — the same
    discipline as ``fleet.static_collective_bytes``."""
    return {"reduce_scatter": plan.padded_bytes,
            "all_gather": plan.shard_bytes}


# ------------------------------------------------- flat (un)bucketing

def _flatten_bucket(leaves: Sequence[jax.Array], bucket: Bucket):
    """Concat the bucket's leaves (tree order) into one flat
    ``[padded]`` array; padding is zeros (sum-neutral under the
    scatter, sliced off at unflatten)."""
    parts = [leaves[i].reshape(-1) for i in bucket.leaf_idx]
    if bucket.padded > bucket.total:
        parts.append(jnp.zeros((bucket.padded - bucket.total,),
                               jnp.dtype(bucket.dtype)))
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


def _unflatten_buckets(flats: Sequence[jax.Array], plan: ZeroPlan,
                       like_tree):
    """Inverse of per-bucket flattening: flat ``[padded]`` arrays back
    to a tree shaped like ``like_tree``."""
    n_leaves = len(plan.leaf_shapes)
    leaves: List[Any] = [None] * n_leaves
    for flat, bucket in zip(flats, plan.buckets):
        for i, off, size in zip(bucket.leaf_idx, bucket.offsets,
                                bucket.sizes):
            leaves[i] = flat[off:off + size].reshape(
                plan.leaf_shapes[i])
    return jax.tree.unflatten(jax.tree.structure(like_tree), leaves)


def _chained(x, chain, overlap: bool):
    """Thread the bucket-order dependency chain: ``x`` gains a data
    dependency on the previous bucket's collective result, so the
    scheduler issues collectives in bucket order (early scatters
    overlap late buckets' computation) without materializing anything
    — ``optimization_barrier`` is the identity."""
    if chain is None or not overlap:
        return x
    return jax.lax.optimization_barrier((x, chain))[0]


def reduce_scatter_grads(grads, plan: ZeroPlan, axis_name: str, *,
                         mean: bool, overlap: bool = True):
    """Bucketed reduce-scatter of a local grad tree along ``axis_name``.

    Returns one ``[shard]`` array per bucket: this rank's slice of the
    cross-replica SUM (``mean=True`` divides by the axis size — the
    ``pmean`` twin). ``overlap=False`` joins every grad leaf before the
    first scatter (the serialized schedule — the bench's baseline for
    the overlap-fraction measurement)."""
    leaves = jax.tree.leaves(grads)
    if not overlap:
        leaves = list(jax.lax.optimization_barrier(tuple(leaves)))
    shards = []
    chain = None
    for bucket in plan.buckets:
        flat = _chained(_flatten_bucket(leaves, bucket), chain, overlap)
        shard = jax.lax.psum_scatter(
            flat, axis_name, scatter_dimension=0, tiled=True)
        chain = shard
        if mean:
            shard = shard / plan.num_shards
        shards.append(shard)
    return shards


def all_gather_buckets(shards: Sequence[jax.Array], plan: ZeroPlan,
                       axis_name: str, *, overlap: bool = True):
    """Per-bucket tiled all-gather (the params-return half), chained
    like the scatters so early gathers overlap late buckets' update
    math."""
    full = []
    chain = None
    for bucket, shard in zip(plan.buckets, shards):
        g = jax.lax.all_gather(_chained(shard, chain, overlap),
                               axis_name, axis=0, tiled=True)
        chain = g
        full.append(g)
    return full


def shard_params(params, plan: ZeroPlan, axis_name: str):
    """This rank's ``[shard]`` slice of each flat param bucket (params
    are replicated under ZeRO-1; the slice is local, no collective)."""
    leaves = jax.tree.leaves(params)
    idx = jax.lax.axis_index(axis_name)
    out = []
    for bucket in plan.buckets:
        flat = _flatten_bucket(leaves, bucket)
        out.append(jax.lax.dynamic_slice_in_dim(
            flat, idx * bucket.shard, bucket.shard))
    return out


def finite_shards(shards: Sequence[jax.Array], axis_name: str):
    """The NaN/inf guard predicate off the SCATTERED grad shards: each
    rank counts non-finite elements in its slices, ONE summed scalar
    psum agrees the verdict — same count-and-sum shape as
    ``step.finite_grads`` (ADD-combines fold under XLA's
    AllReduceReassociate; see that docstring), just computed where the
    reduced grads now live."""
    bad = jnp.asarray(0, jnp.int32)
    for s in shards:
        bad = bad + jnp.sum(
            jnp.logical_not(jnp.isfinite(s)).astype(jnp.int32))
    return jax.lax.psum(bad, axis_name) == 0


def clip_shards_by_global_norm(shards: Sequence[jax.Array],
                               axis_name: str, max_norm: float):
    """Global-norm clipping on scattered shards: partial sum of
    squares per rank + one scalar psum = the full-tree norm; the scale
    is replicated so every rank clips identically.

    NOTE: this is the ONE zero-path piece that is not bit-identical to
    the replicated baseline — the norm sums per-shard partials in rank
    order instead of the replicated path's single leafwise sum, so
    clipped trajectories agree to float-reassociation tolerance only
    (the scale itself differs by ulps when the reassociated sums
    round differently). Unavoidable without gathering the grads the
    schedule exists not to gather; documented at every claim site."""
    sq = sum(jnp.sum(jnp.square(s.astype(jnp.float32))) for s in shards)
    gnorm = jnp.sqrt(jax.lax.psum(sq, axis_name))
    scale = jnp.minimum(1.0, max_norm / (gnorm + 1e-6))
    return [s * scale for s in shards]


def comm_probe(plan: ZeroPlan, mesh: Mesh,
               axis_name: str = DATA_AXIS):
    """Jitted comm-only program: the step's exact bucketed
    reduce-scatter + all-gather dependency chain on dummy grad-sized
    buffers. The bench times it solo (drained, synced) to measure the
    standalone grad-comm wall — the denominator of the overlap
    fraction. Takes a list of ``[padded]`` arrays (one per bucket,
    replicated) and returns the gathered buckets."""
    from jax import shard_map

    def body(flats):
        shards = []
        chain = None
        for flat in flats:
            flat = _chained(flat, chain, True)
            s = jax.lax.psum_scatter(
                flat, axis_name, scatter_dimension=0, tiled=True)
            chain = s
            shards.append(s)
        return all_gather_buckets(shards, plan, axis_name)

    n = len(plan.buckets)
    return jax.jit(shard_map(
        body, mesh=mesh, in_specs=([P()] * n,), out_specs=[P()] * n,
        check_vma=False))


# ------------------------------------------------------ sharded state

@flax.struct.dataclass
class ZeroOptState:
    """Optimizer state with moment buffers stored as per-bucket flat
    arrays of GLOBAL shape ``[padded]``, placed ``P(data)`` on the mesh
    — each rank holds ``padded/N``. ``inner`` keeps the wrapped
    transform's own structure (``OptState``/``LambState``) with each
    moment tree replaced by the bucket list, so the transform's
    ``update`` runs on it unchanged; scalar leaves (step counts, init
    flags) stay replicated."""

    inner: Any
    plan: ZeroPlan = flax.struct.field(pytree_node=False)
    moment_fields: Tuple[str, ...] = flax.struct.field(
        pytree_node=False, default=())

    def specs(self, axis_name: str):
        """The shard_map spec tree: ``P(axis)`` on every bucket array,
        ``P()`` on scalars — mirrors this state's structure."""
        spec = jax.tree.map(lambda _: P(), self.inner)
        spec = spec._replace(**{
            f: [P(axis_name)] * len(self.plan.buckets)
            for f in self.moment_fields})
        return ZeroOptState(inner=spec, plan=self.plan,
                            moment_fields=self.moment_fields)


def _moment_fields(inner, params) -> Tuple[str, ...]:
    """Fields of a NamedTuple-style transform state whose value
    mirrors the param-tree structure (the moment buffers to shard);
    everything else must be scalar-leaved (kept replicated)."""
    fields = getattr(inner, "_fields", None)
    if fields is None:
        raise ValueError(
            "zero mode needs a NamedTuple-style optimizer state "
            f"(OptState/LambState), got {type(inner).__name__}")
    p_struct = jax.tree.structure(params)
    moments = []
    for f in fields:
        val = getattr(inner, f)
        if jax.tree.structure(val) == p_struct and jax.tree.leaves(val):
            moments.append(f)
        else:
            for leaf in jax.tree.leaves(val):
                if getattr(leaf, "ndim", 0) != 0:
                    raise ValueError(
                        f"optimizer state field {f!r} is neither a "
                        "param-shaped moment tree nor scalar-leaved — "
                        "zero mode cannot shard it")
    return tuple(moments)


def _is_abstract(tree) -> bool:
    return any(not hasattr(leaf, "dtype") or isinstance(
        leaf, jax.ShapeDtypeStruct) for leaf in jax.tree.leaves(tree))


def zeroify_state(state, mesh: Mesh, *, axis_name: str = DATA_AXIS,
                  bucket_bytes: Optional[int] = None):
    """Replace a replicated-format ``opt_state`` with a sharded
    :class:`ZeroOptState`: moments flattened into the plan's buckets
    and device_put ``P(axis_name)`` so each rank materializes only its
    slice. Abstract states (``ShapeDtypeStruct`` leaves — the audit
    path) produce abstract bucket leaves, no placement. Values carry
    over exactly, so a resumed inner state round-trips."""
    if isinstance(state.opt_state, ZeroOptState):
        raise ValueError("state is already zero-sharded")
    num = int(mesh.shape[axis_name])
    plan = plan_buckets(state.params, num, bucket_bytes=bucket_bytes)
    inner = state.opt_state
    moments = _moment_fields(inner, state.params)
    if not moments:
        raise ValueError(
            f"{type(inner).__name__} has no param-shaped moment "
            "buffers to shard — zero mode would change nothing")
    abstract = _is_abstract(inner)
    sharding = (None if abstract
                else NamedSharding(mesh, P(axis_name)))

    def bucketize(tree):
        leaves = jax.tree.leaves(tree)
        shapes = tuple(tuple(int(d) for d in leaf.shape)
                       for leaf in leaves)
        if shapes != plan.leaf_shapes:
            raise ValueError(
                "optimizer moment tree does not mirror the param "
                "tree's leaf shapes — cannot bucket it")
        dtypes = tuple(str(jnp.dtype(leaf.dtype)) for leaf in leaves)
        if dtypes != plan.leaf_dtypes:
            raise ValueError(
                "optimizer moment dtypes do not mirror the param "
                "tree's — the dtype-homogeneous buckets would "
                "silently promote; shard such a transform explicitly")
        out = []
        for b in plan.buckets:
            if abstract:
                out.append(jax.ShapeDtypeStruct((b.padded,),
                                                jnp.dtype(b.dtype)))
            else:
                flat = _flatten_bucket([jnp.asarray(x) for x in leaves],
                                       b)
                out.append(jax.device_put(flat, sharding))
        return out

    new_inner = inner._replace(
        **{f: bucketize(getattr(inner, f)) for f in moments})
    return state.replace(opt_state=ZeroOptState(
        inner=new_inner, plan=plan, moment_fields=moments))


def gather_opt_state(zstate: ZeroOptState, params):
    """Inverse of :func:`zeroify_state`'s bucketing: the inner
    (replicated-format) state, moments unflattened to the param tree.
    Host-side (``np.asarray`` reads each global bucket — the
    gather-on-save moment); callers with non-addressable shards gather
    first (``checkpoint._gather_for_host``)."""
    import numpy as np

    plan = zstate.plan

    def unbucket(flats):
        host = [np.asarray(f) for f in flats]
        return _unflatten_buckets(host, plan, params)

    return zstate.inner._replace(
        **{f: unbucket(getattr(zstate.inner, f))
           for f in zstate.moment_fields})


def train_state_specs(state, axis_name: str = DATA_AXIS):
    """Per-leaf shard_map spec tree for a ``TrainState`` carrying a
    :class:`ZeroOptState`: everything replicated (``P()``) except the
    moment buckets (``P(axis)``)."""
    if not isinstance(state.opt_state, ZeroOptState):
        raise ValueError(
            "train_state_specs wants a zero-sharded state (build it "
            "with zeroify_state)")
    return state.replace(
        params=jax.tree.map(lambda _: P(), state.params),
        batch_stats=jax.tree.map(lambda _: P(), state.batch_stats),
        opt_state=state.opt_state.specs(axis_name),
        epoch=P(),
        ema_params=jax.tree.map(lambda _: P(), state.ema_params),
    )


# ------------------------------------------------------ sharded update

def apply_sharded_update(optimizer, zstate: ZeroOptState,
                         grad_shards: Sequence[jax.Array], params,
                         axis_name: str, *, lr_step=None,
                         overlap: bool = True):
    """The ZeRO-1 update: optimizer math on local shards, ONE bucketed
    all-gather back to full params.

    Transforms with the ``shard_update``/``shard_finish`` pair (both
    shipped optimizers) compute the elementwise direction sharded,
    gather it, and apply the finish phase (LR scale, LAMB's per-leaf
    trust ratio) on FULL leaves — the exact replicated math, so the
    trajectory is bit-identical to the baseline. A custom transform
    without the seam falls back to its unmodified ``update`` on the
    flat shard pytrees (lists of ``[shard]`` arrays stand in for the
    param tree) — correct only for purely elementwise updates.

    Returns ``(new_params, new_zstate)``.
    """
    if getattr(optimizer, "apply", None) is not None:
        raise ValueError(
            "zero mode shards the update through the transform's "
            "update()/shard_update() path; a fused whole-update "
            "optimizer (apply=...) cannot run on shards — use the "
            "unfused transform")
    plan = zstate.plan
    p_shards = shard_params(params, plan, axis_name)
    shard_update = getattr(optimizer, "shard_update", None)
    if shard_update is not None:
        u_shards, new_inner = shard_update(
            list(grad_shards), zstate.inner, p_shards, lr_step=lr_step)
    else:
        u_shards, new_inner = optimizer.update(
            list(grad_shards), zstate.inner, p_shards, lr_step=lr_step)
    full = all_gather_buckets(u_shards, plan, axis_name,
                              overlap=overlap)
    updates = _unflatten_buckets(full, plan, params)
    shard_finish = getattr(optimizer, "shard_finish", None)
    if shard_finish is not None:
        updates = shard_finish(updates, params, lr_step=lr_step)
    from ..train.optim import apply_updates

    new_params = apply_updates(params, updates)
    return new_params, ZeroOptState(inner=new_inner, plan=plan,
                                    moment_fields=zstate.moment_fields)
