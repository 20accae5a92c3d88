"""Mixture-of-Experts MLP with expert parallelism (EP).

The reference has no MoE (SURVEY.md §2.3 marks expert parallelism
absent); this is part of the framework's scale-out surface, built the
idiomatic TPU way: the layer is written with GLOBAL semantics
(Switch-style top-1 routing with a fixed per-expert capacity so every
shape is static), the expert-indexed weight tensors carry a mesh-axis
annotation, and GSPMD partitions the dispatch/combine einsums —
lowering them to the all-to-all exchanges an NCCL MoE implementation
would hand-write.

Routing (Switch Transformer top-1 by default; ``top_k >= 2`` switches
to GShard-style renormalized top-k with choice-priority capacity):
  gates  = softmax(x @ Wg)                      [B, S, E]
  expert = top_k(gates) choices                 [B, S, K]
  slot   = position of each (token, choice) within its expert's
           capacity C = ceil(S * K * capacity_factor / E); choice j
           claims slots only after every choice < j; assignments past
           capacity are DROPPED (output 0 — the residual carries them)
  dispatch[b, s, e, c] = 1 iff some choice of token (b, s) is slot c
           of expert e
  h = expert_mlp_e(dispatch^T x)                [E, B, C, D] (vmapped)
  y[b, s] = sum_j weight_j * h[expert_j, b, slot_j]
           (weight = raw top prob for K=1, renormalized top-K else)

Under ``shard_expert_params`` + a mesh, each device stores E/ep of the
expert weights and computes only its experts' FLOPs.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import PartitionSpec as P

# a rung of row_ladder is a whole number of the kernel's row tiles
from .pallas.grouped_matmul import _MAX_ROW_TILE as _ROW_TILE
from .pallas.grouped_matmul import gated_grouped_matmul, grouped_matmul


class MoEMlp(nn.Module):
    """Switch-style top-1 MoE feed-forward block.

    Attributes:
      n_experts: number of expert MLPs (E).
      d_hidden: expert hidden width.
      capacity_factor: per-expert capacity = ceil(S * factor / E).
      expert_axis: optional mesh axis name baked into a
        ``with_sharding_constraint`` on the expert-indexed activations
        (use together with :func:`shard_expert_params`); ``None`` runs
        unconstrained (single device / tests).
      dtype: compute dtype (params stay f32).
      top_k: experts per token. 1 = Switch (combine weight is the RAW
        top softmax probability); >= 2 = GShard-style (weights are the
        top-k probabilities renormalized to sum to 1; choice ``j``
        claims capacity slots only after every choice ``< j`` — a
        token's secondary expert drops before anyone's primary does).
    """

    n_experts: int
    d_hidden: int
    capacity_factor: float = 1.0
    expert_axis: Optional[str] = None
    dtype: Any = None
    top_k: int = 1

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        if not 1 <= self.top_k <= self.n_experts:
            raise ValueError(
                f"top_k must be in [1, n_experts={self.n_experts}], "
                f"got {self.top_k}"
            )
        b, s, d = x.shape
        e = self.n_experts
        # capacity scales with top_k: k assignments per token compete
        # for the same expert slots (GShard sizes top-2 at 2S/E)
        cap = max(
            1, int(-(-s * self.top_k * self.capacity_factor // e))
        )
        dtype = self.dtype or x.dtype

        wg = self.param("gate", nn.initializers.lecun_normal(), (d, e),
                        jnp.float32)
        w1 = self.param(
            "w1", nn.initializers.lecun_normal(), (e, d, self.d_hidden),
            jnp.float32)
        b1 = self.param("b1", nn.initializers.zeros, (e, self.d_hidden),
                        jnp.float32)
        w2 = self.param(
            "w2", nn.initializers.lecun_normal(), (e, self.d_hidden, d),
            jnp.float32)
        b2 = self.param("b2", nn.initializers.zeros, (e, d), jnp.float32)

        k = self.top_k
        router_logits = x.astype(jnp.float32) @ wg  # [B, S, E]
        gates = jax.nn.softmax(
            router_logits, axis=-1
        )  # [B, S, E] — routing math in f32 always
        topv, topi = jax.lax.top_k(gates, k)  # [B, S, K]
        if k == 1:
            weights = topv  # Switch: the raw top probability
        else:
            # GShard: renormalize over the selected experts
            weights = topv / jnp.sum(topv, axis=-1, keepdims=True)
        onehots = jax.nn.one_hot(topi, e, dtype=jnp.float32)  # [B, S, K, E]

        # Load-balancing auxiliary loss (Switch Transformer): E * <f, p>
        # where f_e = fraction of tokens whose PRIMARY choice is expert
        # e (hard, pre-capacity — also the GShard convention for top-2)
        # and p_e = mean router probability of expert e. Minimized
        # (= 1.0) at uniform routing; without it routing collapses onto
        # a few experts in real training. Differentiable through p only
        # (f is argmax-hard), which is exactly the Switch formulation.
        # Sown under the "losses" collection — training steps read it
        # via ``mutable=["losses"]`` and add ``weight * aux``;
        # eval/apply without mutable discards it.
        f = jnp.mean(onehots[:, :, 0, :].reshape(-1, e), axis=0)  # [E]
        p = jnp.mean(gates.reshape(-1, e), axis=0)  # [E]
        self.sow("losses", "moe_aux", e * jnp.sum(f * p))
        # Router z-loss (ST-MoE): mean logsumexp(logits)^2 keeps router
        # logits small/stable in bf16 training.
        self.sow(
            "losses", "moe_z",
            jnp.mean(jax.nn.logsumexp(router_logits, axis=-1) ** 2),
        )

        # Per-choice capacity slots: choice j's tokens claim an
        # expert's slots only after every choice < j (sequence order
        # within a choice), so a secondary assignment can never evict a
        # primary one. ``offset`` carries the running per-expert count.
        dispatches = []
        offset = jnp.zeros((b, 1, e), jnp.float32)
        for j in range(k):
            oh = onehots[:, :, j, :]  # [B, S, E]
            pos = (jnp.cumsum(oh, axis=1) + offset) * oh  # 1-based
            slot = (jnp.sum(pos, axis=-1) - 1.0).astype(jnp.int32)
            offset = offset + jnp.sum(oh, axis=1, keepdims=True)
            kept = (slot < cap)[..., None]  # tokens past capacity drop
            dispatches.append(
                oh[..., None]
                * jax.nn.one_hot(
                    jnp.clip(slot, 0, cap - 1), cap
                )[:, :, None, :]
                * kept[..., None]
            )  # [B, S, E, C]
        # a token's choices go to DIFFERENT experts, so the per-choice
        # dispatch masks are disjoint and their sum stays one-hot
        dispatch = sum(dispatches)

        xin = x.astype(dtype)
        expert_in = jnp.einsum(
            "bsec,bsd->ebcd", dispatch.astype(dtype), xin
        )  # [E, B, C, D] — GSPMD lowers this to the all-to-all dispatch
        expert_in = self._constrain(expert_in)

        def one_expert(inp, w1e, b1e, w2e, b2e):
            h = jax.nn.relu(inp @ w1e.astype(dtype) + b1e.astype(dtype))
            return h @ w2e.astype(dtype) + b2e.astype(dtype)

        h = jax.vmap(one_expert)(expert_in, w1, b1, w2, b2)  # [E, B, C, D]
        h = self._constrain(h)

        combine = sum(
            dispatches[j] * weights[:, :, j, None, None] for j in range(k)
        )  # [B, S, E, C]
        y = jnp.einsum(
            "bsec,ebcd->bsd", combine.astype(dtype), h
        )  # the all-to-all return + weighted combine
        return y.astype(x.dtype)

    def _constrain(self, t):
        if self.expert_axis is None or self.is_initializing():
            return t
        mesh = jax.sharding.get_abstract_mesh()
        if self.expert_axis not in mesh.axis_names:
            # no mesh context (e.g. plain CPU apply in tests): the
            # constraint is a layout hint, not semantics — skip it
            return t
        return jax.lax.with_sharding_constraint(
            t, P(self.expert_axis, *([None] * (t.ndim - 1)))
        )


def shard_expert_params(params, mesh, axis: str):
    """Place a MoEMlp param tree with expert dims sharded over ``axis``."""
    from jax.sharding import NamedSharding

    def place(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name in ("w1", "b1", "w2", "b2"):
            sh = NamedSharding(mesh, P(axis, *([None] * (leaf.ndim - 1))))
        else:
            sh = NamedSharding(mesh, P())
        return jax.device_put(leaf, sh)

    return jax.tree_util.tree_map_with_path(place, params)


# ------------------------------------------------------------- graftcheck

def audit_programs():
    """graftcheck registration hook: the expert-parallel MoE layer.

    The layer's dispatch/combine einsums are WRITTEN dense; the whole
    EP design rests on GSPMD lowering them to expert-axis exchanges
    instead of replicating every expert's input. That is invisible at
    the jaxpr level, so this program COMPILES (CPU, partitioned over a
    ``model``-axis expert mesh with sharded expert weights) and the
    committed HLO budget records the exchange the partitioner actually
    emits — growing all-gather volume here means dropped expert
    sharding (the capacity-vs-replication trade of arXiv:2004.13336).
    """
    def build():
        import jax.numpy as jnp
        from jax.sharding import NamedSharding

        from ..parallel.mesh import MODEL_AXIS, audit_mesh

        mesh = audit_mesh(data=1, model=4)
        d = 8  # token feature width of the audit program
        layer = MoEMlp(n_experts=4, d_hidden=32,
                       expert_axis=MODEL_AXIS, capacity_factor=4.0,
                       dtype=jnp.bfloat16)
        x = jax.ShapeDtypeStruct((2, 16, d), jnp.float32)
        params = jax.eval_shape(
            lambda: layer.init(jax.random.PRNGKey(0),
                               jnp.zeros((2, 16, d))))["params"]

        def shard(path, leaf):
            name = path[-1].key if hasattr(path[-1], "key") else ""
            spec = (P(MODEL_AXIS, *([None] * (leaf.ndim - 1)))
                    if name in ("w1", "b1", "w2", "b2") else P())
            return jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype,
                sharding=NamedSharding(mesh, spec))

        params = jax.tree_util.tree_map_with_path(shard, params)

        def fn(p, inp):
            return layer.apply({"params": p}, inp)

        return {
            "fn": fn, "args": (params, x), "mesh": mesh,
            "compile": True, "compile_fn": jax.jit(fn),
            # expert weights stay resident-sharded: nothing close to
            # the full [E, d, d_hidden] w1/w2 stack may gather
            # (derived from the layer so a geometry change tracks)
            "max_allgather_bytes":
                layer.n_experts * d * layer.d_hidden * 4 - 1,
        }

    return [{"name": "moe_mlp_ep", "min_devices": 4, "build": build}]


# ------------------------------------------------------------ dropless

def route_sigmoid_topk(x32, router, e_bias, top_k: int,
                       routed_scale: float = 1.0, eps: float = 1e-20):
    """Sigmoid-scored top-k routing with a selection bias (the
    ``noaux_tc`` method with one group): ``s = sigmoid(x Wg)`` in
    float32 at the highest matmul precision (the top-k of near-equal
    scores must not turn on a bf16 pass of the MXU); the ``top_k`` of
    ``s + e_bias`` (of ``s`` alone where ``e_bias`` is None) are
    CHOSEN, the weights come from ``s`` alone, normalised over the
    chosen (their sum plus ``eps``, the family's own) and scaled. The
    router scores ALL experts of the model, whichever of them this chip
    holds. ``x32 [T, D]`` -> ``(experts [T, k] int32, weights [T, k]
    f32)``."""
    scores = jax.nn.sigmoid(jnp.dot(
        x32.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    biased = (scores if e_bias is None
              else scores + e_bias.astype(jnp.float32))
    _, chosen = jax.lax.top_k(biased, top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = (picked / (jnp.sum(picked, axis=-1, keepdims=True) + eps)
               * routed_scale)
    return chosen.astype(jnp.int32), weights


def row_ladder(t: int, k: int, held: int, n_experts: int) -> tuple:
    """The static row bounds :func:`dropless_experts` chooses among for
    ``t`` tokens at top-``k``, ``held`` of ``n_experts`` experts held:
    about twice the expected held rows ``t * k * held / n_experts`` in
    whole row tiles, twice that, and last ``t * k`` itself (every row,
    the bound that holds at any routing). Rising; a rung that would
    reach ``t * k`` is left out, which leaves the one rung ``t * k``
    where every expert is held. From static shapes only, so every rung
    is a shape a program is compiled for once."""
    total = t * k
    first = -(-2 * total * held // (n_experts * _ROW_TILE)) * _ROW_TILE
    return tuple(b for b in (first, 2 * first) if b < total) + (total,)


def dropless_experts(x, chosen, weights, w_gate, w_up, w_down, *,
                     n_experts=None, offset: int = 0):
    """The ONE dropless routed-expert layer (prefill, chunk and decode
    alike): every (token, choice) assignment to an expert HELD here is
    computed by the expert it names — no capacity, nothing dropped,
    every shape static.

    The weights are those of the ``held = w_gate.shape[0]`` experts
    ``[offset, offset + held)`` of the ``n_experts`` the router chose
    among (default: all of them are held). An assignment to any other
    expert belongs to another chip: it is counted and left out of the
    sum, and no code stands in for that chip or for the exchange with
    it. The combine weights were normalised over all of a token's
    choices, held or not, so the shares of all chips add up to the
    whole layer.

    The ``T * k`` assignments are sorted by expert, this chip's first
    (stable, so a token's choices keep their order); each held expert's
    rows then form one contiguous group from row 0 and the gated-SiLU
    feed-forward is two grouped matmuls over those groups
    (``ops/pallas/grouped_matmul.py``: on the TPU a kernel that streams
    each expert that has rows once a call, gate and up in one call and
    down in the other; elsewhere ``jax.lax.ragged_dot``). The rows
    routed elsewhere lie behind the last group, in no matmul — but the
    gather before the matmuls and the unsort after them follow the rows
    they are GIVEN. So the layer works on the first ``B`` sorted rows
    only, ``B`` the smallest rung of :func:`row_ladder` that is ``>=
    sum(counts)``, chosen ON THE DEVICE
    from the counts it has (``jax.lax.switch``: no host read, every
    rung a static shape). In a 16-chip deployment the exchange hands a
    chip the rows of its own experts and no others; this is that chip.
    The last rung is ``T * k``: whatever the routing — every token to
    one held expert included — some rung holds every held row, and
    nothing is dropped. With every expert held the ladder has that one
    rung and no conditional is traced.

    Args:
      x: ``[T, D]`` tokens in the compute dtype.
      chosen: ``[T, k]`` int32 expert ids in ``[0, n_experts)``
        (:func:`route_sigmoid_topk`).
      weights: ``[T, k]`` float32 combine weights.
      w_gate, w_up: ``[held, D, F]``; w_down: ``[held, F, D]``.

    Returns ``(y [T, D] float32, counts [held] int32, elsewhere int32,
    given int32)`` — the assignments each held expert received, the
    number routed to experts not held here (together they sum to
    ``T * k``: the dropless invariant, and the load the serving metrics
    report) and the rows the grouped matmuls were given (the rung
    taken).
    """
    t, k = chosen.shape
    held = w_gate.shape[0]
    n_experts = held if n_experts is None else int(n_experts)
    if not 0 <= offset <= n_experts - held:
        raise ValueError(
            f"experts [{offset}, {offset + held}) are not among the "
            f"{n_experts} the router chooses from")
    flat = chosen.reshape(t * k)
    # this chip's experts become 0 .. held - 1, the others follow
    key = flat if offset == 0 else (flat - offset) % n_experts
    order = jnp.argsort(key, stable=True)                # [T*k]
    every = jnp.zeros((n_experts,), jnp.int32).at[key].add(1)
    counts, elsewhere = every[:held], jnp.sum(every[held:])
    in_groups = jnp.sum(counts)

    def over_first(b):
        """The layer over the first ``b`` sorted rows (every held row
        is among them) -> ``y [T, D]`` float32."""
        first = order[:b]
        rows = jnp.take(x, first // k, axis=0)           # [b, D]
        hidden = gated_grouped_matmul(rows, w_gate, w_up, counts)
        out = grouped_matmul(hidden, w_down, counts)     # [b, D] f32
        out = out * jnp.take(weights.reshape(t * k), first)[:, None]
        if held < n_experts:
            # a row behind the last group is in no matmul, and what the
            # grouped kernel leaves there is NOT zero on the chip
            out = jnp.where((jnp.arange(b) < in_groups)[:, None], out, 0.0)
        if b < t * k:
            # each row is added to its token's sum: a one-hot matmul,
            # exact in float32 at this precision (on the chip a
            # scatter-add takes 2.8 us a row: 2.9 ms of a chunk's layer
            # where this takes 0.3, PERF.md)
            mine = (first // k)[None, :] == jnp.arange(t)[:, None]
            return jnp.dot(mine.astype(out.dtype), out,
                           precision=jax.lax.Precision.HIGHEST)
        # every row: back to (token, choice) order (the inverse
        # permutation is a scatter of whole rows), then a sum over each
        # token's k choices
        y = jnp.zeros_like(out).at[order].set(out)
        return jnp.sum(y.reshape(t, k, -1), axis=1)

    rungs = row_ladder(t, k, held, n_experts)
    if len(rungs) == 1:
        return over_first(t * k), counts, elsewhere, jnp.int32(t * k)
    rung = jnp.sum(in_groups > jnp.array(rungs[:-1], jnp.int32))
    y = jax.lax.switch(rung, [lambda b=b: over_first(b) for b in rungs])
    return y, counts, elsewhere, jnp.array(rungs, jnp.int32)[rung]
