"""Plain reference for the ``mimo_v2`` family (Xiaomi MiMo-V2.5's
language model): forward pass and next-token loss.

Written from the published ``config.json`` of MiMo-V2.5 and the
equations below. Straightforward ``jax.numpy`` in float32 at the
highest matmul precision: no kernels, no cache, no pages, no window cut
out of the keys (a window layer is a full score matrix under a band
mask, plus its sink column), no sorting of tokens by expert. It imports
nothing of the program; only the weight values come from it. The matrix
product, the norm, the gated MLP, the routing and the rotation are the
``afmoe`` reference's (``perf/reference/afmoe.py``).

    x <- x + Attn_l( input_layernorm(x) )
    x <- x + FFN_l(  post_attention_layernorm(x) )
    logits = head( norm(x) )                        (untied, no bias)

*Attention*, layer ``l`` of kind ``hybrid_layer_pattern[l]`` (0 full, 1
window): ``q = h Wq`` as ``H`` heads of ``head_dim``, ``k = h Wk`` as
``Hkv_l`` heads of ``head_dim`` and ``v = h Wv * attention_value_scale``
as ``Hkv_l`` heads of ``v_head_dim`` (``Hkv_l``: ``num_key_value_heads``
on a full layer, ``swa_num_key_value_heads`` on a window layer). The
first ``int(head_dim * partial_rotary_factor)`` dimensions of every q
and k head are rotated (pairs ``(i, i + rot / 2)``; ``rope_theta`` on a
full layer, ``swa_rope_theta`` on a window layer), the rest are not.
Query head ``t`` attends key/value head ``t // (H / Hkv_l)`` over
columns ``j <= i`` (full) or ``i - sliding_window < j <= i`` (window),
scores scaled by ``head_dim ^ -0.5``; on a window layer
(``add_swa_attention_sink_bias``) head ``t``'s learned ``sinks[t]`` is
one more column of the softmax that carries no value. The heads'
outputs (``H x v_head_dim``) go into ``Wo``.

*Feed-forward*: a gated-SiLU MLP where ``moe_layer_freq[l]`` is 0.
Elsewhere: ``s = sigmoid(h Wr)`` over ALL experts the router has;
CHOSEN = the top ``num_experts_per_tok`` of ``s + e_bias``; weights
``s[CHOSEN] / (their sum + 1e-20)`` (``norm_topk_prob``) times
``routed_scaling_factor`` (null: 1); output ``sum over the CHOSEN that
are HELD of w_e expert_e(h)``. No shared expert.

*The share.* The tree holds the experts ``[expert_offset, expert_offset
+ held)`` of every expert layer, one chip's share of a stated
expert-parallel deployment; the router and the bias are whole. What the
experts held elsewhere would add is LEFT OUT. The vocabulary may be a
slice: ``embed`` and ``head`` have the rows held.

Weights are read from the program's tree by name: ``embed [V, C]``,
``head/kernel [C, V]``, ``norm_final/scale``, ``layer_<i>/{attn_norm,
ffn_norm}/scale``, ``layer_<i>/attn/{wq [C, H Dk], wk [C, Hkv Dk], wv
[C, Hkv Dv], wo [H Dv, C]}`` and, on a window layer, ``sinks [H]``; and
either ``layer_<i>/mlp/{w_gate, w_up [C, I], w_down [I, C]}`` or
``layer_<i>/moe/{router [C, E], e_bias [E], w_gate, w_up [held, C, F],
w_down [held, F, C]}``. The benchmark calls one SUBLAYER at a time
(:func:`attention_sublayer`, :func:`feed_forward_sublayer`), a long
sequence's attention in blocks of ``block`` query rows.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .afmoe import gated, mm, rms_norm, rotary, route

F32 = jnp.float32


# ----------------------------------------------------------- attention

def grouped_attention(h, p, hp, block, sliding):
    """Causal grouped-query attention over one sequence ``h [S, C]``
    (already normed); ``sliding``: the band of ``hp["window"]`` keys
    and, where the kind has them, the sink column. Queries go in blocks
    of ``block`` rows against every key."""
    s = h.shape[0]
    heads, kv_heads = hp["heads"], hp["kv_heads"][sliding]
    dk, dv, rot = hp["head_dim"], hp["v_head_dim"], hp["rotary_dim"]
    group = heads // kv_heads
    positions = jnp.arange(s)
    inv_freq = hp["inv_freq"][sliding]

    def split(y, n, d):                           # [S, n d] -> [n, S, d]
        return y.reshape(s, n, d).transpose(1, 0, 2)

    def rotate(x):
        return jnp.concatenate(
            [rotary(x[..., :rot], positions, inv_freq), x[..., rot:]],
            axis=-1)

    q = rotate(split(mm(h, p["wq"].astype(F32), hp), heads, dk))
    k = rotate(split(mm(h, p["wk"].astype(F32), hp), kv_heads, dk))
    v = split(mm(h, p["wv"].astype(F32), hp), kv_heads, dv) * hp[
        "value_scale"]
    q = q.reshape(kv_heads, group, s, dk)
    sink = hp["sink"][sliding]

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=2)
        scores = mm(qb, k.transpose(0, 2, 1)[:, None], hp) * hp["scale"]
        i = start + jnp.arange(block)[:, None]
        j = jnp.arange(s)[None, :]
        seen = j <= i
        if sliding:
            seen = jnp.logical_and(seen, i - j < hp["window"])
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        if sink:
            column = jnp.broadcast_to(
                p["sinks"].astype(F32).reshape(kv_heads, group, 1, 1),
                scores.shape[:-1] + (1,))
            probs = jax.nn.softmax(
                jnp.concatenate([scores, column], axis=-1), axis=-1)[..., :-1]
        else:
            probs = jax.nn.softmax(scores, axis=-1)
        out = mm(probs, v[:, None], hp)                 # [kvh, g, B, Dv]
        return out.reshape(heads, block, dv).transpose(1, 0, 2).reshape(
            block, heads * dv)

    out = jax.lax.map(rows, jnp.arange(0, s, block)).reshape(
        s, heads * dv)
    return mm(out, p["wo"].astype(F32), hp)


# -------------------------------------------------------- feed-forward

def experts(h, p, hp):
    """The share's part of the expert layer over ``h [S, C]``: every
    HELD expert over all tokens, masked by its own weight (0 for a
    token that did not choose it), one expert's weights cast at a time.
    A choice of an expert outside ``[offset, offset + held)`` adds
    nothing here."""
    chosen, weights = route(h, p, hp)
    n_experts = p["router"].shape[-1]
    held, offset = p["w_gate"].shape[0], hp["offset"]
    dense = jnp.sum(jax.nn.one_hot(chosen, n_experts, dtype=F32)
                    * weights[..., None], axis=1)              # [S, E]
    mine = dense[:, offset:offset + held]                      # [S, held]

    def one(acc, item):
        w_gate, w_up, w_down, weight = item
        return (acc + weight[:, None] * gated(h, w_gate, w_up, w_down, hp),
                None)

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (p["w_gate"], p["w_up"], p["w_down"], mine.T))
    return out


def feed_forward(h, layer, hp):
    if "moe" in layer:
        return experts(h, layer["moe"], hp)
    m = layer["mlp"]
    return gated(h, m["w_gate"], m["w_up"], m["w_down"], hp)


# --------------------------------------------------------------- model

def attention_sublayer(layer, x, hp, block, sliding):
    """``x [S, C] <- x + Attn(norm(x))``."""
    return x + grouped_attention(
        rms_norm(x, layer["attn_norm"]["scale"], hp["eps"]),
        layer["attn"], hp, block, sliding)


def feed_forward_sublayer(layer, x, hp):
    """``x [S, C] <- x + FFN(norm(x))``."""
    return x + feed_forward(
        rms_norm(x, layer["ffn_norm"]["scale"], hp["eps"]), layer, hp)


def embed(params, tokens, hp):
    return params["embed"][tokens].astype(F32)


def head(params, x, hp):
    """``x [S, C]`` -> ``[S, rows held]``: final RMSNorm, the untied
    head without bias."""
    h = rms_norm(x, params["norm_final"]["scale"], hp["eps"])
    return mm(h, params["head"]["kernel"].astype(F32), hp)


def logits_one(params, tokens, *, hp, block=None):
    """``[S]`` token ids -> ``[S, rows held]`` float32 logits. ``S``
    must be a multiple of ``block`` (default: the whole sequence)."""
    block = block or tokens.shape[0]
    x = embed(params, tokens, hp)
    for i, sliding in enumerate(hp["sliding"]):
        layer = params[f"layer_{i}"]
        x = attention_sublayer(layer, x, hp, block, sliding)
        x = feed_forward_sublayer(layer, x, hp)
    return head(params, x, hp)


def hyper(cfg: dict) -> dict:
    """What the equations read of a configuration. ``expert_offset``
    says which experts the tree's ``held`` are; their number is the
    weights' own leading dimension, and the router's width the
    router's."""
    kinds = cfg["hybrid_layer_pattern"]
    if len(kinds) != cfg["num_hidden_layers"]:
        raise ValueError("hybrid_layer_pattern must name every layer kept")
    dk = cfg["head_dim"]
    rot = int(dk * cfg["partial_rotary_factor"])

    def inv_freq(theta):
        return 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot)

    if not cfg["norm_topk_prob"]:
        raise ValueError("the chosen experts' weights are normalised "
                         "(norm_topk_prob) in this reference")
    scaling = cfg["routed_scaling_factor"]
    return {
        "sliding": tuple(bool(kind) for kind in kinds),
        "window": cfg["sliding_window"],
        "eps": cfg["layernorm_epsilon"],
        "heads": cfg["num_attention_heads"],
        "kv_heads": {False: cfg["num_key_value_heads"],
                     True: cfg["swa_num_key_value_heads"]},
        "sink": {False: cfg["add_full_attention_sink_bias"],
                 True: cfg["add_swa_attention_sink_bias"]},
        "head_dim": dk,
        "v_head_dim": cfg["v_head_dim"],
        "rotary_dim": rot,
        "inv_freq": {False: inv_freq(cfg["rope_theta"]),
                     True: inv_freq(cfg["swa_rope_theta"])},
        "value_scale": cfg["attention_value_scale"],
        "top_k": cfg["num_experts_per_tok"],
        "route_scale": 1.0 if scaling is None else scaling,
        "offset": cfg["expert_offset"],
        "scale": dk ** -0.5,
    }


def make_logits_fn(cfg: dict, block=None):
    """Jitted ``(params, tokens [S]) -> logits [S, rows held]``."""
    hp = hyper(cfg)

    def fn(params, tokens):
        with jax.default_matmul_precision("highest"):
            return logits_one(params, tokens, hp=hp, block=block)

    return jax.jit(fn)


def make_loss_fn(cfg: dict):
    """Jitted ``(params, tokens [B, S]) -> mean next-token
    cross-entropy`` over the rows held, one sequence at a time."""
    hp = hyper(cfg)

    def one(params, seq):
        logits = logits_one(params, seq[:-1], hp=hp)
        logz = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, seq[1:, None], axis=-1)[:, 0]
        return jnp.sum(logz - picked)

    def fn(params, tokens):
        with jax.default_matmul_precision("highest"):
            sums = jax.lax.map(lambda seq: one(params, seq), tokens)
        b, s = tokens.shape
        return jnp.sum(sums) / (b * (s - 1))

    return jax.jit(fn)
