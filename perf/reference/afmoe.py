"""Plain reference for the ``afmoe`` family (Arcee Trinity): forward
pass and next-token loss.

Written from the published ``config.json`` of Trinity-Large-Preview and
the equations of ISSUE 36. Straightforward ``jax.numpy`` in float32 at
the highest matmul precision: no kernels, no cache, no pages, no window
cut out of the keys (a sliding layer is a full score matrix under a
band mask), no sorting of tokens by expert. It imports nothing of the
program; only the weight values come from it.

    x0 = embed[token] * sqrt(hidden_size)                  (mup_enabled)
    x <- x + post_attention_layernorm( Attn_l( input_layernorm(x) ) )
    x <- x + post_mlp_layernorm(       FFN_l(  pre_mlp_layernorm(x) ) )
    logits = head( norm(x) )                        (untied, no bias)

*Attention*, layer ``l`` of kind ``layer_types[l]``: ``q = h Wq`` as
``H`` heads of ``head_dim``, ``k = h Wk`` and ``v = h Wv`` as ``Hkv``
heads, ``g = h Wg`` as ``H`` heads; ``q`` and ``k`` pass an RMSNorm over
``head_dim`` with a learned gain (one gain vector for all heads), THEN,
on a ``sliding_attention`` layer only, the rotary embedding over all
``head_dim`` dimensions (``rope_theta``, pairs ``(i, i + head_dim /
2)``); a ``full_attention`` layer has no position encoding at all.
Query head ``t`` attends key/value head ``t // (H / Hkv)`` over columns
``j <= i`` (full) or ``i - sliding_window < j <= i`` (sliding: that
many keys, the token's own included), scores scaled by ``head_dim ^
-0.5``; the heads' outputs are multiplied elementwise by ``sigmoid(g)``
before ``Wo``.

*Feed-forward*: the first ``num_dense_layers`` layers a gated-SiLU MLP.
Every other layer: ``s = sigmoid(h Wr)`` over ALL experts the router
has; CHOSEN = the top ``num_experts_per_tok`` of ``s + expert_bias``
(the bias moves the selection only); weights ``s[CHOSEN] / (their sum
+ 1e-20) * route_scale``; output ``shared(h) + sum over the CHOSEN that
are HELD of w_e expert_e(h)``.

*The share.* The parameter tree holds the experts ``[expert_offset,
expert_offset + held)`` of every expert layer (``w_gate [held, C,
F]``), one chip's share of a stated expert-parallel deployment; the
router and the bias are whole. The weights are normalised over all
chosen, held or not, and what the experts held elsewhere would add is
LEFT OUT. ``held == num_experts`` is the uncut layer. The vocabulary
may be a slice: ``embed`` and ``head`` have the rows held.

Weights are read from the program's tree by name: ``embed [V, C]``,
``head/kernel [C, V]``, ``norm_final/scale``, ``layer_<i>/{attn_norm,
attn_post_norm, ffn_norm, ffn_post_norm}/scale`` (the published
``input_layernorm``, ``post_attention_layernorm``,
``pre_mlp_layernorm``, ``post_mlp_layernorm``), ``layer_<i>/attn/{wq
[C, H D], wk [C, Hkv D], wv [C, Hkv D], wg [C, H D], q_norm/scale [D],
k_norm/scale [D], wo [H D, C]}``, and either ``layer_<i>/mlp/{w_gate,
w_up [C, I], w_down [I, C]}`` or ``layer_<i>/moe/{router [C, E], e_bias
[E], w_gate, w_up [held, C, F], w_down [held, F, C], shared/{w_gate,
w_up, w_down}}``.

A long sequence's attention is computed in blocks of ``block`` query
rows, and the benchmark calls one SUBLAYER at a time
(:func:`attention_sublayer`, :func:`feed_forward_sublayer`): one
sublayer's weights cast to float32 at a time and one expert's at a time
inside an expert layer, so a 9,216-token stream fits beside the
resident bfloat16 weights.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32

# The CONTROL of the benchmark's comparison (perf/families/afmoe.py)
# runs this same forward in a LOWER precision: ``hp["round"]`` rounds
# both operands of every matrix product (weights, activations, the
# softmax's probabilities) and is the identity in the reference proper.


def mm(a, b, hp):
    r = hp.get("round")
    return a @ b if r is None else r(a) @ r(b)


def rms_norm(x, scale, eps):
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                          + eps)
    return y * scale.astype(F32)


def rotary(x, positions, inv_freq):
    """``x [..., S, dim]`` at ``positions [S]``; pairs ``(i, i + dim/2)``."""
    angle = positions.astype(F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


# ----------------------------------------------------------- attention

def grouped_attention(h, p, hp, block, sliding):
    """Causal grouped-query attention with the output gate over one
    sequence ``h [S, C]`` (already normed); ``sliding``: the band of
    ``hp["window"]`` keys and the rotary embedding. Queries go in
    blocks of ``block`` rows against every key."""
    s = h.shape[0]
    heads, kv_heads, dim = hp["heads"], hp["kv_heads"], hp["head_dim"]
    group = heads // kv_heads
    positions = jnp.arange(s)

    def split(y, n):                                  # [S, n D] -> [n, S, D]
        return y.reshape(s, n, dim).transpose(1, 0, 2)

    q = rms_norm(split(mm(h, p["wq"].astype(F32), hp), heads),
                 p["q_norm"]["scale"], hp["eps"])
    k = rms_norm(split(mm(h, p["wk"].astype(F32), hp), kv_heads),
                 p["k_norm"]["scale"], hp["eps"])
    v = split(mm(h, p["wv"].astype(F32), hp), kv_heads)
    if sliding:
        q = rotary(q, positions, hp["inv_freq"])
        k = rotary(k, positions, hp["inv_freq"])
    gate = jax.nn.sigmoid(mm(h, p["wg"].astype(F32), hp))     # [S, H D]
    q = q.reshape(kv_heads, group, s, dim)

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=2)
        scores = mm(qb, k.transpose(0, 2, 1)[:, None], hp) * hp["scale"]
        i = start + jnp.arange(block)[:, None]
        j = jnp.arange(s)[None, :]
        seen = j <= i
        if sliding:
            seen = jnp.logical_and(seen, i - j < hp["window"])
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        out = mm(jax.nn.softmax(scores, axis=-1), v[:, None], hp)
        return out.reshape(heads, block, dim).transpose(1, 0, 2).reshape(
            block, heads * dim)                                # [B, H D]

    out = jax.lax.map(rows, jnp.arange(0, s, block)).reshape(
        s, heads * dim)
    return mm(out * gate, p["wo"].astype(F32), hp)


# -------------------------------------------------------- feed-forward

def gated(h, w_gate, w_up, w_down, hp):
    return mm(jax.nn.silu(mm(h, w_gate.astype(F32), hp))
              * mm(h, w_up.astype(F32), hp), w_down.astype(F32), hp)


def route(h, p, hp):
    """``(chosen [S, k], weights [S, k])`` over ALL the router's
    experts: the top ``k`` of ``sigmoid score + expert_bias``; the
    weights are the chosen SCORES (no bias) over their sum, scaled."""
    s = jax.nn.sigmoid(mm(h, p["router"].astype(F32), hp))
    _, chosen = jax.lax.top_k(s + p["e_bias"].astype(F32), hp["top_k"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    weights = (picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
               * hp["route_scale"])
    return chosen, weights


def experts(h, p, hp):
    """The share's part of the expert layer over ``h [S, C]``: every
    HELD expert over all tokens, masked by its own weight (0 for a
    token that did not choose it), one expert's weights cast at a time;
    plus the shared expert. A choice of an expert outside ``[offset,
    offset + held)`` adds nothing here."""
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
    sh = p["shared"]
    return out + gated(h, sh["w_gate"], sh["w_up"], sh["w_down"], hp)


def feed_forward(h, layer, hp):
    if "moe" in layer:
        return experts(h, layer["moe"], hp)
    m = layer["mlp"]
    return gated(h, m["w_gate"], m["w_up"], m["w_down"], hp)


# --------------------------------------------------------------- model

def attention_sublayer(layer, x, hp, block, sliding):
    """``x [S, C] <- x + post_norm(Attn(pre_norm(x)))``."""
    y = grouped_attention(
        rms_norm(x, layer["attn_norm"]["scale"], hp["eps"]),
        layer["attn"], hp, block, sliding)
    return x + rms_norm(y, layer["attn_post_norm"]["scale"], hp["eps"])


def feed_forward_sublayer(layer, x, hp):
    """``x [S, C] <- x + post_norm(FFN(pre_norm(x)))``."""
    y = feed_forward(rms_norm(x, layer["ffn_norm"]["scale"], hp["eps"]),
                     layer, hp)
    return x + rms_norm(y, layer["ffn_post_norm"]["scale"], hp["eps"])


def embed(params, tokens, hp):
    return params["embed"][tokens].astype(F32) * hp["embed_scale"]


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
    dim = cfg["head_dim"]
    half = jnp.arange(dim // 2, dtype=F32)
    kinds = cfg["layer_types"]
    if len(kinds) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types must name every layer kept")
    return {
        "sliding": tuple(kind == "sliding_attention" for kind in kinds),
        "window": cfg["sliding_window"],
        "eps": cfg["rms_norm_eps"],
        "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"],
        "head_dim": dim,
        "top_k": cfg["num_experts_per_tok"],
        "route_scale": cfg["route_scale"] if cfg["route_norm"] else 1.0,
        "offset": cfg["expert_offset"],
        "inv_freq": 1.0 / cfg["rope_theta"] ** (half * 2.0 / dim),
        "scale": dim ** -0.5,
        "embed_scale": (math.sqrt(cfg["hidden_size"])
                        if cfg["mup_enabled"] else 1.0),
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
