"""Plain reference for the ``lfm2_moe`` family (LiquidAI LFM2-8B-A1B):
forward pass and next-token loss.

Written from the published ``config.json`` of LFM2-8B-A1B and the
equations below. Straightforward ``jax.numpy`` in float32 at the
highest matmul precision: no kernels, no cache, no pages, no ring, no
sorting of tokens by expert; the convolution is a sum of shifted rows
over the whole sequence. It imports nothing of the program; only the
weight values come from it. The matrix product, the norm, the rotation
and the gated MLP are the ``afmoe`` reference's
(``perf/reference/afmoe.py``).

    x0 = E[token]                                          (no scaling)
    x <- x + Mixer_l( operator_norm(x) )
    x <- x + FFN_l(   ffn_norm(x) )
    logits = norm(x) E^T                      (the head tied to E)

*Conv layer* (``layer_types[l] == "conv"``): ``[B | Cg | X] = h W_in``
(``W_in [C, 3C]``, no bias; B first, then Cg, then X), ``u = B * X``,
``z_t = w0 u_{t-2} + w1 u_{t-1} + w2 u_t`` per channel with ``u_j = 0``
for ``j < 0`` (a depthwise causal conv of width ``conv_L_cache`` = 3,
zero left-padding), output ``(Cg * z) W_out``.

*Attention layer* (``"full_attention"``): ``q = h Wq`` as ``H`` heads
of ``D = hidden / H``, ``k``, ``v`` as ``Hkv`` heads of ``D``; q and k
RMS-normed per head (their own gains) BEFORE the rotation; rotary over
all ``D`` dimensions at ``rope_theta`` (pairs ``(i, i + D / 2)``);
query head ``t`` attends key/value head ``t // (H / Hkv)`` over
columns ``j <= i``, scores scaled by ``D ^ -0.5``; the heads' outputs go
into ``Wo``.

*Feed-forward*: a gated-SiLU MLP (``w1, w3 -> w2``) in the first
``num_dense_layers`` layers. Elsewhere ``s = sigmoid(h Wr)`` over the
``num_experts``; CHOSEN = the top ``num_experts_per_tok`` of ``s +
expert_bias`` (``use_expert_bias``: the bias moves the choice only);
weights ``s[CHOSEN] / (their sum + eps)`` (``norm_topk_prob``; ``eps``
the published modelling code's 1e-6) times ``routed_scaling_factor``;
output the weighted sum of the chosen experts' gated MLPs. No shared
expert.

Weights are read from the program's tree by name: ``embed [V, C]``,
``norm_final/scale``, ``layer_<i>/{attn_norm, ffn_norm}/scale``;
``layer_<i>/conv/{w_in [C, 3C], taps [3, C], w_out [C, C]}`` or
``layer_<i>/attn/{wq [C, H D], wk, wv [C, Hkv D], wo [H D, C],
q_norm/scale, k_norm/scale [D]}``; ``layer_<i>/mlp/{w_gate, w_up [C,
I], w_down [I, C]}`` or ``layer_<i>/moe/{router [C, E], e_bias [E],
w_gate, w_up [E, C, F], w_down [E, F, C]}``. The benchmark calls one
SUBLAYER at a time (:func:`mixer_sublayer`,
:func:`feed_forward_sublayer`), a long sequence's attention in blocks of
``block`` query rows.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .afmoe import gated, mm, rms_norm, rotary

F32 = jnp.float32
# the epsilon under the chosen weights' sum: the published modelling
# code's (the configuration has no key for it; its ``assumed`` says so)
ROUTE_EPS = 1e-6


# -------------------------------------------------------------- mixers

def short_conv(h, p, hp):
    """The gated short convolution over one sequence ``h [S, C]``
    (already normed)."""
    b, cg, x = jnp.split(mm(h, p["w_in"].astype(F32), hp), 3, axis=-1)
    u = b * x
    taps = p["taps"].astype(F32)
    width = taps.shape[0]
    z = jnp.zeros_like(u)
    for k in range(width):          # tap k multiplies u_{t - (width-1-k)}
        shift = width - 1 - k
        shifted = jnp.pad(u, ((shift, 0), (0, 0)))[:u.shape[0]]
        z = z + taps[k] * shifted
    return mm(cg * z, p["w_out"].astype(F32), hp)


def grouped_attention(h, p, hp, block):
    """Causal grouped-query attention over one sequence ``h [S, C]``
    (already normed), queries in blocks of ``block`` rows against every
    key."""
    s = h.shape[0]
    heads, kv_heads, dim = hp["heads"], hp["kv_heads"], hp["head_dim"]
    group = heads // kv_heads
    positions = jnp.arange(s)

    def split(y, n):                                  # [S, n D] -> [n, S, D]
        return y.reshape(s, n, dim).transpose(1, 0, 2)

    q = rotary(rms_norm(split(mm(h, p["wq"].astype(F32), hp), heads),
                        p["q_norm"]["scale"], hp["eps"]),
               positions, hp["inv_freq"])
    k = rotary(rms_norm(split(mm(h, p["wk"].astype(F32), hp), kv_heads),
                        p["k_norm"]["scale"], hp["eps"]),
               positions, hp["inv_freq"])
    v = split(mm(h, p["wv"].astype(F32), hp), kv_heads)
    q = q.reshape(kv_heads, group, s, dim)

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=2)
        scores = mm(qb, k.transpose(0, 2, 1)[:, None], hp) * hp["scale"]
        i = start + jnp.arange(block)[:, None]
        j = jnp.arange(s)[None, :]
        scores = jnp.where((j <= i)[None, None], scores, -jnp.inf)
        out = mm(jax.nn.softmax(scores, axis=-1), v[:, None], hp)
        return out.reshape(heads, block, dim).transpose(1, 0, 2).reshape(
            block, heads * dim)                                # [B, H D]

    out = jax.lax.map(rows, jnp.arange(0, s, block)).reshape(
        s, heads * dim)
    return mm(out, p["wo"].astype(F32), hp)


# -------------------------------------------------------- feed-forward

def route(h, p, hp):
    """``(chosen [S, k], weights [S, k])``: sigmoid scores, the top-k of
    score + bias chosen, weights from the scores alone, normalised with
    the family's epsilon and scaled."""
    scores = jax.nn.sigmoid(h @ p["router"].astype(F32))
    _, chosen = jax.lax.top_k(scores + p["e_bias"].astype(F32), hp["top_k"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, (picked / (jnp.sum(picked, axis=-1, keepdims=True)
                              + hp["route_eps"]) * hp["route_scale"])


def experts(h, p, hp):
    """The expert layer over ``h [S, C]``: every expert over all tokens,
    masked by its own weight (0 for a token that did not choose it), one
    expert's weights cast at a time."""
    chosen, weights = route(h, p, hp)
    n_experts = p["router"].shape[-1]
    dense = jnp.sum(jax.nn.one_hot(chosen, n_experts, dtype=F32)
                    * weights[..., None], axis=1)              # [S, E]

    def one(acc, item):
        w_gate, w_up, w_down, weight = item
        return (acc + weight[:, None] * gated(h, w_gate, w_up, w_down, hp),
                None)

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (p["w_gate"], p["w_up"], p["w_down"], dense.T))
    return out


def feed_forward(h, layer, hp):
    if "moe" in layer:
        return experts(h, layer["moe"], hp)
    m = layer["mlp"]
    return gated(h, m["w_gate"], m["w_up"], m["w_down"], hp)


# --------------------------------------------------------------- model

def mixer_sublayer(layer, x, hp, block, conv):
    """``x [S, C] <- x + Mixer(norm(x))``: the short conv or attention."""
    h = rms_norm(x, layer["attn_norm"]["scale"], hp["eps"])
    if conv:
        return x + short_conv(h, layer["conv"], hp)
    return x + grouped_attention(h, layer["attn"], hp, block)


def feed_forward_sublayer(layer, x, hp):
    """``x [S, C] <- x + FFN(norm(x))``."""
    return x + feed_forward(
        rms_norm(x, layer["ffn_norm"]["scale"], hp["eps"]), layer, hp)


def embed(params, tokens, hp):
    return params["embed"][tokens].astype(F32)


def head(params, x, hp):
    """``x [S, C]`` -> ``[S, V]``: final RMSNorm, then the embedding's
    transpose (the tied head)."""
    h = rms_norm(x, params["norm_final"]["scale"], hp["eps"])
    return mm(h, params["embed"].astype(F32).T, hp)


def logits_one(params, tokens, *, hp, block=None):
    """``[S]`` token ids -> ``[S, V]`` float32 logits. ``S`` must be a
    multiple of ``block`` (default: the whole sequence)."""
    block = block or tokens.shape[0]
    x = embed(params, tokens, hp)
    for i, conv in enumerate(hp["conv"]):
        layer = params[f"layer_{i}"]
        x = mixer_sublayer(layer, x, hp, block, conv)
        x = feed_forward_sublayer(layer, x, hp)
    return head(params, x, hp)


def hyper(cfg: dict) -> dict:
    """What the equations read of a configuration."""
    kinds = list(cfg["layer_types"])
    if len(kinds) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types must name every layer kept")
    if set(kinds) - {"conv", "full_attention"}:
        raise ValueError(f"unknown layer kinds in {kinds}")
    if not cfg["norm_topk_prob"]:
        raise ValueError("the chosen experts' weights are normalised "
                         "(norm_topk_prob) in this reference")
    heads = cfg["num_attention_heads"]
    dim = cfg["hidden_size"] // heads
    return {
        "conv": tuple(kind == "conv" for kind in kinds),
        "eps": cfg["norm_eps"],
        "heads": heads,
        "kv_heads": cfg["num_key_value_heads"],
        "head_dim": dim,
        "inv_freq": 1.0 / cfg["rope_theta"] ** (
            jnp.arange(0, dim, 2, dtype=F32) / dim),
        "top_k": cfg["num_experts_per_tok"],
        "route_scale": float(cfg["routed_scaling_factor"]),
        "route_eps": ROUTE_EPS,
        "scale": dim ** -0.5,
    }


def make_logits_fn(cfg: dict, block=None):
    """Jitted ``(params, tokens [S]) -> logits [S, V]``."""
    hp = hyper(cfg)

    def fn(params, tokens):
        with jax.default_matmul_precision("highest"):
            return logits_one(params, tokens, hp=hp, block=block)

    return jax.jit(fn)


def make_loss_fn(cfg: dict):
    """Jitted ``(params, tokens [B, S]) -> mean next-token
    cross-entropy``, one sequence at a time."""
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
