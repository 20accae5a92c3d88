"""Plain reference for the ``pangu_ultra_moe`` family: forward pass and
next-token loss.

Written from the published ``config.json`` of openPangu-Ultra-MoE-718B
(the DeepSeek-V3 family's keys plus ``sandwich_norm``) and the equations
of ISSUE 33. Straightforward ``jax.numpy`` in float32 at the highest
matmul precision: no kernels, no cache, no absorbed attention, no
sorting of tokens by expert. It imports nothing of the program; only
the weight values come from it.

One token carries ONE residual stream ``x [C]``, with an RMSNorm
(learned gain, ``rms_norm_eps``) before AND after every sublayer:

    x <- x + post_attention_layernorm( MLA( input_layernorm(x) ) )
    x <- x + post_mlp_layernorm(       FFN( pre_mlp_layernorm(x) ) )
    logits = head( final_norm(x) )                  (untied, no bias)

*Latent attention* (DeepSeek-V3's, no YaRN): ``q = Wqb rmsnorm(Wqa h)``
split per head into ``q_nope | q_rope``; ``[c | k_rope] = Wkva h``,
``c <- rmsnorm(c)``; plain rotary (``rope_theta``) on ``q_rope`` and on
the one ``k_rope`` all heads share; ``[k_nope | v] = Wkvb c`` per head;
scores ``(q_nope . k_nope + q_rope . k_rope) * (nope + rope)^-0.5``;
causal softmax; ``Wo concat(P v)``.

*Feed-forward*: the first ``first_k_dense_replace`` layers a gated-SiLU
MLP. Every other layer: ``s = sigmoid(Wg h)`` over ALL
``n_routed_experts`` the router has; the top ``k`` of ``s`` (no
selection bias, no groups); weights ``s[chosen] / (sum of the k chosen
+ 1e-20) * routed_scaling_factor``; output ``shared(h) + sum over the
chosen i that are HELD of w_i down_i(silu(gate_i h) * up_i h)``.

*The share.* The parameter tree holds the experts ``[offset, offset +
held)`` of every expert layer (``w_gate [held, C, F]``), the chip's
share of a stated expert-parallel deployment; the router is whole
(``[C, n_routed_experts]``). The weights are normalised over all ``k``
chosen, held or not, and what the experts held elsewhere would add is
LEFT OUT: that partial sum is this chip's part of the layer and what
goes on to the next layer (the ``model-configs`` guide, section 4).
``held == n_routed_experts`` is the uncut layer. The vocabulary may be
a slice too: ``embed`` and ``head`` have the rows held, and ids,
logits and loss are over them. Every held expert runs over all tokens
and is masked by its own weight: nothing is dropped, nothing sorted.

Weights are read from the program's parameter tree by name:
``embed [V, C]``, ``head/kernel [C, V]``, ``norm_final/scale``,
``layer_<i>/{attn_norm, attn_post_norm, ffn_norm, ffn_post_norm}/scale``
(the published ``input_layernorm``, ``post_attention_layernorm``,
``pre_mlp_layernorm``, ``post_mlp_layernorm``), ``layer_<i>/attn/{wq_a
[C, rq], q_norm/scale, wq_b [rq, H (nope + rope)], wkv_a [C, rkv +
rope], kv_norm/scale, wkv_b [rkv, H (nope + v)], wo [H v, C]}``, and
either ``layer_<i>/mlp/{w_gate, w_up [C, I], w_down [I, C]}`` or
``layer_<i>/moe/{router [C, E], w_gate, w_up [held, C, F], w_down
[held, F, C], shared/{w_gate, w_up, w_down}}``. Rotary pairs are ``(i,
i + rope/2)`` (the half-split layout).

A long sequence's attention is computed in blocks of ``block`` query
rows, and the benchmark calls one SUBLAYER at a time
(:func:`attention_sublayer`, :func:`feed_forward_sublayer`): one
sublayer's weights cast to float32 at a time (attention 0.79 GB at the
published widths) and one expert's at a time inside an expert layer
(0.19 GB), so a 3,072-token stream fits beside the resident bfloat16
weights.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32

# The CONTROL of the benchmark's comparison
# (perf/families/pangu_ultra_moe.py) runs this same forward in a LOWER
# precision: ``hp["round"]`` rounds both operands of every matrix
# product (weights, activations, the softmax's probabilities) and is the
# identity in the reference proper.


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

def latent_attention(h, p, hp, block):
    """Causal latent attention over one sequence ``h [S, C]`` (already
    normed), decompressed: per-head keys and values are built for every
    position. Queries go in blocks of ``block`` rows."""
    s = h.shape[0]
    heads, nope, rope, vd = hp["heads"], hp["nope"], hp["rope"], hp["v"]
    rkv = hp["kv_rank"]
    positions = jnp.arange(s)
    q = mm(rms_norm(mm(h, p["wq_a"].astype(F32), hp), p["q_norm"]["scale"],
                    hp["eps"]), p["wq_b"].astype(F32), hp)
    q = q.reshape(s, heads, nope + rope).transpose(1, 0, 2)   # [H, S, .]
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    kv = mm(h, p["wkv_a"].astype(F32), hp)
    c = rms_norm(kv[:, :rkv], p["kv_norm"]["scale"], hp["eps"])
    k_rope = rotary(kv[:, rkv:], positions, hp["inv_freq"])
    q_rope = rotary(q_rope, positions, hp["inv_freq"])
    kvb = mm(c, p["wkv_b"].astype(F32), hp).reshape(s, heads, nope + vd)
    k_nope = kvb[..., :nope].transpose(1, 0, 2)               # [H, S, nope]
    v = kvb[..., nope:].transpose(1, 0, 2)                    # [H, S, v]

    def rows(start):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, start, block, axis=1)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, start, block, axis=1)
        scores = (mm(qn, k_nope.transpose(0, 2, 1), hp)
                  + mm(qr, k_rope.T[None], hp)) * hp["scale"]
        causal = (jnp.arange(s)[None, :]
                  <= start + jnp.arange(block)[:, None])
        scores = jnp.where(causal[None], scores, -jnp.inf)
        out = mm(jax.nn.softmax(scores, axis=-1), v, hp)      # [H, B, v]
        return out.transpose(1, 0, 2).reshape(block, heads * vd)

    out = jax.lax.map(rows, jnp.arange(0, s, block)).reshape(s, heads * vd)
    return mm(out, p["wo"].astype(F32), hp)


# -------------------------------------------------------- feed-forward

def gated(h, w_gate, w_up, w_down, hp):
    return mm(jax.nn.silu(mm(h, w_gate.astype(F32), hp))
              * mm(h, w_up.astype(F32), hp), w_down.astype(F32), hp)


def route(h, p, hp):
    """``(chosen [S, k], weights [S, k])`` over ALL the router's
    experts: the top ``k`` of the sigmoid scores, normalised over the
    ``k`` chosen and scaled."""
    s = jax.nn.sigmoid(mm(h, p["router"].astype(F32), hp))
    picked, chosen = jax.lax.top_k(s, hp["top_k"])
    weights = (picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
               * hp["routed_scale"])
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
    # [S, E]: a token's weight for each expert, 0 where not chosen
    dense = jnp.sum(jax.nn.one_hot(chosen, n_experts, dtype=F32)
                    * weights[..., None], axis=1)
    mine = dense[:, offset:offset + held]                     # [S, held]

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

def attention_sublayer(layer, x, hp, block):
    """``x [S, C] <- x + post_norm(MLA(pre_norm(x)))``."""
    y = latent_attention(
        rms_norm(x, layer["attn_norm"]["scale"], hp["eps"]),
        layer["attn"], hp, block)
    return x + rms_norm(y, layer["attn_post_norm"]["scale"], hp["eps"])


def feed_forward_sublayer(layer, x, hp):
    """``x [S, C] <- x + post_norm(FFN(pre_norm(x)))``."""
    y = feed_forward(rms_norm(x, layer["ffn_norm"]["scale"], hp["eps"]),
                     layer, hp)
    return x + rms_norm(y, layer["ffn_post_norm"]["scale"], hp["eps"])


def embed(params, tokens):
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
    x = embed(params, tokens)
    for i in range(hp["layers"]):
        layer = params[f"layer_{i}"]
        x = attention_sublayer(layer, x, hp, block)
        x = feed_forward_sublayer(layer, x, hp)
    return head(params, x, hp)


def hyper(cfg: dict) -> dict:
    """What the equations read of a configuration. ``expert_offset``
    says which experts the tree's ``held`` are; their number is the
    weights' own leading dimension, and the router's width the
    router's."""
    rope, nope = cfg["qk_rope_head_dim"], cfg["qk_nope_head_dim"]
    half = jnp.arange(rope // 2, dtype=F32)
    return {
        "layers": cfg["num_hidden_layers"],
        "eps": cfg["rms_norm_eps"],
        "heads": cfg["num_attention_heads"],
        "nope": nope, "rope": rope, "v": cfg["v_head_dim"],
        "kv_rank": cfg["kv_lora_rank"],
        "top_k": cfg["num_experts_per_tok"],
        "routed_scale": cfg["routed_scaling_factor"],
        "offset": cfg["expert_offset"],
        "inv_freq": 1.0 / cfg["rope_theta"] ** (half * 2.0 / rope),
        "scale": (nope + rope) ** -0.5,
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
