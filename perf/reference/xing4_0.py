"""Plain reference for the ``xing4_0`` family: forward pass and
next-token loss.

Written from the published ``config.json`` of Xing4.0-29B-A4B (the
DeepSeek-V3 family's keys plus the ``hc_*`` keys of manifold-constrained
hyper-connections) and the equations of ISSUE 29. Straightforward
``jax.numpy`` in float32 at the highest matmul precision: no kernels,
no cache, no absorbed attention, no sorting of tokens by expert. It
imports nothing of the program; only the weight values come from it.

One token carries ``n = hc_mult`` residual streams ``x [n, C]``.

*Stream mixer* (before the attention and before the feed-forward of
every layer, own parameters each time): ``u = rms(vec(x))`` over all
``n C`` values (no learned gain); ``Hpre = sigmoid(a_pre (u phi_pre) +
b_pre)`` ``[n]``; ``Hpost = 2 sigmoid(a_post (u phi_post) + b_post)``
``[n]``; ``R = clip(a_res mat(u phi_res) + b_res, lo, hi)`` ``[n, n]``;
``M = exp(R)``, then ``hc_sinkhorn_iters`` times: every column divided
by (its sum + ``hc_eps``), then every row by (its sum + ``hc_eps``);
``Hres = M``. The sublayer ``F`` (its RMSNorm included) sees ``Hpre @
x`` and ``x <- Hres @ x + outer(Hpost, F(Hpre @ x))``. The embedding is
copied into the n streams; the streams are summed before the final
RMSNorm.

*Latent attention*: ``q = Wqb rmsnorm(Wqa h)`` split per head into
``q_nope | q_rope``; ``[c | k_rope] = Wkva h``, ``c <- rmsnorm(c)``;
rotary (YaRN) on ``q_rope`` and on the one ``k_rope`` all heads share;
``[k_nope | v] = Wkvb c`` per head; scores ``(q_nope . k_nope + q_rope .
k_rope) * (nope + rope)^-0.5 * m^2``, ``m = 0.1 mscale_all_dim ln(factor)
+ 1``; causal softmax; ``Wo concat(P v)``.

*Experts*: ``s = sigmoid(Wg h)``; the top ``k`` of ``s + e_bias``;
weights ``s[chosen] / (sum + 1e-20) * routed_scaling_factor``; output
``sum_i w_i down_i(silu(gate_i h) * up_i h) + shared(h)``. Every expert
runs over all tokens and is masked (or, at the benchmark's lengths,
over its own tokens in rounds of a fixed number of rows): nothing is
dropped, nothing sorted.
The first ``first_k_dense_replace`` layers have a dense gated-SiLU
feed-forward instead.

Weights are read from the program's parameter tree by name:
``embed [V, C]``, ``head/kernel [C, V]``, ``norm_final/scale``,
``layer_<i>/{hc_attn, hc_ffn}/{phi_pre [nC, n], phi_post [nC, n],
phi_res [nC, n n], a_pre, a_post, a_res, b_pre [n], b_post [n], b_res
[n, n]}``, ``layer_<i>/{attn_norm, ffn_norm}/scale``,
``layer_<i>/attn/{wq_a [C, rq], q_norm/scale, wq_b [rq, H (nope +
rope)], wkv_a [C, rkv + rope], kv_norm/scale, wkv_b [rkv, H (nope +
v)], wo [H v, C]}``, and either ``layer_<i>/mlp/{w_gate, w_up [C, I],
w_down [I, C]}`` or ``layer_<i>/moe/{router [C, E], e_bias [E], w_gate,
w_up [E, C, F], w_down [E, F, C], shared/{w_gate, w_up, w_down}}``.
Rotary pairs are ``(i, i + rope/2)`` (the half-split layout).

A long sequence's attention is computed in blocks of ``block`` query
rows, one layer's weights cast to float32 at a time and one expert's
at a time inside an expert layer (each expert over all rows at once),
so a 6,400-token stream at the published widths fits beside the
resident bfloat16 weights.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32

# The CONTROL of the benchmark's comparison (perf/families/xing4_0.py)
# runs this same forward in a LOWER precision: ``hp["round"]`` rounds
# both operands of every matrix product (weights, activations, the
# softmax's probabilities) and is the identity in the reference proper.


def mm(a, b, hp):
    r = hp.get("round")
    return a @ b if r is None else r(a) @ r(b)


def rms_norm(x, scale, eps):
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                          + eps)
    return y if scale is None else y * scale.astype(F32)


# ----------------------------------------------------------- positions

def yarn_inv_freq(dim, theta, scaling):
    """The DeepSeek-V3 blend: original frequencies where a dimension
    turns more than ``beta_fast`` times over the original length,
    interpolated ones (divided by ``factor``) where it turns fewer than
    ``beta_slow`` times, a linear ramp between."""
    half = dim // 2
    freq = 1.0 / theta ** (jnp.arange(half, dtype=F32) * 2.0 / dim)
    if not scaling:
        return freq
    orig = scaling["original_max_position_embeddings"]

    def correction_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=F32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp                  # 1 = original frequency
    return freq / scaling["factor"] * (1.0 - keep) + freq * keep


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rotary(x, positions, inv_freq, cos_sin_scale):
    """``x [..., S, dim]`` at ``positions [S]``; pairs ``(i, i + dim/2)``."""
    angle = positions.astype(F32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(angle) * cos_sin_scale
    sin = jnp.sin(angle) * cos_sin_scale
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


# --------------------------------------------------------------- mixer

def stream_mix(x, p, *, iters, hc_eps, clamp, norm_eps, hp=None):
    """``x [S, n, C]`` -> ``(Hpre [S, n], Hpost [S, n], Hres [S, n, n])``."""
    s, n, c = x.shape
    hp = hp or {}
    u = rms_norm(x.reshape(s, n * c), None, norm_eps)
    pre = jax.nn.sigmoid(
        p["a_pre"].astype(F32) * mm(u, p["phi_pre"].astype(F32), hp)
        + p["b_pre"].astype(F32))
    post = 2.0 * jax.nn.sigmoid(
        p["a_post"].astype(F32) * mm(u, p["phi_post"].astype(F32), hp)
        + p["b_post"].astype(F32))
    r = (p["a_res"].astype(F32)
         * mm(u, p["phi_res"].astype(F32), hp).reshape(s, n, n)
         + p["b_res"].astype(F32))
    m = jnp.exp(jnp.clip(r, clamp[0], clamp[1]))
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + hc_eps)   # columns
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + hc_eps)   # rows
    return pre, post, m


def mixed(x, p, sublayer, hp):
    """``x <- Hres @ x + outer(Hpost, F(Hpre @ x))`` over ``[S, n, C]``."""
    pre, post, res = stream_mix(
        x, p, iters=hp["hc_iters"], hc_eps=hp["hc_eps"],
        clamp=hp["hc_clamp"], norm_eps=hp["eps"], hp=hp)
    y = sublayer(jnp.einsum("sn,snc->sc", pre, x))
    return (jnp.einsum("snm,smc->snc", res, x)
            + post[:, :, None] * y[:, None, :])


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
    k_rope = rotary(kv[:, rkv:], positions, hp["inv_freq"], hp["cos_scale"])
    q_rope = rotary(q_rope, positions, hp["inv_freq"], hp["cos_scale"])
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
    """``(chosen [S, k], weights [S, k])``: selection by ``s + e_bias``,
    weights from ``s`` alone."""
    s = jax.nn.sigmoid(mm(h, p["router"].astype(F32), hp))
    _, chosen = jax.lax.top_k(s + p["e_bias"].astype(F32), hp["top_k"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    weights = (picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
               * hp["routed_scale"])
    return chosen, weights


def experts(h, p, hp):
    """Every expert over ALL tokens of ``h [S, C]``, masked by its own
    weight (0 for a token that did not choose it), one expert's weights
    cast at a time; plus the shared expert."""
    chosen, weights = route(h, p, hp)
    n_experts = p["router"].shape[-1]
    # [S, E]: a token's weight for each expert, 0 where not chosen
    dense = jnp.sum(jax.nn.one_hot(chosen, n_experts, dtype=F32)
                    * weights[..., None], axis=1)

    def one(acc, item):
        w_gate, w_up, w_down, weight = item
        return (acc + weight[:, None] * gated(h, w_gate, w_up, w_down, hp),
                None)

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (p["w_gate"], p["w_up"], p["w_down"], dense.T))
    sh = p["shared"]
    return out + gated(h, sh["w_gate"], sh["w_up"], sh["w_down"], hp)


def experts_own_rows(h, p, hp, cap):
    """The same sum with every expert over ITS OWN tokens only, for
    sequences at which the masked loop costs sixteen times the chosen
    experts' work. Static shapes and still nothing dropped: an expert
    takes its tokens ``cap`` at a time, in rounds, until the busiest
    expert's last token is done (a token's rank among its expert's
    tokens, divided by ``cap``, is its round)."""
    chosen, weights = route(h, p, hp)
    s, n_experts = h.shape[0], p["router"].shape[-1]
    dense = jnp.sum(jax.nn.one_hot(chosen, n_experts, dtype=F32)
                    * weights[..., None], axis=1)             # [S, E]
    mine = jnp.sum(jax.nn.one_hot(chosen, n_experts, dtype=jnp.int32),
                   axis=1) > 0                                # [S, E]
    rank = jnp.cumsum(mine, axis=0) - 1
    rounds = jnp.max(jnp.sum(mine, axis=0) + cap - 1) // cap
    h_pad = jnp.concatenate([h, jnp.zeros_like(h[:1])])       # row S: 0

    def one_round(state):
        r, acc = state

        def one(acc, item):
            w_gate, w_up, w_down, weight, take = item
            rows = jnp.nonzero(take, size=cap, fill_value=s)[0]
            out = gated(h_pad[rows], w_gate, w_up, w_down, hp)
            scale = jnp.concatenate([weight, jnp.zeros((1,), F32)])[rows]
            return acc.at[rows].add(out * scale[:, None], mode="drop"), None

        takes = jnp.logical_and(mine, rank // cap == r).T     # [E, S]
        acc, _ = jax.lax.scan(
            one, acc, (p["w_gate"], p["w_up"], p["w_down"], dense.T, takes))
        return r + 1, acc

    _, out = jax.lax.while_loop(lambda state: state[0] < rounds, one_round,
                                (jnp.int32(0), jnp.zeros_like(h)))
    sh = p["shared"]
    return out + gated(h, sh["w_gate"], sh["w_up"], sh["w_down"], hp)


def feed_forward(h, layer, hp):
    """The layer's feed-forward over every row of ``h [S, C]``;
    ``hp["expert_rows"]`` (optional) chooses :func:`experts_own_rows`
    with that many rows an expert and round."""
    if "moe" in layer:
        if hp.get("expert_rows"):
            return experts_own_rows(h, layer["moe"], hp, hp["expert_rows"])
        return experts(h, layer["moe"], hp)
    m = layer["mlp"]
    return gated(h, m["w_gate"], m["w_up"], m["w_down"], hp)


# --------------------------------------------------------------- model

def layer_forward(layer, x, hp, block):
    """One layer over ``x [S, n, C]``."""
    x = mixed(x, layer["hc_attn"],
              lambda h: latent_attention(
                  rms_norm(h, layer["attn_norm"]["scale"], hp["eps"]),
                  layer["attn"], hp, block), hp)
    return mixed(x, layer["hc_ffn"],
                 lambda h: feed_forward(
                     rms_norm(h, layer["ffn_norm"]["scale"], hp["eps"]),
                     layer, hp), hp)


def embed(params, tokens, hp):
    x = params["embed"][tokens].astype(F32)
    return jnp.broadcast_to(x[:, None, :], (x.shape[0], hp["n"], x.shape[1]))


def head(params, x, hp):
    """``x [S, n, C]`` -> ``[S, vocab]``: streams summed, final RMSNorm,
    the untied head without bias."""
    h = rms_norm(jnp.sum(x, axis=1), params["norm_final"]["scale"],
                 hp["eps"])
    return mm(h, params["head"]["kernel"].astype(F32), hp)


def logits_one(params, tokens, *, hp, block=None):
    """``[S]`` token ids -> ``[S, vocab]`` float32 logits. ``S`` must
    be a multiple of ``block`` (default: the whole sequence)."""
    block = block or tokens.shape[0]
    x = embed(params, tokens, hp)
    for i in range(hp["layers"]):
        x = layer_forward(params[f"layer_{i}"], x, hp, block)
    return head(params, x, hp)


def hyper(cfg: dict) -> dict:
    scaling = cfg.get("rope_scaling") or {}
    rope = cfg["qk_rope_head_dim"]
    nope = cfg["qk_nope_head_dim"]
    factor = scaling.get("factor", 1)
    m_all = yarn_mscale(factor, scaling.get("mscale_all_dim", 0))
    return {
        "layers": cfg["num_hidden_layers"],
        "n": cfg["hc_mult"],
        "hc_iters": cfg["hc_sinkhorn_iters"],
        "hc_eps": cfg["hc_eps"],
        "hc_clamp": (cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"]),
        "eps": cfg["rms_norm_eps"],
        "heads": cfg["num_attention_heads"],
        "nope": nope, "rope": rope, "v": cfg["v_head_dim"],
        "kv_rank": cfg["kv_lora_rank"],
        "top_k": cfg["num_experts_per_tok"],
        "routed_scale": cfg["routed_scaling_factor"],
        "inv_freq": yarn_inv_freq(rope, cfg["rope_theta"], scaling),
        # the factor on cos and sin: mscale's m over mscale_all_dim's
        "cos_scale": yarn_mscale(factor, scaling.get("mscale", 1)) / m_all,
        "scale": (nope + rope) ** -0.5 * m_all * m_all,
    }


def make_logits_fn(cfg: dict, block=None):
    """Jitted ``(params, tokens [S]) -> logits [S, vocab]``."""
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
