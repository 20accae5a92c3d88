"""Plain reference GPT-2: forward pass and next-token loss.

Written from the published description (Radford et al. 2019, "Language
Models are Unsupervised Multitask Learners", and the public GPT-2
``config.json``): token plus learned position embeddings, ``n_layer``
pre-LayerNorm blocks of causal multi-head self-attention and a 4x MLP
with the tanh GELU (``gelu_new``), a final LayerNorm and a linear head
over the vocabulary. Straightforward ``jax.numpy`` in float32 at the
highest matmul precision; no kernels, no cache, no batching tricks.
It shares no code with the program: only the weight values come from
it.

Departure from the published model, the same one the program makes
(``perf/configs/*.json`` state it): the head has its own ``[d, vocab]``
kernel and an optional bias, where GPT-2 ties it to the token table.

Weights are read from the program's parameter tree by name:
``embed [V, d]``, ``pos_embed [P, d]``, ``block_<i>/{ln1, ln2}/{scale,
bias}``, ``block_<i>/attn/{wqkv, wo}/{kernel, bias}`` (``wqkv`` packs
q | k | v along its output axis, heads contiguous inside each),
``block_<i>/{fc1, fc2}/{kernel, bias}``, ``ln_final/{scale, bias}``,
``head/{kernel[, bias]}``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return ((x - mean) / jnp.sqrt(var + eps) * p["scale"].astype(F32)
            + p["bias"].astype(F32))


def linear(x, p):
    y = x @ p["kernel"].astype(F32)
    if "bias" in p:
        y = y + p["bias"].astype(F32)
    return y


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def attention(x, p, n_head):
    """Causal multi-head self-attention over one sequence ``[S, d]``."""
    s, d = x.shape
    dh = d // n_head
    q, k, v = jnp.split(linear(x, p["wqkv"]), 3, axis=-1)
    q, k, v = (t.reshape(s, n_head, dh).transpose(1, 0, 2)
               for t in (q, k, v))                       # [H, S, dh]
    scores = q @ k.transpose(0, 2, 1) / math.sqrt(dh)     # [H, S, S]
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    out = jax.nn.softmax(scores, axis=-1) @ v             # [H, S, dh]
    return linear(out.transpose(1, 0, 2).reshape(s, d), p["wo"])


def logits_one(params, tokens, *, n_layer, n_head, eps):
    """``[S]`` token ids -> ``[S, vocab]`` float32 logits."""
    s = tokens.shape[0]
    x = (params["embed"].astype(F32)[tokens]
         + params["pos_embed"].astype(F32)[:s])
    for i in range(n_layer):
        blk = params[f"block_{i}"]
        x = x + attention(layer_norm(x, blk["ln1"], eps), blk["attn"],
                          n_head)
        h = layer_norm(x, blk["ln2"], eps)
        x = x + linear(gelu_new(linear(h, blk["fc1"])), blk["fc2"])
    return linear(layer_norm(x, params["ln_final"], eps), params["head"])


def hyper(cfg: dict) -> dict:
    return dict(n_layer=cfg["n_layer"], n_head=cfg["n_head"],
                eps=cfg["layer_norm_epsilon"])


def make_logits_fn(cfg: dict):
    """Jitted ``(params, tokens [S]) -> logits [S, vocab]``."""
    kw = hyper(cfg)

    def fn(params, tokens):
        with jax.default_matmul_precision("highest"):
            return logits_one(params, tokens, **kw)

    return jax.jit(fn)


def make_loss_fn(cfg: dict):
    """Jitted ``(params, tokens [B, S]) -> mean next-token
    cross-entropy`` over all ``B x (S - 1)`` predictions, one sequence
    at a time (``lax.map``) so the ``[S, vocab]`` logits of a single
    sequence are all that is ever live."""
    kw = hyper(cfg)

    def one(params, seq):
        logits = logits_one(params, seq[:-1], **kw)
        logz = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, seq[1:, None], axis=-1)[:, 0]
        return jnp.sum(logz - picked)

    def fn(params, tokens):
        with jax.default_matmul_precision("highest"):
            sums = jax.lax.map(lambda seq: one(params, seq), tokens)
        b, s = tokens.shape
        return jnp.sum(sums) / (b * (s - 1))

    return jax.jit(fn)
