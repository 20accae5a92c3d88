"""The ``xing4_0`` family for SERVING: latent (MLA) attention over a
paged latent cache, dropless sigmoid-routed experts, and a residual
path ``hc_mult`` streams wide whose streams are mixed round every
sublayer by a doubly-stochastic matrix (manifold-constrained
hyper-connections).

The latent attention, the routed feed-forward, the cache row and the
walk through the layers are ``models/latent.py``'s, shared with the
``pangu_ultra_moe`` family; here are the sizes, the stream mixers and
the family's own ends (:class:`Xing4Serving`). Training is not
supported (no ``__call__``); ``ROADMAP.md`` lists what that would take.

One token carries ``n = hc_mult`` residual streams ``x [n, C]`` in
float32. Before the attention and before the feed-forward of every
layer a mixer (its own parameters each time) reads ``u = rms(vec(x))``
and gives ``Hpre [n]``, ``Hpost [n]`` and ``Hres [n, n]`` (the latter
made doubly stochastic by ``hc_iters`` Sinkhorn iterations); the
sublayer sees ``Hpre @ x`` and ``x <- Hres @ x + outer(Hpost, F(Hpre @
x))``. The embedding is copied into the streams; they are summed
before the final RMSNorm.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .latent import LatentMoE, LatentServing, _rms
from .registry import register

_HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Xing4(LatentMoE):
    """Sizes of one ``xing4_0`` model (defaults: Xing4.0-29B-A4B as
    published). ``num_layers`` counts every layer, the first
    ``first_k_dense`` of them with a dense feed-forward."""

    vocab_size: int = 131072
    max_seq_len: int = 262144
    hidden_size: int = 3584
    num_layers: int = 40
    first_k_dense: int = 2
    num_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mlp_dim: int = 9216
    moe_dim: int = 1024
    n_experts: int = 64
    n_shared_experts: int = 1
    moe_top_k: int = 4
    routed_scale: float = 2.0
    rms_eps: float = 1e-6
    rope_theta: float = 10000.0
    yarn: Optional[Tuple[float, int, float, float, float, float]] = (
        64.0, 4096, 32.0, 1.0, 1.0, 1.0)
    hc_mult: int = 4
    hc_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: Tuple[float, float] = (-30.0, 30.0)

    @property
    def serving_family(self):
        return XING4_SERVING

    def _init(self, key):
        """Matrices in ``dtype``; the router, ``e_bias``, the mixers
        and the norms in float32. ``a_*``, ``b_*`` and ``e_bias`` are
        drawn wide, not zero, so that the Sinkhorn iterations and the
        selection bias change the result."""
        c, n, dt = self.hidden_size, self.hc_mult, self.dtype
        keys = iter(jax.random.split(key, 64 * (self.num_layers + 1)))

        def mat(*shape, std=0.02, dtype=dt):
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    * std).astype(dtype)

        def ones(width):
            return {"scale": jnp.ones((width,), jnp.float32)}

        def uniform(lo, hi, *shape):
            return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

        def mixer():
            f32 = jnp.float32
            return {"phi_pre": mat(n * c, n, dtype=f32),
                    "phi_post": mat(n * c, n, dtype=f32),
                    "phi_res": mat(n * c, n * n, dtype=f32),
                    "a_pre": uniform(0.2, 0.5), "a_post": uniform(0.2, 0.5),
                    "a_res": uniform(0.2, 0.5),
                    "b_pre": mat(n, std=0.5, dtype=f32),
                    "b_post": mat(n, std=0.5, dtype=f32),
                    "b_res": mat(n, n, std=1.0, dtype=f32)}

        params = {"embed": mat(self.vocab_size, c),
                  "head": {"kernel": mat(c, self.vocab_size)},
                  "norm_final": ones(c)}
        for i in range(self.num_layers):
            params[f"layer_{i}"] = {
                "hc_attn": mixer(), "hc_ffn": mixer(),
                "attn_norm": ones(c), "ffn_norm": ones(c),
                **self._sublayer_weights(
                    i, mat, ones,
                    e_bias=lambda: uniform(-0.2, 0.2, self.n_experts))}
        return params


# ------------------------------------------------------------ the mixers

def _mixer(x, p, model):
    """``x [T, n, C]`` float32 -> ``(Hpre [T, n], Hpost [T, n], Hres
    [T, n, n])``, all float32 at the highest matmul precision."""
    t, n, c = x.shape
    u = _rms(x.reshape(t, n * c), None, model.rms_eps)

    def proj(phi):
        return jnp.dot(u, phi, precision=_HIGHEST)

    pre = jax.nn.sigmoid(p["a_pre"] * proj(p["phi_pre"]) + p["b_pre"])
    post = 2.0 * jax.nn.sigmoid(p["a_post"] * proj(p["phi_post"])
                                + p["b_post"])
    r = p["a_res"] * proj(p["phi_res"]).reshape(t, n, n) + p["b_res"]
    m = jnp.exp(jnp.clip(r, model.hc_clamp[0], model.hc_clamp[1]))
    for _ in range(model.hc_iters):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + model.hc_eps)
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + model.hc_eps)
    return pre, post, m


def _mixed(x, p, model, sublayer):
    """``x <- Hres @ x + outer(Hpost, F(Hpre @ x))``. The n-wide
    contractions are multiplies and sums on the vector unit: as a
    matmul they would be T batches of 4 x 4, and a float32 matmul at
    the default precision is a bf16 pass."""
    pre, post, res = _mixer(x, p, model)
    y, aux = sublayer(jnp.sum(pre[:, :, None] * x, axis=1))
    x = (jnp.sum(res[:, :, :, None] * x[:, None, :, :], axis=2)
         + post[:, :, None] * y.astype(jnp.float32)[:, None, :])
    return x, aux


# ---------------------------------------------------- the serving family

class Xing4Serving(LatentServing):
    """The family's own ends of :class:`..latent.LatentServing`."""

    name = "xing4_0"

    def embed(self, model, params, tokens):
        """``tokens [T]`` -> ``[T, n, C]`` float32: the embedding
        copied into every stream."""
        x = params["embed"][tokens].astype(jnp.float32)
        return jnp.broadcast_to(x[:, None, :],
                                (x.shape[0], model.hc_mult, x.shape[1]))

    def residual(self, model, x, layer, which, sublayer):
        """The sublayer, behind its RMSNorm, inside its stream mixer."""
        return _mixed(
            x, layer[f"hc_{which}"], model,
            lambda h: sublayer(_rms(h, layer[f"{which}_norm"]["scale"],
                                    model.rms_eps)))

    def logits(self, model, params, x, cs=None):
        """``x [..., n, C]`` -> ``[..., vocab]`` float32: streams
        summed, final RMSNorm, the untied head without bias."""
        h = _rms(jnp.sum(x, axis=-2), params["norm_final"]["scale"],
                 model.rms_eps)
        return jnp.dot(h.astype(model.dtype),
                       params["head"]["kernel"].astype(model.dtype),
                       preferred_element_type=jnp.float32)


XING4_SERVING = Xing4Serving()


# -------------------------------------------------------------- registry

def Xing4_29B_A4B(**kw) -> Xing4:
    """Xing4.0-29B-A4B at its published sizes; ``num_layers`` and
    ``first_k_dense`` are keywords (a serving stage holds a cut in
    depth), as ``ln_eps`` is for GPT-2."""
    return Xing4(**kw)


def Xing4_Tiny(**kw) -> Xing4:
    """Every mechanism of the family at a size the CPU tests run:
    two heads' worth of everything, 8 experts at top-2, 3 streams."""
    defaults = dict(
        vocab_size=211, max_seq_len=16384, hidden_size=64, num_layers=3,
        first_k_dense=1, num_heads=4, q_lora_rank=24, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        mlp_dim=96, moe_dim=32, n_experts=8, n_shared_experts=1,
        moe_top_k=2, hc_mult=3,
        yarn=(4.0, 64, 32.0, 1.0, 1.0, 1.0))
    defaults.update(kw)
    return Xing4(**defaults)


register("xing4_29b_a4b", lm=True)(Xing4_29B_A4B)
register("xing4_tiny", lm=True)(Xing4_Tiny)
