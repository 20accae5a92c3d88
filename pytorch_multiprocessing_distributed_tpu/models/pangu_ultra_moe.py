"""The ``pangu_ultra_moe`` family for SERVING (openPangu-Ultra-MoE):
latent (MLA) attention without YaRN over a paged latent cache, dropless
sigmoid-routed experts of which a chip may hold a share, and ONE
residual stream with a norm before AND after every sublayer (the
config's ``sandwich_norm``):

    x <- x + attn_post_norm( MLA( attn_norm(x) ) )
    x <- x + ffn_post_norm(  FFN( ffn_norm(x) ) )
    logits = head( norm_final(x) )

(the published names: ``input_layernorm``, ``post_attention_layernorm``,
``pre_mlp_layernorm``, ``post_mlp_layernorm``). The latent attention,
the routed feed-forward, the cache row and the walk through the layers
are ``models/latent.py``'s, shared with the ``xing4_0`` family; here
are the sizes, the sandwich and the family's own ends
(:class:`PanguUltraMoEServing`). Routing scores every one of
``n_experts`` with a sigmoid and takes the top ``moe_top_k`` with no
selection bias and no groups. Training is not supported; the
multi-token prediction module of the published model is not loaded.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .latent import LatentMoE, LatentServing, _rms
from .registry import register


@dataclasses.dataclass(frozen=True)
class PanguUltraMoE(LatentMoE):
    """Sizes of one ``pangu_ultra_moe`` model (defaults:
    openPangu-Ultra-MoE-718B as published, every expert and vocabulary
    row held). A serving stage holds a cut in depth, and one chip of an
    expert-parallel stage a share of the experts (``experts_held`` from
    ``expert_offset``) and a slice of the vocabulary (``vocab_size``
    rows: ids, logits and sampling are over the slice)."""

    vocab_size: int = 153600
    max_seq_len: int = 131072
    hidden_size: int = 7680
    num_layers: int = 61
    first_k_dense: int = 3
    num_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mlp_dim: int = 18432
    moe_dim: int = 2048
    n_experts: int = 256
    n_shared_experts: int = 1
    moe_top_k: int = 8
    routed_scale: float = 2.5
    rms_eps: float = 1e-5
    rope_theta: float = 25600000.0

    @property
    def serving_family(self):
        return PANGU_ULTRA_MOE_SERVING

    def _init(self, key):
        """Matrices normal(0, 0.02) in ``dtype``; the router float32;
        every norm's gain 1 (a post-norm with gain 1 already rescales
        its sublayer's output to unit size: it matters as it is)."""
        c, dt = self.hidden_size, self.dtype
        keys = iter(jax.random.split(key, 16 * (self.num_layers + 1)))

        def mat(*shape, dtype=dt):
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    * 0.02).astype(dtype)

        def ones(width):
            return {"scale": jnp.ones((width,), jnp.float32)}

        params = {"embed": mat(self.vocab_size, c),
                  "head": {"kernel": mat(c, self.vocab_size)},
                  "norm_final": ones(c)}
        for i in range(self.num_layers):
            params[f"layer_{i}"] = {
                "attn_norm": ones(c), "attn_post_norm": ones(c),
                "ffn_norm": ones(c), "ffn_post_norm": ones(c),
                **self._sublayer_weights(i, mat, ones)}
        return params


class PanguUltraMoEServing(LatentServing):
    """The family's own ends of :class:`..latent.LatentServing`."""

    name = "pangu_ultra_moe"

    def embed(self, model, params, tokens):
        """``tokens [T]`` -> ``[T, C]`` float32."""
        return params["embed"][tokens].astype(jnp.float32)

    def residual(self, model, x, layer, which, sublayer):
        """The sandwich: a norm before the sublayer and one on what it
        returns, then the sum, in float32."""
        y, aux = sublayer(_rms(x, layer[f"{which}_norm"]["scale"],
                               model.rms_eps))
        return x + _rms(y, layer[f"{which}_post_norm"]["scale"],
                        model.rms_eps), aux

    def logits(self, model, params, x, cs=None):
        """``x [..., C]`` -> ``[..., vocab]`` float32: final RMSNorm,
        the untied head without bias over the rows held."""
        h = _rms(x, params["norm_final"]["scale"], model.rms_eps)
        return jnp.dot(h.astype(model.dtype),
                       params["head"]["kernel"].astype(model.dtype),
                       preferred_element_type=jnp.float32)


PANGU_ULTRA_MOE_SERVING = PanguUltraMoEServing()


# -------------------------------------------------------------- registry

def PanguUltraMoE_718B(**kw) -> PanguUltraMoE:
    """openPangu-Ultra-MoE-718B at its published sizes; ``num_layers``,
    ``first_k_dense``, ``experts_held`` / ``expert_offset`` and
    ``vocab_size`` are keywords (one chip of a serving stage holds a
    cut in depth, a share of the experts and a slice of the
    vocabulary; whole, the model is 1.4 TB)."""
    return PanguUltraMoE(**kw)


def PanguUltraMoE_Tiny(**kw) -> PanguUltraMoE:
    """Every mechanism of the family at a size the CPU tests run: four
    heads, 16 experts at top-4 of which a chip may hold some."""
    defaults = dict(
        vocab_size=211, max_seq_len=16384, hidden_size=64, num_layers=3,
        first_k_dense=1, num_heads=4, q_lora_rank=24, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        mlp_dim=96, moe_dim=32, n_experts=16, n_shared_experts=1,
        moe_top_k=4, rope_theta=10000.0)
    defaults.update(kw)
    return PanguUltraMoE(**defaults)


register("pangu_ultra_moe_718b", lm=True)(PanguUltraMoE_718B)
register("pangu_ultra_moe_tiny", lm=True)(PanguUltraMoE_Tiny)
