"""The ``mimo_v2`` family for SERVING (Xiaomi MiMo-V2.5): window and
full layers side by side with a different number of key/value heads in
each kind, keys wider than values, partial rotary with a ``rope_theta``
a kind, a learned sink logit on the window layers, dropless
sigmoid-routed experts with no shared expert of which a chip may hold a
share, and one pre-norm residual stream:

    x <- x + Attn_l( attn_norm(x) )
    x <- x + FFN_l(  ffn_norm(x) )
    logits = head( norm_final(x) )

    Attn_l(h): q = h Wq [Hq x Dk], k = h Wk [Hkv_l x Dk],
               v = (h Wv [Hkv_l x Dv]) * attention_value_scale
               rotary on the first int(Dk * partial_rotary_factor)
               dimensions of q and k (half-split pairs), at the
               kind's theta; the rest of each head passes as it is
               head t attends key/value head t // (Hq / Hkv_l) over
               columns j <= i (full) or i - window < j <= i (window),
               scores scaled by Dk ^ -0.5; a window layer's head adds
               exp(sink_t) to its softmax's denominator (a column with
               no value)
               out = concat_heads(o) [Hq x Dv] Wo

Layer ``i``'s kind is ``hybrid_layer_pattern[i]``: 0 a full layer, 1 a
window layer. The first ``first_k_dense`` layers have a dense gated
feed-forward, the rest the routed one of ``models/latent.py::_ffn``:
sigmoid scores over all ``n_experts``, the top ``moe_top_k`` of ``score
+ e_bias`` (``noaux_tc`` with one group), weights normalised over the
chosen (scaling 1) and NO shared expert.

**Two kinds of layer, two pools of unequal rows.** A token's cache row
in a layer is its K of every key/value head, then its V: ``Hkv_l (Dk +
Dv)`` values, 4 x 320 = 1,280 on a full layer and 8 x 320 = 2,560 on a
window layer at the published sizes. The full layers' pool is under the
page table, the window layers' a ring of ``ceil(window / ps) + 1``
pages a slot (``serving/kv_pages.py``), each pool with its own row. The
walk through the layers, the rings and the splice are ``models/
afmoe.py``'s: this family gives its own attention, residual, embedding
and cache rows. Training is not supported; the published model's
multi-token prediction layers and its vision and audio towers are not
loaded.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..ops.pallas.chunk_attention import gqa_chunk_attention
from ..ops.pallas.decode_attention import gqa_paged_decode_attention
from .afmoe import AfmoeServing, _write_row
from .latent import _dot, _rms, _rotate
from .pangu_ultra_moe import PanguUltraMoEServing
from .registry import register

# the published ``hybrid_layer_pattern``: a full layer first, then five
# window layers and one full layer, period after period (48 layers)
PUBLISHED_PATTERN = (0,) + (1, 1, 1, 1, 0) + (1, 1, 1, 1, 1, 0) * 7


@dataclasses.dataclass(frozen=True)
class MiMoV2:
    """Sizes of one ``mimo_v2`` model (defaults: MiMo-V2.5's language
    model as published, every expert and vocabulary row held). A
    serving stage holds a cut in depth (the first ``num_layers`` kinds
    of ``hybrid_layer_pattern``), and one chip of an expert-parallel
    stage a share of the experts (``experts_held`` from
    ``expert_offset``) and a slice of the vocabulary (``vocab_size``
    rows)."""

    vocab_size: int = 152576
    max_seq_len: int = 1048576
    hidden_size: int = 4096
    num_layers: int = 48
    first_k_dense: int = 1
    num_heads: int = 64
    num_kv_heads: int = 4           # a full layer's
    swa_num_kv_heads: int = 8       # a window layer's
    head_dim: int = 192             # q and k
    v_head_dim: int = 128
    partial_rotary_factor: float = 0.334
    attention_value_scale: float = 0.707
    mlp_dim: int = 16384
    moe_dim: int = 2048
    n_experts: int = 256            # the router's width
    n_shared_experts: int = 0
    moe_top_k: int = 8
    routed_scale: float = 1.0
    rms_eps: float = 1e-5
    rope_theta: float = 10000000.0  # a full layer's
    swa_rope_theta: float = 10000.0
    sliding_window: int = 128       # keys a window layer attends
    hybrid_layer_pattern: tuple = PUBLISHED_PATTERN
    experts_held: Optional[int] = None
    expert_offset: int = 0
    dtype: Any = jnp.float32
    # the engine's decode_attn picks the chunk's and decode's attention;
    # the CLIs pass and print the field
    attn_impl: str = "xla"

    def __post_init__(self):
        # a list (as a configuration file has it) held as a tuple: the
        # model is a jit static and must hash
        pattern = tuple(int(k) for k in self.hybrid_layer_pattern)
        if len(pattern) < self.num_layers or set(pattern) - {0, 1}:
            raise ValueError(
                f"hybrid_layer_pattern must give 0 (full) or 1 (window) "
                f"for each of the {self.num_layers} layers: {pattern}")
        object.__setattr__(self, "hybrid_layer_pattern", pattern)

    # ---- derived sizes ------------------------------------------------
    @property
    def layer_types(self) -> tuple:
        """The kinds of the layers kept, by the names the walk of
        ``models/afmoe.py`` reads."""
        return tuple("sliding_attention" if kind else "full_attention"
                     for kind in self.hybrid_layer_pattern[:self.num_layers])

    @property
    def n_full(self) -> int:
        return self.layer_types.count("full_attention")

    @property
    def n_sliding(self) -> int:
        return self.num_layers - self.n_full

    @property
    def n_moe_layers(self) -> int:
        return self.num_layers - self.first_k_dense

    @property
    def n_held(self) -> int:
        return (self.n_experts if self.experts_held is None
                else self.experts_held)

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    def kv_heads(self, sliding: bool) -> int:
        return self.swa_num_kv_heads if sliding else self.num_kv_heads

    def kv_row(self, sliding: bool) -> int:
        """Values a token keeps in one layer of the kind: K of every
        key/value head, then V."""
        return self.kv_heads(sliding) * (self.head_dim + self.v_head_dim)

    def rope_freqs(self, sliding: bool) -> np.ndarray:
        """``inv_freq [rotary_dim / 2]`` float32 at the kind's theta."""
        theta = self.swa_rope_theta if sliding else self.rope_theta
        rot = self.rotary_dim
        return (1.0 / theta ** (np.arange(0, rot, 2, dtype=np.float32)
                                / rot)).astype(np.float32)

    @property
    def serving_family(self):
        return MIMO_V2_SERVING

    # ---- weights ------------------------------------------------------
    def init(self, key, _dummy=None):
        """``{"params": tree}`` of seeded random weights, made on the
        device in ONE jitted call in the dtype they are served in."""
        return {"params": jax.jit(self._init)(key)}

    def _init(self, key):
        """Matrices normal(0, 0.02) in ``dtype``; the router, the
        selection bias ``e_bias`` (normal(0, 0.01)), every gain (1) and
        the window layers' ``sinks`` float32. A sink is drawn
        normal(ln 128, 1) a head: against a window of 128 near-equal
        scores a sink of 0 would hold under 1 % of the mass and no
        comparison could see it; a trained model's is whatever training
        left."""
        c, dt = self.hidden_size, self.dtype
        hq, dk, dv = self.num_heads, self.head_dim, self.v_head_dim
        keys = iter(jax.random.split(key, 16 * (self.num_layers + 1)))

        def mat(*shape, dtype=dt, std=0.02, mean=0.0):
            return (mean + jax.random.normal(next(keys), shape, jnp.float32)
                    * std).astype(dtype)

        def ones(width):
            return {"scale": jnp.ones((width,), jnp.float32)}

        def gated(width, lead=()):
            return {"w_gate": mat(*lead, c, width),
                    "w_up": mat(*lead, c, width),
                    "w_down": mat(*lead, width, c)}

        params = {"embed": mat(self.vocab_size, c),
                  "head": {"kernel": mat(c, self.vocab_size)},
                  "norm_final": ones(c)}
        for i, kind in enumerate(self.layer_types):
            sliding = kind == "sliding_attention"
            hk = self.kv_heads(sliding)
            attn = {"wq": mat(c, hq * dk), "wk": mat(c, hk * dk),
                    "wv": mat(c, hk * dv), "wo": mat(hq * dv, c)}
            if sliding:             # a learned sink on window layers only
                attn["sinks"] = mat(hq, dtype=jnp.float32, std=1.0,
                                    mean=math.log(self.sliding_window))
            layer = {"attn_norm": ones(c), "ffn_norm": ones(c),
                     "attn": attn}
            if i < self.first_k_dense:
                layer["mlp"] = gated(self.mlp_dim)
            else:
                layer["moe"] = {
                    "router": mat(c, self.n_experts, dtype=jnp.float32),
                    "e_bias": mat(self.n_experts, dtype=jnp.float32,
                                  std=0.01),
                    **gated(self.moe_dim, (self.n_held,))}
                if self.n_shared_experts:
                    layer["moe"]["shared"] = gated(
                        self.moe_dim * self.n_shared_experts)
            params[f"layer_{i}"] = layer
        return params


# ------------------------------------------------------------ attention

def _partial_rotary(x, positions, inv_freq):
    """Rotary on the first ``2 len(inv_freq)`` dimensions of each head
    of ``x [T, H, D]`` (pairs ``(i, i + rot / 2)``), float32; the rest
    passes."""
    rot = 2 * inv_freq.shape[0]
    return jnp.concatenate(
        [_rotate(x[..., :rot], positions, inv_freq),
         x[..., rot:].astype(jnp.float32)], axis=-1)


def _qkv(h, p, positions, sliding, model):
    """Normed hidden ``h [T, C]`` -> ``(q [T, Hq, Dk], row [T, Hkv (Dk +
    Dv)])``: the cache row is K of every key/value head, then V (the
    value scale applied)."""
    dt = model.dtype
    t = h.shape[0]
    hk, dk = model.kv_heads(sliding), model.head_dim
    inv_freq = model.rope_freqs(sliding)
    q = _partial_rotary(_dot(h, p["wq"], dt).reshape(t, model.num_heads, dk),
                        positions, inv_freq)
    k = _partial_rotary(_dot(h, p["wk"], dt).reshape(t, hk, dk),
                        positions, inv_freq)
    v = _dot(h, p["wv"], dt) * model.attention_value_scale
    row = jnp.concatenate([k.reshape(t, hk * dk), v], axis=-1)
    return q.astype(dt), row.astype(dt)


def _attn_prefill(h, p, cache, start, sliding, model, attn_impl="xla"):
    """Causal grouped attention of a chunk ``h [T, C]`` at absolute
    positions ``[start, start + T)`` against one layer's standalone
    cache ``[W, Hkv (Dk + Dv)]``, which already holds ``[0, start)``:
    :func:`...ops.pallas.chunk_attention.gqa_chunk_attention` at ``Dk
    != Dv`` in the engine's ``attn_impl``, a window layer's ``window``
    columns up to each query and its heads' sink logits. Returns
    ``(out [T, C] float32, cache)``."""
    t = h.shape[0]
    positions = start + jnp.arange(t)
    q, row = _qkv(h, p, positions, sliding, model)
    cache = jax.lax.dynamic_update_slice(cache, row, (start, 0))
    out = gqa_chunk_attention(
        q, cache, start, kv_heads=model.kv_heads(sliding),
        scale=model.head_dim ** -0.5,
        reach=model.sliding_window if sliding else None,
        sinks=p.get("sinks"), impl=attn_impl)
    return _dot(out.reshape(t, -1), p["wo"], model.dtype), cache


def _attn_decode(h, p, pool, layer, table, read_table, sliding, positions,
                 page_size, attn_impl, model):
    """Attention of one pending token a slot (``h [N, C]``): writes
    each slot's row into layer ``layer`` of the WHOLE pool in place
    (``table``: the page table, or the slots' rings), then attends
    through ``read_table`` with the grouped kernel at ``Dk != Dv``, a
    window layer's sink logits beside its lower column bound. Returns
    ``(out [N, C] float32, pool)``."""
    q, row = _qkv(h, p, positions, sliding, model)
    pool = _write_row(pool, layer, table, positions, row, int(page_size),
                      sliding)
    out = gqa_paged_decode_attention(
        q, pool, read_table, positions, layer=layer,
        kv_heads=model.kv_heads(sliding), scale=model.head_dim ** -0.5,
        reach=model.sliding_window if sliding else None,
        sinks=p.get("sinks"), impl=attn_impl)
    return _dot(out.reshape(h.shape[0], -1), p["wo"], model.dtype), pool


class MiMoV2Serving(AfmoeServing):
    """What ``ServingEngine`` asks of the family (the seam is
    :func:`...inference.generate.serving_family`). The walk through the
    two kinds of layer, both pools carried whole, the rings and what
    the family refuses are :class:`..afmoe.AfmoeServing`'s; the head and
    the expert counts behind a token block the latent families'."""

    name = "mimo_v2"

    attn_prefill = staticmethod(_attn_prefill)
    attn_decode = staticmethod(_attn_decode)
    # the token's row of the table, no scaling
    embed = PanguUltraMoEServing.embed

    def residual(self, model, x, layer, which, sublayer):
        """Pre-norm: ``x + sublayer(norm(x))``, in float32."""
        y, aux = sublayer(_rms(x, layer[f"{which}_norm"]["scale"],
                               model.rms_eps))
        return x + y, aux

    def cache_rows(self, model):
        """Two pools of UNEQUAL rows: the full layers' (``Hkv (Dk +
        Dv)`` of a full layer's key/value heads) under the page table,
        the window layers' (of theirs) a ring that holds
        ``sliding_window`` columns a slot and no more."""
        return (("full", (model.kv_row(False),), model.dtype, model.n_full,
                 None),
                ("sliding", (model.kv_row(True),), model.dtype,
                 model.n_sliding, model.sliding_window))


MIMO_V2_SERVING = MiMoV2Serving()


# -------------------------------------------------------------- registry

def MiMo_V2_5(**kw) -> MiMoV2:
    """MiMo-V2.5's language model at its published sizes;
    ``num_layers``, ``first_k_dense``, ``experts_held`` /
    ``expert_offset`` and ``vocab_size`` are keywords (one chip of a
    serving stage holds a cut in depth, a share of the experts and a
    slice of the vocabulary; whole, the model is 0.6 TB)."""
    return MiMoV2(**kw)


def MiMo_V2_Tiny(**kw) -> MiMoV2:
    """Every mechanism of the family at a size the CPU tests run: eight
    query heads on one key/value head in a full layer and two in a
    window layer, keys of 24 and values of 16, rotary on 8 of the 24,
    a window of 8 with sinks, 16 experts at top-4 and no shared expert,
    kinds full-window-window-full-window."""
    defaults = dict(
        vocab_size=211, max_seq_len=16384, hidden_size=64, num_layers=5,
        first_k_dense=1, num_heads=8, num_kv_heads=1, swa_num_kv_heads=2,
        head_dim=24, v_head_dim=16, mlp_dim=96, moe_dim=32, n_experts=16,
        moe_top_k=4, sliding_window=8, hybrid_layer_pattern=(0, 1, 1, 0, 1))
    defaults.update(kw)
    return MiMoV2(**defaults)


register("mimo_v2_5", lm=True)(MiMo_V2_5)
register("mimo_v2_tiny", lm=True)(MiMo_V2_Tiny)
