"""The ``lfm2_moe`` family for SERVING (LiquidAI LFM2-8B-A1B): gated
short-convolution layers beside grouped-query full-attention layers,
dense feed-forwards in the leading layers and dropless sigmoid-routed
experts with no shared expert after them, one pre-norm residual stream
and a head tied to the embedding:

    x0 = embed[token]                                   (no scaling)
    x <- x + Mixer_l( attn_norm(x) )
    x <- x + FFN_l(   ffn_norm(x) )
    logits = norm_final(x) embed^T

    conv layer:  [B | Cg | X] = h W_in              (B, then Cg, then X)
                 u_t = B_t * X_t
                 z_t = w0 u_{t-2} + w1 u_{t-1} + w2 u_t   (u_j = 0, j < 0)
                 out = (Cg * z) W_out
    attn layer:  q = h Wq [Hq x D], k = h Wk [Hkv x D], v = h Wv
                 q, k <- RMSNorm_D(.) * gain   (per head, before rotation)
                 rotary on all D dimensions (half-split pairs)
                 head t attends key/value head t // (Hq / Hkv) over
                 columns j <= i, scores scaled by D ^ -0.5
                 out = concat_heads(o) Wo

Layer ``i``'s kind is ``layer_pattern[i]`` (the published
``layer_types``, cut to ``num_layers``). The first ``first_k_dense``
layers have a dense gated feed-forward, the rest the routed one of
``models/latent.py::_ffn``: sigmoid scores over all ``n_experts``, the
top ``moe_top_k`` of ``score + e_bias`` (``use_expert_bias``: the bias
moves the selection only), weights normalised over the chosen with the
family's ``route_eps`` and scaled by ``routed_scale``.

**Two pools, and the second holds no key or value.** A full layer's
cache row is a token's K of every key/value head, then its V (``2 Hkv
D`` values, 1,024 at the published sizes), under the page table. A conv
layer's row is the token's ``u`` (``C`` values): a decode step reads
the last two and writes its own, so the conv layers' pool is a ring
that holds ``conv_width`` columns a slot (``serving/kv_pages.py``: two
pages a slot at pages of 16), and the walk, the rings and the splice
are ``models/afmoe.py``'s with the conv as the ring's mixer
(:mod:`...ops.pallas.short_conv`). The conv treats a position below 0
as zero itself: what a ring holds below a request's first column is
never read. Training is not supported.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp

from ..ops.pallas.chunk_attention import gqa_chunk_attention
from ..ops.pallas.decode_attention import gqa_paged_decode_attention
from ..ops.pallas.short_conv import short_conv, short_conv_chunk
from .afmoe import AfmoeServing, _write_row
from .latent import _dot, _rms, _rotary
from .mimo_v2 import MiMoV2Serving
from .registry import register

CONV, FULL = "conv", "full_attention"

# the published ``layer_types``: 18 conv and 6 full-attention layers
PUBLISHED_LAYER_TYPES = (
    CONV, CONV, FULL, CONV, CONV, CONV, FULL, CONV, CONV, CONV, FULL, CONV,
    CONV, CONV, FULL, CONV, CONV, CONV, FULL, CONV, CONV, FULL, CONV, CONV)


@dataclasses.dataclass(frozen=True)
class Lfm2Moe:
    """Sizes of one ``lfm2_moe`` model (defaults: LFM2-8B-A1B as
    published). A serving stage holds a cut in depth: the first
    ``num_layers`` kinds of ``layer_pattern``."""

    vocab_size: int = 65536
    max_seq_len: int = 128000
    hidden_size: int = 2048
    num_layers: int = 24
    first_k_dense: int = 2
    num_heads: int = 32
    num_kv_heads: int = 8
    mlp_dim: int = 7168
    moe_dim: int = 1792
    n_experts: int = 32
    moe_top_k: int = 4
    routed_scale: float = 1.0
    # the normalisation's epsilon over the chosen weights (the
    # published modelling code's; the other sigmoid-routed families
    # here use 1e-20)
    route_eps: float = 1e-6
    rms_eps: float = 1e-5
    rope_theta: float = 1000000.0
    conv_width: int = 3             # conv_L_cache: u_{t-2}, u_{t-1}, u_t
    layer_pattern: tuple = PUBLISHED_LAYER_TYPES
    dtype: Any = jnp.float32
    # the engine's decode_attn picks the chunk's and decode's attention
    # and the conv's decode kernel; the CLIs pass and print the field
    attn_impl: str = "xla"

    def __post_init__(self):
        # a list (as a configuration file has it) held as a tuple: the
        # model is a jit static and must hash
        pattern = tuple(str(k) for k in self.layer_pattern)
        if len(pattern) < self.num_layers or set(pattern) - {CONV, FULL}:
            raise ValueError(
                f"layer_pattern must give {CONV!r} or {FULL!r} for each "
                f"of the {self.num_layers} layers: {pattern}")
        object.__setattr__(self, "layer_pattern", pattern)

    # ---- derived sizes ------------------------------------------------
    @property
    def layer_types(self) -> tuple:
        return self.layer_pattern[:self.num_layers]

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def n_full(self) -> int:
        return self.layer_types.count(FULL)

    @property
    def n_conv(self) -> int:
        return self.num_layers - self.n_full

    @property
    def n_moe_layers(self) -> int:
        return self.num_layers - self.first_k_dense

    @property
    def n_held(self) -> int:
        """Every expert of a layer is held on the chip, from 0."""
        return self.n_experts

    @property
    def expert_offset(self) -> int:
        return 0

    @property
    def kv_row(self) -> int:
        """Values a token keeps in a full layer: K of every key/value
        head, then V."""
        return 2 * self.num_kv_heads * self.head_dim

    @property
    def serving_family(self):
        return LFM2_MOE_SERVING

    def rope_tables(self):
        """``(inv_freq [D/2] float32, 1.0)``: plain rotary over all
        ``head_dim`` dimensions."""
        half = self.head_dim // 2
        freq = 1.0 / self.rope_theta ** (
            np.arange(half, dtype=np.float32) * 2.0 / self.head_dim)
        return freq.astype(np.float32), 1.0

    # ---- weights ------------------------------------------------------
    def init(self, key, _dummy=None):
        """``{"params": tree}`` of seeded random weights, made on the
        device in ONE jitted call in the dtype they are served in."""
        return {"params": jax.jit(self._init)(key)}

    def _init(self, key):
        """Matrices normal(0, 0.02) in ``dtype``; the conv's taps
        uniform(+-1/sqrt(3)) in ``dtype`` (a depthwise conv of width 3's
        default: at 0.02 the conv's part would vanish under a bfloat16
        comparison, and a wrong carry with it); the router, the
        selection bias ``e_bias`` (normal(0, 0.01)) and every gain (1)
        float32."""
        c, dt = self.hidden_size, self.dtype
        hq, hk, d = self.num_heads, self.num_kv_heads, self.head_dim
        keys = iter(jax.random.split(key, 16 * (self.num_layers + 1)))

        def mat(*shape, dtype=dt, std=0.02):
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    * std).astype(dtype)

        def ones(width):
            return {"scale": jnp.ones((width,), jnp.float32)}

        def gated(width, lead=()):
            return {"w_gate": mat(*lead, c, width),
                    "w_up": mat(*lead, c, width),
                    "w_down": mat(*lead, width, c)}

        bound = 1.0 / np.sqrt(self.conv_width)
        params = {"embed": mat(self.vocab_size, c), "norm_final": ones(c)}
        for i, kind in enumerate(self.layer_types):
            layer = {"attn_norm": ones(c), "ffn_norm": ones(c)}
            if kind == CONV:
                layer["conv"] = {
                    "w_in": mat(c, 3 * c),
                    "taps": jax.random.uniform(
                        next(keys), (self.conv_width, c), jnp.float32,
                        -bound, bound).astype(dt),
                    "w_out": mat(c, c)}
            else:
                layer["attn"] = {"wq": mat(c, hq * d), "wk": mat(c, hk * d),
                                 "wv": mat(c, hk * d), "wo": mat(hq * d, c),
                                 "q_norm": ones(d), "k_norm": ones(d)}
            if i < self.first_k_dense:
                layer["mlp"] = gated(self.mlp_dim)
            else:
                layer["moe"] = {
                    "router": mat(c, self.n_experts, dtype=jnp.float32),
                    "e_bias": mat(self.n_experts, dtype=jnp.float32,
                                  std=0.01),
                    **gated(self.moe_dim, (self.n_experts,))}
            params[f"layer_{i}"] = layer
        return params


# --------------------------------------------------------------- mixers

def _qkv(h, p, positions, model):
    """Normed hidden ``h [T, C]`` -> ``(q [T, Hq, D], row [T, 2 Hkv
    D])``: per-head RMSNorm on q and k, then the rotation; the cache row
    is K of every key/value head, then V."""
    dt = model.dtype
    t = h.shape[0]
    hq, hk, d = model.num_heads, model.num_kv_heads, model.head_dim
    q = _rotary(_rms(_dot(h, p["wq"], dt).reshape(t, hq, d),
                     p["q_norm"]["scale"], model.rms_eps), positions, model)
    k = _rotary(_rms(_dot(h, p["wk"], dt).reshape(t, hk, d),
                     p["k_norm"]["scale"], model.rms_eps), positions, model)
    row = jnp.concatenate([k.reshape(t, hk * d), _dot(h, p["wv"], dt)],
                          axis=-1)
    return q.astype(dt), row.astype(dt)


def _mixer_prefill(h, p, cache, start, conv, model, attn_impl="xla"):
    """A chunk ``h [T, C]`` at positions ``[start, start + T)`` through
    one layer's mixer against its standalone cache, which holds ``[0,
    start)``: the short conv over its ``u`` rows (``[W, C]``), or
    causal grouped attention over its K|V rows (``[W, 2 Hkv D]``,
    :func:`...ops.pallas.chunk_attention.gqa_chunk_attention` in the
    engine's ``attn_impl``). Writes the chunk's rows; returns ``(out
    [T, C] float32, cache)``."""
    dt = model.dtype
    t = h.shape[0]
    if conv:
        gated, cache = short_conv_chunk(_dot(h, p["w_in"], dt), p["taps"],
                                        cache, start)
        return _dot(gated, p["w_out"], dt), cache
    q, row = _qkv(h, p, start + jnp.arange(t), model)
    cache = jax.lax.dynamic_update_slice(cache, row, (start, 0))
    out = gqa_chunk_attention(q, cache, start, kv_heads=model.num_kv_heads,
                              scale=model.head_dim ** -0.5, impl=attn_impl)
    return _dot(out.reshape(t, -1), p["wo"], dt), cache


def _mixer_decode(h, p, pool, layer, table, read_table, conv, positions,
                  page_size, attn_impl, model):
    """One pending token a slot (``h [N, C]``) through one layer's
    mixer over the WHOLE pool of its kind, written in place: the short
    conv reads ``u_{t-1}``, ``u_{t-2}`` out of each slot's ring and
    writes ``u_t`` (the kernel ``short_conv``, through the walk's ring
    ``table``); a full layer writes its K|V row under the page table and
    attends through the bucket's slice of it with the grouped kernel.
    Returns ``(out [N, C] float32, pool)``."""
    dt = model.dtype
    if conv:
        gated, pool = short_conv(_dot(h, p["w_in"], dt), p["taps"], pool,
                                 table, positions, layer=layer,
                                 impl=attn_impl)
        return _dot(gated, p["w_out"], dt), pool
    q, row = _qkv(h, p, positions, model)
    pool = _write_row(pool, layer, table, positions, row, int(page_size),
                      False)
    out = gqa_paged_decode_attention(
        q, pool, read_table, positions, layer=layer,
        kv_heads=model.num_kv_heads, scale=model.head_dim ** -0.5,
        impl=attn_impl)
    return _dot(out.reshape(h.shape[0], -1), p["wo"], dt), pool


class Lfm2MoeServing(AfmoeServing):
    """What ``ServingEngine`` asks of the family (the seam is
    :func:`...inference.generate.serving_family`). The walk through the
    two kinds of layer and both pools is :class:`..afmoe.
    AfmoeServing`'s with the conv as the ring's mixer; the pre-norm
    residual and the plain embedding :class:`..mimo_v2.MiMoV2Serving`'s;
    the expert counts behind a token block the latent families'."""

    name = "lfm2_moe"

    refuses = {
        "prefix_cache": "no snapshot of the conv's carried rows at the "
                        "end of a shared prefix yet",
        "draft_k": "no rollback of the conv's carried rows after a "
                   "rejected draft yet",
        "kv_dtype=int8": "no quantised grouped page yet",
        "mesh": "no tensor-parallel grouped decode or conv yet",
    }

    attn_prefill = staticmethod(_mixer_prefill)
    attn_decode = staticmethod(_mixer_decode)
    ring_kind = CONV
    ring_mixer = "conv"
    embed = MiMoV2Serving.embed
    residual = MiMoV2Serving.residual

    def logits(self, model, params, x, cs=None):
        """``x [..., C]`` -> ``[..., vocab]`` float32: final RMSNorm,
        then the head tied to the embedding (``embed^T``)."""
        h = _rms(x, params["norm_final"]["scale"], model.rms_eps)
        return jnp.einsum("...c,vc->...v", h.astype(model.dtype),
                          params["embed"].astype(model.dtype),
                          preferred_element_type=jnp.float32)

    def cache_rows(self, model):
        """Two pools: the full layers' K|V rows (``2 Hkv D``) under the
        page table, and the conv layers' ``u`` rows (``C``), a ring that
        holds the conv's ``conv_width`` columns a slot."""
        return (("full", (model.kv_row,), model.dtype, model.n_full, None),
                ("conv", (model.hidden_size,), model.dtype, model.n_conv,
                 model.conv_width))


LFM2_MOE_SERVING = Lfm2MoeServing()


# -------------------------------------------------------------- registry

def LFM2_8B_A1B(**kw) -> Lfm2Moe:
    """LFM2-8B-A1B at its published sizes; ``num_layers`` is a keyword
    (a serving stage holds a cut in depth; whole, the model is 16.7 GB
    in bfloat16, more than one chip holds)."""
    return Lfm2Moe(**kw)


def Lfm2Moe_Tiny(**kw) -> Lfm2Moe:
    """Every mechanism of the family at a size the CPU tests run: four
    query heads of 16 on two key/value heads, 8 experts at top-4, two
    dense layers, kinds conv-full-conv-conv-full."""
    defaults = dict(
        vocab_size=211, max_seq_len=16384, hidden_size=64, num_layers=5,
        first_k_dense=2, num_heads=4, num_kv_heads=2, mlp_dim=96,
        moe_dim=32, n_experts=8, moe_top_k=4,
        layer_pattern=(CONV, FULL, CONV, CONV, FULL))
    defaults.update(kw)
    return Lfm2Moe(**defaults)


register("lfm2_8b_a1b", lm=True)(LFM2_8B_A1B)
register("lfm2_moe_tiny", lm=True)(Lfm2Moe_Tiny)
