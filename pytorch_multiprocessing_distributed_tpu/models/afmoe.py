"""The ``afmoe`` family for SERVING (Arcee Trinity): grouped-query
attention with per-head QK norms and an output gate, sliding-window and
full layers side by side, dropless sigmoid-routed experts of which a
chip may hold a share, and one residual stream with a norm before AND
after every sublayer:

    x0 = embed[token] * sqrt(hidden)                       (mup_enabled)
    x <- x + attn_post_norm( Attn_l( attn_norm(x) ) )
    x <- x + ffn_post_norm(  FFN_l(  ffn_norm(x) ) )
    logits = head( norm_final(x) )

    Attn_l(h): q = h Wq [Hq x Dh], k = h Wk [Hkv x Dh], v = h Wv, g = h Wg
               q, k <- RMSNorm_Dh(.) * gain          (per head, before rotation)
               sliding layer: rotary on q and k (all Dh dimensions, half-
               split pairs); full layer: no position encoding at all
               head t attends key/value head t // (Hq / Hkv) over columns
               j <= i (full) or i - window < j <= i (sliding)
               out = ( concat_heads(o) * sigmoid(g) ) Wo

Layer ``i`` is a full layer where ``(i + 1) % full_every == 0``. The
routed feed-forward, the sandwich and the head are the latent families'
(``models/latent.py::_ffn``, ``models/pangu_ultra_moe.py``): sigmoid
scores over all ``n_experts``, the top ``moe_top_k`` of ``score +
e_bias`` (the bias moves the selection only), weights normalised over
the chosen and scaled by ``routed_scale``, plus one shared expert.

**Two kinds of layer, two pools.** The cache row of a token and layer
is its K of every key/value head, then its V: ``2 * Hkv * Dh`` values
(2,048 at the published sizes), so a page is one DMA. The engine's two
cache operands are, for this family, the FULL layers' pool ``[n_full,
P, ps, row]`` under the page table and allocator every family has, and
the SLIDING layers' pool ``[n_sliding, slots * Wp, ps, row]``: a ring
of ``Wp = ceil(window / ps) + 1`` pages a slot, token ``t`` at the
slot's ring entry ``(t // ps) % Wp``. A ring needs no free list and no
second table (its page ids are the slot's index times ``Wp`` plus the
entry), its bytes are bounded by the window whatever the context, and a
page that falls out of reach is simply written over.

Names, so that no reader takes one for the other: ``sliding_window`` /
``reach`` is the MODEL's lower column bound on a sliding layer (4,096
keys, the token itself included); ``window`` in ``decode_step`` is the
engine's decode BUCKET, an upper bound on the step's columns that only
shortens the slice of the full layers' page table.

Training is not supported.
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
from .latent import _dot, _ffn, _rms, _rotary
from .pangu_ultra_moe import PanguUltraMoEServing
from .registry import register


@dataclasses.dataclass(frozen=True)
class Afmoe:
    """Sizes of one ``afmoe`` model (defaults: Trinity-Large-Preview as
    published, every expert and vocabulary row held). A serving stage
    holds a cut in depth, and one chip of an expert-parallel stage a
    share of the experts (``experts_held`` from ``expert_offset``) and
    a slice of the vocabulary (``vocab_size`` rows)."""

    vocab_size: int = 200192
    max_seq_len: int = 262144
    hidden_size: int = 3072
    num_layers: int = 60
    first_k_dense: int = 6
    num_heads: int = 48
    num_kv_heads: int = 8
    head_dim: int = 128
    mlp_dim: int = 12288
    moe_dim: int = 3072
    n_experts: int = 256            # the router's width
    n_shared_experts: int = 1
    moe_top_k: int = 4
    routed_scale: float = 2.448
    rms_eps: float = 1e-5
    rope_theta: float = 10000.0
    sliding_window: int = 4096      # keys a sliding layer attends
    full_every: int = 4             # layer i is full where (i+1) % this == 0
    experts_held: Optional[int] = None
    expert_offset: int = 0
    dtype: Any = jnp.float32
    # the engine's decode_attn picks the chunk's and decode's attention;
    # the CLIs pass and print the field
    attn_impl: str = "xla"

    # ---- derived sizes ------------------------------------------------
    @property
    def layer_types(self) -> tuple:
        return tuple(
            "full_attention" if (i + 1) % self.full_every == 0
            else "sliding_attention" for i in range(self.num_layers))

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
    def kv_row(self) -> int:
        """Values a token keeps a layer: K of every key/value head,
        then V."""
        return 2 * self.num_kv_heads * self.head_dim

    @property
    def serving_family(self):
        return AFMOE_SERVING

    def rope_tables(self):
        """``(inv_freq [Dh/2] float32, 1.0)``: plain rotary positions
        over all ``head_dim`` dimensions, no scaling."""
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
        """Matrices normal(0, 0.02) in ``dtype``; the router, the
        selection bias ``e_bias`` (normal(0, 0.01), so that it moves
        some choices: a trained model's is whatever load balancing
        left) and every gain (1) float32."""
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

        params = {"embed": mat(self.vocab_size, c),
                  "head": {"kernel": mat(c, self.vocab_size)},
                  "norm_final": ones(c)}
        for i in range(self.num_layers):
            layer = {
                "attn_norm": ones(c), "attn_post_norm": ones(c),
                "ffn_norm": ones(c), "ffn_post_norm": ones(c),
                "attn": {"wq": mat(c, hq * d), "wk": mat(c, hk * d),
                         "wv": mat(c, hk * d), "wg": mat(c, hq * d),
                         "q_norm": ones(d), "k_norm": ones(d),
                         "wo": mat(hq * d, c)}}
            if i < self.first_k_dense:
                layer["mlp"] = gated(self.mlp_dim)
            else:
                layer["moe"] = {
                    "router": mat(c, self.n_experts, dtype=jnp.float32),
                    "e_bias": mat(self.n_experts, dtype=jnp.float32,
                                  std=0.01),
                    **gated(self.moe_dim, (self.n_held,)),
                    "shared": gated(self.moe_dim * self.n_shared_experts)}
            params[f"layer_{i}"] = layer
        return params


# ------------------------------------------------------------ attention

def _qkvg(h, p, positions, rotate, model):
    """Normed hidden ``h [T, C]`` -> ``(q [T, Hq, Dh], row [T, 2 Hkv
    Dh], gate [T, Hq Dh] float32)``: per-head RMSNorm on q and k, then
    (sliding layers only) the rotation; the cache row is K of every
    key/value head, then V."""
    dt = model.dtype
    t = h.shape[0]
    hq, hk, d = model.num_heads, model.num_kv_heads, model.head_dim
    q = _rms(_dot(h, p["wq"], dt).reshape(t, hq, d),
             p["q_norm"]["scale"], model.rms_eps)
    k = _rms(_dot(h, p["wk"], dt).reshape(t, hk, d),
             p["k_norm"]["scale"], model.rms_eps)
    if rotate:
        q = _rotary(q, positions, model)
        k = _rotary(k, positions, model)
    v = _dot(h, p["wv"], dt)
    row = jnp.concatenate([k.reshape(t, hk * d), v], axis=-1)
    return (q.astype(dt), row.astype(dt),
            jax.nn.sigmoid(_dot(h, p["wg"], dt)))


def _write_row(pool, layer, table, positions, row, page_size, ring):
    """Each slot's new ``row`` into layer ``layer`` of the WHOLE pool,
    in place: at ``table[slot, pos // ps]`` under a page table, at the
    slot's ring entry ``(pos // ps) % Wp`` where ``ring``."""
    entry = positions // page_size
    if ring:
        entry = entry % table.shape[1]
    page_ids = jnp.take_along_axis(table, entry[:, None], axis=1)[:, 0]
    return pool.at[layer, page_ids, positions % page_size].set(row)


def _attn_prefill(h, p, cache, start, sliding, model, attn_impl="xla"):
    """Causal grouped attention of a chunk ``h [T, C]`` at absolute
    positions ``[start, start + T)`` against one layer's standalone
    cache ``[W, 2 Hkv Dh]``, which already holds ``[0, start)``: writes
    the chunk's rows, then attends every column under the causal mask
    (a full layer) or the ``sliding_window`` columns up to each query
    (a sliding layer) through :func:`...ops.pallas.chunk_attention.
    gqa_chunk_attention` in the engine's ``attn_impl``. Returns ``(out
    [T, C] float32, cache)``."""
    t = h.shape[0]
    positions = start + jnp.arange(t)
    q, row, gate = _qkvg(h, p, positions, sliding, model)
    cache = jax.lax.dynamic_update_slice(cache, row, (start, 0))
    out = gqa_chunk_attention(
        q, cache, start, kv_heads=model.num_kv_heads,
        scale=model.head_dim ** -0.5,
        reach=model.sliding_window if sliding else None, impl=attn_impl)
    return _dot(out.reshape(t, -1) * gate, p["wo"], model.dtype), cache


def _attn_decode(h, p, pool, layer, table, read_table, sliding, positions,
                 page_size, attn_impl, model):
    """Attention of one pending token a slot (``h [N, C]``): writes
    each slot's row into layer ``layer`` of the WHOLE pool in place,
    then attends through ``read_table``. A full layer's ``table`` is
    the page table (written at ``table[slot, pos // ps]``; read through
    its slice up to the decode bucket); a sliding layer's is the slot's
    ring (written at entry ``(pos // ps) % Wp``), and the kernel reads
    no page below ``pos - sliding_window + 1``. Returns ``(out [N, C]
    float32, pool)``."""
    ps = int(page_size)
    q, row, gate = _qkvg(h, p, positions, sliding, model)
    pool = _write_row(pool, layer, table, positions, row, ps, sliding)
    out = gqa_paged_decode_attention(
        q, pool, read_table, positions, layer=layer,
        kv_heads=model.num_kv_heads, scale=model.head_dim ** -0.5,
        reach=model.sliding_window if sliding else None, impl=attn_impl)
    return _dot(out.reshape(h.shape[0], -1) * gate, p["wo"],
                model.dtype), pool


class AfmoeServing(PanguUltraMoEServing):
    """What ``ServingEngine`` asks of the family (the seam is
    :func:`...inference.generate.serving_family`). The sandwich, the
    head and the expert counts behind a token block are
    :class:`..pangu_ultra_moe.PanguUltraMoEServing`'s."""

    name = "afmoe"

    refuses = {
        "kv_dtype=int8": "no quantised grouped page yet",
        "draft_k": "no verify pass over two kinds of page yet",
        "prefix_cache": "no page fork or gather for a ring of pages yet",
        "mesh": "no tensor-parallel grouped decode yet",
    }

    # one layer's attention in the walks below: a family with another
    # attention over the same two pools gives its own
    attn_prefill = staticmethod(_attn_prefill)
    attn_decode = staticmethod(_attn_decode)
    # the kind of layer whose rows the second pool holds (a ring a
    # slot), and the key of its mixer's weights in the layer: a family
    # with another mixer over the ring names its own
    ring_kind = "sliding_attention"
    ring_mixer = "attn"

    def mixer(self, layer, ring):
        """The weights of a layer's first sublayer: its attention, or
        on a layer of ``ring_kind`` the family's ring mixer."""
        return layer[self.ring_mixer if ring else "attn"]

    def embed(self, model, params, tokens):
        """``tokens [T]`` -> ``[T, C]`` float32, times ``sqrt(C)``
        (``mup_enabled``)."""
        return (params["embed"][tokens].astype(jnp.float32)
                * math.sqrt(model.hidden_size))

    def cache_rows(self, model):
        """Two pools of ONE row each (a token's K of every key/value
        head, then its V): the full layers' under the page table, the
        sliding layers' a ring that holds ``sliding_window`` columns a
        slot and no more. The five-field form ``(name, row, dtype,
        layers, columns a slot holds at most)``:
        :func:`...inference.generate.cache_pools`."""
        row = (model.kv_row,)
        return (("full", row, model.dtype, model.n_full, None),
                ("sliding", row, model.dtype, model.n_sliding,
                 model.sliding_window))

    def chunk(self, model, params, pref_full, pref_sliding, tokens, start,
              cs=None, cs_cache=None, attn_impl="xla"):
        """One chunk ``tokens [1, T]`` at positions ``[start, start +
        T)`` against the two standalone caches ``[n_full, 1, W, row]``
        and ``[n_sliding, 1, W, row]`` (both hold every column: the
        window is cut when the prompt is spliced into the ring), its
        attention in ``attn_impl`` (the engine's, as in decode);
        returns ``(x [1, T, C], pref_full, pref_sliding)``."""
        x = self.embed(model, params, tokens[0])
        caches = {False: [], True: []}
        for i, kind in enumerate(model.layer_types):
            layer = params[f"layer_{i}"]
            sliding = kind == self.ring_kind

            def attention(h, layer=layer, sliding=sliding):
                pref = pref_sliding if sliding else pref_full
                out, cache = self.attn_prefill(
                    h, self.mixer(layer, sliding),
                    pref[len(caches[sliding]), 0], start, sliding, model,
                    attn_impl)
                caches[sliding].append(cache)
                return out, None

            x, _ = self.residual(model, x, layer, "attn", attention)
            x, _ = self.residual(
                model, x, layer, "ffn",
                lambda h, layer=layer: _ffn(h, layer, model))

        def stacked(kind, like):
            return (jnp.stack(caches[kind])[:, None] if caches[kind]
                    else like)

        return (x[None], stacked(False, pref_full),
                stacked(True, pref_sliding))

    def prefill(self, model, params, prompt, cs=None, cs_cache=None):
        """Whole-prompt prefill of ``prompt [1, S]``: one chunk of
        ``S`` rows from position 0 into fresh caches."""
        caches = [jnp.zeros((layers, 1, prompt.shape[1]) + row, dtype)
                  for _, row, dtype, layers, _ in self.cache_rows(model)]
        return self.chunk(model, params, *caches, prompt, jnp.int32(0))

    def decode_step(self, model, params, full_pages, ring_pages, positions,
                    last_tokens, *, window=None, attn_impl="xla",
                    page_table=None, page_size=None, kv_valid=None,
                    uniform_positions=False, offsets=None, **_):
        """One pending token a slot through every layer, both pools
        carried whole; returns ``(x [N, 1, C], full_pages, ring_pages,
        load [moe layers, held + 2])``. ``window`` is the engine's
        decode BUCKET (an upper bound on this step's columns): it
        shortens the slice of the page table the full layers' kernel
        is given and nothing else; the sliding layers' lower bound is
        ``model.sliding_window``."""
        if (page_table is None or kv_valid is not None
                or uniform_positions or offsets is not None):
            raise NotImplementedError(
                f"the {self.name} family decodes over paged slots only")
        n, ps = positions.shape[0], int(page_size)
        n_win = (-(-int(window) // ps) if window is not None
                 else page_table.shape[1])
        bucket_table = jax.lax.slice_in_dim(
            page_table, 0, min(n_win, page_table.shape[1]), axis=1)
        # a slot's ring: its index times the ring's length, plus the entry
        ring = ring_pages.shape[1] // n
        ring_table = (jnp.arange(n, dtype=jnp.int32)[:, None] * ring
                      + jnp.arange(ring, dtype=jnp.int32)[None, :])
        pools = {False: full_pages, True: ring_pages}
        index = {False: 0, True: 0}
        x = self.embed(model, params, last_tokens)
        loads = []
        for i, kind in enumerate(model.layer_types):
            layer = params[f"layer_{i}"]
            sliding = kind == self.ring_kind

            def attention(h, layer=layer, sliding=sliding):
                out, pools[sliding] = self.attn_decode(
                    h, self.mixer(layer, sliding), pools[sliding],
                    index[sliding],
                    ring_table if sliding else page_table,
                    ring_table if sliding else bucket_table, sliding,
                    positions, ps, attn_impl, model)
                index[sliding] += 1
                return out, None

            x, _ = self.residual(model, x, layer, "attn", attention)
            x, load = self.residual(
                model, x, layer, "ffn",
                lambda h, layer=layer: _ffn(h, layer, model))
            if load is not None:
                loads.append(load)
        load = (jnp.stack(loads) if loads
                else jnp.zeros(self.aux_shape(model), jnp.int32))
        return x[:, None], pools[False], pools[True], load


AFMOE_SERVING = AfmoeServing()


# -------------------------------------------------------------- registry

def Trinity_Large_Preview(**kw) -> Afmoe:
    """Trinity-Large-Preview at its published sizes; ``num_layers``,
    ``first_k_dense``, ``experts_held`` / ``expert_offset`` and
    ``vocab_size`` are keywords (one chip of a serving stage holds a
    cut in depth, a share of the experts and a slice of the
    vocabulary; whole, the model is 0.8 TB)."""
    return Afmoe(**kw)


def Afmoe_Tiny(**kw) -> Afmoe:
    """Every mechanism of the family at a size the CPU tests run: six
    query heads on two key/value heads, a window of 8, 16 experts at
    top-4 of which a chip may hold some, kinds sliding-sliding-sliding-
    full-sliding."""
    defaults = dict(
        vocab_size=211, max_seq_len=16384, hidden_size=64, num_layers=5,
        first_k_dense=1, num_heads=6, num_kv_heads=2, head_dim=16,
        mlp_dim=96, moe_dim=32, n_experts=16, n_shared_experts=1,
        moe_top_k=4, sliding_window=8)
    defaults.update(kw)
    return Afmoe(**defaults)


register("trinity_large_preview", lm=True)(Trinity_Large_Preview)
register("afmoe_tiny", lm=True)(Afmoe_Tiny)
