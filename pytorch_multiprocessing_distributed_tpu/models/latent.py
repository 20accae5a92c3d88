"""What the latent-attention, routed-expert families share for SERVING
(``models/xing4.py``, ``models/pangu_ultra_moe.py``): the record of
sizes, the latent (MLA) attention in both its forms, the gated and the
routed feed-forward, and the walk through the layers that the serving
engine asks a family for. A family adds what is its own: how a sublayer
sits on the residual path (stream mixers, sandwich norms), the
embedding and the head.

No flax module: a model is a frozen record of sizes (hashable, so it
can be a jit static like a ``GPT``), the weights a plain nested dict
made on the device in the dtype they are served in, the forward passes
the three functions of the seam ``inference/generate.py::
serving_family``: whole-prompt prefill, one chunk of an incremental
prefill, one decode step over all slots.

The cache holds, a token and layer, ONE row that all heads share: the
normed latent ``c`` (``R = kv_lora_rank`` values), then the rotated
position key ``k_rope``, zero-padded to whole lanes of 128: a pool
``[L, P, ps, R + Rw]`` (640 values a row at the published sizes, 576 of
them used). Why one padded row and not two pools or an unpadded one:
the chip's tiling pads a 64-wide minor dimension to 128 anyway, and a
pool it has to pad is kept a second time inside the decode program and
copied every step (``ROADMAP.md`` A2; the rotary keys as a pool of
their own at width 64 cost 0.67 GB of temporaries a call, measured by
compiling for the described chip); and the latent kernel's time is the
number of page DMAs it issues, so a page is one DMA, not two
(``PERF.md`` section 6). The engine threads two cache operands through
every program (K and V for GPT); the second one here is a zero-width
placeholder. Prefill decompresses (per-head keys and values from
``c``); decode is absorbed (the query goes into the latent space and
the output comes back out of it, ``ops/pallas/decode_attention.py``).

A model may hold a SHARE of each layer's routed experts
(``experts_held`` of ``n_experts`` from ``expert_offset``: what one
chip of an expert-parallel deployment holds). The router keeps its
width, the top-k is taken over all experts, and the layer adds the part
its own experts give (``ops/moe.py::dropless_experts``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..ops.moe import dropless_experts, route_sigmoid_topk
from ..ops.pallas.chunk_attention import softmax_rows
from ..ops.pallas.decode_attention import mla_paged_decode_attention

_LANES = 128
# heads one pass of the decompressed (prefill) attention holds scores
# for: [group, chunk, width] float32 at a time, not all heads' at once
_PREFILL_HEAD_GROUP = 8


@dataclasses.dataclass(frozen=True)
class LatentMoE:
    """The sizes the shared blocks read. ``num_layers`` counts every
    layer, the first ``first_k_dense`` of them with a dense
    feed-forward."""

    vocab_size: int
    max_seq_len: int
    hidden_size: int
    num_layers: int
    first_k_dense: int
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    mlp_dim: int
    moe_dim: int
    n_experts: int              # the router's width
    n_shared_experts: int
    moe_top_k: int
    routed_scale: float
    rms_eps: float
    rope_theta: float
    # (factor, original length, beta_fast, beta_slow, mscale,
    # mscale_all_dim); None = plain rotary positions
    yarn: Optional[Tuple[float, int, float, float, float, float]] = None
    # the routed experts this chip holds, [expert_offset, expert_offset
    # + experts_held) of every expert layer; None = all of them
    experts_held: Optional[int] = None
    expert_offset: int = 0
    dtype: Any = jnp.float32
    # the prefill attention is plain XLA in every dtype (a flash kernel
    # whose query-key and value widths differ is ROADMAP.md's); the
    # field exists because the CLIs pass and print it
    attn_impl: str = "xla"

    # ---- derived sizes ------------------------------------------------
    @property
    def rope_cache_dim(self) -> int:
        return -(-self.qk_rope_head_dim // _LANES) * _LANES

    @property
    def n_moe_layers(self) -> int:
        return self.num_layers - self.first_k_dense

    @property
    def n_held(self) -> int:
        return (self.n_experts if self.experts_held is None
                else self.experts_held)

    def softmax_scale(self) -> float:
        m = 1.0
        if self.yarn is not None and self.yarn[0] > 1:
            m = 0.1 * self.yarn[5] * math.log(self.yarn[0]) + 1.0
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m

    def rope_tables(self):
        """``(inv_freq [rope/2] float32, factor on cos and sin)``: plain
        frequencies, or the DeepSeek-V3 YaRN blend of original
        frequencies (dimensions that turn more than ``beta_fast`` times
        over the original length) and interpolated ones (fewer than
        ``beta_slow``)."""
        dim, theta = self.qk_rope_head_dim, self.rope_theta
        half = dim // 2
        freq = 1.0 / theta ** (np.arange(half, dtype=np.float32) * 2.0 / dim)
        if self.yarn is None:
            return freq.astype(np.float32), 1.0
        factor, orig, beta_fast, beta_slow, mscale, mscale_all = self.yarn

        def correction_dim(rotations):
            return (dim * math.log(orig / (rotations * 2 * math.pi))
                    / (2 * math.log(theta)))

        low = max(math.floor(correction_dim(beta_fast)), 0)
        high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
        ramp = np.clip((np.arange(half, dtype=np.float32) - low)
                       / max(high - low, 1e-3), 0.0, 1.0)
        inv = freq / factor * ramp + freq * (1.0 - ramp)

        def m(scale):
            return 1.0 if factor <= 1 else 0.1 * scale * math.log(factor) + 1.0

        return inv.astype(np.float32), m(mscale) / m(mscale_all)

    # ---- weights ------------------------------------------------------
    def init(self, key, _dummy=None):
        """``{"params": tree}`` of seeded random weights, made on the
        device in ONE jitted call in the dtype they are served in
        (the family's ``_init``): matrices in ``dtype``, the router and
        the norms in float32."""
        return {"params": jax.jit(self._init)(key)}

    def _sublayer_weights(self, i, mat, ones, e_bias=None):
        """Layer ``i``'s attention and feed-forward weights, drawn
        through the family's ``mat(*shape, dtype=)``: the dense
        feed-forward in the leading layers, then the router over ALL
        experts (and ``e_bias()``, a family's selection bias), the
        experts held here and the shared one."""
        c, h = self.hidden_size, self.num_heads
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim

        def gated(width, lead=()):
            return {"w_gate": mat(*lead, c, width),
                    "w_up": mat(*lead, c, width),
                    "w_down": mat(*lead, width, c)}

        layer = {"attn": {
            "wq_a": mat(c, self.q_lora_rank),
            "q_norm": ones(self.q_lora_rank),
            "wq_b": mat(self.q_lora_rank, h * qk),
            "wkv_a": mat(c, self.kv_lora_rank + self.qk_rope_head_dim),
            "kv_norm": ones(self.kv_lora_rank),
            "wkv_b": mat(self.kv_lora_rank,
                         h * (self.qk_nope_head_dim + self.v_head_dim)),
            "wo": mat(h * self.v_head_dim, c)}}
        if i < self.first_k_dense:
            layer["mlp"] = gated(self.mlp_dim)
        else:
            layer["moe"] = {
                "router": mat(c, self.n_experts, dtype=jnp.float32),
                **({} if e_bias is None else {"e_bias": e_bias()}),
                **gated(self.moe_dim, (self.n_held,)),
                "shared": gated(self.moe_dim * self.n_shared_experts)}
        return layer


# ------------------------------------------------------------ the blocks

def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return y if scale is None else y * scale


def _rotary(x, positions, model):
    """``x [T, ..., rope]`` at ``positions [T]``, pairs ``(i, i +
    rope/2)``, in float32, by the model's ``rope_tables()``."""
    return _rotate(x, positions, *model.rope_tables())


def _rotate(x, positions, inv_freq, factor=1.0):
    """:func:`_rotary` by the tables themselves: ``inv_freq [rope/2]``
    and a factor on cos and sin."""
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (angle.shape[-1],)
    cos = (jnp.cos(angle) * factor).reshape(shape)
    sin = (jnp.sin(angle) * factor).reshape(shape)
    a, b = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _dot(a, b, dt):
    return jnp.dot(a.astype(dt), b.astype(dt),
                   preferred_element_type=jnp.float32)


def _qkv(h, p, positions, model):
    """Normed hidden ``h [T, C]`` -> ``(q_nope [T, H, nope], q_rope
    [T, H, rope] rotated, row [T, R + Rw])``: the cache row is the
    normed latent, then the rotated position key zero-padded to whole
    lanes. All in the compute dtype."""
    dt = model.dtype
    t = h.shape[0]
    nope, rope = model.qk_nope_head_dim, model.qk_rope_head_dim
    q = _dot(_rms(_dot(h, p["wq_a"], dt), p["q_norm"]["scale"],
                  model.rms_eps), p["wq_b"], dt)
    q = q.reshape(t, model.num_heads, nope + rope)
    kv = _dot(h, p["wkv_a"], dt)
    c = _rms(kv[:, :model.kv_lora_rank], p["kv_norm"]["scale"],
             model.rms_eps)
    k_rope = _rotary(kv[:, model.kv_lora_rank:], positions, model)
    row = jnp.concatenate(
        [c, k_rope, jnp.zeros((t, model.rope_cache_dim - rope),
                              jnp.float32)], axis=-1)
    return (q[..., :nope].astype(dt),
            _rotary(q[..., nope:], positions, model).astype(dt),
            row.astype(dt))


def _attn_prefill(h, p, cache, start, model):
    """Decompressed causal attention of a chunk ``h [T, C]`` at
    absolute positions ``[start, start + T)`` against one layer's
    standalone cache ``[W, R + Rw]``, which already holds ``[0,
    start)``. Writes the chunk's rows, builds every head's keys and
    values for all ``W`` columns from the latents, attends row ``r`` to
    columns ``[0, start + r]``. Returns ``(out [T, C] float32,
    cache)``."""
    dt = model.dtype
    t, w = h.shape[0], cache.shape[0]
    heads, nope, vd = (model.num_heads, model.qk_nope_head_dim,
                       model.v_head_dim)
    rank, rope = model.kv_lora_rank, model.qk_rope_head_dim
    positions = start + jnp.arange(t)
    q_nope, q_rope, row = _qkv(h, p, positions, model)
    cache = jax.lax.dynamic_update_slice(cache, row, (start, 0))
    kvb = _dot(cache[:, :rank], p["wkv_b"], dt).astype(dt).reshape(
        w, heads, nope + vd)
    # one contraction over nope + rope: the shared position key is
    # copied to every head beside its own no-position key
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [kvb[..., :nope],
         jnp.broadcast_to(cache[:, None, rank:rank + rope],
                          (w, heads, rope))], axis=-1)
    mask = jnp.arange(w)[None, :] <= positions[:, None]      # [T, W]
    scale = model.softmax_scale()
    group = math.gcd(heads, _PREFILL_HEAD_GROUP)

    def heads_of(args):
        qg, kg, vg = args           # [T, g, .], [W, g, .], [W, g, .]
        s = jnp.einsum("tgd,wgd->gtw", qg, kg,
                       preferred_element_type=jnp.float32) * scale
        pr, total = softmax_rows(jnp.where(mask[None], s, -jnp.inf))
        out = jnp.einsum("gtw,wgd->gtd", pr.astype(dt), vg,
                         preferred_element_type=jnp.float32)
        return (out / total).astype(dt)                     # [g, T, v]

    def grouped(a):                 # [X, H, d] -> [H/g, X, g, d]
        return jnp.moveaxis(
            a.reshape(a.shape[0], heads // group, group, a.shape[-1]), 1, 0)

    out = jax.lax.map(heads_of, (grouped(q), grouped(k),
                                 grouped(kvb[..., nope:])))
    out = jnp.moveaxis(out.reshape(heads, t, vd), 0, 1).reshape(
        t, heads * vd)
    return _dot(out, p["wo"], dt), cache


def _attn_decode(h, p, pages, layer, positions, page_table, page_size,
                 window, attn_impl, model):
    """Absorbed attention of one pending token a slot (``h [N, C]``):
    writes each slot's row through the page table into layer ``layer``
    of the WHOLE pool (an in-place scatter into the donated array: no
    layer is sliced out and stacked back), then attends in the latent
    space. Per-head keys and values of the cached context are never
    built."""
    dt = model.dtype
    n = h.shape[0]
    heads, nope, vd = (model.num_heads, model.qk_nope_head_dim,
                       model.v_head_dim)
    rank = model.kv_lora_rank
    q_nope, q_rope, row = _qkv(h, p, positions, model)
    ps = int(page_size)
    page_ids = jnp.take_along_axis(
        page_table, (positions // ps)[:, None], axis=1)[:, 0]
    pages = pages.at[layer, page_ids, positions % ps].set(row)
    w_kvb = p["wkv_b"].astype(dt).reshape(rank, heads, nope + vd)
    q_lat = jnp.einsum("nhd,rhd->nhr", q_nope, w_kvb[..., :nope],
                       preferred_element_type=jnp.float32).astype(dt)
    # the query in a cache row's layout: latent | rotary | zeros
    q = jnp.concatenate(
        [q_lat, q_rope,
         jnp.zeros((n, heads, model.rope_cache_dim - q_rope.shape[-1]), dt)],
        axis=-1)
    n_win = (-(-int(window) // ps) if window is not None
             else page_table.shape[1])
    ids = jax.lax.slice_in_dim(page_table, 0,
                               min(n_win, page_table.shape[1]), axis=1)
    o_lat = mla_paged_decode_attention(
        q, pages, ids, positions, layer=layer, rank=rank,
        scale=model.softmax_scale(), window=window, impl=attn_impl)
    out = jnp.einsum("nhr,rhd->nhd", o_lat.astype(dt), w_kvb[..., nope:],
                     preferred_element_type=jnp.float32)
    return _dot(out.reshape(n, heads * vd), p["wo"], dt), pages


def _gated(h, p, dt):
    hidden = jax.nn.silu(_dot(h, p["w_gate"], dt)) * _dot(h, p["w_up"], dt)
    return _dot(hidden, p["w_down"], dt)


def _ffn(h32, layer, model):
    """The layer's feed-forward of normed ``h32 [T, C]`` float32 ->
    ``(y [T, C] float32, load [held + 2] int32 or None)``: the
    assignments each held expert took, those routed to experts this
    chip does not hold and, last, the rows the grouped matmuls were
    given. ``e_bias``, where the family has one, moves the selection
    only; ``shared``, where the layer has a shared expert, is added."""
    dt = model.dtype
    if "mlp" in layer:
        return _gated(h32, layer["mlp"], dt), None
    moe = layer["moe"]
    chosen, weights = route_sigmoid_topk(
        h32, moe["router"], moe.get("e_bias"), model.moe_top_k,
        model.routed_scale, getattr(model, "route_eps", 1e-20))
    y, counts, elsewhere, given = dropless_experts(
        h32.astype(dt), chosen, weights, moe["w_gate"], moe["w_up"],
        moe["w_down"], n_experts=model.n_experts,
        offset=model.expert_offset)
    if "shared" in moe:
        y = y + _gated(h32, moe["shared"], dt)
    return y, jnp.concatenate([counts, elsewhere[None], given[None]])


# ---------------------------------------------------- the serving family

class LatentServing:
    """What ``ServingEngine`` asks of a latent-attention family (the
    seam is :func:`...inference.generate.serving_family`; the GPT
    family's twin is ``GPTServing`` there). A family gives ``name``,
    ``embed``, ``residual`` and ``logits``; the walk through the layers
    is here."""

    # engine options these families do not support yet: option -> what
    # is missing (the engine refuses them at construction, by name)
    refuses = {
        "kv_dtype=int8": "no quantised latent page yet",
        "draft_k": "no verify pass over latent pages yet",
        "prefix_cache": "no page fork or gather for latent pages yet",
        "mesh": "no tensor-parallel latent decode yet",
    }

    def embed(self, model, params, tokens):
        """``tokens [T]`` -> the residual ``[T, ...]`` float32."""
        raise NotImplementedError

    def residual(self, model, x, layer, which, sublayer):
        """One sublayer (``which``: ``"attn"`` or ``"ffn"``) on the
        residual path: ``sublayer(normed [T, C]) -> (y [T, C], aux)``
        wrapped the family's way; returns ``(x, aux)``."""
        raise NotImplementedError

    def cache_rows(self, model):
        """Per token and layer ONE row: the normed latent, then the
        rotated shared position key zero-padded to whole lanes. The
        engine's second cache operand is a zero-width placeholder."""
        return (("latent", (model.kv_lora_rank + model.rope_cache_dim,),
                 model.dtype),
                ("unused", (0,), model.dtype))

    def aux_shape(self, model):
        """Integers a decode horizon returns behind its token block,
        in the same readback: per expert layer the assignments of its
        steps to each held expert, those routed elsewhere, then the
        rows its grouped matmuls were given."""
        return (model.n_moe_layers, model.n_held + 2)

    def chunk(self, model, params, pref, unused, tokens, start,
              cs=None, cs_cache=None, attn_impl="xla"):
        """One chunk ``tokens [1, T]`` at positions ``[start, start +
        T)`` against the standalone cache ``[L, 1, W, R + Rw]``;
        returns ``(x [1, T, ...], pref, unused)``. The decompressed
        attention is plain XLA whatever the engine's ``attn_impl``."""
        x = self.embed(model, params, tokens[0])
        caches = []
        for i in range(model.num_layers):
            layer = params[f"layer_{i}"]

            def attention(h, layer=layer, i=i):
                out, cache = _attn_prefill(h, layer["attn"], pref[i, 0],
                                           start, model)
                caches.append(cache)
                return out, None

            x, _ = self.residual(model, x, layer, "attn", attention)
            x, _ = self.residual(
                model, x, layer, "ffn",
                lambda h, layer=layer: _ffn(h, layer, model))
        return x[None], jnp.stack(caches)[:, None], unused

    def prefill(self, model, params, prompt, cs=None, cs_cache=None):
        """Whole-prompt prefill of ``prompt [1, S]``: one chunk of
        ``S`` rows from position 0 into fresh caches."""
        shape = (model.num_layers, 1, prompt.shape[1])
        caches = [jnp.zeros(shape + row, dtype)
                  for _, row, dtype in self.cache_rows(model)]
        return self.chunk(model, params, *caches, prompt, jnp.int32(0))

    def decode_step(self, model, params, pages, unused, positions,
                    last_tokens, *, window=None, attn_impl="xla",
                    page_table=None, page_size=None, kv_valid=None,
                    uniform_positions=False, offsets=None, **_):
        """One pending token a slot through every layer, the whole
        page pool carried through; returns ``(x [N, 1, ...], pages,
        unused, load [moe layers, held + 2])``. ``window`` is the
        engine's decode BUCKET: an upper bound on this step's columns
        (the page table is cut to it), never a model's sliding window
        (these families attend the whole context in every layer; a
        family with window layers: ``models/afmoe.py``)."""
        if (page_table is None or kv_valid is not None
                or uniform_positions or offsets is not None):
            raise NotImplementedError(
                f"the {self.name} family decodes over paged slots only")
        x = self.embed(model, params, last_tokens)
        loads = []
        for i in range(model.num_layers):
            layer = params[f"layer_{i}"]

            def attention(h, layer=layer, i=i):
                nonlocal pages
                out, pages = _attn_decode(
                    h, layer["attn"], pages, i, positions, page_table,
                    page_size, window, attn_impl, model)
                return out, None

            x, _ = self.residual(model, x, layer, "attn", attention)
            x, load = self.residual(
                model, x, layer, "ffn",
                lambda h, layer=layer: _ffn(h, layer, model))
            if load is not None:
                loads.append(load)
        load = (jnp.stack(loads) if loads
                else jnp.zeros(self.aux_shape(model), jnp.int32))
        return x[:, None], pages, unused, load
