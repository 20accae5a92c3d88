"""KV-cached autoregressive generation for the GPT family.

The reference is a vision trainer with no inference path; a complete LM
framework needs one. TPU-idiomatic by construction:

- STATIC shapes end to end: the KV cache is ``[B, max_seq_len, H, Dh]``
  per layer from the start, positions advance by ``dynamic_update_slice``
  — one compiled program serves every step (no per-length recompiles);
- the decode loop is a ``lax.scan`` over step indices inside ONE jit —
  no host round-trip per token;
- prefill is a single vectorized causal pass over the prompt (MXU-sized
  matmuls), decode steps are the bandwidth-bound cached attention.

Mirrors the model's own conventions (``models/gpt.py``): matmuls in
``model.dtype``, LayerNorm/softmax/head in f32, eps from ``model.ln_eps``. Works off the
plain GPT param tree — the same params `make_lm_train_step` trains.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.kv_quant import (QuantizedKV, flatten_heads, kv_slice_in_dim,
                            quantize_kv, stack_kv)
from ..ops.pallas.decode_attention import (decode_attention,
                                           paged_decode_attention,
                                           paged_verify_decode_attention,
                                           xla_decode_attention)

# flax-default fallback for models predating the ln_eps field; every
# helper takes eps EXPLICITLY (a forgotten argument must TypeError,
# not silently run 1e-6 on a GPT-2 checkpoint)
_LN_EPS = 1e-6


def _no_cs(x, *spec):
    return x


def _make_cs(mesh):
    """Sharding-constraint helper for TP decode: ``cs(x, *axes)`` pins
    ``x`` to ``PartitionSpec(*axes)`` on ``mesh``; the no-mesh variant
    is the identity so the single-shard path stays constraint-free."""
    if mesh is None:
        return _no_cs

    def cs(x, *spec):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*spec)))

    return cs


def shard_params_for_tp_decode(params, mesh: Mesh):
    """Place a plain GPT param tree TP-sharded for :func:`generate`.

    Same trailing-dim rule as the GSPMD training path
    (:func:`..train.step.tp_param_spec`): every Dense kernel's output
    dim — wqkv (=> heads), MLP, and the [D, V] head (=> vocab) — is
    sharded over the ``model`` axis; odd-sized leaves replicate. Each
    device then holds 1/tp of the weights at rest, which is the memory
    headroom TP decode exists for."""
    from ..train.step import MODEL_AXIS, tp_param_spec

    tp = int(mesh.shape[MODEL_AXIS])
    return jax.device_put(
        params,
        jax.tree.map(
            lambda l: NamedSharding(mesh, tp_param_spec(l, tp)), params),
    )


def _ln(x, p, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    # fast variance (E[x^2] - E[x]^2), matching flax LayerNorm's default
    # — the cached path must be BIT-identical to the model's forward or
    # near-tied argmaxes flip tokens
    var = jnp.mean(xf * xf, -1, keepdims=True) - mu * mu
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return out * p["scale"] + p["bias"]


def _dense(x, p, dtype):
    return x.astype(dtype) @ p["kernel"].astype(dtype) + p["bias"].astype(dtype)


def _moe_ffn(p, x32, dtype, top_k):
    """Dropless top-k routed feed-forward, mirroring ``ops.moe.MoEMlp``
    math exactly (router in f32 on the f32 LN output, expert ReLU MLPs
    in ``dtype``, Switch raw-top-prob / GShard renormalized combine,
    f32 result like the training block) — minus the capacity slots:
    at decode each token routes unconditionally. Identical to the
    training forward whenever capacity does not bind there
    (``moe_capacity_factor >= n_experts`` guarantees it; at the default
    1.0 a heavily imbalanced prompt may drop tokens in the training
    forward that decode keeps — dropless inference is the standard
    trade)."""
    gates = jax.nn.softmax(x32 @ p["gate"], axis=-1)  # [B, S, E] f32
    topv, topi = jax.lax.top_k(gates, top_k)
    if top_k == 1:
        weights = topv  # Switch: the raw top probability
    else:
        weights = topv / jnp.sum(topv, axis=-1, keepdims=True)
    xin = x32.astype(dtype)

    def one_expert(w1e, b1e, w2e, b2e):
        h = jax.nn.relu(xin @ w1e.astype(dtype) + b1e.astype(dtype))
        return h @ w2e.astype(dtype) + b2e.astype(dtype)

    # all-experts-masked-combine: E/top_k x the routed FLOPs, chosen
    # deliberately — static shapes, MXU-shaped matmuls, no per-token
    # weight gathers (at [D, H] per token those are worse than the
    # extra compute for the expert counts this decodes), and decode is
    # cache-bandwidth-bound anyway. Capacity-compacted routed execution
    # only pays at large E.
    ys = jax.vmap(one_expert)(p["w1"], p["b1"], p["w2"], p["b2"])
    onehots = jax.nn.one_hot(topi, p["gate"].shape[-1],
                             dtype=jnp.float32)  # [B, S, K, E]
    combine = jnp.einsum("bske,bsk->bse", onehots, weights)
    y = jnp.einsum("bse,ebsd->bsd", combine.astype(dtype), ys)
    return y.astype(jnp.float32)  # MoEMlp returns x.dtype = f32 LN out


def _ffn(p, x, dtype, eps, top_k):
    """ln2 -> feed-forward (dense GELU MLP, or MoE when the block
    carries a ``moe`` subtree), following Block's dtype conventions."""
    if "moe" in p:
        return _moe_ffn(p["moe"], _ln(x, p["ln2"], eps), dtype, top_k)
    hn = _ln(x, p["ln2"], eps).astype(dtype)
    y = _dense(hn, p["fc1"], dtype)
    return _dense(jax.nn.gelu(y), p["fc2"], dtype)


def _split_heads(t, h):
    b, s, d = t.shape
    return t.reshape(b, s, h, d // h)


def _block_prefill(p, x, h, dtype, eps, cs=_no_cs, top_k=1,
                   kv_valid=None):
    """Full causal pass over the prompt; returns (y, k, v).
    ``kv_valid`` ([B, s] bool, optional): key-column validity for
    left-padded ragged batches — pad columns never receive attention
    mass; pad QUERIES fall back to attending (only) themselves so the
    softmax stays finite (their outputs are never consumed)."""
    b, s, _ = x.shape
    hn = _ln(x, p["ln1"], eps).astype(dtype)
    q, k, v = jnp.split(_dense(hn, p["attn"]["wqkv"], dtype), 3, axis=-1)
    q, k, v = _split_heads(q, h), _split_heads(k, h), _split_heads(v, h)
    # TP: heads live on the model axis — the attention einsums below
    # then partition per-head with no resharding
    q = cs(q, None, None, "model", None)
    k = cs(k, None, None, "model", None)
    v = cs(v, None, None, "model", None)
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    mask = jnp.tril(jnp.ones((s, s), bool))
    if kv_valid is not None:
        mask = jnp.logical_or(
            jnp.logical_and(mask, kv_valid[:, None, None, :]),
            jnp.eye(s, dtype=bool)[None, None],
        )
    probs = jax.nn.softmax(jnp.where(mask, logits, -jnp.inf), axis=-1)
    att = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    att = att.reshape(b, s, -1).astype(dtype)
    x = x + _dense(att, p["attn"]["wo"], dtype)
    return x + _ffn(p, x, dtype, eps, top_k), k, v


def _block_decode(p, x_t, k_cache, v_cache, pos, h, dtype, eps,
                  cs=_no_cs, top_k=1, kv_valid=None):
    """One cached step: x_t [B, 1, D]; caches [B, S, H, Dh].
    ``kv_valid`` ([B, S] bool, optional): excludes left-pad cache
    columns from attention for ragged batches."""
    b = x_t.shape[0]
    hn = _ln(x_t, p["ln1"], eps).astype(dtype)
    q, k, v = jnp.split(_dense(hn, p["attn"]["wqkv"], dtype), 3, axis=-1)
    q, k, v = _split_heads(q, h), _split_heads(k, h), _split_heads(v, h)
    q = cs(q, None, None, "model", None)
    k = cs(k, None, None, "model", None)
    v = cs(v, None, None, "model", None)
    k_cache = jax.lax.dynamic_update_slice(k_cache, k, (0, pos, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(v_cache, v, (0, pos, 0, 0))
    mask = (jnp.arange(k_cache.shape[1]) <= pos)[None, :]
    if kv_valid is not None:
        mask = jnp.logical_and(mask, kv_valid)
    att = xla_decode_attention(q, k_cache, v_cache, mask)
    att = att.reshape(b, 1, -1).astype(dtype)
    x_t = x_t + _dense(att, p["attn"]["wo"], dtype)
    return (x_t + _ffn(p, x_t, dtype, eps, top_k), k_cache, v_cache)


def _write_pages(pages, layer, page_ids, offs, rows):
    """New K or V ``rows [..., H, Dh]`` into a ``[L, P, ps, H * Dh]``
    pool at ``(layer, page_ids, offs)`` (both ``[...]``): a token's
    heads side by side in one row of lanes. graftquant pools quantize
    the fresh rows over ``Dh`` and write BOTH leaves (data ``[..., H *
    Dh]``, scale ``[..., H]``) at the same place."""
    if isinstance(pages, QuantizedKV):
        rows = quantize_kv(rows)
    return jax.tree.map(
        lambda pool, new: pool.at[layer, page_ids, offs].set(new),
        pages, flatten_heads(rows))


def _window_table(page_table, window, page_size):
    """The table's leading ``ceil(window / page_size)`` entries: the
    pages a windowed paged attention may name."""
    n_win = (-(-int(window) // page_size) if window is not None
             else page_table.shape[1])
    return jax.lax.slice_in_dim(page_table, 0,
                                min(n_win, page_table.shape[1]), axis=1)


def _block_decode_slots(p, x_t, k_cache, v_cache, positions, h, dtype,
                        eps, cs=_no_cs, top_k=1, window=None,
                        attn_impl="xla", block_k=256, interpret=None,
                        kv_valid=None, uniform_positions=False,
                        page_table=None, page_size=None, layer=None):
    """Vector-position variant of :func:`_block_decode` — the shared
    decode body (:func:`_decode_horizon`). Each row (slot) writes its
    pending token's K/V at its OWN position, then attends over the
    cache prefix ``[0, window)`` (a STATIC slice: the engine picks
    ``window`` as the power-of-two bucket covering the longest active
    sequence, so the attention cost tracks real occupancy while the
    compiled-shape set stays bounded). ``window=None`` (or >= the
    cache) is the original full-``s_max`` step — the token-exactness
    reference.

    Writes always go to the FULL cache (an inactive row's frozen
    position may lie beyond the window; re-hitting its own column is
    the documented freeze behavior), only the attention reads are
    windowed. ``attn_impl`` selects the fused flash-decode kernel or
    the XLA reference (:mod:`...ops.pallas.decode_attention`).
    ``kv_valid`` ([B, S] bool, XLA path only): extra key-column
    validity for ragged left-padded batches — pad columns never
    receive attention mass (``generate``'s ``prompt_lengths`` path).
    ``uniform_positions=True`` asserts every row writes the SAME
    column (``generate``'s lockstep batch): the cache update then
    stays the cheap ``dynamic_update_slice`` instead of a per-row
    scatter — on TPU the scatter is markedly slower, and this is the
    hottest loop in the framework.

    **Paged mode** (``page_table`` + ``page_size`` + ``layer``,
    graftpage): ``k_cache``/``v_cache`` are the WHOLE pools, ALL
    layers' page storage ``[L, num_pages, page_size, H * Dh]`` (heads
    side by side in the lanes), carried through the layers untouched
    but for this layer's new rows, so the donated pool is written in
    place: each row's logical column ``p`` lives at ``(layer,
    page_table[row, p // page_size], p % page_size)``. The write
    scatters through the table; attention gathers through it
    (:func:`...ops.pallas.decode_attention.paged_decode_attention` —
    take-based XLA reference, or the Pallas kernel that copies each
    live page itself out of layer ``layer`` of the pool). A
    released slot's table row points at the scratch page 0, so the
    frozen-row re-write invariant (masked rows re-hit "their own
    column" each step) lands in scratch instead of a page since
    re-allocated to another tenant. Composes with ``window`` (the
    table is sliced to ``ceil(window / page_size)`` entries by the
    caller) and NOT with ``kv_valid``/``uniform_positions`` (serving
    slots only).
    """
    n = x_t.shape[0]
    hn = _ln(x_t, p["ln1"], eps).astype(dtype)
    q, k, v = jnp.split(_dense(hn, p["attn"]["wqkv"], dtype), 3, axis=-1)
    q = cs(_split_heads(q, h), None, None, "model", None)
    k = cs(_split_heads(k, h), None, None, "model", None)
    v = cs(_split_heads(v, h), None, None, "model", None)
    if page_table is not None:
        if kv_valid is not None or uniform_positions:
            raise ValueError(
                "paged decode composes with neither kv_valid nor "
                "uniform_positions (serving slots only)")
        ps = int(page_size)
        page_ids = jnp.take_along_axis(
            page_table, (positions // ps)[:, None], axis=1)[:, 0]
        offs = positions % ps
        # per-row write through the table: row j's K/V lands at its
        # own (layer, page, offset)
        k_cache = _write_pages(k_cache, layer, page_ids, offs, k[:, 0])
        v_cache = _write_pages(v_cache, layer, page_ids, offs, v[:, 0])
        att = paged_decode_attention(
            q, k_cache, v_cache, _window_table(page_table, window, ps),
            positions, layer=layer, window=window, impl=attn_impl,
            interpret=interpret)
        att = att.reshape(n, 1, -1).astype(dtype)
        x_t = x_t + _dense(att, p["attn"]["wo"], dtype)
        return (x_t + _ffn(p, x_t, dtype, eps, top_k), k_cache, v_cache)
    if uniform_positions:
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k, (0, positions[0], 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v, (0, positions[0], 0, 0))
    elif isinstance(k_cache, QuantizedKV):
        # graftquant slots: quantize the fresh K/V over Dh, scatter
        # data AND scale to each slot's own column
        rows = jnp.arange(n)
        qk, qv = quantize_kv(k[:, 0]), quantize_kv(v[:, 0])
        k_cache = QuantizedKV(
            k_cache.data.at[rows, positions].set(qk.data),
            k_cache.scale.at[rows, positions].set(qk.scale))
        v_cache = QuantizedKV(
            v_cache.data.at[rows, positions].set(qv.data),
            v_cache.scale.at[rows, positions].set(qv.scale))
    else:
        # per-slot column write: slot j's K/V lands at its own position
        # (generate's dynamic_update_slice, vectorized)
        rows = jnp.arange(n)
        k_cache = k_cache.at[rows, positions].set(k[:, 0])
        v_cache = v_cache.at[rows, positions].set(v[:, 0])
    if window is not None and window < k_cache.shape[1]:
        k_win = kv_slice_in_dim(k_cache, 0, window, axis=1)
        v_win = kv_slice_in_dim(v_cache, 0, window, axis=1)
        valid_win = (None if kv_valid is None
                     else jax.lax.slice_in_dim(kv_valid, 0, window,
                                               axis=1))
    else:
        k_win, v_win = k_cache, v_cache
        valid_win = kv_valid
    if valid_win is not None:
        if attn_impl == "pallas":
            raise ValueError(
                "kv_valid (ragged left-pad masking) composes only with "
                "the XLA decode path")
        mask = jnp.logical_and(
            jnp.arange(k_win.shape[1])[None, :] <= positions[:, None],
            valid_win)
        att = decode_attention(q, k_win, v_win, mask=mask, impl="xla")
    else:
        att = decode_attention(q, k_win, v_win, positions,
                               impl=attn_impl, block_k=block_k,
                               interpret=interpret)
    att = att.reshape(n, 1, -1).astype(dtype)
    x_t = x_t + _dense(att, p["attn"]["wo"], dtype)
    return (x_t + _ffn(p, x_t, dtype, eps, top_k), k_cache, v_cache)


# ------------------------------------------------------------- graftspec

# Knuth multiplicative constant for the unigram draft-table hash. ONE
# formula shared (test-pinned) by the host-side table builder
# (``serving.spec.NgramDrafter``, numpy — uint32 wraparound) and the
# in-scan device lookup below, the same host/device-hash discipline
# the PR 10 prefix cache uses for its prompt keys.
DRAFT_HASH_PRIME = 2654435761


def draft_bucket(tokens, n_buckets: int):
    """Draft-table bucket of each token id (jnp; uint32 wraparound)."""
    t = tokens.astype(jnp.uint32) * jnp.uint32(DRAFT_HASH_PRIME)
    return (t % jnp.uint32(n_buckets)).astype(jnp.int32)


def _block_verify_slots(p, x_t, k_cache, v_cache, positions, h, dtype,
                        eps, cs=_no_cs, top_k=1, window=None,
                        attn_impl="xla", interpret=None, *,
                        page_table, page_size, layer):
    """k-query VERIFY variant of :func:`_block_decode_slots`'s paged
    arm (graftspec): ``x_t`` is ``[N, K1, D]`` — each slot's pending
    token plus its ``K1 - 1`` draft proposals. Row ``i``'s K/V is
    written at column ``positions + i`` (all K1 columns, BEFORE the
    attention, so later rows see earlier rows' keys — the same
    write-then-attend order as the single-query step), then row ``i``
    attends ``[0, positions + i]`` through the k-query paged kernel or
    its XLA reference (:func:`...ops.pallas.decode_attention.
    paged_verify_decode_attention`).

    Rejected/overflow draft columns follow the stale-column
    invariant: a column beyond the slot's accepted frontier is masked
    by every later read until the frontier's own (correct) write
    overwrites it. Writes whose column falls beyond the slot's table
    land on the scratch page 0 (such a column could never be emitted
    anyway: ``position + remaining <= s_max - 1``), so a draft write
    can never touch a page owned by another tenant or a shared
    read-only prefix page. The whole pools and a static ``layer`` are
    carried, as :func:`_block_decode_slots` does."""
    n, k1, _ = x_t.shape
    hn = _ln(x_t, p["ln1"], eps).astype(dtype)
    q, k, v = jnp.split(_dense(hn, p["attn"]["wqkv"], dtype), 3, axis=-1)
    q = cs(_split_heads(q, h), None, None, "model", None)
    k = cs(_split_heads(k, h), None, None, "model", None)
    v = cs(_split_heads(v, h), None, None, "model", None)
    cols = positions[:, None] + jnp.arange(k1)[None, :]     # [N, K1]
    ps = int(page_size)
    blk = cols // ps
    n_tab = page_table.shape[1]
    page_ids = jnp.take_along_axis(
        page_table, jnp.clip(blk, 0, n_tab - 1), axis=1)
    page_ids = jnp.where(blk < n_tab, page_ids, 0)
    offs = cols % ps
    k_cache = _write_pages(k_cache, layer, page_ids, offs, k)
    v_cache = _write_pages(v_cache, layer, page_ids, offs, v)
    att = paged_verify_decode_attention(
        q, k_cache, v_cache, _window_table(page_table, window, ps),
        positions, layer=layer, window=window, impl=attn_impl,
        interpret=interpret)
    att = att.reshape(n, k1, -1).astype(dtype)
    x_t = x_t + _dense(att, p["attn"]["wo"], dtype)
    return (x_t + _ffn(p, x_t, dtype, eps, top_k), k_cache, v_cache)


# ------------------------------------------------------------- the seam

class GPTServing:
    """What the serving engine asks of a MODEL FAMILY, answered for the
    GPT family with the block functions above, untouched.

    The engine (``serving.engine``), its pool (``serving.kv_pages``)
    and the shared decode core
    (:func:`_decode_horizon`) know a family only through this surface;
    a model of another family carries its own as ``model.
    serving_family`` (:func:`serving_family`). The two cache operands
    the engine threads through every program are the family's two
    ``cache_rows``: K and V here, the latent and the position key of a
    latent-attention family.
    """

    name = "gpt"
    refuses: dict = {}      # engine option -> why this family lacks it

    def cache_rows(self, model):
        """``((name, trailing shape, dtype), (...))``: what one token
        of one layer keeps in each of the two caches. A family whose
        layers are of two kinds adds ``(layers, columns)`` to each
        (:func:`cache_pools`)."""
        h = model.num_heads
        row = (h, model.hidden_size // h)
        return (("k", row, model.dtype), ("v", row, model.dtype))

    def aux_shape(self, model):
        """Shape of the integers a decode horizon returns behind its
        token block (None: none)."""
        return None

    def logits(self, model, params, x, cs=_no_cs):
        return _logits(params, x, getattr(model, "ln_eps", _LN_EPS), cs)

    def prefill(self, model, params, prompt, cs=_no_cs, cs_cache=None):
        """Whole-prompt prefill of ``prompt [1, S]`` -> ``(x, k_pref,
        v_pref)``, caches ``[L, 1, S, *row]``."""
        return _prefill(model, params, prompt, prompt.shape[1], cs=cs,
                        cs_cache=cs_cache)

    def chunk(self, model, params, k_pref, v_pref, tokens, start,
              cs=_no_cs, cs_cache=None, attn_impl="xla"):
        """One ``[1, chunk]`` slice of an incremental prefill at
        ``[start, start + chunk)`` against the standalone caches (the
        attention plain XLA whatever the engine's ``attn_impl``)."""
        dtype = model.dtype
        eps = getattr(model, "ln_eps", _LN_EPS)
        moe_k = getattr(model, "moe_top_k", 1)
        x = _embed_at(params, tokens, start, dtype)
        new_k, new_v = [], []
        for i in range(model.num_layers):
            x, kc, vc = _block_chunk_prefill(
                params[f"block_{i}"], x, k_pref[i], v_pref[i],
                start, model.num_heads, dtype, eps, cs, moe_k)
            new_k.append(kc)
            new_v.append(vc)
        return (x, cs_cache(jnp.stack(new_k)), cs_cache(jnp.stack(new_v)))

    def decode_step(self, model, params, k_caches, v_caches, positions,
                    last_tokens, *, cs=_no_cs, cs_cache, window=None,
                    attn_impl="xla", block_k=256, kv_valid=None,
                    uniform_positions=False, page_table=None,
                    page_size=None, offsets=None):
        """One pending token a slot through every block; returns ``(x_t
        [N, 1, D], k_caches, v_caches, aux)``."""
        dtype = model.dtype
        eps = getattr(model, "ln_eps", _LN_EPS)
        moe_k = getattr(model, "moe_top_k", 1)
        ids = (positions if offsets is None
               else jnp.maximum(positions - offsets, 0))
        # cast-then-add, the model's own order — see _embed
        pos_emb = params["pos_embed"][ids][:, None, :]
        x_t = (params["embed"][last_tokens][:, None, :].astype(dtype)
               + pos_emb.astype(dtype))
        block = partial(
            _block_decode_slots, positions=positions, h=model.num_heads,
            dtype=dtype, eps=eps, cs=cs, top_k=moe_k, window=window,
            attn_impl=attn_impl, block_k=block_k, kv_valid=kv_valid,
            uniform_positions=uniform_positions, page_table=page_table,
            page_size=page_size)
        if page_table is not None:
            # the page pools travel WHOLE through the layers: a layer
            # writes its rows into them and reads its pages out of them
            # in place, so the donated pools are never copied
            for i in range(model.num_layers):
                x_t, k_caches, v_caches = block(
                    params[f"block_{i}"], x_t, k_caches, v_caches,
                    layer=i)
            return x_t, cs_cache(k_caches), cs_cache(v_caches), None
        new_k, new_v = [], []
        for i in range(model.num_layers):
            x_t, kc, vc = block(params[f"block_{i}"], x_t, k_caches[i],
                                v_caches[i])
            new_k.append(kc)
            new_v.append(vc)
        return (x_t, cs_cache(stack_kv(new_k)), cs_cache(stack_kv(new_v)),
                None)


GPT_SERVING = GPTServing()


def serving_family(model):
    """The family surface of ``model``: its own (``model.
    serving_family``) or, for every flax GPT, :data:`GPT_SERVING`."""
    return getattr(model, "serving_family", None) or GPT_SERVING


def cache_pools(model):
    """The family's two ``cache_rows`` in full: ``(name, row, dtype,
    layers, columns)``. ``layers``: how many of the model's layers
    keep this row (their pool's leading axis; the three-field form
    means all of them). ``columns``: how many of a slot's columns the
    pool holds at most (None: the whole context, pages under the page
    table and the allocator; a number: a model's sliding window, held
    as a ring of ``ceil(columns / page_size) + 1`` pages a slot)."""
    return tuple(
        tuple(entry) if len(entry) == 5
        else tuple(entry) + (model.num_layers, None)
        for entry in serving_family(model).cache_rows(model))


def pref_cache_shapes(model, width: int):
    """Shapes of the two standalone prefill caches ``[layers, 1,
    width, *row]`` a chunked prefill accumulates into (every column of
    the prompt in both; a ring's window is cut at the splice)."""
    return tuple((layers, 1, int(width)) + tuple(row)
                 for _, row, _, layers, _ in cache_pools(model))


def _decode_horizon(model, params, k_caches, v_caches, positions,
                    last_tokens, active, remaining, eos_ids, keys, *,
                    cs=_no_cs, cs_cache=None, window=None,
                    attn_impl="xla", block_k=256, temperature=0.0,
                    top_k=0, top_p=0.0, offsets=None, kv_valid=None,
                    uniform_positions=False, page_table=None,
                    page_size=None, draft_k=0, draft_table=None,
                    draft_model=None, draft_params=None,
                    draft_k_caches=None, draft_v_caches=None):
    """THE fused multi-step decode loop: ``H = keys.shape[0]`` cached
    decode steps as one ``lax.scan`` — one dispatch, zero host
    round-trips inside. Both decode callers run on this core:
    :func:`generate`'s whole decode tail is one call of it, and the
    serving engine's jitted horizon program is a thin wrapper (so the
    two cannot drift — the engine==generate token-exactness pin rests
    on the shared body).

    Per-row freeze gating runs ON DEVICE so a horizon stays token-exact
    with H single steps even when a row finishes mid-horizon: a row
    whose sampled token hits its ``eos_ids`` entry, or whose
    ``remaining`` budget reaches zero, emits that final token and then
    freezes — position pinned (its masked write re-hits the same
    column), pending token unchanged, later steps emit ``-1`` for it.
    :func:`generate` passes never-binding gates (``eos_ids = -1``,
    ``remaining > H``) so every row runs the full horizon, exactly its
    old scan.

    Args:
      model: the ``GPT`` (geometry/dtype/eps/MoE statics).
      k_caches, v_caches: ``[L, N, S, H, Dh]`` slot caches.
      positions: ``[N]`` int32 — each row's next write column.
      last_tokens: ``[N]`` int32 pending tokens (consumed by step 0).
      active: ``[N]`` bool — frozen rows re-write their own column and
        emit ``-1``.
      remaining: ``[N]`` int32 decode-token budgets (decremented per
        emitted token; 0 freezes the row after its final emit).
      eos_ids: ``[N]`` int32 stop tokens (``-1`` = none; token ids are
        non-negative so ``-1`` never matches).
      keys: ``[H, 2]`` uint32 per-step sample keys (ignored when
        ``temperature == 0``).
      window / attn_impl / block_k / kv_valid / uniform_positions: see
        :func:`_block_decode_slots` (``generate`` sets
        ``uniform_positions`` — its rows advance in lockstep, so cache
        writes stay ``dynamic_update_slice``; the engine's slots hold
        genuinely divergent positions and take the scatter).
      offsets: ``[N]`` int32 left-pad offsets for ragged ``generate``
        (position-embedding ids become ``max(positions - offsets, 0)``).
      page_table / page_size: paged-KV mode (graftpage): ``k_caches``/
        ``v_caches`` are ``[L, num_pages, page_size, H * Dh]`` page
        storage and ``page_table`` ``[N, pages_per_slot]`` int32 maps
        each slot's logical columns onto pages (read-only inside the
        scan — allocation is host-side, pre-jit). See
        :func:`_block_decode_slots`.
      draft_k (graftspec): > 0 arms SPECULATIVE decode — each scan
        step proposes ``draft_k`` tokens per slot, verifies them with
        ONE batched (draft_k + 1)-query target pass
        (:func:`_block_verify_slots`), and accepts greedily ON DEVICE:
        the emitted prefix per pass is ``g_0 .. g_a`` where ``a`` is
        the leading-match count of drafts against the target's own
        greedy outputs, composed with the same eos/budget freeze
        gating as the non-speculative step (a pass emits between 1 and
        draft_k + 1 tokens per active row; the finishing token is
        emitted, then the row freezes). Greedy only (``temperature``
        must be 0); every emitted token is a target-model greedy
        continuation of the accepted history, which is what makes the
        accepted streams token-identical to the non-speculative
        engine (pinned across the serving matrix).
      draft_table: self-drafting mode — ``[N, buckets, draft_k]``
        int32 per-slot unigram n-gram tables (entry ``-1`` = no
        proposal, never accepted); looked up by
        :func:`draft_bucket` on each pass's pending token.
      draft_model / draft_params / draft_k_caches / draft_v_caches:
        draft-model mode — a small registry GPT proposes the k tokens
        autoregressively inside the scan against its own dense
        ``[L_d, N, S, H_d, Dh_d]`` caches (carried through the scan
        and returned at the END of ``carry``; the draft runs
        ``draft_k + 1`` steps so its cache stays gap-free under full
        acceptance).

    Returns ``(tokens, carry)``: ``tokens`` ``[H, N]`` int32 (``-1``
    where the row was frozen BEFORE the step) — with ``draft_k`` > 0
    the block is ``[H * (draft_k + 1), N]`` in step-major order (pass
    j's k+1 emission rows, then pass j+1's), ``-1`` marking
    rejected/frozen rows, so a drain loop replays finish rules row by
    row exactly as in the non-speculative shape. ``carry`` is the
    updated ``(k_caches, v_caches, positions, last_tokens, active,
    remaining)`` (+ the draft caches in draft-model mode).
    """
    family = serving_family(model)
    if cs_cache is None:
        def cs_cache(c):
            return c

    if draft_k:
        if temperature > 0.0:
            raise ValueError(
                "speculative decode (draft_k > 0) is greedy-only: a "
                "sampled stream cannot be verified by argmax matching "
                "(temperature > 0)")
        if (draft_table is None) == (draft_model is None):
            raise ValueError(
                "draft_k > 0 needs exactly one draft source: "
                "draft_table (self-drafting) or draft_model (+ params "
                "and caches)")
        if kv_valid is not None or uniform_positions or page_table is None:
            raise ValueError(
                "speculative decode composes with neither kv_valid "
                "nor uniform_positions and verifies on pages (the "
                "serving engine's slots only)")
        return _decode_horizon_spec(
            model, params, k_caches, v_caches, positions, last_tokens,
            active, remaining, eos_ids, keys, cs=cs, cs_cache=cs_cache,
            window=window, attn_impl=attn_impl,
            page_table=page_table, page_size=page_size,
            draft_k=int(draft_k), draft_table=draft_table,
            draft_model=draft_model, draft_params=draft_params,
            draft_k_caches=draft_k_caches,
            draft_v_caches=draft_v_caches)

    def step(carry, key):
        (k_caches, v_caches, positions, last_tokens, active,
         remaining) = carry
        x_t, k_caches, v_caches, aux = family.decode_step(
            model, params, k_caches, v_caches, positions, last_tokens,
            cs=cs, cs_cache=cs_cache, window=window, attn_impl=attn_impl,
            block_k=block_k, kv_valid=kv_valid,
            uniform_positions=uniform_positions, page_table=page_table,
            page_size=page_size, offsets=offsets)
        logits = family.logits(model, params, x_t, cs)[:, 0]
        nxt = _sample(logits, temperature, top_k, top_p,
                      key).astype(jnp.int32)
        # the finishing token IS emitted (the step engine appends the
        # token before checking eos/budget — same order here), then the
        # row freezes for the rest of the horizon
        emitted = jnp.where(active, nxt, -1)
        remaining = jnp.where(active, remaining - 1, remaining)
        finished = jnp.logical_and(
            active, jnp.logical_or(nxt == eos_ids, remaining <= 0))
        positions = jnp.where(active, positions + 1, positions)
        last_tokens = jnp.where(active, nxt, last_tokens)
        active = jnp.logical_and(active, jnp.logical_not(finished))
        return (k_caches, v_caches, positions, last_tokens, active,
                remaining), (emitted if aux is None else (emitted, aux))

    carry, tokens = jax.lax.scan(
        step, (k_caches, v_caches, positions, last_tokens, active,
               remaining), keys)
    if isinstance(tokens, tuple):
        # a family with per-step integers (expert counts): summed over
        # the horizon and packed BEHIND the token block, so they come
        # back in the block's own readback (serving.engine._drain_one)
        tokens, aux = tokens
        tokens = jnp.concatenate(
            [tokens.reshape(-1),
             jnp.sum(aux, axis=0).astype(jnp.int32).reshape(-1)])
    return tokens, carry


def _decode_horizon_spec(model, params, k_caches, v_caches, positions,
                         last_tokens, active, remaining, eos_ids, keys,
                         *, cs, cs_cache, window, attn_impl,
                         page_table, page_size, draft_k, draft_table,
                         draft_model, draft_params, draft_k_caches,
                         draft_v_caches):
    """The speculative body of :func:`_decode_horizon` (graftspec):
    ``H`` draft-then-verify passes as one ``lax.scan``. Per pass and
    slot: propose ``k = draft_k`` tokens (n-gram table lookup, or the
    draft model run ``k + 1`` cached steps), run ONE batched
    ``k + 1``-query target pass (the pending token + the k drafts —
    the same weight/KV stream one decode step owes, at ``k + 1`` MXU
    query rows), take the target's greedy outputs ``g_0 .. g_k``, and
    emit the verified prefix: ``g_i`` emits iff every draft before it
    matched (``d_j == g_{j-1}`` for ``j <= i``), the row is active,
    ``i < remaining``, and no earlier ``g_j`` was the stop token —
    i.e. exactly the tokens ``i`` sequential non-speculative steps
    would have emitted, in order, with the same freeze gating. The
    per-row acceptance is pure on-device masking: no shape depends on
    it, so one compiled program serves every acceptance pattern."""
    dtype = model.dtype
    eps = getattr(model, "ln_eps", _LN_EPS)
    moe_k = getattr(model, "moe_top_k", 1)
    h = model.num_heads
    n_layers = model.num_layers
    kk = draft_k
    vocab = model.vocab_size
    n = positions.shape[0]

    def draft_with_model(dk, dv, positions, last_tokens):
        """k+1 cached draft-model steps (the last one only feeds the
        draft cache's column ``p + k``, so full acceptance leaves no
        gap for the NEXT pass to read stale data through); proposals
        are the first k greedy outputs."""
        d_dtype = draft_model.dtype
        d_eps = getattr(draft_model, "ln_eps", _LN_EPS)
        d_moe = getattr(draft_model, "moe_top_k", 1)
        d_h = draft_model.num_heads
        d_pe = draft_params["pos_embed"]
        t = last_tokens
        p_d = positions
        toks = []
        for _ in range(kk + 1):
            ids = jnp.clip(p_d, 0, d_pe.shape[0] - 1)
            x_d = (draft_params["embed"][t][:, None, :].astype(d_dtype)
                   + d_pe[ids][:, None, :].astype(d_dtype))
            new_dk, new_dv = [], []
            for i in range(draft_model.num_layers):
                x_d, kc, vc = _block_decode_slots(
                    draft_params[f"block_{i}"], x_d, dk[i], dv[i],
                    p_d, d_h, d_dtype, d_eps, _no_cs, d_moe,
                    attn_impl="xla")
                new_dk.append(kc)
                new_dv.append(vc)
            dk, dv = jnp.stack(new_dk), jnp.stack(new_dv)
            t = jnp.argmax(
                _logits(draft_params, x_d, d_eps)[:, 0],
                axis=-1).astype(jnp.int32)
            toks.append(t)
            p_d = p_d + 1
        return jnp.stack(toks[:kk], axis=1), dk, dv  # [N, k]

    def step(carry, key):
        del key  # greedy-only (validated by the caller)
        if draft_model is not None:
            (k_caches, v_caches, positions, last_tokens, active,
             remaining, dk, dv) = carry
            drafts, dk, dv = draft_with_model(dk, dv, positions,
                                              last_tokens)
            draft_ok = jnp.ones(drafts.shape, bool)
        else:
            (k_caches, v_caches, positions, last_tokens, active,
             remaining) = carry
            bucket = draft_bucket(last_tokens, draft_table.shape[1])
            drafts = draft_table[jnp.arange(n), bucket]      # [N, k]
            draft_ok = drafts >= 0  # -1 = no proposal, never accepted
        drafts = jnp.where(draft_ok, jnp.clip(drafts, 0, vocab - 1), 0)

        # ---- verify: ONE (k+1)-query target pass
        qtok = jnp.concatenate([last_tokens[:, None], drafts], axis=1)
        cols = positions[:, None] + jnp.arange(kk + 1)[None, :]
        pe = params["pos_embed"]
        ids = jnp.clip(cols, 0, pe.shape[0] - 1)
        x_t = (params["embed"][qtok].astype(dtype)
               + pe[ids].astype(dtype))
        verify = partial(
            _block_verify_slots, positions=positions, h=h, dtype=dtype,
            eps=eps, cs=cs, top_k=moe_k, window=window,
            attn_impl=attn_impl, page_table=page_table,
            page_size=page_size)
        for i in range(n_layers):  # whole pools, written in place
            x_t, k_caches, v_caches = verify(
                params[f"block_{i}"], x_t, k_caches, v_caches, layer=i)
        logits = _logits(params, x_t, eps, cs)        # [N, k+1, V]
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

        # ---- greedy acceptance, composed with the freeze gates
        match = jnp.logical_and(drafts == greedy[:, :kk], draft_ok)
        a = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1),
                    axis=1)                            # [N] leading matches
        idx = jnp.arange(kk + 1)[None, :]
        is_eos = greedy == eos_ids[:, None]
        eos_before = (jnp.cumsum(is_eos.astype(jnp.int32), axis=1)
                      - is_eos.astype(jnp.int32))
        can = jnp.logical_and(
            jnp.logical_and(idx <= a[:, None], idx < remaining[:, None]),
            jnp.logical_and(eos_before == 0, active[:, None]))
        e = jnp.sum(can.astype(jnp.int32), axis=1)     # [N] emitted
        emitted = jnp.where(can, greedy, -1)           # [N, k+1]
        last_tokens = jnp.where(
            e > 0,
            jnp.take_along_axis(greedy, jnp.maximum(e - 1, 0)[:, None],
                                axis=1)[:, 0],
            last_tokens)
        remaining = remaining - e
        hit_eos = jnp.any(jnp.logical_and(can, is_eos), axis=1)
        finished = jnp.logical_and(
            active, jnp.logical_or(hit_eos, remaining <= 0))
        positions = positions + e
        active = jnp.logical_and(active, jnp.logical_not(finished))
        out = (cs_cache(k_caches), cs_cache(v_caches),
               positions, last_tokens, active, remaining)
        if draft_model is not None:
            out = out + (dk, dv)
        return out, emitted

    carry0 = (k_caches, v_caches, positions, last_tokens, active,
              remaining)
    if draft_model is not None:
        carry0 = carry0 + (draft_k_caches, draft_v_caches)
    carry, toks = jax.lax.scan(step, carry0, keys)
    # [H, N, k+1] -> [H * (k+1), N], step-major: the drain loop reads
    # the block exactly like H*(k+1) single steps with -1 holes
    tokens = jnp.moveaxis(toks, 2, 1).reshape(-1, n)
    return tokens, carry


def _block_chunk_prefill(p, x, k_cache, v_cache, start, h, dtype, eps,
                         cs=_no_cs, top_k=1):
    """One chunk of an incremental prefill: ``x`` [B, C, D] holds the
    prompt tokens at absolute positions ``[start, start + C)``;
    ``k_cache``/``v_cache`` [B, W, H, Dh] already hold the prefix
    columns ``[0, start)`` from earlier chunks. Writes this chunk's K/V
    at ``[start, start + C)`` and attends row ``r`` to columns
    ``[0, start + r]`` — exactly the causal set the one-shot
    :func:`_block_prefill` gives that token, so chunked and whole-prompt
    prefill are token-equivalent. Right-pad rows of a final partial
    chunk write garbage beyond the prompt length; those columns stay
    masked until the decode loop overwrites them (the standard stale-
    column invariant)."""
    b, c, _ = x.shape
    hn = _ln(x, p["ln1"], eps).astype(dtype)
    q, k, v = jnp.split(_dense(hn, p["attn"]["wqkv"], dtype), 3, axis=-1)
    q = cs(_split_heads(q, h), None, None, "model", None)
    k = cs(_split_heads(k, h), None, None, "model", None)
    v = cs(_split_heads(v, h), None, None, "model", None)
    k_cache = jax.lax.dynamic_update_slice(k_cache, k, (0, start, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(v_cache, v, (0, start, 0, 0))
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k_cache.astype(jnp.float32)) * scale  # [B,H,C,W]
    w = k_cache.shape[1]
    mask = (jnp.arange(w)[None, :]
            <= start + jnp.arange(c)[:, None])  # [C, W]
    probs = jax.nn.softmax(
        jnp.where(mask[None, None], logits, -jnp.inf), axis=-1)
    att = jnp.einsum("bhqk,bkhd->bqhd", probs,
                     v_cache.astype(jnp.float32))
    att = att.reshape(b, c, -1).astype(dtype)
    x = x + _dense(att, p["attn"]["wo"], dtype)
    return x + _ffn(p, x, dtype, eps, top_k), k_cache, v_cache


def _embed_at(params, tokens, start, dtype):
    """Embed ``tokens`` [B, C] at absolute positions ``start + r``
    (traced ``start``), clamping position ids into the table — pad rows
    past the prompt may sit beyond ``max_seq_len``; their (clamped)
    embeddings are never attended to. The one-shot paths use
    :func:`_embed`'s ``dynamic_slice`` instead, whose own clamping
    would SHIFT valid rows near the table edge."""
    c = tokens.shape[1]
    ids = jnp.clip(start + jnp.arange(c)[None, :], 0,
                   params["pos_embed"].shape[0] - 1)
    pos = params["pos_embed"][ids]  # [1, C, D] (B=1 broadcast)
    return (params["embed"][tokens].astype(dtype) + pos.astype(dtype))


def _embed(params, tokens, pos_start, dtype, offsets=None):
    s = tokens.shape[1]
    if offsets is None:
        pos = jax.lax.dynamic_slice_in_dim(
            params["pos_embed"], pos_start, s, axis=0)
    else:
        # ragged left-padded batch: row i's first REAL token sits at
        # column offsets[i] and must get position 0; pad columns clamp
        # to position 0 (their embeddings are never attended to)
        ids = jnp.maximum(
            pos_start + jnp.arange(s)[None, :] - offsets[:, None], 0)
        pos = params["pos_embed"][ids]  # [B, s, D]
    # cast-then-add, exactly as GPT.__call__ does: under bf16,
    # bf16(a) + bf16(b) != bf16(a + b) and the drift flips tokens
    return (params["embed"][tokens].astype(dtype) + pos.astype(dtype))


def _logits(params, x, eps, cs=_no_cs):
    h = _ln(x, params["ln_final"], eps)
    # TP: the [D, V] head kernel is vocab-sharded; logits stay sharded
    # through the bias add, argmax/sampling gathers only [B] tokens
    out = cs(h @ params["head"]["kernel"].astype(jnp.float32),
             None, None, "model")
    if "bias" in params["head"]:  # absent on head_bias=False models
        out = out + params["head"]["bias"]
    return out


def _prefill(model, params, prompt, s_max, *, cs=_no_cs,
             cs_cache=None, offsets=None, kv_valid=None):
    """One vectorized causal pass over the prompt; returns ``(x,
    k_caches, v_caches)`` with caches ``[L, B, s_max, H, Dh]`` written
    on ``[0, t)``. ONE copy shared by :func:`generate` and
    :func:`beam_search` so their prefills cannot drift (dtype/eps/MoE
    conventions all come from ``model`` here)."""
    b, t = prompt.shape
    dtype = model.dtype
    eps = getattr(model, "ln_eps", _LN_EPS)
    moe_k = getattr(model, "moe_top_k", 1)
    h = model.num_heads
    head_dim = model.hidden_size // h
    n_layers = model.num_layers
    if cs_cache is None:
        def cs_cache(c):
            return c
    x = _embed(params, prompt, 0, dtype, offsets)
    k_caches = cs_cache(jnp.zeros((n_layers, b, s_max, h, head_dim),
                                  dtype))
    v_caches = cs_cache(jnp.zeros((n_layers, b, s_max, h, head_dim),
                                  dtype))
    for i in range(n_layers):
        x, k, v = _block_prefill(params[f"block_{i}"], x, h, dtype,
                                 eps, cs, moe_k,
                                 None if kv_valid is None
                                 else kv_valid[:, :t])
        k_caches = k_caches.at[i, :, :t].set(k.astype(dtype))
        v_caches = v_caches.at[i, :, :t].set(v.astype(dtype))
    return x, cs_cache(k_caches), cs_cache(v_caches)


def _sample(logits, temperature, top_k, top_p, key):
    """[B, V] logits -> [B] tokens (greedy when temperature == 0)."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1)
    logits = logits / temperature
    if top_k:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p and top_p < 1.0:
        # nucleus: keep the smallest prefix of probability-sorted tokens
        # whose cumulative mass reaches top_p (the top token always
        # stays; probability ties at the cut are kept together).
        # top_p=1.0 is a true no-op ABOVE, not here: f32 cumsum on a
        # big vocab can hit 1.0 early and drop tail tokens
        probs = jax.nn.softmax(logits, axis=-1)
        sorted_p = jnp.sort(probs, axis=-1)[:, ::-1]
        before = jnp.cumsum(sorted_p, axis=-1) - sorted_p
        kept = before < top_p
        cut = jnp.min(jnp.where(kept, sorted_p, jnp.inf), axis=-1,
                      keepdims=True)
        logits = jnp.where(probs >= cut, logits, -jnp.inf)
    return jax.random.categorical(key, logits, axis=-1)


@partial(jax.jit, static_argnames=("model", "max_new_tokens",
                                   "temperature", "top_k", "top_p",
                                   "mesh"))
def generate(
    model,
    params,
    prompt: jax.Array,
    *,
    max_new_tokens: int,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 0.0,
    rng: Optional[jax.Array] = None,
    mesh: Optional[Mesh] = None,
    prompt_lengths: Optional[jax.Array] = None,
) -> jax.Array:
    """Generate ``max_new_tokens`` continuations of ``prompt``.

    Args:
      model: the ``GPT`` the params belong to — supplies geometry
        (heads, dtype, max_seq_len, moe_top_k); hashable, so it is a
        jit static. MoE models decode with dropless routing (see
        ``_moe_ffn``); SP models must pass their dense clone
        (``model.clone(seq_axis=None)`` — identical params).
      params: plain GPT param tree (as trained). For tensor-parallel
        decode place it with :func:`shard_params_for_tp_decode` first
        (replicated params + a mesh still compute correctly — GSPMD
        reshards — but the memory win comes from sharded placement).
      prompt: ``[B, T]`` int tokens, ``T + max_new_tokens <=
        model.max_seq_len``.
      temperature: 0 = greedy; else softmax temperature sampling.
      top_k: restrict sampling to the k highest logits (0 = full vocab).
      top_p: nucleus sampling — restrict to the smallest set of tokens
        whose cumulative probability reaches ``top_p`` (0 = off;
        composes with ``top_k``, applied after it).
      rng: PRNGKey (required when temperature > 0).
      prompt_lengths: optional ``[B]`` int array for RAGGED batches:
        each row of ``prompt`` must be LEFT-padded to the common
        length ``T`` with its real tokens in columns ``[T - L_i, T)``
        (any pad token id works — pad columns are excluded from
        attention and get clamped positions, so their values never
        influence the output). Row ``i`` then generates exactly what a
        single-row call on its unpadded prompt would (test-pinned).
        Caller contract: ``1 <= L_i <= T`` (traced values — not
        validated at trace time).
      mesh: optional ``Mesh`` with a ``model`` axis: attention heads,
        KV caches and the vocab dim of the head matmul are then sharded
        over it (Megatron-style TP decode, prefill AND decode). The
        axis size must divide the number of heads. Same tokens as the
        single-shard path — TP is an execution strategy, not different
        math (``tests/test_generate.py`` pins this).

    Returns ``[B, T + max_new_tokens]`` tokens (prompt included).
    """
    b, t = prompt.shape
    s_max = t + max_new_tokens
    if serving_family(model) is not GPT_SERVING:
        raise NotImplementedError(
            f"generate() decodes over dense caches, which the "
            f"{serving_family(model).name} family does not have: serve "
            "it through ServingEngine(kv_layout='paged')")
    if max_new_tokens < 1:
        raise ValueError(
            f"max_new_tokens must be >= 1, got {max_new_tokens}"
        )
    if top_k < 0 or top_k > model.vocab_size:
        raise ValueError(
            f"top_k must be in [0, vocab_size={model.vocab_size}], "
            f"got {top_k}"
        )
    if not 0.0 <= top_p <= 1.0:
        raise ValueError(f"top_p must be in [0, 1], got {top_p}")
    if s_max > model.max_seq_len:
        raise ValueError(
            f"prompt {t} + max_new_tokens {max_new_tokens} exceeds "
            f"max_seq_len={model.max_seq_len}"
        )
    if temperature > 0.0 and rng is None:
        raise ValueError("sampling (temperature > 0) requires rng")
    if getattr(model, "seq_axis", None) is not None:
        raise NotImplementedError(
            "generate wants the dense view of an SP model — pass "
            "model.clone(seq_axis=None) (the params are identical; "
            "train_lm.py --sample does this)"
        )
    if mesh is not None:
        if "model" not in mesh.axis_names:
            raise ValueError(
                f"TP decode needs a 'model' mesh axis, got "
                f"{mesh.axis_names}")
        tp = int(mesh.shape["model"])
        if model.num_heads % tp:
            raise ValueError(
                f"num_heads={model.num_heads} not divisible by the "
                f"model axis size {tp}")
    offsets = None
    kv_valid = None
    if prompt_lengths is not None:
        if prompt_lengths.shape != (b,):
            raise ValueError(
                f"prompt_lengths must have shape ({b},), got "
                f"{prompt_lengths.shape}")
        offsets = (t - prompt_lengths).astype(jnp.int32)  # [B]
        # key-column validity over the FULL cache: pad columns
        # [0, offset) never receive attention; prompt + generated
        # columns do
        kv_valid = jnp.arange(s_max)[None, :] >= offsets[:, None]
    cs = _make_cs(mesh)
    eps = getattr(model, "ln_eps", _LN_EPS)

    def cs_cache(c):
        # caches [L, B, S, H, Dh]: resident head-sharded — the per-chip
        # KV memory drops 1/tp, the actual capacity win of TP decode
        return cs(c, None, None, None, "model", None)

    # ---- prefill: one vectorized causal pass, caches written [0, t)
    x, k_caches, v_caches = _prefill(
        model, params, prompt, s_max, cs=cs, cs_cache=cs_cache,
        offsets=offsets, kv_valid=kv_valid)
    first_logits = _logits(params, x[:, -1:], eps, cs)[:, 0]  # [B, V]

    keys = (jax.random.split(rng, max_new_tokens) if rng is not None
            else jnp.zeros((max_new_tokens, 2), jnp.uint32))
    tok0 = _sample(first_logits, temperature, top_k, top_p,
                   keys[0]).astype(jnp.int32)

    # decode tail: ONE call of the shared fused-scan core (the same
    # body the serving engine's horizon program runs). Step j consumes
    # token j-1 (written at position t+j-1) and emits token j; the
    # freeze gates never bind here (no EOS, budget > steps), so every
    # row runs all max_new_tokens - 1 steps.
    if max_new_tokens > 1:
        toks, _ = _decode_horizon(
            model, params, k_caches, v_caches,
            jnp.full((b,), t, jnp.int32), tok0,
            jnp.ones((b,), bool),
            jnp.full((b,), max_new_tokens, jnp.int32),
            jnp.full((b,), -1, jnp.int32), keys[1:], cs=cs,
            cs_cache=cs_cache, temperature=temperature, top_k=top_k,
            top_p=top_p, offsets=offsets, kv_valid=kv_valid,
            uniform_positions=True)
        generated = jnp.concatenate(
            [tok0[:, None], jnp.moveaxis(toks, 0, 1)], axis=1)
    else:
        generated = tok0[:, None]
    return jnp.concatenate([prompt, generated], axis=1)


@partial(jax.jit, static_argnames=("model", "max_new_tokens",
                                   "beam_size"))
def beam_search(
    model,
    params,
    prompt: jax.Array,
    *,
    max_new_tokens: int,
    beam_size: int,
) -> tuple:
    """Beam-search decoding over the same KV-cached machinery.

    Standard log-probability beam search, no length penalty (scores
    are summed token log-probs — document-level reranking belongs to
    the caller). ``beam_size=1`` is exactly greedy :func:`generate`,
    and ``beam_size >= V**(max_new_tokens-1)`` is exhaustive (the
    tiny-vocab test pins beam == brute-force argmax).

    Args:
      model: the ``GPT`` the params belong to (dense or MoE; pass the
        dense clone of an SP model).
      prompt: ``[B, T]`` int tokens (uniform length).
      beam_size: beams kept per batch row.

    Returns ``(tokens, scores)``: ``tokens`` ``[B, K, T +
    max_new_tokens]`` (prompt included), ``scores`` ``[B, K]`` summed
    log-probs, both sorted best-first along K.
    """
    b, t = prompt.shape
    s_max = t + max_new_tokens
    k_beams = beam_size
    if max_new_tokens < 1:
        raise ValueError(
            f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if k_beams < 1 or k_beams > model.vocab_size:
        raise ValueError(
            f"beam_size must be in [1, vocab_size={model.vocab_size}], "
            f"got {k_beams}")
    if s_max > model.max_seq_len:
        raise ValueError(
            f"prompt {t} + max_new_tokens {max_new_tokens} exceeds "
            f"max_seq_len={model.max_seq_len}")
    if getattr(model, "seq_axis", None) is not None:
        raise NotImplementedError(
            "beam_search wants the dense view of an SP model — pass "
            "model.clone(seq_axis=None)")
    dtype = model.dtype
    eps = getattr(model, "ln_eps", _LN_EPS)
    moe_k = getattr(model, "moe_top_k", 1)
    h = model.num_heads
    n_layers = model.num_layers
    v_size = model.vocab_size

    # ---- prefill once on the B prompts (the SAME shared pass
    # generate uses — dtype/eps/MoE conventions cannot drift)
    x, k_caches, v_caches = _prefill(model, params, prompt, s_max)
    logp0 = jax.nn.log_softmax(
        _logits(params, x[:, -1:], eps)[:, 0], axis=-1)  # [B, V]

    # ---- seed K beams from the top-K first tokens
    scores, tok = jax.lax.top_k(logp0, k_beams)  # [B, K] both
    # caches tiled per beam: [L, B*K, S, H, Dh] (row b*K + j = beam j)
    def tile(c):
        return jnp.repeat(c, k_beams, axis=1)

    k_caches, v_caches = tile(k_caches), tile(v_caches)
    history = jnp.zeros((b, k_beams, max_new_tokens), jnp.int32)
    history = history.at[:, :, 0].set(tok)

    def step(carry, inp):
        tok, scores, history, k_caches, v_caches = carry
        pos, j = inp
        x_t = _embed(params, tok.reshape(b * k_beams, 1), pos, dtype)
        new_k, new_v = [], []
        for i in range(n_layers):
            x_t, kc, vc = _block_decode(
                params[f"block_{i}"], x_t, k_caches[i], v_caches[i],
                pos, h, dtype, eps, _no_cs, moe_k)
            new_k.append(kc)
            new_v.append(vc)
        k_caches, v_caches = jnp.stack(new_k), jnp.stack(new_v)
        logp = jax.nn.log_softmax(
            _logits(params, x_t, eps)[:, 0], axis=-1
        ).reshape(b, k_beams, v_size)
        total = scores[:, :, None] + logp  # [B, K, V]
        scores, flat = jax.lax.top_k(
            total.reshape(b, k_beams * v_size), k_beams)
        beam_idx = flat // v_size  # [B, K] surviving parent beams
        tok = flat % v_size

        def reindex(buf):
            # [L, B*K, ...] -> gather surviving parents per batch row
            l = buf.shape[0]
            r = buf.reshape((l, b, k_beams) + buf.shape[2:])
            idx = beam_idx.reshape(
                (1, b, k_beams) + (1,) * (buf.ndim - 2))
            r = jnp.take_along_axis(r, idx, axis=2)
            return r.reshape(buf.shape)

        k_caches, v_caches = reindex(k_caches), reindex(v_caches)
        history = jnp.take_along_axis(
            history, beam_idx[:, :, None], axis=1)
        history = history.at[:, :, j].set(tok)
        return (tok, scores, history, k_caches, v_caches), None

    if max_new_tokens > 1:
        positions = jnp.arange(t, s_max - 1)
        steps = jnp.arange(1, max_new_tokens)
        (tok, scores, history, _, _), _ = jax.lax.scan(
            step, (tok, scores, history, k_caches, v_caches),
            (positions, steps))

    prompt_k = jnp.broadcast_to(
        prompt[:, None, :], (b, k_beams, t))
    return jnp.concatenate([prompt_k, history], axis=2), scores


# ----------------------------------------------------------- graftquant

def teacher_forced_logits(model, params, tokens, prompt_len: int, *,
                          kv_dtype: str = "model", attn_impl: str = "xla",
                          block_k: int = 256, interpret=None):
    """Decode-path logits along a FIXED transcript with the KV cache in
    ``kv_dtype`` — the graftquant quality instrument.

    Prefills ``tokens[:, :prompt_len]``, (optionally) quantizes the
    prefilled cache exactly as the serving engine's insert does, then
    teacher-forces ``tokens[:, prompt_len:]`` through the shared decode
    body (:func:`_block_decode_slots`, per-slot scatter writes — the
    engine's path). Step ``j`` consumes ``tokens[:, prompt_len + j]``
    and yields the logits predicting position ``prompt_len + j + 1``.

    Returns ``[T - prompt_len, B, V]`` f32: row 0 is the prefill's
    next-token logits (predicting position ``prompt_len``), row ``j``
    predicts position ``prompt_len + j``. Because the transcript is
    held fixed, running this twice (``kv_dtype="model"`` vs ``"int8"``)
    isolates the cache representation: the elementwise max-abs delta is
    the quantization's logit cost, free of divergence compounding —
    the number the quant bench budgets and the tests pin."""
    b, total = tokens.shape
    steps = total - int(prompt_len)
    if steps < 1:
        raise ValueError(
            f"need at least one decode position: prompt_len="
            f"{prompt_len} vs {total} tokens")
    dtype = model.dtype
    eps = getattr(model, "ln_eps", _LN_EPS)
    moe_k = getattr(model, "moe_top_k", 1)
    h = model.num_heads
    n_layers = model.num_layers
    x, k_caches, v_caches = _prefill(
        model, params, tokens[:, :prompt_len], total)
    first = _logits(params, x[:, -1:], eps)[:, 0]         # [B, V]
    if kv_dtype == "int8":
        # whole-cache quantization == insert-time quantization: the
        # untouched tail columns are zeros -> (data 0, scale 1), the
        # empty-pool layout
        k_caches, v_caches = quantize_kv(k_caches), quantize_kv(v_caches)
    if steps == 1:
        return first[None]

    def step(carry, inp):
        k_caches, v_caches = carry
        tok, p = inp
        pos = jnp.full((b,), p, jnp.int32)
        x_t = (params["embed"][tok][:, None, :].astype(dtype)
               + params["pos_embed"][p][None, None, :].astype(dtype))
        new_k, new_v = [], []
        for i in range(n_layers):
            x_t, kc, vc = _block_decode_slots(
                params[f"block_{i}"], x_t, k_caches[i], v_caches[i],
                pos, h, dtype, eps, _no_cs, moe_k, attn_impl=attn_impl,
                block_k=block_k, interpret=interpret)
            new_k.append(kc)
            new_v.append(vc)
        logits = _logits(params, x_t, eps)[:, 0]
        return (stack_kv(new_k), stack_kv(new_v)), logits

    xs = (jnp.moveaxis(tokens[:, prompt_len:-1], 0, 1),
          jnp.arange(prompt_len, total - 1, dtype=jnp.int32))
    _, rest = jax.lax.scan(step, (k_caches, v_caches), xs)
    return jnp.concatenate([first[None], rest], axis=0)


# ----------------------------------------------------------- graftmeter

def generate_kv_bytes(model, batch: int, s_max: int,
                      kv_dtype: str = "model") -> int:
    """Worst-case K+V cache bytes one :func:`generate` call holds
    resident: the exact ``[L, B, s_max, H, Dh]`` x2 allocation
    ``_prefill`` makes — ``batch`` rows of the SAME per-slot product
    the serving pool allocates, so the ONE copy of the shape x dtype
    math lives in ``PagePool.per_slot_kv_bytes`` (a KV-layout change
    there moves the planner's ``max_generate_batch`` and this ledger
    entry together). Lazy import: ``serving`` imports this module."""
    from ..serving.kv_pages import PagePool

    return int(batch) * PagePool.per_slot_kv_bytes(model, int(s_max),
                                                   kv_dtype)


def register_generate_hbm(model, batch: int, s_max: int) -> None:
    """Ledger one generate call's KV residency (host boundary —
    :func:`generate` itself is jitted, so the allocation site's
    bookkeeping lives here and the CLIs call it right before the
    decode; disarmed: one global read)."""
    from ..runtime import hbm

    hbm.register("inference.kv_cache",
                 generate_kv_bytes(model, batch, s_max),
                 category="kv", batch=int(batch), s_max=int(s_max))


# ----------------------------------------------------------- graftcheck

def audit_programs():
    """graftcheck registration hook: the canonical inference programs.

    - ``generate_dense``: prefill + fused decode scan on the bf16 tiny
      GPT — zero collectives (single shard), and the committed dtype
      budget pins exactly which bf16->f32 upcasts feed matmuls (the
      deliberate f32 logit/attention-probability islands); a new
      upcast on an activation-sized tensor moves the count and fails
      the gate.
    - ``generate_tp``: the same program under a ``model``-axis mesh,
      COMPILED (CPU, partitioned) so GSPMD's inserted collectives are
      countable: the committed HLO budget is the Megatron contract —
      all-reduces for the row-parallel matmuls, no weight-sized
      all-gather (``max_allgather_bytes`` caps implicit
      replication; cf. arXiv:2112.01075 on redistribution cost).
    """
    def tiny_model():
        # ONE audit geometry across the LM-family hooks
        from ..analysis.programs import audit_tiny_gpt

        return audit_tiny_gpt()

    def pieces():
        model = tiny_model()
        params = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 8), jnp.int32),
                               train=False))["params"]
        prompt = jax.ShapeDtypeStruct((2, 8), jnp.int32)
        return model, params, prompt

    def build_dense():
        model, params, prompt = pieces()

        def fn(p, t):
            return generate(model, p, t, max_new_tokens=8)

        return {"fn": fn, "args": (params, prompt),
                "expect_collectives": {}}

    def build_tp():
        from ..parallel.mesh import audit_mesh

        model, params, prompt = pieces()
        mesh = audit_mesh(data=1, model=2)

        def fn(p, t):
            return generate(model, p, t, max_new_tokens=8, mesh=mesh)

        return {
            "fn": fn, "args": (params, prompt), "mesh": mesh,
            "compile": True, "compile_fn": jax.jit(fn),
            "require_hlo": ("all-reduce",),
            # the Megatron contract, pinned: one fused row-parallel
            # all-reduce per layer per phase (prefill pass + decode
            # scan body) on this jax's partitioner; a third per-layer
            # reduction means someone broke the column-then-row
            # sharding pattern. Derived from the SHARED audit model so
            # an audit_tiny_gpt geometry change tracks automatically.
            "expect_hlo_counts": {"all-reduce": model.num_layers * 2},
            # implicit replication cap: the largest legitimate gather
            # in TP decode is activation-sized; a weight- or
            # cache-sized one means a dropped sharding. The [D, V]
            # head kernel is the biggest weight — cap STRICTLY below
            # it (-1: the check is `worst > cap`, and gathering
            # exactly the whole head weight IS the dropped-sharding
            # case).
            "max_allgather_bytes":
                model.hidden_size * model.vocab_size * 4 - 1,
        }

    return [
        {"name": "generate_dense", "min_devices": 1,
         "build": build_dense},
        {"name": "generate_tp", "min_devices": 2, "build": build_tp},
    ]
