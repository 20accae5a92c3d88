"""Chunked-prefill attention of GROUPED query heads against one layer's
standalone cache: the chunk program's attention in the families with
window and full layers (``models/afmoe.py``, ``models/mimo_v2.py``).

A chunk is ``T`` query tokens at positions ``[start, start + T)``; the
layer's cache ``[W, Hkv (Dk + Dv)]`` already holds every column up to
``start + T`` (the chunk's own rows written in XLA before the call). A
row is a token's K of every key/value head (``Hkv * Dk`` lanes), then
its V (``Hkv * Dv``: values may be narrower than keys), as in the
decode pools. Query head ``t`` attends key/value head ``t // (Hq /
Hkv)`` over the columns ``j <= i`` (a full layer) or ``i - reach < j <=
i`` (a window layer), and a window layer may add a learned sink logit a
head to its softmax's denominator.

The KERNEL (``pallas_call(name="gqa_chunk_attention")``) holds the
online-softmax state of every query head of a block of ``block_q``
queries in VMEM and streams blocks of ``block_k`` WHOLE cache rows
(K and V of every key/value head in one copy) through it. The queries
are laid out once a query block as ``[Hkv, group * block_q, Dk]``, a
key/value head's group of ``Hq / Hkv`` heads one under the other, so
that K and V are never repeated and a matmul takes ``fold`` of those
heads as its rows: ONE where heads are lane-aligned (``Dk`` 128:
``[256, 256]`` scores a matmul, which the chip runs 20 % faster than
the group of 6 against 512 columns at once), the whole group where
they are not (keys of 192, whose head slices would otherwise be cut
again for every matmul). The grid is ``(query block, column block)``
and the column axis walks only the LIVE blocks of its query block,
``[lo, start + q1)`` with ``lo`` 0 on a full layer and ``max(0, start
+ q0 - reach + 1)`` on a window layer: the row block's index map names
block ``lo // block_k + kb`` clamped to the last live one, so a dead
step names the block before it, which the pipeline does not copy
again, and does no arithmetic. A window layer's grid stops at the most
blocks a query block can reach. Only the blocks that the causal bound,
the window's bound or the cache's end cut through are masked; the
bucket's dead tail past ``start + T`` is never read. Chosen over a
loop of manual copies (the form of the latent and GPT paged decode
kernels) because a block here is one copy of 1 MiB, whose pipelined
price (an index map and a descriptor a step) is nothing beside its
transfer, and every column block is folded against 1,536 query rows
(trinity's), so the vector work on the scores, not the copy, bounds a
step.

``start`` rides in SMEM (scalar prefetch): the chunk program is one
program for every ``start``, and every layer of a kind calls one
lowered kernel (``reach`` is static: a full and a window kernel).

``impl="xla"`` is the plain form the families shipped with (a map over
the key/value heads, each with its group's score matrix over every
column in reach), kept as the reference and the CPU fallback; CPU
tier-1 runs the kernel in interpret mode. Inputs in the model's dtype,
matmuls in it, softmax and accumulation in float32; the output is
float32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NEG_INF

__all__ = ["gqa_chunk_attention", "xla_gqa_chunk_attention",
           "softmax_rows"]

# queries and columns a block where a matmul folds one lane-aligned
# query head (the trinity cell's heads of 128)
_ALIGNED_BLOCK = 256

# where a matmul folds a whole group of unaligned heads (the MiMo
# cell's keys of 192): its folded rows (16 x 64 on a full layer, 8 x
# 128 on a window layer) and its columns, the most of a window layer's
_FOLDED_ROWS = 1024
_BLOCK_COLUMNS = 512

# the kernel's VMEM: two buffers each of a query, a row and an output
# block beside the folded queries, the accumulators and a block's
# scores: ~42 MiB at the trinity cell's shapes
_VMEM_LIMIT = 64 << 20


def softmax_rows(s):
    """``exp(s - max)`` and its row sums, float32. The barrier keeps
    the row maximum out of the fusion that exponentiates: fused into
    it, the chip's compiler recomputes the maximum of a whole
    8,192-wide row for every tile of the output (23 ms for a [8, 1024,
    8192] block in place of 1 on a TPU v5e)."""
    m = jax.lax.optimization_barrier(jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m)
    return p, jnp.sum(p, axis=-1, keepdims=True)


def _in_reach(cache, positions, start, reach):
    """The rows of one layer's standalone cache ``[W, row]`` that a
    chunk at ``positions`` (from ``start``) may attend, and its mask
    ``[T, span]``: every column under the causal mask where ``reach``
    is None (a full layer); else the ``T + reach`` columns from ``start
    - reach`` SLICED out (where the cache is wider) and ``i - j <
    reach`` masked inside."""
    t, w = positions.shape[0], cache.shape[0]
    if reach is not None and t + reach < w:
        span = t + reach
        begin = jnp.clip(start - reach, 0, w - span)
        rows = jax.lax.dynamic_slice_in_dim(cache, begin, span, axis=0)
    else:
        span, begin, rows = w, 0, cache
    cols = begin + jnp.arange(span)
    mask = cols[None, :] <= positions[:, None]              # [T, span]
    if reach is not None:
        mask = jnp.logical_and(
            mask, positions[:, None] - cols[None, :] < reach)
    return rows, mask


def xla_gqa_chunk_attention(q, cache, start, *, kv_heads, scale,
                            reach: Optional[int] = None, sinks=None):
    """The chunk's grouped attention in plain XLA: the columns in reach
    (:func:`_in_reach`), then one key/value head at a time with its
    group of query heads (``jax.lax.map``), a float32 score matrix a
    group, a head's sink logit one more column of its softmax with no
    value. Returns ``[T, Hq, Dv]`` float32."""
    t, heads, dk = q.shape
    group = heads // kv_heads
    dv = cache.shape[1] // kv_heads - dk
    positions = start + jnp.arange(t)
    rows, mask = _in_reach(cache, positions, start, reach)
    span = rows.shape[0]
    keys = rows[:, :kv_heads * dk].reshape(span, kv_heads, dk)
    values = rows[:, kv_heads * dk:].reshape(span, kv_heads, dv)

    def one_group(args):
        qg, kg, vg = args[:3]   # [T, g, Dk], [span, Dk], [span, Dv]
        s = jnp.einsum("tgd,wd->gtw", qg, kg,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask[None], s, -jnp.inf)
        if sinks is not None:   # args[3]: the group's sinks [g]
            s = jnp.concatenate(
                [s, jnp.broadcast_to(args[3][:, None, None], (group, t, 1))],
                axis=-1)
        pr, total = softmax_rows(s)
        if sinks is not None:
            pr = pr[..., :-1]
        out = jnp.einsum("gtw,wd->gtd", pr.astype(rows.dtype), vg,
                         preferred_element_type=jnp.float32)
        return out / total                                  # [g, T, Dv]

    groups = (jnp.moveaxis(q.reshape(t, kv_heads, group, dk), 1, 0),
              jnp.moveaxis(keys, 1, 0), jnp.moveaxis(values, 1, 0))
    if sinks is not None:
        groups += (sinks.astype(jnp.float32).reshape(kv_heads, group),)
    out = jax.lax.map(one_group, groups)
    return jnp.moveaxis(out.reshape(heads, t, dv), 0, 1)


def _chunk_blocks(t, w, group, reach, dk):
    """``(block_q, block_k, fold)``: queries a block, columns a block
    and query heads a matmul. Heads of a lane-aligned width (``Dk`` a
    multiple of 128) fold ONE a matmul, ``[block_q, block_k]`` scores
    at a time, with ``_ALIGNED_BLOCK`` queries and columns; unaligned
    heads (keys of 192) fold their whole group into one matmul, whose
    head slices are cut once a column block, with the most queries (a
    power of two, 16 to 128) whose folded rows fit ``_FOLDED_ROWS`` and
    ``_BLOCK_COLUMNS`` columns. A window layer's block is at most the
    reach's power of two (128 at least); no block is wider than ``T``
    or ``W``."""
    if dk % 128 == 0:
        block_q = block_k = _ALIGNED_BLOCK
        fold = 1
    else:
        block_q = min(1 << max((_FOLDED_ROWS // group).bit_length() - 1, 4),
                      128)
        block_k, fold = _BLOCK_COLUMNS, group
    if reach is not None:
        block_k = min(block_k, max(128, 1 << (reach - 1).bit_length()))
    return min(block_q, t), min(block_k, w), fold


def _live_blocks(start, qb, block_q, block_k, width, reach):
    """``(first, last, low, high)`` of query block ``qb``: the positions
    of its first and last query (no further than the cache's last
    column) and the first and last column blocks that any of its
    queries reaches (scalars: the index map's and the kernel's)."""
    first = start + qb * block_q
    last = jnp.minimum(first + block_q - 1, width - 1)
    low = (0 if reach is None
           else jnp.maximum(first - (reach - 1), 0) // block_k)
    return first, last, low, last // block_k


def _chunk_kernel(start_ref, q_ref, rows_ref, *refs, kv_heads, group, dk,
                  dv, scale, reach, block_q, block_k, fold, width, sink):
    """One (query block, live column block) cell. At the first column
    step the block's queries are folded into ``q_scr [Hkv, group *
    block_q, Dk]`` (row ``j * block_q + r``: query ``r`` of head ``h *
    group + j``) and the state set: ``m`` the head's sink and ``l`` 1
    where ``sink`` (a column with no value that starts the recurrence,
    as in ``decode_attention._gqa_paged_decode_kernel``), else
    ``NEG_INF`` and 0. A live step folds its column block into every
    query head's state, ``fold`` heads a matmul; the last step writes each head's ``acc / l`` into
    its lanes of the output block ``[block_q, Hq * Dv]``. A row that
    the window has not reached yet in a block takes ``exp(0)`` of each
    masked column while its ``m`` is ``NEG_INF``; its first live column
    multiplies that by ``exp(NEG_INF - m) = 0``."""
    if sink:
        sink_ref, *refs = refs
    o_ref, q_scr, acc_ref, m_ref, l_ref = refs
    qb, kb = pl.program_id(0), pl.program_id(1)
    first, last, low, high = _live_blocks(start_ref[0], qb, block_q,
                                          block_k, width, reach)
    col0 = (low + kb) * block_k
    rows = fold * block_q                            # rows a matmul

    @pl.when(kb == 0)
    def _():
        for h in range(kv_heads):
            for j in range(group):
                head = h * group + j
                q_scr[h, j * block_q:(j + 1) * block_q, :] = q_ref[
                    :, head * dk:(head + 1) * dk]
                if sink:
                    m_ref[h, j * block_q:(j + 1) * block_q, :] = jnp.full(
                        (block_q, 1), sink_ref[head], jnp.float32)
        if not sink:
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.full_like(l_ref, 1.0 if sink else 0.0)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def fold_block(masked):
        if masked:
            pos = first + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_q, block_k), 1)
            col = col0 + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_q, block_k), 2)
            keep = col <= pos
            if reach is not None:
                keep = jnp.logical_and(keep, pos - col < reach)
            # a block past the cache's end holds anything, NaN too: its
            # masked columns must not give 0 x NaN
            beyond = col0 + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, 1), 0) >= width
        for h in range(kv_heads):
            k = rows_ref[:, h * dk:(h + 1) * dk]             # [bk, Dk]
            v = rows_ref[:, kv_heads * dk + h * dv:
                         kv_heads * dk + (h + 1) * dv]       # [bk, Dv]
            if masked and width % block_k:
                v = jnp.where(beyond, jnp.zeros((), v.dtype), v)
            for j in range(0, group, fold):   # `fold` heads a matmul
                r = pl.ds(j * block_q, rows)
                s = jax.lax.dot_general(
                    q_scr[h, r, :], k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                if masked:
                    s = jnp.where(keep, s.reshape(fold, block_q, block_k),
                                  NEG_INF).reshape(rows, block_k)
                m_prev = m_ref[h, r, :]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=-1, keepdims=True))
                p = jnp.exp(s - m_new)
                corr = jnp.exp(m_prev - m_new)
                m_ref[h, r, :] = m_new
                l_ref[h, r, :] = (l_ref[h, r, :] * corr
                                  + jnp.sum(p, axis=-1, keepdims=True))
                acc_ref[h, r, :] = acc_ref[h, r, :] * corr + jnp.dot(
                    p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    # every query of the block reaches every column of the block
    inside = col0 + block_k - 1 <= first
    if reach is not None:
        inside = jnp.logical_and(inside, last - col0 < reach)

    @pl.when(jnp.logical_and(low + kb <= high, inside))
    def _():
        fold_block(False)

    @pl.when(jnp.logical_and(low + kb <= high, jnp.logical_not(inside)))
    def _():
        fold_block(True)

    @pl.when(kb == pl.num_programs(1) - 1)
    def _():
        for h in range(kv_heads):
            out = acc_ref[h] / l_ref[h]                      # [rows, Dv]
            for j in range(group):
                head = h * group + j
                o_ref[:, head * dv:(head + 1) * dv] = out[
                    j * block_q:(j + 1) * block_q]


@functools.partial(jax.jit, static_argnames=(
    "kv_heads", "scale", "reach", "block_q", "block_k", "fold", "interpret"))
def _pallas_gqa_chunk(q, cache, start, sinks, *, kv_heads, scale, reach,
                      block_q, block_k, fold, interpret):
    """q ``[T, Hq, Dk]``, cache ``[W, Hkv (Dk + Dv)]``, start a scalar,
    sinks ``[Hq]`` or None -> ``[T, Hq, Dv]`` float32. Jitted with the
    shapes' statics only: the layers of a kind share one lowered
    kernel."""
    t, heads, dk = q.shape
    w, width = cache.shape
    group = heads // kv_heads
    dv = width // kv_heads - dk
    n_k = pl.cdiv(w, block_k)
    if reach is not None:       # the most blocks a query block reaches
        n_k = min(n_k, pl.cdiv(reach + block_q - 1, block_k) + 1)

    def row_block(qb, kb, start_ref):
        _, _, low, high = _live_blocks(start_ref[0], qb, block_q, block_k,
                                       w, reach)
        return jnp.minimum(low + kb, high), 0

    in_specs = [pl.BlockSpec((block_q, heads * dk),
                             lambda qb, kb, start_ref: (qb, 0)),
                pl.BlockSpec((block_k, width), row_block)]
    operands = [q.reshape(t, heads * dk), cache]
    if sinks is not None:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(sinks.astype(jnp.float32))
    folded = group * block_q
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # start
        grid=(pl.cdiv(t, block_q), n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_q, heads * dv),
                               lambda qb, kb, start_ref: (qb, 0)),
        scratch_shapes=[
            pltpu.VMEM((kv_heads, folded, dk), q.dtype),     # folded q
            pltpu.VMEM((kv_heads, folded, dv), jnp.float32),  # accumulator
            pltpu.VMEM((kv_heads, folded, 1), jnp.float32),  # running max
            pltpu.VMEM((kv_heads, folded, 1), jnp.float32),  # denominator
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _chunk_kernel, kv_heads=kv_heads, group=group, dk=dk, dv=dv,
            scale=scale, reach=reach, block_q=block_q, block_k=block_k,
            fold=fold, width=w, sink=sinks is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, heads * dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="gqa_chunk_attention",
    )(jnp.asarray(start, jnp.int32).reshape(1), *operands)
    return out.reshape(t, heads, dv)


def gqa_chunk_attention(
    q: jax.Array,
    cache: jax.Array,
    start,
    *,
    kv_heads: int,
    scale: float,
    reach: Optional[int] = None,
    sinks: Optional[jax.Array] = None,
    impl: str = "auto",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Causal attention of a chunk's GROUPED query heads against one
    layer's standalone cache, with an optional lower column bound (a
    sliding window) and an optional learned sink logit a head.

    Args:
      q: ``[T, Hq, Dk]`` - the chunk's queries at positions ``[start,
        start + T)``; heads ``t`` with equal ``t // (Hq / Hkv)`` share
        a key/value head.
      cache: ``[W, Hkv * (Dk + Dv)]`` - the layer's standalone cache
        with the chunk's own rows written (``start + T <= W``); a row
        is a token's K of every key/value head, then its V.
      start: the chunk's first position, a traced int32 scalar.
      kv_heads: ``Hkv``; scale: the softmax scale.
      reach: the model's sliding window in columns (query ``i`` attends
        ``(i - reach, i]``), None for a layer that attends ``[0, i]``.
      sinks: ``[Hq]`` float32 or None - head ``t``'s learned sink logit
        adds ``exp(sinks[t])`` to its softmax's denominator and no
        value.
      impl: ``"pallas"`` | ``"xla"`` | ``"auto"`` (the kernel on a TPU,
        XLA elsewhere); interpret: Pallas interpret mode, by default
        everywhere but a TPU.

    Returns ``[T, Hq, Dv]`` float32.
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    reach = None if reach is None else int(reach)
    if impl == "pallas":
        if interpret is None:
            from . import default_interpret

            interpret = default_interpret()
        block_q, block_k, fold = _chunk_blocks(
            q.shape[0], cache.shape[0], q.shape[1] // kv_heads, reach,
            q.shape[2])
        return _pallas_gqa_chunk(
            q, cache, start, sinks, kv_heads=int(kv_heads),
            scale=float(scale), reach=reach, block_q=block_q,
            block_k=block_k, fold=fold, interpret=bool(interpret))
    if impl != "xla":
        raise ValueError(
            f"impl must be 'pallas', 'xla' or 'auto', got {impl!r}")
    return xla_gqa_chunk_attention(q, cache, start, kv_heads=kv_heads,
                                   scale=scale, reach=reach, sinks=sinks)
