"""Flash-decode attention: one cached step over a KV window, fused.

The serving engine's decode step is the textbook bandwidth-bound
workload: ONE query token per slot attending over every cached column.
XLA's dot+softmax+dot materializes the ``[B, H, 1, S]`` logit row in
HBM twice (once for the softmax read-back, once for the PV matmul);
this kernel streams K/V blocks through VMEM exactly once, folding each
block into an online-softmax recurrence (running max / denominator /
unnormalized accumulator — the same recurrence as
:mod:`.flash_attention`, degenerate q-block of 1), so HBM traffic is
the single K/V read the step fundamentally owes.

Per-slot positions ride in SMEM (the whole ``[B]`` vector, scalar-
prefetched — the TPU lowering has no one-element SMEM block): block
``kb`` is folded only when ``kb * block_k <= position`` — a slot at
position p pays for ``ceil((p+1)/block_k)`` blocks, not ``S/block_k``,
which is what makes the engine's length-bucketed window *and* this
kernel compose (the bucket bounds the grid, the position gate bounds
the work inside it).

The PAGED kernels read the pool the engine holds, whole:
``[L, P, ps, H * Dh]`` — a page is ``ps`` rows of every head's ``Dh``
values side by side in the lanes (head ``h`` in lanes ``h * Dh .. (h +
1) * Dh``), so it is lane-dense whatever ``Dh`` is, one contiguous DMA,
and the layer is an index of every page read (static, or a scalar in
SMEM): no layer is ever sliced out of the pool, so the donated pool is
written and read in place. Two kernels COPY THEIR OWN PAGES, the GPT kernel
(:func:`paged_decode_attention`, :func:`paged_verify_decode_attention`)
and the LATENT one (:func:`mla_paged_decode_attention`: one row a
token that every head shares). The pools are passed once and stay in
HBM (``pl.ANY``), the grid is the slots, and a slot is a loop over its
LIVE blocks of G pages (up to the last query row's reach; G = 16 pages
of 16 in the GPT kernel, 64 in the latent one): one
``make_async_copy`` a live page (:func:`_live_page_copies`) into a VMEM
buffer while the block before is folded out of another, the next
slot's first block in flight at a slot's end, so a slot costs its live
pages, not its window. The latent kernel holds two blocks; the GPT
kernel, whose slots are short (a few hundred columns), a ring of four,
so that the copies of the next three blocks, the next slots' too, are
in flight under a fold. What a page copy costs decides between this
form and the pipelined one (a TPU v5e, PERF.md PRs 37 and 40): as a
pipelined operand — an index map, a descriptor and a semaphore wait a
grid step and operand, a dead step's index maps included — ~0.05-0.1
us whatever the page holds; as a manual copy 19 scalar bundles to
issue (~20 ns), 12 of them the compiler's two bounds checks, ~7 ns with
the checks off and the ids clipped outside, and ONE wait a whole
block.

In the GPT kernel heads are kept apart by the QUERY: the caller's
``[H, Dh]`` query becomes block-diagonal ``[H, H * Dh]`` (row ``h``
holds ``q_h`` in head ``h``'s lanes, zeros elsewhere), so ``Q rows^T``
is every head's scores in one contraction (the extra products are
exact zeros), ``P rows`` is ``[H, H * Dh]`` and head ``h``'s output is
its diagonal block, taken outside the kernel. The k-query verify pass
is the same kernel with ``K1 * H`` query rows and a row-staggered mask.

The GROUPED kernel (:func:`gqa_paged_decode_attention`: fewer
key/value heads than query heads, and layers that attend a sliding
window) is PIPELINED: its grid is ``(slot, block of G pages)`` and each
pool is passed G times with plain ``BlockSpec``s (operand ``g`` of a
step is page ``g`` of its block, one page of 64 KiB whose copy the
pipeline's price per operand does not bound), the page each operand
names worked out in XLA (:func:`_reach_page_ids`), a dead step naming
its predecessor's pages, which the pipeline does not copy twice. It
reads a row that holds K and V side by side (keys may be wider than
values: ``Hkv * Dk`` lanes, then ``Hkv * Dv``): a block-diagonal query
over the KEY/VALUE heads' key lanes, a LOWER column bound ``reach``
that follows each slot's position (no page below it is named) beside
the upper bound every paged kernel has, and an optional learned sink
logit a head that starts the online softmax. Two words, two things:
``window`` in this file is always the decode BUCKET's column bound,
``reach`` a model's sliding window.

Matmuls stay in the input dtype (bf16 hits the MXU's native rate),
accumulation is f32, outputs are f32 (the engine casts back to model
dtype after the residual add, matching the XLA path's dtypes exactly).

**graftquant**: every kernel (and every XLA reference) also takes the
KV operand as a :class:`...kv_quant.QuantizedKV` pair — int8 data plus
a per-(token, head) f32 scale streamed beside it (dense: a ``[B*H,
1, S]`` row per block; paged: the ``[ps, H]`` sidecar of the SAME page,
``[L, P, ps, H]``, through the same page ids — the GPT kernel's
gathered in XLA, a slot's window of them one lane-dense ``[H, window]``
block, since the chip's compiler slices no float32 array in HBM whose
last axis is under 128 lanes). The dense kernel
dequants each block in the VMEM stream (ONE multiply, before the MXU
dot); the paged kernels feed the int8 lanes to the MXU as they are
(exact in the compute dtype) and apply the scale where it is one number
a head and column: to the scores (K) and to the probabilities (V) —
``sum_d q_d (k_d s) = s sum_d q_d k_d``. Either way the decode step's
dominant HBM bytes term (the K/V read) halves while the matmul dtype
and f32 accumulation stay exactly as above. The XLA fallbacks dequant
with :func:`...kv_quant.dequantize_kv` before the reference einsum —
the same numbers up to f32 rounding — so CPU tier-1 pins the math the
TPU kernel runs.

``impl="xla"`` is the reference fallback — the exact einsum/softmax
math the engine shipped with (and ``inference.generate`` still uses),
kept here so both paths live side by side and the equivalence test has
a single seam. CPU tier-1 exercises the kernel via Pallas interpret
mode (auto-selected off-TPU, same convention as every kernel in this
package).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..kv_quant import QuantizedKV, dequantize_kv
from .flash_attention import NEG_INF

__all__ = ["decode_attention", "paged_decode_attention",
           "mla_paged_decode_attention", "xla_mla_paged_decode_attention",
           "gqa_paged_decode_attention", "xla_gqa_paged_decode_attention",
           "paged_verify_decode_attention",
           "xla_decode_attention", "xla_paged_decode_attention",
           "xla_verify_decode_attention",
           "xla_paged_verify_decode_attention"]


def _kernel_dequant(blk, scale_row, dtype):
    """graftquant's ONE in-kernel dequant expression: int8 lanes times
    the per-(token, head) f32 scale, cast to the MXU compute dtype —
    the same math as :func:`...kv_quant.dequantize_kv`, so the XLA
    fallbacks pin exactly what the kernel streams."""
    return (blk.astype(jnp.float32)
            * scale_row[..., None]).astype(dtype)


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, *rest, scale, block_k,
                   heads, quant):
    """One (slot*head, k-block) grid cell; k is the innermost axis so
    the softmax state lives in VMEM scratch across the K/V stream.
    ``pos_ref`` is the whole scalar-prefetched ``[B]`` positions vector
    in SMEM (a per-row SMEM block of one element is not a legal TPU
    block). ``quant`` (static) inserts two scale refs after v_ref and
    dequants each K/V block in the VMEM stream before the dot."""
    if quant:
        ks_ref, vs_ref, o_ref, acc, m_scr, l_scr = rest
    else:
        o_ref, acc, m_scr, l_scr = rest
    i = pl.program_id(0)
    kb = pl.program_id(1)
    n_k = pl.num_programs(1)

    @pl.when(kb == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    pos = pos_ref[i // heads]

    # whole block beyond the slot's position -> nothing to fold (this,
    # not the grid, is what makes cost track each slot's true length)
    @pl.when(kb * block_k <= pos)
    def _():
        q = q_ref[0]          # [1, d]
        kblk = k_ref[0]       # [bk, d]
        vblk = v_ref[0]
        if quant:
            kblk = _kernel_dequant(kblk, ks_ref[0, 0], q.dtype)
            vblk = _kernel_dequant(vblk, vs_ref[0, 0], q.dtype)
        s = jnp.dot(q, kblk.T,
                    preferred_element_type=jnp.float32) * scale  # [1, bk]
        col = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        s = jnp.where(col <= pos, s, NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc[:] = acc[:] * corr + jnp.dot(
            p.astype(vblk.dtype), vblk,
            preferred_element_type=jnp.float32)

    @pl.when(kb == n_k - 1)
    def _():
        o_ref[0] = acc[:] / jnp.maximum(l_scr[:], 1e-30)


def _pallas_decode(q, k, v, positions, scale, block_k, interpret,
                   k_scale=None, v_scale=None):
    """q [B, 1, H, Dh]; k/v [B, S, H, Dh]; positions [B] -> f32
    [B, 1, H, Dh]. Heads merge into the grid's batch axis (one
    (slot, head) pair per row program), K/V stream blockwise.
    graftquant: with ``k_scale``/``v_scale`` (``[B, S, H]`` f32) the
    K/V operands are int8 and each block dequants in VMEM."""
    b, _, h, d = q.shape
    s = k.shape[1]
    quant = k_scale is not None
    block_k = max(8, min(block_k, ((s + 7) // 8) * 8))
    pad = (-s) % block_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if quant:
            k_scale = jnp.pad(k_scale, ((0, 0), (0, pad), (0, 0)))
            v_scale = jnp.pad(v_scale, ((0, 0), (0, pad), (0, 0)))
    n_k = k.shape[1] // block_k

    def merge(x):  # [B, S, H, Dh] -> [B*H, S, Dh]
        return jnp.moveaxis(x, 2, 1).reshape(b * h, x.shape[1], d)

    def merge_scale(x):  # [B, S, H] -> [B*H, 1, S]
        return jnp.moveaxis(x, 2, 1).reshape(b * h, 1, x.shape[1])

    q3 = merge(q)                      # [B*H, 1, Dh]
    k3, v3 = merge(k), merge(v)

    in_specs = [
        pl.BlockSpec((1, 1, d), lambda i, kb, pos: (i, 0, 0)),
        pl.BlockSpec((1, block_k, d), lambda i, kb, pos: (i, kb, 0)),
        pl.BlockSpec((1, block_k, d), lambda i, kb, pos: (i, kb, 0)),
    ]
    operands = [q3, k3, v3]
    if quant:
        in_specs += [pl.BlockSpec((1, 1, block_k),
                                  lambda i, kb, pos: (i, 0, kb))] * 2
        operands += [merge_scale(k_scale), merge_scale(v_scale)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # positions
        grid=(b * h, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, d), lambda i, kb, pos: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, d), jnp.float32),   # output accumulator
            pltpu.VMEM((1, 1), jnp.float32),   # running max
            pltpu.VMEM((1, 1), jnp.float32),   # running denominator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, block_k=block_k,
                          heads=h, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, 1, d), jnp.float32),
        interpret=interpret,
        name="decode_attention",
    )(positions.astype(jnp.int32), *operands)
    return jnp.moveaxis(out.reshape(b, h, 1, d), 1, 2)  # [B, 1, H, Dh]


# the K and V page blocks one grid step holds in VMEM (each double-
# buffered by the pipeline) may take this much; a lane-dense page is
# not padded, so this is what they take
_PAGE_BLOCK_BYTES = 4 << 20

# the GPT kernel's ring of K and V blocks may take this much VMEM (its
# own budget: the latent kernel's block follows from _PAGE_BLOCK_BYTES)
_GPT_BLOCK_BYTES = 8 << 20

# blocks in the GPT kernel's ring: the one folded and the copies of the
# next ones in flight, across slots, so that a short slot's copies are
# issued a few folds before they are waited for (at the cell's shapes on
# a TPU v5e 0.092 ms a call against 0.104 with two; three and eight
# read as four: PERF.md, PR 40)
_GPT_BUFFERS = 4


def _gpt_block_pages(page_size, n_win, rows, row_bytes):
    """G, the pages one block of the GPT kernel copies and folds: the
    most (a power of two, no more than the window has) whose columns
    fit ``_GPT_BLOCK_BYTES`` of VMEM as a ring of ``_GPT_BUFFERS`` K and
    V blocks, a row counted at ``row_bytes`` (the width in the QUERY's
    dtype: an int8 row is widened to it before the MXU), beside a
    float32 score a query row. 16 pages of 16 (256 columns) at the
    GPT-2 cell's 16 heads of 64: a block's fold streams every column
    through the MXU, live or not, so the dead columns of a slot's last
    block weigh against the fold's fixed chain a block; on the chip 256
    columns beat 128, 512 and 1,024 (PERF.md, PR 40)."""
    fit = _GPT_BLOCK_BYTES // (
        (2 * _GPT_BUFFERS * row_bytes + 4 * rows) * page_size)
    return min(1 << max(fit, 1).bit_length() - 1, n_win)


def _page_spec(block_shape, layer, g, group):
    """Block of page ``g`` of a grid step's ``group`` in layer ``layer``
    of a ``[L, P, ps, .]`` pool (or of its scale sidecar): the layer is
    static, the page comes from the scalar-prefetched
    :func:`_live_page_ids`."""
    return pl.BlockSpec(
        block_shape,
        lambda i, kb, pos, ids: (layer, ids[i, kb * group + g], 0, 0))


def _block_diagonal(q):
    """``[B, K1, H, Dh]`` -> ``[B, K1 * H, H * Dh]``: query row ``(i,
    h)`` holds ``q[b, i, h]`` in head ``h``'s lanes and zeros in every
    other head's, so one contraction over a page's ``H * Dh`` lanes is
    every head's own scores."""
    b, k1, h, d = q.shape
    eye = jnp.eye(h, dtype=q.dtype)
    return (q[:, :, :, None, :] * eye[None, None, :, :, None]).reshape(
        b, k1 * h, h * d)


def _diagonal_blocks(out, k1, heads):
    """``[B, K1 * H, H * Dh]`` -> ``[B, K1, H, Dh]``: of row ``(i,
    h)``, the lanes of head ``h`` (what the other heads' lanes hold is
    ``p_h`` against another head's values: dropped, never summed)."""
    b = out.shape[0]
    out = out.reshape(b, k1, heads, heads, -1)
    eye = jnp.eye(heads, dtype=bool)[None, None, :, :, None]
    return jnp.sum(jnp.where(eye, out, 0.0), axis=3)


def _paged_attention_kernel(pos_ref, ids_ref, layer_ref, q_ref, k_pool,
                            v_pool, *refs, scale, page_size, group, n_win,
                            heads, k1, buffers, quant):
    """One SLOT of the PAGED flash-decode, all heads and all ``k1``
    query tokens at once: the same online-softmax recurrence as
    :func:`_decode_kernel` with a softmax state per query row. The
    query block is :func:`_block_diagonal`, ``[K1 * H, H * Dh]``; query
    row ``(i, h)`` sits at column ``pos + i`` (``k1`` 1: the
    single-query decode step).

    The kernel copies its own pages (:func:`_live_page_copies`): the K
    and V pools stay in HBM and a slot is a loop over its LIVE blocks
    of ``group`` pages, ``(pos + k1 - 1) // (group * ps) + 1`` of them
    (up to the LAST query row's reach). The blocks of all slots, in
    order, go round a ring of ``buffers`` K and V blocks: while one is
    folded the copies of the next ``buffers - 1`` are in flight, the
    next slots' included (``next_ref``: the slot and block whose copies
    start next, and how many have started; ``done_ref``: how many blocks
    were folded, whose count names the buffer). The column mask keeps
    each row's columns beyond its reach out of the softmax; what lies
    behind the last live page is a block copied earlier or the zeros of
    the first step, so a masked column is ``0 x`` a finite number.
    ``quant`` (static): two more operands, the slot's K and V scales
    ``[H, window]`` (gathered by the caller); the int8 lanes go to the
    MXU as they are and the scale multiplies the scores (K) and the
    probabilities (V), one number a head and column."""
    if quant:
        ks_ref, vs_ref, *refs = refs
    (o_ref, k_buf, v_buf, k_sem, v_sem, acc_ref, m_ref, l_ref, done_ref,
     next_ref) = refs
    i = pl.program_id(0)
    layer = layer_ref[0]
    block_k = group * page_size
    rows = k1 * heads

    def last_page(slot):
        return jnp.clip(pos_ref[slot] + (k1 - 1), 0,
                        n_win * page_size - 1) // page_size

    def copies(slot, block, ring, wait=False):
        count = jnp.minimum(last_page(slot) + 1 - block * group, group)
        for pool_ref, buf_ref, sem in ((k_pool, k_buf, k_sem),
                                       (v_pool, v_buf, v_sem)):
            _live_page_copies(
                pool_ref, layer, ids_ref, slot * n_win + block * group,
                count, buf_ref.at[ring], sem.at[ring], group=group,
                page_size=page_size, wait=wait)

    def start_next():
        """Start the copies of the next block in the order of slots
        and blocks, if there is one, into the ring's next buffer."""
        slot, block, started = next_ref[0], next_ref[1], next_ref[2]

        @pl.when(slot < pl.num_programs(0))
        def _():
            copies(slot, block, started % buffers)
            last = block == last_page(slot) // group
            next_ref[0] = jnp.where(last, slot + 1, slot)
            next_ref[1] = jnp.where(last, 0, block + 1)
            next_ref[2] = started + 1

    @pl.when(i == 0)
    def _():
        k_buf[:] = jnp.zeros_like(k_buf)
        v_buf[:] = jnp.zeros_like(v_buf)
        done_ref[0] = 0
        next_ref[0] = 0
        next_ref[1] = 0
        next_ref[2] = 0
        for _ in range(buffers - 1):
            start_next()

    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    pos = pos_ref[i]
    n_blocks = last_page(i) // group + 1
    first = done_ref[0]
    # row (i, h) reaches column pos + i; i = row // heads, as a sum of
    # comparisons (no vector division)
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    reach = pos + sum((row >= j * heads).astype(jnp.int32)
                      for j in range(1, k1))

    def fold(kb, carry):
        ring = (first + kb) % buffers
        # into the buffer the block before this one was folded out of
        start_next()

        def row_scales(ref):
            """``[K1 * H, G * ps]``: row ``(i, h)`` holds head ``h``'s
            scale of each of the block's columns."""
            blk = ref[0, :, pl.ds(pl.multiple_of(kb * block_k, block_k),
                                  block_k)]
            return blk if k1 == 1 else jnp.concatenate([blk] * k1, axis=0)

        copies(i, kb, ring, wait=True)
        q = q_ref[0]                                 # [K1*H, H*Dh]
        kblk, vblk = k_buf[ring], v_buf[ring]        # [G*ps, H*Dh]
        if quant:
            kblk, vblk = kblk.astype(q.dtype), vblk.astype(q.dtype)
        s = jax.lax.dot_general(
            q, kblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [K1*H, G*ps]
        if quant:
            s = s * row_scales(ks_ref)
        col = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(col <= reach, s, NEG_INF)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        if quant:  # a scale beyond the reach may be anything, NaN too
            p = jnp.where(col <= reach, p * row_scales(vs_ref), 0.0)
        acc_ref[:] = acc_ref[:] * corr + jnp.dot(
            p.astype(vblk.dtype), vblk,
            preferred_element_type=jnp.float32)      # [K1*H, H*Dh]
        return carry

    jax.lax.fori_loop(0, n_blocks, fold, 0)
    done_ref[0] = first + n_blocks
    o_ref[0] = acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)


# one program a shape: the layer is an operand, so the 24 calls of a
# GPT-2 medium decode step trace and lower ONE kernel (with the layer a
# static argument, 24 kernels took 13-15 s of the engine's set-up a
# decode program on the host's CPU, against 1.0-1.5 s so)
@functools.partial(jax.jit, static_argnames=("scale", "interpret", "name"))
def _pallas_paged_attention(q, k_pages, v_pages, page_table, positions,
                            layer, scale, interpret, name):
    """q [B, K1, H, Dh]; k/v pools [L, P, ps, H*Dh] (or the int8
    pairs, scale [L, P, ps, H]); page_table [B, n_win] int32;
    positions [B] -> f32 [B, K1, H, Dh]. The grid is the slots; a slot
    folds all heads and query rows of its live blocks of G pages, G
    from :func:`_gpt_block_pages`. Positions and the windowed page
    table (flat, every id clipped into the pool) ride in SMEM via
    scalar prefetch, and so does ``layer``; the K and V pools are
    passed ONCE and read where they lie in HBM, the layer an index of
    each page copy, and no page beyond a slot's reach is copied.

    The int8 pairs' scale sidecars are gathered here, a slot's window
    of them as one lane-dense ``[H, n_win * ps]`` block: the chip's
    compiler slices no float32 array in HBM whose last axis is
    narrower than 128 lanes, as a ``[ps, H]`` sidecar page is."""
    b, k1, h, _ = q.shape
    quant = isinstance(k_pages, QuantizedKV)
    k_data, v_data = ((k_pages.data, v_pages.data) if quant
                      else (k_pages, v_pages))
    n_pages, ps, width = k_data.shape[1:]
    n_win = page_table.shape[1]
    rows = k1 * h
    group = _gpt_block_pages(ps, n_win, rows, width * q.dtype.itemsize)
    table = jnp.clip(page_table.astype(jnp.int32), 0, n_pages - 1)

    def slot_spec(shape):
        return pl.BlockSpec((1,) + shape,
                            lambda i, pos, ids, layer: (i, 0, 0))

    in_specs = [slot_spec((rows, width))] + [
        pl.BlockSpec(memory_space=pl.ANY)] * 2
    operands = [_block_diagonal(q), k_data, v_data]
    if quant:
        def window_scales(pool):   # [L, P, ps, H] -> [B, H, n_win * ps]
            g = jnp.take(pool[layer], table, axis=0)
            return jnp.swapaxes(g.reshape(b, n_win * ps, h), 1, 2)

        in_specs += [slot_spec((h, n_win * ps))] * 2
        operands += [window_scales(k_pages.scale),
                     window_scales(v_pages.scale)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # positions, the windowed table, the layer
        grid=(b,),
        in_specs=in_specs,
        out_specs=slot_spec((rows, width)),
        scratch_shapes=[
            # the ring of K blocks and of V blocks, a semaphore a block
            pltpu.VMEM((_GPT_BUFFERS, group * ps, width), k_data.dtype),
            pltpu.VMEM((_GPT_BUFFERS, group * ps, width), v_data.dtype),
            pltpu.SemaphoreType.DMA((_GPT_BUFFERS,)),
            pltpu.SemaphoreType.DMA((_GPT_BUFFERS,)),
            pltpu.VMEM((rows, width), jnp.float32),  # output accumulator
            pltpu.VMEM((rows, 1), jnp.float32),      # running max
            pltpu.VMEM((rows, 1), jnp.float32),      # running denominator
            pltpu.SMEM((1,), jnp.int32),             # blocks folded so far
            pltpu.SMEM((3,), jnp.int32),             # the next copies
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_attention_kernel, scale=scale,
                          page_size=ps, group=group, n_win=n_win,
                          heads=h, k1=k1,
                          buffers=_GPT_BUFFERS, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, width), jnp.float32),
        # slots in order (a fold starts the copies of a block after it,
        # the next slots' too); the bounds checks off as in the latent
        # kernel: every id is held inside the pool below and a copy's
        # VMEM side is static
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), disable_bounds_checks=True),
        interpret=interpret,
        name=name,
    )(positions.astype(jnp.int32), table.reshape(-1),
      jnp.asarray(layer, jnp.int32).reshape(1), *operands)
    return _diagonal_blocks(out, k1, h)              # [B, K1, H, Dh]


def _gather_paged_window(pages, layer, page_table, heads, q_dtype,
                         window: Optional[int] = None):
    """``take``-gather layer ``layer``'s windowed pages into the
    contiguous ``[B, W, H, Dh]`` view the dense references consume.
    graftquant pages gather BOTH leaves through the same table, then
    dequant (:func:`...kv_quant.dequantize_kv`) — which is what keeps
    the XLA fallback the pin."""
    b, n_win = page_table.shape

    def gather(pool, last):  # [L, P, ps, H * last] -> [B, W, H, last]
        g = jnp.take(pool[layer], page_table, axis=0)
        return g.reshape((b, n_win * g.shape[2], heads) + last)

    if isinstance(pages, QuantizedKV):
        g = dequantize_kv(QuantizedKV(gather(pages.data, (-1,)),
                                      gather(pages.scale, ())), q_dtype)
    else:
        g = gather(pages, (-1,))
    if window is not None and window < g.shape[1]:
        g = jax.lax.slice_in_dim(g, 0, window, axis=1)
    return g


def xla_paged_decode_attention(q, k_pages, v_pages, page_table,
                               positions, window: Optional[int] = None,
                               *, layer: int):
    """Reference paged path: ``take``-gather the windowed pages into
    the contiguous ``[B, W, H, Dh]`` view and run the EXACT dense
    reference math (:func:`xla_decode_attention`) — bit-identical to
    the dense-slot engine on the same logical columns, which is the
    seam the paged==dense equivalence pin rests on. Quantized pages
    dequant at the gather."""
    h = q.shape[2]
    k_win = _gather_paged_window(k_pages, layer, page_table, h, q.dtype,
                                 window)
    v_win = _gather_paged_window(v_pages, layer, page_table, h, q.dtype,
                                 window)
    mask = (jnp.arange(k_win.shape[1])[None, :] <= positions[:, None])
    return xla_decode_attention(q, k_win, v_win, mask)


def paged_decode_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,
    positions: jax.Array,
    *,
    layer: int,
    window: Optional[int] = None,
    impl: str = "auto",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Single-step cached attention through a page table (graftpage).

    Args:
      q: ``[B, 1, H, Dh]`` — one pending query token per slot.
      k_pages, v_pages: ``[L, P, page_size, H * Dh]`` — ALL layers'
        pages, a row a token with the heads side by side in the lanes.
        The kernel reads the pools where they lie in HBM, ``layer`` an
        index of each page copy, so no layer is ever sliced out of (and
        copied from) the pool, and a live page is one contiguous
        lane-dense DMA. Or a :class:`...kv_quant.
        QuantizedKV` pair (int8 data + the ``[L, P, page_size, H]``
        f32 scale sidecar).
      page_table: ``[B, n_win]`` int32 — slot ``b``'s logical column
        block ``kb`` lives in page ``page_table[b, kb]``. Callers pass
        the WINDOWED slice of the full table (``ceil(window /
        page_size)`` entries); unallocated entries point at the
        scratch page 0, whose contents the position mask keeps out of
        the softmax.
      positions: ``[B]`` int — slot ``b`` attends columns
        ``[0, positions[b]]`` inclusive.
      layer: static layer index into the pools.
      window: the decode BUCKET's column bound, an UPPER bound the
        engine picks for the whole step from the longest active
        sequence (< ``n_win * page_size`` trims the gathered tail on
        the XLA path; the Pallas path's column mask makes it a no-op
        there). It is NOT a model's sliding window: a layer that
        attends only the last so many columns names that LOWER bound
        ``reach`` (:func:`gqa_paged_decode_attention`).
      impl / interpret: as :func:`decode_attention`.

    Returns ``[B, 1, H, Dh]`` f32 attention output (caller casts).
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "pallas":
        if interpret is None:
            from . import default_interpret

            interpret = default_interpret()
        return _pallas_paged_attention(
            q, k_pages, v_pages, page_table, positions, int(layer),
            q.shape[-1] ** -0.5, bool(interpret),
            "paged_decode_attention")
    if impl != "xla":
        raise ValueError(
            f"impl must be 'pallas', 'xla' or 'auto', got {impl!r}")
    return xla_paged_decode_attention(q, k_pages, v_pages, page_table,
                                      positions, window, layer=layer)


# ---------------------------------------------------- latent (MLA) pages

def _mla_block_pages(page_size, n_win, heads, row_bytes):
    """G, the pages one block of the latent kernel copies and folds:
    the most (a power of two, no more than the window has) whose
    columns fit ``_PAGE_BLOCK_BYTES`` of VMEM as two buffers of rows
    and a float32 score a head. 64 pages of 16 (1,024 columns) at 32
    and at 128 heads: a block costs ~0.4-0.5 us beside its columns (the
    chain matmul, row maximum, exponent, matmul), so on the chip 1,024
    columns beat 512 and 256 at both head counts, and 2,048 lose at 128
    heads to the dead columns of a slot's last block (PERF.md, PR 37)."""
    fit = _PAGE_BLOCK_BYTES // ((2 * row_bytes + 4 * heads) * page_size)
    return min(1 << max(fit, 1).bit_length() - 1, n_win)


def _live_page_copies(pool_ref, layer, ids_ref, first, count, buf_ref, sem,
                      *, group, page_size, wait):
    """A paged kernel that copies its own pages: start (``wait``: wait
    for) the copies of the ``count`` live pages (``1 <= count <=
    group``) that the FLAT table ``ids_ref`` (SMEM) names from entry
    ``first`` on, out of layer ``layer`` of the ``[L, P, ps, .]`` pool
    where it lies in HBM, into the ``[group * ps, .]`` VMEM buffer
    ``buf_ref``, page ``g`` under page ``g - 1``, all on the one DMA
    semaphore ``sem``. A whole block is ``group`` copies issued as
    straight-line code and ONE wait (a DMA semaphore counts bytes, so
    a wait for the buffer's size is a wait for all its pages); a
    slot's last, partial block is a loop of ``count`` either way.
    Nothing beyond ``count`` is copied: what the rest of the buffer
    holds is the caller's to mask."""
    def copy(g):
        return pltpu.make_async_copy(
            pool_ref.at[layer, ids_ref[first + g]],
            buf_ref.at[pl.ds(g * page_size, page_size)], sem)

    @pl.when(count == group)
    def _():
        if wait:
            pltpu.make_async_copy(buf_ref, buf_ref, sem).wait()
        else:
            for g in range(group):
                copy(g).start()

    @pl.when(count < group)
    def _():
        def one(g, carry):
            if wait:
                copy(g).wait()
            else:
                copy(g).start()
            return carry
        jax.lax.fori_loop(0, count, one, 0)


def _mla_paged_decode_kernel(pos_ref, ids_ref, q_ref, pool_ref, o_ref,
                             buf_ref, sem, acc_ref, m_ref, l_ref, done_ref,
                             *, scale, layer, page_size, group, n_win, rank):
    """One SLOT of the ABSORBED latent decode, all heads at once. The
    cache holds one row a token that every head shares: the normed
    latent ``c`` (``rank`` values), then the rotated ``k_rope``,
    zero-padded to whole lanes. The query is laid out the same way
    (``q_lat | q_rope | 0``), so the scores ``q_lat . c + q_rope .
    k_rope`` are ONE contraction over the row, and the output is ``P
    c`` — still in the latent space; the caller applies the per-head
    value up-projection. A row is read once and used as key and (its
    first ``rank`` lanes) as value.

    The kernel copies its own pages (:func:`_live_page_copies`): the
    pool stays in HBM and the grid is the slots. A slot is a loop over
    its LIVE blocks of ``group`` pages, ``pos // (group * ps) + 1`` of
    them: the block's pages arrive in one half of ``buf_ref`` while the
    block before is folded out of the other, the next slot's first
    block at a slot's end (``done_ref`` counts the blocks folded so far,
    whose parity names the half). The fold is the online-softmax
    recurrence of :func:`_paged_attention_kernel`; the columns beyond
    the position are masked, and what lies behind the last live page
    is a block copied earlier or the zeros of the first step, so a
    masked column is ``0 x`` a finite number."""
    i = pl.program_id(0)
    block_k = group * page_size

    def last_page(slot):
        return jnp.clip(pos_ref[slot], 0, n_win * page_size - 1) // page_size

    def copies(slot, block, half, wait=False):
        _live_page_copies(
            pool_ref, layer, ids_ref, slot * n_win + block * group,
            jnp.minimum(last_page(slot) + 1 - block * group, group),
            buf_ref.at[half], sem.at[half], group=group,
            page_size=page_size, wait=wait)

    @pl.when(i == 0)
    def _():
        buf_ref[:] = jnp.zeros_like(buf_ref)
        done_ref[0] = 0
        copies(0, 0, 0)

    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    pos = pos_ref[i]
    n_blocks = last_page(i) // group + 1
    first = done_ref[0]

    def fold(kb, carry):
        half = (first + kb) % 2

        @pl.when(kb + 1 < n_blocks)
        def _():
            copies(i, kb + 1, 1 - half)

        @pl.when(jnp.logical_and(kb + 1 == n_blocks,
                                 i + 1 < pl.num_programs(0)))
        def _():
            copies(i + 1, 0, 1 - half)

        copies(i, kb, half, wait=True)
        rows = buf_ref[half]                             # [G*ps, R + Rw]
        s = jax.lax.dot_general(
            q_ref[0], rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [H, G*ps]
        col = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(col <= pos, s, NEG_INF)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jnp.dot(
            p.astype(rows.dtype), rows[:, :rank],
            preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, n_blocks, fold, 0)
    done_ref[0] = first + n_blocks
    o_ref[0] = acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)


def _pallas_mla_paged_decode(q, pages, page_table, positions, layer,
                             rank, scale, interpret):
    b, h, width = q.shape
    n_pages, ps = pages.shape[1], pages.shape[2]
    n_win = page_table.shape[1]
    group = _mla_block_pages(ps, n_win, h, width * pages.dtype.itemsize)

    def slot_spec(last):
        return pl.BlockSpec((1, h, last), lambda i, pos, ids: (i, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # positions, the windowed page table
        grid=(b,),
        in_specs=[slot_spec(width), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=slot_spec(rank),
        scratch_shapes=[
            pltpu.VMEM((2, group * ps, width), pages.dtype),  # two blocks
            pltpu.SemaphoreType.DMA((2,)),        # one a block in flight
            pltpu.VMEM((h, rank), jnp.float32),   # latent accumulator
            pltpu.VMEM((h, 1), jnp.float32),      # running max
            pltpu.VMEM((h, 1), jnp.float32),      # running denominator
            pltpu.SMEM((1,), jnp.int32),          # blocks folded so far
        ],
    )
    return pl.pallas_call(
        functools.partial(_mla_paged_decode_kernel, scale=scale,
                          layer=layer, page_size=ps, group=group,
                          n_win=n_win, rank=rank),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, rank), jnp.float32),
        # slots in order (a slot's end starts its successor's copies).
        # The compiler's two bounds checks a copy are 12 of the 19
        # scalar bundles a copy costs to issue, and the issue does not
        # run under the fold: off, because every id is held inside the
        # pool below and a copy's VMEM side is static
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), disable_bounds_checks=True),
        interpret=interpret,
        name="mla_paged_decode_attention",
    )(positions.astype(jnp.int32),
      jnp.clip(page_table.astype(jnp.int32), 0, n_pages - 1).reshape(-1),
      q, pages)


def xla_mla_paged_decode_attention(q, pages, page_table, positions, *,
                                   layer, rank, scale,
                                   window: Optional[int] = None):
    """The latent decode in plain XLA: ``take``-gather layer
    ``layer``'s windowed pages into ``[B, W, R + Rw]`` rows and run the
    absorbed math with a float32 softmax."""
    b, n_win = page_table.shape
    rows = jnp.take(pages[layer], page_table, axis=0)    # [B, n, ps, .]
    rows = rows.reshape(b, n_win * rows.shape[2], rows.shape[3])
    if window is not None and window < rows.shape[1]:
        rows = jax.lax.slice_in_dim(rows, 0, window, axis=1)
    s = jnp.einsum("bhr,bwr->bhw", q, rows,
                   preferred_element_type=jnp.float32) * scale
    mask = jnp.arange(rows.shape[1])[None, :] <= positions[:, None]
    p = jax.nn.softmax(jnp.where(mask[:, None, :], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhw,bwr->bhr", p.astype(rows.dtype),
                      rows[..., :rank],
                      preferred_element_type=jnp.float32)


def mla_paged_decode_attention(
    q: jax.Array,
    pages: jax.Array,
    page_table: jax.Array,
    positions: jax.Array,
    *,
    layer: int,
    rank: int,
    scale: float,
    window: Optional[int] = None,
    impl: str = "auto",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Single-step ABSORBED latent attention through a page table.

    Args:
      q: ``[B, H, R + Rw]`` — per head, the no-position query carried
        into the latent space (``q_nope @ W_uk``, ``R = rank`` values),
        then the rotated position query, zero beyond the rotary width:
        the layout of a cache row.
      pages: ``[L, P, page_size, R + Rw]`` — ALL layers' pages, a row a
        token: the normed latent, then the rotated shared position key,
        zero-padded to whole lanes. The kernel reads the pool where it
        lies in HBM, ``layer`` a static index of each page copy: no
        layer is ever sliced out of (and copied from) the pool, and a
        live page is one contiguous DMA.
      page_table: ``[B, n_win]`` int32, windowed (see
        :func:`paged_decode_attention`).
      positions: ``[B]``, none negative — slot ``b`` attends columns
        ``[0, pos]``; no page beyond ``pos`` is read.
      layer: static layer index; rank: ``R``.
      scale: softmax scale (the YaRN ``m^2`` folded in).
      window: the decode BUCKET's column bound (an upper bound on the
        columns of this step, XLA path only), as in
        :func:`paged_decode_attention`; never a model's sliding window.

    Returns ``[B, H, R]`` f32: ``softmax(scores) @ c`` per head, to be
    carried out of the latent space by ``W_uv`` in the caller.
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "pallas":
        if interpret is None:
            from . import default_interpret

            interpret = default_interpret()
        return _pallas_mla_paged_decode(
            q, pages, page_table, positions, int(layer), int(rank),
            float(scale), bool(interpret))
    if impl != "xla":
        raise ValueError(
            f"impl must be 'pallas', 'xla' or 'auto', got {impl!r}")
    return xla_mla_paged_decode_attention(
        q, pages, page_table, positions, layer=layer, rank=rank,
        scale=scale, window=window)


# ------------------------------------- grouped heads, a lower column bound

# columns one grid step of the grouped kernel folds: G = this //
# page_size pages, each ONE DMA of one contiguous [ps, 2 * Hkv * Dh]
# page (K and V of a token side by side in one row)
_GQA_BLOCK_COLUMNS = 512


def _reach_page_ids(table, positions, group, page_size, reach):
    """``[B, n_blocks * G]``: the pool page each (grid step, operand)
    of the grouped kernel names, for a slot that attends columns
    ``[max(0, pos - reach + 1), pos]`` (``reach`` None: from 0).
    :func:`_live_page_ids` with a LOWER bound: the first grid step
    starts at the page that holds the first column in reach, so a page
    wholly below it is never named, never copied. Logical page ``g``
    sits at table entry ``g % E``: the identity for a page table (``g <
    E``), the wrap for a ring of ``E`` pages a slot (at most ``E``
    logical pages are in reach at once). Dead operands repeat a live
    page, which the pipeline does not copy twice."""
    b, entries = table.shape
    if reach is None:
        pos = jnp.clip(positions, 0, entries * page_size - 1)
        first = jnp.zeros_like(pos)
    else:
        pos = jnp.maximum(positions, 0)
        first = jnp.maximum(pos - (reach - 1), 0) // page_size
    live = (pos // page_size - first)[:, None, None]   # last live, from first
    blk = jnp.minimum(
        jnp.arange(pl.cdiv(entries, group))[None, :, None], live // group)
    rel = blk * group + jnp.arange(group)[None, None, :]
    rel = jnp.where(rel <= live, rel,
                    jnp.where(blk > 0, rel - group, live))
    logical = first[:, None, None] + rel
    return jnp.take_along_axis(table, (logical % entries).reshape(b, -1),
                               axis=1)


def _gqa_paged_decode_kernel(pos_ref, ids_ref, q_ref, *rest, scale,
                             page_size, group, reach, sink):
    """One (slot, block of ``group`` pages) cell of the GROUPED-head
    paged decode, all query heads at once. A cache row holds K then V
    of the ``Hkv`` key/value heads side by side (``[ps, Hkv * (Dk +
    Dv)]`` a page: one DMA for both). The query is block-diagonal over
    the KEY/VALUE heads (``[Hq, Hkv * Dk]``: row ``t`` holds ``q_t`` in
    the lanes of head ``t // (Hq / Hkv)``), so one contraction with the
    K lanes is every query head's scores against its own group's keys
    and ``P @ V lanes`` (``[Hq, Hkv * Dv]``) holds head ``t``'s output
    in the lanes of its group. Block ``kb`` starts at the page of the
    first column in reach, not at column 0: a slot attends ``[max(0,
    pos - reach + 1), pos]`` (``reach`` None: ``[0, pos]``) and the
    first live page is masked below that bound. Same online-softmax
    recurrence as :func:`_mla_paged_decode_kernel`; ``sink`` (static):
    a ``[Hq, 1]`` logit a head follows the pages, a column with no
    value that starts the recurrence (``m = sink``, ``l = 1``)."""
    row_refs = rest[:group]
    sink_ref = rest[group] if sink else None
    o_ref, acc, m_scr, l_scr = rest[group + int(sink):]
    i = pl.program_id(0)
    kb = pl.program_id(1)
    keys = q_ref.shape[-1]                              # Hkv * Dk

    @pl.when(kb == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        if sink_ref is None:
            m_scr[:] = jnp.full_like(m_scr, NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
        else:
            m_scr[:] = sink_ref[...]
            l_scr[:] = jnp.ones_like(l_scr)

    pos = pos_ref[i]
    low = 0 if reach is None else jnp.maximum(pos - (reach - 1), 0)
    start = (low // page_size + kb * group) * page_size

    @pl.when(start <= pos)
    def _():
        pages = [ref[0, 0] for ref in row_refs]      # [ps, 2 * Hkv * Dh]
        rows = pages[0] if group == 1 else jnp.concatenate(pages, axis=0)
        s = jax.lax.dot_general(
            q_ref[0], rows[:, :keys], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [Hq, G*ps]
        col = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(jnp.logical_and(col >= low, col <= pos), s, NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc[:] = acc[:] * corr + jnp.dot(
            p.astype(rows.dtype), rows[:, keys:],
            preferred_element_type=jnp.float32)      # [Hq, Hkv * Dv]

    @pl.when(kb == pl.num_programs(1) - 1)
    def _():
        o_ref[0] = acc[:] / jnp.maximum(l_scr[:], 1e-30)


def _group_mask(heads, kv_heads):
    """``[Hq, Hkv]`` bool: query head ``t`` reads key/value head ``t //
    (Hq / Hkv)``."""
    return (jnp.arange(heads)[:, None] // (heads // kv_heads)
            == jnp.arange(kv_heads)[None, :])


def _pallas_gqa_paged_decode(q, pages, table, positions, layer, kv_heads,
                             reach, scale, interpret, sinks=None):
    b, heads, d = q.shape
    ps, width = pages.shape[2], pages.shape[3]
    keys = kv_heads * d                                 # Hkv * Dk
    entries = table.shape[1]
    group = max(1, min(_GQA_BLOCK_COLUMNS // ps, entries))
    pick = _group_mask(heads, kv_heads)
    q_bd = jnp.where(pick[None, :, :, None], q[:, :, None, :],
                     jnp.zeros((), q.dtype)).reshape(b, heads, keys)

    def slot_spec(lanes):
        return pl.BlockSpec((1, heads, lanes),
                            lambda i, kb, pos, ids: (i, 0, 0))

    sink_specs, sink_args = [], []
    if sinks is not None:
        sink_specs = [pl.BlockSpec((heads, 1),
                                   lambda i, kb, pos, ids: (0, 0))]
        sink_args = [sinks.astype(jnp.float32).reshape(heads, 1)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # positions, page ids
        grid=(b, pl.cdiv(entries, group)),
        in_specs=[slot_spec(keys)] + [
            _page_spec((1, 1, ps, width), layer, g, group)
            for g in range(group)] + sink_specs,
        out_specs=slot_spec(width - keys),
        scratch_shapes=[
            pltpu.VMEM((heads, width - keys), jnp.float32),  # accumulator
            pltpu.VMEM((heads, 1), jnp.float32),     # running max
            pltpu.VMEM((heads, 1), jnp.float32),     # running denominator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_gqa_paged_decode_kernel, scale=scale,
                          page_size=ps, group=group, reach=reach,
                          sink=sinks is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, heads, width - keys),
                                       jnp.float32),
        interpret=interpret,
        # one body, two names: the trace tells the layers that read a
        # window from those that read the context
        name=("gqa_paged_decode_attention_full" if reach is None
              else "gqa_paged_decode_attention_window"),
    )(positions.astype(jnp.int32),
      _reach_page_ids(table.astype(jnp.int32), positions, group, ps, reach),
      q_bd, *([pages] * group), *sink_args)
    out = out.reshape(b, heads, kv_heads, (width - keys) // kv_heads)
    return jnp.sum(jnp.where(pick[None, :, :, None], out, 0.0), axis=2)


def xla_gqa_paged_decode_attention(q, pages, table, positions, *, layer,
                                   kv_heads, scale,
                                   reach: Optional[int] = None,
                                   sinks=None):
    """The grouped decode in plain XLA: ``take``-gather every table
    entry of layer ``layer`` into ``[B, E * ps, .]`` rows, give each
    entry the logical page it holds now (entry ``j`` holds the newest
    page ``g <= pos // ps`` with ``g % E == j``: itself under a page
    table, the wrap under a ring), mask columns outside ``[max(0, pos -
    reach + 1), pos]`` and run grouped attention with a float32
    softmax, K and V never repeated per query head; a head's ``sinks``
    logit, where given, is one more column of the softmax with no
    value."""
    b, entries = table.shape
    heads, d = q.shape[1], q.shape[2]
    ps = pages.shape[2]
    rows = jnp.take(pages[layer], table, axis=0)         # [B, E, ps, .]
    last = (positions // ps)[:, None]
    logical = last - jnp.mod(last - jnp.arange(entries)[None, :], entries)
    col = (logical[:, :, None] * ps
           + jnp.arange(ps)[None, None, :]).reshape(b, entries * ps)
    low = (jnp.zeros_like(positions) if reach is None
           else jnp.maximum(positions - (reach - 1), 0))
    mask = jnp.logical_and(col >= low[:, None], col <= positions[:, None])
    rows = rows.reshape(b, entries * ps, -1)
    keys = kv_heads * d
    k = rows[..., :keys].reshape(b, entries * ps, kv_heads, d)
    v = rows[..., keys:].reshape(b, entries * ps, kv_heads, -1)
    s = jnp.einsum("bkgd,bwkd->bkgw",
                   q.reshape(b, kv_heads, heads // kv_heads, d), k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
    if sinks is None:
        p = jax.nn.softmax(s, axis=-1)
    else:
        sink = jnp.broadcast_to(
            sinks.astype(jnp.float32).reshape(1, kv_heads, -1, 1),
            s.shape[:-1] + (1,))
        p = jax.nn.softmax(jnp.concatenate([s, sink], axis=-1),
                           axis=-1)[..., :-1]
    out = jnp.einsum("bkgw,bwkd->bkgd", p.astype(rows.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, heads, -1)


def gqa_paged_decode_attention(
    q: jax.Array,
    pages: jax.Array,
    table: jax.Array,
    positions: jax.Array,
    *,
    layer: int,
    kv_heads: int,
    scale: float,
    reach: Optional[int] = None,
    sinks: Optional[jax.Array] = None,
    impl: str = "auto",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Single-step attention of GROUPED query heads through a table of
    pages, with an optional LOWER column bound (a sliding window) and
    an optional learned sink logit a head.

    Args:
      q: ``[B, Hq, Dk]`` - one pending query token a slot; heads ``t``
        with equal ``t // (Hq / Hkv)`` share a key/value head.
      pages: ``[L, P, page_size, Hkv * (Dk + Dv)]`` - ALL the pool's
        layers; a row is a token's K of every key/value head (``Hkv *
        Dk`` lanes), then its V (``Hkv * Dv``: values may be narrower
        than keys), so a page is one contiguous DMA. The kernel's
        index map picks ``layer``: the donated pool is read in place.
      table: ``[B, E]`` int32 - logical page ``g`` of slot ``b`` lives
        at ``table[b, g % E]``. A PAGE TABLE (``g < E``: callers pass
        the slice up to the decode BUCKET's column bound, as for
        :func:`paged_decode_attention`) or a RING of ``E`` pages a slot
        that token ``t`` writes at entry ``(t // page_size) % E``
        (``E >= ceil(reach / page_size) + 1``, so no page in reach is
        overwritten).
      positions: ``[B]`` - slot ``b`` attends columns ``[max(0, pos -
        reach + 1), pos]``: ``reach`` keys, its own included.
      layer: static layer index into ``pages``; kv_heads: ``Hkv``.
      scale: softmax scale.
      reach: the model's sliding window in columns (a LOWER bound that
        follows each slot's position), None for a layer that attends
        the whole context. Not the decode bucket's ``window``, which
        bounds a step's columns from above and is spent in the slice of
        ``table`` the caller passes.
      sinks: ``[Hq]`` float32 or None - head ``t``'s learned sink logit
        adds ``exp(sinks[t])`` to its softmax's denominator and no
        value (the online softmax starts from it).

    Only pages that hold a column in reach are copied; the first of
    them is masked below the bound. Returns ``[B, Hq, Dv]`` f32.
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    reach = None if reach is None else int(reach)
    if impl == "pallas":
        if interpret is None:
            from . import default_interpret

            interpret = default_interpret()
        return _pallas_gqa_paged_decode(
            q, pages, table, positions, int(layer), int(kv_heads), reach,
            float(scale), bool(interpret), sinks)
    if impl != "xla":
        raise ValueError(
            f"impl must be 'pallas', 'xla' or 'auto', got {impl!r}")
    return xla_gqa_paged_decode_attention(
        q, pages, table, positions, layer=layer, kv_heads=kv_heads,
        scale=scale, reach=reach, sinks=sinks)


def xla_decode_attention(q, k, v, mask):
    """The reference math (bit-identical to the engine's original
    inline einsums and ``inference.generate._block_decode``): f32
    logits, masked softmax, f32 PV. ``mask``: [B, S] key validity."""
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    probs = jax.nn.softmax(
        jnp.where(mask[:, None, None, :], logits, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))


def decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    positions: Optional[jax.Array] = None,
    *,
    mask: Optional[jax.Array] = None,
    impl: str = "auto",
    block_k: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Single-step cached attention over a KV window.

    Args:
      q: ``[B, 1, H, Dh]`` — one pending query token per slot.
      k, v: ``[B, S, H, Dh]`` KV window (the engine passes the
        length-bucketed prefix slice of its slot caches), or a
        :class:`...kv_quant.QuantizedKV` pair (int8 data + the
        ``[B, S, H]`` f32 scale sidecar, dequanted in-stream).
      positions: ``[B]`` int — slot ``b`` attends columns
        ``[0, positions[b]]`` inclusive. Required for the Pallas path;
        the XLA path derives ``mask`` from it when ``mask`` is None.
      mask: ``[B, S]`` bool key validity (XLA path only) — lets ragged
        ``generate`` compose its pad-column mask in.
      impl: ``"pallas"`` | ``"xla"`` | ``"auto"`` (pallas on real TPU,
        xla elsewhere — the serving engine overrides to exercise the
        kernel in interpret mode on CPU tests).
      block_k: K/V block streamed per grid step (pallas path).
      interpret: force Pallas interpret mode; default auto (interpret
        everywhere except real TPU).

    Returns ``[B, 1, H, Dh]`` f32 attention output (caller casts).
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "pallas":
        if positions is None:
            raise ValueError("the pallas decode path needs positions")
        if mask is not None:
            raise ValueError(
                "mask composes only with impl='xla' (the pallas kernel "
                "masks from positions)")
        if interpret is None:
            from . import default_interpret

            interpret = default_interpret()
        scale = q.shape[-1] ** -0.5
        if isinstance(k, QuantizedKV):
            return _pallas_decode(q, k.data, v.data, positions, scale,
                                  int(block_k), bool(interpret),
                                  k_scale=k.scale, v_scale=v.scale)
        return _pallas_decode(q, k, v, positions, scale, int(block_k),
                              bool(interpret))
    if impl != "xla":
        raise ValueError(
            f"impl must be 'pallas', 'xla' or 'auto', got {impl!r}")
    if mask is None:
        if positions is None:
            raise ValueError("xla path needs positions or mask")
        mask = (jnp.arange(k.shape[1])[None, :]
                <= positions[:, None])
    if isinstance(k, QuantizedKV):
        k, v = dequantize_kv(k, q.dtype), dequantize_kv(v, q.dtype)
    return xla_decode_attention(q, k, v, mask)


# ------------------------------------------------------------- graftspec
#
# k-query VERIFY attention: the speculative-decode verify pass runs
# k+1 query tokens per slot (the pending token + k drafts) against the
# same cached columns one decode step reads, in ONE batched pass —
# more MXU rows over the SAME K/V stream, which is the whole
# bandwidth-bound argument for speculation (the committed costs.json
# budgets pin verify bytes ~ decode bytes at (k+1)x the query FLOPs).
# Query row i sits at column positions[b] + i and attends [0, pos+i]
# — after the caller's cache writes, that window includes the
# in-flight keys of the preceding draft queries, exactly the causal
# set a future single-query step would see. The XLA reference is the
# same einsum/masked-softmax math as xla_decode_attention with the
# row-staggered mask; the Pallas kernel is the paged kernel's body
# with K1 * H query rows (the engine verifies on pages only).


def xla_verify_decode_attention(q, k, v, positions):
    """Reference k-query verify math: xla_decode_attention's exact
    einsum/masked-softmax shape with the row-staggered mask — query
    row ``i`` attends columns ``[0, positions[b] + i]`` inclusive.
    K1=1 degenerates to the single-query reference bit-for-bit."""
    scale = q.shape[-1] ** -0.5
    k1 = q.shape[1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    mask = (jnp.arange(k.shape[1])[None, None, :]
            <= positions[:, None, None]
            + jnp.arange(k1)[None, :, None])          # [B, K1, S]
    probs = jax.nn.softmax(
        jnp.where(mask[:, None], logits, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))


def xla_paged_verify_decode_attention(q, k_pages, v_pages, page_table,
                                      positions,
                                      window: Optional[int] = None,
                                      *, layer: int):
    """Paged reference verify: the same take-gather (+ graftquant
    dequant) as :func:`xla_paged_decode_attention`, then the dense
    reference."""
    h = q.shape[2]
    k_win = _gather_paged_window(k_pages, layer, page_table, h, q.dtype,
                                 window)
    v_win = _gather_paged_window(v_pages, layer, page_table, h, q.dtype,
                                 window)
    return xla_verify_decode_attention(q, k_win, v_win, positions)


def paged_verify_decode_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,
    positions: jax.Array,
    *,
    layer: int,
    window: Optional[int] = None,
    impl: str = "auto",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Speculative-verify attention (graftspec x graftpage): ``q``
    is ``[B, K1, H, Dh]`` — row ``i`` is the query at column
    ``positions[b] + i`` (the pending token, then the k drafts; the
    caller has already written the K1 in-flight columns) and attends
    ``[0, positions[b] + i]`` inclusive. It reads layer ``layer`` of
    the same ``[L, P, page_size, H * Dh]`` pools through the same
    windowed page-table slice the single-query paged step uses — the
    same kernel body with ``K1 * H`` query rows. Pages may be
    :class:`...ops.kv_quant.QuantizedKV` (graftquant). Returns
    ``[B, K1, H, Dh]`` f32 (caller casts)."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "pallas":
        if interpret is None:
            from . import default_interpret

            interpret = default_interpret()
        return _pallas_paged_attention(
            q, k_pages, v_pages, page_table, positions, int(layer),
            q.shape[-1] ** -0.5, bool(interpret),
            "paged_verify_decode_attention")
    if impl != "xla":
        raise ValueError(
            f"impl must be 'pallas', 'xla' or 'auto', got {impl!r}")
    return xla_paged_verify_decode_attention(
        q, k_pages, v_pages, page_table, positions, window, layer=layer)
