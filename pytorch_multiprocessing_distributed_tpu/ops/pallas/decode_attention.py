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

The PAGED decode kernel (``[P, H, ps, Dh]`` pools behind a page table)
has the grid ``(slot, block of G pages)``: one step folds ALL heads of
``G * ps`` columns (G = 8 pages of 16, 1 page of 128), a softmax state
per head, the two contractions batched over heads. A page's
``[H, ps, Dh]`` is contiguous, so it is one DMA; each pool is passed G
times with plain ``BlockSpec``s — operand ``g`` of a step is page ``g``
of its block — and the pipeline double-buffers them, the next step's
pages (the next slot's first block at a slot's end) in flight while
this one is folded. Which pool page a step's operand names is worked
out once, in XLA, from positions and table (:func:`_live_page_ids`):
only pages at or before the slot's position, and a block beyond it
names its predecessor's pages again, which the pipeline does not copy
twice — so a slot costs its live pages, not its window. (A hand-rolled
``make_async_copy`` of a page does not lower for ``Dh`` 64: Mosaic pads
the pool's minor dimension to 128 lanes and then refuses the 64-wide
slice.)

Matmuls stay in the input dtype (bf16 hits the MXU's native rate),
accumulation is f32, outputs are f32 (the engine casts back to model
dtype after the residual add, matching the XLA path's dtypes exactly).

**graftquant**: every kernel (and every XLA reference) also takes the
KV operand as a :class:`...kv_quant.QuantizedKV` pair — int8 data plus
a per-(token, head) f32 scale streamed beside it (dense: a ``[B*H,
1, S]`` row per block; paged decode: the ``[H, ps]`` sidecar of the
SAME page, through the same page ids; paged verify: one ``[ps]`` row
of it, viewed ``[P, H, 1, ps]`` — the size-1 axis is what makes a
one-row scale block legal on the TPU). The
dequant is ONE multiply in the
VMEM stream, applied before the existing MXU dot — so the decode step's
dominant HBM bytes term (the K/V read) halves while the matmul dtype
and f32 accumulation stay exactly as above. The XLA fallbacks dequant
with the identical expression before the reference einsum, so CPU
tier-1 pins the exact math the TPU kernel runs.

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
           "verify_decode_attention", "paged_verify_decode_attention",
           "xla_decode_attention", "xla_paged_decode_attention",
           "xla_verify_decode_attention",
           "xla_paged_verify_decode_attention"]


def _paged_scale_spec(page_size, heads):
    """Block of one (page, head) row of the ``[P, H, 1, ps]`` scale
    side-car view, steered by the same scalar-prefetched table as its
    page. The size-1 axis keeps the block's trailing two dims equal to
    the array's — a ``(1, ps)`` block of ``[H, ps]`` is not a legal TPU
    block."""
    return pl.BlockSpec(
        (1, 1, 1, page_size),
        lambda i, kb, pos, tab: (tab[i // heads, kb], i % heads, 0, 0))


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
# buffered by the pipeline) may take this much by their nominal size;
# the chip's tiled layout pads a 64-wide head to 128 lanes, so at
# most twice this
_PAGE_BLOCK_BYTES = 4 << 20


def _pages_per_step(page_size, n_win, page_bytes):
    """G, the pages one grid step folds as one block: the fewest whose
    block has 128 columns (8 at ``page_size`` 16, 1 at 128), no more
    than the window has, and no more than the VMEM budget holds (K and
    V, two buffers each)."""
    fit = _PAGE_BLOCK_BYTES // (4 * page_bytes)
    return max(1, min(128 // page_size, n_win, fit))


def _live_page_ids(page_table, positions, group, page_size):
    """``[B, n_blocks * G]``: the pool page each (grid step, operand)
    of the paged kernel names. Only live pages are ever named, so
    nothing beyond a slot's position is copied: a block that starts
    beyond the position names the pages of the slot's last live block
    again, and a page beyond the position inside that block names the
    page its operand held a step before (in a slot's first block: the
    last live page) — the pipeline copies nothing when a block index
    repeats. Computed once from ``positions`` in XLA, so an index map
    is one SMEM read."""
    b, n_win = page_table.shape
    last = jnp.clip(positions, 0, n_win * page_size - 1) // page_size
    last = last[:, None, None]                           # [B, 1, 1]
    blk = jnp.minimum(jnp.arange(pl.cdiv(n_win, group))[None, :, None],
                      last // group)
    page = blk * group + jnp.arange(group)[None, None, :]
    page = jnp.where(page <= last, page,
                     jnp.where(blk > 0, page - group, last))
    return jnp.take_along_axis(page_table, page.reshape(b, -1), axis=1)


def _page_spec(block_shape, g, group):
    """Block of page ``g`` of a grid step's ``group``: all heads of ONE
    page (or of its scale sidecar), steered by the scalar-prefetched
    :func:`_live_page_ids`."""
    zeros = (0,) * (len(block_shape) - 1)
    return pl.BlockSpec(
        block_shape,
        lambda i, kb, pos, ids: (ids[i, kb * group + g],) + zeros)


def _paged_decode_kernel(pos_ref, ids_ref, q_ref, *rest, scale,
                         page_size, group, quant):
    """One (slot, block of ``group`` pages) grid cell of the PAGED
    flash-decode, all heads at once: the same online-softmax
    recurrence as :func:`_decode_kernel` with a softmax state per
    head. Each of the block's pages arrives as its own operand —
    whatever PAGE the table maps it to, the index map doing the
    indirection BEFORE the DMA (:func:`_page_spec`) — and the pages
    are folded side by side as ONE block of ``group * ps`` columns. A
    block that starts beyond the slot's position folds nothing (and
    copied nothing); the column mask keeps the pages beyond the
    position inside the last live block out of the softmax.
    ``quant`` (static): each page brings its ``[H, ps]`` scale
    sidecar through the same indirection."""
    k_refs, v_refs = rest[:group], rest[group:2 * group]
    rest = rest[2 * group:]
    ks_refs = vs_refs = (None,) * group
    if quant:
        ks_refs, vs_refs = rest[:group], rest[group:2 * group]
        rest = rest[2 * group:]
    o_ref, acc, m_scr, l_scr = rest
    i = pl.program_id(0)
    kb = pl.program_id(1)
    block_k = group * page_size

    def block(refs, scale_refs):
        """The step's pages side by side: ``[H, G * ps, Dh]``."""
        pages = [ref[0] if s_ref is None
                 else _kernel_dequant(ref[0], s_ref[0], q_ref.dtype)
                 for ref, s_ref in zip(refs, scale_refs)]
        return pages[0] if group == 1 else jnp.concatenate(pages, axis=1)

    @pl.when(kb == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    pos = pos_ref[i]

    # block entirely beyond the slot's position -> skip (same per-slot
    # cost gate as the dense kernel's block gate; unallocated table
    # entries point at the scratch page, whose values this gate and
    # the column mask keep out of the softmax)
    @pl.when(kb * block_k <= pos)
    def _():
        q = q_ref[0]                                     # [H, 1, Dh]
        s = jax.lax.dot_general(
            q, block(k_refs, ks_refs),
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale  # [H, 1, G*ps]
        col = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 2)
        s = jnp.where(col <= pos, s, NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc[:] = acc[:] * corr + jax.lax.dot_general(
            p.astype(q.dtype), block(v_refs, vs_refs),
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)          # [H, 1, Dh]

    @pl.when(kb == pl.num_programs(1) - 1)
    def _():
        o_ref[0] = acc[:] / jnp.maximum(l_scr[:], 1e-30)


def _pallas_paged_decode(q, k_pages, v_pages, page_table, positions,
                         scale, interpret, k_scale=None, v_scale=None):
    """q [B, 1, H, Dh]; k/v pages [P, H, ps, Dh]; page_table
    [B, n_win] int32; positions [B] -> f32 [B, 1, H, Dh]. Grid is
    (slot, block of G pages), G from :func:`_pages_per_step`: one step
    folds all heads of ``G * ps`` columns. Positions and the page ids
    ride in SMEM via scalar prefetch; each pool is passed G times,
    once per page of a step's block, so a whole ``[H, ps, Dh]`` page
    is one DMA and the pipeline keeps the next step's G pages in
    flight (the next slot's first block at a slot's end) — never a
    page beyond a slot's position (:func:`_live_page_ids`). graftquant:
    ``k_scale``/``v_scale`` (``[P, H, ps]`` f32) ride the same
    indirection as their pages."""
    b, _, h, d = q.shape
    ps = k_pages.shape[2]
    n_win = page_table.shape[1]
    quant = k_scale is not None
    group = _pages_per_step(ps, n_win,
                            h * ps * d * k_pages.dtype.itemsize)
    q4 = jnp.moveaxis(q, 2, 1)                           # [B, H, 1, Dh]

    def page_specs(block_shape):
        return [_page_spec(block_shape, g, group) for g in range(group)]

    q_spec = pl.BlockSpec((1, h, 1, d),
                          lambda i, kb, pos, ids: (i, 0, 0, 0))
    in_specs = [q_spec] + page_specs((1, h, ps, d)) * 2
    operands = [q4] + [k_pages] * group + [v_pages] * group
    if quant:
        in_specs += page_specs((1, h, ps)) * 2
        operands += [k_scale] * group + [v_scale] * group
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # positions, page ids
        grid=(b, pl.cdiv(n_win, group)),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((h, 1, d), jnp.float32),   # output accumulator
            pltpu.VMEM((h, 1, 1), jnp.float32),   # running max
            pltpu.VMEM((h, 1, 1), jnp.float32),   # running denominator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=scale,
                          page_size=ps, group=group, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, 1, d), jnp.float32),
        interpret=interpret,
        name="paged_decode_attention",
    )(positions.astype(jnp.int32),
      _live_page_ids(page_table.astype(jnp.int32), positions, group, ps),
      *operands)
    return jnp.moveaxis(out, 1, 2)                       # [B, 1, H, Dh]


def _gather_paged_window(pages, page_table, q_dtype,
                         window: Optional[int] = None):
    """``take``-gather windowed pages into the contiguous
    ``[B, W, H, Dh]`` view the dense references consume. graftquant
    pages gather BOTH leaves through the same table, then dequant with
    the kernel's exact expression — per-element identical to the
    in-VMEM dequant, which is what keeps the XLA fallback the pin."""
    b, n_win = page_table.shape
    if isinstance(pages, QuantizedKV):
        h, ps, d = pages.shape[1], pages.shape[2], pages.shape[3]
        gd = jnp.take(pages.data, page_table, axis=0)
        gd = jnp.moveaxis(gd, 3, 2).reshape(b, n_win * ps, h, d)
        gs = jnp.take(pages.scale, page_table, axis=0)
        gs = jnp.moveaxis(gs, 3, 2).reshape(b, n_win * ps, h)
        g = dequantize_kv(QuantizedKV(gd, gs), q_dtype)
    else:
        h, ps, d = pages.shape[1], pages.shape[2], pages.shape[3]
        g = jnp.take(pages, page_table, axis=0)  # [B, n_win, H, ps, Dh]
        g = jnp.moveaxis(g, 3, 2).reshape(b, n_win * ps, h, d)
    if window is not None and window < n_win * ps:
        g = jax.lax.slice_in_dim(g, 0, window, axis=1)
    return g


def xla_paged_decode_attention(q, k_pages, v_pages, page_table,
                               positions, window: Optional[int] = None):
    """Reference paged path: ``take``-gather the windowed pages into
    the contiguous ``[B, W, H, Dh]`` view and run the EXACT dense
    reference math (:func:`xla_decode_attention`) — bit-identical to
    the dense-slot engine on the same logical columns, which is the
    seam the paged==dense equivalence pin rests on. Quantized pages
    dequant at the gather (the kernel's exact per-element math)."""
    k_win = _gather_paged_window(k_pages, page_table, q.dtype, window)
    v_win = _gather_paged_window(v_pages, page_table, q.dtype, window)
    mask = (jnp.arange(k_win.shape[1])[None, :] <= positions[:, None])
    return xla_decode_attention(q, k_win, v_win, mask)


def paged_decode_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,
    positions: jax.Array,
    *,
    window: Optional[int] = None,
    impl: str = "auto",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Single-step cached attention through a page table (graftpage).

    Args:
      q: ``[B, 1, H, Dh]`` — one pending query token per slot.
      k_pages, v_pages: ``[P, H, page_size, Dh]`` page storage (ONE
        layer's pages — heads before the column offset so the Pallas
        block's trailing dims are the tileable ``[page_size, Dh]``),
        or a :class:`...kv_quant.QuantizedKV` pair (int8 data + the
        ``[P, H, page_size]`` f32 scale sidecar, dequanted in-stream).
      page_table: ``[B, n_win]`` int32 — slot ``b``'s logical column
        block ``kb`` lives in page ``page_table[b, kb]``. Callers pass
        the WINDOWED slice of the full table (``ceil(window /
        page_size)`` entries); unallocated entries point at the
        scratch page 0, whose contents the position mask keeps out of
        the softmax.
      positions: ``[B]`` int — slot ``b`` attends columns
        ``[0, positions[b]]`` inclusive.
      window: optional logical column bound (< ``n_win * page_size``
        trims the gathered tail on the XLA path; the Pallas path's
        column mask makes it a no-op there).
      impl / interpret: as :func:`decode_attention`.

    Returns ``[B, 1, H, Dh]`` f32 attention output (caller casts).
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "pallas":
        if interpret is None:
            from . import default_interpret

            interpret = default_interpret()
        scale = q.shape[-1] ** -0.5
        if isinstance(k_pages, QuantizedKV):
            return _pallas_paged_decode(
                q, k_pages.data, v_pages.data, page_table, positions,
                scale, bool(interpret), k_scale=k_pages.scale,
                v_scale=v_pages.scale)
        return _pallas_paged_decode(q, k_pages, v_pages, page_table,
                                    positions, scale, bool(interpret))
    if impl != "xla":
        raise ValueError(
            f"impl must be 'pallas', 'xla' or 'auto', got {impl!r}")
    return xla_paged_decode_attention(q, k_pages, v_pages, page_table,
                                      positions, window)


# ---------------------------------------------------- latent (MLA) pages

# columns one grid step of the latent kernel folds: G = this // page_size
# pages, each ONE DMA of one contiguous [ps, R + Rw] page (the kernel's
# time is the DMAs' issue, ~0.1 us each, not their bytes: PERF.md)
_MLA_BLOCK_COLUMNS = 512


def _mla_page_spec(block_shape, layer, g, group):
    """Block of page ``g`` of a grid step's ``group`` in layer ``layer``
    of a ``[L, P, ps, .]`` pool: the layer is static, the page comes
    from the scalar-prefetched :func:`_live_page_ids`."""
    return pl.BlockSpec(
        block_shape,
        lambda i, kb, pos, ids: (layer, ids[i, kb * group + g], 0, 0))


def _mla_paged_decode_kernel(pos_ref, ids_ref, q_ref, *rest, scale,
                             page_size, group, rank):
    """One (slot, block of ``group`` pages) cell of the ABSORBED latent
    decode, all heads at once. The cache holds one row a token that
    every head shares: the normed latent ``c`` (``rank`` values), then
    the rotated ``k_rope``, zero-padded to whole lanes. The query is
    laid out the same way (``q_lat | q_rope | 0``), so the scores
    ``q_lat . c + q_rope . k_rope`` are ONE contraction over the row,
    and the output is ``P c`` — still in the latent space; the caller
    applies the per-head value up-projection. Same online-softmax
    recurrence and the same live-page indirection as
    :func:`_paged_decode_kernel`; a row is read once and used as key
    and (its first ``rank`` lanes) as value."""
    row_refs = rest[:group]
    o_ref, acc, m_scr, l_scr = rest[group:]
    i = pl.program_id(0)
    kb = pl.program_id(1)
    block_k = group * page_size

    @pl.when(kb == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    pos = pos_ref[i]

    @pl.when(kb * block_k <= pos)
    def _():
        pages = [ref[0, 0] for ref in row_refs]          # [ps, R + Rw]
        rows = pages[0] if group == 1 else jnp.concatenate(pages, axis=0)
        s = jax.lax.dot_general(
            q_ref[0], rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [H, G*ps]
        col = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(col <= pos, s, NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc[:] = acc[:] * corr + jnp.dot(
            p.astype(rows.dtype), rows[:, :rank],
            preferred_element_type=jnp.float32)

    @pl.when(kb == pl.num_programs(1) - 1)
    def _():
        o_ref[0] = acc[:] / jnp.maximum(l_scr[:], 1e-30)


def _pallas_mla_paged_decode(q, pages, page_table, positions, layer,
                             rank, scale, interpret):
    b, h, width = q.shape
    ps = pages.shape[2]
    n_win = page_table.shape[1]
    group = max(1, min(_MLA_BLOCK_COLUMNS // ps, n_win))

    def slot_spec(last):
        return pl.BlockSpec((1, h, last), lambda i, kb, pos, ids: (i, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # positions, page ids
        grid=(b, pl.cdiv(n_win, group)),
        in_specs=[slot_spec(width)] + [
            _mla_page_spec((1, 1, ps, width), layer, g, group)
            for g in range(group)],
        out_specs=slot_spec(rank),
        scratch_shapes=[
            pltpu.VMEM((h, rank), jnp.float32),   # latent accumulator
            pltpu.VMEM((h, 1), jnp.float32),      # running max
            pltpu.VMEM((h, 1), jnp.float32),      # running denominator
        ],
    )
    return pl.pallas_call(
        functools.partial(_mla_paged_decode_kernel, scale=scale,
                          page_size=ps, group=group, rank=rank),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, rank), jnp.float32),
        interpret=interpret,
        name="mla_paged_decode_attention",
    )(positions.astype(jnp.int32),
      _live_page_ids(page_table.astype(jnp.int32), positions, group, ps),
      q, *([pages] * group))


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
        zero-padded to whole lanes. The kernel's index map picks
        ``layer``, so no layer is ever sliced out of (and copied from)
        the pool, and a page is one contiguous DMA.
      page_table: ``[B, n_win]`` int32, windowed (see
        :func:`paged_decode_attention`).
      positions: ``[B]`` — slot ``b`` attends columns ``[0, pos]``.
      layer: static layer index; rank: ``R``.
      scale: softmax scale (the YaRN ``m^2`` folded in).

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
# row-staggered mask; the Pallas kernels are the flash recurrence
# with a [K1, d] query block instead of [1, d].


def _verify_kernel(pos_ref, q_ref, k_ref, v_ref, *rest, scale, block_k,
                   heads, k1, quant):
    """One (slot*head, k-block) grid cell; the softmax state is [K1]
    rows of the same online recurrence as :func:`_decode_kernel`
    (``pos_ref``: the scalar-prefetched ``[B]`` positions, as there).
    ``quant`` (static): dequant each K/V block in-stream — the verify
    pass reads the SAME quantized pages one decode step reads, so
    spec-decode bandwidth halves with it."""
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

    # the block matters to SOME query row iff its first column is
    # within the last row's reach (pos + k1 - 1); per-row masking
    # below keeps earlier rows exact
    @pl.when(kb * block_k <= pos + k1 - 1)
    def _():
        q = q_ref[0]          # [K1, d]
        kblk = k_ref[0]       # [bk, d]
        vblk = v_ref[0]
        if quant:
            kblk = _kernel_dequant(kblk, ks_ref[0, 0], q.dtype)
            vblk = _kernel_dequant(vblk, vs_ref[0, 0], q.dtype)
        s = jnp.dot(q, kblk.T,
                    preferred_element_type=jnp.float32) * scale  # [K1, bk]
        col = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (k1, block_k), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (k1, block_k), 0)
        s = jnp.where(col <= pos + row, s, NEG_INF)
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


def _pallas_verify(q, k, v, positions, scale, block_k, interpret,
                   k_scale=None, v_scale=None):
    """q [B, K1, H, Dh]; k/v [B, S, H, Dh]; positions [B] -> f32
    [B, K1, H, Dh]. graftquant: ``k_scale``/``v_scale`` ([B, S, H]
    f32) mark the K/V operands int8, dequanted per block in VMEM."""
    b, k1, h, d = q.shape
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

    q3 = merge(q)                      # [B*H, K1, Dh]
    k3, v3 = merge(k), merge(v)

    in_specs = [
        pl.BlockSpec((1, k1, d), lambda i, kb, pos: (i, 0, 0)),
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
        out_specs=pl.BlockSpec((1, k1, d), lambda i, kb, pos: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((k1, d), jnp.float32),   # output accumulator
            pltpu.VMEM((k1, 1), jnp.float32),   # running max
            pltpu.VMEM((k1, 1), jnp.float32),   # running denominator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_verify_kernel, scale=scale, block_k=block_k,
                          heads=h, k1=k1, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, k1, d), jnp.float32),
        interpret=interpret,
        name="verify_decode_attention",
    )(positions.astype(jnp.int32), *operands)
    return jnp.moveaxis(out.reshape(b, h, k1, d), 1, 2)  # [B, K1, H, Dh]


def _paged_verify_kernel(pos_ref, tab_ref, q_ref, k_ref, v_ref, *rest,
                         scale, page_size, heads, k1, quant):
    """Paged k-query verify: :func:`_paged_decode_kernel`'s
    scalar-prefetched page indirection with the [K1, d] query block
    and the row-staggered column mask. ``quant`` (static): the scale
    sidecars ride the same table indirection."""
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

    @pl.when(kb * page_size <= pos + k1 - 1)
    def _():
        q = q_ref[0]             # [K1, d]
        kblk = k_ref[0, 0]       # [ps, d]
        vblk = v_ref[0, 0]
        if quant:
            kblk = _kernel_dequant(kblk, ks_ref[0, 0, 0], q.dtype)
            vblk = _kernel_dequant(vblk, vs_ref[0, 0, 0], q.dtype)
        s = jnp.dot(q, kblk.T,
                    preferred_element_type=jnp.float32) * scale
        col = kb * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (k1, page_size), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (k1, page_size), 0)
        s = jnp.where(col <= pos + row, s, NEG_INF)
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


def _pallas_paged_verify(q, k_pages, v_pages, page_table, positions,
                         scale, interpret, k_scale=None, v_scale=None):
    """q [B, K1, H, Dh]; pages [P, H, ps, Dh]; page_table [B, n_win]
    -> f32 [B, K1, H, Dh]. graftquant: ``k_scale``/``v_scale``
    ([P, H, ps] f32) ride the same indirection as their pages."""
    b, k1, h, d = q.shape
    ps = k_pages.shape[2]
    n_win = page_table.shape[1]
    quant = k_scale is not None
    q3 = jnp.moveaxis(q, 2, 1).reshape(b * h, k1, d)

    in_specs = [
        pl.BlockSpec((1, k1, d),
                     lambda i, kb, pos, tab: (i, 0, 0)),
        pl.BlockSpec((1, 1, ps, d),
                     lambda i, kb, pos, tab:
                     (tab[i // h, kb], i % h, 0, 0)),
        pl.BlockSpec((1, 1, ps, d),
                     lambda i, kb, pos, tab:
                     (tab[i // h, kb], i % h, 0, 0)),
    ]
    operands = [q3, k_pages, v_pages]
    if quant:
        in_specs += [_paged_scale_spec(ps, h)] * 2
        operands += [k_scale[:, :, None], v_scale[:, :, None]]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # positions, page table
        grid=(b * h, n_win),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, k1, d),
                               lambda i, kb, pos, tab: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((k1, d), jnp.float32),   # output accumulator
            pltpu.VMEM((k1, 1), jnp.float32),   # running max
            pltpu.VMEM((k1, 1), jnp.float32),   # running denominator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_verify_kernel, scale=scale,
                          page_size=ps, heads=h, k1=k1, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, k1, d), jnp.float32),
        interpret=interpret,
        name="paged_verify_decode_attention",
    )(positions.astype(jnp.int32), page_table.astype(jnp.int32),
      *operands)
    return jnp.moveaxis(out.reshape(b, h, k1, d), 1, 2)


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
                                      window: Optional[int] = None):
    """Paged reference verify: the same take-gather (+ graftquant
    dequant) as :func:`xla_paged_decode_attention`, then the dense
    reference."""
    k_win = _gather_paged_window(k_pages, page_table, q.dtype, window)
    v_win = _gather_paged_window(v_pages, page_table, q.dtype, window)
    return xla_verify_decode_attention(q, k_win, v_win, positions)


def verify_decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    positions: jax.Array,
    *,
    impl: str = "auto",
    block_k: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Speculative-verify attention: ``K1 = k_draft + 1`` query tokens
    per slot over one KV window.

    Args:
      q: ``[B, K1, H, Dh]`` — row ``i`` is the query at column
        ``positions[b] + i`` (the pending token, then the k drafts).
      k, v: ``[B, S, H, Dh]`` KV window (the caller has already
        written the K1 in-flight columns, so row ``i`` sees its
        predecessors' keys — the causal verify set). May be
        :class:`...ops.kv_quant.QuantizedKV` (graftquant int8 +
        scale) — dequantized in the kernel's VMEM stream.
      positions: ``[B]`` int — row ``i`` attends ``[0, positions[b]
        + i]`` inclusive.
      impl / block_k / interpret: as :func:`decode_attention`.

    Returns ``[B, K1, H, Dh]`` f32 (caller casts)."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "pallas":
        if interpret is None:
            from . import default_interpret

            interpret = default_interpret()
        scale = q.shape[-1] ** -0.5
        if isinstance(k, QuantizedKV):
            return _pallas_verify(q, k.data, v.data, positions, scale,
                                  int(block_k), bool(interpret),
                                  k_scale=k.scale, v_scale=v.scale)
        return _pallas_verify(q, k, v, positions, scale, int(block_k),
                              bool(interpret))
    if impl != "xla":
        raise ValueError(
            f"impl must be 'pallas', 'xla' or 'auto', got {impl!r}")
    if isinstance(k, QuantizedKV):
        k = dequantize_kv(k, q.dtype)
        v = dequantize_kv(v, q.dtype)
    return xla_verify_decode_attention(q, k, v, positions)


def paged_verify_decode_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,
    positions: jax.Array,
    *,
    window: Optional[int] = None,
    impl: str = "auto",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Paged twin of :func:`verify_decode_attention` (graftspec x
    graftpage): the k-query verify reads KV through the same windowed
    page-table slice the single-query paged step uses. Pages may be
    :class:`...ops.kv_quant.QuantizedKV` (graftquant)."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "pallas":
        if interpret is None:
            from . import default_interpret

            interpret = default_interpret()
        scale = q.shape[-1] ** -0.5
        if isinstance(k_pages, QuantizedKV):
            return _pallas_paged_verify(
                q, k_pages.data, v_pages.data, page_table, positions,
                scale, bool(interpret),
                k_scale=k_pages.scale, v_scale=v_pages.scale)
        return _pallas_paged_verify(q, k_pages, v_pages, page_table,
                                    positions, scale, bool(interpret))
    if impl != "xla":
        raise ValueError(
            f"impl must be 'pallas', 'xla' or 'auto', got {impl!r}")
    return xla_paged_verify_decode_attention(q, k_pages, v_pages,
                                             page_table, positions,
                                             window)
