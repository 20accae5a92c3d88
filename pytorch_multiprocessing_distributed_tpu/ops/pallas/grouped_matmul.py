"""The grouped matmul of the routed experts (``ops/moe.py::
dropless_experts``): rows sorted by expert, each held expert's rows one
contiguous group from row 0, every group multiplied by its own
expert's matrix.

    out[r] = x[r] @ w[g]          for every row r of group g
    h[r]   = silu(x[r] @ w_gate[g]) * (x[r] @ w_up[g])   (the gated form)

Rows behind the last group are in no group; what the kernel leaves
there is unspecified (the caller masks them).

The KERNEL (``pallas_call(name="ragged-dot-experts")``) is a grid of
``(column block, work item)``. A work item is one (row tile, group)
pair whose rows meet: a group of 16 rows inside one 128-row tile is one
item, a tile that two groups cross is two. The items are listed in row
order, so one group's items follow one another, and so do one tile's.
The weight block's index map names the item's expert: consecutive items
of one expert name the same block, which the pipeline does not copy
again, and an expert with no rows is named by no item at all. So each
expert that has rows streams its matrix from HBM exactly once a call,
and one with none costs nothing. The row tile stays in VMEM across its
items, and so does its output block, each item writing only its own
group's rows (a mask of the group's bounds over the tile's rows): no
row is multiplied against an expert it does not belong to beyond its
own tile. The column axis is outermost, so the rows are read once a
column block and the weights once in all. The grid is static: items
beyond the call's count name the last item's blocks again and compute
nothing.

The gated form reads ``w_gate`` and ``w_up`` blocks of the same output
columns in one step against one read of the rows and writes ``silu(g) *
u`` (float32, cast to the rows' dtype): the layer's gate and up in one
call, down in a second. Matmuls accumulate in float32 over the whole
contraction (no contraction tiling); the plain form returns float32.

Blocks follow from static shapes alone (rows, contraction, columns, the
number of matrices): a row tile of up to 128 rows and the widest column
block whose double-buffered blocks fit ``_BLOCK_BYTES`` of VMEM.

The item list (``work_items``), the groups' bounds and the item count
ride in SMEM (scalar prefetch), computed in XLA from the group sizes.

``impl="xla"`` is ``jax.lax.ragged_dot`` (the compiler's own grouped
matmul): the reference and the CPU fallback; CPU tier-1 runs the
kernel in interpret mode.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["grouped_matmul", "gated_grouped_matmul", "work_items"]

# the most rows a tile: below the MXU's 128 a tile costs the chip about
# what 128 rows cost (the matrix passes through the MXU either way),
# above it every group boundary multiplies more dead rows
_MAX_ROW_TILE = 128

# bfloat16 packs 16 rows to a tile of the VMEM: a row tile is a
# multiple of it
_SUBLANES = 16

# the VMEM the pipelined blocks (two buffers each of rows, weights and
# output) and the float32 products may take; the kernel's limit beside it
_BLOCK_BYTES = 32 << 20
_VMEM_LIMIT = 64 << 20


def _row_tile(rows: int) -> int:
    """Rows a tile: the rows given, in whole sublane tiles, up to 128."""
    return min(_MAX_ROW_TILE, -(-rows // _SUBLANES) * _SUBLANES)


def _col_block(k: int, n: int, mats: int, tm: int, itemsize: int) -> int:
    """Columns a block: the widest divisor of ``n`` in whole lanes (or
    ``n`` itself) whose blocks fit ``_BLOCK_BYTES``: two buffers each of
    a ``[tm, k]`` row tile, ``mats`` ``[k, tn]`` weight blocks and a
    ``[tm, tn]`` output block, and ``mats + 1`` float32 ``[tm, tn]``
    products. The narrowest candidate where none fits."""
    cands = sorted({n} | {c for c in range(128, n, 128) if n % c == 0},
                   reverse=True)
    for tn in cands:
        need = (2 * itemsize * (tm * k + mats * k * tn + tm * tn)
                + 4 * (mats + 1) * tm * tn)
        if need <= _BLOCK_BYTES:
            return tn
    return cands[-1]


def work_items(counts, tiles: int, tm: int):
    """The kernel's items for groups of ``counts [G]`` rows laid out
    one after another from row 0 over ``tiles`` row tiles of ``tm``.

    Returns ``(group [W], tile [W], n)`` int32 with ``W = tiles + G -
    1`` (the most items any counts can make): item ``i < n`` is the
    rows of group ``group[i]`` in tile ``tile[i]``, in row order; an
    empty group has no item. Items ``i >= n`` repeat item ``n - 1``
    (group 0 and tile 0 where no group has rows)."""
    g_count = counts.shape[0]
    width = tiles + g_count - 1
    ends = jnp.cumsum(counts)
    starts = ends - counts
    first = starts // tm
    spans = jnp.where(counts > 0, (ends - 1) // tm - first + 1, 0)
    item_ends = jnp.cumsum(spans)                        # inclusive
    n = item_ends[-1]
    i = jnp.minimum(jnp.arange(width, dtype=jnp.int32),
                    jnp.maximum(n - 1, 0))
    group = jnp.minimum(
        jnp.searchsorted(item_ends, i, side="right"), g_count - 1)
    tile = first[group] + i - (item_ends[group] - spans[group])
    some = n > 0
    return (jnp.where(some, group, 0).astype(jnp.int32),
            jnp.where(some, tile, 0).astype(jnp.int32),
            n.astype(jnp.int32))


def _grouped_kernel(group_ref, tile_ref, bounds_ref, n_ref, x_ref, *rest,
                    tm, groups, gated):
    """One item in one column block: the item's rows of its tile against
    its expert's block(s), written into the tile's output block under
    the group's mask; the tile's first item clears the block."""
    *w_refs, out_ref = rest
    i = pl.program_id(1)
    tile = tile_ref[i]

    @pl.when((i == 0) | (tile != tile_ref[jnp.maximum(i - 1, 0)]))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(i < n_ref[0])
    def _():
        g = group_ref[i]
        row = tile * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        mine = (row >= bounds_ref[g]) & (row < bounds_ref[groups + g])
        x = x_ref[...]
        y = jnp.dot(x, w_refs[0][...], preferred_element_type=jnp.float32)
        if gated:
            u = jnp.dot(x, w_refs[1][...],
                        preferred_element_type=jnp.float32)
            y = jax.nn.silu(y) * u
        out_ref[...] = jnp.where(mine, y.astype(out_ref.dtype),
                                 out_ref[...])


@functools.partial(jax.jit, static_argnames=("tm", "tn", "interpret"))
def _pallas_grouped(x, ws, counts, *, tm, tn, interpret):
    rows, k = x.shape
    groups, _, n_cols = ws[0].shape
    gated = len(ws) == 2
    pad = -rows % tm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    tiles = (rows + pad) // tm
    counts = counts.astype(jnp.int32)
    group, tile, n = work_items(counts, tiles, tm)
    ends = jnp.cumsum(counts)
    bounds = jnp.concatenate([ends - counts, ends])      # starts, ends
    out_dtype = x.dtype if gated else jnp.float32

    # index maps of (column block j, item i) and the scalar operands
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # item groups, item tiles, bounds, count
        grid=(n_cols // tn, group.shape[0]),
        in_specs=[pl.BlockSpec((tm, k),
                               lambda j, i, g, t, b, c: (t[i], 0))]
        + [pl.BlockSpec((None, k, tn),
                        lambda j, i, g, t, b, c: (g[i], 0, j))
           for _ in ws],
        out_specs=pl.BlockSpec((tm, tn),
                               lambda j, i, g, t, b, c: (t[i], j)),
    )
    held_bytes = sum(w.size * w.dtype.itemsize for w in ws)
    out = pl.pallas_call(
        functools.partial(_grouped_kernel, tm=tm, groups=groups,
                          gated=gated),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows + pad, n_cols), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * k * n_cols * len(ws),
            transcendentals=rows * n_cols if gated else 0,
            bytes_accessed=held_bytes + x.size * x.dtype.itemsize
            * (n_cols // tn) + rows * n_cols * jnp.dtype(out_dtype).itemsize),
        interpret=interpret,
        name="ragged-dot-experts",
    )(group, tile, bounds, n.reshape(1), x, *ws)
    return out[:rows] if pad else out


def _impl(impl: str) -> str:
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl not in ("pallas", "xla"):
        raise ValueError(
            f"impl must be 'pallas', 'xla' or 'auto', got {impl!r}")
    return impl


def _run(x, ws, counts, interpret):
    if interpret is None:
        from . import default_interpret

        interpret = default_interpret()
    groups, k, n_cols = ws[0].shape
    if x.shape[1] != k or any(w.shape != ws[0].shape for w in ws):
        raise ValueError(
            f"rows {x.shape} do not meet weights "
            f"{[w.shape for w in ws]}")
    tm = _row_tile(x.shape[0])
    tn = _col_block(k, n_cols, len(ws), tm, ws[0].dtype.itemsize)
    return _pallas_grouped(x, tuple(ws), counts, tm=tm, tn=tn,
                           interpret=interpret)


def grouped_matmul(x: jax.Array, w: jax.Array, counts: jax.Array, *,
                   impl: str = "auto",
                   interpret: Optional[bool] = None) -> jax.Array:
    """``x [R, K]`` rows sorted by group, ``w [G, K, N]``, ``counts
    [G]`` int32 rows a group (``sum(counts) <= R``) -> ``[R, N]``
    float32: each group's rows times its own matrix. ``impl``:
    ``"pallas"`` | ``"xla"`` | ``"auto"`` (the kernel on a TPU);
    ``interpret``: Pallas interpret mode, by default everywhere but a
    TPU."""
    if _impl(impl) == "xla":
        return jax.lax.ragged_dot(x, w, counts,
                                  preferred_element_type=jnp.float32)
    return _run(x, (w,), counts, interpret)


def gated_grouped_matmul(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                         counts: jax.Array, *, impl: str = "auto",
                         interpret: Optional[bool] = None) -> jax.Array:
    """The gated form: ``silu(x @ w_gate[g]) * (x @ w_up[g])`` in
    float32 for each group's rows, cast to ``x``'s dtype -> ``[R, F]``.
    Arguments as :func:`grouped_matmul`, ``w_gate`` and ``w_up`` both
    ``[G, K, F]``."""
    if _impl(impl) == "xla":
        gate = jax.lax.ragged_dot(x, w_gate, counts,
                                  preferred_element_type=jnp.float32)
        up = jax.lax.ragged_dot(x, w_up, counts,
                                preferred_element_type=jnp.float32)
        return (jax.nn.silu(gate) * up).astype(x.dtype)
    return _run(x, (w_gate, w_up), counts, interpret)
