"""The gated short convolution of the LFM2 family's conv layers
(``models/lfm2_moe.py``): a depthwise causal convolution of width 3
over the gated input, with the conv's carried state held in a ring of
pages a slot.

A conv layer projects its normed input ``h`` to ``[B | Cg | X] = h
W_in`` (``[.., 3C]``: B first, then Cg, then X) and computes, per
channel,

    u_t = B_t * X_t
    z_t = w0 u_{t-2} + w1 u_{t-1} + w2 u_t        (u_j = 0 for j < 0)
    o_t = Cg_t * z_t

which :func:`short_conv` returns for ``W_out`` to project. The state a
slot carries from one token to the next is ``u`` at its last two
positions, so the family keeps ``u_t`` as the token's cache row: the
chunk writes its rows into the standalone prefill cache, the splice
cuts the prompt's last rows into the slot's ring, and a decode step
writes ``u_t`` at ring entry ``(t // page_size) % ring`` after reading
``u_{t-1}`` and ``u_{t-2}`` there. ``u`` is rounded to the cache's
dtype before the convolution everywhere (chunk and decode alike), so
what a step reads back is exactly what the step before it used.

A position below 0 reads as zero WHATEVER the ring holds there: the
splice fills a short prompt's missing ring entries with other rows of
that prompt's cache, and a ring that was another request's before
holds that request's rows.

The decode KERNEL (``pallas_call(name="short_conv")``) is a grid over
blocks of slots. A block's projection rows and outputs are pipelined
``[slots, 3C]`` / ``[slots, C]`` blocks; the ring pool stays in HBM
(``pl.ANY``) and is aliased to an output, so a step copies only the
page each slot writes (and, where ``t % page_size < 2``, the page
before it, which holds ``u_{t-1}`` or ``u_{t-2}``) into VMEM, writes
``u_t`` into it and copies that page back: the donated pool is written
in place, never copied whole. The layer, the positions and the walk's
ring table ride in SMEM (scalar prefetch), so every conv layer of a
step calls one lowered kernel, and the ring's layout is the table's
alone. A page is the smallest piece the kernel moves: a token's row
is a sixteenth of a bfloat16 tile, so the page's other rows travel
with it.

``impl="xla"`` is the plain form, the reference and the CPU fallback;
CPU tier-1 runs the kernel in interpret mode. The chunk is plain XLA
(:func:`short_conv_chunk`): one fused pass of shifted products over
the chunk's rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["short_conv", "xla_short_conv", "short_conv_chunk"]

# the conv's width (``conv_L_cache``): taps ``w0 .. w2`` over u_{t-2},
# u_{t-1}, u_t
WIDTH = 3


def _split(proj):
    """``[.., 3C]`` -> ``(B, Cg, X)`` float32."""
    b, cg, x = jnp.split(proj.astype(jnp.float32), 3, axis=-1)
    return b, cg, x


def _conv(taps, u2, u1, u0):
    """``w0 u_{t-2} + w1 u_{t-1} + w2 u_t`` in float32."""
    w = taps.astype(jnp.float32)
    return w[0] * u2 + w[1] * u1 + w[2] * u0


def short_conv_chunk(proj, taps, cache, start):
    """The conv of a chunk ``proj [T, 3C]`` at positions ``[start,
    start + T)`` against one layer's standalone cache ``[W, C]``, which
    holds ``u`` at ``[0, start)``: writes the chunk's ``u`` rows, reads
    the two rows before ``start`` (zero below position 0). Returns
    ``(Cg * z [T, C] float32, cache)``."""
    t = proj.shape[0]
    b, cg, x = _split(proj)
    u = (b * x).astype(cache.dtype)
    before = start - jnp.arange(WIDTH - 1, 0, -1)          # start-2, -1
    carried = jnp.where(
        (before >= 0)[:, None],
        jnp.take(cache, jnp.maximum(before, 0), axis=0).astype(jnp.float32),
        0.0)
    rows = jnp.concatenate([carried, u.astype(jnp.float32)], axis=0)
    z = _conv(taps, rows[:t], rows[1:t + 1], rows[2:])
    cache = jax.lax.dynamic_update_slice(cache, u, (start, 0))
    return cg * z, cache


def _ring_page_ids(table, positions, page_size):
    """The page of each slot's column ``positions`` under its ring
    ``table [N, ring]``: entry ``(t // ps) % ring`` (any integer; a
    negative one lands on some entry, and is masked by the caller)."""
    entry = jnp.mod(positions // page_size, table.shape[1])
    return jnp.take_along_axis(table, entry[:, None], axis=1)[:, 0]


def xla_short_conv(proj, taps, pool, table, positions, *, layer):
    """The plain form of :func:`short_conv`: gathers ``u_{t-1}`` and
    ``u_{t-2}`` of every slot out of layer ``layer`` of the ring pool
    through ``table``, scatters ``u_t``. Returns ``(Cg * z [N, C] in
    the pool's dtype, pool)``."""
    ps = pool.shape[2]
    b, cg, x = _split(proj)
    u = (b * x).astype(pool.dtype)

    def carried(k):
        col = positions - k
        row = pool[layer, _ring_page_ids(table, col, ps),
                   jnp.mod(col, ps)].astype(jnp.float32)
        return jnp.where((col >= 0)[:, None], row, 0.0)

    z = _conv(taps, carried(2), carried(1), u.astype(jnp.float32))
    pool = pool.at[layer, _ring_page_ids(table, positions, ps),
                   positions % ps].set(u)
    return (cg * z).astype(pool.dtype), pool


def _block_slots(n: int) -> int:
    """Slots a grid step: a whole bfloat16 tile of rows where the slot
    count allows, else every slot in one step."""
    for s in (16, 8):
        if n % s == 0:
            return s
    return n


def _short_conv_kernel(pos_ref, table_ref, layer_ref, proj_ref, taps_ref,
                       pool_ref, out_ref, pool_out, cur_buf, prev_buf,
                       u_rows, carried, sems, *, slots, ring, page_size,
                       width):
    """One block of ``slots`` slots: copy each slot's page of ``t`` (and
    the page before where ``t % ps < 2``) in, read ``u_{t-1}``,
    ``u_{t-2}`` out of them by masks (no dynamic row index into a packed
    tile), write ``u_t`` into the page, copy it back. Every copy has a
    semaphore of its own (``sems[in / out / before, j]``): a wait counts
    bytes, and the pages of a block are all one size, so a shared one
    would let slot ``j``'s wait return on another slot's page."""
    i = pl.program_id(0)
    layer = layer_ref[0]
    ps = page_size

    def slot_of(j):
        return i * slots + j

    def pages(j):
        t = pos_ref[slot_of(j)]
        g = t // ps
        row = slot_of(j) * ring                  # the slot's ring entries
        return (t, table_ref[row + jax.lax.rem(g, ring)],
                table_ref[row + jax.lax.rem(g - 1 + ring, ring)])

    def copy_cur(j, out=False):
        _, cur, _ = pages(j)
        src, dst = pool_ref.at[layer, cur], cur_buf.at[j]
        if out:
            src, dst = cur_buf.at[j], pool_out.at[layer, cur]
        return pltpu.make_async_copy(src, dst, sems.at[1 if out else 0, j])

    def copy_prev(j):
        _, _, prev = pages(j)
        return pltpu.make_async_copy(pool_ref.at[layer, prev],
                                     prev_buf.at[j], sems.at[2, j])

    def needs_prev(j):
        t, _, _ = pages(j)
        return jax.lax.rem(t, ps) < WIDTH - 1

    for j in range(slots):
        copy_cur(j).start()

        @pl.when(needs_prev(j))
        def _(j=j):
            copy_prev(j).start()

    b, cg, x = _split(proj_ref[...])
    u = (b * x).astype(cur_buf.dtype)                      # [S, C]
    u_rows[...] = u.astype(jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, (ps, width), 0)
    for j in range(slots):
        t, _, _ = pages(j)
        off = jax.lax.rem(t, ps)
        copy_cur(j).wait()

        @pl.when(needs_prev(j))
        def _(j=j):
            copy_prev(j).wait()

        cur = cur_buf[j].astype(jnp.float32)               # [ps, C]
        prev = jnp.where(needs_prev(j), prev_buf[j].astype(jnp.float32),
                         0.0)
        for k in (1, 2):
            # u_{t-k}: row off - k of this page, or row off - k + ps of
            # the page before; nothing below position 0
            picked = (jnp.where(row == off - k, cur, 0.0)
                      + jnp.where(row == off - k + ps, prev, 0.0))
            carried[k - 1, pl.ds(j, 1), :] = jnp.where(
                t - k >= 0, jnp.sum(picked, axis=0, keepdims=True), 0.0)
        cur_buf[j] = jnp.where(row == off, u_rows[pl.ds(j, 1), :],
                               cur).astype(cur_buf.dtype)
        copy_cur(j, out=True).start()

    z = _conv(taps_ref[...], carried[1], carried[0], u_rows[...])
    out_ref[...] = (cg * z).astype(out_ref.dtype)
    for j in range(slots):
        copy_cur(j, out=True).wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pallas_short_conv(proj, taps, pool, table, positions, layer,
                       interpret):
    n, three_c = proj.shape
    c = three_c // WIDTH
    ring, ps = table.shape[1], pool.shape[2]
    slots = _block_slots(n)

    def block(width):
        return pl.BlockSpec((slots, width), lambda i, *_: (i, 0))

    any_space = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # positions, the ring table, the layer
        grid=(n // slots,),
        in_specs=[block(three_c),
                  pl.BlockSpec((WIDTH, c), lambda i, *_: (0, 0)),
                  any_space],
        out_specs=[block(c), any_space],
        scratch_shapes=[
            pltpu.VMEM((slots, ps, c), pool.dtype),   # the page of t
            pltpu.VMEM((slots, ps, c), pool.dtype),   # the page before
            pltpu.VMEM((slots, c), jnp.float32),      # u_t
            pltpu.VMEM((WIDTH - 1, slots, c), jnp.float32),  # u_{t-1, t-2}
            pltpu.SemaphoreType.DMA((3, slots)),
        ],
    )
    out, pool = pl.pallas_call(
        functools.partial(_short_conv_kernel, slots=slots, ring=ring,
                          page_size=ps, width=c),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((n, c), pool.dtype),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)),
        # operand 5 (after the three scalar operands, proj and taps) is
        # the pool: written where it lies
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="short_conv",
    )(positions.astype(jnp.int32), table.astype(jnp.int32).reshape(-1),
      jnp.asarray(layer, jnp.int32).reshape(1), proj, taps, pool)
    return out, pool


def short_conv(
    proj: jax.Array,
    taps: jax.Array,
    pool: jax.Array,
    table: jax.Array,
    positions: jax.Array,
    *,
    layer,
    impl: str = "auto",
    interpret=None,
):
    """One decode step of the gated short convolution for every slot.

    Args:
      proj: ``[N, 3C]`` - each slot's ``[B | Cg | X]`` at its pending
        token.
      taps: ``[3, C]`` - ``w0, w1, w2`` per channel.
      pool: ``[L, P, page_size, C]`` - the conv layers' ring pool
        (ALL its layers).
      table: ``[N, ring]`` - each slot's ring: slot ``s`` keeps column
        ``t`` at page ``table[s, (t // page_size) % ring]``, row ``t %
        page_size``. ``ring`` pages must hold the last 3 columns:
        ``ring >= 2``.
      positions: ``[N]`` - each slot's pending column ``t``.
      layer: the conv layer's index into ``pool`` (an operand of the
        kernel, not a static).
      impl: ``"pallas"``, ``"xla"`` or ``"auto"`` (Pallas on TPU).
      interpret: Pallas's ``interpret`` (a bool or
        ``pltpu.InterpretParams``); None = the package's default.

    Returns ``(Cg * z [N, C] in the pool's dtype, pool)`` with ``u_t``
    written at each slot's column ``t``.
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if table.shape[1] < 2:
        raise ValueError(
            f"a ring of {table.shape[1]} page(s) a slot cannot hold the "
            "last 3 columns")
    if impl == "pallas":
        if interpret is None:
            from . import default_interpret

            interpret = default_interpret()
        return _pallas_short_conv(proj, taps, pool, table, positions, layer,
                                  interpret)
    if impl != "xla":
        raise ValueError(
            f"impl must be 'pallas', 'xla' or 'auto', got {impl!r}")
    return xla_short_conv(proj, taps, pool, table, positions, layer=layer)
