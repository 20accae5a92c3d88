"""The paged decode kernel against its XLA pin, shape by shape.

``_pallas_paged_attention`` is a grid of slots; a slot is a loop over
its live blocks of G pages, each block's pages copied by the kernel
itself out of layer ``LAYER`` of the whole ``[L, P, ps, H * Dh]`` pool
where it lies, all heads folded at once
(``ops/pallas/decode_attention.py``). Here it runs in interpret mode
against ``xla_paged_decode_attention`` over the page sizes, head counts
and window lengths the engine can hand it, and every batch carries the
positions where an off-by-one would show: column 0, a page's last and
first column, the window's last column, a length in between, and an
inactive slot whose table row is all scratch page 0. Page ids are
shuffled, so logical order never equals pool order.
"""

import importlib

import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_multiprocessing_distributed_tpu.ops.kv_quant import (
    QuantizedKV, flatten_heads, quantize_kv)

# the module, not the same-named function ops.pallas re-exports
da = importlib.import_module(
    "pytorch_multiprocessing_distributed_tpu.ops.pallas.decode_attention")

D = 16
L, LAYER = 2, 1     # pools of two layers; the kernel reads the second
# page_size -> (a window that is a multiple of G, one that is not);
# G = 128 // page_size pages a step (1 at 128: every window is one)
WINDOWS = {8: (32, 20), 16: (16, 11), 128: (2, 3)}


def _positions(ps, n_win, rng):
    """One row per case the docstring lists; the last is the inactive
    slot's frozen position."""
    window = n_win * ps
    return np.array([0, ps - 1, (n_win // 2) * ps, window - 1,
                     int(rng.integers(ps, window - 1)),
                     int(rng.integers(0, window))], np.int32)


def _case(ps, heads, n_win, seed):
    rng = np.random.default_rng(seed)
    pos = _positions(ps, n_win, rng)
    b = len(pos)
    n_pages = b * n_win + 1
    q = jnp.asarray(rng.standard_normal((b, 1, heads, D)), jnp.float32)
    k, v = (rng.standard_normal((L, n_pages, ps, heads * D)).astype(
        np.float32) for _ in range(2))
    table = rng.permutation(np.arange(1, n_pages)).reshape(b, n_win)
    table[-1] = 0          # released slot: every entry the scratch page
    return q, k, v, table.astype(np.int32), pos


def _pages(x, int8):
    """The pool, or its int8 pair: one scale a token and head, the
    data back in the pool's lane-dense rows."""
    x = jnp.asarray(x)
    if not int8:
        return x
    return flatten_heads(quantize_kv(x.reshape(x.shape[:-1] + (-1, D))))


def _poison(pages, table, pos, ps):
    """NaN in every page beyond each slot's position and in every
    page of the layer the kernel must not read (the scale sidecar of
    an int8 page: its data cannot hold one). Page 0 stays: the
    inactive slot reads it as its live pages."""
    dead = np.unique(np.concatenate(
        [row[p // ps + 1:] for row, p in zip(table, pos)]))
    dead = dead[dead != 0]

    def nan(x):
        return x.at[LAYER, dead].set(jnp.nan).at[1 - LAYER].set(jnp.nan)

    if isinstance(pages, QuantizedKV):
        return QuantizedKV(pages.data, nan(pages.scale))
    return nan(pages)


_CASES = [
    pytest.param(ps, heads, n_win, int8, poison,
                 id=f"ps{ps}-h{heads}-win{n_win}"
                    f"-{'int8' if int8 else 'f32'}"
                    + ("-poisoned" if poison else ""))
    for ps in (8, 16, 128)
    for heads in (2, 12, 16)
    for n_win in WINDOWS[ps]
    for int8 in (False, True)
    # every page beyond a position poisoned: once per page size and
    # dtype, on the window that is not a multiple of G
    for poison in ((False, True) if heads == 12 and n_win == WINDOWS[ps][1]
                   else (False,))
]


@pytest.mark.parametrize("ps, heads, n_win, int8, poison", _CASES)
def test_pallas_paged_decode_matches_xla(ps, heads, n_win, int8, poison):
    q, k, v, table, pos = _case(ps, heads, n_win,
                                seed=ps * 1000 + heads * 10 + n_win)
    kp, vp = _pages(k, int8), _pages(v, int8)
    ref = da.paged_decode_attention(q, kp, vp, jnp.asarray(table),
                                    jnp.asarray(pos), layer=LAYER,
                                    impl="xla")
    assert np.isfinite(np.asarray(ref)).all()
    if poison:
        # nothing beyond a position may be folded: the reference saw
        # the clean pool, the kernel sees NaN wherever it must not look
        kp, vp = (_poison(kp, table, pos, ps),
                  _poison(vp, table, pos, ps))
    got = da.paged_decode_attention(q, kp, vp, jnp.asarray(table),
                                    jnp.asarray(pos), layer=LAYER,
                                    impl="pallas", interpret=True)
    # tests/test_graftquant.py's tolerance for the same pair of paths
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5)


@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("ps, heads, n_win, k1", [
    (8, 2, 20, 3), (16, 12, 11, 5), (16, 16, 16, 2), (128, 2, 3, 5)])
def test_pallas_paged_verify_matches_xla(ps, heads, n_win, k1, int8):
    """The k-query verify pass is the same kernel body with ``K1 * H``
    query rows: row ``i`` of a slot reaches column ``pos + i``, the
    pages up to the LAST row's reach are live, and everything beyond
    it — and the other layer — is poisoned."""
    q, k, v, table, pos = _case(ps, heads, n_win,
                                seed=ps * 100 + heads + k1)
    # the K1 columns a pass writes lie inside the window
    pos = np.minimum(pos, n_win * ps - k1)
    rng = np.random.default_rng(k1)
    q = jnp.asarray(rng.standard_normal((len(pos), k1, heads, D)),
                    jnp.float32)
    kp, vp = _pages(k, int8), _pages(v, int8)
    args = (jnp.asarray(table), jnp.asarray(pos))
    ref = da.paged_verify_decode_attention(q, kp, vp, *args, layer=LAYER,
                                           impl="xla")
    assert np.isfinite(np.asarray(ref)).all()
    reach = pos + k1 - 1
    got = da.paged_verify_decode_attention(
        q, _poison(kp, table, reach, ps), _poison(vp, table, reach, ps),
        *args, layer=LAYER, impl="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5)


# (positions, a block's columns, page, table entries, heads, int8): the
# edges the loop over a slot's live blocks creates
_EDGES = [
    # column 0, a page's last and first column, in a block of two pages
    # and in one block a slot
    pytest.param([0, 15, 16], 32, 16, 8, 4, False, id="page-edges-2-pages"),
    pytest.param([0, 15, 16], 128, 16, 8, 4, False, id="page-edges-1-block"),
    # a block's last and first column; a slot of one block before one of
    # two and one of four (the ring's place carries over the slots)
    pytest.param([31, 32, 127], 32, 16, 8, 4, False, id="block-edges"),
    pytest.param([127, 0, 127], 32, 16, 8, 4, False, id="window-end"),
    pytest.param([127, 64, 126], 64, 16, 8, 2, False,
                 id="window-end-2-blocks"),
    # a window that is no whole number of blocks: the last block is short
    pytest.param([87, 44, 48], 32, 8, 11, 4, False, id="short-last-block"),
    pytest.param([31, 32, 127], 32, 16, 8, 4, True, id="block-edges-int8"),
    pytest.param([127, 5, 64], 64, 16, 8, 2, True, id="window-end-int8"),
]


def _loop_case(monkeypatch, positions, columns, ps, n_win, heads, int8,
               k1, seed):
    """(query, clean pools, table, positions) with the kernel's block
    forced to ``columns``: three slots whose table rows are shuffled
    pool pages (logical order never equals pool order)."""
    monkeypatch.setattr(da, "_gpt_block_pages",
                        lambda page, entries, *_: min(columns // page,
                                                      entries))
    rng = np.random.default_rng(seed)
    slots = len(positions)
    n_pages = slots * n_win + 1
    q = jnp.asarray(rng.standard_normal((slots, k1, heads, D)), jnp.float32)
    k, v = (_pages(rng.standard_normal((L, n_pages, ps, heads * D)).astype(
        np.float32), int8) for _ in range(2))
    table = rng.permutation(np.arange(1, n_pages)).reshape(slots, n_win)
    return q, k, v, table.astype(np.int32), np.asarray(positions, np.int32)


@pytest.mark.parametrize("positions, columns, ps, n_win, heads, int8",
                         _EDGES)
def test_loop_over_live_blocks_matches_xla_at_its_edges(
        monkeypatch, positions, columns, ps, n_win, heads, int8):
    """The kernel under the Pallas interpreter against the XLA form at
    the columns where the loop over a slot's live blocks turns: a
    page's edges, a block's edges, the window's end, one block a slot
    and several. Every page beyond a slot's reach, and the other
    layer, holds NaN: nothing beyond a reach is ever copied."""
    q, k, v, table, pos = _loop_case(monkeypatch, positions, columns, ps,
                                     n_win, heads, int8, k1=1, seed=columns)
    args = (jnp.asarray(table), jnp.asarray(pos))
    want = da.paged_decode_attention(q, k, v, *args, layer=LAYER,
                                     impl="xla")
    got = da.paged_decode_attention(
        q, _poison(k, table, pos, ps), _poison(v, table, pos, ps),
        *args, layer=LAYER, impl="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5)


@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
def test_verify_rows_cross_a_block_edge(monkeypatch, int8):
    """k1 = 5: a slot's five query rows reach columns ``pos .. pos +
    4``, and here they straddle a block's edge (the first rows end in
    one block, the last in the next, which only they may read) or the
    window's end; the pages up to the LAST row's reach are the live
    ones, and everything else holds NaN."""
    q, k, v, table, pos = _loop_case(monkeypatch, [29, 62, 123], 32, 16, 8,
                                     2, int8, k1=5, seed=5)
    args = (jnp.asarray(table), jnp.asarray(pos))
    want = da.paged_verify_decode_attention(q, k, v, *args, layer=LAYER,
                                            impl="xla")
    reach = pos + 4
    got = da.paged_verify_decode_attention(
        q, _poison(k, table, reach, 16), _poison(v, table, reach, 16),
        *args, layer=LAYER, impl="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5)


@pytest.mark.parametrize("page, n_win, rows, row_bytes, want", [
    (16, 64, 16, 2048, 16),   # gpt2-medium.serve.closed: 256 columns
    (16, 64, 80, 2048, 16),   # its verify pass, five query tokens
    (16, 64, 12, 1536, 32),   # gpt_small's narrower rows: 512 columns
    (16, 11, 16, 2048, 11),   # no more than the window has
    (128, 8, 16, 2048, 2),    # pages of 128: the same 256 columns
    (16, 64, 16, 4096, 8),    # a float32 query: rows twice as wide
], ids=["gpt2-medium", "verify", "gpt-small", "short-window", "page128",
        "f32"])
def test_gpt_block_is_chosen_from_heads_rows_and_the_vmem_budget(
        page, n_win, rows, row_bytes, want):
    assert da._gpt_block_pages(page, n_win, rows, row_bytes) == want
