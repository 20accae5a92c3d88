"""The paged decode kernel against its XLA pin, shape by shape.

``_pallas_paged_attention`` folds all heads of a block of G pages in
one grid step, reads layer ``LAYER`` of the whole ``[L, P, ps, H * Dh]``
pool in place and names only live pages to the pipeline
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


@pytest.mark.parametrize("ps, n_win", [(8, 20), (16, 64), (16, 11),
                                       (128, 8)])
def test_live_page_ids_name_live_pages_only(ps, n_win):
    """What the pipeline is asked to copy, step by step: never a page
    beyond the slot's position, and from a slot's last live block to
    its last grid step the SAME pages (a repeated block index is not
    copied again) — so a dead block costs no DMA."""
    rng = np.random.default_rng(ps + n_win)
    window = n_win * ps
    pos = np.array([0, ps - 1, ps, window - 1,
                    int(rng.integers(0, window))], np.int32)
    table = np.arange(len(pos) * n_win, dtype=np.int32).reshape(
        len(pos), n_win) + 1          # entry -> slot and logical page
    group = da._pages_per_step(ps, n_win, ps * 2 * D * 2)
    assert group == min(max(1, 128 // ps), n_win)
    named = np.asarray(da._live_page_ids(
        jnp.asarray(table), jnp.asarray(pos), group, ps)).reshape(
            len(pos), -(-n_win // group), group)
    for slot, p in enumerate(pos):
        last_page, last_block = p // ps, p // (group * ps)
        logical = named[slot] - 1 - slot * n_win
        assert (logical >= 0).all() and (logical <= last_page).all()
        # a live block names each of its live pages, in order
        for kb in range(last_block + 1):
            live = min(group, last_page - kb * group + 1)
            assert list(logical[kb, :live]) == list(
                range(kb * group, kb * group + live))
        # and a dead block names what the last live one did
        assert (named[slot, last_block:] == named[slot, last_block]).all()
        # a page beyond the position inside the last live block keeps
        # the page its operand held in the block before
        if last_block > 0:
            beyond = slice(last_page - last_block * group + 1, group)
            assert (named[slot, last_block, beyond]
                    == named[slot, last_block - 1, beyond]).all()
