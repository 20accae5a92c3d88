"""The routed experts' grouped matmul (``ops/pallas/grouped_matmul.py``):
the kernel in interpret mode against ``jax.lax.ragged_dot``, its work
items (each touched expert's matrix named by one run of consecutive
items, an empty expert by none), and ``ops/moe.py::dropless_experts``
on the kernel's path against the same layer on ragged_dot's."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_multiprocessing_distributed_tpu.ops.moe import (
    dropless_experts, route_sigmoid_topk, row_ladder)

gm = importlib.import_module(
    "pytorch_multiprocessing_distributed_tpu.ops.pallas.grouped_matmul")


def _operands(rows, groups, k=32, n=256, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(rows, k)), dtype)
    ws = [jnp.asarray(rng.normal(size=(groups, k, n)) * 0.3, dtype)
          for _ in range(2)]
    return x, ws


# (rows given, rows a group): every case's groups laid from row 0
_LAYOUTS = {
    "empty-experts": (32, [3, 0, 0, 9, 0, 4, 0, 0]),
    "single-row": (16, [0, 0, 1, 0]),
    "every-row-in-one-expert": (48, [0, 48, 0, 0]),
    "rows-behind-the-last-group": (64, [5, 0, 7, 2]),
    "unaligned-starts-over-tiles": (384, [37, 0, 91, 128, 5, 0, 83, 40]),
    "one-group-over-three-tiles": (384, [1, 300, 0, 2]),
    "rows-not-a-whole-tile": (37, [10, 0, 27]),
}


def _check(layout, dtype, tol):
    rows, counts = _LAYOUTS[layout]
    counts = jnp.asarray(counts, jnp.int32)
    held = int(counts.sum())
    x, (w1, w2) = _operands(rows, counts.shape[0], dtype=dtype)
    got = gm.grouped_matmul(x, w1, counts, impl="pallas", interpret=True)
    want = jax.lax.ragged_dot(x, w1, counts,
                              preferred_element_type=jnp.float32)
    assert got.shape == (rows, w1.shape[2]) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got[:held]),
                               np.asarray(want[:held]), rtol=tol, atol=tol)
    got = gm.gated_grouped_matmul(x, w1, w2, counts, impl="pallas",
                                  interpret=True)
    gate = jax.lax.ragged_dot(x, w1, counts,
                              preferred_element_type=jnp.float32)
    up = jax.lax.ragged_dot(x, w2, counts,
                            preferred_element_type=jnp.float32)
    want = (jax.nn.silu(gate) * up).astype(dtype)
    assert got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got[:held], np.float32),
                               np.asarray(want[:held], np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("layout", list(_LAYOUTS))
def test_the_kernel_equals_ragged_dot(layout):
    """Both forms against ``ragged_dot`` (the gated one against
    ``silu(ragged_dot(gate)) * ragged_dot(up)``) on every held row:
    empty experts, one row, one expert with every row, rows behind the
    last group, groups that start inside a tile and cross into the
    next."""
    _check(layout, jnp.float32, 1e-5)


@pytest.mark.parametrize("layout", ["empty-experts",
                                    "unaligned-starts-over-tiles"])
def test_the_kernel_equals_ragged_dot_in_bfloat16(layout):
    """The model's dtype: products accumulated in float32, the gated
    form rounded once to bfloat16 (one unit of its last place)."""
    _check(layout, jnp.bfloat16, 2e-2)


def test_the_kernel_over_several_column_blocks():
    """A block narrower than the columns: the rows are read once a
    column block, every block of every touched expert once."""
    counts = jnp.asarray([37, 0, 91, 128, 5, 0, 83, 40], jnp.int32)
    x, (w1, w2) = _operands(384, 8, n=384)
    got = gm._pallas_grouped(x, (w1, w2), counts, tm=128, tn=128,
                             interpret=True)
    gate = jax.lax.ragged_dot(x, w1, counts,
                              preferred_element_type=jnp.float32)
    up = jax.lax.ragged_dot(x, w2, counts,
                            preferred_element_type=jnp.float32)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jax.nn.silu(gate) * up),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("rows", row_ladder(128, 4, 2, 16))
def test_the_kernel_at_each_rung_of_the_ladder(rows):
    """Each rung of a share's ladder as the rows given, the held rows
    filling it to the rung."""
    assert row_ladder(128, 4, 2, 16) == (128, 256, 512)
    counts = jnp.asarray([rows // 3, rows - rows // 3], jnp.int32)
    x, (w1, _) = _operands(rows, 2)
    got = gm.grouped_matmul(x, w1, counts, impl="pallas", interpret=True)
    want = jax.lax.ragged_dot(x, w1, counts,
                              preferred_element_type=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("counts, tiles, want_groups, want_tiles", [
    # 16 rows a tile: an expert with no rows is named by no item; the
    # items past the count name the last item's blocks again
    ([3, 0, 0, 9, 0, 4], 2, [0, 3, 5, 5, 5, 5, 5], [0, 0, 0, 0, 0, 0, 0]),
    ([0, 20, 0, 13], 3, [1, 1, 3, 3, 3, 3], [0, 1, 1, 2, 2, 2]),
    ([16, 16, 16], 3, [0, 1, 2, 2, 2], [0, 1, 2, 2, 2]),
    ([0, 0, 0], 2, [0, 0, 0, 0], [0, 0, 0, 0]),
], ids=["empty-between", "crossing-tiles", "aligned", "no-rows"])
def test_work_items_name_no_empty_expert(counts, tiles, want_groups,
                                         want_tiles):
    """The weight block's index map reads ``group[i]``: the groups it
    names never include an empty expert, never go back, and past the
    last item stay on the last touched expert, so the pipeline copies
    each touched expert's block once and no other."""
    counts = jnp.asarray(counts, jnp.int32)
    group, tile, n = gm.work_items(counts, tiles, 16)
    assert list(np.asarray(group)) == want_groups
    assert list(np.asarray(tile)) == want_tiles
    touched = [g for g, c in enumerate(np.asarray(counts)) if c]
    assert int(n) == sum(
        (s + c - 1) // 16 - s // 16 + 1
        for s, c in zip(np.cumsum(counts) - counts, np.asarray(counts))
        if c)
    copies = [g for i, g in enumerate(want_groups)
              if i == 0 or g != want_groups[i - 1]]
    assert copies == (touched or [0])


def _layer(t, d=32, f=64, held=4, e=16, seed=3):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(d, e)) * 0.3, jnp.float32)
    ws = [jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)
          for s in ((held, d, f), (held, d, f), (held, f, d))]
    return x, router, ws


@pytest.mark.parametrize("held, e, offset, bias_to", [
    (16, 16, 0, None),       # every expert held: one rung, T * k rows
    (4, 16, 4, None),        # a share: the first rung
    (4, 16, 4, (5,)),        # every token to one held expert
    (4, 16, 4, (0, 1, 2, 13)),   # every choice elsewhere: nothing held
], ids=["all-held", "share", "share-all-to-one", "share-none-held"])
def test_dropless_experts_on_the_kernel_path(monkeypatch, held, e, offset,
                                             bias_to):
    """The layer with the kernel (interpret mode) where the chip would
    run it returns the ragged_dot layer's ``(y, counts, elsewhere,
    given)``."""
    t, k = 64, 4
    x, router, ws = _layer(t, held=held, e=e)
    bias = jnp.zeros((e,), jnp.float32)
    if bias_to is not None:
        bias = bias.at[jnp.asarray(bias_to)].set(10.0)
    chosen, weights = route_sigmoid_topk(x, router, bias, k, 2.5)

    def layer():
        return jax.jit(lambda *a: dropless_experts(
            *a, n_experts=e, offset=offset))(x, chosen, weights, *ws)

    want = layer()
    monkeypatch.setattr(gm, "_impl", lambda impl: "pallas")
    got = layer()
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=1e-5, atol=1e-5)
    for g, w in zip(got[1:], want[1:]):
        assert (np.asarray(g) == np.asarray(w)).all()
    if bias_to and len(bias_to) == k:
        assert int(got[1].sum()) == 0 and float(jnp.abs(got[0]).max()) == 0


def test_the_blocks_follow_from_shapes_and_the_vmem_budget():
    """The row tile is the rows given up to 128 in whole sublane tiles;
    the column block the widest whole-lane divisor that fits the
    budget, the same for every layer of a cell (no layer index)."""
    assert gm._row_tile(512) == gm._row_tile(4096) == 128
    assert gm._row_tile(37) == 48 and gm._row_tile(8) == 16
    # lfm2-8b-a1b: D 2,048, F 1,792 (gate and up; down)
    assert gm._col_block(2048, 1792, 2, 128, 2) == 896
    assert gm._col_block(1792, 2048, 1, 128, 2) == 2048
    # openpangu-ultra-moe-718b: D 7,680, F 2,048
    assert gm._col_block(7680, 2048, 2, 128, 2) == 256
    assert gm._col_block(2048, 7680, 1, 128, 2) == 2560
    # widths under a lane: the whole width
    assert gm._col_block(32, 64, 2, 16, 4) == 64
