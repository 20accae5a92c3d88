"""The grouped chunk-attention kernel (``ops/pallas/chunk_attention.py``)
in interpret mode against its ``impl="xla"`` form, on the CPU.

Blocks of 8 queries and 16 columns, so that a chunk of 16 queries
folds several column blocks a query block, skips the blocks out of
reach, and masks the blocks that the causal bound, the window's bound
or the cache's end cut through; a matmul folds one query head (the
form of lane-aligned heads) or a key/value head's whole group (the
form of keys of 192). Every row the kernel must not read
(below the first live block of a window layer, past the last live
block) holds NaN on the kernel's side: one copy of such a row would
show. The keys and values are those of the two families that call it:
``Dk = Dv`` with no sink (``afmoe``), keys of 192 and values of 128
with a sink on the window layers (``mimo_v2``).
"""

import numpy as np
import jax.numpy as jnp
import pytest

from pytorch_multiprocessing_distributed_tpu.ops.pallas import (
    chunk_attention as ca)

T, BLOCK_Q, BLOCK_K, REACH = 16, 8, 16, 40

# (query heads, key/value heads, Dk, Dv, reach, sink)
_KINDS = {
    "full-dk16": (6, 2, 16, 16, None, False),
    "window-dk16": (6, 2, 16, 16, REACH, False),
    "full-dk192-dv128": (8, 1, 192, 128, None, False),
    "window-dk192-dv128-sink": (8, 2, 192, 128, REACH, True),
}
# (the chunk's start, the cache's width W)
_STARTS = {
    "start-0": (0, 96),
    "unaligned-start": (30, 96),      # a block's last column is 31
    "under-the-window": (16, 96),     # queries 16-31 reach column 0
    "over-the-window": (48, 96),      # query 48 reaches column 9
    "last-chunk": (80, 96),
    "ragged-width-last-chunk": (72, 88),   # 88 is no multiple of 16
}


@pytest.mark.parametrize("fold", ["one-head", "whole-group"])
@pytest.mark.parametrize("where", list(_STARTS))
@pytest.mark.parametrize("kind", list(_KINDS))
def test_kernel_in_interpret_mode_equals_its_xla_form(monkeypatch, kind,
                                                      where, fold):
    heads, kv_heads, dk, dv, reach, sink = _KINDS[kind]
    start, width = _STARTS[where]
    heads_a_matmul = 1 if fold == "one-head" else heads // kv_heads
    monkeypatch.setattr(ca, "_chunk_blocks",
                        lambda *_: (BLOCK_Q, BLOCK_K, heads_a_matmul))
    rng = np.random.default_rng(start + width)
    q = jnp.asarray(rng.normal(size=(T, heads, dk)), jnp.float32)
    cache = rng.normal(size=(width, kv_heads * (dk + dv))).astype(
        np.float32)
    sinks = (jnp.asarray(rng.normal(size=(heads,)), jnp.float32) if sink
             else None)
    # the rows no query block reaches: below the first query's window
    # (a window layer), from the block after the last query's on
    low = 0 if reach is None else max(0, start - reach + 1)
    poisoned = cache.copy()
    poisoned[:low // BLOCK_K * BLOCK_K] = np.nan
    poisoned[((start + T - 1) // BLOCK_K + 1) * BLOCK_K:] = np.nan
    kw = dict(kv_heads=kv_heads, scale=dk ** -0.5, reach=reach,
              sinks=sinks)
    got = ca.gqa_chunk_attention(q, jnp.asarray(poisoned), jnp.int32(start),
                                 impl="pallas", interpret=True, **kw)
    want = ca.gqa_chunk_attention(q, jnp.asarray(cache), jnp.int32(start),
                                  impl="xla", **kw)
    assert got.shape == want.shape == (T, heads, dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("t, w, group, reach, dk, want", [
    (1024, 8192, 6, None, 128, (256, 256, 1)),    # trinity's full layer
    (1024, 8192, 6, 4096, 128, (256, 256, 1)),    # and its sliding layers
    (1024, 1024, 16, None, 192, (64, 512, 16)),   # MiMo's full layers
    (1024, 1024, 8, 128, 192, (128, 128, 8)),     # and its window layers
    (8, 24, 3, 8, 16, (8, 24, 3)),                # a tiny chunk: all of
    (8, 24, 3, None, 128, (8, 24, 1)),            # both, either fold
], ids=["trinity-full", "trinity-window", "mimo-full", "mimo-window",
        "tiny", "tiny-aligned"])
def test_blocks_follow_the_heads_and_the_reach(t, w, group, reach, dk,
                                               want):
    assert ca._chunk_blocks(t, w, group, reach, dk) == want
