"""The ``mimo_v2`` family against its plain reference
(``perf/reference/mimo_v2.py``) at the tiny preset on the CPU, seeded
random weights: grouped-query attention with keys wider than values, a
different number of key/value heads in each kind of layer, partial
rotary at a theta a kind and a learned sink on the window layers, in
its three forms (whole-prompt prefill, chunked prefill, decode through
BOTH pools far beyond the window, the ring wrapping); the grouped paged
kernel in interpret mode against its XLA form at ``Dk != Dv`` with and
without sinks; the share of the routed experts with no shared expert; a
cache manager whose two kinds of layer keep rows of unequal width; what
the engine refuses for the family; planted faults the comparison has to
catch.

``mimo_v2_tiny``: eight query heads on one key/value head in a full
layer and two in a window layer, keys of 24 and values of 16, rotary on
8 of the 24, a window of 8, 16 experts at top-4 and no shared expert,
kinds full-window-window-full-window; pages of 4, so a ring of
``ceil(8 / 4) + 1 = 3`` pages a slot; rows of 40 (full) and 80
(window) values.

Tolerances are shares of the reference logits' standard deviation, as
in ``tests/test_afmoe.py``: the float32 program's LARGEST error reads
5e-7 to 2e-6 (limit 2e-5); bfloat16's MEAN error is held to 0.05; each
planted fault reads above that in the float32 program.
"""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.reference import mimo_v2 as reference
from pytorch_multiprocessing_distributed_tpu import models
from pytorch_multiprocessing_distributed_tpu.inference.generate import (
    cache_pools, generate, pref_cache_shapes, serving_family)
from pytorch_multiprocessing_distributed_tpu.models import latent, mimo_v2
from pytorch_multiprocessing_distributed_tpu.ops.moe import (
    dropless_experts, route_sigmoid_topk)
from pytorch_multiprocessing_distributed_tpu.ops.pallas import (
    chunk_attention)
from pytorch_multiprocessing_distributed_tpu.runtime.scope import scoped
from pytorch_multiprocessing_distributed_tpu.serving import (
    PagePool, ServingEngine, init_params)

# the module, not the same-named function ops.pallas re-exports
da = importlib.import_module(
    "pytorch_multiprocessing_distributed_tpu.ops.pallas.decode_attention")

F32_LIMIT = 2e-5
BF16_LIMIT = 0.05
VOCAB = 211
PS = 4                      # pages of 4: a ring of 3 for the window of 8
# the tiny model routes over 16 experts at top-4; this chip holds four
SHARE = dict(experts_held=4, expert_offset=8)


def _config(model) -> dict:
    """The published key names for a model's sizes: what the reference
    is configured from (the experts held and the router's width it
    reads off the weights)."""
    return {
        "num_hidden_layers": model.num_layers,
        "hybrid_layer_pattern": list(
            model.hybrid_layer_pattern[:model.num_layers]),
        "sliding_window": model.sliding_window,
        "layernorm_epsilon": model.rms_eps,
        "num_attention_heads": model.num_heads,
        "num_key_value_heads": model.num_kv_heads,
        "swa_num_key_value_heads": model.swa_num_kv_heads,
        "add_full_attention_sink_bias": False,
        "add_swa_attention_sink_bias": True,
        "head_dim": model.head_dim, "v_head_dim": model.v_head_dim,
        "partial_rotary_factor": model.partial_rotary_factor,
        "rope_theta": model.rope_theta,
        "swa_rope_theta": model.swa_rope_theta,
        "attention_value_scale": model.attention_value_scale,
        "num_experts_per_tok": model.moe_top_k, "norm_topk_prob": True,
        "routed_scaling_factor": None,
        "expert_offset": model.expert_offset}


def _rel(got, want) -> float:
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(np.asarray(got, np.float32) - want))
                 / np.std(want))


def _mean_rel(got, want) -> float:
    want = np.asarray(want, np.float32)
    return float(np.mean(np.abs(np.asarray(got, np.float32) - want))
                 / np.std(want))


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, size=n)


@pytest.fixture(scope="module")
def tiny():
    model = models.get_model("mimo_v2_tiny", dtype=jnp.float32, **SHARE)
    return model, init_params(model, 0)


@pytest.fixture(scope="module")
def ref_logits(tiny):
    model, params = tiny
    fn = reference.make_logits_fn(_config(model))
    return lambda tokens: np.asarray(fn(params, jnp.asarray(tokens)))


def _prefill_logits(model, params, tokens):
    family = model.serving_family
    x, _, _ = family.prefill(model, params, jnp.asarray(tokens)[None])
    return np.asarray(family.logits(model, params, x)[0])


def _chunked_logits(model, params, tokens, chunk=16, impl="xla"):
    family = model.serving_family
    full, window = (jnp.zeros(shape, jnp.float32)
                    for shape in pref_cache_shapes(model, len(tokens)))
    out = []
    for start in range(0, len(tokens), chunk):
        x, full, window = family.chunk(
            model, params, full, window,
            jnp.asarray(tokens[start:start + chunk])[None],
            jnp.int32(start), attn_impl=impl)
        out.append(family.logits(model, params, x)[0])
    return np.concatenate(out)


def _pools(model, slots, pages_per_slot):
    """Both pools for ``slots`` slots, empty, each at its OWN row: the
    full layers' behind an identity page table (page 0 is scratch), the
    window layers' rings."""
    ring = -(-model.sliding_window // PS) + 1
    full = jnp.zeros((model.n_full, slots * pages_per_slot + 1, PS,
                      model.kv_row(False)), jnp.float32)
    rings = jnp.zeros((model.n_sliding, slots * ring, PS,
                       model.kv_row(True)), jnp.float32)
    table = (1 + jnp.arange(slots * pages_per_slot, dtype=jnp.int32)
             ).reshape(slots, pages_per_slot)
    return full, rings, table


def _decode_logits(model, params, tokens, prompt=8, impl="xla", slots=2):
    """Prefill ``prompt`` tokens, splice them into both pools the
    engine's way, then decode the rest ONE token a step through the
    page table and the ring; the logits of every decoded position."""
    family = model.serving_family
    n = len(tokens)
    width = -(-prompt // PS) * PS
    padded = np.zeros((1, width), np.int32)
    padded[0, :prompt] = tokens[:prompt]
    _, pref_full, pref_window = family.prefill(model, params,
                                               jnp.asarray(padded))
    full, rings, table = _pools(model, slots, -(-n // PS))
    state = (jnp.zeros((slots,), jnp.int32),) * 2 + (
        jnp.zeros((slots,), bool), jnp.zeros((slots,), jnp.int32),
        jnp.full((slots,), -1, jnp.int32))
    full, rings, positions, *_ = ServingEngine._ring_insert_fn(
        full, rings, *state, pref_full, pref_window,
        table[0, :width // PS], jnp.int32(0), jnp.int32(prompt),
        jnp.int32(0), jnp.int32(0), jnp.int32(-1))
    step = jax.jit(lambda full, rings, positions, last: family.decode_step(
        model, params, full, rings, positions, last, window=n,
        attn_impl=impl, page_table=table, page_size=PS)[:3])
    out = []
    for at in range(prompt, n):
        last = jnp.zeros((slots,), jnp.int32).at[0].set(int(tokens[at]))
        x, full, rings = step(full, rings, positions, last)
        out.append(family.logits(model, params, x)[0, 0])
        positions = positions.at[0].add(1)
    return np.asarray(jnp.stack(out))


# --------------------------------------------------------- the forward

def test_registry_and_published_sizes():
    """The stage the benchmark serves: layers 0-6 (the dense full
    layer, five window layers, one full layer), 16 of 256 experts, an
    eighth of the vocabulary: 3,429.9 M parameters, 6.87 GB
    as served; two pools of UNEQUAL rows, 1,280 and 2,560 values."""
    model = models.get_model(
        "mimo_v2_5", dtype=jnp.bfloat16, num_layers=7, first_k_dense=1,
        experts_held=16, vocab_size=19072)
    assert serving_family(model).name == "mimo_v2"
    assert (model.hidden_size, model.num_heads, model.num_kv_heads,
            model.swa_num_kv_heads, model.head_dim, model.v_head_dim,
            model.rotary_dim) == (4096, 64, 4, 8, 192, 128, 64)
    assert (model.n_experts, model.n_held, model.moe_top_k, model.moe_dim,
            model.mlp_dim, model.n_shared_experts) == (
                256, 16, 8, 2048, 16384, 0)
    assert model.layer_types == ("full_attention",) + (
        "sliding_attention",) * 4 + ("full_attention", "sliding_attention")
    assert (model.n_full, model.n_sliding, model.n_moe_layers) == (2, 5, 6)
    assert cache_pools(model) == (
        ("full", (1280,), jnp.bfloat16, 2, None),
        ("sliding", (2560,), jnp.bfloat16, 5, 128))
    assert pref_cache_shapes(model, 1024) == ((2, 1, 1024, 1280),
                                              (5, 1, 1024, 2560))
    shapes = jax.eval_shape(lambda: model._init(jax.random.PRNGKey(0)))
    leaves = jax.tree.leaves(shapes)
    count = sum(int(np.prod(s.shape)) for s in leaves)
    held = sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in leaves)
    assert abs(count - 3.4299e9) < 1e6          # the cut's own count
    assert abs(held - 6.866e9) < 1e7
    assert shapes["layer_0"]["attn"]["wk"].shape == (4096, 4 * 192)
    assert shapes["layer_0"]["attn"]["wv"].shape == (4096, 4 * 128)
    assert shapes["layer_1"]["attn"]["wk"].shape == (4096, 8 * 192)
    assert shapes["layer_1"]["attn"]["wo"].shape == (64 * 128, 4096)
    assert shapes["layer_1"]["attn"]["sinks"].dtype == jnp.float32
    assert "sinks" not in shapes["layer_5"]["attn"]
    moe = shapes["layer_1"]["moe"]
    assert moe["router"].shape == (4096, 256)
    assert moe["w_gate"].shape == (16, 4096, 2048)
    assert "shared" not in moe and "mlp" in shapes["layer_0"]
    # the registry's default is the published model, whole: 9 full
    # layers (0, 5, 11, ..., 47) and 39 window layers
    whole = models.get_model("mimo_v2_5")
    assert (whole.num_layers, whole.n_held, whole.vocab_size) == (
        48, 256, 152576)
    assert (whole.n_full, whole.n_sliding) == (9, 39)
    assert [i for i, k in enumerate(whole.layer_types)
            if k == "full_attention"] == [0] + list(range(5, 48, 6))
    with pytest.raises(ValueError, match="hybrid_layer_pattern"):
        models.get_model("mimo_v2_tiny", num_layers=6)


@pytest.mark.parametrize("form", ["whole-prompt", "chunked",
                                  "chunked-kernel", "decode",
                                  "decode-kernel"])
def test_program_equals_the_reference(tiny, ref_logits, monkeypatch, form):
    """Attention in its three forms against the reference's full score
    matrix under a band mask with a sink column: 96 tokens are twelve
    windows; the decode runs from position 8 to 47 through the page
    table AND the ring, which wraps every three pages."""
    model, params = tiny
    if form == "whole-prompt":
        tokens = _tokens(96)
        got, want = _prefill_logits(model, params, tokens), ref_logits(tokens)
    elif form.startswith("chunked"):
        tokens = _tokens(96, seed=1)
        impl = "xla"
        if form == "chunked-kernel":
            # blocks of 8 queries and 16 columns: the kernel skips and
            # masks column blocks in every layer of each chunk, a
            # matmul a group as at MiMo's keys of 192
            monkeypatch.setattr(chunk_attention, "_chunk_blocks",
                                lambda t, w, group, *_: (8, 16, group))
            impl = "pallas"
        got = _chunked_logits(model, params, tokens, impl=impl)
        want = ref_logits(tokens)
    else:
        tokens = _tokens(48, seed=2)
        got = _decode_logits(model, params, tokens, impl=(
            "pallas" if form == "decode-kernel" else "xla"))
        want = ref_logits(tokens)[8:]
    assert _rel(got, want) < F32_LIMIT


@pytest.mark.parametrize("share", [
    {}, dict(experts_held=4, expert_offset=0),
    dict(experts_held=4, expert_offset=12)],
    ids=["every-expert", "experts-0-3", "experts-12-15"])
def test_other_shares_equal_the_reference(share):
    model = models.get_model("mimo_v2_tiny", dtype=jnp.float32, **share)
    params = init_params(model, 0)
    tokens = _tokens(64)
    want = reference.make_logits_fn(_config(model))(params,
                                                    jnp.asarray(tokens))
    assert _rel(_prefill_logits(model, params, tokens), want) < F32_LIMIT


# ----------------------------------------------------------- the kernel

WINDOW, ENTRIES = 8, 3
_POSITIONS = {
    "before-the-edge": [0, 2, 5, 6],
    "at-the-edge": [7, 7, 8, 8],
    # the ring of 3 pages wraps at 12, 24, 36, ...
    "across-a-wrap": [11, 12, 23, 25, 35, 36, 37, 47],
}


@pytest.mark.parametrize("sink", [False, True], ids=["no-sink", "sink"])
@pytest.mark.parametrize("where", list(_POSITIONS))
@pytest.mark.parametrize("kind", ["full", "window"])
def test_kernel_with_narrower_values_equals_its_xla_form(kind, where, sink):
    """One body at ``Dk`` 24 != ``Dv`` 16, rows of ``Hkv (Dk + Dv)``
    values, two tables. ``full``: a page table, every column up to the
    position. ``window``: a ring of 3 pages a slot holding the LAST
    three pages written, the first live page masked below ``pos - 7``.
    With a sink a head's softmax has one more column that carries no
    value. Both forms against dense attention written out here."""
    rng = np.random.default_rng(11)
    positions = np.array(_POSITIONS[where])
    b, heads, kv_heads, dk, dv, n_pages = len(positions), 8, 2, 24, 16, 12
    row = kv_heads * (dk + dv)
    q = jnp.asarray(rng.normal(size=(b, heads, dk)), jnp.float32)
    hist = rng.normal(size=(b, n_pages * PS, row)).astype(np.float32)
    sinks = (jnp.asarray(rng.normal(size=(heads,)) + 1.5, jnp.float32)
             if sink else None)
    if kind == "full":
        reach, entries = None, n_pages
        table = 1 + np.arange(b * n_pages).reshape(b, n_pages)
        pool = np.zeros((2, b * n_pages + 1, PS, row), np.float32)
        pool[1, 1:] = hist.reshape(b * n_pages, PS, row)
    else:
        reach, entries = WINDOW, ENTRIES
        table = np.arange(b * entries).reshape(b, entries)
        pool = rng.normal(size=(2, b * entries, PS, row)).astype(np.float32)
        for s, pos in enumerate(positions):    # what decode left behind
            for page in range(pos // PS + 1):  # later pages overwrite
                pool[1, table[s, page % entries]] = hist[
                    s, page * PS:(page + 1) * PS]
    args = (q, jnp.asarray(pool), jnp.asarray(table, jnp.int32),
            jnp.asarray(positions, jnp.int32))
    kw = dict(layer=1, kv_heads=kv_heads, scale=dk ** -0.5, reach=reach,
              sinks=sinks)
    got = da.gqa_paged_decode_attention(*args, impl="pallas",
                                        interpret=True, **kw)
    twin = da.gqa_paged_decode_attention(*args, impl="xla", **kw)
    assert got.shape == twin.shape == (b, heads, dv)
    want = np.zeros((b, heads, dv), np.float32)
    for s, pos in enumerate(positions):
        lo = 0 if reach is None else max(0, pos - reach + 1)
        cols = hist[s, lo:pos + 1]
        for t in range(heads):
            g = t // (heads // kv_heads)
            k = cols[:, g * dk:(g + 1) * dk]
            v = cols[:, kv_heads * dk + g * dv:kv_heads * dk + (g + 1) * dv]
            score = np.asarray(q[s, t]) @ k.T * dk ** -0.5
            top = score.max() if sinks is None else max(score.max(),
                                                        float(sinks[t]))
            p = np.exp(score - top)
            total = p.sum() + (0.0 if sinks is None
                               else np.exp(float(sinks[t]) - top))
            want[s, t] = (p / total) @ v
    np.testing.assert_allclose(np.asarray(twin), want, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-6)


# ------------------------------------------------------------ the share

def _layer(seed=1, t=48, d=16, f=24, e=16):
    rng = np.random.default_rng(seed)

    def mat(*shape, scale=.3):
        return jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)

    return (mat(t, d, scale=1.0),
            {"router": mat(d, e, scale=1.0), "e_bias": mat(e, scale=0.5),
             "w_gate": mat(e, d, f), "w_up": mat(e, d, f),
             "w_down": mat(e, f, d)})


@pytest.mark.parametrize("side", ["program", "reference"])
def test_the_sixteen_shares_add_up_to_the_uncut_expert_layer(side):
    """The guide's share test at the deployment's count, with no shared
    expert: the parts that all 16 shares of one expert give add up to
    the uncut reference's expert layer (top-8, selection bias, weights
    normalised, scaling 1); the program's layer is ``models/latent.py::
    _ffn`` itself, which adds no shared term for a layer without one."""
    x, p = _layer()
    t, e, k = x.shape[0], 16, 8
    hp = {"top_k": k, "route_scale": 1.0}
    want = reference.experts(x, p, {**hp, "offset": 0})
    model = models.get_model("mimo_v2_tiny", dtype=jnp.float32, moe_top_k=k)
    total, every = 0.0, []
    for offset in range(e):
        mine = {**p, **{name: p[name][offset:offset + 1]
                        for name in ("w_gate", "w_up", "w_down")}}
        if side == "program":
            one = dataclasses.replace(model, experts_held=1,
                                      expert_offset=offset)
            part, load = latent._ffn(x, {"moe": mine}, one)
            assert int(load[:-1].sum()) == t * k
            every.append(np.asarray(load[0]))
        else:
            part = reference.experts(x, mine, {**hp, "offset": offset})
        total = total + part
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    if side == "program":
        chosen, _ = route_sigmoid_topk(x, p["router"], p["e_bias"], k, 1.0)
        assert (np.array(every) == np.bincount(
            np.asarray(chosen).ravel(), minlength=e)).all()


def test_a_layer_without_a_shared_expert_adds_none():
    """``_ffn`` of a layer with no ``shared`` key is the routed part
    alone; with one, the shared expert's output is added as before."""
    x, p = _layer(seed=3)
    model = models.get_model("mimo_v2_tiny", dtype=jnp.float32)
    routed, load = latent._ffn(x, {"moe": p}, model)
    chosen, weights = route_sigmoid_topk(x, p["router"], p["e_bias"],
                                         model.moe_top_k, 1.0)
    alone, *_ = dropless_experts(x, chosen, weights, p["w_gate"], p["w_up"],
                                 p["w_down"], n_experts=16, offset=0)
    np.testing.assert_array_equal(np.asarray(routed), np.asarray(alone))
    shared = {"w_gate": p["w_gate"][0], "w_up": p["w_up"][0],
              "w_down": p["w_down"][0]}
    both, _ = latent._ffn(x, {"moe": {**p, "shared": shared}}, model)
    np.testing.assert_allclose(
        np.asarray(both - routed),
        np.asarray(latent._gated(x, shared, jnp.float32)), atol=1e-5)


# ---------------------------------- two pools of unequal rows, the splice

def _reserve(pool, slot_tokens):
    slot = pool.acquire()
    pool.bind_slot(slot, pool.alloc_pages(
        PagePool.pages_for(slot_tokens, pool.page_size)))
    return slot


def test_page_pool_keeps_each_kind_at_its_own_row(tiny):
    """The full layers' pool at 40 values a row, the window layers'
    rings at 80: bytes held, undivided and live each weigh a kind's
    pages by its own row."""
    model, _ = tiny
    pool = PagePool(model, 3, 64, page_size=PS)
    full_row, ring_row = 40 * 4, 80 * 4              # float32 bytes
    assert pool.k_pages.shape == (2, 3 * 16 + 1, PS, 40)
    assert pool.v_pages.shape == (3, 3 * 3, PS, 80)
    assert pool.page_bytes == 2 * PS * full_row
    assert pool.ring_page_bytes == 3 * PS * ring_row
    assert (pool.page_bytes * pool.num_pages + pool.ring_page_bytes * 9
            == pool.k_pages.nbytes + pool.v_pages.nbytes)
    slot = _reserve(pool, 40)                        # 10 pages, ring of 3
    assert pool.pages_held() == {"full": 10, "sliding": 3}
    assert pool.kv_bytes_held == (10 * 2 * full_row + 3 * 3 * ring_row) * PS
    # one undivided pool: every held page across all five layers
    assert pool.kv_bytes_undivided == 10 * (2 * full_row + 3 * ring_row) * PS
    pool.note_insert(slot, 37)
    pages = pool.live_pages_by_kind()
    assert pages == {"kv_pages_live_full": 10, "kv_pages_live_window": 3}
    assert pool.live_bytes_by_kind(pages) == {
        "kv_bytes_live_full": 10 * PS * full_row,
        "kv_bytes_live_window": 3 * PS * ring_row}
    pool.release(slot)
    assert pool.pages_held() == {"full": 0, "sliding": 0}


def test_the_cell_holds_a_fifth_of_an_undivided_cache():
    """The cut's arithmetic at the published widths, one slot of 9,216
    columns: 47.2 MB of full pages and 3.7 MB of rings against 283.1 MB
    if every layer held the whole context under the page table."""
    model = models.get_model("mimo_v2_5", dtype=jnp.bfloat16, num_layers=7,
                             experts_held=16, vocab_size=19072)
    pool = PagePool(model, 1, 9216, page_size=16)
    _reserve(pool, 9216)
    usage = pool.kv_usage()
    assert usage["bytes_held"] == 2 * 9216 * 2560 + 5 * 144 * 5120
    assert usage["bytes_undivided"] == 9216 * (2 * 2560 + 5 * 5120)
    assert usage["bytes_held"] / usage["bytes_undivided"] == pytest.approx(
        0.1797, abs=1e-4)


@pytest.mark.parametrize("prompt", [3, 9, 30])
def test_the_splice_cuts_each_kind_at_its_own_row(tiny, prompt):
    """After the insert ring entry ``g % 3`` holds page ``g`` of the
    prompt's window caches (rows of 80) for the newest three pages; the
    full pool (rows of 40) holds every page."""
    model, _ = tiny
    width = -(-prompt // 16) * 16
    rng = np.random.default_rng(prompt)
    pref_full, pref_window = (
        jnp.asarray(rng.normal(size=shape), jnp.float32)
        for shape in pref_cache_shapes(model, width))
    full, rings, table = _pools(model, 2, width // PS)
    state = (jnp.zeros((2,), jnp.int32),) * 2 + (
        jnp.zeros((2,), bool), jnp.zeros((2,), jnp.int32),
        jnp.full((2,), -1, jnp.int32))
    full, rings, positions, *_ = ServingEngine._ring_insert_fn(
        full, rings, *state, pref_full, pref_window, table[1],
        jnp.int32(1), jnp.int32(prompt), jnp.int32(5), jnp.int32(7),
        jnp.int32(-1))
    assert int(positions[1]) == prompt
    pages = np.asarray(pref_window).reshape(3, width // PS, PS, 80)
    newest = (prompt - 1) // PS
    for g in range(max(0, newest - 2), newest + 1):
        assert (np.asarray(rings[:, 3 + g % 3]) == pages[:, g]).all()
    assert (np.asarray(full[:, np.asarray(table[1])]).reshape(
        2, width, 40) == np.asarray(pref_full)[:, 0]).all()


# ------------------------------------------------------------ the engine

def _serve(model, params, requests, **kw):
    kw.setdefault("max_slots", 3)
    engine = ServingEngine(model, params, s_max=128, page_size=PS, **kw)
    out = []
    for prompt, n in requests:              # staggered: one a step
        out.append(engine.submit(list(prompt), n))
        engine.step()
    while engine.in_flight:
        engine.step()
    return engine, out


def _gaps(ref_fn, params, request):
    stream = np.array(list(request.prompt) + list(request.tokens))
    logits = np.asarray(ref_fn(params, jnp.asarray(stream)))
    first = len(request.prompt) - 1
    rows = logits[first:len(stream) - 1]
    picked = rows[np.arange(len(rows)), stream[first + 1:]]
    return (rows.max(axis=1) - picked) / logits.std()


@pytest.mark.parametrize("chunk, impl", [(8, "xla"), (None, "xla"),
                                         (8, "pallas")],
                         ids=["chunked", "whole-prompt", "chunked-kernel"])
def test_engine_staggered_admissions_agree_with_the_reference(tiny, chunk,
                                                              impl):
    """Through ServingEngine, the two-pool PagePool of unequal rows and
    the scheduler (five requests over three slots, admissions a step
    apart): every emitted token is the reference's own argmax at its
    position; the dispatch events carry what one layer of each kind
    reads, in pages and in bytes at the kind's own row."""
    model, params = tiny
    prompts = [(_tokens(70, 1), 20), (_tokens(33, 2), 12),
               (_tokens(50, 3), 9), (_tokens(5, 4), 30),
               (_tokens(17, 5), 8)]
    with scoped() as scope:
        engine, served = _serve(model, params, prompts,
                                prefill_chunk=chunk, decode_attn=impl)
    ref_fn = reference.make_logits_fn(_config(model))
    for request, (_, n) in zip(served, prompts):
        assert len(request.tokens) == n
        assert _gaps(ref_fn, params, request).max() == 0.0
    snap = engine.metrics.snapshot()
    assert (snap["moe_assignments"] + snap["moe_assignments_elsewhere"]
            == snap["decode_dispatches"] * 3 * model.moe_top_k
            * model.n_moe_layers)
    assert 0 < snap["kv_bytes_held_over_undivided"] < 1
    assert snap["kv_ring_pages_overwritten"] > 0
    dispatches = [e for e in scope.events() if e.name == "decode.dispatch"]
    assert len(dispatches) == snap["decode_dispatches"]
    for e in dispatches:
        a = e.attrs
        assert a["kv_bytes_live_full"] == a["kv_pages_live_full"] * PS * 160
        assert (a["kv_bytes_live_window"]
                == a["kv_pages_live_window"] * PS * 320)
    assert max(e.attrs["kv_pages_live_window"] for e in dispatches) <= 9
    assert engine.in_flight == 0 and engine.pool.pages_in_use == 0


def test_engine_bfloat16_within_its_tolerance():
    model = models.get_model("mimo_v2_tiny", dtype=jnp.bfloat16, **SHARE)
    params = init_params(model, 0)
    assert params["layer_1"]["attn"]["wq"].dtype == jnp.bfloat16
    assert params["layer_1"]["attn"]["sinks"].dtype == jnp.float32
    ref_fn = reference.make_logits_fn(_config(model))
    tokens = _tokens(96)
    got = _prefill_logits(model, params, tokens)
    assert _mean_rel(got, ref_fn(params, jnp.asarray(tokens))) < BF16_LIMIT
    _, served = _serve(model, params, [(_tokens(70, 1), 20),
                                       (_tokens(33, 2), 12)],
                       prefill_chunk=8)
    for request in served:
        assert _gaps(ref_fn, params, request).mean() < BF16_LIMIT


@pytest.mark.parametrize("options, named", [
    (dict(kv_dtype="int8"), "kv_dtype=int8 is not supported for the mimo_v2"),
    (dict(draft_k=2), "draft_k is not supported for the mimo_v2"),
    (dict(prefix_cache=4), "prefix_cache is not supported for the mimo_v2"),
    (dict(mesh=True), "mesh is not supported for the mimo_v2"),
], ids=["kv_dtype=int8", "draft_k", "prefix_cache", "mesh"])
def test_engine_refuses_by_name_what_the_family_lacks(tiny, options, named):
    from jax.sharding import Mesh

    model, params = tiny
    if "mesh" in options:
        options = dict(mesh=Mesh(np.array(jax.devices()[:2]).reshape(1, 2),
                                 ("data", "model")))
    with pytest.raises(NotImplementedError) as e:
        ServingEngine(model, params, max_slots=2, s_max=64, page_size=PS,
                      **options)
    assert named in str(e.value)


def test_generate_is_refused_by_name(tiny):
    model, params = tiny
    with pytest.raises(NotImplementedError, match="mimo_v2"):
        generate(model, params, jnp.zeros((1, 4), jnp.int32),
                 max_new_tokens=2)


# ------------------------------------------------------- planted faults

def _sink_dropped(monkeypatch, params):
    """The window layers lose their sink in the program."""
    for name, layer in params.items():
        if name.startswith("layer_"):
            layer["attn"].pop("sinks", None)
    return {}


def _value_scale_dropped(monkeypatch, params):
    return {"attention_value_scale": 1.0}


def _window_ignored(monkeypatch, params):
    real = mimo_v2.gqa_paged_decode_attention
    monkeypatch.setattr(
        mimo_v2, "gqa_paged_decode_attention",
        lambda *a, reach=None, **kw: real(*a, reach=None, **kw))
    return {}


@pytest.mark.parametrize("plant", [
    _sink_dropped, _value_scale_dropped, _window_ignored],
    ids=["sink-dropped", "value-scale-dropped", "window-ignored"])
def test_planted_fault_exceeds_the_limit(monkeypatch, plant):
    """Each fault, planted in the PROGRAM only (its model or its
    weights), moves its decoded logits by more than the bfloat16 limit,
    though the float32 program passes F32_LIMIT a thousand times
    over."""
    model = models.get_model("mimo_v2_tiny", dtype=jnp.float32, **SHARE)
    params = init_params(model, 0)
    tokens = _tokens(48, seed=2)
    want = np.asarray(reference.make_logits_fn(_config(model))(
        params, jnp.asarray(tokens)))[8:]
    params = jax.tree.map(lambda a: a, params)        # a tree of our own
    faulty = models.get_model("mimo_v2_tiny", dtype=jnp.float32, **SHARE,
                              **plant(monkeypatch, params))
    got = _decode_logits(faulty, params, tokens)
    assert _mean_rel(got, want) > BF16_LIMIT


@pytest.mark.parametrize("wrong", [
    {"partial_rotary_factor": 1.0}, {"rope_theta": 10000.0},
    {"swa_rope_theta": 10000000.0}],
    ids=["rotary-on-every-dimension", "one-theta-full", "one-theta-window"])
def test_a_wrong_rotation_exceeds_the_float32_limit(wrong):
    """Random weights of 0.02 keep every score near 0, so where a key
    sits barely moves a logit: a rotation of the wrong dimensions or at
    the wrong theta stays under the bfloat16 limit at this size and is
    caught by the float32 comparison (the largest error over F32_LIMIT;
    PERF.md names what the chip's comparison cannot see)."""
    model = models.get_model("mimo_v2_tiny", dtype=jnp.float32, **SHARE)
    params = init_params(model, 0)
    tokens = _tokens(48, seed=2)
    want = np.asarray(reference.make_logits_fn(_config(model))(
        params, jnp.asarray(tokens)))[8:]
    faulty = models.get_model("mimo_v2_tiny", dtype=jnp.float32, **SHARE,
                              **wrong)
    assert _rel(_decode_logits(faulty, params, tokens), want) > 10 * F32_LIMIT
