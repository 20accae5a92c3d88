"""The ``lfm2_moe`` family against its plain reference
(``perf/reference/lfm2_moe.py``) at the tiny preset on the CPU, seeded
random weights: gated short-convolution layers beside grouped-query
full-attention layers with QK norms and rotary, in the three forms
(whole-prompt prefill, chunked prefill with the conv's carry crossing
chunk boundaries mid-page, decode through BOTH pools: the full layers'
pages and the conv layers' two-page rings); a slot re-admitted with a
1- or 2-token prompt after a longer request; the kernel ``short_conv``
in interpret mode against its XLA form; the dense and routed
feed-forwards with every expert held; what the engine refuses for the
family; the counters that name each pool; planted faults the
comparison has to catch.

``lfm2_moe_tiny``: four query heads of 16 on two key/value heads, 8
experts at top-4, two dense layers, kinds conv-full-conv-conv-full;
pages of 4, so a ring of ``ceil(3 / 4) + 1 = 2`` pages a slot; rows of
64 (full: K then V) and 64 (conv: ``u``) values. The layers' matrices
are drawn four times wider than the init's 0.02 (:func:`_spread`): at
a width of 64 the tied head would otherwise rank each token's own row
first whatever the layers did, where at the published widths the
layers' sum is 60 times the embedding's size.

Tolerances are shares of the reference logits' standard deviation, as
in ``tests/test_afmoe.py``: the float32 program's LARGEST error is held
to 2e-5; bfloat16's MEAN error to 0.05; each planted fault reads above
that in the float32 program.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from perf.reference import lfm2_moe as reference
from pytorch_multiprocessing_distributed_tpu import models
from pytorch_multiprocessing_distributed_tpu.inference.generate import (
    cache_pools, generate, pref_cache_shapes, serving_family)
from pytorch_multiprocessing_distributed_tpu.models import lfm2_moe
from pytorch_multiprocessing_distributed_tpu.ops.moe import (
    route_sigmoid_topk)
from pytorch_multiprocessing_distributed_tpu.ops.pallas import (
    chunk_attention)
from pytorch_multiprocessing_distributed_tpu.runtime.scope import scoped
from pytorch_multiprocessing_distributed_tpu.serving import (
    PagePool, ServingEngine, init_params)
from pytorch_multiprocessing_distributed_tpu.serving.kv_pages import (
    live_counter)

# the module, not a same-named function
sc = importlib.import_module(
    "pytorch_multiprocessing_distributed_tpu.ops.pallas.short_conv")

F32_LIMIT = 2e-5
BF16_LIMIT = 0.05
VOCAB = 211
PS = 4                      # pages of 4: a ring of 2 for the conv's 3


def _config(model) -> dict:
    """The published key names for a model's sizes: what the reference
    is configured from."""
    return {
        "num_hidden_layers": model.num_layers,
        "layer_types": list(model.layer_types),
        "norm_eps": model.rms_eps,
        "hidden_size": model.hidden_size,
        "num_attention_heads": model.num_heads,
        "num_key_value_heads": model.num_kv_heads,
        "rope_theta": model.rope_theta,
        "num_experts_per_tok": model.moe_top_k, "norm_topk_prob": True,
        "routed_scaling_factor": model.routed_scale}


def _spread(params, scale=4.0):
    """The layers' matrices (not the embedding, the router or the
    gains) times ``scale``."""
    def one(path, a):
        names = jax.tree_util.keystr(path)
        if a.ndim >= 2 and "layer_" in names and "router" not in names:
            return (a.astype(jnp.float32) * scale).astype(a.dtype)
        return a
    return jax.tree_util.tree_map_with_path(one, params)


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
    model = models.get_model("lfm2_moe_tiny", dtype=jnp.float32)
    return model, _spread(init_params(model, 0))


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
    full, conv = (jnp.zeros(shape, model.dtype)
                  for shape in pref_cache_shapes(model, len(tokens)))
    out = []
    for start in range(0, len(tokens), chunk):
        x, full, conv = family.chunk(
            model, params, full, conv,
            jnp.asarray(tokens[start:start + chunk])[None],
            jnp.int32(start), attn_impl=impl)
        out.append(family.logits(model, params, x)[0])
    return np.concatenate(out)


def _pools(model, slots, pages_per_slot, fill=0.0):
    """Both pools for ``slots`` slots, each at its own row: the full
    layers' behind an identity page table (page 0 is scratch), the
    conv layers' rings, every value ``fill`` (what a slot's earlier
    tenant could have left)."""
    full = jnp.full((model.n_full, slots * pages_per_slot + 1, PS,
                     model.kv_row), fill, model.dtype)
    rings = jnp.full((model.n_conv, slots * 2, PS, model.hidden_size),
                     fill, model.dtype)
    table = (1 + jnp.arange(slots * pages_per_slot, dtype=jnp.int32)
             ).reshape(slots, pages_per_slot)
    return full, rings, table


def _decode_logits(model, params, tokens, prompt=8, impl="xla", slots=2,
                   fill=0.0):
    """Prefill ``prompt`` tokens (right-padded to whole pages), splice
    them into both pools the engine's way, then decode the rest ONE
    token a step through the page table and the ring; the logits of
    every decoded position. ``fill``: what both pools held before."""
    family = model.serving_family
    n = len(tokens)
    width = -(-prompt // PS) * PS
    padded = np.full((1, width), 7, np.int32)     # a pad token, not 0
    padded[0, :prompt] = tokens[:prompt]
    _, pref_full, pref_conv = family.prefill(model, params,
                                             jnp.asarray(padded))
    full, rings, table = _pools(model, slots, -(-n // PS), fill)
    state = (jnp.zeros((slots,), jnp.int32),) * 2 + (
        jnp.zeros((slots,), bool), jnp.zeros((slots,), jnp.int32),
        jnp.full((slots,), -1, jnp.int32))
    full, rings, positions, *_ = ServingEngine._ring_insert_fn(
        full, rings, *state, pref_full, pref_conv,
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
    """The stage the benchmark serves: layers 0-11 (both dense layers,
    then ten expert layers: three full and seven conv), every expert
    and vocabulary row, the head tied to the embedding: 3,928.7 M
    parameters, 7.86 GB as served; two pools, K|V rows of 1,024 values
    under the page table and ``u`` rows of 2,048 in a ring of 3
    columns."""
    model = models.get_model("lfm2_8b_a1b", dtype=jnp.bfloat16,
                             num_layers=12)
    assert serving_family(model).name == "lfm2_moe"
    assert (model.hidden_size, model.num_heads, model.num_kv_heads,
            model.head_dim, model.kv_row) == (2048, 32, 8, 64, 1024)
    assert (model.n_experts, model.n_held, model.moe_top_k, model.moe_dim,
            model.mlp_dim, model.first_k_dense) == (32, 32, 4, 1792, 7168, 2)
    assert (model.route_eps, model.rms_eps, model.rope_theta,
            model.conv_width) == (1e-6, 1e-5, 1e6, 3)
    assert model.layer_types == ("conv", "conv", "full_attention",
                                 "conv", "conv", "conv", "full_attention",
                                 "conv", "conv", "conv", "full_attention",
                                 "conv")
    assert (model.n_full, model.n_conv, model.n_moe_layers) == (3, 9, 10)
    assert cache_pools(model) == (
        ("full", (1024,), jnp.bfloat16, 3, None),
        ("conv", (2048,), jnp.bfloat16, 9, 3))
    assert pref_cache_shapes(model, 1024) == ((3, 1, 1024, 1024),
                                              (9, 1, 1024, 2048))
    shapes = jax.eval_shape(lambda: model._init(jax.random.PRNGKey(0)))
    leaves = jax.tree.leaves(shapes)
    count = sum(int(np.prod(s.shape)) for s in leaves)
    held = sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in leaves)
    assert abs(count - 3.9287e9) < 1e5
    assert abs(held - 7.859e9) < 1e7
    assert "head" not in shapes                      # tied to the embedding
    conv = shapes["layer_0"]["conv"]
    assert (conv["w_in"].shape, conv["taps"].shape, conv["w_out"].shape) == (
        (2048, 6144), (3, 2048), (2048, 2048))
    attn = shapes["layer_2"]["attn"]
    assert (attn["wq"].shape, attn["wk"].shape, attn["q_norm"]["scale"].shape
            ) == ((2048, 2048), (2048, 512), (64,))
    assert "mlp" in shapes["layer_1"] and "moe" in shapes["layer_2"]
    moe = shapes["layer_2"]["moe"]
    assert moe["router"].shape == (2048, 32) and "shared" not in moe
    assert moe["w_gate"].shape == (32, 2048, 1792)
    # the registry's default is the published model, whole
    whole = models.get_model("lfm2_8b_a1b")
    assert (whole.num_layers, whole.n_full, whole.n_conv) == (24, 6, 18)
    assert [i for i, k in enumerate(whole.layer_types)
            if k == "full_attention"] == [2, 6, 10, 14, 18, 21]
    with pytest.raises(ValueError, match="layer_pattern"):
        models.get_model("lfm2_moe_tiny", num_layers=6)


def test_the_taps_are_drawn_wide():
    """The taps are uniform(+-1/sqrt(3)), a depthwise conv of width 3's
    default draw, not the matrices' normal(0, 0.02)."""
    model = models.get_model("lfm2_moe_tiny")
    taps = np.asarray(init_params(model, 1)["layer_0"]["conv"]["taps"])
    assert taps.shape == (3, model.hidden_size)
    assert np.abs(taps).max() <= 3 ** -0.5 and np.abs(taps).mean() > 0.2


@pytest.mark.parametrize("form", ["whole-prompt", "chunked",
                                  "chunked-mid-page", "chunked-kernel",
                                  "decode", "decode-kernel"])
def test_program_equals_the_reference(tiny, ref_logits, monkeypatch, form):
    """Both mixers in their three forms against the reference's sum of
    shifted rows and full causal score matrix: chunks of 16 and of 6
    (the conv's two carried rows cross every chunk boundary, at 6 in
    the middle of a page of 4); the decode runs from position 8 to 47
    through the page table and the two-page ring, which wraps every 8
    tokens."""
    model, params = tiny
    if form == "whole-prompt":
        tokens = _tokens(96)
        got, want = _prefill_logits(model, params, tokens), ref_logits(tokens)
    elif form.startswith("chunked"):
        tokens = _tokens(96, seed=1)
        impl, chunk = "xla", 16
        if form == "chunked-mid-page":
            tokens, chunk = tokens[:90], 6
        if form == "chunked-kernel":
            # blocks of 8 queries and 16 columns: the grouped kernel
            # skips and masks column blocks in every layer of each chunk
            monkeypatch.setattr(chunk_attention, "_chunk_blocks",
                                lambda t, w, group, *_: (8, 16, group))
            impl = "pallas"
        got = _chunked_logits(model, params, tokens, chunk=chunk, impl=impl)
        want = ref_logits(tokens)
    else:
        tokens = _tokens(48, seed=2)
        got = _decode_logits(model, params, tokens, impl=(
            "pallas" if form == "decode-kernel" else "xla"))
        want = ref_logits(tokens)[8:]
    assert _rel(got, want) < F32_LIMIT


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("prompt", [1, 2])
def test_a_short_prompt_in_a_used_slot_equals_the_reference(tiny, ref_logits,
                                                            prompt, impl):
    """A slot whose earlier tenant was longer, re-admitted with a 1- or
    2-token prompt: both pools hold that tenant's rows (here: 3.0
    everywhere), and the splice fills the ring's missing entry with
    rows of the new prompt's padded page. The conv reads nothing below
    position 0, so the decode's logits are the reference's."""
    model, params = tiny
    tokens = _tokens(20, seed=5)
    got = _decode_logits(model, params, tokens, prompt=prompt, impl=impl,
                         fill=3.0)
    assert _rel(got, ref_logits(tokens)[prompt:]) < F32_LIMIT


def _serve(model, params, prompts, slots=3, **kw):
    engine = ServingEngine(model, params, max_slots=slots, s_max=128,
                           kv_layout="paged", page_size=PS, **kw)
    served = []
    for tokens, n in prompts:
        served.append(engine.submit(tokens.tolist(), n))
        engine.step()
    while engine.in_flight:
        engine.step()
    return engine, served


def _gaps(ref_fn, params, request):
    """The reference's largest logit minus its logit for the token the
    engine emitted, at every generated position, over the logits'
    standard deviation."""
    stream = np.asarray(list(request.prompt) + list(request.tokens))
    logits = np.asarray(ref_fn(params, jnp.asarray(stream)))
    first = len(request.prompt) - 1
    rows = logits[first:len(stream) - 1]
    picked = rows[np.arange(len(rows)), stream[first + 1:]]
    return (rows.max(axis=1) - picked) / logits.std()


@pytest.mark.parametrize("chunk, impl", [(8, "xla"), (None, "xla"),
                                         (6, "pallas")],
                         ids=["chunked", "whole-prompt", "chunked-kernel"])
def test_engine_staggered_admissions_agree_with_the_reference(tiny, chunk,
                                                              impl):
    """Through ServingEngine, the two-pool PagePool and the scheduler
    (six requests over three slots, admissions a step apart, slots
    re-used by shorter prompts, 1- and 2-token prompts among them):
    every emitted token is the reference's own argmax at its position;
    the dispatch events name the conv ring's live bytes as state, never
    as KV."""
    model, params = tiny
    prompts = [(_tokens(70, 1), 20), (_tokens(33, 2), 12),
               (_tokens(50, 3), 9), (_tokens(1, 4), 30),
               (_tokens(2, 5), 8), (_tokens(17, 6), 10)]
    with scoped() as scope:
        engine, served = _serve(model, params, prompts,
                                prefill_chunk=chunk, decode_attn=impl)
    ref_fn = reference.make_logits_fn(_config(model))
    for request, (_, n) in zip(served, prompts):
        assert len(request.tokens) == n
        assert _gaps(ref_fn, params, request).max() == 0.0
    snap = engine.metrics.snapshot()
    assert (snap["moe_assignments"]
            == snap["decode_dispatches"] * 3 * model.moe_top_k
            * model.n_moe_layers)
    assert snap["moe_assignments_elsewhere"] == 0
    dispatches = [e for e in scope.events() if e.name == "decode.dispatch"]
    assert len(dispatches) == snap["decode_dispatches"]
    for e in dispatches:
        a = e.attrs
        assert "kv_bytes_live_window" not in a
        assert a["kv_bytes_live_full"] == a["kv_pages_live_full"] * PS * 256
        assert (a["state_bytes_live_conv"]
                == a["state_pages_live_conv"] * PS * 256)
        # a slot's conv reaches its last 3 columns: one or two pages
        assert a["state_pages_live_conv"] <= 2 * 3
    assert engine.in_flight == 0 and engine.pool.pages_in_use == 0


def test_engine_bfloat16_within_its_tolerance():
    model = models.get_model("lfm2_moe_tiny", dtype=jnp.bfloat16)
    params = _spread(init_params(model, 0))
    assert params["layer_0"]["conv"]["w_in"].dtype == jnp.bfloat16
    assert params["layer_0"]["conv"]["taps"].dtype == jnp.bfloat16
    assert params["layer_2"]["moe"]["router"].dtype == jnp.float32
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
    (dict(kv_dtype="int8"), "kv_dtype=int8 is not supported for the "
                            "lfm2_moe family yet: no quantised"),
    (dict(draft_k=2), "draft_k is not supported for the lfm2_moe family "
                      "yet: no rollback of the conv's carried rows"),
    (dict(prefix_cache=4), "prefix_cache is not supported for the lfm2_moe "
                           "family yet: no snapshot of the conv's"),
    (dict(mesh=True), "mesh is not supported for the lfm2_moe family yet"),
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
    with pytest.raises(NotImplementedError, match="lfm2_moe"):
        generate(model, params, jnp.zeros((1, 4), jnp.int32),
                 max_new_tokens=2)


# ---------------------------------------------------- the kernel alone

_POSITIONS = {
    # columns 0 and 1 (nothing, then one row below them: the rest is
    # zero whatever the ring holds), a page's first two columns (the
    # carried rows in the page before), mid-page, a wrapped ring
    "young": [0, 1, 2, 3],
    "page-starts": [4, 5, 8, 9],
    "mid-page": [6, 7, 10, 14],
    "wrapped": [37, 40, 41, 63],
}


def _ring_table(n, ring):
    """Each slot's ring as the walk builds it: ``ring`` pages a slot in
    slot order."""
    return (jnp.arange(n, dtype=jnp.int32)[:, None] * ring
            + jnp.arange(ring, dtype=jnp.int32)[None, :])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("where", list(_POSITIONS))
def test_short_conv_kernel_equals_its_xla_form(where, dtype):
    """``short_conv`` in interpret mode against ``impl="xla"`` over a
    three-layer ring pool of random rows (what other requests left):
    the same outputs and the same pool after the step, layer 1 written
    in place and nothing else."""
    n, ring, c = 4, 2, 256
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    proj = jax.random.normal(keys[0], (n, 3 * c)).astype(dtype)
    taps = jax.random.uniform(keys[1], (3, c), minval=-0.6,
                              maxval=0.6).astype(dtype)
    pool = jax.random.normal(keys[2], (3, n * ring, PS, c)).astype(dtype)
    table = _ring_table(n, ring)
    positions = jnp.asarray(_POSITIONS[where], jnp.int32)
    want, want_pool = sc.short_conv(proj, taps, pool, table, positions,
                                    layer=1, impl="xla")
    got, got_pool = sc.short_conv(proj, taps, pool, table, positions,
                                  layer=1, impl="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-6, atol=1e-6)
    assert bool(jnp.all(got_pool == want_pool))
    assert bool(jnp.all(got_pool[jnp.array([0, 2])]
                        == pool[jnp.array([0, 2])]))
    # each slot's new row, at its column of its own ring
    for s, t in enumerate(_POSITIONS[where]):
        page = s * ring + (t // PS) % ring
        b, _, x = np.split(np.asarray(proj[s], np.float32), 3)
        np.testing.assert_array_equal(
            np.asarray(got_pool[1, page, t % PS], np.float32),
            np.asarray(jnp.asarray(b * x).astype(dtype), np.float32))


@pytest.mark.parametrize("slots", [4, 16, 32], ids=lambda n: f"{n}slots")
def test_short_conv_kernel_waits_for_each_slots_own_page(slots):
    """Under the interpreter's ``on_wait`` DMA mode (a copy lands only
    when its own semaphore is waited on, as on the chip a wait returns
    when its semaphore has counted a page's bytes) the kernel still
    equals its XLA form: each slot's page, and the page before, is read
    only after the wait on that very copy. One grid step (4, 16 slots)
    and two (32); every slot at a page's first columns or mid-page,
    over a ring whose pages are SHUFFLED across slots (the kernel takes
    the walk's table, not a layout of its own)."""
    ring, c = 2, 128
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    proj = jax.random.normal(keys[0], (slots, 3 * c))
    taps = jax.random.uniform(keys[1], (3, c), minval=-0.6, maxval=0.6)
    pool = jax.random.normal(keys[2], (2, slots * ring, PS, c))
    table = jax.random.permutation(
        keys[3], slots * ring).astype(jnp.int32).reshape(slots, ring)
    positions = jnp.asarray(
        [(0, 1, 4, 5, 6, 9, 13, 42)[s % 8] for s in range(slots)],
        jnp.int32)
    want, want_pool = sc.short_conv(proj, taps, pool, table, positions,
                                    layer=1, impl="xla")
    got, got_pool = sc.short_conv(
        proj, taps, pool, table, positions, layer=1, impl="pallas",
        interpret=pltpu.InterpretParams(dma_execution_mode="on_wait"))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    assert bool(jnp.all(got_pool == want_pool))


def test_short_conv_at_position_zero_reads_nothing():
    """At column 0 the output is ``Cg * w2 * u_0`` whatever the ring
    holds: no row below 0 is read."""
    c = 128
    proj = jax.random.normal(jax.random.PRNGKey(0), (2, 3 * c))
    taps = jax.random.normal(jax.random.PRNGKey(1), (3, c))
    pool = jnp.full((1, 4, PS, c), 1e3)
    positions = jnp.zeros((2,), jnp.int32)
    b, cg, x = np.split(np.asarray(proj), 3, axis=1)
    for impl in ("xla", "pallas"):
        out, _ = sc.short_conv(proj, taps, pool, _ring_table(2, 2),
                               positions, layer=0, impl=impl,
                               interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   cg * np.asarray(taps[2]) * b * x,
                                   rtol=1e-6, atol=1e-6)


def test_a_ring_of_one_page_is_refused():
    with pytest.raises(ValueError, match="cannot hold the last 3"):
        sc.short_conv(jnp.zeros((2, 3 * 128)), jnp.zeros((3, 128)),
                      jnp.zeros((1, 2, 16, 128)), _ring_table(2, 1),
                      jnp.zeros((2,), jnp.int32), layer=0)


# ------------------------------------------------------ pools, counters

def test_page_pool_holds_the_conv_state_as_a_ring_of_two_pages(tiny):
    model, _ = tiny
    pool = PagePool(model, max_slots=3, s_max=64, page_size=PS)
    assert pool.ring_window == 3 and pool.ring_pages == 2
    assert pool.k_pages.shape == (2, 3 * 16 + 1, PS, 64)
    assert pool.v_pages.shape == (3, 3 * 2, PS, 64)
    # a slot's state is its ring whatever the context: 2 pages of 3
    # layers x 4 rows x 64 values
    assert pool.ring_page_bytes == 3 * PS * 64 * 4


@pytest.mark.parametrize("name, kwargs, want", [
    ("afmoe_tiny", {}, {"kv_bytes_live_full", "kv_bytes_live_window"}),
    ("mimo_v2_tiny", {}, {"kv_bytes_live_full", "kv_bytes_live_window"}),
    ("lfm2_moe_tiny", {}, {"kv_bytes_live_full", "state_bytes_live_conv"}),
], ids=["afmoe", "mimo_v2", "lfm2_moe"])
def test_live_counters_are_named_by_each_pool(name, kwargs, want):
    """The live bytes are keyed by the pool's declared name: the
    attention families keep the counter names metrics and traces read
    (``kv_bytes_live_full``, ``kv_bytes_live_window``); the conv ring
    is state, never KV."""
    model = models.get_model(name, **kwargs)
    pool = PagePool(model, max_slots=2, s_max=64, page_size=PS)
    pool.note_insert(0, 13)
    pages = pool.live_pages_by_kind()
    assert set(pool.live_bytes_by_kind(pages)) == want
    assert set(pages) == {k.replace("bytes", "pages") for k in want}
    assert live_counter("sliding") == "kv_{}_live_window"
    assert live_counter("conv").format("bytes") == "state_bytes_live_conv"


def test_the_routing_epsilon_is_the_familys():
    """The chosen weights are normalised with the family's epsilon
    (1e-6), not the other sigmoid-routed families' 1e-20: visible where
    the chosen scores are small."""
    x = jnp.full((1, 4), -3.0)
    router = jnp.eye(4) * 10.0
    _, tight = route_sigmoid_topk(x, router, None, 2)
    _, loose = route_sigmoid_topk(x, router, None, 2, eps=1e-6)
    s = float(jax.nn.sigmoid(-30.0))
    assert float(tight[0, 0]) == pytest.approx(0.5)
    assert float(loose[0, 0]) == pytest.approx(s / (2 * s + 1e-6))
    assert float(loose[0, 0]) < 0.4


# ------------------------------------------------------- planted faults

def _carry_dropped(monkeypatch):
    """The chunk's conv reads zeros for the two rows before its start
    (the carry across a chunk boundary lost)."""
    real = lfm2_moe.short_conv_chunk

    def dropped(proj, taps, cache, start):
        out, _ = real(proj, taps, jnp.zeros_like(cache), start)
        _, cache = real(proj, taps, cache, start)
        return out, cache

    monkeypatch.setattr(lfm2_moe, "short_conv_chunk", dropped)


def _stale_rows_read(monkeypatch):
    """The decode's conv reads the ring's rows below position 0 as
    they are (no mask)."""
    def unmasked(proj, taps, pool, table, positions, *, layer,
                 impl="auto"):
        ps = pool.shape[2]
        b, cg, x = sc._split(proj)
        u = (b * x).astype(pool.dtype)

        def carried(k):
            col = positions - k
            return pool[layer, sc._ring_page_ids(table, col, ps),
                        jnp.mod(col, ps)].astype(jnp.float32)

        z = sc._conv(taps, carried(2), carried(1), u.astype(jnp.float32))
        pool = pool.at[layer, sc._ring_page_ids(table, positions, ps),
                       positions % ps].set(u)
        return (cg * z).astype(pool.dtype), pool

    monkeypatch.setattr(lfm2_moe, "short_conv", unmasked)


def test_a_dropped_carry_fails_the_comparison(tiny, ref_logits,
                                              monkeypatch):
    """Zeroing the carried rows at each chunk boundary moves the
    chunked logits past the bfloat16 limit, though the float32 program
    passes F32_LIMIT a thousand times over."""
    model, params = tiny
    tokens = _tokens(90, seed=1)
    _carry_dropped(monkeypatch)
    got = _chunked_logits(model, params, tokens, chunk=6)
    assert _mean_rel(got, ref_logits(tokens)) > BF16_LIMIT


def test_reading_the_rings_stale_rows_fails_the_comparison(
        tiny, ref_logits, monkeypatch):
    """A conv that reads what the ring holds below position 0 (here the
    splice's stand-in: a row of the new prompt's padded page) gives a
    slot re-admitted with a 1-token prompt wrong logits at its first
    decoded token, which reads column -1. (From a 2-token prompt on, a
    decode step reads no column below 0: the chunk's own mask covers
    the prompt.)"""
    model, params = tiny
    tokens = _tokens(20, seed=5)
    _stale_rows_read(monkeypatch)
    got = _decode_logits(model, params, tokens, prompt=1, fill=3.0)
    want = ref_logits(tokens)[1:]
    assert _rel(got[:1], want[:1]) > BF16_LIMIT
