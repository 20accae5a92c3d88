"""The ``pangu_ultra_moe`` family against its plain reference
(``perf/reference/pangu_ultra_moe.py``) at the tiny preset on the CPU,
seeded random weights: a chip's SHARE of the routed experts (the router
scores all, the top-k is over all, the held ones are computed and the
shares of all chips add up to the uncut layer), sandwich norms, latent
attention in both forms over the paged latent cache, what the engine
refuses for the family, and planted faults that the comparison has to
catch.

Tolerances are shares of the reference logits' standard deviation, as
in ``tests/test_xing4.py`` (``_rel``): float32 program and reference do
the same arithmetic in another order (true program 1.5e-6 to 1.8e-6,
limit 2e-5); bfloat16 weights and matmul inputs, three layers deep,
read under 0.05. The planted faults are in the share's routing, where a
wrong implementation would still "run": each reads far above both.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.reference import pangu_ultra_moe as reference
from pytorch_multiprocessing_distributed_tpu import models
from pytorch_multiprocessing_distributed_tpu.inference.generate import (
    generate, serving_family)
from pytorch_multiprocessing_distributed_tpu.models import latent
from pytorch_multiprocessing_distributed_tpu.ops.moe import (
    dropless_experts, route_sigmoid_topk)
from pytorch_multiprocessing_distributed_tpu.runtime.scope import scoped
from pytorch_multiprocessing_distributed_tpu.serving import (
    PagePool, ServingEngine, init_params)

F32_LIMIT = 2e-5
BF16_LIMIT = 0.05
VOCAB = 211
# the tiny model routes over 16 experts at top-4; this chip holds four
SHARE = dict(experts_held=4, expert_offset=8)


def _config(model) -> dict:
    """The published key names for a model's sizes: what the reference
    is configured from (the experts held and the router's width it
    reads off the weights)."""
    return {
        "num_hidden_layers": model.num_layers,
        "rms_norm_eps": model.rms_eps,
        "num_attention_heads": model.num_heads,
        "qk_nope_head_dim": model.qk_nope_head_dim,
        "qk_rope_head_dim": model.qk_rope_head_dim,
        "v_head_dim": model.v_head_dim, "kv_lora_rank": model.kv_lora_rank,
        "num_experts_per_tok": model.moe_top_k,
        "routed_scaling_factor": model.routed_scale,
        "rope_theta": model.rope_theta,
        "expert_offset": model.expert_offset}


def _rel(got, want) -> float:
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(np.asarray(got, np.float32) - want))
                 / np.std(want))


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, size=n)


@pytest.fixture(scope="module")
def tiny():
    model = models.get_model("pangu_ultra_moe_tiny", dtype=jnp.float32,
                             **SHARE)
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


# --------------------------------------------------------- the forward

def test_registry_and_published_sizes():
    """The stage the benchmark serves: one dense and four expert
    layers, 16 of 256 experts, an eighth of the vocabulary: ISSUE 33's
    4.92 B parameters, 9.85 GB as served."""
    model = models.get_model(
        "pangu_ultra_moe_718b", dtype=jnp.bfloat16, num_layers=5,
        first_k_dense=1, experts_held=16, vocab_size=19200)
    assert serving_family(model).name == "pangu_ultra_moe"
    assert (model.hidden_size, model.num_heads, model.q_lora_rank,
            model.kv_lora_rank) == (7680, 128, 1536, 512)
    assert (model.n_experts, model.n_held, model.moe_top_k, model.moe_dim,
            model.mlp_dim) == (256, 16, 8, 2048, 18432)
    assert model.rope_cache_dim == 128 and model.n_moe_layers == 4
    assert model.yarn is None
    assert model.softmax_scale() == pytest.approx(192 ** -0.5)
    shapes = jax.eval_shape(lambda: model._init(jax.random.PRNGKey(0)))
    leaves = jax.tree.leaves(shapes)
    count = sum(int(np.prod(s.shape)) for s in leaves)
    held = sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in leaves)
    assert abs(count - 4.9189e9) < 1e6         # ISSUE 33's own count
    assert abs(held - 9.854e9) < 2e6
    moe = shapes["layer_1"]["moe"]
    assert moe["router"].shape == (7680, 256)
    assert moe["router"].dtype == jnp.float32 and "e_bias" not in moe
    assert moe["w_gate"].shape == (16, 7680, 2048)
    assert moe["w_gate"].dtype == jnp.bfloat16
    assert shapes["head"]["kernel"].shape == (7680, 19200)
    # the registry's default is the published model, whole
    whole = models.get_model("pangu_ultra_moe_718b")
    assert (whole.num_layers, whole.first_k_dense, whole.n_held,
            whole.vocab_size) == (61, 3, 256, 153600)


@pytest.mark.parametrize("share", [
    {}, SHARE, dict(experts_held=4, expert_offset=0),
    dict(experts_held=4, expert_offset=12)],
    ids=["every-expert", "experts-8-11", "experts-0-3", "experts-12-15"])
def test_whole_prompt_prefill_equals_the_reference(share):
    model = models.get_model("pangu_ultra_moe_tiny", dtype=jnp.float32,
                             **share)
    params = init_params(model, 0)
    tokens = _tokens(96)
    want = reference.make_logits_fn(_config(model))(params,
                                                    jnp.asarray(tokens))
    assert _rel(_prefill_logits(model, params, tokens), want) < F32_LIMIT


def _paged(pref, page_size):
    """A standalone cache ``[L, 1, W, .]`` as a page pool behind an
    identity table (page 0 is scratch)."""
    l, _, w, r = pref.shape
    pages = pref.reshape(l, w // page_size, page_size, r)
    pool = jnp.concatenate([jnp.zeros_like(pages[:, :1]), pages], axis=1)
    return pool, jnp.arange(1, w // page_size + 1)[None]


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_absorbed_decode_equals_decompressed_prefill(tiny, impl):
    """One decode step over the paged latent cache (absorbed, through
    the kernel in interpret mode or its XLA form) gives the logits the
    decompressed prefill gives for the same position, and returns the
    share's load: held counts, then the assignments routed elsewhere."""
    model, params = tiny
    family = model.serving_family
    tokens = _tokens(41, seed=5)
    want = _prefill_logits(model, params, tokens)[-1]
    width = 48
    shape = (model.num_layers, 1, width)
    rows = family.cache_rows(model)
    padded = np.zeros((1, width), np.int32)
    padded[0, :40] = tokens[:40]
    _, pref, unused = family.chunk(
        model, params, jnp.zeros(shape + rows[0][1]),
        jnp.zeros(shape + rows[1][1]), jnp.asarray(padded), jnp.int32(0))
    pages, table = _paged(pref, 8)
    x, pages, _, load = family.decode_step(
        model, params, pages, _paged(unused, 8)[0], jnp.array([40]),
        jnp.asarray(tokens[40:41]), window=width, attn_impl=impl,
        page_table=table, page_size=8)
    got = np.asarray(family.logits(model, params, x)[0, 0])
    assert _rel(got, want) < F32_LIMIT
    assert load.shape == family.aux_shape(model) == (2, 4 + 1)
    # dropless: held + elsewhere = token x top-k in every expert layer
    assert np.asarray(load).sum(axis=1).tolist() == [model.moe_top_k] * 2


def _serve(model, params, requests, **kw):
    kw.setdefault("max_slots", 3)
    engine = ServingEngine(model, params, s_max=256,
                           kv_layout="paged", page_size=8, **kw)
    out = [engine.submit(list(p), n) for p, n in requests]
    while engine.in_flight:
        engine.step()
    return engine, out


def _gaps(ref_fn, params, request):
    """Per emitted position: the reference's largest logit minus its
    logit for the emitted token, in reference standard deviations."""
    stream = np.array(list(request.prompt) + list(request.tokens))
    logits = np.asarray(ref_fn(params, jnp.asarray(stream)))
    first = len(request.prompt) - 1
    rows = logits[first:len(stream) - 1]
    picked = rows[np.arange(len(rows)), stream[first + 1:]]
    return (rows.max(axis=1) - picked) / logits.std()


@pytest.mark.parametrize("chunk", [16, None],
                         ids=["chunked", "whole-prompt"])
def test_engine_prefill_then_paged_decode_float32(tiny, chunk):
    """Through ServingEngine, PagePool and the scheduler (the pipelined
    step, slots handed on): every emitted token is the reference's own
    argmax at its position, and the engine's meters carry the share."""
    model, params = tiny
    prompts = [(_tokens(70, 1), 20), (_tokens(33, 2), 12),
               (_tokens(50, 3), 9), (_tokens(21, 4), 15),
               (_tokens(17, 5), 8)]
    with scoped() as scope:
        engine, served = _serve(model, params, prompts, prefill_chunk=chunk)
    ref_fn = reference.make_logits_fn(_config(model))
    for request, (_, n) in zip(served, prompts):
        assert len(request.tokens) == n
        assert _gaps(ref_fn, params, request).max() == 0.0
    snap = engine.metrics.snapshot()
    # the load came back in the token blocks' own readbacks ...
    assert snap["decode_host_syncs"] == snap["decode_dispatches"]
    # ... and is dropless: held + elsewhere = rows x top-k x layers
    assert (snap["moe_assignments"] + snap["moe_assignments_elsewhere"]
            == snap["decode_dispatches"] * 3 * model.moe_top_k
            * model.n_moe_layers)
    assert 0 < snap["moe_assignments"] < snap["moe_assignments_elsewhere"]
    assert snap["moe_held_share"] == pytest.approx(
        snap["moe_assignments"] / (snap["moe_assignments"]
                                   + snap["moe_assignments_elsewhere"]))
    assert snap["moe_load_max_over_mean"] >= 1.0
    dispatches = [e for e in scope.events() if e.name == "decode.dispatch"]
    assert len(dispatches) == snap["decode_dispatches"]
    assert all(e.attrs["experts_held"] == 4 for e in dispatches)
    assert engine.in_flight == 0 and engine.pool.pages_in_use == 0


def test_engine_bfloat16_within_its_tolerance():
    """bfloat16 weights and matmuls against the float32 reference of
    the SAME (bfloat16-valued) weights: prefill logits and every
    emitted token within BF16_LIMIT."""
    model = models.get_model("pangu_ultra_moe_tiny", dtype=jnp.bfloat16,
                             **SHARE)
    params = init_params(model, 0)
    assert params["layer_0"]["attn"]["wo"].dtype == jnp.bfloat16
    assert params["layer_1"]["moe"]["router"].dtype == jnp.float32
    ref_fn = reference.make_logits_fn(_config(model))
    tokens = _tokens(96)
    got = _prefill_logits(model, params, tokens)
    assert _rel(got, ref_fn(params, jnp.asarray(tokens))) < BF16_LIMIT
    _, served = _serve(model, params, [(_tokens(70, 1), 20),
                                       (_tokens(33, 2), 12)],
                       prefill_chunk=16)
    for request in served:
        assert _gaps(ref_fn, params, request).max() < BF16_LIMIT


# ------------------------------------------------------------ the share

def _layer(seed=1, t=48, d=16, f=24, e=16):
    rng = np.random.default_rng(seed)

    def mat(*shape, scale=.3):
        return jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)

    return (mat(t, d, scale=1.0),
            {"router": mat(d, e, scale=1.0), "w_gate": mat(e, d, f),
             "w_up": mat(e, d, f), "w_down": mat(e, f, d),
             "shared": {"w_gate": mat(d, f), "w_up": mat(d, f),
                        "w_down": mat(f, d)}})


def _share_of(p, offset, held):
    return {**p, **{name: p[name][offset:offset + held]
                    for name in ("w_gate", "w_up", "w_down")}}


@pytest.mark.parametrize("held", [4, 8, 16])
@pytest.mark.parametrize("side", ["program", "reference"])
def test_the_shares_add_up_to_the_uncut_expert_layer(side, held):
    """The guide's share test: the parts that all ``n_experts / held``
    shares give, with the shared expert (which every chip computes
    alike) counted once, add up to the uncut reference's expert layer;
    and each share is dropless: held + elsewhere = T x k."""
    x, p = _layer()
    t, e, k = x.shape[0], 16, 4
    hp = {"top_k": k, "routed_scale": 2.5}
    want = reference.experts(x, p, {**hp, "offset": 0})
    shared = reference.gated(x, p["shared"]["w_gate"], p["shared"]["w_up"],
                             p["shared"]["w_down"], {})
    chosen, weights = route_sigmoid_topk(x, p["router"], None, k, 2.5)
    total, held_counts = shared, []
    for offset in range(0, e, held):
        mine = _share_of(p, offset, held)
        if side == "program":
            part, counts, elsewhere = dropless_experts(
                x, chosen, weights, mine["w_gate"], mine["w_up"],
                mine["w_down"], n_experts=e, offset=offset)
            assert int(counts.sum()) + int(elsewhere) == t * k
            held_counts.append(np.asarray(counts))
        else:
            part = reference.experts(x, mine, {**hp, "offset": offset}
                                     ) - shared
        total = total + part
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    if side == "program":
        # every assignment was computed by exactly one share
        every = np.concatenate(held_counts)
        assert every.sum() == t * k
        assert (every == np.bincount(np.asarray(chosen).ravel(),
                                     minlength=e)).all()


def _dropless_experts_of_pr_29(x, chosen, weights, w_gate, w_up, w_down):
    """``ops/moe.py::dropless_experts`` as it stood before a chip could
    hold a share (every expert held), kept word for word as the
    reference of the case ``held == n_experts``."""
    t, k = chosen.shape
    n_experts = w_gate.shape[0]
    flat = chosen.reshape(t * k)
    order = jnp.argsort(flat, stable=True)
    counts = jnp.zeros((n_experts,), jnp.int32).at[flat].add(1)
    rows = jnp.take(x, order // k, axis=0)
    gate = jax.lax.ragged_dot(rows, w_gate, counts,
                              preferred_element_type=jnp.float32)
    up = jax.lax.ragged_dot(rows, w_up, counts,
                            preferred_element_type=jnp.float32)
    hidden = (jax.nn.silu(gate) * up).astype(x.dtype)
    out = jax.lax.ragged_dot(hidden, w_down, counts,
                             preferred_element_type=jnp.float32)
    out = out * jnp.take(weights.reshape(t * k), order)[:, None]
    y = jnp.zeros_like(out).at[order].set(out)
    return jnp.sum(y.reshape(t, k, -1), axis=1), counts


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_every_expert_held_is_the_layer_xing4_ran_before(dtype):
    """``held == n_experts`` on ``xing4_tiny``'s own weights: the
    values and the counts of the layer as PR 29 wrote it, bit for bit,
    and nothing routed elsewhere."""
    model = models.get_model("xing4_tiny", dtype=dtype)
    moe = init_params(model, 0)["layer_1"]["moe"]
    x = jnp.asarray(np.random.default_rng(3).normal(size=(40, 64)),
                    jnp.float32)
    chosen, weights = route_sigmoid_topk(x, moe["router"], moe["e_bias"],
                                         model.moe_top_k, model.routed_scale)
    args = (x.astype(dtype), chosen, weights, moe["w_gate"], moe["w_up"],
            moe["w_down"])
    y, counts, elsewhere = dropless_experts(*args)
    want_y, want_counts = _dropless_experts_of_pr_29(*args)
    assert (np.asarray(y) == np.asarray(want_y)).all()
    assert (np.asarray(counts) == np.asarray(want_counts)).all()
    assert int(elsewhere) == 0
    assert int(counts.sum()) == 40 * model.moe_top_k


@pytest.mark.parametrize("layout", ["spread", "all-to-one-held-expert",
                                    "all-routed-elsewhere"])
def test_a_share_drops_nothing_among_the_held(layout):
    """With every token sent to ONE held expert (what a capacity would
    drop) nothing is dropped; with every token sent to experts held
    elsewhere the share adds exactly nothing, whatever the grouped
    matmul leaves in rows that lie in no group."""
    x, p = _layer(seed=2)
    t, e, k, offset, held = x.shape[0], 16, 2, 4, 4
    bias = jnp.zeros((e,), jnp.float32)
    if layout == "all-to-one-held-expert":
        bias = bias.at[6].set(10.0)
    elif layout == "all-routed-elsewhere":
        bias = bias.at[jnp.array([0, 13])].set(10.0)
    chosen, weights = route_sigmoid_topk(x, p["router"], bias, k, 2.5)
    mine = _share_of(p, offset, held)
    got, counts, elsewhere = dropless_experts(
        x, chosen, weights, mine["w_gate"], mine["w_up"], mine["w_down"],
        n_experts=e, offset=offset)
    dense = jnp.sum(jax.nn.one_hot(chosen, e) * weights[..., None], axis=1)
    want = sum(dense[:, offset + i, None] * reference.gated(
        x, mine["w_gate"][i], mine["w_up"][i], mine["w_down"][i], {})
        for i in range(held))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    assert int(counts.sum()) + int(elsewhere) == t * k
    if layout == "all-to-one-held-expert":
        assert int(counts[2]) == t             # every token, none dropped
    if layout == "all-routed-elsewhere":
        assert int(elsewhere) == t * k
        assert float(jnp.abs(got).max()) == 0.0
    with pytest.raises(ValueError, match="not among the 16"):
        dropless_experts(x, chosen, weights, mine["w_gate"], mine["w_up"],
                         mine["w_down"], n_experts=e, offset=13)


# ----------------------------------------------------- pool and refusals

def test_page_kv_bytes_equals_the_allocation(tiny):
    model, _ = tiny
    pool = PagePool(model, 3, 64, page_size=8)
    assert pool.k_pages.shape == (3, 25, 8, model.kv_lora_rank + 128)
    assert pool.v_pages.shape == (3, 25, 8, 0)
    assert (PagePool.page_kv_bytes(model, 8) * pool.num_pages
            == pool.k_pages.nbytes + pool.v_pages.nbytes)


@pytest.mark.parametrize("options, named", [
    (dict(kv_dtype="int8"), "kv_dtype=int8 is not supported for the "
                            "pangu_ultra_moe"),
    (dict(draft_k=2), "draft_k is not supported for the pangu_ultra_moe"),
    (dict(prefix_cache=4), "prefix_cache is not supported for the "
                           "pangu_ultra_moe"),
    (dict(mesh=True), "mesh is not supported for the pangu_ultra_moe"),
], ids=["kv_dtype=int8", "draft_k", "prefix_cache", "mesh"])
def test_engine_refuses_by_name_what_the_family_lacks(tiny, options, named):
    from jax.sharding import Mesh

    model, params = tiny
    if "mesh" in options:
        options = dict(mesh=Mesh(np.array(jax.devices()[:2]).reshape(1, 2),
                                 ("data", "model")))
    with pytest.raises(NotImplementedError) as e:
        ServingEngine(model, params, max_slots=2, s_max=64, page_size=8,
                      **options)
    assert named in str(e.value)


def test_generate_is_refused_by_name(tiny):
    model, params = tiny
    with pytest.raises(NotImplementedError, match="pangu_ultra_moe"):
        generate(model, params, jnp.zeros((1, 4), jnp.int32),
                 max_new_tokens=2)


# ------------------------------------------------------- planted faults

def _top_k_over_the_held_only(model, monkeypatch):
    """The router cut to the experts held: a layer that holds a share
    and routes over its own experts, not over all."""
    inner = latent.route_sigmoid_topk

    def route(x, router, e_bias, top_k, scale):
        lo = model.expert_offset
        chosen, weights = inner(x, router[:, lo:lo + model.n_held], e_bias,
                                top_k, scale)
        return chosen + lo, weights

    monkeypatch.setattr(latent, "route_sigmoid_topk", route)
    return model


def _weights_normalised_over_the_held_only(model, monkeypatch):
    inner = latent.route_sigmoid_topk

    def route(x, router, e_bias, top_k, scale):
        chosen, weights = inner(x, router, e_bias, top_k, scale)
        lo = model.expert_offset
        here = (chosen >= lo) & (chosen < lo + model.n_held)
        mine = jnp.where(here, weights, 0.0)
        return chosen, mine / (mine.sum(-1, keepdims=True) + 1e-20) * scale

    monkeypatch.setattr(latent, "route_sigmoid_topk", route)
    return model


def _another_chips_experts(model, monkeypatch):
    return dataclasses.replace(model, expert_offset=model.expert_offset - 4)


def _post_norms_left_out(model, monkeypatch):
    inner = latent._rms
    family = type(model.serving_family)

    def residual(self, model, x, layer, which, sublayer):
        y, aux = sublayer(inner(x, layer[f"{which}_norm"]["scale"],
                                model.rms_eps))
        return x + y, aux

    monkeypatch.setattr(family, "residual", residual)
    return model


def _rotary_part_left_out_of_the_cache(model, monkeypatch):
    inner = latent._qkv

    def qkv(h, p, positions, m):
        q_nope, q_rope, row = inner(h, p, positions, m)
        return q_nope, q_rope, row.at[:, m.kv_lora_rank:].set(0)

    monkeypatch.setattr(latent, "_qkv", qkv)
    return model


@pytest.mark.parametrize("plant", [
    _top_k_over_the_held_only, _weights_normalised_over_the_held_only,
    _another_chips_experts, _post_norms_left_out,
    _rotary_part_left_out_of_the_cache,
], ids=["top-k-over-the-held-only", "weights-normalised-over-the-held-only",
        "another-chips-experts", "post-norms-left-out",
        "rotary-part-left-out-of-the-cache"])
def test_planted_fault_exceeds_the_limit(tiny, ref_logits, monkeypatch,
                                         plant):
    """Each fault, planted in the float32 program from outside it,
    reads above the bfloat16 limit, which the true program passes a
    thousand times over."""
    model, params = tiny
    tokens = _tokens(320)
    want = ref_logits(tokens)
    assert _rel(_prefill_logits(model, params, tokens), want) < F32_LIMIT
    faulty = plant(model, monkeypatch)
    assert _rel(_prefill_logits(faulty, params, tokens), want) > BF16_LIMIT
