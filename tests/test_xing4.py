"""The ``xing4_0`` family against its plain reference
(``perf/reference/xing4_0.py``) at the tiny preset on the CPU, seeded
random weights: latent attention in both forms over the paged latent
cache, dropless sigmoid-routed experts, the doubly-stochastic stream
mixers, YaRN positions, what the engine refuses for the family, and
planted faults that the comparison has to catch.

Every tolerance is a share of the reference logits' standard deviation
(``_rel``), so it reads the same at any width:

- float32 (``F32_LIMIT``): program and reference do the same arithmetic
  in another order (absorbed against decompressed attention, sorted
  against masked experts); the true program reads 1.1e-6 to 1.5e-6, the
  limit 2e-5 leaves room for the CPU's summation order and is a
  thousand times below the smallest planted fault.
- bfloat16 (``BF16_LIMIT``): weights and matmul inputs rounded to 8
  bits of mantissa, three layers deep; the true program reads 0.013 of
  a standard deviation, the limit is 0.05. Planted in the float32
  program over 320 tokens, top-k minus one reads 0.60, the rotary part
  left out of the cache 0.27 and a bfloat16 router 0.27 (one flipped
  choice is enough), all above it; one Sinkhorn iteration in place of
  twenty reads 0.021 (the first pass already brings a 3 x 3 matrix near
  the manifold): a thousand times the float32 limit it is held to.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.reference import xing4_0 as reference
from pytorch_multiprocessing_distributed_tpu import models
from pytorch_multiprocessing_distributed_tpu.inference.generate import (
    GPT_SERVING, generate, serving_family)
from pytorch_multiprocessing_distributed_tpu.models import latent, xing4
from pytorch_multiprocessing_distributed_tpu.ops.moe import (
    dropless_experts, route_sigmoid_topk)
from pytorch_multiprocessing_distributed_tpu.serving import (
    PagePool, ServingEngine, init_params)

F32_LIMIT = 2e-5
BF16_LIMIT = 0.05
VOCAB = 211


def _config(model) -> dict:
    """The published key names for a model's sizes: what the reference
    is configured from."""
    factor, orig, fast, slow, mscale, mscale_all = model.yarn
    return {
        "num_hidden_layers": model.num_layers, "hc_mult": model.hc_mult,
        "hc_sinkhorn_iters": model.hc_iters, "hc_eps": model.hc_eps,
        "mhc_h_res_clamp_min": model.hc_clamp[0],
        "mhc_h_res_clamp_max": model.hc_clamp[1],
        "rms_norm_eps": model.rms_eps,
        "num_attention_heads": model.num_heads,
        "qk_nope_head_dim": model.qk_nope_head_dim,
        "qk_rope_head_dim": model.qk_rope_head_dim,
        "v_head_dim": model.v_head_dim, "kv_lora_rank": model.kv_lora_rank,
        "num_experts_per_tok": model.moe_top_k,
        "routed_scaling_factor": model.routed_scale,
        "rope_theta": model.rope_theta,
        "rope_scaling": {
            "type": "yarn", "factor": factor,
            "original_max_position_embeddings": orig, "beta_fast": fast,
            "beta_slow": slow, "mscale": mscale,
            "mscale_all_dim": mscale_all}}


def _rel(got, want) -> float:
    """Largest logit difference as a share of the reference logits'
    standard deviation."""
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(np.asarray(got, np.float32) - want))
                 / np.std(want))


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, size=n)


@pytest.fixture(scope="module")
def tiny():
    model = models.get_model("xing4_tiny", dtype=jnp.float32)
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
    model = models.get_model("xing4_29b_a4b", num_layers=5, first_k_dense=1)
    assert serving_family(model).name == "xing4_0"
    assert (model.hidden_size, model.num_heads, model.q_lora_rank,
            model.kv_lora_rank) == (3584, 32, 768, 512)
    assert (model.n_experts, model.moe_top_k, model.moe_dim,
            model.mlp_dim) == (64, 4, 1024, 9216)
    assert model.rope_cache_dim == 128 and model.n_moe_layers == 4
    shapes = jax.eval_shape(lambda: model._init(jax.random.PRNGKey(0)))
    count = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert abs(count - 4.048e9) < 2e6          # ISSUE 29's own count
    assert shapes["layer_1"]["moe"]["w_gate"].dtype == model.dtype
    assert shapes["layer_1"]["moe"]["router"].dtype == jnp.float32
    assert serving_family(models.get_model("gpt_tiny")) is GPT_SERVING


def test_whole_prompt_prefill_equals_the_reference(tiny, ref_logits):
    model, params = tiny
    tokens = _tokens(96)
    assert _rel(_prefill_logits(model, params, tokens),
                ref_logits(tokens)) < F32_LIMIT


def test_yarn_tables_and_positions_past_the_original_length(ref_logits):
    """The published YaRN numbers give the reference's frequencies, and
    a sequence longer than the original 4,096 positions still equals
    the reference (tiny widths, the published rotary scaling)."""
    big = models.get_model("xing4_29b_a4b")
    scaling = _config(big)["rope_scaling"]
    inv, factor = big.rope_tables()
    want = reference.yarn_inv_freq(64, 10000.0, scaling)
    np.testing.assert_allclose(inv, np.asarray(want), rtol=1e-6)
    assert factor == 1.0
    assert abs(big.softmax_scale()
               - 192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2) < 1e-9
    # interpolated where a dimension turns less than once, original
    # where it turns more than 32 times over 4,096 positions
    plain = 1.0 / 10000.0 ** (np.arange(32) * 2.0 / 64)
    assert inv[0] == pytest.approx(plain[0]) and inv[-1] == pytest.approx(
        plain[-1] / 64)
    model = models.get_model("xing4_tiny", dtype=jnp.float32,
                             num_layers=2, yarn=big.yarn)
    params = init_params(model, 1)
    tokens = _tokens(4224, seed=3)
    want = reference.make_logits_fn(_config(model), block=128)(
        params, jnp.asarray(tokens))
    got = _prefill_logits(model, params, tokens)
    assert _rel(got[4000:], np.asarray(want)[4000:]) < F32_LIMIT


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
    decompressed prefill gives for the same position."""
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
    x, pages, _, counts = family.decode_step(
        model, params, pages, _paged(unused, 8)[0], jnp.array([40]),
        jnp.asarray(tokens[40:41]), window=width, attn_impl=impl,
        page_table=table, page_size=8)
    got = np.asarray(family.logits(model, params, x)[0, 0])
    assert _rel(got, want) < F32_LIMIT
    # dropless: every expert layer computed token x top-k assignments,
    # none of them routed elsewhere (the last column but one): all are
    # held, and all were given to the grouped matmuls (the last)
    assert counts.shape == (model.n_moe_layers, model.n_experts + 2)
    counts = np.asarray(counts)
    assert counts[:, :-2].sum(axis=1).tolist() == [model.moe_top_k] * 2
    assert counts[:, -2].tolist() == [0, 0]
    assert counts[:, -1].tolist() == [model.moe_top_k] * 2
    # the new token's row went through the table: latent, rotated key,
    # zeros beyond the rotary width
    rank, rope = model.kv_lora_rank, model.qk_rope_head_dim
    new = pages[:, 6, 0]
    assert float(jnp.abs(new[:, rank + rope:]).max()) == 0
    assert float(jnp.abs(new[:, :rank]).min(axis=1).max()) > 0
    assert float(jnp.abs(new[:, rank:rank + rope]).max()) > 0


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
    """Through ServingEngine, PagePool and the scheduler: every emitted
    token is the reference's own argmax at its position."""
    model, params = tiny
    prompts = [(_tokens(70, 1), 20), (_tokens(33, 2), 12),
               (_tokens(50, 3), 9), (_tokens(21, 4), 15)]
    engine, served = _serve(model, params, prompts, prefill_chunk=chunk)
    ref_fn = reference.make_logits_fn(_config(model))
    for request, (_, n) in zip(served, prompts):
        assert len(request.tokens) == n
        assert _gaps(ref_fn, params, request).max() == 0.0
    snap = engine.metrics.snapshot()
    # the expert counts came back in the token blocks' own readbacks
    assert snap["decode_host_syncs"] == snap["decode_dispatches"]
    assert snap["moe_assignments"] == (
        snap["decode_dispatches"] * 3 * model.moe_top_k
        * model.n_moe_layers)
    assert snap["moe_assignments_elsewhere"] == 0
    assert snap["moe_held_share"] == 1.0
    assert snap["moe_load_max_over_mean"] >= 1.0
    # every expert held: one rung, every row of every layer and block
    assert snap["moe_rows_given"] == snap["moe_assignments"]
    assert snap["moe_rows_given_over_held"] == 1.0
    assert snap["moe_full_width_share"] == 1.0


def test_engine_pipelined_step_reuses_slots_under_a_block(tiny):
    """The engine's one-block-deep pipeline is one rule for every
    family: five requests through two slots, chunked admission, every
    dispatch but the cold start's made before the previous block (and
    its expert counts) was read back, slots and latent pages handed on
    while a block still names the previous tenant — and every token is
    still the reference's own argmax."""
    model, params = tiny
    prompts = [(_tokens(40, 1), 9), (_tokens(33, 2), 14),
               (_tokens(21, 3), 1), (_tokens(50, 4), 6),
               (_tokens(17, 5), 8)]
    engine, served = _serve(model, params, prompts, max_slots=2,
                            prefill_chunk=16)
    ref_fn = reference.make_logits_fn(_config(model))
    for request, (_, n) in zip(served, prompts):
        assert len(request.tokens) == n
        assert _gaps(ref_fn, params, request).max() == 0.0
    snap = engine.metrics.snapshot()
    assert snap["overlapped_dispatches"] == snap["decode_dispatches"] - 1
    assert snap["decode_host_syncs"] == snap["decode_dispatches"]
    assert engine.in_flight == 0 and not engine._blocks
    assert engine.pool.pages_in_use == 0


def test_engine_bfloat16_within_its_tolerance(ref_logits):
    """bfloat16 weights and matmuls against the float32 reference of
    the SAME (bfloat16-valued) weights: prefill logits and every
    emitted token within BF16_LIMIT."""
    model = models.get_model("xing4_tiny", dtype=jnp.bfloat16)
    params = init_params(model, 0)
    assert params["layer_0"]["attn"]["wo"].dtype == jnp.bfloat16
    assert params["layer_1"]["moe"]["e_bias"].dtype == jnp.float32
    ref_fn = reference.make_logits_fn(_config(model))
    tokens = _tokens(96)
    got = _prefill_logits(model, params, tokens)
    assert _rel(got, ref_fn(params, jnp.asarray(tokens))) < BF16_LIMIT
    _, served = _serve(model, params, [(_tokens(70, 1), 20),
                                       (_tokens(33, 2), 12)],
                       prefill_chunk=16)
    for request in served:
        assert _gaps(ref_fn, params, request).max() < BF16_LIMIT


# ------------------------------------------------------------- experts

def test_selection_by_biased_scores_weights_from_the_scores():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(40, 16)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
    bias = jnp.asarray(rng.uniform(-1, 1, size=(8,)), jnp.float32)
    chosen, weights = route_sigmoid_topk(x, router, bias, 3, 2.0)
    scores = np.asarray(jax.nn.sigmoid(x @ router))
    want = np.argsort(-(scores + np.asarray(bias)), axis=1)[:, :3]
    assert (np.sort(np.asarray(chosen), 1) == np.sort(want, 1)).all()
    picked = np.take_along_axis(scores, np.asarray(chosen), axis=1)
    np.testing.assert_allclose(
        np.asarray(weights), picked / picked.sum(1, keepdims=True) * 2.0,
        rtol=1e-5)
    # the bias moves the choice away from the plain top-3
    plain, _ = route_sigmoid_topk(x, router, jnp.zeros(8), 3, 2.0)
    assert (np.sort(np.asarray(plain), 1)
            != np.sort(np.asarray(chosen), 1)).any()


@pytest.mark.parametrize("layout", ["spread", "all-to-one-expert"])
def test_dropless_experts_equal_the_masked_loop(layout):
    """Sorted grouped matmuls against the reference's every-expert-on-
    every-token loop; with every token sent to ONE expert (what a
    capacity would drop) nothing is dropped."""
    rng = np.random.default_rng(1)
    t, d, f, e, k = 48, 16, 24, 8, 2
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    p = {"router": jnp.asarray(rng.normal(size=(d, e)), jnp.float32),
         "e_bias": jnp.zeros((e,), jnp.float32),
         "w_gate": jnp.asarray(rng.normal(size=(e, d, f)) * .3, jnp.float32),
         "w_up": jnp.asarray(rng.normal(size=(e, d, f)) * .3, jnp.float32),
         "w_down": jnp.asarray(rng.normal(size=(e, f, d)) * .3, jnp.float32)}
    if layout == "all-to-one-expert":
        p["e_bias"] = p["e_bias"].at[5].set(10.0)
    hp = {"top_k": k, "routed_scale": 2.0}
    chosen, weights = route_sigmoid_topk(x, p["router"], p["e_bias"], k, 2.0)
    got, counts, elsewhere, _ = dropless_experts(
        x, chosen, weights, p["w_gate"], p["w_up"], p["w_down"])
    shared = {name: jnp.zeros_like(p[name][0])
              for name in ("w_gate", "w_up", "w_down")}
    want = reference.experts(x, {**p, "shared": shared}, hp)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    # the reference's own-rows form (the benchmark's lengths), 8 rows a
    # round: 48 tokens to ONE expert take six rounds
    own = reference.experts_own_rows(x, {**p, "shared": shared}, hp, 8)
    np.testing.assert_allclose(np.asarray(own), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    assert int(counts.sum()) == t * k and int(elsewhere) == 0
    if layout == "all-to-one-expert":
        assert int(counts[5]) == t        # every token, none dropped


# -------------------------------------------------------------- mixers

def test_stream_mixer_is_doubly_stochastic_and_clamped(tiny):
    model, params = tiny
    x = jnp.asarray(np.random.default_rng(2).normal(
        size=(50, model.hc_mult, model.hidden_size)), jnp.float32)
    p = dict(params["layer_1"]["hc_ffn"])
    pre, post, res = xing4._mixer(x, p, model)
    assert float(jnp.abs(res.sum(-1) - 1).max()) < 1e-3
    assert float(jnp.abs(res.sum(-2) - 1).max()) < 1e-3
    assert 0 < float(pre.min()) and float(pre.max()) < 1
    assert 0 < float(post.min()) and float(post.max()) < 2
    want = reference.stream_mix(
        x, p, iters=model.hc_iters, hc_eps=model.hc_eps,
        clamp=model.hc_clamp, norm_eps=model.rms_eps)
    for a, b in zip((pre, post, res), want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    # the clamp holds at +-30: a bias of 1e4 would overflow exp without
    # it, and one of -1e4 would zero a row
    p["b_res"] = jnp.array([[1e4, 0, 0], [0, -1e4, 0], [0, 0, 0.]])
    _, _, res = xing4._mixer(x, p, model)
    assert bool(jnp.isfinite(res).all())
    assert float(jnp.abs(res.sum(-1) - 1).max()) < 1e-3
    no_pass = xing4._mixer(x, p, dataclasses.replace(model, hc_iters=0))[2]
    assert float(no_pass.max()) == pytest.approx(np.exp(30.0), rel=1e-5)


# ----------------------------------------------------- pool and refusals

def test_page_kv_bytes_equals_the_allocation(tiny):
    model, _ = tiny
    pool = PagePool(model, 3, 64, page_size=8)
    held = pool.k_pages.nbytes + pool.v_pages.nbytes
    # one row a token: latent + the rotary key padded to whole lanes;
    # the engine's second cache operand is a zero-width placeholder
    assert pool.k_pages.shape == (3, 25, 8, model.kv_lora_rank + 128)
    assert pool.v_pages.shape == (3, 25, 8, 0)
    assert PagePool.page_kv_bytes(model, 8) == 3 * 8 * (32 + 128) * 4
    assert PagePool.page_kv_bytes(model, 8) * pool.num_pages == held
    # the GPT family's pages follow the same rule, [L, P, ps, H * Dh]
    # (4 heads of 32 side by side), and keep their byte count
    gpt = models.get_model("gpt_tiny")
    pool = PagePool(gpt, 2, 32, page_size=8)
    assert pool.k_pages.shape == pool.v_pages.shape == (4, 9, 8, 4 * 32)
    assert (PagePool.page_kv_bytes(gpt, 8) * pool.num_pages
            == pool.k_pages.nbytes + pool.v_pages.nbytes)
    assert PagePool.page_kv_bytes(gpt, 8, "int8") == 2 * 4 * 4 * 8 * 36
    # int8: one scale a token and head beside the data's lanes
    pool = PagePool(gpt, 2, 32, page_size=8, kv_dtype="int8")
    assert pool.k_pages.data.shape == (4, 9, 8, 4 * 32)
    assert pool.k_pages.scale.shape == (4, 9, 8, 4)
    assert (PagePool.page_kv_bytes(gpt, 8, "int8") * pool.num_pages
            == pool.k_pages.nbytes + pool.v_pages.nbytes)


@pytest.mark.parametrize("options, error, named", [
    # the engine's own refusal, for any family: the dense pool is gone
    (dict(kv_layout="dense"), ValueError, "dense slot pool"),
    (dict(page_size=8, kv_dtype="int8"), NotImplementedError,
     "kv_dtype=int8 is not supported for the xing4_0"),
    (dict(page_size=8, draft_k=2), NotImplementedError,
     "draft_k is not supported for the xing4_0"),
    (dict(page_size=8, prefix_cache=4), NotImplementedError,
     "prefix_cache is not supported for the xing4_0"),
], ids=["kv_layout=dense", "kv_dtype=int8", "draft_k", "prefix_cache"])
def test_engine_refuses_by_name_what_the_family_lacks(tiny, options, error,
                                                      named):
    model, params = tiny
    with pytest.raises(error) as e:
        ServingEngine(model, params, max_slots=2, s_max=64, **options)
    assert named in str(e.value)


def test_tensor_parallel_and_generate_are_refused_by_name(tiny):
    from jax.sharding import Mesh

    model, params = tiny
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2),
                ("data", "model"))
    with pytest.raises(NotImplementedError, match="mesh.*xing4_0"):
        ServingEngine(model, params, max_slots=2, s_max=64, mesh=mesh,
                      kv_layout="paged", page_size=8)
    with pytest.raises(NotImplementedError, match="xing4_0"):
        generate(model, params, jnp.zeros((1, 4), jnp.int32),
                 max_new_tokens=2)


# ------------------------------------------------------- planted faults

def _top_k_minus_one(model, monkeypatch):
    return dataclasses.replace(model, moe_top_k=model.moe_top_k - 1)


def _one_sinkhorn_iteration(model, monkeypatch):
    return dataclasses.replace(model, hc_iters=1)


def _rotary_part_left_out_of_the_cache(model, monkeypatch):
    inner = latent._qkv

    def qkv(h, p, positions, m):
        q_nope, q_rope, row = inner(h, p, positions, m)
        return q_nope, q_rope, row.at[:, m.kv_lora_rank:].set(0)

    monkeypatch.setattr(latent, "_qkv", qkv)
    return model


def _bfloat16_router(model, monkeypatch):
    inner = latent.route_sigmoid_topk
    monkeypatch.setattr(
        latent, "route_sigmoid_topk",
        lambda x, router, *rest: inner(
            x.astype(jnp.bfloat16).astype(jnp.float32),
            router.astype(jnp.bfloat16).astype(jnp.float32), *rest))
    return model


@pytest.mark.parametrize("plant, above", [
    (_top_k_minus_one, BF16_LIMIT),
    (_one_sinkhorn_iteration, F32_LIMIT),
    (_rotary_part_left_out_of_the_cache, BF16_LIMIT),
    (_bfloat16_router, BF16_LIMIT),
], ids=["top-k-minus-one", "one-sinkhorn-iteration",
        "rotary-part-left-out-of-the-cache", "bfloat16-router"])
def test_planted_fault_exceeds_the_limit(tiny, ref_logits, monkeypatch,
                                         plant, above):
    """Each fault, planted in the float32 program from outside it,
    reads above the limit the true program passes (all but the
    Sinkhorn count above the bfloat16 limit too)."""
    model, params = tiny
    tokens = _tokens(320)
    want = ref_logits(tokens)
    assert _rel(_prefill_logits(model, params, tokens), want) < F32_LIMIT
    faulty = plant(model, monkeypatch)
    assert _rel(_prefill_logits(faulty, params, tokens), want) > above


def test_published_config_file_builds_the_registry_model():
    """perf/families/xing4_0.py holds the registry model to every size
    of perf/configs/xing4-29b-a4b.json, and refuses a drifted one."""
    import json
    import os

    from perf import families, harness

    path = os.path.join(harness.ROOT, "perf/configs/xing4-29b-a4b.json")
    with open(path) as f:
        config = json.load(f)
    family = families.load(config)
    model = family.build_model(config, "bfloat16", "cpu")
    assert (model.num_layers, model.first_k_dense) == (5, 1)
    assert family.kv_bytes_per_token(config) == 5760
    work = family.kernel_work(config, "mla_paged_decode_attention", {
        "context_lens": [1], "dtype": "bfloat16", "kv_dtype": "bfloat16"})
    assert work["ops"] == 69632 * 5
    with pytest.raises(harness.ManifestError, match="kv_lora_rank"):
        family.build_model({**config, "kv_lora_rank": 256}, "bfloat16", "cpu")
    with pytest.raises(harness.ManifestError, match="scoring_func"):
        family.build_model({**config, "scoring_func": "softmax"},
                           "bfloat16", "cpu")
    assert dataclasses.replace(model, num_layers=40, first_k_dense=2) == (
        models.get_model("xing4_29b_a4b", dtype=jnp.bfloat16))
