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
    dropless_experts, route_sigmoid_topk, row_ladder)
from pytorch_multiprocessing_distributed_tpu.runtime.scope import scoped
from pytorch_multiprocessing_distributed_tpu.serving import (
    PagePool, ServingEngine, init_params)
from pytorch_multiprocessing_distributed_tpu.utils.metrics import (
    ServingMetrics)

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
    share's load: held counts, the assignments routed elsewhere, then
    the rows the grouped matmuls were given."""
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
    assert load.shape == family.aux_shape(model) == (2, 4 + 2)
    # dropless: held + elsewhere = token x top-k in every expert layer,
    # and one token's four rows are the ladder's one rung
    load = np.asarray(load)
    assert load[:, :-1].sum(axis=1).tolist() == [model.moe_top_k] * 2
    assert load[:, -1].tolist() == [model.moe_top_k] * 2


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
            part, counts, elsewhere, _ = dropless_experts(
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
    y, counts, elsewhere, given = dropless_experts(*args)
    want_y, want_counts = _dropless_experts_of_pr_29(*args)
    assert (np.asarray(y) == np.asarray(want_y)).all()
    assert (np.asarray(counts) == np.asarray(want_counts)).all()
    assert int(elsewhere) == 0
    assert int(counts.sum()) == int(given) == 40 * model.moe_top_k


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
    got, counts, elsewhere, _ = dropless_experts(
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


# ----------------------------------------------------------- the ladder

def _dropless_experts_of_pr_33(x, chosen, weights, w_gate, w_up, w_down, *,
                               n_experts=None, offset: int = 0):
    """``ops/moe.py::dropless_experts`` as it stood before the ladder
    (every one of the ``T * k`` sorted rows given to the grouped
    matmuls), kept word for word as the reference of the laddered
    layer."""
    t, k = chosen.shape
    held = w_gate.shape[0]
    n_experts = held if n_experts is None else int(n_experts)
    if not 0 <= offset <= n_experts - held:
        raise ValueError(
            f"experts [{offset}, {offset + held}) are not among the "
            f"{n_experts} the router chooses from")
    flat = chosen.reshape(t * k)
    # this chip's experts become 0 .. held - 1, the others follow
    key = flat if offset == 0 else (flat - offset) % n_experts
    order = jnp.argsort(key, stable=True)                # [T*k]
    every = jnp.zeros((n_experts,), jnp.int32).at[key].add(1)
    counts, elsewhere = every[:held], jnp.sum(every[held:])
    rows = jnp.take(x, order // k, axis=0)               # [T*k, D]
    gate = jax.lax.ragged_dot(rows, w_gate, counts,
                              preferred_element_type=jnp.float32)
    up = jax.lax.ragged_dot(rows, w_up, counts,
                            preferred_element_type=jnp.float32)
    hidden = (jax.nn.silu(gate) * up).astype(x.dtype)
    out = jax.lax.ragged_dot(hidden, w_down, counts,
                             preferred_element_type=jnp.float32)
    out = out * jnp.take(weights.reshape(t * k), order)[:, None]
    if held < n_experts:
        # a row behind the last group is in no matmul: whatever the
        # grouped kernel left there, it adds nothing
        out = jnp.where((jnp.arange(t * k) < jnp.sum(counts))[:, None],
                        out, 0.0)
    # back to (token, choice) order: the inverse permutation is a
    # scatter of whole rows, then a sum over each token's k choices
    y = jnp.zeros_like(out).at[order].set(out)
    return jnp.sum(y.reshape(t, k, -1), axis=1), counts, elsewhere


def _routing(t, k, n_experts, offset, held, held_rows, seed=0):
    """``chosen [t, k]`` with exactly ``held_rows`` assignments to the
    experts ``[offset, offset + held)``, spread over them and over the
    tokens, and combine weights that sum to 2.5 a token."""
    rng = np.random.default_rng(seed)
    mine = offset + np.arange(held_rows) % held
    others = (offset + held + np.arange(t * k - held_rows)
              % max(n_experts - held, 1)) % n_experts
    chosen = rng.permutation(np.concatenate([mine, others]))
    weights = rng.random((t, k)) + 0.1
    return (jnp.asarray(chosen.reshape(t, k), jnp.int32),
            jnp.asarray(weights / weights.sum(1, keepdims=True) * 2.5,
                        jnp.float32))


# 1,024 assignments, 2 of 16 experts held: 128 expected, rungs 256, 512
# and 1,024
@pytest.mark.parametrize("layout, k, given", [
    ("spread", 2, 256), ("all-to-one-held-expert", 1, 1024),
    ("all-routed-elsewhere", 2, 256), ("total-at-the-first-rung", 2, 256),
    ("total-one-above-the-first-rung", 2, 512),
    ("total-at-the-second-rung", 2, 512),
    ("total-one-above-the-second-rung", 2, 1024)])
def test_the_laddered_layer_is_the_layer_of_pr_33(layout, k, given):
    """Whatever rung the counts choose, the layer over the first
    ``given`` sorted rows is the layer over all of them: values to the
    order of a float32 sum, the same counts, nothing dropped — every
    row to ONE held expert (the last rung: every row is held)
    included."""
    e, offset, held = 16, 4, 2
    t = 1024 // k
    x, p = _layer(seed=3, t=t)
    mine = _share_of(p, offset, held)
    assert row_ladder(t, k, held, e) == (256, 512, 1024)
    if layout in ("spread", "all-to-one-held-expert",
                  "all-routed-elsewhere"):
        bias = jnp.zeros((e,), jnp.float32)
        if layout == "all-to-one-held-expert":
            bias = bias.at[5].set(10.0)
        elif layout == "all-routed-elsewhere":
            bias = bias.at[jnp.array([0, 13])].set(10.0)
        chosen, weights = route_sigmoid_topk(x, p["router"], bias, k, 2.5)
    else:
        rows = {"total-at-the-first-rung": 256,
                "total-one-above-the-first-rung": 257,
                "total-at-the-second-rung": 512,
                "total-one-above-the-second-rung": 513}[layout]
        chosen, weights = _routing(t, k, e, offset, held, rows)
    args = (x, chosen, weights, mine["w_gate"], mine["w_up"],
            mine["w_down"])
    got, counts, elsewhere, rows_given = jax.jit(
        lambda *a: dropless_experts(*a, n_experts=e, offset=offset))(*args)
    want, want_counts, want_elsewhere = _dropless_experts_of_pr_33(
        *args, n_experts=e, offset=offset)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    assert (np.asarray(counts) == np.asarray(want_counts)).all()
    assert int(elsewhere) == int(want_elsewhere)
    assert int(counts.sum()) + int(elsewhere) == t * k
    assert int(rows_given) == given >= int(counts.sum())
    if layout == "all-to-one-held-expert":
        assert int(counts[1]) == t * k and float(jnp.abs(got).max()) > 0
    if layout == "all-routed-elsewhere":
        assert int(elsewhere) == t * k
        assert float(jnp.abs(got).max()) == 0.0


@pytest.mark.parametrize("t, k, held, e, rungs", [
    (128, 8, 16, 256, (128, 256, 1024)),      # the cell's decode step
    (1024, 8, 16, 256, (1024, 2048, 8192)),   # the cell's chunk
    (256, 4, 2, 16, (256, 512, 1024)),
    (48, 4, 4, 16, (128, 192)),               # the second rung reaches T*k
    (3, 4, 4, 16, (12,)),                     # the first one does
    (64, 4, 64, 64, (256,)),                  # every expert held
], ids=["decode-128x8-16of256", "chunk-1024x8-16of256", "256x4-2of16",
        "48x4-4of16", "3x4-4of16", "64x4-64of64"])
def test_the_rung_taken_is_the_smallest_that_holds_the_held_rows(
        t, k, held, e, rungs):
    """The ladder from static shapes: about twice the expected held
    rows in whole row tiles, twice that, and last ``T * k``; for totals
    of 0, a rung, one above it and ``T * k`` the layer takes the
    smallest rung that is ``>=`` the total (on the device: the function
    is jitted and the total is data)."""
    assert row_ladder(t, k, held, e) == rungs
    assert rungs[-1] == t * k and list(rungs) == sorted(set(rungs))
    assert rungs[0] >= min(t * k, 2 * t * k * held // e)
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(t, 8)), jnp.float32)
    w = [jnp.asarray(rng.normal(size=shape) * .3, jnp.float32)
         for shape in ((held, 8, 8), (held, 8, 8), (held, 8, 8))]
    layer = jax.jit(lambda *a: dropless_experts(*a, n_experts=e))
    totals = {0, t * k} | {b + d for b in rungs for d in (0, 1)
                           if b + d <= t * k}
    if held == e:
        totals = {t * k}           # nothing can be routed elsewhere
    for total in sorted(totals):
        chosen, weights = _routing(t, k, e, 0, held, total)
        _, counts, elsewhere, given = layer(x, chosen, weights, *w)
        assert int(counts.sum()) == total
        assert int(elsewhere) == t * k - total
        assert int(given) == min(b for b in rungs if b >= total)


def test_every_expert_held_traces_no_conditional():
    """``held == n_experts`` (the ``xing4`` family): one rung, the layer
    PR 29 wrote with no conditional in it; a share of the experts: ONE
    conditional, a branch a rung."""
    x, p = _layer(t=256)
    chosen, weights = route_sigmoid_topk(x, p["router"], None, 4, 2.5)

    def traced(held):
        mine = _share_of(p, 0, held)
        jaxpr = jax.make_jaxpr(lambda *a: dropless_experts(
            *a, n_experts=16))(x, chosen, weights, mine["w_gate"],
                               mine["w_up"], mine["w_down"])
        return [eqn for eqn in jaxpr.jaxpr.eqns
                if eqn.primitive.name == "cond"]

    assert traced(16) == []
    (switch,) = traced(2)
    assert len(switch.params["branches"]) == len(
        row_ladder(256, 4, 2, 16)) == 3


# ---------------------------------------------------------- the counter

def test_record_moe_splits_the_rows_given_off_the_load():
    """``[layers, held + 2]``: held counts, elsewhere, rows given. A
    layer given as many rows as it had assignments ran at full width."""
    metrics = ServingMetrics()
    assert metrics.snapshot()["moe_rows_given_over_held"] == 0.0
    assert metrics.snapshot()["moe_full_width_share"] == 0.0
    metrics.record_moe(np.array([[3, 1, 0, 4, 24, 16],
                                 [9, 8, 2, 1, 12, 32]]))
    metrics.record_moe(np.array([[2, 2, 2, 2, 24, 16],
                                 [0, 0, 0, 0, 32, 16]]))
    snap = metrics.snapshot()
    assert snap["moe_assignments"] == 8 + 20 + 8 + 0
    assert snap["moe_assignments_elsewhere"] == 24 + 12 + 24 + 32
    assert snap["moe_rows_given"] == 16 + 32 + 16 + 16
    assert snap["moe_rows_given_over_held"] == pytest.approx(80 / 36)
    # one of the four (layer, block) entries was given all 32 rows
    assert snap["moe_full_width_share"] == 0.25
    assert snap["moe_held_share"] == pytest.approx(36 / 128)
    # the busiest held expert over the mean, of the layers with a load
    assert snap["moe_load_max_over_mean"] == pytest.approx(
        ((4 / 2 + 9 / 5) / 2 + 1.0) / 2)


def test_engine_reports_the_rung_every_layer_and_block_took(tiny):
    """48 slots at top-4 are 192 rows a layer and step, 48 of them
    expected at the 4 of 16 experts held: rungs 128 and 192. Every
    (layer, block) was given a rung that holds its held rows, the
    counters add them up, and they rode in the token block's one
    read-back."""
    model, params = tiny
    rungs = row_ladder(48, model.moe_top_k, model.n_held, model.n_experts)
    assert rungs == (128, 192)
    engine = ServingEngine(model, params, max_slots=48, s_max=64,
                           page_size=8)
    blocks = []
    record = engine.metrics.record_moe
    engine.metrics.record_moe = lambda load: (blocks.append(load.copy()),
                                              record(load))[1]
    served = [engine.submit(list(_tokens(9 + i, i)), 6) for i in range(5)]
    while engine.in_flight:
        engine.step()
    assert all(len(request.tokens) == 6 for request in served)
    snap = engine.metrics.snapshot()
    assert len(blocks) == snap["decode_dispatches"] > 0
    for load in blocks:
        assert load.shape == (model.n_moe_layers, model.n_held + 2)
        held, elsewhere, given = (load[:, :-2].sum(axis=1), load[:, -2],
                                  load[:, -1])
        assert (held + elsewhere == 192).all()
        assert all(g in rungs for g in given) and (given >= held).all()
    assert snap["moe_rows_given"] == sum(b[:, -1].sum() for b in blocks)
    assert snap["moe_rows_given_over_held"] == pytest.approx(
        snap["moe_rows_given"] / snap["moe_assignments"])
    assert snap["moe_full_width_share"] == pytest.approx(
        np.mean([g == 192 for b in blocks for g in b[:, -1]]))
    # still ONE read-back a block: the counter is a column of it
    assert snap["decode_host_syncs"] == snap["decode_dispatches"]


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

    def route(x, router, e_bias, top_k, scale, eps=1e-20):
        lo = model.expert_offset
        chosen, weights = inner(x, router[:, lo:lo + model.n_held], e_bias,
                                top_k, scale, eps)
        return chosen + lo, weights

    monkeypatch.setattr(latent, "route_sigmoid_topk", route)
    return model


def _weights_normalised_over_the_held_only(model, monkeypatch):
    inner = latent.route_sigmoid_topk

    def route(x, router, e_bias, top_k, scale, eps=1e-20):
        chosen, weights = inner(x, router, e_bias, top_k, scale, eps)
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
