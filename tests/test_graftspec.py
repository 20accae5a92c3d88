"""graftspec: self-drafting speculative decoding fused into the
horizon scan (ISSUE 12).

Tier-1 slim matrix: the speculative engine's greedy streams
byte-identical to the non-speculative engine AND per-request
``generate()`` — paged + chunked admission, bucketed windows crossed
mid-stream, H > 1 with mid-horizon EOS, draft-model mode, fault
quarantine with spec armed — plus the drafter/scheduler units, the
host/device hash parity pin, loud rejection of sampled spec, the
committed costs.json bandwidth budgets (verify FLOPs ~(k+1)x at ~1x
bytes), and the ``make spec`` smoke body. The full cross-product
sweep and TP spec are slow-marked (``make test``).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_multiprocessing_distributed_tpu import models
from pytorch_multiprocessing_distributed_tpu.inference import generate
from pytorch_multiprocessing_distributed_tpu.inference.generate import (
    draft_bucket)
from pytorch_multiprocessing_distributed_tpu.runtime import faults
from pytorch_multiprocessing_distributed_tpu.serving import (
    DONE, FAILED, NgramDrafter, ServingEngine, init_params,
    ngram_bucket, pick_draft_k, pick_horizon)


def _tiny(**kw):
    return models.GPT(vocab_size=61, max_seq_len=64, hidden_size=32,
                      num_layers=2, num_heads=2, mlp_dim=64,
                      attn_impl="xla", **kw)


@pytest.fixture(scope="module")
def served():
    model = _tiny()
    params = init_params(model, 1)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, model.vocab_size, (n,)).tolist()
               for n in (3, 7, 12, 5, 9)]
    return model, params, prompts


def _ref_tail(model, params, prompt, n):
    out = generate(model, params, jnp.asarray(prompt)[None, :],
                   max_new_tokens=n)
    return np.asarray(out[0, -n:]).tolist()


def _spec(model, params, **kw):
    kw.setdefault("s_max", 32)
    kw.setdefault("min_bucket", 8)
    kw.setdefault("draft_k", 4)
    return ServingEngine(model, params, **kw)


# --------------------------------------------------------- equivalence

@pytest.mark.slow
def test_spec_paged_chunked_horizon_eos(served):
    """THE slim matrix pin: speculative decode over the paged engine
    with chunked admission, H=4 horizons, a bucket ladder crossed
    mid-stream, and a mid-horizon EOS — byte-identical to generate(),
    all pages returned, and re-serving makes zero fresh spec
    programs.

    Slow-marked (PR 14 tier-1 rebalance for the graftroute suite):
    the heaviest spec-matrix variant — the whole-prompt spec pins and
    the non-spec pins stay fast-marked; the full cross stays in
    `make test`."""
    model, params, prompts = served
    engine = _spec(model, params, max_slots=3, kv_layout="paged",
                   page_size=8, prefill_chunk=5, decode_horizon=4,
                   decode_buckets=(8, 32))
    got = engine.serve([(p, 8) for p in prompts])
    for r, p in zip(got, prompts):
        assert r.tokens == _ref_tail(model, params, p, 8), (
            f"prompt len {len(p)}")
    assert engine.pool.pages_in_use == 0
    assert engine.metrics.tokens_drafted > 0
    programs = engine.spec_programs
    # churn: same mix again — ladder closed, no leaks
    engine.serve([(p, 8) for p in prompts])
    assert engine.spec_programs == programs
    assert engine.pool.pages_in_use == 0

    # mid-horizon EOS: the finishing token is emitted, then freeze
    ref = _ref_tail(model, params, prompts[1], 8)
    engine.submit(prompts[1], 8, eos_id=int(ref[2]))
    (done,) = [r for r, _, d in engine.run() if d]
    assert done.finish_reason == "eos"
    assert done.tokens == ref[:3]


@pytest.mark.slow
def test_spec_bucket_boundary(served):
    """Spec across a fine bucket ladder (windows of 1, 2 and 4 pages
    of 8): the window pick must
    reserve k+1 read columns per pass (a verify query reads past its
    write frontier), so streams that cross bucket boundaries stay
    token-exact."""
    model, params, prompts = served
    engine = _spec(model, params, max_slots=2, decode_horizon=4,
                   decode_buckets=(8, 16, 32))
    got = engine.serve([(p, 10) for p in prompts[:3]])
    for r, p in zip(got, prompts):
        assert r.tokens == _ref_tail(model, params, p, 10)


def test_spec_draft_model_mode(served):
    """Draft-model speculation (the target as its own draft — the
    structural-acceptance smoke): token-exact, and acceptance is high
    by construction (the draft's greedy IS the target's greedy)."""
    model, params, prompts = served
    engine = _spec(model, params, max_slots=2, decode_horizon=4,
                   draft_model=model, draft_params=params)
    got = engine.serve([(p, 6) for p in prompts[:2]])
    for r, p in zip(got, prompts):
        assert r.tokens == _ref_tail(model, params, p, 6)
    snap = engine.metrics.snapshot()
    assert snap["spec_accept_rate"] > 0.5
    assert snap["spec_accepted_per_target_step"] > 1.0


def test_spec_fault_quarantine_with_spec_armed(served):
    """Acceptance: a persistent prefill fault with spec ARMED
    quarantines exactly the poisoned request; every other stream is
    byte-identical to the fault-free run (the spec path's extra
    admission work — drafter rebuild — rides the same quarantine
    discipline)."""
    model, params, prompts = served
    engine = _spec(model, params, max_slots=2, retry_backoff_s=0.0,
                   dispatch_retries=2, decode_horizon=4)
    plan = faults.FaultPlan(
        [faults.FaultRule("serving.prefill", "error", times=2)])
    faults.arm(plan)
    try:
        reqs = [engine.submit(p, 4) for p in prompts[:4]]
        for _ in engine.run():
            pass
    finally:
        faults.disarm()
    assert plan.triggered() == 2
    assert reqs[0].state == FAILED
    assert isinstance(reqs[0].error, faults.FaultInjected)
    assert [r.state for r in reqs[1:]] == [DONE] * 3
    for r, p in zip(reqs[1:], prompts[1:4]):
        assert r.tokens == _ref_tail(model, params, p, 4)
    # the engine keeps serving, speculatively, after the quarantine
    (again,) = engine.serve([(prompts[0], 4)])
    assert again.tokens == _ref_tail(model, params, prompts[0], 4)


# ------------------------------------------------------- units / guards

def test_hash_parity_host_device():
    """ngram_bucket (numpy, drafter) == draft_bucket (jnp, scan) —
    the one-formula pin the table lookup rests on."""
    toks = np.array([0, 1, 7, 60, 255, 50000], np.int32)
    host = ngram_bucket(toks, 64)
    dev = np.asarray(draft_bucket(jnp.asarray(toks), 64))
    np.testing.assert_array_equal(host, dev)


def test_ngram_drafter_unit():
    drafter = NgramDrafter(2, 3, n_buckets=16)
    hist = [5, 9, 5, 7, 2]
    row = drafter.build_row(hist)
    b5 = int(ngram_bucket([5], 16)[0])
    # most recent occurrence of 5 (index 2) wins: drafts 7, 2
    assert row[b5].tolist() == [7, 2, -1]
    drafter.note_history(0, hist)
    t1 = drafter.device_table()
    ups = drafter.uploads
    # unchanged history -> no re-upload (the lazy-dirty discipline)
    drafter.note_history(0, hist)
    assert drafter.device_table() is t1 and drafter.uploads == ups
    drafter.note_history(0, hist + [9])
    assert drafter.uploads == ups  # dirty, but upload is lazy
    assert drafter.device_table() is not t1
    assert drafter.uploads == ups + 1


def test_ngram_drafter_scan_window_bounded():
    """The rebuild walks a bounded recency window (early-exit once
    every bucket is owned) — an s_max-length history costs O(window),
    and positions older than the window never claim a bucket."""
    drafter = NgramDrafter(1, 2, n_buckets=16, scan_window=4)
    # token 3 occurs ONLY outside the 4-position recency window
    # (buckets mod 16 are identity for these small ids — no collision)
    hist = [3, 9] + [1, 2] * 6
    row = drafter.build_row(hist)
    b3 = int(ngram_bucket([3], 16)[0])
    b1 = int(ngram_bucket([1], 16)[0])
    b2 = int(ngram_bucket([2], 16)[0])
    assert row[b3].tolist() == [-1, -1]  # beyond the window: unseen
    # most recent occurrence wins: 1 at the penultimate position has
    # ONE successor left; 2's latest context position drafts [1, 2]
    assert row[b1].tolist() == [2, -1]
    assert row[b2].tolist() == [1, 2]


def test_probe_rearms_collapsed_spec(served):
    """Regression: the re-probe counter advances on COLLAPSED picks
    too — after low acceptance disarms speculation, a later pick must
    still come due as a probe (else spec is off for the engine's
    lifetime)."""
    model, params, _ = served
    engine = _spec(model, params, max_slots=1)
    engine._accept_ema = 0.0  # sustained-low-acceptance collapse
    picks = [engine._pick_k() for _ in range(33)]
    assert 0 in picks, "collapse must actually disarm"
    assert picks.count(engine.draft_k) >= 2, (
        "the periodic probe must keep firing while collapsed")


def test_pick_draft_k_unit():
    assert pick_draft_k(0, None, False) == 0
    assert pick_draft_k(4, None, False) == 4          # optimistic arm
    assert pick_draft_k(4, 0.9, False) == 4
    assert pick_draft_k(4, 0.0, False) == 0           # collapsed
    assert pick_draft_k(4, 0.0, False, probe=True) == 4
    assert pick_draft_k(4, 0.9, True) == 0            # fault cooldown
    # pick_horizon's per_step factor: a spec pass advances k+1 columns
    assert pick_horizon(4, 16, 0, 100, False, per_step=5) == 1
    assert pick_horizon(4, 16, 0, 100, False, per_step=1) == 4
    assert pick_horizon(4, 64, 48, 100, False, per_step=5) == 1
    assert pick_horizon(4, 64, 8, 100, False, per_step=5) == 4


def test_spec_validation(served):
    model, params, _ = served
    with pytest.raises(ValueError, match="greedy-only"):
        ServingEngine(model, params, max_slots=2, s_max=32, draft_k=2,
                      temperature=0.5, rng=jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="BOTH draft_model"):
        ServingEngine(model, params, max_slots=2, s_max=32, draft_k=2,
                      draft_model=model)
    with pytest.raises(ValueError, match="draft_k > 0"):
        ServingEngine(model, params, max_slots=2, s_max=32,
                      draft_model=model, draft_params=params)
    bad = models.GPT(vocab_size=17, max_seq_len=64, hidden_size=32,
                     num_layers=2, num_heads=2, mlp_dim=64,
                     attn_impl="xla")
    with pytest.raises(ValueError, match="vocab"):
        ServingEngine(model, params, max_slots=2, s_max=32, draft_k=2,
                      draft_model=bad,
                      draft_params=init_params(bad, 0))


def test_costs_budget_verify_bandwidth():
    """The committed costs.json records ARE the bandwidth claim: the
    k=4 verify program does > 3x the FLOPs of its non-spec twin while
    touching < 1.7x the bytes (at the tiny audit geometry the
    activation terms inflate bytes; at serving geometry params+KV
    dominate and the ratio tends to 1) — more tokens per weight
    stream, enforceable. Drift re-fails here AND in make check."""
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "pytorch_multiprocessing_distributed_tpu", "analysis",
        "costs.json")
    with open(path) as fh:
        programs = json.load(fh)["programs"]
    spec_name = "serving_decode_spec_paged_w32_h4_k4"
    spec = programs[spec_name]
    base = programs["serving_decode_paged_w32_h4"]
    flops_ratio = spec["flops"] / base["flops"]
    bytes_ratio = spec["bytes_accessed"] / base["bytes_accessed"]
    assert flops_ratio > 3.0, (
        f"{spec_name}: verify FLOPs only {flops_ratio:.2f}x — the "
        "k-query pass lost its extra MXU rows")
    assert bytes_ratio < 1.7, (
        f"{spec_name}: verify bytes {bytes_ratio:.2f}x the "
        "non-spec stream — speculation is supposed to REUSE the "
        "weight/KV bytes, not multiply them")


# ------------------------------------------------------------- smoke

def test_spec_smoke_end_to_end():
    """The ``make spec`` body, mirrored in tier-1 (token-exactness,
    >1.0 accepted/target-step on the repetitive config in fewer
    dispatches, bus + goodput accounting, k=0 disarmed)."""
    from benchmarks.spec_smoke import run_smoke

    run_smoke()


# ------------------------------------------------------ slow full sweep

@pytest.mark.slow
def test_spec_tp_matches_single_shard(served):
    """TP speculative serving: verify attention + k-query writes under
    a 'model'-axis mesh — same tokens as single-shard."""
    from pytorch_multiprocessing_distributed_tpu.inference import (
        shard_params_for_tp_decode)
    from pytorch_multiprocessing_distributed_tpu.parallel import (
        make_mesh)

    model, params, prompts = served
    mesh = make_mesh(4, 2)
    tp_params = shard_params_for_tp_decode(params, mesh)
    engine = _spec(model, tp_params, max_slots=2, mesh=mesh,
                   decode_horizon=4)
    finished = engine.serve([(p, 4) for p in prompts[:3]])
    for r, p in zip(finished, prompts):
        assert r.tokens == _ref_tail(model, params, p, 4)


@pytest.mark.slow
def test_spec_full_matrix_slow(served):
    """Full cross-product: {whole, chunked} x {k=2, k=4} x H in
    {1, 4}, every stream byte-identical to generate()."""
    model, params, prompts = served
    for chunk in (None, 5):
        for k in (2, 4):
            for h in (1, 4):
                engine = _spec(model, params, max_slots=3,
                               prefill_chunk=chunk, decode_horizon=h,
                               draft_k=k)
                got = engine.serve([(p, 6) for p in prompts])
                for r, p in zip(got, prompts):
                    assert r.tokens == _ref_tail(
                        model, params, p, 6), (chunk, k, h)
                assert engine.pool.pages_in_use == 0
