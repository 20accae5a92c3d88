"""Fused multi-step decode horizon: token-exactness vs the per-step
engine and generate() (including EOS/budget freezes mid-horizon and
ragged join/leave churn), the buckets x {1, H} compile ladder, the
one-block-deep pipeline of the step (the next block dispatched before
this one is read back, at every horizon, admissions and evictions
under the block in flight) and the pure horizon-pick policy."""

import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_multiprocessing_distributed_tpu import models
from pytorch_multiprocessing_distributed_tpu.inference import generate
from pytorch_multiprocessing_distributed_tpu.serving import (
    ServingEngine, init_params, pick_horizon)


def _tiny(**kw):
    return models.GPT(vocab_size=61, max_seq_len=64, hidden_size=32,
                      num_layers=2, num_heads=2, mlp_dim=64,
                      attn_impl="xla", **kw)


@pytest.fixture(scope="module")
def served():
    model = _tiny()
    params = init_params(model, 1)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, model.vocab_size, (n,))
               for n in (3, 7, 12, 5, 9)]
    return model, params, prompts


def _ref_tail(model, params, prompt, n):
    out = generate(model, params, jnp.asarray(prompt)[None, :],
                   max_new_tokens=n)
    return np.asarray(out[0, -n:]).tolist()


def _serve_tokens(engine, prompts, n):
    return [list(r.tokens)
            for r in engine.serve([(p, n) for p in prompts])]


def test_horizon_matches_step_engine_ragged(served):
    """The acceptance pin: decode_horizon in {1, 4, 8} is byte-
    identical to per-request generate() (and hence to the PR-2
    step-by-step engine, whose equivalence with generate() is pinned
    in test_serving) — 5 ragged requests through 2 slots, so requests
    join and leave while horizons are in flight."""
    model, params, prompts = served
    ref = [_ref_tail(model, params, p, 6) for p in prompts]
    for h in (1, 4, 8):
        engine = ServingEngine(model, params, max_slots=2, s_max=32,
                               min_bucket=8, decode_horizon=h)
        assert _serve_tokens(engine, prompts, 6) == ref, f"H={h}"
        # every compiled program sits on the buckets x {1, H} ladder
        for window, horizon in engine.decode_programs:
            assert window in engine.decode_buckets
            assert horizon in (1, h)
        assert engine.pool.occupancy == 0
        assert not engine._blocks  # every token block drained


def test_eos_freezes_mid_horizon(served):
    """A request whose stop token lands mid-horizon emits exactly up
    to (and including) the EOS token — the device freeze — and the
    tail of the [H, slots] block is discarded by the host mirror."""
    model, params, prompts = served
    ref = _ref_tail(model, params, prompts[1], 12)
    eos = int(ref[4])
    engine = ServingEngine(model, params, max_slots=1, s_max=32,
                           min_bucket=8, decode_buckets=(),
                           decode_horizon=8)
    engine.submit(prompts[1], 12, eos_id=eos)
    done = [r for r, _, fin in engine.run() if fin]
    (request,) = done
    assert request.finish_reason == "eos"
    assert list(request.tokens) == ref[:5]
    assert engine.pool.occupancy == 0
    # the freeze happened INSIDE a fused horizon, not on a 1-step tail
    assert any(h > 1 for _, h in engine.decode_programs)


@pytest.mark.parametrize("h", [1, 4])
def test_steady_state_sync_and_dispatch_budget(served, h):
    """The dispatch-overhead contract: a solo request makes ONE
    dispatch and ONE host sync per horizon — syncs per decode token =
    1/H — with the readback hidden at EVERY horizon, 1 included (every
    block but the cold start's launched before the previous block
    synced), and re-serving the same shape compiles nothing new."""
    model, params, prompts = served
    engine = ServingEngine(model, params, max_slots=1, s_max=32,
                           min_bucket=8, decode_buckets=(),
                           decode_horizon=h)
    (request,) = engine.serve([(prompts[0], 13)])
    assert list(request.tokens) == _ref_tail(model, params,
                                             prompts[0], 13)
    snap = engine.metrics.snapshot()
    # 12 decode tokens = 12 / H horizons: one dispatch + one sync
    # each, none past the budget, all but the first dispatched before
    # the previous sync
    assert snap["decode_dispatches"] == 12 // h
    assert snap["decode_host_syncs"] == 12 // h
    assert snap["overlapped_dispatches"] == 12 // h - 1
    assert snap["decode_horizon_avg"] == float(h)
    assert snap["host_syncs_per_token"] == pytest.approx(1 / h)
    assert engine.decode_programs == ((32, h),)
    # steady state: the same request shape retraces nothing
    engine.serve([(prompts[0], 13)])
    assert engine.decode_programs == ((32, h),)


def _drive(engine):
    """Step to the end, one list of token events a step."""
    steps = []
    while engine.in_flight:
        steps.append(engine.step())
    return steps


@pytest.mark.parametrize("edge", ["one-token", "two-tokens",
                                  "eos-first", "eos-in-flight"])
def test_pipeline_edges(served, edge):
    """The ends of a stream under the pipeline, two slots and more
    requests than slots: a request of one token (retired at its first
    token, never holds a slot), of two (its only decode block is the
    cold start's), an EOS on the first token, and an EOS sitting in
    the block IN FLIGHT when a successor is admitted beside it — the
    host still believes the row alive and dispatches it once more,
    frozen. Every stream equals generate()'s."""
    model, params, prompts = served
    refs = [_ref_tail(model, params, p, 8) for p in prompts]
    engine = ServingEngine(model, params, max_slots=2, s_max=32,
                           min_bucket=8, decode_horizon=1)
    if edge in ("one-token", "two-tokens"):
        n = 1 if edge == "one-token" else 2
        lengths = [n, 8, n, n, 5]
        reqs = [engine.submit(p, k) for p, k in zip(prompts, lengths)]
        _drive(engine)
        for r, ref, k in zip(reqs, refs, lengths):
            assert r.finish_reason == "length"
            assert list(r.tokens) == ref[:k]
    elif edge == "eos-first":
        reqs = [engine.submit(p, 8, eos_id=(ref[0] if i % 2 == 0
                                            else None))
                for i, (p, ref) in enumerate(zip(prompts, refs))]
        _drive(engine)
        for i, (r, ref) in enumerate(zip(reqs, refs)):
            if i % 2 == 0:
                assert r.finish_reason == "eos"
                assert list(r.tokens) == ref[:1]
            else:
                assert list(r.tokens) == ref
    else:
        # c's last token comes back in block 2; a's EOS is token 3, in
        # block 3, which is in flight when b takes c's slot
        assert refs[0][3] not in refs[0][:3]
        a = engine.submit(prompts[0], 8, eos_id=refs[0][3])
        c = engine.submit(prompts[1], 3)
        b = engine.submit(prompts[2], 8)
        d = engine.submit(prompts[3], 8)
        steps = _drive(engine)
        assert a.finish_reason == "eos" and list(a.tokens) == refs[0][:4]
        assert list(c.tokens) == refs[1][:3]
        assert list(b.tokens) == refs[2] and list(d.tokens) == refs[3]
        joined = next(i for i, ev in enumerate(steps)
                      if any(r is b for r, _t, _fin in ev))
        # b's first token is read a step after its prefill was
        # dispatched, and that was the step that drained a's EOS:
        # the prefill queued behind the block holding it
        assert any(r is a and fin for r, _t, fin in steps[joined - 1])
    assert engine.pool.occupancy == 0
    assert engine.in_flight == 0 and not engine._blocks


@pytest.mark.parametrize("driver", ["run", "serve", "drain"])
def test_every_drive_loop_ends_with_nothing_in_flight(served, driver):
    """run(), serve() and drain() all read the last block back: the
    pipeline leaves no dispatched block behind, and ``in_flight``
    counts requests (a block in flight under running requests is not
    one more unit of load)."""
    model, params, prompts = served
    engine = ServingEngine(model, params, max_slots=2, s_max=32,
                           min_bucket=8, decode_horizon=1)
    if driver == "serve":
        reqs = engine.serve([(p, 6) for p in prompts])
    else:
        reqs = [engine.submit(p, 6) for p in prompts]
        engine.step()
        # two prefills dispatched and unread, nothing running yet
        assert not engine._blocks and engine.in_flight == 5
        engine.step()
        # two running under one block in flight, three queued
        assert len(engine._blocks) == 1 and engine.in_flight == 5
        if driver == "run":
            for _ in engine.run():
                pass
        else:
            engine.drain()
    assert engine.in_flight == 0 and not engine._blocks
    assert engine.pool.occupancy == 0
    for r, p in zip(reqs, prompts):
        assert list(r.tokens) == _ref_tail(model, params, p, 6)


@pytest.mark.parametrize("how", ["withdraw", "deadline"])
def test_eviction_of_a_slot_named_by_the_block_in_flight(served, how):
    """A running request is evicted (client withdrawal; deadline
    expiry) while the undrained block still names its slot with a
    LIVE row: its scrub and its successor's insert queue behind that
    block, the block's tokens for the slot are dropped at the drain,
    and every other stream, the successor's in the same slot
    included, equals generate()'s."""
    from pytorch_multiprocessing_distributed_tpu.serving.scheduler import (
        FAILED)

    model, params, prompts = served
    engine = ServingEngine(model, params, max_slots=2, s_max=32,
                           min_bucket=8, decode_horizon=1)
    engine.serve([(p, 3) for p in prompts[:2]])      # compiled, warm
    doomed = engine.submit(prompts[0], 12, deadline_s=(
        3600.0 if how == "deadline" else None))
    peer = engine.submit(prompts[1], 12)
    heir = engine.submit(prompts[2], 6)
    for _ in range(3):
        engine.step()
    (block,) = engine._blocks
    assert block.slots[doomed.slot] is doomed       # named, and alive
    slot, had = doomed.slot, len(doomed.tokens)
    if how == "withdraw":
        assert engine.withdraw(doomed.uid)
    else:
        doomed.deadline_s = 0.0              # overdue at the next step
    events = engine.step()
    assert doomed.state == FAILED and len(doomed.tokens) == had
    assert doomed.finish_reason == how
    assert not any(r is doomed for r, _t, _f in events)
    # the heir's prefill went out under that block; it takes the slot
    # a step later, when its first token is read
    assert heir.slot is None and engine.in_flight == 2
    engine.step()
    assert heir.slot == slot
    _drive(engine)
    assert list(peer.tokens) == _ref_tail(model, params, prompts[1], 12)
    assert list(heir.tokens) == _ref_tail(model, params, prompts[2], 6)
    assert engine.pool.occupancy == 0 and engine.pool.pages_in_use == 0
    assert engine.in_flight == 0 and not engine._blocks


@pytest.mark.parametrize("how", ["withdraw", "deadline"])
def test_eviction_between_the_two_stages_of_an_admission(served, how):
    """A request whose prefill is dispatched and whose first token is
    still unread (admission's two stages are a step apart) is neither
    queued nor running: withdrawal and deadline expiry find it there,
    return its reserved pages and the slot held back for it, and it
    never emits a token; its neighbour's stream is generate()'s."""
    from pytorch_multiprocessing_distributed_tpu.serving.scheduler import (
        FAILED)

    model, params, prompts = served
    engine = ServingEngine(model, params, max_slots=2, s_max=32,
                           min_bucket=8, decode_horizon=1)
    peer = engine.submit(prompts[1], 8)
    engine.step()
    engine.step()
    held = engine.pool.pages_in_use
    doomed = engine.submit(prompts[0], 8, deadline_s=(
        3600.0 if how == "deadline" else None))
    engine.step()
    assert [p.request for p in engine._unread] == [doomed]
    assert doomed.slot is None and engine.pool.pages_in_use > held
    assert engine.in_flight == 2
    if how == "withdraw":
        assert engine.withdraw(doomed.uid)
    else:
        doomed.deadline_s = 0.0              # overdue at the next step
        engine.step()
    assert doomed.state == FAILED and doomed.finish_reason == how
    assert not doomed.tokens and doomed.first_token_time is None
    assert not engine._unread and engine.pool.free_slots == 1
    _drive(engine)
    assert list(peer.tokens) == _ref_tail(model, params, prompts[1], 8)
    assert engine.pool.occupancy == 0 and engine.pool.pages_in_use == 0
    assert engine.in_flight == 0 and not engine._blocks


def test_queue_pressure_collapses_horizon(served):
    """While the queue holds waiting requests the scheduler pins H=1
    (the continuous-batching join-latency bound): with more requests
    than slots, fused horizons only appear once the queue drains."""
    model, params, prompts = served
    engine = ServingEngine(model, params, max_slots=1, s_max=32,
                           min_bucket=8, decode_buckets=(),
                           decode_horizon=8)
    ref = [_ref_tail(model, params, p, 9) for p in prompts[:2]]
    assert _serve_tokens(engine, prompts[:2], 9) == ref
    programs = dict(engine.decode_programs)
    assert set(programs.values()) <= {1, 8}
    # the first tenant decodes under queue pressure -> some H=1 work;
    # the last tenant's tail runs fused -> some H=8 work
    horizons = [h for _, h in engine.decode_programs]
    assert 1 in horizons and 8 in horizons


def test_horizon_with_chunked_prefill(served):
    """Chunked admission interleaves with horizon decode: while a
    prefill plan is mid-flight the horizon collapses to 1 (the chunk
    gets its step), and the streams stay token-exact."""
    model, params, prompts = served
    ref = [_ref_tail(model, params, p, 6) for p in prompts[:3]]
    engine = ServingEngine(model, params, max_slots=2, s_max=32,
                           min_bucket=8, prefill_chunk=4,
                           decode_horizon=8)
    assert _serve_tokens(engine, prompts[:3], 6) == ref


@pytest.mark.slow
def test_horizon_matches_generate_moe(served):
    """Horizon decode through dropless MoE routing: fused steps route
    per token exactly like the per-step engine / generate()."""
    _, _, prompts = served
    model = _tiny(n_experts=2, moe_top_k=2, moe_capacity_factor=2.0)
    params = init_params(model, 2)
    ref = [_ref_tail(model, params, p, 6) for p in prompts[:3]]
    engine = ServingEngine(model, params, max_slots=2, s_max=32,
                           min_bucket=8, decode_horizon=4)
    assert _serve_tokens(engine, prompts[:3], 6) == ref


@pytest.mark.slow
def test_tp_horizon_matches_single_shard(served):
    """TP serving with fused horizons: the scan carries the head-
    sharded caches through H steps without respecializing, same tokens
    as single-shard."""
    from pytorch_multiprocessing_distributed_tpu.inference import (
        shard_params_for_tp_decode)
    from pytorch_multiprocessing_distributed_tpu.parallel import make_mesh

    model, params, prompts = served
    mesh = make_mesh(4, 2)
    tp_params = shard_params_for_tp_decode(params, mesh)
    ref = [_ref_tail(model, params, p, 6) for p in prompts[:3]]
    engine = ServingEngine(model, tp_params, max_slots=2, s_max=32,
                           mesh=mesh, min_bucket=8, decode_horizon=4)
    assert _serve_tokens(engine, prompts[:3], 6) == ref
    programs = set(engine.decode_programs)
    # join/leave churn on a mesh must not respecialize any program
    engine.serve([(p, 6) for p in prompts[:3]])
    assert set(engine.decode_programs) == programs


def test_pick_horizon_unit():
    """The pure scheduling policy: ladder snapping and each clamp."""
    # H=1 engine / admission pressure always collapse to 1
    assert pick_horizon(1, 32, 5, 100, False) == 1
    assert pick_horizon(8, 32, 5, 100, True) == 1
    # full headroom: the fused rung
    assert pick_horizon(8, 32, 5, 100, False) == 8
    # bucket boundary closer than H -> snap DOWN to 1, not a mid value
    assert pick_horizon(8, 32, 27, 100, False) == 1
    assert pick_horizon(8, 32, 24, 100, False) == 8  # exactly fits
    # shortest remaining budget below H -> 1 (don't outlive everyone)
    assert pick_horizon(8, 256, 5, 3, False) == 1
    assert pick_horizon(8, 256, 5, 8, False) == 8


def test_engine_validates_horizon(served):
    model, params, _ = served
    with pytest.raises(ValueError, match="decode_horizon"):
        ServingEngine(model, params, max_slots=1, decode_horizon=0)
