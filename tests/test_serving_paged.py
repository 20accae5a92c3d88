"""graftpage: paged KV cache + shared-prefix reuse (ISSUE 10).

Tier-1 slim matrix: paged engine token-exact vs the dense-slot engine
AND per-request generate() (whole/chunked admission, bucketed windows,
H>1 with mid-horizon EOS, Pallas interpret, TP), page-table edge cases
(recycling without leaks across 100-request churn, COW fork under
divergence, refcount drops on quarantine/drain, PagePoolExhausted
holds), planner/ledger byte-exactness, and the armed-sentinel
steady-state pins. The full cross-product sweep is slow-marked.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_multiprocessing_distributed_tpu import models
from pytorch_multiprocessing_distributed_tpu.inference import generate
from pytorch_multiprocessing_distributed_tpu.runtime import hbm
from pytorch_multiprocessing_distributed_tpu.serving import (
    PagePool, PagePoolExhausted, PrefixCache, ServingEngine,
    init_params)


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
    return np.asarray(out[0, -n:])


def _paged(model, params, **kw):
    kw.setdefault("s_max", 32)
    kw.setdefault("min_bucket", 8)
    kw.setdefault("kv_layout", "paged")
    kw.setdefault("page_size", 8)
    return ServingEngine(model, params, **kw)


# --------------------------------------------------------- equivalence

def test_paged_matches_generate(served):
    """THE acceptance pin: the engine's greedy streams are
    byte-identical to per-request generate() (the plain reference,
    over its own dense caches), over ragged concurrent requests
    churning through fewer slots — with the decode compile ladder
    bounded by the buckets (the page table is a traced operand, not a
    new static)."""
    model, params, prompts = served
    paged = _paged(model, params, max_slots=3)
    got = paged.serve([(p, 4) for p in prompts])
    for a, p in zip(got, prompts):
        np.testing.assert_array_equal(
            np.asarray(a.tokens), _ref_tail(model, params, p, 4),
            err_msg=f"prompt len {len(p)}")
    # one program a window the traffic touched, horizon 1 only
    programs = paged.decode_programs
    assert set(programs) <= {(w, 1) for w in paged.decode_buckets}
    assert paged.decode_step_compiles == len(programs)
    # all pages returned once drained
    assert paged.pool.pages_in_use == 0
    assert paged.pool.free_pages == paged.pool.num_pages - 1
    # churn over the same mix: zero fresh traces, zero leaks
    paged.serve([(p, 4) for p in prompts])
    assert paged.decode_programs == programs
    assert paged.pool.pages_in_use == 0


def _count_joins_under_a_block(engine):
    """Wrap ``_admit`` on the instance (the engine looks it up at
    every step, as the benchmark's traced runs rely on): counts the
    tenants that joined while a dispatched block was still unread."""
    seen = {"under_block": 0, "joined": 0}
    inner = engine._admit

    def admit():
        blocks = len(engine._blocks)
        before = list(engine._running.values())
        events = inner()
        joined = sum(all(r is not b for b in before)
                     for r in engine._running.values())
        seen["joined"] += joined
        seen["under_block"] += joined if blocks else 0
        return events

    engine._admit = admit
    return seen


@pytest.mark.parametrize("options, holds", [
    (dict(max_slots=2), False),
    (dict(max_slots=2, prefill_chunk=4), False),
    (dict(max_slots=3, page_size=4, num_pages=12), True),
], ids=["whole-prompt", "chunked", "page-hold"])
def test_pipelined_reuse_of_slots_and_pages_matches_generate(
        served, options, holds):
    """The step is pipelined one block deep at horizon 1: with more
    requests than slots (and, in the last case, fewer pages than the
    slots could use, so the head is HELD), slots and pages are handed
    to the next tenant while a block dispatched for the previous one
    is still in flight. Its frozen row's stale write precedes the
    successor's insert in device order; every stream equals
    generate()'s and nothing leaks."""
    model, params, prompts = served
    rng = np.random.default_rng(7)
    work = [(p, n) for p, n in zip(
        prompts + [rng.integers(0, 61, (k,)).tolist()
                   for k in (9, 2, 14, 6)],
        (12, 6, 9, 10, 8, 11, 1, 7, 9))]
    engine = _paged(model, params, **options)
    seen = _count_joins_under_a_block(engine)
    got = engine.serve(work)
    for r, (p, n) in zip(got, work):
        np.testing.assert_array_equal(
            np.asarray(r.tokens), _ref_tail(model, params, p, n),
            err_msg=f"prompt len {len(p)}, {n} tokens")
    snap = engine.metrics.snapshot()
    # the one-token request never held a slot; most of the others
    # joined under a block; every dispatch was made before the
    # previous block was read but the cold starts' (the first, and one
    # where the only running request was at its last token while its
    # successor's first token was still unread)
    assert seen["joined"] == 8 and seen["under_block"] >= 5
    assert snap["decode_dispatches"] - snap["overlapped_dispatches"] <= 2
    assert (snap["page_holds"] > 0) == holds
    assert engine.pool.pages_in_use == 0
    assert engine.in_flight == 0 and not engine._blocks


@pytest.mark.slow
def test_paged_chunked_horizon_eos(served):
    """Chunked admission + fused H=4 horizons + an EOS that fires
    mid-horizon: token-exact with generate(), device freeze respected
    (no page writes past the frozen position corrupt anything).

    Slow-marked (PR 14 tier-1 rebalance for the graftroute suite):
    the heaviest paged-matrix variant — its components (paged decode,
    chunked admission, horizon+EOS freeze) each keep their own
    fast-marked pins; the full cross stays in `make test`."""
    model, params, prompts = served
    ref = _ref_tail(model, params, prompts[1], 8)
    eos = int(ref[2])
    engine = _paged(model, params, max_slots=2, prefill_chunk=5,
                    decode_horizon=4)
    got = engine.serve([(p, 8) for p in (prompts[0], prompts[2])])
    for r, p in zip(got, (prompts[0], prompts[2])):
        np.testing.assert_array_equal(
            np.asarray(r.tokens), _ref_tail(model, params, p, 8))
    engine.submit(prompts[1], 8, eos_id=eos)
    (request,) = [r for r, _, done in engine.run() if done]
    assert request.finish_reason == "eos"
    np.testing.assert_array_equal(np.asarray(request.tokens), ref[:3])
    assert engine.pool.pages_in_use == 0


def test_paged_pallas_decode_engine(served):
    """The paged flash-decode kernel (scalar-prefetched page table,
    interpret mode on CPU) through the full engine: same greedy
    tokens as the XLA take-based reference."""
    model, params, prompts = served
    engine = _paged(model, params, max_slots=2, decode_attn="pallas")
    finished = engine.serve([(p, 4) for p in prompts[:2]])
    for request, prompt in zip(finished, prompts):
        np.testing.assert_array_equal(
            np.asarray(request.tokens),
            _ref_tail(model, params, prompt, 4))


def test_paged_tp_matches_single_shard(served):
    """TP paged serving (pages + heads + vocab sharded over 'model'):
    same tokens, compile set stable across join/leave churn."""
    from pytorch_multiprocessing_distributed_tpu.inference import (
        shard_params_for_tp_decode)
    from pytorch_multiprocessing_distributed_tpu.parallel import make_mesh

    model, params, prompts = served
    mesh = make_mesh(4, 2)
    tp_params = shard_params_for_tp_decode(params, mesh)
    engine = _paged(model, tp_params, max_slots=2, mesh=mesh,
                    prefill_chunk=4)
    finished = engine.serve([(p, 4) for p in prompts[:3]])
    for request, prompt in zip(finished, prompts):
        np.testing.assert_array_equal(
            np.asarray(request.tokens),
            _ref_tail(model, params, prompt, 4))
    windows = set(engine.decode_windows)
    assert engine.decode_step_compiles == len(windows)
    engine.serve([(p, 4) for p in prompts[:3]])
    assert engine.decode_step_compiles == len(windows)
    assert engine.pool.pages_in_use == 0


@pytest.mark.parametrize("kv_dtype", ["model", "int8"])
def test_paged_pool_shards_contiguous_head_groups(served, kv_dtype):
    """On a ``model`` mesh a ``[L, P, ps, H * Dh]`` pool (and an int8
    pool's ``[L, P, ps, H]`` scales) is split on its LAST axis: each
    chip holds a contiguous group of heads' lanes of every page."""
    from pytorch_multiprocessing_distributed_tpu.parallel import make_mesh
    from pytorch_multiprocessing_distributed_tpu.serving.kv_pages import (
        PAGE_SPEC)

    model, _, _ = served
    pool = PagePool(model, 2, 32, make_mesh(4, 2), page_size=8,
                    kv_dtype=kv_dtype)
    leaves = jax.tree.leaves((pool.k_pages, pool.v_pages))
    assert len(leaves) == (4 if kv_dtype == "int8" else 2)
    for leaf in leaves:
        assert leaf.sharding.spec == PAGE_SPEC
        assert leaf.addressable_shards[0].data.shape == (
            leaf.shape[:3] + (leaf.shape[3] // 2,))


# --------------------------------------------------------- prefix cache

def test_prefix_cache_full_hit(served):
    """An identical prompt resubmitted is a FULL hit: token-exact,
    ZERO new prefill work (no prefill/chunk compiles, the cached tok0
    is replayed), pages referenced read-only, and TTFT below the miss
    TTFT."""
    model, params, prompts = served
    engine = _paged(model, params, max_slots=2, page_size=4,
                    prefix_cache=8)
    prompt = prompts[2]  # len 12 = 3 aligned pages at ps=4
    (miss,) = engine.serve([(prompt, 4)])
    assert miss.prefix_hit is None
    prefills = engine.prefill_compiles
    snap0 = engine.metrics.snapshot()
    assert snap0["prefix_misses"] == 1 and snap0["prefix_hits"] == 0
    (hit,) = engine.serve([(prompt, 4)])
    np.testing.assert_array_equal(np.asarray(hit.tokens),
                                  np.asarray(miss.tokens))
    np.testing.assert_array_equal(np.asarray(hit.tokens),
                                  _ref_tail(model, params, prompt, 4))
    assert hit.prefix_hit == "full"
    assert engine.prefill_compiles == prefills  # no prefill program ran
    snap = engine.metrics.snapshot()
    assert snap["prefix_hits"] == 1
    ttft_miss = miss.first_token_time - miss.submit_time
    ttft_hit = hit.first_token_time - hit.submit_time
    assert ttft_hit < ttft_miss, (
        f"full-hit TTFT {ttft_hit:.4f}s not below miss "
        f"{ttft_miss:.4f}s")
    # cache holds the prefix pages resident; clearing returns them
    assert engine.pool.pages_in_use > 0
    engine._prefix_cache.clear()
    assert engine.pool.pages_in_use == 0


def test_prefix_cache_cow_divergence(served):
    """COW under divergence: (a) prompts sharing an aligned prefix
    but diverging later are PARTIAL hits — shared pages read-only,
    suffix prefilled, streams token-exact; (b) two full-hit joiners of
    one cached prompt decode CONCURRENTLY with different budgets/EOS
    (divergence mid-horizon) — the fork keeps them isolated and both
    stay exact."""
    model, params, prompts = served
    engine = _paged(model, params, max_slots=3, page_size=4,
                    prefix_cache=8, decode_horizon=4)
    base = prompts[2] + prompts[3]  # len 17: partial page at ps=4
    (creator,) = engine.serve([(base, 4)])
    np.testing.assert_array_equal(
        np.asarray(creator.tokens), _ref_tail(model, params, base, 4))
    entry, k = engine._prefix_cache.lookup(base)
    assert entry is not None and k == 4 and entry.partial_id is not None
    # (a) divergent suffix -> partial hit, shared pages refcounted up
    fork = base[:8] + [1, 2, 3]
    before = [engine.pool.page_refcount(p) for p in entry.shared_ids[:2]]
    (partial,) = engine.serve([(fork, 4)])
    assert partial.prefix_hit == "partial"
    np.testing.assert_array_equal(
        np.asarray(partial.tokens), _ref_tail(model, params, fork, 4))
    # the joiner released its shared refs at completion
    after = [engine.pool.page_refcount(p) for p in entry.shared_ids[:2]]
    assert after == before
    # (b) two concurrent full hits, one stopped early by EOS
    ref8 = _ref_tail(model, params, base, 8)
    a = engine.submit(base, 8)
    b = engine.submit(base, 8, eos_id=int(ref8[2]))
    for _ in engine.run():
        pass
    assert a.prefix_hit == "full" and b.prefix_hit == "full"
    np.testing.assert_array_equal(np.asarray(a.tokens), ref8)
    np.testing.assert_array_equal(np.asarray(b.tokens), ref8[:3])
    assert b.finish_reason == "eos"
    # only the cache's own references remain
    engine._prefix_cache.clear()
    assert engine.pool.pages_in_use == 0


def test_prefix_is_aligned_subprompt_of_cached(served):
    """Edge: a prompt that IS a page-aligned prefix of a LONGER cached
    prompt (lookup matches every one of its pages but it is not a full
    hit — different terminal token context). The partial-hit path must
    leave >= 1 suffix token to prefill for tok0, not fail the
    request."""
    model, params, prompts = served
    engine = _paged(model, params, max_slots=2, page_size=4,
                    prefix_cache=8)
    long_p = prompts[2] + prompts[3]       # len 17
    (creator,) = engine.serve([(long_p, 4)])
    assert creator.state == "done"
    sub = long_p[:16]                       # exactly 4 aligned pages
    (r,) = engine.serve([(sub, 4)])
    assert r.state == "done"
    assert r.prefix_hit == "partial"
    np.testing.assert_array_equal(
        np.asarray(r.tokens), _ref_tail(model, params, sub, 4))
    engine._prefix_cache.clear()
    assert engine.pool.pages_in_use == 0


def test_prefix_cache_validation(served):
    model, params, _ = served
    with pytest.raises(ValueError, match="greedy"):
        ServingEngine(model, params, max_slots=1, kv_layout="paged",
                      page_size=8, prefix_cache=4, temperature=0.5,
                      rng=jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="kv_layout"):
        ServingEngine(model, params, max_slots=1, kv_layout="vram")


def test_dense_layout_refused_by_name(served):
    """The dense slot pool is gone: asking for it names the removal,
    and an engine built with no layout at all runs on pages."""
    model, params, _ = served
    with pytest.raises(ValueError, match="dense slot pool .* removed"):
        ServingEngine(model, params, max_slots=1, kv_layout="dense")
    engine = ServingEngine(model, params, max_slots=2, s_max=32)
    # page_size=None is min_bucket, num_pages=None every slot's worst
    # case plus the scratch page: the capacity the dense pool had
    assert engine.pool.page_size == engine.min_bucket == 16
    assert engine.pool.num_pages == 2 * 2 + 1


# ------------------------------------------------- page-table edge cases

def test_page_recycling_no_leak_churn(served):
    """100-request churn through a small pool: every page returns to
    the free list, refcounts end zero (scratch excepted), and the
    table mirror ends all-scratch."""
    model, params, _ = served
    engine = _paged(model, params, max_slots=2, page_size=8)
    rng = np.random.default_rng(3)
    pool = engine.pool
    free0, n0 = pool.free_pages, pool.pages_in_use
    assert n0 == 0
    for i in range(25):  # 4 requests per serve = 100 requests
        batch = [(rng.integers(0, model.vocab_size,
                               (int(rng.integers(1, 20)),)).tolist(), 2)
                 for _ in range(4)]
        finished = engine.serve(batch)
        assert all(r.state == "done" for r in finished)
        assert pool.pages_in_use == 0, f"leak after round {i}"
    assert pool.free_pages == free0
    assert all(pool.page_refcount(p) == 0
               for p in range(1, pool.num_pages))
    assert not pool._table.any()


def test_page_exhaustion_hold_and_named_shed(served):
    """Admission under page pressure: the FIFO head is HELD queued
    (counted, never failed) until running work frees pages; a head
    that nothing in flight could EVER satisfy fails named
    PagePoolExhausted; never-fits is rejected at submission."""
    model, params, _ = served
    rng = np.random.default_rng(1)
    engine = _paged(model, params, max_slots=2, page_size=4,
                    num_pages=6)
    p1 = rng.integers(0, 61, (9,)).tolist()   # 9 + 4 -> 4 pages
    p2 = rng.integers(0, 61, (9,)).tolist()
    r1, r2 = engine.submit(p1, 4), engine.submit(p2, 4)
    holds = 0
    while engine.in_flight:
        engine.step()
        holds = max(holds, engine.metrics.page_holds)
    assert r1.state == "done" and r2.state == "done"
    assert holds > 0
    np.testing.assert_array_equal(
        np.asarray(r1.tokens), _ref_tail(model, params, p1, 4))
    # never-fits: submission-time rejection, like the s_max check
    with pytest.raises(ValueError, match="page"):
        engine.submit(list(range(20)), 8)
    # hopeless-but-submittable: pages exist in total but a cached
    # prefix is NOT holding them and nothing is running -> the gate
    # would hold forever; it must fail NAMED instead. Shrink the pool
    # via a stuck allocation to simulate.
    stuck = engine.pool.alloc_pages(3)  # leaves 2 free of 5
    r3 = engine.submit(rng.integers(0, 61, (5,)).tolist(), 4)  # 3 pages
    engine.step()
    assert r3.state == "failed"
    assert isinstance(r3.error, PagePoolExhausted)
    assert r3.finish_reason == "pages"
    engine.pool.decref(stuck)


def test_quarantine_returns_pages(served):
    """A request quarantined by an injected insert fault releases
    every page it reserved; the engine keeps serving."""
    from pytorch_multiprocessing_distributed_tpu.runtime import faults

    model, params, prompts = served
    engine = _paged(model, params, max_slots=2, dispatch_retries=1)
    plan = faults.FaultPlan(
        [faults.FaultRule("serving.slot_insert", "error", times=1)],
        seed=5)
    faults.arm(plan)
    try:
        finished = engine.serve([(p, 3) for p in prompts[:3]])
    finally:
        faults.disarm()
    states = [r.state for r in finished]
    assert states.count("failed") == 1 and states.count("done") == 2
    for r, p in zip(finished, prompts):
        if r.state == "done":
            np.testing.assert_array_equal(
                np.asarray(r.tokens), _ref_tail(model, params, p, 3))
    assert engine.pool.pages_in_use == 0


def test_drain_redelivery_paged(served, tmp_path):
    """Supervised-restart redelivery on the PAGED engine: the WAL's
    unfinished requests replay token-exact through a fresh paged
    engine (prefix-dedup against emitted tokens), and pages drain to
    zero after."""
    from pytorch_multiprocessing_distributed_tpu.runtime import heal

    model, params, prompts = served
    wal = str(tmp_path / "wal.jsonl")
    crashed = _paged(model, params, max_slots=2,
                     journal=heal.RequestJournal(wal))
    pre = [crashed.submit(p, 6) for p in prompts[:3]]
    for _ in range(3):
        crashed.step()
    prefix = {r.uid: list(r.tokens) for r in pre}
    del crashed  # the crash shape: WAL left open

    journal2 = heal.RequestJournal(wal)
    fresh = _paged(model, params, max_slots=2, journal=journal2)
    redelivered = fresh.redeliver(journal2.unfinished())
    fresh.drain(None)
    assert redelivered, "crash left nothing to redeliver?"
    for r in redelivered:
        assert r.state == "done"
        want = prefix.get(r.uid, [])
        assert r.tokens[:len(want)] == want
        np.testing.assert_array_equal(
            np.asarray(r.tokens),
            _ref_tail(model, params, r.prompt, 6))
    assert fresh.pool.pages_in_use == 0


# ------------------------------------------------------- pool unit tests

def test_pagepool_unit(served):
    model, _, _ = served
    pool = PagePool(model, max_slots=2, s_max=32, page_size=8,
                    num_pages=6)
    assert pool.pages_per_slot == 4
    assert pool.free_pages == 5 and pool.pages_in_use == 0
    ids = pool.alloc_pages(3)
    assert ids == [1, 2, 3] and pool.pages_in_use == 3
    pool.incref([ids[0]])
    pool.decref(ids)
    assert pool.pages_in_use == 1  # ids[0] still referenced
    pool.decref([ids[0]])
    assert pool.pages_in_use == 0 and pool.free_pages == 5
    with pytest.raises(PagePoolExhausted):
        pool.alloc_pages(6)
    with pytest.raises(ValueError):
        pool.decref([1])  # already free
    # bind/release: the row owns the refs, release drops them and
    # resets the row to scratch
    ids = pool.alloc_pages(2)
    slot = pool.acquire()
    pool.bind_slot(slot, ids)
    assert pool.slot_pages(slot) == ids
    table = np.asarray(pool.device_table())
    assert list(table[slot][:2]) == ids
    pool.release(slot)
    assert pool.pages_in_use == 0
    assert pool.slot_pages(slot) == []
    with pytest.raises(ValueError, match="num_pages"):
        PagePool(model, max_slots=1, s_max=32, page_size=8, num_pages=1)
    with pytest.raises(ValueError, match="page_size"):
        PagePool(model, max_slots=1, s_max=32, page_size=0)


def test_prefix_cache_unit(served):
    """Host-side cache policy without an engine: registration,
    longest-prefix lookup, LRU eviction dropping page refs."""
    model, _, _ = served
    pool = PagePool(model, max_slots=2, s_max=32, page_size=4)
    cache = PrefixCache(pool, max_entries=2)
    copies = []

    def fake_copy(src, dst):
        copies.append((src, dst))

    ids = pool.alloc_pages(3)
    prompt = list(range(10))  # 2 full pages + partial (10 % 4 = 2)
    entry = cache.register(prompt, ids, tok0=7, copy_page=fake_copy)
    assert entry.n_full == 2 and entry.partial_id is not None
    assert copies == [(ids[2], entry.partial_id)]
    got, k = cache.lookup(prompt)
    assert got is entry and k == 2 and got.tok0 == 7
    got, k = cache.lookup(prompt[:8] + [55, 56, 57])
    assert got is entry and k == 2  # aligned-prefix partial hit
    assert cache.lookup([9] * 12) == (None, 0)
    # releasing the creator's refs leaves the cache's alive
    pool.decref(ids)
    assert pool.page_refcount(ids[0]) == 1
    # LRU bound: two more entries evict the first, freeing its refs
    for base in (100, 200):
        ids2 = pool.alloc_pages(1)
        cache.register([base] * 4, ids2, tok0=1,
                       copy_page=fake_copy)
        pool.decref(ids2)
    assert len(cache) == 2
    assert cache.lookup(prompt) == (None, 0)
    assert pool.page_refcount(ids[0]) == 0
    cache.clear()
    assert pool.pages_in_use == 0
    # evicting an entry must RE-INDEX survivors sharing its prefix
    # keys (registration's setdefault kept the older entry) — the
    # survivor's pages stay reachable, not orphaned
    cache = PrefixCache(pool, max_entries=4)
    ia = pool.alloc_pages(1)
    a = cache.register([5, 6, 7, 8], ia, tok0=1, copy_page=fake_copy)
    ib = pool.alloc_pages(2)
    b = cache.register([5, 6, 7, 8, 9, 10, 11, 12], ib, tok0=2,
                       copy_page=fake_copy)
    pool.decref(ia)
    pool.decref(ib)
    assert cache.lookup([5, 6, 7, 8, 99])[0] is a
    cache._drop(a)
    got, k = cache.lookup([5, 6, 7, 8, 99])
    assert got is b and k == 1
    cache.clear()
    assert pool.pages_in_use == 0


# ------------------------------------------------- planner / ledger pins

def test_planner_paged_byte_exact(served):
    """plan_capacity(page_size=): page_bytes and total paged KV bytes
    match a REAL PagePool allocation byte-for-byte, and the expected-
    resident prediction follows the length distribution."""
    from pytorch_multiprocessing_distributed_tpu.analysis.meter import (
        plan_capacity)

    model, params, _ = served
    budget = hbm.tree_nbytes(params) + 6 * (1 << 20)
    dist = [12, 12, 28, 44]  # pages at ps=8: 2, 2, 4, 6 -> mean 3.5
    plan = plan_capacity(model, 64, budget, params=params,
                         page_size=8, length_dist=dist)
    assert plan["page_bytes"] == PagePool.page_kv_bytes(model, 8)
    assert plan["expected_pages_per_request"] == 3.5
    assert plan["expected_resident_requests"] == int(
        plan["max_pages"] / 3.5)
    with hbm.scoped_ledger() as ledger:
        pool = PagePool(model, max_slots=4, s_max=64, page_size=8,
                        num_pages=plan["max_pages"] + 1)
        entry = ledger.entries()["serving.kv_pages"]
        # BYTE-EXACT: planner pages == allocator pages
        assert entry[1] == plan["paged_kv_bytes_at_max"]
        assert entry[0] == "kv_pages"
        assert entry[2]["hbm_page_bytes"] == plan["page_bytes"]
        # live utilization gauges ride the snapshot un-double-counted
        ids = pool.alloc_pages(3)
        snap = ledger.snapshot()
        assert snap["hbm_pages_in_use"] == 3
        assert snap["hbm_kv_pages_in_use_bytes"] == 3 * plan["page_bytes"]
        assert snap["hbm_page_bytes"] == plan["page_bytes"]
        assert snap["hbm_kv_pages_bytes"] == entry[1]
        total_with_gauges = snap["hbm_total_bytes"]
        pool.decref(ids)
        assert ledger.snapshot()["hbm_total_bytes"] == total_with_gauges


def test_paged_armed_sentinel_steady_state(served):
    """Acceptance: with the HBM ledger ARMED, a warmed paged engine
    re-serving the same length mix makes 0 fresh compiles and no
    unexpected transfers — the page table re-uploads only at
    admission/release boundaries (expected-transfer annotated), never
    in steady state."""
    from pytorch_multiprocessing_distributed_tpu.analysis.sentinels import (
        guard_transfers, recompile_budget)

    model, params, prompts = served
    with hbm.scoped_ledger() as ledger:
        engine = _paged(model, params, max_slots=2, decode_horizon=4)
        engine.serve([(p, 5) for p in prompts[:3]])  # warm every bucket
        touched = engine.decode_step_compiles
        with guard_transfers():
            with recompile_budget(engine._decode, 0,
                                  label="paged decode steady state"):
                finished = engine.serve([(p, 5) for p in prompts[:3]])
        assert engine.decode_step_compiles == touched
        for r, p in zip(finished, prompts):
            np.testing.assert_array_equal(
                np.asarray(r.tokens), _ref_tail(model, params, p, 5))
        assert ledger.snapshot()["hbm_pages_in_use"] == 0
        assert "serving.kv_pages" in ledger.entries()


# ------------------------------------------------------ slow full sweep

@pytest.mark.slow
def test_paged_matrix_full_slow(served):
    """The full cross-product: {dense GPT, MoE} x {whole, chunked} x
    {H=1, H=4} x {xla, pallas} x window-crossing prompts — every cell
    token-exact vs generate(), no page leaks anywhere."""
    model, params, prompts = served
    moe = _tiny(n_experts=2, moe_top_k=2, moe_capacity_factor=2.0)
    moe_params = init_params(moe, 2)
    rng = np.random.default_rng(7)
    crosser = rng.integers(0, model.vocab_size, (14,)).tolist()
    cases = [(model, params), (moe, moe_params)]
    for m, pr in cases:
        for chunk in (None, 5):
            for h in (1, 4):
                for attn in ("xla", "pallas"):
                    if attn == "pallas" and m is moe:
                        continue
                    engine = _paged(m, pr, max_slots=2,
                                    prefill_chunk=chunk,
                                    decode_horizon=h, decode_attn=attn,
                                    prefix_cache=4, page_size=8)
                    batch = [prompts[0], crosser, prompts[2]]
                    finished = engine.serve([(p, 8) for p in batch])
                    for r, p in zip(finished, batch):
                        np.testing.assert_array_equal(
                            np.asarray(r.tokens),
                            _ref_tail(m, pr, p, 8),
                            err_msg=f"chunk={chunk} h={h} attn={attn}")
                    # windows crossed a bucket boundary at 16
                    assert 32 in engine.decode_windows
                    # only the prefix cache retains pages (by design)
                    engine._prefix_cache.clear()
                    assert engine.pool.pages_in_use == 0
