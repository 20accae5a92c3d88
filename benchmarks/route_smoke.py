"""graftroute smoke: a 2-replica fleet over an in-process store must
serve, survive a replica death, and route a warm prefix — end to end.

The ``make route`` target (and the tier-1 test that drives this module
in-process) builds two paged engine replicas behind one
:class:`~pytorch_multiprocessing_distributed_tpu.serving.Router` over
a ``MemStore`` (the same client surface the real C++ ``TCPStore``
serves), then asserts:

1. **byte-identity** — every routed stream equals the single-engine
   baseline, request for request;
2. **death → redelivery** — one injected engine-fatal
   (``serving.decode_dispatch``, the existing graftfault site) kills
   a replica mid-run; its journal's unfinished requests redeliver to
   the peer under their ORIGINAL uids, every stream still byte-exact,
   and the fleet-level ``tokens_generated`` merge is
   redelivery-deduped to the unique token count;
3. **warm prefix routing** — a prompt served once registers in the
   fleet :class:`PrefixCacheDirectory`; an identical prompt routes to
   the HOLDING replica and admits as an engine-level prefix-cache
   FULL hit (no prefill compute), with its TTFT beating the cold
   replica's;
4. **directory + health surfaces** — the store-published replica
   directory (``runtime.fleet.publish_replica`` /
   ``replica_directory``) lists both replicas with roles/states, and
   ``Router.healthz`` aggregates per-replica ``state_name``.

Exit code 0 and one ``graftroute smoke OK`` line = the fleet serving
stack is wired. Run: ``python benchmarks/route_smoke.py``
(one process, in-process replicas — no child ever needs a device;
tiny model, seconds).
"""

import argparse
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run_smoke(verbose: bool = True) -> dict:
    import numpy as np

    from pytorch_multiprocessing_distributed_tpu import models
    from pytorch_multiprocessing_distributed_tpu.runtime import (
        faults, fleet as graftfleet, heal)
    from pytorch_multiprocessing_distributed_tpu.runtime.store import (
        MemStore)
    from pytorch_multiprocessing_distributed_tpu.serving import (
        Router, ServingEngine, ServingReplica, init_params)

    def note(msg):
        if verbose:
            print(msg, flush=True)

    model = models.GPT(vocab_size=61, max_seq_len=64, hidden_size=32,
                       num_layers=2, num_heads=2, mlp_dim=64,
                       attn_impl="xla")
    params = init_params(model, 1)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, model.vocab_size,
                            (int(rng.integers(4, 20)),)).tolist()
               for _ in range(6)]

    def mk(journal=None):
        return ServingEngine(model, params, max_slots=2, s_max=32,
                             min_bucket=8, kv_layout="paged",
                             page_size=8, prefix_cache=4,
                             retry_backoff_s=0.0, dispatch_retries=1,
                             journal=journal)

    # ---- single-engine baseline (the byte-identity reference)
    base = mk()
    ref = {f"u{i}": list(r.tokens) for i, r in enumerate(
        base.serve((p, 8) for p in prompts))}
    total_unique = sum(len(t) for t in ref.values())

    # ---- 2 replicas over MemStore, journals armed
    store = MemStore()
    tmpdir = tempfile.mkdtemp(prefix="pmdt_route_smoke_")

    def mkrep(i):
        journal = heal.RequestJournal(
            os.path.join(tmpdir, f"wal{i}.jsonl"))
        return ServingReplica(f"r{i}", mk(journal), journal=journal)

    router = Router([mkrep(0), mkrep(1)], store=store,
                    run_uid="smoke")

    # 4. store-published replica directory
    directory = graftfleet.replica_directory(store, run_uid="smoke")
    assert set(directory) == {"r0", "r1"}, directory
    assert all(d["role"] == "both" for d in directory.values())
    note(f"directory: {sorted(directory)} published over MemStore")

    # 2. one injected death mid-run -> journal redelivery to the peer
    for i, p in enumerate(prompts):
        router.submit(p, 8, uid=f"u{i}")
    for _ in range(3):
        router.step()  # tokens into both WALs before the kill
    plan = faults.FaultPlan(seed=7, rules=[faults.FaultRule(
        "serving.decode_dispatch", "fatal", times=1)])
    faults.arm(plan)
    try:
        while router.in_flight:
            router.step()
    finally:
        faults.disarm()
    dead = [r.rid for r in router.replicas if r.reaped]
    assert len(dead) == 1, f"expected exactly one dead replica: {dead}"
    assert router.requests_redelivered >= 1
    recs = router.records()
    for uid, want in ref.items():
        got = list(recs[uid].tokens)
        assert got == want, (
            f"stream {uid} diverged after the replica death: "
            f"{got} vs {want}")
    merged = router.merged_metrics()
    assert merged["tokens_generated"] == total_unique, (
        "redelivery dedup broke the fleet token count: "
        f"{merged['tokens_generated']} vs {total_unique} unique")
    note(f"death: {dead[0]} died, "
         f"{router.requests_redelivered} redelivered to the peer, "
         f"all {len(ref)} streams byte-exact, merged tokens "
         f"{merged['tokens_generated']} == unique {total_unique}")

    # fleet health: survivor READY, dead replica named DEAD
    hz = router.healthz()
    assert hz["state_name"] == "READY"
    assert hz["replicas"][dead[0]]["state_name"] == "DEAD"

    # 3. warm prefix routing: serve once, the identical prompt routes
    # to the holder and admits as a FULL engine-cache hit
    # a FRESH page-aligned prompt (sharing no served prefix — an
    # aligned subprompt of a longer cached one stays a partial hit by
    # the engine cache's own contract)
    warm = rng.integers(0, model.vocab_size, (16,)).tolist()
    router.serve([(warm, 4)])              # registers pages + entry
    # first hit pays the state-splice program's compile; steady-state
    # hits are what the ratio judges
    router.serve([(warm, 4)])
    routed_before = router.prefix_routed
    hits_before = sum(r.engine.metrics.prefix_hits
                      for r in router.replicas)
    # best-of-N on BOTH sides: single-shot millisecond TTFTs on a
    # noisy box flip on scheduler hiccups; the min is the number the
    # cache win actually controls
    warm_ttfts = []
    for _ in range(4):
        rec = router.serve([(warm, 4)])[0]
        warm_ttfts.append(rec.first_token_time - rec.submit_time)
    warm_ttft = min(warm_ttfts)
    assert router.prefix_routed == routed_before + 4, (
        "identical prompt did not route through the directory")
    assert sum(r.engine.metrics.prefix_hits
               for r in router.replicas) == hits_before + 4, (
        "directory-routed prompt was not an engine-level FULL hit")
    # cold TTFT: fresh same-length prompts MISSING the same engine's
    # cache (same replica, same compiled programs — the hit's win is
    # skipped prefill compute, not compile luck)
    cold_ttfts = []
    for _ in range(4):
        cold_prompt = rng.integers(0, model.vocab_size, (16,)).tolist()
        cold_rec = router.serve([(cold_prompt, 4)])[0]
        if cold_rec.first_token_time:
            cold_ttfts.append(cold_rec.first_token_time
                              - cold_rec.submit_time)
    ratio = None
    if cold_ttfts:
        cold_ttft = min(cold_ttfts)
        ratio = warm_ttft / cold_ttft
    note(f"prefix: warm TTFT {warm_ttft * 1e3:.2f} ms"
         + (f" vs cold {cold_ttft * 1e3:.2f} ms "
            f"(ratio {ratio:.2f}, min of 4)"
            if ratio is not None else ""))

    # 1. byte-identity on a FRESH healthy fleet (no faults in play)
    fresh = Router([ServingReplica("a", mk()),
                    ServingReplica("b", mk())])
    out = fresh.serve([(p, 8) for p in prompts])
    for i, r in enumerate(out):
        assert r.state == "done"
        assert list(r.tokens) == ref[f"u{i}"], (
            f"fresh-fleet stream {i} diverged from the baseline")
    note(f"fleet: {len(out)} streams byte-identical to the "
         "single-engine baseline across 2 replicas")

    import shutil

    shutil.rmtree(tmpdir, ignore_errors=True)
    return {
        "dead": dead[0],
        "redelivered": router.requests_redelivered,
        "replayed_tokens": router.redelivery_replayed_tokens,
        "merged_tokens": merged["tokens_generated"],
        "prefix_routed": router.prefix_routed,
        "warm_ttft_s": warm_ttft,
        "ttft_ratio_warm_over_cold": ratio,
    }


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.parse_args(argv)
    from pytorch_multiprocessing_distributed_tpu.utils.hostenv import (
        force_cpu_devices_from_env)

    force_cpu_devices_from_env()
    out = run_smoke(verbose=True)
    print(f"graftroute smoke OK ({out['redelivered']} redelivered, "
          f"ratio {out['ttft_ratio_warm_over_cold']})", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
