"""graftmeter smoke: the capacity/efficiency surface must round-trip.

The ``make meter`` target (and the tier-1 test that drives this module
in-process) runs a short synthetic workload and asserts the whole
graftmeter stack end-to-end:

1. **costs.json freshness** — a cheap subset of the registry
   re-measures clean against the committed ``analysis/costs.json``
   budgets (the full 15-program gate is ``make check``; this is the
   fast canary that the comparison machinery itself works);
2. **planner round-trip** — ``plan_capacity``'s slot prediction is
   validated against a REAL CPU-backend :class:`PagePool` allocation:
   predicted per-slot/pool bytes must match the arrays actually
   allocated within 0.5% (in practice they are byte-exact — the
   planner and the allocator share one shape x dtype product);
3. **live gauges** — a served engine with the HBM ledger armed
   exposes ``pmdt_hbm_*`` gauges (params, KV pool, per-bucket decode
   temps) on a live ``/metrics`` scrape, beside the serving meters;
4. **breakdown artifact** — ``utils.plotting.draw_hbm_breakdown``
   renders the ledger to a PNG (the plot_curves-parity artifact for
   memory).

Exit code 0 and ``graftmeter smoke OK`` = the capacity surface is
wired. Run: ``python benchmarks/meter_smoke.py [--out_dir DIR]``
(CPU-safe: gpt_tiny, a handful of requests, seconds of work — the
registry subset re-compile is the slowest part).
"""

import argparse
import os
import sys
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import benchmarks._common as _common  # noqa: E402

# the cheap canary subset: the MoE expert-parallel layer + the
# all-reduce microprogram — sub-second compiles that still exercise
# build -> compile -> cost/memory -> compare end-to-end. The full
# 15-program registry is `make check`.
CANARY_PROGRAMS = ("collectives_all_reduce", "moe_mlp_ep")

# planner-vs-allocation tolerance, pinned by the tier-1 twin of this
# smoke: the planner and PagePool share one shape x dtype product, so
# the match is byte-exact in practice; 0.5% absorbs a future dtype/
# padding surprise without letting a real drift (a forgotten cache
# copy doubles bytes) through.
PLAN_TOLERANCE = 0.005


def run(out_dir: str) -> dict:
    """The smoke body; returns the measured pieces for the caller
    (the tier-1 test asserts on them in-process)."""
    import numpy as np

    from pytorch_multiprocessing_distributed_tpu import models
    from pytorch_multiprocessing_distributed_tpu.analysis import meter
    from pytorch_multiprocessing_distributed_tpu.runtime import (
        scope as graftscope)
    from pytorch_multiprocessing_distributed_tpu.runtime import hbm
    from pytorch_multiprocessing_distributed_tpu.serving import (
        ServingEngine, init_params)
    from pytorch_multiprocessing_distributed_tpu.serving.kv_pages import (
        PagePool)
    from pytorch_multiprocessing_distributed_tpu.serving.scheduler import (
        DONE)
    from pytorch_multiprocessing_distributed_tpu.utils.plotting import (
        draw_hbm_breakdown)

    os.makedirs(out_dir, exist_ok=True)

    # ---- 1. committed cost budgets: canary subset re-measures clean
    findings, cost_records, skipped = meter.run_meter(CANARY_PROGRAMS)
    assert not findings, ("graftmeter canary RED vs analysis/costs."
                          "json:\n" + "\n".join(f.render()
                                                for f in findings))
    assert not skipped, f"canary programs skipped: {skipped}"
    for name in CANARY_PROGRAMS:
        rec = cost_records[name]
        assert rec["flops"] and rec["flops"] > 0, (name, rec)
        assert rec["memory"]["peak_bytes"] > 0, (name, rec)

    # ---- 2. planner round-trip vs REAL CPU-backend allocation
    model = models.get_model("gpt_tiny", attn_impl="xla")
    params = init_params(model, 0)
    params_bytes = hbm.tree_nbytes(params)
    s_max = 32
    budget = params_bytes + 4 * (
        PagePool.per_slot_kv_bytes(model, s_max)
        + PagePool.per_slot_state_bytes()) + 1000
    plan = meter.plan_capacity(model, s_max, budget, params=params)
    assert plan["max_slots"] == 4, plan
    # pages at the default num_pages hold every slot's worst case,
    # plus the scratch page and the int32 page table
    pool = PagePool(model, plan["max_slots"], s_max, page_size=8)
    predicted = plan["max_slots"] * plan["per_slot_bytes"]
    actual = (pool.hbm_bytes - pool.page_bytes
              - 4 * pool.max_slots * pool.pages_per_slot)
    rel_err = abs(predicted - actual) / actual
    assert rel_err <= PLAN_TOLERANCE, (
        f"plan_capacity predicted {predicted} bytes for "
        f"{plan['max_slots']} slots, the pool actually allocated "
        f"{actual} ({100 * rel_err:.2f}% off > "
        f"{100 * PLAN_TOLERANCE}% tolerance)")

    # ---- 3. live gauges: served engine, ledger armed, one scrape
    with hbm.scoped_ledger() as ledger:
        engine = ServingEngine(model, params, max_slots=2, s_max=32,
                               min_bucket=8, decode_horizon=2)
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, model.vocab_size,
                                (int(rng.integers(3, 12)),)).tolist()
                   for _ in range(4)]
        served = engine.serve([(p, 5) for p in prompts])
        assert all(r.state == DONE for r in served)

        def live_snapshot():
            snap = engine.metrics.snapshot()
            snap.update(ledger.snapshot())
            snap["hbm_per_slot_bytes"] = engine.pool.per_slot_bytes
            return snap

        server = graftscope.start_stats_server(live_snapshot, port=0,
                                               prefix="pmdt")
        try:
            port = server.server_address[1]
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics") as resp:
                live_prom = resp.read().decode()
        finally:
            server.shutdown()
        breakdown = ledger.breakdown()
        snapshot = ledger.snapshot()

    # the ledger saw every allocation site: params, KV pool, slot
    # state, and at least one per-bucket decode-program temp
    assert "params" in breakdown and "kv" in breakdown, breakdown
    assert "serving.kv_pages" in breakdown["kv_pages"], breakdown
    assert any(n.startswith("serving.decode_temp_w")
               for n in breakdown.get("temps", {})), breakdown
    samples = {}
    for line in live_prom.splitlines():
        if line and not line.startswith("#"):
            name, value = line.rsplit(" ", 1)
            samples[name] = float(value)
    hbm_gauges = {k: v for k, v in samples.items()
                  if k.startswith("pmdt_hbm_")}
    assert "pmdt_hbm_total_bytes" in hbm_gauges, sorted(samples)[:20]
    assert hbm_gauges["pmdt_hbm_total_bytes"] > params_bytes
    assert "pmdt_hbm_per_slot_bytes" in samples

    # ---- 4. breakdown artifact renders
    png = draw_hbm_breakdown(
        breakdown, os.path.join(out_dir, "hbm_breakdown.png"),
        title="meter smoke HBM", budget_bytes=2 * snapshot[
            "hbm_total_bytes"])
    assert os.path.getsize(png) > 0

    return {"plan": plan, "pool_bytes": actual,
            "cost_records": cost_records, "breakdown": breakdown,
            "snapshot": snapshot, "samples": samples, "png": png}


def main(argv=None):
    _common.enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--out_dir", default="/tmp/pmdt_meter_smoke",
                   help="artifact directory (hbm_breakdown.png)")
    args = p.parse_args(argv)
    out = run(args.out_dir)
    plan = out["plan"]
    print(f"# plan: {plan['max_slots']} slots x "
          f"{plan['per_slot_bytes']} B/slot beside "
          f"{plan['params_bytes']} B params; pool allocated "
          f"{out['pool_bytes']} B; "
          f"hbm_total={out['snapshot']['hbm_total_bytes']} B; "
          f"artifacts in {args.out_dir}")
    print("graftmeter smoke OK")


if __name__ == "__main__":
    main()
