"""graftmeter: the static cost/memory model, the committed
``analysis/costs.json`` gate, the live HBM ledger, and the capacity
planner.

What must stay true:

- **normalized analyses**: ``memory_analysis_dict`` /
  ``costs_record`` turn XLA's per-generation shapes into ONE record,
  and a backend without a memory model yields explicit Nones, never a
  fake zero;
- **budget drift is loud and readable**: a tampered or drifted
  costs.json entry fails with the program AND field named, byte
  deltas in MiB ("+N MiB temp") — and `make check` enforces it in the
  same pass as the fingerprints (tier-1 gate in test_graftcheck);
- **ledger truth**: allocation sites (params, KV pool, slot state,
  per-bucket decode temps) land on the armed ledger with the exact
  bytes the arrays report; disarmed, every site is one global read;
- **armed cost is zero on device paths**: serving steady state under
  ``guard_transfers`` + ``recompile_budget(0)`` holds with the ledger
  ARMED (decode-temp metering only ever rides a compile that already
  happened, through AOT lowering the jit cache cannot see);
- **the planner inverts the allocator**: ``plan_capacity``'s
  per-slot/pool byte prediction matches a real CPU-backend
  ``PagePool`` allocation within the documented 0.5% tolerance
  (byte-exact in practice — pinned);
- **roofline honesty**: efficiency attribution is null-safe — no
  peak, no cost model, no number.
"""

import importlib.util
import json
import os

import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from pytorch_multiprocessing_distributed_tpu import models  # noqa: E402
from pytorch_multiprocessing_distributed_tpu.analysis import (  # noqa: E402
    check as graftcheck)
from pytorch_multiprocessing_distributed_tpu.analysis import (  # noqa: E402
    meter)
from pytorch_multiprocessing_distributed_tpu.analysis.sentinels import (  # noqa: E402
    guard_transfers, recompile_budget)
from pytorch_multiprocessing_distributed_tpu.inference.generate import (  # noqa: E402
    generate_kv_bytes)
from pytorch_multiprocessing_distributed_tpu.runtime import hbm  # noqa: E402
from pytorch_multiprocessing_distributed_tpu.serving import (  # noqa: E402
    ServingEngine, init_params)
from pytorch_multiprocessing_distributed_tpu.serving.kv_pages import (  # noqa: E402
    PagePool)
from pytorch_multiprocessing_distributed_tpu.serving.scheduler import (  # noqa: E402
    DONE)
from pytorch_multiprocessing_distributed_tpu.utils.compat import (  # noqa: E402
    memory_analysis_dict)


def _tiny():
    return models.get_model("gpt_tiny", attn_impl="xla")


# ------------------------------------------------- normalized analyses

class _FakeStats:
    argument_size_in_bytes = 100
    output_size_in_bytes = 40
    temp_size_in_bytes = 300
    alias_size_in_bytes = 30
    generated_code_size_in_bytes = 7
    host_argument_size_in_bytes = 0


class _FakeCompiled:
    def __init__(self, stats):
        self._stats = stats

    def memory_analysis(self):
        return self._stats


def test_memory_analysis_dict_reads_the_stats_attributes():
    want = {"argument_bytes": 100, "output_bytes": 40,
            "temp_bytes": 300, "alias_bytes": 30,
            "generated_code_bytes": 7,
            "peak_bytes": 100 + 40 + 300 + 7 - 30}
    assert memory_analysis_dict(_FakeCompiled(_FakeStats())) == want


def test_memory_analysis_dict_unavailable_is_none_never_zero():
    class Partial:
        def memory_analysis(self):
            return object()  # none of the expected attributes

    assert memory_analysis_dict(Partial()) is None
    assert memory_analysis_dict(_FakeCompiled(None)) is None


def test_memory_analysis_dict_real_compiled_program():
    fn = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    compiled = fn.lower(
        jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
    mem = memory_analysis_dict(compiled)
    assert mem is not None
    assert mem["argument_bytes"] == 64 * 64 * 4
    assert mem["peak_bytes"] > 0


def test_costs_record_math_and_null_safety():
    rec = meter.costs_record({"flops": 1000.0, "bytes accessed": 250.0},
                             {k: 1 for k in (
                                 "argument_bytes", "output_bytes",
                                 "temp_bytes", "alias_bytes",
                                 "generated_code_bytes", "peak_bytes")})
    assert rec["flops"] == 1000
    assert rec["bytes_accessed"] == 250
    assert rec["arithmetic_intensity"] == 4.0
    assert rec["memory"]["temp_bytes"] == 1
    empty = meter.costs_record(None, None)
    assert empty == {"flops": None, "bytes_accessed": None,
                     "arithmetic_intensity": None, "memory": None}


# ------------------------------------------- committed-budget compare

def _rec(flops=100, temp=1 << 20):
    return {"flops": flops, "bytes_accessed": 50,
            "arithmetic_intensity": 2.0,
            "memory": {"argument_bytes": 10, "output_bytes": 10,
                       "temp_bytes": temp, "alias_bytes": 0,
                       "generated_code_bytes": 0,
                       "peak_bytes": 20 + temp}}


def test_compare_costs_memory_drift_named_in_mib():
    committed = {"prog": _rec(temp=1 << 20)}
    traced = {"prog": _rec(temp=3 << 20)}
    findings = meter.compare_costs(traced, committed, full_scope=True)
    rules = {f.rule for f in findings}
    assert rules == {"GM102"}
    joined = " | ".join(f.message for f in findings)
    assert "memory.temp_bytes" in joined
    assert "+2.00 MiB temp" in joined
    assert all(f.program == "prog" for f in findings)


def test_compare_costs_flops_drift_and_coverage():
    committed = {"prog": _rec(), "stale": _rec()}
    traced = {"prog": _rec(flops=999), "fresh": _rec()}
    findings = meter.compare_costs(traced, committed, full_scope=True)
    by_rule = {}
    for f in findings:
        by_rule.setdefault(f.rule, []).append(f)
    assert {f.program for f in by_rule["GM101"]} == {"prog"}
    assert "committed 100 -> traced 999" in by_rule["GM101"][0].message
    # fresh has no committed entry, stale names no program
    assert {f.program for f in by_rule["GM103"]} == {"fresh", "stale"}


def test_compare_costs_failed_program_entry_not_stale():
    committed = {"broken": _rec()}
    findings = meter.compare_costs({}, committed, full_scope=True,
                                   failed=frozenset({"broken"}))
    assert findings == []


def test_tampered_costs_json_turns_gate_red(tmp_path):
    """Re-measure ONE cheap real program against a doctored costs
    snapshot: the gate goes red with program + rule + MiB delta."""
    payload = json.load(open(meter.default_costs_path()))
    name = "collectives_all_reduce"
    payload["programs"][name]["memory"]["temp_bytes"] += 5 << 20
    doctored = tmp_path / "costs.json"
    doctored.write_text(json.dumps(payload))
    findings, _records, _skipped = graftcheck.run_check(
        [name], costs=str(doctored))
    assert [(f.program, f.rule) for f in findings] == [(name, "GM102")]
    assert "-5.00 MiB temp" in findings[0].message


def test_costs_committed_for_every_registry_program():
    """Acceptance pin: analysis/costs.json carries a budget (with
    flops, bytes and a full memory record) for ALL registry programs
    — the clean-gate half is test_graftcheck's tier-1 gate, which now
    compares costs in the same pass."""
    from pytorch_multiprocessing_distributed_tpu.analysis.programs import (
        collect)

    committed = meter.load_costs()
    names = {s.name for s in collect()}
    assert names == set(committed)
    assert len(names) >= 15
    for name, rec in committed.items():
        assert rec["flops"] and rec["flops"] > 0, name
        assert rec["bytes_accessed"] and rec["bytes_accessed"] > 0, name
        assert rec["memory"] is not None, name
        assert rec["memory"]["peak_bytes"] > 0, name


# --------------------------------------------------------- the ledger

def test_ledger_register_update_release_snapshot():
    ledger = hbm.HbmLedger()
    ledger.register("a.params", 1000, "params")
    ledger.register("b.pool", 500, "kv", slots=4)
    assert ledger.total_bytes == 1500
    ledger.update("b.pool", 700)
    assert ledger.total_bytes == 1700
    snap = ledger.snapshot()
    assert snap["hbm_total_bytes"] == 1700
    assert snap["hbm_params_bytes"] == 1000
    assert snap["hbm_kv_bytes"] == 700
    assert snap["hbm_kv_b_pool_bytes"] == 700
    assert snap["hbm_entries"] == 2
    assert ledger.breakdown() == {"params": {"a.params": 1000},
                                  "kv": {"b.pool": 700}}
    ledger.release("a.params")
    ledger.release("a.params")  # idempotent
    assert ledger.total_bytes == 700
    with pytest.raises(KeyError):
        ledger.update("never.registered", 1)
    with pytest.raises(ValueError):
        ledger.register("bad", -1)
    # re-registration replaces, never double-counts
    ledger.register("b.pool", 900, "kv")
    assert ledger.total_bytes == 900


def test_module_level_registration_is_noop_disarmed():
    assert hbm.active_ledger() is None
    hbm.register("ghost", 123)  # must not raise, must not retain
    hbm.release("ghost")
    with hbm.scoped_ledger() as ledger:
        hbm.register("real", 42, "other")
        assert ledger.total_bytes == 42
    assert hbm.active_ledger() is None


def test_nbytes_helpers():
    x = jnp.zeros((4, 8), jnp.bfloat16)
    assert hbm.nbytes_of(x) == 4 * 8 * 2
    assert hbm.nbytes_of(jax.ShapeDtypeStruct((3,), jnp.int32)) == 12
    assert hbm.tree_nbytes({"a": x, "b": {"c": jnp.zeros((2,),
                                                         jnp.float32)}}
                           ) == 64 + 8
    with pytest.raises(TypeError):
        hbm.nbytes_of("not an array")


def test_slot_pool_per_slot_math_matches_allocation():
    model = _tiny()
    s_max = 32
    # dense parity: every slot's worst case, plus the scratch page
    pool = PagePool(model, 4, s_max, page_size=8)
    assert (PagePool.per_slot_kv_bytes(model, s_max) * 4
            + pool.page_bytes
            == pool.k_pages.nbytes + pool.v_pages.nbytes)
    assert pool.per_slot_bytes == (
        PagePool.per_slot_kv_bytes(model, s_max)
        + PagePool.per_slot_state_bytes())
    assert pool.hbm_bytes == (
        pool.k_pages.nbytes + pool.v_pages.nbytes
        + pool.positions.nbytes + pool.last_tokens.nbytes
        + pool.active.nbytes + pool.budgets.nbytes
        + pool.eos_ids.nbytes
        + 4 * pool.max_slots * pool.pages_per_slot)  # the int32 table


def test_engine_ledger_sites_and_armed_steady_state_sentinels():
    """ONE engine, both acceptance pins. (a) Allocation sites: params
    + KV pool + slot state at construction, per-bucket decode-program
    temps the step their signature first compiles — with the exact
    bytes the arrays/compiled executable report. (b) Armed cost:
    steady-state re-serve under ``guard_transfers`` +
    ``recompile_budget(0)`` stays green with the ledger ARMED — temp
    metering only rides FRESH compiles (AOT lowering, invisible to
    the jit cache), so a warm engine never re-measures anything."""
    model = _tiny()
    params = init_params(model, 3)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, model.vocab_size, (n,)).tolist()
               for n in (3, 9)]
    with hbm.scoped_ledger() as ledger:
        engine = ServingEngine(model, params, max_slots=2, s_max=24,
                               min_bucket=16, decode_horizon=2)
        entries = ledger.entries()
        assert entries["serving.params"][1] == hbm.tree_nbytes(params)
        assert entries["serving.kv_pages"][1] == (
            engine.pool.k_pages.nbytes + engine.pool.v_pages.nbytes)
        assert "serving.slot_state" in entries
        served = engine.serve([(p, 4) for p in prompts])  # warm
        assert all(r.state == DONE for r in served)
        temps = {name: row for name, row in ledger.entries().items()
                 if name.startswith("serving.decode_temp_w")}
        # one temp entry per compiled (window, horizon) signature
        assert len(temps) == len(engine.decode_programs)
        assert temps  # the serve really compiled decode programs
        for (w, h) in engine.decode_programs:
            name = f"serving.decode_temp_w{w}_h{h}"
            assert temps[name][0] == "temps"
            assert temps[name][1] == engine.decode_program_analysis(
                w, h)["memory"]["temp_bytes"]
        # (b) steady state: everything warm — zero compiles, zero
        # transfers, zero re-measurement, gauges still live
        compiles = engine.decode_step_compiles
        syncs_before = engine.metrics.snapshot()["decode_host_syncs"]
        total_before = ledger.total_bytes
        with guard_transfers():
            with recompile_budget(engine._decode, 0,
                                  label="armed-ledger steady state"):
                finished = engine.serve([(p, 4) for p in prompts])
        assert all(r.state == DONE for r in finished)
        assert engine.decode_step_compiles == compiles
        assert ledger.total_bytes == total_before  # nothing re-measured
        assert (engine.metrics.snapshot()["decode_host_syncs"]
                > syncs_before)
        snap = ledger.snapshot()
        assert snap["hbm_total_bytes"] > 0
        assert snap["hbm_params_bytes"] > 0


# --------------------------------------------------- capacity planner

def test_plan_capacity_inverts_real_allocation():
    """The acceptance criterion: the planner's slot prediction matches
    actual CPU-backend allocation within the documented tolerance
    (0.5%; byte-exact in practice — both sides share one shape x
    dtype product)."""
    model = _tiny()
    params = init_params(model, 0)
    params_bytes = hbm.tree_nbytes(params)
    s_max = 32
    per_slot = (PagePool.per_slot_kv_bytes(model, s_max)
                + PagePool.per_slot_state_bytes())
    plan = meter.plan_capacity(
        model, s_max, params_bytes + 5 * per_slot + 100, params=params)
    assert plan["max_slots"] == 5
    assert plan["per_slot_bytes"] == per_slot
    assert plan["headroom_bytes"] == 100
    assert plan["fits"]
    # pages at the default num_pages hold every slot's worst case,
    # plus the scratch page and the int32 page table
    pool = PagePool(model, plan["max_slots"], s_max, page_size=8)
    predicted = plan["max_slots"] * plan["per_slot_bytes"]
    actual = (pool.hbm_bytes - pool.page_bytes
              - 4 * pool.max_slots * pool.pages_per_slot)
    assert abs(predicted - actual) / actual <= 0.005
    # byte-exact today — a drift past the pin means allocator and
    # planner no longer share their shape math
    assert predicted == actual


def test_plan_capacity_abstract_params_and_edges():
    model = _tiny()
    plan = meter.plan_capacity(model, 32, 1 << 40)
    # eval_shape'd params match the initialized tree's bytes
    assert plan["params_bytes"] == hbm.tree_nbytes(init_params(model, 0))
    assert plan["max_slots"] > 0
    tight = meter.plan_capacity(model, 32, plan["params_bytes"] + 1)
    assert tight["max_slots"] == 0 and tight["fits"]
    over = meter.plan_capacity(model, 32, 10, optimizer_moments=2)
    assert not over["fits"] and over["max_slots"] == 0
    assert over["opt_state_bytes"] == 2 * over["params_bytes"]
    with pytest.raises(ValueError):
        meter.plan_capacity(model, 32, 0)


def test_plan_generate_batch_matches_generate_kv_bytes():
    model = _tiny()
    params = init_params(model, 0)
    budget = hbm.tree_nbytes(params) + 3 * generate_kv_bytes(
        model, 1, 64) + 5
    plan = meter.plan_capacity(model, 64, budget, params=params)
    assert plan["max_generate_batch"] == 3


# ----------------------------------------------------------- roofline

def test_roofline_classification_and_null_safety():
    # intensity 2 FLOP/B on a chip whose ridge is at 10 FLOP/B:
    # bandwidth-bound, ceiling = 2 * bw
    eff = meter.roofline(flops=2000, bytes_accessed=1000,
                         step_seconds=1.0, peak_flops=1e6,
                         peak_bw=1e5)
    assert eff["roofline_bound"] == "memory"
    assert eff["roofline_flops_per_sec"] == 2e5
    assert eff["roofline_frac"] == pytest.approx(0.01)
    assert eff["mfu"] == pytest.approx(0.002)
    # high intensity: compute-bound, ceiling = peak
    eff = meter.roofline(2e6, 10.0, 1.0, 1e6, 1e5)
    assert eff["roofline_bound"] == "compute"
    assert eff["roofline_flops_per_sec"] == 1e6
    # null inputs null the dependent outputs, never fake numbers
    eff = meter.roofline(None, None, 1.0, None, None)
    assert all(v is None for v in eff.values())
    eff = meter.roofline(100, 50, 0.0, 1e6, 1e5)
    assert all(v is None for v in eff.values())


def test_bench_chip_tables_align():
    """Every chip generation with a FLOPs peak has an HBM-bandwidth
    peak (the roofline needs both axes)."""
    import importlib.util as _il

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = _il.spec_from_file_location(
        "bench_mod", os.path.join(repo, "bench.py"))
    bench = _il.module_from_spec(spec)
    spec.loader.exec_module(bench)
    assert ([k for k, _ in bench.PEAK_FLOPS]
            == [k for k, _ in bench.PEAK_HBM_BW])


# ------------------------------------------------------ the artifacts

def test_draw_hbm_breakdown_renders(tmp_path):
    pytest.importorskip("matplotlib")
    from pytorch_multiprocessing_distributed_tpu.utils.plotting import (
        draw_hbm_breakdown)

    ledger = hbm.HbmLedger()
    ledger.register("train.params", 3 << 20, "params")
    ledger.register("serving.kv_pool", 2 << 20, "kv")
    out = draw_hbm_breakdown(ledger.breakdown(),
                             str(tmp_path / "hbm.png"),
                             budget_bytes=8 << 20)
    assert os.path.getsize(out) > 0
    # flat dict accepted too (one-category convenience shape)
    out2 = draw_hbm_breakdown({"params": 100, "kv": 50},
                              str(tmp_path / "flat.png"))
    assert os.path.getsize(out2) > 0
    with pytest.raises(ValueError):
        draw_hbm_breakdown({}, str(tmp_path / "empty.png"))


def test_serving_bench_point_carries_hbm_and_mfu_fields():
    """Every sweep point records its resident HBM and the efficiency
    attribution beside throughput (mfu None off-TPU — never faked)."""
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from benchmarks.serving_bench import run_point

    model = _tiny()
    params = init_params(model, 0)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, model.vocab_size, (5,)).tolist()
               for _ in range(2)]
    # s_max of whole pages (2 x 16): a slot's worst case is exact
    r = run_point(model, params, prompts, 3, 2, float("inf"), 32)
    assert r["hbm_resident_bytes"] > 0
    assert r["hbm_per_slot_bytes"] == (
        PagePool.per_slot_kv_bytes(model, 32)
        + PagePool.per_slot_state_bytes())
    assert "mfu" in r
    assert r["decode_flops_per_dispatch"] > 0
    if jax.devices()[0].platform != "tpu":
        assert r["mfu"] is None
    assert hbm.active_ledger() is None  # run_point disarms


# --------------------------------------------------- make-meter smoke

def test_meter_smoke_end_to_end(tmp_path):
    """The ``make meter`` body, in-process: canary budgets re-measure
    clean, the planner round-trips against a real pool, pmdt_hbm_*
    gauges serve live, and the breakdown PNG renders — every
    assertion lives in benchmarks/meter_smoke.py so the CI target and
    this tier-1 test can never drift apart."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "meter_smoke", os.path.join(repo, "benchmarks",
                                    "meter_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = mod.run(str(tmp_path))
    assert out["plan"]["max_slots"] == 4
    assert out["samples"]["pmdt_hbm_total_bytes"] > 0
    assert hbm.active_ledger() is None  # smoke disarms


@pytest.mark.slow
def test_full_registry_meter_standalone():
    """The meter CLI's own full pass (the `make check` gate already
    compares costs in tier-1; this slow twin pins the standalone
    entry point + JSON contract)."""
    findings, records, skipped = meter.run_meter()
    assert not findings, "\n".join(f.render() for f in findings)
    assert not skipped
    assert len(records) >= 15
