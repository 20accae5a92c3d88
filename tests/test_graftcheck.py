"""graftcheck: the jaxpr-level program auditor and its tier-1 gate.

Three layers, mirroring test_graftlint:
- toy programs with KNOWN audit answers: exact psum count/bytes under
  shard_map (scan-multiplied), a deliberately dropped donation, a
  forced bf16->f32 upcast on a matmul path, fingerprint drift with a
  readable op-delta diff;
- the registry/compare machinery: coverage of the serving decode
  ladder, tampered-snapshot detection naming program + rule;
- THE gate: every registered canonical program audits clean against
  the committed ``analysis/fingerprints.json`` (the tier-1 twin of
  ``make check``).

Skips cleanly when jax cannot import.
"""

import json

import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from pytorch_multiprocessing_distributed_tpu.analysis import ir  # noqa: E402
from pytorch_multiprocessing_distributed_tpu.analysis import (  # noqa: E402
    check as graftcheck)
from pytorch_multiprocessing_distributed_tpu.analysis.programs import (  # noqa: E402
    ProgramSpec, RULES_GC, audit_program, collect)
from pytorch_multiprocessing_distributed_tpu.parallel.mesh import (  # noqa: E402
    audit_mesh)
from jax import shard_map  # noqa: E402

P = jax.sharding.PartitionSpec


def _spec(name, build, min_devices=1):
    return ProgramSpec(name=name, min_devices=min_devices, build=build,
                       module="test")


# ---------------------------------------------------------------- toys

def test_psum_budget_exact_count_and_bytes():
    """One psum of a [4] f32 per-shard payload over the data axis:
    the budget reads exactly 1 call / 16 bytes at psum@data."""
    mesh = audit_mesh(data=8)

    def body(x):
        return jax.lax.psum(x, "data")

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("data"),),
                           out_specs=P(None), check_vma=False))
    closed = ir.trace(fn, jax.ShapeDtypeStruct((32,), jnp.float32))
    assert ir.collective_budget(closed) == {
        "psum@data": {"count": 1, "bytes": 16}}


def test_scan_trip_count_multiplies_budget():
    """A psum inside a length-5 scan body is 5 dynamic calls — the
    budget counts executions, not equations."""
    mesh = audit_mesh(data=8)

    def body(c, xs):
        def step(c, x):
            return c + jax.lax.psum(x, "data"), c

        return jax.lax.scan(step, c, xs)

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(P(), P(None)),
                           out_specs=(P(), P(None)), check_vma=False))
    closed = ir.trace(fn, jax.ShapeDtypeStruct((), jnp.float32),
                      jax.ShapeDtypeStruct((5,), jnp.float32))
    budget = ir.collective_budget(closed)
    assert budget["psum@data"]["count"] == 5
    assert budget["psum@data"]["bytes"] == 5 * 4


def test_declared_collective_budget_mismatch_is_gc101():
    mesh = audit_mesh(data=8)

    def body(x):
        return jax.lax.psum(jax.lax.psum(x, "data"), "data")  # doubled

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("data"),),
                           out_specs=P(None), check_vma=False))

    def build():
        return {"fn": fn,
                "args": (jax.ShapeDtypeStruct((32, 4), jnp.float32),),
                "expect_collectives": {
                    "psum@data": {"count": 1, "bytes": 16}}}

    record, findings = audit_program(_spec("doubled_psum", build))
    assert [f.rule for f in findings] == ["GC101"]
    assert record["collectives"]["psum@data"]["count"] == 2


def test_grad_sized_psum_invariant():
    """expect_grad_psums counts psums whose PER-CALL bytes equal the
    parameter tree exactly — a second grad-sized reduction (the
    doubled-grad-psum bug class) trips GC101."""
    mesh = audit_mesh(data=8)
    pb = 4 * 8  # [8] f32 "params"

    def once(g):
        return jax.lax.pmean(g, "data")

    def twice(g):
        return jax.lax.psum(jax.lax.pmean(g, "data"), "data")

    for body, expect_ok in ((once, True), (twice, False)):
        fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(P(),),
                               out_specs=P(), check_vma=False))

        def build(fn=fn):
            return {"fn": fn,
                    "args": (jax.ShapeDtypeStruct((8,), jnp.float32),),
                    "params_bytes": pb, "expect_grad_psums": 1}

        record, findings = audit_program(_spec("grad_psum", build))
        if expect_ok:
            assert not findings
            assert record["grad_sized_psums"] == 1
        else:
            assert [f.rule for f in findings] == ["GC101"]
            assert "gradient all-reduce contract" in findings[0].message


def test_dropped_donation_is_gc102():
    """The exact acceptance scenario in miniature: a state-in/state-out
    jit whose donate_argnums was deleted — the lowered module aliases
    nothing, and min_donated turns that into a named finding."""
    def step(state, x):
        return jax.tree.map(lambda s: s + x.sum(), state)

    state = {"w": jax.ShapeDtypeStruct((4, 4), jnp.float32)}
    x = jax.ShapeDtypeStruct((4, 4), jnp.float32)

    def build_donating():
        fn = jax.jit(step, donate_argnums=(0,))
        return {"fn": fn, "args": (state, x), "lower_fn": fn,
                "min_donated": 1}

    def build_dropped():
        fn = jax.jit(step)  # donate_argnums deleted
        return {"fn": fn, "args": (state, x), "lower_fn": fn,
                "min_donated": 1}

    record, findings = audit_program(_spec("donating", build_donating))
    assert not findings
    assert record["donation"]["aliased"] >= 1

    record, findings = audit_program(_spec("dropped", build_dropped))
    assert [f.rule for f in findings] == ["GC102"]
    assert "donate_argnums" in findings[0].message
    assert record["donation"]["aliased"] == 0


def test_forced_f32_upcast_on_matmul_path_detected():
    """bf16 activations upcast to f32 feeding a dot_general count (and
    size) in the dtype audit; keeping the matmul in bf16 — or an f32
    island that feeds only a softmax — does not."""
    a = jax.ShapeDtypeStruct((16, 32), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((32, 8), jnp.float32)

    def upcast(x, k):
        return x.astype(jnp.float32) @ k

    def stays_bf16(x, k):
        return (x @ k.astype(jnp.bfloat16)).astype(jnp.float32)

    def f32_island_no_matmul(x, k):
        del k
        return jax.nn.softmax(x.astype(jnp.float32), axis=-1)

    got = ir.dtype_promotions(ir.trace(jax.jit(upcast), a, w))
    assert got == {"count": 1, "bytes": 16 * 32 * 2}
    assert ir.dtype_promotions(
        ir.trace(jax.jit(stays_bf16), a, w))["count"] == 0
    assert ir.dtype_promotions(
        ir.trace(jax.jit(f32_island_no_matmul), a, w))["count"] == 0


def test_fingerprint_drift_readable_diff():
    """Mutating a program changes the digest, and the comparison
    renders a HUMAN diff naming the op delta."""
    x = jax.ShapeDtypeStruct((8, 8), jnp.float32)

    def original(v):
        return v @ v

    def mutated(v):
        return jnp.tanh(v @ v)

    fp_old = ir.fingerprint(ir.trace(jax.jit(original), x))
    fp_new = ir.fingerprint(ir.trace(jax.jit(mutated), x))
    assert fp_old["digest"] != fp_new["digest"]
    delta = ir.diff_histograms(fp_old["ops"], fp_new["ops"])
    assert "+1 tanh" in delta

    findings = graftcheck.compare(
        {"prog": {"fingerprint": fp_new, "collectives": {},
                  "dtype_promotions": {"count": 0, "bytes": 0}}},
        {"prog": {"fingerprint": fp_old, "collectives": {},
                  "dtype_promotions": {"count": 0, "bytes": 0}}},
        full_scope=True)
    assert [f.rule for f in findings] == ["GC105"]
    assert "prog" == findings[0].program
    assert "+1 tanh" in findings[0].message


def test_deleted_grad_psum_declaration_still_flags():
    """Presence-or semantics: deleting the inline expect_grad_psums
    declaration (traced record loses the field while the committed
    entry keeps it) must flag, not silently disable the invariant —
    and the symmetric tamper (field dropped from the snapshot) too."""
    fp = {"digest": "d", "eqns": 1, "ops": {}}
    base = {"fingerprint": fp, "collectives": {},
            "dtype_promotions": {"count": 0, "bytes": 0}}
    with_field = dict(base, grad_sized_psums=1)
    for committed, traced in ((with_field, base), (base, with_field)):
        findings = graftcheck.compare({"p": dict(traced)},
                                      {"p": dict(committed)},
                                      full_scope=True)
        assert [f.rule for f in findings] == ["GC101"]
        assert "None" in findings[0].message


def test_compare_flags_budget_and_dtype_drift():
    fp = {"digest": "d", "eqns": 1, "ops": {"dot_general": 1}}
    base = {"fingerprint": fp,
            "collectives": {"psum@data": {"count": 1, "bytes": 16}},
            "dtype_promotions": {"count": 0, "bytes": 0}}
    drifted = {"fingerprint": fp,
               "collectives": {"psum@data": {"count": 2, "bytes": 32}},
               "dtype_promotions": {"count": 3, "bytes": 4096}}
    findings = graftcheck.compare({"p": drifted}, {"p": base},
                                  full_scope=True)
    rules = sorted(f.rule for f in findings)
    assert rules == ["GC101", "GC104"]
    msg = next(f.message for f in findings if f.rule == "GC101")
    assert "committed" in msg and "traced" in msg


# ------------------------------------------------- registry / coverage

def test_registry_covers_the_canonical_programs():
    names = {s.name for s in collect()}
    for required in ("train_step_dp_resnet18", "lm_step_dp",
                     "lm_step_tp", "lm_step_fsdp", "lm_step_moe",
                     "generate_dense", "generate_tp",
                     "collectives_all_reduce", "moe_mlp_ep"):
        assert required in names


def test_serving_ladder_fingerprints_cover_decode_programs():
    """Every (bucket, horizon) program the engine can ever compile —
    the ``buckets x {1, H}`` ladder ``engine.decode_programs`` draws
    from — has a registered audit program, so no runtime-reachable
    decode signature ships unfingerprinted."""
    from pytorch_multiprocessing_distributed_tpu.serving.engine import (
        audit_programs)

    names = {e["name"] for e in audit_programs()}
    # the hook's engine geometry: the ladder is pinned on the reduced
    # {8, 32} bucket set (one gather/scatter shape recipe per window)
    expected = {f"serving_decode_paged_w{w}_h{h}"
                for w in (8, 32) for h in (1, 4)}
    # graftspec: the draft+verify program, self-draft and draft-model
    expected |= {"serving_decode_spec_paged_w32_h4_k4",
                 "serving_decode_spec_draft_w32_h4_k4"}
    # graftquant: the int8-KV decode step beside its model-dtype twin
    # at the same geometry — the costs.json pair is what pins the KV
    # argument-bytes halving
    expected |= {"serving_decode_quant_paged_w32_h4",
                 "serving_decode_quantref_paged_w32_h4"}
    # graftlink: admit_prefilled's insert program, budgeted at ZERO
    # collectives (the device put IS the transfer)
    expected |= {"serving_transfer_insert_paged_w32"}
    assert names == expected
    committed = graftcheck.load_fingerprints(
        graftcheck.default_fingerprints_path())
    assert expected <= set(committed)


def test_tampered_fingerprint_turns_gate_red(tmp_path):
    """Re-trace ONE cheap real program against a doctored snapshot:
    the gate goes red with the program and rule named and the digest
    delta in the message."""
    src = graftcheck.default_fingerprints_path()
    payload = json.load(open(src))
    name = "serving_decode_paged_w8_h1"
    payload["programs"][name]["fingerprint"]["digest"] = "0" * 16
    doctored = tmp_path / "fingerprints.json"
    doctored.write_text(json.dumps(payload))
    findings, records, skipped = graftcheck.run_check(
        [name], fingerprints=str(doctored))
    assert [(f.program, f.rule) for f in findings] == [(name, "GC105")]
    assert "0000000000000000" in findings[0].message


def test_update_keeps_entries_when_a_build_fails(tmp_path, monkeypatch):
    """--update must not prune the committed entry of a program whose
    build/trace just failed (GC100): records for it are absent, but
    its budget history is not stale — losing it would launder the
    breakage into a GC106 'never existed'."""
    from pytorch_multiprocessing_distributed_tpu.analysis.programs import (
        Finding as GCFinding)

    committed = tmp_path / "fp.json"
    committed.write_text(json.dumps({"programs": {
        "healthy": {"fingerprint": {"digest": "a", "eqns": 1,
                                    "ops": {}}},
        "broken": {"fingerprint": {"digest": "b", "eqns": 1,
                                   "ops": {}}},
    }}))

    def fake_audits(names=None, devices=None):
        return ({"healthy": {"fingerprint": {"digest": "a2", "eqns": 1,
                                             "ops": {}}}},
                [GCFinding("broken", "GC100", "build exploded")], [])

    monkeypatch.setattr(graftcheck, "run_audits", fake_audits)
    findings, records, skipped = graftcheck.run_check(
        update=True, fingerprints=str(committed))
    assert [f.rule for f in findings] == ["GC100"]
    kept = json.load(open(committed))["programs"]
    assert set(kept) == {"healthy", "broken"}
    assert kept["broken"]["fingerprint"]["digest"] == "b"
    assert kept["healthy"]["fingerprint"]["digest"] == "a2"


def test_unknown_program_name_is_a_usage_error():
    with pytest.raises(KeyError):
        collect(["no_such_program"])
    assert graftcheck.main(["--programs", "no_such_program"]) == 2


def test_cli_json_contract(capsys):
    rc = graftcheck.main(
        ["--programs", "serving_decode_paged_w8_h1", "--json"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 0 and payload["ok"]
    assert payload["programs"] == ["serving_decode_paged_w8_h1"]
    assert payload["findings"] == []


def test_rule_table_is_documented():
    assert set(RULES_GC) == {f"GC10{i}" for i in range(7)}
    rc = graftcheck.main(["--list-rules"])
    assert rc == 0


# ------------------------------------------------------------ THE gate

def test_package_audit_green_tier1_gate():
    """THE gate (the in-process twin of ``make check``): every
    registered canonical program audits clean against the committed
    budgets/fingerprints. Red here means a hot program's
    communication, donation, sharding or dtype contract changed — fix
    it, or re-baseline DELIBERATELY with ``make check-update`` and
    justify the JSON diff in the PR."""
    findings, records, skipped = graftcheck.run_check()
    assert not skipped, (
        "programs skipped on the tier-1 mesh (device-count "
        f"regression?): {skipped}")
    assert not findings, "graftcheck gate RED:\n" + "\n".join(
        f.render() for f in findings)
    committed = graftcheck.load_fingerprints(
        graftcheck.default_fingerprints_path())
    assert set(records) == set(committed)
