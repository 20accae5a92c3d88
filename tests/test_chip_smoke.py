"""``chip_smoke.py`` rehearsed on the CPU.

The script's own ``--platform cpu`` switch (gpt_tiny, a switch of the
script, not of the program) drives the same control flow the chip run
takes: phases as children, one after the other, a jax-free parent, a
failed child making the whole run fail loudly.
"""

import ast
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")

sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402  (jax-free by construction — see below)

DEFAULT_PHASES = ["probe", "train", "resnet", "kernels", "serve_paged",
                  "serve_paged_spec", "serve_paged_int8",
                  "serve_paged_int8_spec"]

# the parent under a tripwire: ANY ``import jax`` in it raises, while
# its children are fresh interpreters and import what they like
TRIPWIRE = ("import runpy, sys; sys.modules['jax'] = None; "
            "sys.argv = sys.argv[1:]; "
            "runpy.run_path(sys.argv[0], run_name='__main__')")


def _run(script, *args, cwd=REPO, timeout=600):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PMDT_FORCE_CPU_DEVICES")}
    proc = subprocess.run(
        [sys.executable, "-c", TRIPWIRE, script, *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=timeout)
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()]
    return proc.returncode, lines, proc.stderr


def test_parent_imports_jax_only_inside_the_kernels_child():
    """Source-level twin of the run-time tripwire: the one function that
    imports jax is the body of the ``--child-kernels`` process."""
    tree = ast.parse(open(SCRIPT).read())
    offenders = []
    for scope in tree.body:
        for node in ast.walk(scope):
            names = ([a.name for a in node.names]
                     if isinstance(node, ast.Import)
                     else [node.module or ""]
                     if isinstance(node, ast.ImportFrom) else [])
            if any(n.split(".")[0] in ("jax", "numpy", "flax") or
                   n.startswith("pytorch_multiprocessing_distributed_tpu")
                   for n in names):
                offenders.append(getattr(scope, "name", type(scope).__name__))
    assert set(offenders) == {"child_kernels"}


def test_chips_4_runs_only_dp_and_its_one_device_twin(tmp_path):
    """``--chips 4`` on four VIRTUAL devices: the DP phase and what it
    is compared with, no serving, no ResNet, and ``count`` 4 last."""
    out = tmp_path / "out"
    rc, lines, err = _run(SCRIPT, "--platform", "cpu", "--chips", "4",
                          "--out", str(out))
    assert rc == 0, err
    assert [ln.get("phase") for ln in lines[:-1]] == ["probe", "dp"]
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 4}}
    dp = lines[1]
    assert dp["dp4"]["device_count"] == 4 and dp["dp1"]["device_count"] == 1
    assert dp["dp4"]["placement"]["param_devices"] == 4
    assert dp["dp4"]["placement"]["batch_devices"] == 4
    assert dp["dp4"]["step_program"]["all_reduces"] > 0
    assert dp["dp4"]["losses"] == pytest.approx(dp["dp1"]["losses"],
                                                rel=2e-2)
    # every child the run started left its log: nothing but the two
    assert sorted(p.name for p in out.iterdir()) == ["dp1", "dp4",
                                                    "probe.err", "probe.out"]


def test_failing_children_fail_the_run_with_ok_false(tmp_path):
    """In a directory that holds ``chip_smoke.py`` and nothing else of
    the repo every phase after the probe fails; each is reported, none
    is passed over, and the last line is ``ok: false``."""
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(SCRIPT, alone)
    rc, lines, _ = _run(str(alone / "chip_smoke.py"), "--platform", "cpu",
                        cwd=str(alone))
    assert rc != 0
    assert [ln["phase"] for ln in lines[:-1]] == DEFAULT_PHASES
    assert [ln["ok"] for ln in lines[:-1]] == [True] + [False] * 7
    assert lines[-1] == {"ok": False, "failed": DEFAULT_PHASES[1:]}


def test_no_accelerator_fails_at_the_probe_and_runs_nothing_else(tmp_path):
    """As the driver runs it (no arguments) in a sandbox whose jax is
    held to the CPU: non-zero, no result, no other phase."""
    rc, lines, _ = _run(SCRIPT, "--out", str(tmp_path / "out"))
    assert rc != 0
    assert [ln.get("phase") for ln in lines] == ["probe", None]
    assert lines[0]["ok"] is False and "no accelerator" in lines[0]["error"]
    assert lines[-1] == {"ok": False, "failed": ["probe"]}


@pytest.mark.parametrize("flips, passes", [
    ({}, True),                       # identical streams
    ({"src-3": 5}, True),             # one bf16 near-tie, late
    ({f"src-{i}": 1 for i in range(8)}, False),  # a wrong kernel
    ({"src-0": 0}, False),            # prefill differs: not the same run
])
def test_stream_comparison_tolerates_a_near_tie_not_a_wrong_kernel(
        monkeypatch, flips, passes):
    """A Pallas run may part from its XLA run where a near-tie flips;
    streams that ALL part at their first decoded token are a wrong
    kernel (or wrong page/position plumbing) and fail the phase."""
    cfg = dict(chip_smoke.TINY, platform="cpu", chips=1, out="unused")
    base = {f"src-{i}": [10 * i + t for t in range(cfg["max_new"])]
            for i in range(8)}

    def fake_serve_lm(cfg, name, flags):
        streams = {u: list(t) for u, t in base.items()}
        if name.endswith("_pallas"):
            for uid, at in flips.items():
                streams[uid][at:] = [-1] * (cfg["max_new"] - at)
        snap = {"platform": "cpu", "device_kind": "cpu",
                "decode_attn": name.rsplit("_", 1)[1],
                "prefill_attn": "xla", "donate_cache": False,
                "spec_verify_passes": 3, "compile_cache_dir": None,
                "compile_s": 1.0, "cache_hits": 0,
                "tokens_generated": 8 * cfg["max_new"],
                "peak_hbm_bytes": [None]}
        return snap, streams, 1.0

    monkeypatch.setattr(chip_smoke, "serve_lm", fake_serve_lm)
    if passes:
        facts = chip_smoke.phase_serve(cfg, "paged_int8_spec")
        assert facts["streams_equal"] == f"{8 - len(flips)}/8"
    else:
        with pytest.raises(chip_smoke.PhaseFailed):
            chip_smoke.phase_serve(cfg, "paged_int8_spec")


def test_process_fleet_refuses_children_when_the_parent_holds_a_tpu(
        monkeypatch, tmp_path):
    """One process per chip: the spawner must refuse, not hang."""
    import jax

    from pytorch_multiprocessing_distributed_tpu.serving import (
        ProcessReplicaSpawner)

    started = []
    monkeypatch.setattr(subprocess, "Popen",
                        lambda *a, **k: started.append(a))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    spawner = ProcessReplicaSpawner(lambda *a: ["true"], str(tmp_path))
    with pytest.raises(RuntimeError, match="one process per chip"):
        spawner.spawn("s0")
    assert started == []


@pytest.mark.slow
def test_full_rehearsal_on_cpu(tmp_path):
    """Every default phase at gpt_tiny size (~4 min)."""
    rc, lines, err = _run(SCRIPT, "--platform", "cpu",
                          "--out", str(tmp_path / "out"), timeout=1500)
    assert rc == 0, err
    assert [ln.get("phase") for ln in lines[:-1]] == DEFAULT_PHASES
    assert all(ln["ok"] for ln in lines)
    assert lines[-1]["device"]["count"] == 1
