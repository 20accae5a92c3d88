"""The drivers end to end on the CPU at ``gpt_tiny`` (rehearsals 1 and
2 of the on-chip guide), and the real command without a TPU."""

import json
import os
import subprocess
import sys

import jax
import pytest

from perf import harness, run
from perf.tests import tiny


def _no_metric(line):
    assert line["metrics"] == {}, "a CPU run carries no metric"
    assert line["device"]["platform"] == "cpu"
    assert "rehearsal" in line


@pytest.mark.parametrize("chips", [1, 4])
def test_train_driver_end_to_end(chips):
    if len(jax.devices()) < chips:
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_"
                    "count=4 (the root conftest.py sets 8)")
    line = run.measure("rehearsal", 2 ** 31 + 11, 2.0, chips == 1,
                       cell=tiny.tiny_train_cell(chips), allow_cpu=True)
    _no_metric(line)
    checks = line["checks"]
    assert line["correct"], checks
    assert line["failed"] == 0 and line["attempted"] == checks["steps"] > 4
    assert checks["loss_gap"] < 1e-4          # float32 against float32
    assert checks["state_and_batch_on_every_chip"]
    assert checks["global_batch"] == 4 * chips
    assert checks["compiles_in_window"] == 0
    rehearsed = line["rehearsal"]
    assert rehearsed["end_to_end"]["train_tokens_per_s_chip"] > 0
    if chips == 1:      # the traced run reads the per-layer metrics
        layer = rehearsed["per_layer"]
        assert layer["step_ms_p50.train"] > 0
        assert layer["data_wait_ms.train"] >= 0
        assert layer["compile_s"] > 0


def test_serve_driver_end_to_end():
    line = run.measure("rehearsal", 2 ** 31 + 13, 2.0, True,
                       cell=tiny.tiny_serve_cell(), allow_cpu=True)
    _no_metric(line)
    checks = line["checks"]
    assert line["correct"], checks
    assert line["failed"] == 0 and line["attempted"] > 8
    assert checks["worst_logit_gap"] < 1e-3   # float32 against float32
    assert checks["wrong_length_streams"] == 0
    assert checks["compiles_in_window"] == 0
    layer = line["rehearsal"]["per_layer"]
    assert layer["occupancy_avg.serve"] > 50
    assert 0 < layer["host_syncs_per_token.serve"] <= 1
    assert layer["decode_step_ms_p50.serve"] > 0


def test_warmup_plan_reaches_every_bucket_and_window():
    from perf.drivers import serve

    class Pool:
        s_max = 1024

    class Engine:
        pool = Pool()
        min_bucket = 16
        decode_buckets = (16, 32, 64, 128, 256, 512, 1024)

    mix = harness._load(harness.data_path("traffic", "serve.closed"))
    plan = serve.warmup_requests(Engine(), mix)
    # one prompt per prefill bucket 16..1024 (768 lands in 1024)
    assert [p for p, _n in plan[:7]] == [16, 32, 64, 128, 256, 512, 768]
    # the first decode step after a prompt of p runs the smallest
    # window above p: windows 32..1024, each reached by some request
    firsts = {min(w for w in Engine.decode_buckets if w > p)
              for p, _n in plan}
    assert firsts == {32, 64, 128, 256, 512, 1024}
    assert all(p + n <= 1024 and n >= 2 for p, n in plan)


def test_real_command_without_a_tpu_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.PERF_DIR, "run.py"),
         "--workload", "gpt2-small.train.1chip", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == "", "no result without an accelerator"
    assert "no accelerator" in proc.stderr


def test_unknown_workload_is_refused():
    with pytest.raises(harness.ManifestError):
        harness.load_cell("no.such.cell")
    assert json.dumps(harness.load_manifest())     # plain JSON
