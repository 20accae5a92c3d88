"""perf/stats.py and perf/flops.py against hand-worked values."""

import json
import os

import pytest

from perf import flops, harness, stats


def test_percentile_interpolates():
    values = [10, 20, 30, 40, 50]
    assert stats.percentile(values, 0) == 10
    assert stats.percentile(values, 50) == 30
    assert stats.percentile(values, 95) == pytest.approx(48.0)
    assert stats.percentile(values, 100) == 50


def test_a_tail_needs_ten_samples_beyond_it():
    # 200 samples: rank 0.95 x 199 = 189.05, so indices 190..199 (ten
    # samples) lie beyond the percentile; 180 samples leave only nine
    assert stats.samples_beyond(200, 95) == 10
    assert stats.supported(200, 95)
    assert stats.samples_beyond(180, 95) == 9
    assert not stats.supported(180, 95)
    assert stats.supported_percentile(list(range(100)), 95) is None
    assert stats.supported_percentile(list(range(1001)), 95) == 950


def test_iqr_share_is_the_drivers_rule():
    values = [100, 101, 102, 103, 104, 105]
    # statistics.quantiles(n=4), exclusive: q1 = 100.75, q3 = 104.25
    assert stats.iqr_share(values) == pytest.approx(3.5 / 102.5)


def _config(name):
    with open(os.path.join(harness.PERF_DIR, "configs", name + ".json")) as f:
        return json.load(f)


def test_gpt2_small_costs_0_80_gflop_a_token():
    cfg = _config("gpt2-small")
    # 12 x (4 x 768^2 + 2 x 768 x 3072) + 768 x 50257
    assert flops.matmul_params(cfg) == 84_934_656 + 38_597_376
    # + 6 x 12 x 1024 x 768 of causal attention
    assert flops.train_flops_per_token(cfg, 1024) == pytest.approx(
        6 * 123_532_032 + 56_623_104)
    assert flops.train_flops_per_token(cfg, 1024) / 1e9 == pytest.approx(
        0.80, abs=0.005)


def test_gpt2_medium_kv_token_is_98304_bytes():
    cfg = _config("gpt2-medium")
    assert flops.kv_bytes_per_token(cfg) == 2 * 24 * 1024 * 2 == 98_304
    assert flops.matmul_params(cfg) == 24 * 12 * 1024 ** 2 + 1024 * 50257


def test_peaks_known_kind_and_unknown_kind():
    assert harness.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(harness.ManifestError):
        harness.load_peaks("TPU v9 imaginary")
    with pytest.raises(harness.ManifestError):
        harness.load_peaks("_source")
