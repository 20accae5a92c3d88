"""The traffic generator: same seed, same requests; another seed,
other requests of the same sizes."""

import numpy as np
import pytest

from perf import harness, traffic


def _mix():
    return harness._load(harness.data_path("traffic", "serve.closed"))


def _take(seed, n=70):
    gen = traffic.ServeTraffic(_mix(), 50257, 1024, seed)
    return [gen.take() for _ in range(n)]


def test_same_seed_same_requests():
    a, b = _take(2 ** 31 + 7), _take(2 ** 31 + 7)
    for x, y in zip(a, b):
        assert x.max_new_tokens == y.max_new_tokens
        assert np.array_equal(x.prompt, y.prompt)


def test_other_seed_other_requests_same_sizes():
    mix = _mix()
    a = traffic.ServeTraffic(mix, 50257, 1024, 1)
    b = traffic.ServeTraffic(mix, 50257, 1024, 2)
    assert not np.array_equal(a.prompt_lens, b.prompt_lens)
    # the same multiset of (prompt, output) sizes, in another order
    assert sorted(zip(a.prompt_lens, a.output_lens)) == sorted(
        zip(b.prompt_lens, b.output_lens))
    assert not np.array_equal(a.take().prompt[:16], b.take().prompt[:16])


def test_lengths_respect_the_mix():
    mix = _mix()
    gen = traffic.ServeTraffic(mix, 50257, 1024, 0)
    p, o = gen.prompt_lens, gen.output_lens
    assert p.min() >= 16 and p.max() <= 768
    assert o.min() >= 16 and o.max() <= 256
    assert (p + o).max() <= 1024
    # 32 draws: the medians are near the mix's, not on them
    assert 120 < np.median(p) < 300 and 40 < np.median(o) < 100
    spec = gen.take()
    assert spec.prompt.dtype == np.int32
    assert 0 <= spec.prompt.min() and spec.prompt.max() < 50257


def test_open_loop_is_timed_from_the_due_time():
    mix = dict(_mix(), loop="open", pool_requests=64,
               arrivals={"process": "poisson", "rate_per_s": 100.0})
    loop = traffic.OpenLoop(traffic.ServeTraffic(mix, 257, 1024, 3))
    first = list(loop.due(0.5))
    assert first and all(r.due_s <= 0.5 for r in first)
    assert [r.due_s for r in first] == sorted(r.due_s for r in first)
    # the generator came 0.5 s into the schedule: every request it
    # offers then is late by 0.5 s minus its due time
    assert loop.lateness_s == pytest.approx(
        [0.5 - r.due_s for r in first])
    assert all(r.due_s > 0.5 for r in loop.due(1.0))


def test_arrival_processes():
    rng = traffic.rng_for(0)
    assert traffic.arrival_gaps({"process": "even", "rate_per_s": 4},
                                8, rng).tolist() == [0.25] * 8
    gaps = traffic.arrival_gaps({"process": "poisson", "rate_per_s": 50},
                                4000, rng)
    assert gaps.mean() == pytest.approx(0.02, rel=0.1)
    burst = traffic.arrival_gaps(
        {"process": "bursty", "rate_per_s": 50, "burst": 4}, 4000, rng)
    assert (burst[1::4] == 0).all() and burst.mean() == pytest.approx(
        0.02, rel=0.15)


def test_train_corpus_from_the_seed():
    mix = harness._load(harness.data_path("traffic", "train.1chip"))
    a = traffic.train_tokens(mix, 50257, 4096, 2 ** 31 + 5)
    b = traffic.train_tokens(mix, 50257, 4096, 2 ** 31 + 5)
    c = traffic.train_tokens(mix, 50257, 4096, 6)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.dtype == np.int32 and a.max() < 50257
