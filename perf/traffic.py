"""Traffic generation: one general generator, driven by a data file.

A traffic mix is ``perf/traffic/<name>.json``. Its ``kind`` says which
driver runs it (``train`` or ``serve``); every other key is a parameter
read here or by that driver. A later PR adds a mix by adding a file.

Seeds. The SIZES of a serving mix (prompt and output lengths, and an
open loop's arrival gaps) are drawn once from the mix's own
``size_seed``, so every ``--seed`` offers the same multiset of work;
``--seed`` permutes their order and draws the token ids. A seed that
changed the sizes would change the work, and runs with different seeds
would differ by more than the system's own noise.
"""

from __future__ import annotations

import math
from typing import Iterator, List, NamedTuple

import numpy as np

# numpy seeds are 32-bit words; the driver's seeds can pass 2**31
_SEED_MASK = 0xFFFFFFFF


def rng_for(seed: int, stream: int = 0) -> np.random.Generator:
    """Independent generator for (seed, stream); any whole number."""
    seed = int(seed)
    return np.random.default_rng(
        [seed & _SEED_MASK, (seed >> 32) & _SEED_MASK, stream])


def draw_lengths(spec: dict, n: int, rng: np.random.Generator
                 ) -> np.ndarray:
    """``n`` whole lengths from a distribution spec:

    ``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``
    ``{"dist": "uniform", "min": a, "max": b}``
    ``{"dist": "fixed", "value": v}``

    Values are rounded and clipped to ``[min, max]``."""
    dist = spec["dist"]
    if dist == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    lo, hi = int(spec["min"]), int(spec["max"])
    if not 1 <= lo <= hi:
        raise ValueError(f"length range [{lo}, {hi}] is empty or < 1")
    if dist == "uniform":
        return rng.integers(lo, hi + 1, size=n)
    if dist == "lognormal":
        raw = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
        return np.clip(np.rint(raw), lo, hi).astype(np.int64)
    raise ValueError(f"unknown length distribution {dist!r}")


class RequestSpec(NamedTuple):
    index: int
    prompt: np.ndarray      # int32 token ids
    max_new_tokens: int
    due_s: float            # open loop: offset from the window's start


class ServeTraffic:
    """The requests of one serving run, in the order they are offered.

    ``sizes`` come from the mix's ``size_seed``; ``--seed`` shuffles
    them and draws the token ids (uniform over the vocabulary, no
    shared prefixes unless the mix asks for them)."""

    def __init__(self, mix: dict, vocab_size: int, s_max: int,
                 seed: int):
        n = int(mix["pool_requests"])
        sizes = rng_for(mix["size_seed"], 1)
        prompts = draw_lengths(mix["prompt_len"], n, sizes)
        outputs = draw_lengths(mix["output_len"], n, sizes)
        # prompt + output must fit a slot: trim the output, never drop
        outputs = np.minimum(outputs, s_max - prompts)
        if outputs.min() < 1:
            raise ValueError("a prompt fills the whole slot: "
                             f"max prompt {prompts.max()} vs s_max {s_max}")
        gaps = None
        if mix["loop"] == "open":
            gaps = arrival_gaps(mix["arrivals"], n, sizes)
        order = rng_for(seed, 2).permutation(n)
        self.prompt_lens = prompts[order]
        self.output_lens = outputs[order]
        # arrivals keep their schedule; only which request arrives
        # when is permuted
        self.due_s = (np.cumsum(gaps) if gaps is not None
                      else np.zeros(n))
        # closed loop, "first_turn": "uniform_age": client k's first
        # request keeps this fraction of its output length (from the
        # size seed, like the sizes), so the batch starts as a steady
        # loop would find it: requests at every stage of their lives
        self.first_turn_fraction = rng_for(mix["size_seed"], 5).uniform(
            size=n)
        self.vocab_size = int(vocab_size)
        self._tokens = rng_for(seed, 3)
        self._next = 0
        self.n = n

    def take(self) -> RequestSpec:
        """The next request (wraps round a pool that runs out)."""
        i = self._next % self.n
        lap = self._next // self.n
        self._next += 1
        prompt = self._tokens.integers(
            0, self.vocab_size, size=int(self.prompt_lens[i]),
            dtype=np.int32)
        due = float(self.due_s[i]
                    + lap * (self.due_s[-1] if self.n else 0.0))
        return RequestSpec(self._next - 1, prompt,
                           int(self.output_lens[i]), due)


def arrival_gaps(spec: dict, n: int, rng: np.random.Generator
                 ) -> np.ndarray:
    """Seconds between consecutive arrivals of an open loop:

    ``{"process": "poisson", "rate_per_s": r}``
    ``{"process": "even", "rate_per_s": r}``
    ``{"process": "bursty", "rate_per_s": r, "burst": k}`` — Poisson
    bursts of ``k`` simultaneous requests at rate ``r / k``."""
    rate = float(spec["rate_per_s"])
    if rate <= 0:
        raise ValueError(f"rate_per_s must be > 0, got {rate}")
    process = spec["process"]
    if process == "even":
        return np.full(n, 1.0 / rate)
    if process == "poisson":
        return rng.exponential(1.0 / rate, n)
    if process == "bursty":
        k = int(spec["burst"])
        gaps = np.zeros(n)
        gaps[::k] = rng.exponential(k / rate, len(gaps[::k]))
        return gaps
    raise ValueError(f"unknown arrival process {process!r}")


class OpenLoop:
    """Offers requests on their schedule whatever the system does.

    A request is timed from when it was DUE, not from when the
    generator got round to submitting it, so a stall in the system (or
    a starved generator) is charged to the requests that waited for
    it. ``lateness_s`` records how late each submission really was."""

    def __init__(self, traffic: ServeTraffic):
        self.traffic = traffic
        self._pending = traffic.take()
        self.lateness_s: List[float] = []

    def due(self, elapsed_s: float) -> Iterator[RequestSpec]:
        """Every request due by ``elapsed_s`` and not yet offered."""
        while self._pending.due_s <= elapsed_s:
            self.lateness_s.append(elapsed_s - self._pending.due_s)
            yield self._pending
            self._pending = self.traffic.take()


def train_tokens(mix: dict, vocab_size: int, n_tokens: int,
                 seed: int) -> np.ndarray:
    """The training corpus of a run, from the seed."""
    corpus = mix["corpus"]
    if corpus == "synthetic_zipf":
        # the program's own synthetic corpus (train_lm.py without
        # --corpus): the cell is that job, so it reads that stream
        from pytorch_multiprocessing_distributed_tpu.data.lm import (
            synthetic_tokens)

        return synthetic_tokens(n_tokens, vocab_size=vocab_size,
                                seed=int(seed) & _SEED_MASK)
    if corpus == "uniform":
        return rng_for(seed, 4).integers(
            0, vocab_size, size=n_tokens, dtype=np.int32)
    raise ValueError(f"unknown corpus {corpus!r}")
