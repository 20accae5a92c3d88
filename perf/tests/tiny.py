"""Tiny stand-ins for the rehearsal: the real cells' files with the
model swapped for ``gpt_tiny`` and every size cut, so the drivers run
end to end on the CPU in seconds. A rehearsal carries no metric."""

import dataclasses

from perf import harness

TINY_CONFIG = {
    "name": "gpt-tiny", "registry_name": "gpt_tiny", "model_kwargs": {},
    "vocab_size": 257, "n_positions": 256, "n_embd": 128, "n_layer": 4,
    "n_head": 4, "n_inner": None, "layer_norm_epsilon": 1e-6,
}


def tiny_train_cell(chips: int) -> harness.Cell:
    cell = harness.load_cell("gpt2-small.train.1chip")
    return dataclasses.replace(
        cell, config=TINY_CONFIG, chips=chips,
        options={**cell.options, "dtype": "float32", "per_chip_batch": 4,
                 "readback_every": 2},
        traffic={**cell.traffic, "seq_len": 64, "corpus_batches": 8,
                 "replicas": chips})


def tiny_serve_cell() -> harness.Cell:
    cell = harness.load_cell("gpt2-medium.serve.closed")
    return dataclasses.replace(
        cell, config=TINY_CONFIG,
        options={**cell.options, "dtype": "float32", "max_slots": 4,
                 "s_max": 128, "trace_seconds": 0.5},
        traffic={**cell.traffic, "pool_requests": 16,
                 "warmup_completions": 4,
                 "prompt_len": {"dist": "lognormal", "median": 24,
                                "sigma": 0.6, "min": 8, "max": 64},
                 "output_len": {"dist": "lognormal", "median": 12,
                                "sigma": 0.5, "min": 4, "max": 32}})
