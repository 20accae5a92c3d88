"""The benchmark's door for the two cells of the ``mimo-v2.5``
configuration, in tier-1: ``mimo-v2.5.serve.closed-1k8k`` and
``gpt2-medium.serve.open80`` hold their parameters; the
configuration equals its catalog row (``tests/fixtures/
mimo_v2_5_catalog_row.json``, the row of the ``model-configs`` guide's
``architectures.jsonl``, which is not in the repository) but for the
cut; the family door builds the registry model and counts the work the
mathematics requires; and the MiMo cell's driver, family door, engine
and reference run end to end at a tiny size on the CPU.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

from perf import families, harness
from perf.traffic import ServeTraffic

HERE = os.path.dirname(os.path.abspath(__file__))
MIMO_CELL = "mimo-v2.5.serve.closed-1k8k"
OPEN_CELL = "gpt2-medium.serve.open80"


def _reported(cell):
    return {m["name"] for m in cell.end_to_end + cell.per_layer}


def test_the_mimo_cell_holds_its_parameters():
    serve = harness.load_cell(MIMO_CELL)
    assert serve.chips == 1 and serve.kind == "serve"
    want = {"dtype": "bfloat16", "max_slots": 128, "s_max": 9216,
            "kv_dtype": "model", "page_size": 16, "num_pages": None,
            "prefill_chunk": 1024, "decode_horizon": 1,
            "decode_attn": "auto", "prefix_cache": 0, "draft_k": 0,
            "temperature": 0.0,
            # the engine's own ladder: the window runs the bucket its
            # contexts reach, and set-up warms every bucket
            "decode_buckets": None}
    assert {k: serve.options[k] for k in want} == want
    mix = serve.traffic
    assert (mix["loop"], mix["clients"]) == ("closed", "max_slots")
    assert mix["prompt_len"] == {"dist": "fixed", "value": 1024}
    assert mix["output_len"] == {"dist": "fixed", "value": 8192}
    assert (mix["pool_requests"], mix["stagger_per_step"],
            mix["first_turn"], mix["warmup_completions"]) == (
                128, 2, "uniform_age", 16)
    reported = _reported(serve)
    trinity = harness.load_cell("trinity-large-preview.serve.closed-8k1k")
    # every metric of the cell it shares the grouped kernel, the two
    # pools and the share's experts with, and the window's roofline;
    # not the chunk kernel's time, which its tail draws about once
    assert reported == (_reported(trinity) - {"chunk_attn_ms.serve"}) | {
        "window_decode_attn_roofline.serve"}
    assert "paged_decode_attn_ms.serve" not in reported


def test_the_open_cell_holds_its_parameters():
    serve = harness.load_cell(OPEN_CELL)
    closed = harness.load_cell("gpt2-medium.serve.closed")
    assert serve.chips == 1 and serve.config == closed.config
    # the closed GPT cell's engine, every option unchanged
    assert serve.options == closed.options
    mix = serve.traffic
    assert mix["loop"] == "open" and "clients" not in mix
    assert mix["arrivals"]["process"] in ("poisson", "even")
    for key in ("prompt_len", "output_len", "size_seed"):
        assert mix[key] == closed.traffic[key], key
    assert mix["pool_requests"] == 512 and mix["warmup_seconds"] > 0
    assert _reported(serve) == _reported(closed)
    # the schedule the generator offers: arrivals at the file's rate
    traffic = ServeTraffic(mix, 50257, 1024, 2 ** 31 + 41)
    rate = mix["arrivals"]["rate_per_s"]
    assert traffic.n == 512
    assert traffic.due_s[-1] == pytest.approx(512 / rate, rel=0.15)


def test_mimo_configuration_equals_its_catalog_row_but_for_the_cut():
    with open(os.path.join(HERE, "fixtures",
                           "mimo_v2_5_catalog_row.json")) as f:
        row = json.load(f)
    with open(os.path.join(harness.ROOT, "perf", "configs",
                           "mimo-v2.5.json")) as f:
        held = json.load(f)
    assert held["source"] == row["source_url"]
    assert held["model_type"] == row["config"]["model_type"] == "mimo_v2"
    assert held["reduced"] == [
        "num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
        "n_routed_experts", "vocab_size"]
    assert set(held["published"]) == set(held["reduced"])
    for key, value in row["config"].items():
        if key in held["reduced"]:
            assert held[key] != value
            if not isinstance(value, list):
                assert held["published"][key] == value, key
        else:
            assert held[key] == value, key
    # the lists keep their first seven entries: one whole period
    for key in ("hybrid_layer_pattern", "moe_layer_freq"):
        assert held[key] == row["config"][key][:7], key
        assert json.dumps(held[key]) in held["published"][key]
    assert (held["num_hidden_layers"], held["n_routed_experts"],
            held["vocab_size"]) == (7, 16, 19072)
    # the guide's floors: a whole period and four layers after the dense
    # one, 8 experts, an eighth of the vocabulary
    assert held["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 0, 1]
    assert held["num_hidden_layers"] - 1 >= 4
    assert held["n_routed_experts"] >= 8
    assert held["vocab_size"] * 8 == held["published"]["vocab_size"]
    for key in ("assumed", "departures", "deployment", "parameters_held"):
        assert held[key], key
    assert "16 chips share each layer" in held["deployment"]
    # no width is reduced
    widths = {"hidden_size", "intermediate_size", "moe_intermediate_size",
              "head_dim", "v_head_dim", "swa_head_dim", "swa_v_head_dim",
              "num_experts_per_tok", "num_attention_heads",
              "num_key_value_heads", "swa_num_key_value_heads",
              "sliding_window"}
    assert not widths & set(held["reduced"])


def test_mimo_config_file_builds_the_registry_model():
    """perf/families/mimo_v2.py holds the registry model to every size
    of the configuration's file (the share, the slice and the kinds of
    the layers kept too), and counts what the share's mathematics
    requires."""
    import jax.numpy as jnp

    from pytorch_multiprocessing_distributed_tpu import models

    config = harness.load_cell(MIMO_CELL).config
    family = families.load(config)
    model = family.build_model(config, "bfloat16", "cpu")
    assert model == models.get_model(
        "mimo_v2_5", dtype=jnp.bfloat16, num_layers=7, first_k_dense=1,
        n_experts=256, experts_held=16, expert_offset=0, vocab_size=19072)
    assert model.n_experts == family.router_width(config) == 256
    # 2 full layers x 2,560 B + 5 window layers x 5,120 B a token
    assert family.kv_bytes_per_token(config) == 30720
    shapes = {"context_lens": [100, 1000], "kv_dtype": "bfloat16"}
    work = family.kernel_work(config, "gqa_paged_decode_attention", shapes)
    # columns: 2 full layers x 1,100 + 5 window layers x (100 + 128)
    assert work["ops"] == 40960 * (2 * 1100 + 5 * 228)
    assert work["bytes"] == 2560 * 2 * 1100 + 5120 * 5 * 228
    window = family.kernel_work(config, "gqa_paged_decode_attention_window",
                                shapes)
    assert window == {"ops": 40960 * 5 * 228, "bytes": 5120 * 5 * 228}
    assert family.kernel_work(config, "mla_paged_decode_attention",
                              shapes) is None
    # a token's weights: attention of its kind, then dense or router +
    # the EXPECTED 0.5 held assignments of its 8; no shared expert
    full, win = 89_128_960, 94_371_840
    expert = 3 * 4096 * 2048
    assert family.block_params_per_token(config) == (
        2 * full + 5 * win + 3 * 4096 * 16384
        + 6 * (4096 * 256 + 0.5 * expert))
    decode = family.kernel_work(config, "forward.decode",
                                {"context_lens": [100]})
    assert decode["ops"] == 2.0 * (family.block_params_per_token(config)
                                   + 4096 * 19072) + 40960.0 * 700
    for key, bad in (("head_dim", 128), ("add_swa_attention_sink_bias", False),
                     ("swa_num_key_value_heads", 4),
                     ("attention_chunk_size", 256)):
        with pytest.raises(harness.ManifestError, match=key):
            family.build_model({**config, key: bad}, "bfloat16", "cpu")
    with pytest.raises(harness.ManifestError, match="n_shared_experts"):
        family.build_model({**config, "n_shared_experts": 1}, "bfloat16",
                           "cpu")
    with pytest.raises(harness.ManifestError, match="moe_layer_freq"):
        family.build_model({**config, "moe_layer_freq": [0, 1, 0, 1, 1, 1, 1]},
                           "bfloat16", "cpu")
    with pytest.raises(harness.ManifestError, match="served, not trained"):
        family.compare_loss(config, None, None)


def _tiny_mimo_cell():
    """The MiMo cell's files with the model swapped for ``mimo_v2_tiny``
    holding 4 of its 16 experts and every size cut: the driver, the
    family door, the engine and the reference end to end on the CPU. A
    rehearsal carries no metric."""
    from pytorch_multiprocessing_distributed_tpu import models

    cell = harness.load_cell(MIMO_CELL)
    model = models.get_model("mimo_v2_tiny")
    kinds = list(model.hybrid_layer_pattern)
    config = {
        **cell.config, "name": "mimo-v2-tiny",
        "registry_name": "mimo_v2_tiny",
        "vocab_size": model.vocab_size,
        "max_position_embeddings": model.max_seq_len,
        "hidden_size": model.hidden_size, "num_hidden_layers": 5,
        "hybrid_layer_pattern": kinds, "moe_layer_freq": [0, 1, 1, 1, 1],
        "num_attention_heads": model.num_heads,
        "swa_num_attention_heads": model.num_heads,
        "num_key_value_heads": model.num_kv_heads,
        "swa_num_key_value_heads": model.swa_num_kv_heads,
        "head_dim": model.head_dim, "swa_head_dim": model.head_dim,
        "v_head_dim": model.v_head_dim, "swa_v_head_dim": model.v_head_dim,
        "intermediate_size": model.mlp_dim,
        "moe_intermediate_size": model.moe_dim,
        "n_routed_experts": 4, "expert_offset": 8,
        "published": {"n_routed_experts": model.n_experts},
        "num_experts_per_tok": model.moe_top_k,
        "sliding_window": 8, "sliding_window_size": 8,
        "attention_chunk_size": 8}
    return dataclasses.replace(
        cell, config=config,
        options={**cell.options, "dtype": "float32", "max_slots": 4,
                 "s_max": 128, "page_size": 4, "prefill_chunk": 16,
                 "decode_buckets": None, "trace_seconds": 0.5},
        traffic={**cell.traffic, "pool_requests": 16,
                 "warmup_completions": 4,
                 "prompt_len": {"dist": "uniform", "min": 24, "max": 64},
                 "output_len": {"dist": "uniform", "min": 6, "max": 16}})


def test_the_mimo_family_serves_through_the_driver_at_tiny_size(
        monkeypatch):
    from perf import run
    from perf.families import mimo_v2

    # the reference's row block and padding at a size the tiny streams fill
    monkeypatch.setattr(mimo_v2, "REFERENCE_BLOCK", 16)
    monkeypatch.setattr(mimo_v2, "REFERENCE_PAD", 32)
    line = run.measure("rehearsal", 2 ** 31 + 41, 1.0, True,
                       cell=_tiny_mimo_cell(), allow_cpu=True)
    checks = line["checks"]
    assert line["correct"], checks
    mean, over = checks["compared"][:2]
    assert (mean["what"], over["what"]) == ("mean_logit_gap",
                                            "share_of_gaps_over_half")
    assert mean["limit"] == mimo_v2.MEAN_GAP_LIMIT
    assert over["limit"] == mimo_v2.OVER_HALF_LIMIT
    # float32 against float32: every token is the reference's argmax
    assert mean["value"] < 1e-4 and over["value"] == 0
    assert checks["worst_logit_gap"] < 1e-3
    assert checks["checked_positions"] > 0
    assert checks["reference"] == os.path.join("perf", "reference",
                                               "mimo_v2.py")
    assert checks["requests_failed"] == checks["compiles_in_window"] == 0
    per_token = line["rehearsal"]["per_layer"]["host_syncs_per_token.serve"]
    assert 0 < per_token <= 1.0


def test_the_mimo_float8_control_emits_tokens_the_reference_ranks_lower(
        monkeypatch):
    """The control that PERF.md reads on the chip, here at tiny size:
    the reference rounded to float8_e4m3fn emits tokens the float32
    reference does not rank first, while the float32 program's own
    tokens read 0."""
    from perf.families import mimo_v2
    from pytorch_multiprocessing_distributed_tpu.serving import (
        ServingEngine, init_params)

    monkeypatch.setattr(mimo_v2, "REFERENCE_BLOCK", 16)
    monkeypatch.setattr(mimo_v2, "REFERENCE_PAD", 32)
    cell = _tiny_mimo_cell()
    family = families.load(cell.config)
    model = family.build_model(cell.config, "float32", "cpu")
    params = init_params(model, 3)
    engine = ServingEngine(model, params, max_slots=2, s_max=128,
                           kv_layout="paged", page_size=4, prefill_chunk=16)
    rng = np.random.default_rng(0)
    served = [engine.submit(rng.integers(0, 211, size=n).tolist(), 40)
              for n in (40, 56)]
    while engine.in_flight:
        engine.step()
    ours = family.judge_gaps(family.stream_gaps(cell.config, params, served))
    assert all(c["value"] == 0 for c in ours["compared"])
    control = family.judge_gaps(family.control_gaps(cell.config, params,
                                                    served))
    assert control["checks"]["mean_logit_gap"] > 1e-4
    assert control["checks"]["worst_logit_gap"] > 1e-2
