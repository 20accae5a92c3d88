"""The benchmark's door for the LFM2 configuration's cell and the open
GPT cell above capacity, in tier-1: ``lfm2-8b-a1b.serve.closed-4k1k`` and
``gpt2-medium.serve.open120`` hold their parameters; the configuration
equals its catalog row (``tests/fixtures/lfm2_8b_a1b_catalog_row.json``,
the row of the ``model-configs`` guide's ``architectures.jsonl``, which
is not in the repository) but for the cut; the family door builds the
registry model, refuses a file that differs from it in any key, and
counts the work the mathematics requires; and the cell's driver,
family door, engine and reference run end to end at a tiny size on the
CPU.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

from perf import families, harness
from perf.traffic import ServeTraffic

HERE = os.path.dirname(os.path.abspath(__file__))
LFM2_CELL = "lfm2-8b-a1b.serve.closed-4k1k"
OPEN_CELL = "gpt2-medium.serve.open120"


def _reported(cell):
    return {m["name"] for m in cell.end_to_end + cell.per_layer}


def _config():
    return harness.load_cell(LFM2_CELL).config


def test_the_lfm2_cell_holds_its_parameters():
    serve = harness.load_cell(LFM2_CELL)
    assert serve.chips == 1 and serve.kind == "serve"
    want = {"dtype": "bfloat16", "max_slots": 128, "s_max": 5120,
            "kv_dtype": "model", "page_size": 16, "num_pages": None,
            "prefill_chunk": 1024, "decode_horizon": 1,
            "decode_attn": "auto", "prefix_cache": 0, "draft_k": 0,
            "temperature": 0.0, "decode_buckets": None}
    assert {k: serve.options[k] for k in want} == want
    # the 4k1k mix of the xing4 cell, unchanged
    xing4 = harness.load_cell("xing4-29b-a4b.serve.closed-4k1k")
    assert serve.traffic == xing4.traffic
    mix = serve.traffic
    assert (mix["loop"], mix["clients"]) == ("closed", "max_slots")
    assert mix["prompt_len"] == {"dist": "fixed", "value": 4096}
    assert mix["output_len"] == {"dist": "fixed", "value": 1024}
    reported = _reported(serve)
    trinity = harness.load_cell("trinity-large-preview.serve.closed-8k1k")
    # every metric of the grouped cell it shares the walk, the two pools
    # and both grouped kernels with, less the window layers' kernel
    # (no layer here has a window), the sliced router's share and the
    # KV-named bytes; plus the conv kernel's two
    assert reported == (_reported(trinity) - {
        "window_decode_attn_ms.serve", "moe_rows_given_over_held.serve",
        "kv_bytes_held_over_undivided.serve"}) | {
        "short_conv_decode_ms.serve", "short_conv_roofline.serve"}
    for name in ("short_conv_decode_ms.serve", "short_conv_roofline.serve"):
        entry = next(m for m in serve.per_layer if m["name"] == name)
        assert entry["workloads"] == [LFM2_CELL]
        assert entry["layer"] == "kernels"
    assert serve.layer_files["short_conv_decode_ms.serve"]["args"] == {
        "program": "^jit_paged_horizon_step$", "match": "^mosaic:short_conv"}
    roofline = serve.layer_files["short_conv_roofline.serve"]
    assert (roofline["reducer"], roofline["args"]["kernel"]) == (
        "roofline_share", "short_conv")


def test_the_open120_cell_holds_its_parameters():
    serve = harness.load_cell(OPEN_CELL)
    closed = harness.load_cell("gpt2-medium.serve.closed")
    open80 = harness.load_cell("gpt2-medium.serve.open80")
    assert serve.chips == 1 and serve.config == closed.config
    # the closed GPT cell's engine, every option unchanged
    assert serve.options == closed.options == open80.options
    mix = serve.traffic
    assert mix["loop"] == "open" and "clients" not in mix
    assert mix["arrivals"] == {"process": "even", "rate_per_s": 100.0}
    for key in ("prompt_len", "output_len", "size_seed", "warmup_seconds"):
        assert mix[key] == open80.traffic[key], key
    assert mix["pool_requests"] == 4096
    # wherever the open80 cell stands, the open120 cell stands beside it
    assert _reported(serve) == _reported(open80)
    # the schedule the generator offers: arrivals at the file's rate
    # over the warm-up and the whole 30-s window before the first
    # request is offered again (4,096 even gaps span 40.96 s)
    traffic = ServeTraffic(mix, 50257, 1024, 2 ** 31 + 43)
    rate = mix["arrivals"]["rate_per_s"]
    assert traffic.n == 4096
    assert traffic.due_s[-1] == pytest.approx(4096 / rate, rel=0.05)
    assert traffic.due_s[-1] > mix["warmup_seconds"] + 30


def test_lfm2_configuration_equals_its_catalog_row_but_for_the_cut():
    with open(os.path.join(HERE, "fixtures",
                           "lfm2_8b_a1b_catalog_row.json")) as f:
        row = json.load(f)
    held = _config()
    assert held["source"] == row["source_url"]
    assert held["model_type"] == row["config"]["model_type"] == "lfm2_moe"
    assert held["reduced"] == ["num_hidden_layers", "layer_types"]
    assert set(held["published"]) == set(held["reduced"])
    for key, value in row["config"].items():
        if key in held["reduced"]:
            assert held[key] != value
            if not isinstance(value, list):
                assert held["published"][key] == value, key
        else:
            assert held[key] == value, key
    # the first twelve kinds: both dense layers, then ten expert layers
    # holding three of the published period's full layers and seven conv
    assert held["layer_types"] == row["config"]["layer_types"][:12]
    assert json.dumps(held["layer_types"]) in held["published"][
        "layer_types"]
    assert held["num_hidden_layers"] == 12
    kinds = held["layer_types"]
    assert (kinds.count("full_attention"), kinds.count("conv")) == (3, 9)
    # the guide's floors: a whole period at the published ratio (3 of 12
    # against 6 of 24), four or more layers after the dense ones, every
    # expert, the whole vocabulary
    assert kinds.count("full_attention") * 24 == 6 * 12
    assert held["num_hidden_layers"] - held["num_dense_layers"] >= 4
    assert held["num_experts"] == 32 and held["vocab_size"] == 65536
    for key in ("assumed", "departures", "deployment", "parameters_held"):
        assert held[key], key
    assert "two-stage pipeline" in held["deployment"]
    assert any("tied" in a for a in held["assumed"])
    assert any("1e-6" in a for a in held["assumed"])
    # no width is reduced
    widths = {"hidden_size", "intermediate_size", "moe_intermediate_size",
              "num_experts_per_tok", "num_attention_heads",
              "num_key_value_heads", "conv_L_cache"}
    assert not widths & set(held["reduced"])


def test_the_parameters_held_come_to_3928_7_m():
    """The door's count, the program's weights and the file's own words
    agree: 3,928.7 M parameters, 7.86 GB as served."""
    import jax
    import jax.numpy as jnp

    config = _config()
    family = families.load(config)
    assert round(family.parameters(config) / 1e5) == 39287
    model = family.build_model(config, "bfloat16", "cpu")
    shapes = jax.eval_shape(lambda: model._init(jax.random.PRNGKey(0)))
    leaves = jax.tree.leaves(shapes)
    assert sum(int(np.prod(s.shape)) for s in leaves) == family.parameters(
        config)
    held = sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in leaves)
    assert abs(held - 7.859e9) < 1e7
    assert config["parameters_held"].startswith("3,928.7 M")
    assert model == __import__(
        "pytorch_multiprocessing_distributed_tpu.models",
        fromlist=["get_model"]).get_model("lfm2_8b_a1b",
                                          dtype=jnp.bfloat16, num_layers=12)


@pytest.mark.parametrize("key, bad", [
    ("hidden_size", 1024), ("num_key_value_heads", 4),
    ("num_attention_heads", 16), ("intermediate_size", 7000),
    ("moe_intermediate_size", 1024), ("num_experts", 16),
    ("num_experts_per_tok", 2), ("num_dense_layers", 1),
    ("norm_eps", 1e-6), ("rope_theta", 10000), ("conv_L_cache", 4),
    ("routed_scaling_factor", 2.5), ("vocab_size", 8192),
    ("max_position_embeddings", 4096),
    ("layer_types", ["conv"] * 12), ("conv_bias", True),
    ("norm_topk_prob", False), ("use_expert_bias", False),
    ("tie_word_embeddings", False)])
def test_build_model_refuses_a_file_that_differs_in_any_key(key, bad):
    config = _config()
    family = families.load(config)
    with pytest.raises(harness.ManifestError, match=key):
        family.build_model({**config, key: bad}, "bfloat16", "cpu")


def test_the_door_counts_the_kernels_work_at_the_cells_shapes():
    config = _config()
    family = families.load(config)
    with pytest.raises(harness.ManifestError, match="served, not trained"):
        family.compare_loss(config, None, None)
    # 3 full layers x 2,048 B a token
    assert family.kv_bytes_per_token(config) == 6144
    # 128 slots at contexts 4,097 and 5,120 (positions 4,096, 5,119)
    lens = [4097] * 64 + [5120] * 64
    shapes = {"context_lens": lens, "prompt_lens": [4096],
              "kv_dtype": "bfloat16"}
    conv = family.kernel_work(config, "short_conv", shapes)
    # a token and conv layer: 3C in, 2 carried rows of C, C out, C new
    # row, 2 B each: 28 KiB; 7C operations
    assert conv == {"ops": 7 * 2048 * 9 * 128,
                    "bytes": 9 * 128 * 7 * 2048 * 2}
    assert conv["bytes"] == 9 * 128 * 28672
    # a token at position 0 or 1 carries fewer rows
    young = family.kernel_work(config, "short_conv", {
        "context_lens": [1, 2], "kv_dtype": "bfloat16"})
    assert young["bytes"] == 9 * (5 + 0 + 5 + 1) * 2048 * 2
    gqa = family.kernel_work(config, "gqa_paged_decode_attention", shapes)
    columns = 3 * sum(lens)
    assert gqa == {"ops": 8192.0 * columns, "bytes": 2048.0 * columns}
    chunk = family.kernel_work(config, "gqa_chunk_attention", shapes)
    assert chunk == {"ops": 8192.0 * 3 * 4096 * 4097 / 2,
                     "bytes": 3.0 * 2048 * 4096}
    for kernel in ("mla_paged_decode_attention",
                   "gqa_paged_decode_attention_window",
                   "paged_decode_attention"):
        assert family.kernel_work(config, kernel, shapes) is None
    # a token's weights: 3 attention + 9 conv mixers, 2 dense layers, 10
    # routers and 4 of a layer's 32 experts each
    c = 2048
    attn, mixer = 2 * c * c + c * 1024, 4 * c * c + 3 * c
    per_token = (3 * attn + 9 * mixer + 2 * 3 * c * 7168
                 + 10 * (c * 32 + 4 * 3 * c * 1792))
    assert family.block_params_per_token(config) == per_token
    decode = family.kernel_work(config, "forward.decode",
                                {"context_lens": [100]})
    assert decode["ops"] == (2.0 * (per_token + c * 65536) + 9 * 7 * c
                             + 8192.0 * 3 * 100)
    prefill = family.kernel_work(config, "forward.prefill",
                                 {"prompt_lens": [10]})
    assert prefill["ops"] == ((2.0 * per_token + 9 * 7 * c) * 10
                              + 2.0 * c * 65536 + 8192.0 * 3 * 55)


def _tiny_lfm2_cell():
    """The LFM2 cell's files with the model swapped for
    ``lfm2_moe_tiny`` and every size cut: the driver, the family door,
    the engine and the reference end to end on the CPU. A rehearsal
    carries no metric."""
    from pytorch_multiprocessing_distributed_tpu import models

    cell = harness.load_cell(LFM2_CELL)
    model = models.get_model("lfm2_moe_tiny")
    config = {
        **cell.config, "name": "lfm2-moe-tiny",
        "registry_name": "lfm2_moe_tiny",
        "vocab_size": model.vocab_size,
        "max_position_embeddings": model.max_seq_len,
        "hidden_size": model.hidden_size, "num_hidden_layers": 5,
        "layer_types": list(model.layer_types),
        "num_dense_layers": model.first_k_dense,
        "num_attention_heads": model.num_heads,
        "num_key_value_heads": model.num_kv_heads,
        "intermediate_size": model.mlp_dim,
        "moe_intermediate_size": model.moe_dim,
        "num_experts": model.n_experts,
        "num_experts_per_tok": model.moe_top_k}
    return dataclasses.replace(
        cell, config=config,
        options={**cell.options, "dtype": "float32", "max_slots": 4,
                 "s_max": 128, "page_size": 4, "prefill_chunk": 16,
                 "decode_buckets": None, "trace_seconds": 0.5},
        traffic={**cell.traffic, "pool_requests": 16,
                 "warmup_completions": 4,
                 "prompt_len": {"dist": "uniform", "min": 18, "max": 64},
                 "output_len": {"dist": "uniform", "min": 6, "max": 16}})


def test_the_lfm2_family_serves_through_the_driver_at_tiny_size(
        monkeypatch):
    from perf import run
    from perf.families import lfm2_moe

    # the reference's row block and padding at a size the tiny streams fill
    monkeypatch.setattr(lfm2_moe, "REFERENCE_BLOCK", 16)
    monkeypatch.setattr(lfm2_moe, "REFERENCE_PAD", 32)
    line = run.measure("rehearsal", 2 ** 31 + 43, 1.0, True,
                       cell=_tiny_lfm2_cell(), allow_cpu=True)
    checks = line["checks"]
    assert line["correct"], checks
    mean, over = checks["compared"][:2]
    assert (mean["what"], over["what"]) == ("mean_logit_gap",
                                            "share_of_gaps_over_half")
    assert mean["limit"] == lfm2_moe.MEAN_GAP_LIMIT
    assert over["limit"] == lfm2_moe.OVER_HALF_LIMIT
    # float32 against float32: every token is the reference's argmax
    assert mean["value"] < 1e-4 and over["value"] == 0
    assert checks["worst_logit_gap"] < 1e-3
    assert checks["checked_positions"] > 0
    assert checks["reference"] == os.path.join("perf", "reference",
                                               "lfm2_moe.py")
    assert checks["requests_failed"] == checks["compiles_in_window"] == 0


def _tiny_control(monkeypatch, widen):
    """The tiny cell's streams served in float32 with the layers'
    matrices drawn ``widen`` times the init's 0.02; returns the judged
    gaps of the program's own tokens and of the float8 control's."""
    import jax

    from perf.families import lfm2_moe
    from pytorch_multiprocessing_distributed_tpu.serving import (
        ServingEngine, init_params)

    monkeypatch.setattr(lfm2_moe, "REFERENCE_BLOCK", 16)
    monkeypatch.setattr(lfm2_moe, "REFERENCE_PAD", 32)
    cell = _tiny_lfm2_cell()
    family = families.load(cell.config)
    model = family.build_model(cell.config, "float32", "cpu")
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a * widen if a.ndim >= 2
        and "layer_" in str(path[0]) and "router" not in str(path[-1])
        else a, init_params(model, 3))
    engine = ServingEngine(model, params, max_slots=2, s_max=128,
                           kv_layout="paged", page_size=4, prefill_chunk=16)
    rng = np.random.default_rng(0)
    served = [engine.submit(rng.integers(0, 211, size=n).tolist(), 40)
              for n in (40, 56)]
    while engine.in_flight:
        engine.step()
    ours = family.judge_gaps(family.stream_gaps(cell.config, params, served))
    control = family.judge_gaps(family.control_gaps(cell.config, params,
                                                    served))
    return ours, control


def test_the_lfm2_float8_control_emits_tokens_the_reference_ranks_lower(
        monkeypatch):
    """The control that PERF.md reads on the chip, here at tiny size:
    the reference rounded to float8_e4m3fn emits tokens the float32
    reference does not rank first, while the float32 program's own
    tokens read 0. The layers' matrices are drawn four times wider than
    the init's 0.02: at a width of 64 the tied head would otherwise
    rank each token's own row first (the embedding outweighs the
    layers' sum, which at the published widths is 60 times its size),
    and every system would emit the same tokens."""
    ours, control = _tiny_control(monkeypatch, 4)
    assert all(c["value"] == 0 for c in ours["compared"])
    assert control["checks"]["mean_logit_gap"] > 1e-4
    assert control["checks"]["worst_logit_gap"] > 1e-2


def test_the_lfm2_float8_control_fails_the_familys_limits(monkeypatch):
    """The family's judgement refuses the control, as on the chip: with
    the layers' matrices sixteen times the init's 0.02 (four is too
    narrow a spread of logits at a width of 64 for float8's rounding to
    flip many tokens: a mean gap of ~0.01) the float8 control's gaps
    exceed at least one of ``MEAN_GAP_LIMIT`` / ``OVER_HALF_LIMIT``,
    while the float32 program's own tokens read 0 under both."""
    ours, control = _tiny_control(monkeypatch, 16)
    assert all(c["value"] == 0 for c in ours["compared"])
    assert any(c["value"] > c["limit"] for c in control["compared"])
