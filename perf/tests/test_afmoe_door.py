"""The ``afmoe`` family's door: the configuration's file resolves to
the model it describes, and the required operations and bytes of the
grouped decode kernel and of the forward pass are the numbers worked by
hand from the published sizes."""

import json
import os

import pytest

from perf import families, harness
from perf.families import afmoe as door

CELL = "trinity-large-preview.serve.closed-8k1k"


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(harness.PERF_DIR, "configs",
                           "trinity-large-preview.json")) as f:
        return json.load(f)


def test_the_cell_is_as_the_issue_names_it():
    cell = harness.load_cell(CELL)
    assert (cell.chips, cell.kind) == (1, "serve")
    opts = cell.options
    assert (opts["max_slots"], opts["s_max"], opts["page_size"],
            opts["prefill_chunk"], opts["num_pages"],
            opts["decode_horizon"]) == (48, 9216, 16, 1024, None, 1)
    mix = cell.traffic
    assert mix["loop"] == "closed" and mix["clients"] == "max_slots"
    assert mix["prompt_len"] == {"dist": "fixed", "value": 8192}
    assert mix["output_len"] == {"dist": "fixed", "value": 1024}
    names = {m["name"] for m in cell.per_layer}
    assert {"gqa_decode_attn_ms.serve", "gqa_decode_attn_roofline.serve",
            "window_decode_attn_ms.serve", "mfu.serve",
            "routed_expert_matmul_ms.serve"} <= names
    assert not {n for n in names if n.startswith("mla_")}


def test_build_model_holds_the_model_to_the_file(config):
    family = families.load(config)
    assert family is door
    model = family.build_model(config, "bfloat16", "cpu")
    assert (model.num_layers, model.first_k_dense, model.n_held,
            model.n_experts, model.expert_offset, model.vocab_size) == (
        5, 1, 32, 256, 0, 25024)
    assert list(model.layer_types) == config["layer_types"]
    assert (model.num_heads, model.num_kv_heads, model.sliding_window) == (
        48, 8, 4096)
    # a file that disagrees with the registry's model is refused, in
    # the kinds of the layers kept as in any size
    for key, value in (("layer_types", ["full_attention"] * 5),
                       ("sliding_window", 2048), ("num_key_value_heads", 4),
                       ("num_experts_per_tok", 8)):
        with pytest.raises(harness.ManifestError):
            family.build_model({**config, key: value}, "bfloat16", "cpu")
    with pytest.raises(harness.ManifestError, match="implements"):
        family.build_model({**config, "score_func": "softmax"},
                           "bfloat16", "cpu")
    with pytest.raises(harness.ManifestError, match="served, not trained"):
        family.compare_loss(config, None, None)


def test_parameters_per_token_by_hand(config):
    # attention: q, gate, out of 3,072 x 6,144 and k, v of 3,072 x 1,024
    attention = 3 * 3072 * 6144 + 2 * 3072 * 1024
    assert attention == 62_914_560 == door._attention_params(config)
    expert = 3 * 3072 * 3072                          # 28.31 M
    dense = 3 * 3072 * 12288                          # 113.25 M
    # four expert layers: the router's 256 outputs, the shared expert
    # and the EXPECTED 4 x 32 / 256 = 0.5 held assignments a token
    want = 5 * attention + dense + 4 * (3072 * 256 + 1.5 * expert)
    assert door.block_params_per_token(config) == want
    assert door.kv_bytes_per_token(config) == 5 * 4096 == 20480


def test_kernel_work_of_the_grouped_decode_by_hand(config):
    """Two tokens decoded at contexts 100 and 9,000: the one full layer
    attends 100 and 9,000 columns, each of the four sliding layers 100
    and 4,096 (the window's cap): 25,884 columns in all, each 4,096
    bytes (K and V of 8 heads x 128 in bfloat16) and 4 x 48 x 128
    operations."""
    shapes = {"context_lens": [100, 9000], "prompt_lens": [],
              "dtype": "bfloat16", "kv_dtype": "bfloat16"}
    columns = (100 + 9000) + 4 * (100 + 4096)
    assert columns == 25_884
    work = door.kernel_work(config, "gqa_paged_decode_attention", shapes)
    assert work == {"ops": 24576.0 * columns, "bytes": 4096.0 * columns}
    # 6 operations a byte: far under the chip's ridge, the bytes decide
    assert work["ops"] / work["bytes"] == 6.0
    decode = door.kernel_work(config, "forward.decode", shapes)
    assert decode["ops"] == (
        2.0 * (door.block_params_per_token(config) + 3072 * 25024) * 2
        + 24576.0 * columns)
    assert door.kernel_work(config, "mla_paged_decode_attention",
                            shapes) is None


def test_prefill_work_caps_the_attention_at_the_window(config):
    shapes = {"context_lens": [], "prompt_lens": [8192],
              "dtype": "bfloat16", "kv_dtype": "bfloat16"}
    n, w = 8192, 4096
    pairs = n * (n + 1) / 2 + 4 * (w * (w + 1) / 2 + (n - w) * w)
    assert door._prefill_pairs(config, n) == pairs
    # under the window a sliding layer is a full one
    assert door._prefill_pairs(config, 100) == 5 * 100 * 101 / 2
    work = door.kernel_work(config, "forward.prefill", shapes)
    assert work["ops"] == (2.0 * door.block_params_per_token(config) * n
                           + 2.0 * 3072 * 25024 + 24576.0 * pairs)
