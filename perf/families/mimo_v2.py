"""The ``mimo_v2`` family (``"model_type": "mimo_v2"``, Xiaomi
MiMo-V2.5's language model): everything the benchmark knows about it,
and the only file that does.

Configuration keys are the published ``config.json``'s. A configuration
may be ONE CHIP'S SHARE of an expert-parallel deployment:
``n_routed_experts`` then counts the experts HELD (``expert_offset``
says from which), the router keeps the published width that stands
under ``published``, ``vocab_size`` is the slice of the vocabulary held,
and ``hybrid_layer_pattern`` / ``moe_layer_freq`` list the layers kept.
``registry_name`` and ``model_kwargs`` say which model of the program's
registry is built from them. Serving only: the program has no training
path for this family, and ``compare_loss`` says so.

Operations and bytes here are what the share's mathematics REQUIRES of
a forward pass, never what a compiled program executes (the decode
kernel multiplies a block-diagonal query against every key/value head's
lanes and copies a window layer's whole ring; a chunk of a full layer
attends the whole bucket under a mask; the decode program computes
frozen slots; none of that is counted).
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from ..harness import ManifestError, prng_key
from ..reference import mimo_v2 as reference
from .afmoe import judge_gaps as _judge_gaps

# options this family adds to the drivers' own: none. The resident
# weight type is the family's init (bfloat16 matrices), not an option.
ENGINE_OPTIONS: dict = {}
TRAINER_OPTIONS: dict = {}

# For a sampled finished request the float32 reference scores the whole
# of prompt + generated tokens (1,024 + 8,192 in the cell: every
# generated token but the first hundred was decoded through the full
# layers' pages AND a window layer's ring of 9 pages that wraps every 16
# tokens, its sink in the softmax); at each generated position the GAP
# is the reference's largest logit minus its logit for the emitted
# token. The worst gap says nothing about a bfloat16 system (a near-tie
# of two logits, or of the eighth and ninth of 256 biased scores in some
# layer of some earlier token, flips) and is reported, not compared:
# the rule of every expert family here. Two numbers are compared, as in
# perf/families/afmoe.py:
#
# MEAN_GAP_LIMIT on the mean gap over all checked positions;
# OVER_HALF_LIMIT on the share of positions whose gap is over 0.5.
#
# Both stand between two readings taken on the chip at the published
# widths under the cell's traffic (PERF.md section 6): the
# largest the system showed over twelve or more seeds, and what the
# CONTROL showed: this reference with both operands of every matrix
# product rounded to float8_e4m3fn, the nearest precision below
# bfloat16, emitting its own greedy tokens along the same streams
# (:func:`control_gaps`).
MEAN_GAP_LIMIT = 0.05
OVER_HALF_LIMIT = 0.03

# rows of queries the reference's attention takes at a time (a block's
# scores are [64, rows, 9216] float32)
REFERENCE_BLOCK = 256
# a stream is padded to a multiple of this, so that a run's streams
# compile the sublayers for one length
REFERENCE_PAD = 1024

_ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}

# file key -> the built model's attribute, for every size the file has
# (n_routed_experts: the experts HELD; the router's width is under
# ``published`` and is held to ``n_experts`` in build_model). The
# attention of both kinds has 64 query heads of 192 and values of 128:
# the ``swa_`` twins are held to the same attributes.
_SIZES = {
    "vocab_size": "vocab_size",
    "max_position_embeddings": "max_seq_len",
    "hidden_size": "hidden_size",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "swa_num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "swa_num_key_value_heads": "swa_num_kv_heads",
    "head_dim": "head_dim",
    "swa_head_dim": "head_dim",
    "v_head_dim": "v_head_dim",
    "swa_v_head_dim": "v_head_dim",
    "partial_rotary_factor": "partial_rotary_factor",
    "attention_value_scale": "attention_value_scale",
    "intermediate_size": "mlp_dim",
    "moe_intermediate_size": "moe_dim",
    "n_routed_experts": "n_held",
    "expert_offset": "expert_offset",
    "num_experts_per_tok": "moe_top_k",
    "layernorm_epsilon": "rms_eps",
    "rope_theta": "rope_theta",
    "swa_rope_theta": "swa_rope_theta",
    "sliding_window": "sliding_window",
    "sliding_window_size": "sliding_window",
    # read as the window, not as a second mask (the file's ``assumed``)
    "attention_chunk_size": "sliding_window",
}
# keys of the file whose value the program supports in one form only
_FIXED = {"hidden_act": "silu", "scoring_func": "sigmoid",
          "topk_method": "noaux_tc", "norm_topk_prob": True, "n_group": 1,
          "topk_group": 1, "n_shared_experts": None,
          "routed_scaling_factor": None, "tie_word_embeddings": False,
          "attention_bias": False, "hybrid_block_size": None,
          # a learned sink on the window layers, none on the full ones
          "add_swa_attention_sink_bias": True,
          "add_full_attention_sink_bias": False,
          "rope_scaling": {"rope_type": "default", "type": "default"}}


def router_width(config: dict) -> int:
    """The number of experts the router scores: the published count,
    which a configuration that holds a share keeps under
    ``published``."""
    return int(config.get("published", {}).get(
        "n_routed_experts", config["n_routed_experts"]))


def _dense_layers(config: dict) -> int:
    """The leading layers with a dense feed-forward (``moe_layer_freq``
    0), which the program's ``first_k_dense`` counts."""
    freq = list(config["moe_layer_freq"])
    dense = freq.index(1) if 1 in freq else len(freq)
    if freq != [0] * dense + [1] * (len(freq) - dense):
        raise ManifestError(
            f"moe_layer_freq = {freq}: the program puts the dense layers "
            "first, then expert layers only")
    return dense


# -------------------------------------------------------------- model

def build_model(config: dict, dtype: str, platform: str, **extra):
    """The registry model this configuration names, at the file's
    depth, share of the experts and slice of the vocabulary, held to
    every size in the file (the kinds of the layers kept too) and to
    the one form of each switch the program implements."""
    import jax.numpy as jnp

    from pytorch_multiprocessing_distributed_tpu import models

    try:
        model = models.get_model(
            config["registry_name"],
            dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[dtype],
            num_layers=config["num_hidden_layers"],
            first_k_dense=_dense_layers(config),
            n_experts=router_width(config),
            experts_held=config["n_routed_experts"],
            expert_offset=config["expert_offset"],
            vocab_size=config["vocab_size"],
            **config.get("model_kwargs", {}), **extra)
    except KeyError as e:        # a program that lacks the family
        raise ManifestError(
            f"the program's registry has no model "
            f"{config['registry_name']!r}: {e}") from e
    want = {key: config[key] for key in _SIZES}
    got = {key: getattr(model, attr) for key, attr in _SIZES.items()}
    want["router_width"], got["router_width"] = (router_width(config),
                                                 model.n_experts)
    want["hybrid_layer_pattern"], got["hybrid_layer_pattern"] = (
        list(config["hybrid_layer_pattern"]),
        list(model.hybrid_layer_pattern[:model.num_layers]))
    want["shared_experts"], got["shared_experts"] = 0, model.n_shared_experts
    want["routed_scale"], got["routed_scale"] = 1.0, model.routed_scale
    if got != want:
        raise ManifestError(
            f"registry model {config['registry_name']!r} is {got}, the "
            f"configuration file says {want}")
    for key, value in _FIXED.items():
        if config[key] != value:
            raise ManifestError(
                f"{key} = {config[key]!r}: the program implements "
                f"{value!r} only")
    return model


def init_params(model, seed: int):
    """Random weights on the device in one jitted call, in the types
    they are served in (bfloat16 matrices; float32 router, selection
    bias, sinks and gains)."""
    return model.init(prng_key(seed))["params"]


# -------------------------------------------------------- comparisons

def compare_streams(config: dict, params, requests, s_max: int) -> dict:
    """Served streams against the reference: ``compared`` is what
    decides ``correct``."""
    return judge_gaps(stream_gaps(config, params, requests))


def judge_gaps(gaps: List[float]) -> dict:
    """``afmoe``'s judgement (the same two numbers, the worst gap and
    the 99th reported) under this family's limits."""
    judged = _judge_gaps(gaps)
    for entry, limit in zip(judged["compared"],
                            (MEAN_GAP_LIMIT, OVER_HALF_LIMIT)):
        entry["limit"] = limit
    judged["checks"]["mean_gap_limit"] = MEAN_GAP_LIMIT
    judged["checks"]["over_half_limit"] = OVER_HALF_LIMIT
    return judged


def compare_loss(config: dict, params, tokens):
    raise ManifestError(
        "the mimo_v2 family is served, not trained: the program has no "
        "training forward for it (ROADMAP.md B1)")


def stream_gaps(config: dict, params, requests) -> List[float]:
    """For each generated token of each request: the reference's
    largest logit at that position minus its logit for the token the
    system emitted (0 = the reference's own argmax)."""
    return _gaps(config, params, requests, None)


def control_gaps(config: dict, params, requests,
                 precision: str = "float8_e4m3fn") -> List[float]:
    """The control of PERF.md: the same gaps for the tokens a system
    computing in ``precision`` would emit: the reference with both
    operands of every matrix product rounded to it (weights,
    activations, the softmax's probabilities; sums, norms and the
    residual stream stay float32, as in the system), greedy at every
    generated position of the same streams (teacher-forced)."""
    return _gaps(config, params, requests, precision)


def _gaps(config: dict, params, requests, control) -> List[float]:
    """One stream at a time, padded to a multiple of ``REFERENCE_PAD``
    (padding sits after the stream and the mask is causal, so it
    changes nothing), one SUBLAYER's program at a time: what is live in
    float32 is one attention (0.38 GB), one dense feed-forward (0.81
    GB) or one expert of the scan (0.10 GB), and a block of scores."""
    import jax
    import jax.numpy as jnp

    if not requests:
        return []
    block = REFERENCE_BLOCK

    def low(a):
        return a.astype(jnp.dtype(control)).astype(jnp.float32)

    exact_hp = reference.hyper(config)
    low_hp = {**exact_hp, "round": low}

    def forward(hp):
        @jax.jit
        def embed(top, tokens):
            return reference.embed(top, tokens, hp)

        def attention(sliding):
            @jax.jit
            def fn(weights, x):
                with jax.default_matmul_precision("highest"):
                    return reference.attention_sublayer(
                        weights, x, hp, block, sliding)
            return fn

        attend = {kind: attention(kind) for kind in set(hp["sliding"])}

        @jax.jit
        def feed_forward(weights, x):
            with jax.default_matmul_precision("highest"):
                return reference.feed_forward_sublayer(weights, x, hp)

        def run(tokens):
            x = embed(top, tokens)
            for i, sliding in enumerate(hp["sliding"]):
                x = attend[sliding](params[f"layer_{i}"], x)
                x = feed_forward(params[f"layer_{i}"], x)
            return x

        return run

    # the head over the generated positions only, a fixed number of
    # rows (the longest answer's, rounded up) so that it compiles once
    n_rows = -(-max(len(r.tokens) for r in requests) // 256) * 256

    def head_rows(hp, x, first):
        rows = jnp.minimum(first + jnp.arange(n_rows), x.shape[0] - 2)
        with jax.default_matmul_precision("highest"):
            return reference.head(top, x[rows], hp)

    @jax.jit
    def gaps_of(x, emitted, first):
        # position j's logits score token j + 1
        logits = head_rows(exact_hp, x, first)
        picked = jnp.take_along_axis(logits, emitted[:, None], axis=-1)
        return jnp.max(logits, axis=-1) - picked[:, 0]

    @jax.jit
    def greedy_of(x, first):
        return jnp.argmax(head_rows(low_hp, x, first), axis=-1)

    top = {k: v for k, v in params.items() if not k.startswith("layer_")}
    exact = forward(exact_hp)
    rounded = forward(low_hp) if control else None
    out: List[float] = []
    for request in requests:
        stream = list(request.prompt) + list(request.tokens)
        first = len(request.prompt) - 1     # scores generated token 0
        length = -(-len(stream) // REFERENCE_PAD) * REFERENCE_PAD
        padded = np.zeros((length,), np.int32)
        padded[:len(stream)] = stream
        tokens = jnp.asarray(padded)
        if control:
            emitted = greedy_of(rounded(tokens), first)
        else:
            emitted = tokens[jnp.minimum(first + 1 + jnp.arange(n_rows),
                                         length - 1)]
        gaps = gaps_of(exact(tokens), emitted, first)
        out.extend(float(g) for g in np.asarray(gaps)[:len(request.tokens)])
    return out


# ------------------------------------------- required operations, bytes

def _kinds(cfg: dict):
    """``(full layers, window layers)`` among the layers kept."""
    window = sum(1 for kind in cfg["hybrid_layer_pattern"] if kind)
    return len(cfg["hybrid_layer_pattern"]) - window, window


def _row_values(cfg: dict, window: bool) -> int:
    """Values a token keeps in one layer of the kind: K of every
    key/value head, then V (1,280 on a full layer, 2,560 on a window
    layer at the published widths)."""
    heads = cfg["swa_num_key_value_heads" if window
                else "num_key_value_heads"]
    return heads * (cfg["head_dim"] + cfg["v_head_dim"])


def _attention_params(cfg: dict, window: bool) -> int:
    """q of ``H x head_dim``, k and v of the kind's ``Hkv`` heads, out
    of ``H x v_head_dim`` (89.13 M on a full layer, 94.37 M on a window
    layer at the published widths)."""
    c, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return (c * heads * (cfg["head_dim"] + cfg["v_head_dim"])
            + c * _row_values(cfg, window))


def block_params_per_token(cfg: dict) -> float:
    """Weights of the layers that multiply ONE token's activations on
    THIS chip: attention, and the dense feed-forward or the router (all
    of its outputs) and the EXPECTED number of a token's chosen experts
    that are held here: ``num_experts_per_tok x held / router width``
    (0.5 with 16 of 256 at top-8: routing over random weights is even),
    not the 8 the whole deployment computes. No shared expert."""
    c = cfg["hidden_size"]
    full, window = _kinds(cfg)
    dense = _dense_layers(cfg)
    sparse = cfg["num_hidden_layers"] - dense
    expert = 3 * c * cfg["moe_intermediate_size"]
    width = router_width(cfg)
    held_per_token = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
                      / width)
    return (full * _attention_params(cfg, False)
            + window * _attention_params(cfg, True)
            + dense * 3 * c * cfg["intermediate_size"]
            + sparse * (c * width + held_per_token * expert))


def _head_params(cfg: dict) -> int:
    """The head over the rows of the vocabulary held here."""
    return cfg["hidden_size"] * cfg["vocab_size"]


def _pair_ops(cfg: dict) -> float:
    """Operations of one query (all heads) against one cached column:
    ``2 H Dk`` for the score and ``2 H Dv`` for the output (40,960 at
    64 x (192 + 128))."""
    return (2.0 * cfg["num_attention_heads"]
            * (cfg["head_dim"] + cfg["v_head_dim"]))


def _decode_columns(cfg: dict, lens, kinds=(False, True)) -> dict:
    """Cached columns the layers of each kind attend, summed over
    decoded tokens at contexts ``lens`` (each its own column included):
    the context on a full layer, the window's worth at most on a window
    layer."""
    full, window = _kinds(cfg)
    reach = cfg["sliding_window"]
    return {kind: float(sum(full * n for n in lens)) if not kind
            else float(sum(window * min(n, reach) for n in lens))
            for kind in kinds}


def _prefill_pairs(cfg: dict, n: int) -> float:
    """(query, key) pairs all layers attend over a prompt of ``n``
    tokens: half a square on a full layer, a band of the window's width
    on a window layer."""
    full, window = _kinds(cfg)
    w = min(n, cfg["sliding_window"])
    return (full * n * (n + 1) / 2.0
            + window * (w * (w + 1) / 2.0 + (n - w) * w))


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward operations a token of a ``seq_len``-long
    causal sequence would require of this share (3 x the forward); the
    program has no training path for the family, so no cell reads
    this."""
    attention = _pair_ops(cfg) * _prefill_pairs(cfg, seq_len) / seq_len
    return 3.0 * (2.0 * (block_params_per_token(cfg) + _head_params(cfg))
                  + attention)


def kv_bytes_per_token(cfg: dict, kv_bytes: int = 2) -> int:
    """K and V of every key/value head of one token across all layers
    kept (30,720 at the cut's two full and five window layers in
    bfloat16: 2,560 a full layer, 5,120 a window layer). A token beyond
    a window layer's reach is no longer held there: what a SLOT holds
    is ``full x n x 2,560 + window x min(n, 128) x 5,120``."""
    full, window = _kinds(cfg)
    return (full * _row_values(cfg, False)
            + window * _row_values(cfg, True)) * kv_bytes


def kernel_work(cfg: dict, kernel: str, shapes: dict) -> Optional[dict]:
    """``{"ops", "bytes"}`` the mathematics requires of ``kernel`` over
    ``shapes``, all layers, or None for a kernel this family lacks.

    ``gqa_paged_decode_attention`` (both names of the one kernel;
    ``context_lens``: for every decoded token the cached positions its
    query could attend, its own included): a token at context ``n``
    attends ``n`` columns on each full layer and ``min(n,
    sliding_window)`` on each window layer. One query of H heads
    against one cached column is :func:`_pair_ops` operations (40,960)
    and reads that column's row of the layer once: 2,560 bytes in
    bfloat16 on a full layer (16 operations a byte), 5,120 on a window
    layer (8), against the chip's ridge of 240.5: the bytes decide.
    ``gqa_paged_decode_attention_window``: the window layers' part
    alone (the kernel under its ``_window`` name).

    ``forward.decode`` / ``forward.prefill``: the operations of THIS
    CHIP'S SHARE of the model, for ``mfu.serve``. Decode: every weight
    that multiplies the token (:func:`block_params_per_token`: the
    routed experts at the expected 0.5 held assignments a token and
    layer; the head over the 19,072 rows held) and its attention over
    the columns in reach. Prefill: the layers over every prompt token,
    causal attention with the window's cap (:func:`_prefill_pairs`),
    and the head for the one token that is sampled.
    """
    lens = shapes.get("context_lens", ())
    if kernel in ("gqa_paged_decode_attention",
                  "gqa_paged_decode_attention_window"):
        kinds = ((True,) if kernel.endswith("_window")
                 else (False, True))
        columns = _decode_columns(cfg, lens, kinds)
        itemsize = _ITEMSIZE[shapes["kv_dtype"]]
        return {"ops": _pair_ops(cfg) * sum(columns.values()),
                "bytes": float(sum(
                    _row_values(cfg, kind) * itemsize * columns[kind]
                    for kind in kinds))}
    if kernel == "forward.decode":
        return {"ops": 2.0 * (block_params_per_token(cfg)
                              + _head_params(cfg)) * len(lens)
                + _pair_ops(cfg) * sum(_decode_columns(cfg, lens).values())}
    if kernel == "forward.prefill":
        prompts = shapes["prompt_lens"]
        return {"ops": 2.0 * block_params_per_token(cfg)
                * float(sum(prompts))
                + 2.0 * _head_params(cfg) * len(prompts)
                + _pair_ops(cfg) * sum(_prefill_pairs(cfg, n)
                                       for n in prompts)}
    return None
