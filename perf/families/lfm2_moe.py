"""The ``lfm2_moe`` family (``"model_type": "lfm2_moe"``, LiquidAI
LFM2-8B-A1B): everything the benchmark knows about it, and the only
file that does.

Configuration keys are the published ``config.json``'s. A configuration
may be a cut in depth (``num_hidden_layers`` and the ``layer_types``
kept); every expert and every vocabulary row is held. ``registry_name``
and ``model_kwargs`` say which model of the program's registry is built
from them. Serving only: the program has no training path for this
family, and ``compare_loss`` says so.

Operations and bytes here are what the mathematics REQUIRES of a
forward pass, never what a compiled program executes (the decode
kernel multiplies a block-diagonal query against every key/value head's
lanes; the conv kernel moves whole pages of its ring; the decode
program computes frozen slots; none of that is counted).
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from ..harness import ManifestError, prng_key
from ..reference import lfm2_moe as reference
from .afmoe import judge_gaps as _judge_gaps

# options this family adds to the drivers' own: none. The resident
# weight type is the family's init (bfloat16 matrices), not an option.
ENGINE_OPTIONS: dict = {}
TRAINER_OPTIONS: dict = {}

# For a sampled finished request the float32 reference scores the whole
# of prompt + generated tokens (4,096 + 1,024 in the cell: every
# generated token but the first was decoded through the full layers'
# pages AND the conv layers' two-page rings, whose carried rows crossed
# three chunk boundaries of the prompt); at each generated position the
# GAP is the reference's largest logit minus its logit for the emitted
# token. The worst gap says nothing about a bfloat16 system (a near-tie
# of two logits, or of the fourth and fifth of 32 biased scores in some
# layer of some earlier token, flips) and is reported, not compared:
# the rule of every expert family here. Two numbers are compared, as in
# perf/families/afmoe.py:
#
# MEAN_GAP_LIMIT on the mean gap over all checked positions;
# OVER_HALF_LIMIT on the share of positions whose gap is over 0.5.
#
# Both stand between two readings taken on a TPU v5e at the published
# widths under the cell's traffic (PERF.md section 6), near their
# geometric mean: the largest the system showed over its seeds (mean
# 0.0289, share 0.0075 over fifteen seeds), and what the CONTROL showed:
# this reference with both operands of every matrix product rounded to
# float8_e4m3fn, the nearest precision below the bfloat16 the
# configuration serves in, emitting its own greedy tokens along the same
# streams (:func:`control_gaps`; mean 0.390-0.417, share 0.354-0.383 on
# two seeds). The limits leave the system 3.5 and 6.7 times its largest
# reading, and the control fails both, by 3.9 and 7 times.
MEAN_GAP_LIMIT = 0.1
OVER_HALF_LIMIT = 0.05

# rows of queries the reference's attention takes at a time (a block's
# scores are [32, rows, 5120] float32)
REFERENCE_BLOCK = 256
# a stream is padded to a multiple of this, so that a run's streams
# compile the sublayers for one length
REFERENCE_PAD = 1024

_ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}

# file key -> the built model's attribute, for every size the file has
_SIZES = {
    "vocab_size": "vocab_size",
    "max_position_embeddings": "max_seq_len",
    "hidden_size": "hidden_size",
    "num_hidden_layers": "num_layers",
    "num_dense_layers": "first_k_dense",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "mlp_dim",
    "moe_intermediate_size": "moe_dim",
    "num_experts": "n_experts",
    "num_experts_per_tok": "moe_top_k",
    "routed_scaling_factor": "routed_scale",
    "norm_eps": "rms_eps",
    "rope_theta": "rope_theta",
    "conv_L_cache": "conv_width",
}
# keys of the file whose value the program supports in one form only
_FIXED = {"conv_bias": False, "norm_topk_prob": True,
          "use_expert_bias": True}
# keys that describe the configuration, not the model
_ABOUT = {"name", "model_type", "registry_name", "model_kwargs", "source",
          "reduced", "published", "assumed", "departures", "deployment",
          "parameters_held", "published_parameters"}


# -------------------------------------------------------------- model

def build_model(config: dict, dtype: str, platform: str, **extra):
    """The registry model this configuration names, at the file's
    depth, held to every key of the file: each size, the kinds of the
    layers kept, the one form of each switch the program implements,
    and no key the family does not know."""
    import jax.numpy as jnp

    from pytorch_multiprocessing_distributed_tpu import models

    unknown = set(config) - set(_SIZES) - set(_FIXED) - _ABOUT - {
        "layer_types"}
    if unknown:
        raise ManifestError(
            f"keys {sorted(unknown)}: the lfm2_moe family does not know "
            "them")
    try:
        model = models.get_model(
            config["registry_name"],
            dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[dtype],
            num_layers=config["num_hidden_layers"],
            **config.get("model_kwargs", {}), **extra)
    except KeyError as e:        # a program that lacks the family
        raise ManifestError(
            f"the program's registry has no model "
            f"{config['registry_name']!r}: {e}") from e
    want = {key: config[key] for key in _SIZES}
    got = {key: getattr(model, attr) for key, attr in _SIZES.items()}
    want["layer_types"] = list(config["layer_types"])
    got["layer_types"] = list(model.layer_types)
    if got != want:
        bad = sorted(k for k in want if got[k] != want[k])
        raise ManifestError(
            f"registry model {config['registry_name']!r} differs in "
            f"{bad}: it is {got}, the configuration file says {want}")
    for key, value in _FIXED.items():
        if config[key] != value:
            raise ManifestError(
                f"{key} = {config[key]!r}: the program implements "
                f"{value!r} only")
    return model


def init_params(model, seed: int):
    """Random weights on the device in one jitted call, in the types
    they are served in (bfloat16 matrices and taps; float32 router,
    selection bias and gains)."""
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
        "the lfm2_moe family is served, not trained: the program has no "
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
    activations, the softmax's probabilities; sums, norms, the conv's
    products and the residual stream stay float32, as in the system),
    greedy at every generated position of the same streams
    (teacher-forced)."""
    return _gaps(config, params, requests, precision)


def _gaps(config: dict, params, requests, control) -> List[float]:
    """One stream at a time, padded to a multiple of ``REFERENCE_PAD``
    (padding sits after the stream and both mixers are causal, so it
    changes nothing), one SUBLAYER's program at a time: what is live in
    float32 is one mixer (0.07 GB), one dense feed-forward (0.18 GB) or
    one expert of the scan (0.04 GB), and a block of scores."""
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

        def mixer(conv):
            @jax.jit
            def fn(weights, x):
                with jax.default_matmul_precision("highest"):
                    return reference.mixer_sublayer(weights, x, hp, block,
                                                    conv)
            return fn

        mix = {kind: mixer(kind) for kind in set(hp["conv"])}

        @jax.jit
        def feed_forward(weights, x):
            with jax.default_matmul_precision("highest"):
                return reference.feed_forward_sublayer(weights, x, hp)

        def run(tokens):
            x = embed(top, tokens)
            for i, conv in enumerate(hp["conv"]):
                x = mix[conv](params[f"layer_{i}"], x)
                x = feed_forward(params[f"layer_{i}"], x)
            return x

        return run

    # the head over the generated positions only, a fixed number of
    # rows (the longest answer's, rounded up) so that it compiles once
    n_rows = -(-max(len(r.tokens) for r in requests) // 256) * 256

    def head_rows(hp, top, x, first):
        rows = jnp.minimum(first + jnp.arange(n_rows), x.shape[0] - 2)
        with jax.default_matmul_precision("highest"):
            return reference.head(top, x[rows], hp)

    # the tied head's table is an argument: a program that closed over it
    # would carry its 0.27 GB as a constant
    @jax.jit
    def gaps_of(top, x, emitted, first):
        # position j's logits score token j + 1
        logits = head_rows(exact_hp, top, x, first)
        picked = jnp.take_along_axis(logits, emitted[:, None], axis=-1)
        return jnp.max(logits, axis=-1) - picked[:, 0]

    @jax.jit
    def greedy_of(top, x, first):
        return jnp.argmax(head_rows(low_hp, top, x, first), axis=-1)

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
            emitted = greedy_of(top, rounded(tokens), first)
        else:
            emitted = tokens[jnp.minimum(first + 1 + jnp.arange(n_rows),
                                         length - 1)]
        gaps = gaps_of(top, exact(tokens), emitted, first)
        out.extend(float(g) for g in np.asarray(gaps)[:len(request.tokens)])
    return out


# ------------------------------------------- required operations, bytes

def _kinds(cfg: dict):
    """``(full-attention layers, conv layers)`` among the layers kept."""
    full = list(cfg["layer_types"]).count("full_attention")
    return full, len(cfg["layer_types"]) - full


def _head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def _row_values(cfg: dict) -> int:
    """Values a token keeps in one full layer: K of every key/value
    head, then V (1,024 at the published widths)."""
    return 2 * cfg["num_key_value_heads"] * _head_dim(cfg)


def _attention_params(cfg: dict) -> int:
    """q and out of ``H x D``, k and v of ``Hkv x D`` (10.49 M at the
    published widths)."""
    c = cfg["hidden_size"]
    return 2 * c * c + c * _row_values(cfg)


def _conv_params(cfg: dict) -> int:
    """``W_in [C, 3C]``, ``W_out [C, C]`` and the taps ``[3, C]``
    (16.78 M at the published widths)."""
    c = cfg["hidden_size"]
    return 4 * c * c + cfg["conv_L_cache"] * c


def block_params_per_token(cfg: dict) -> float:
    """Weights of the layers that multiply ONE token's activations:
    each layer's mixer, and the dense feed-forward or the router (all
    of its outputs) and the ``num_experts_per_tok`` experts the token
    chose (all are held). No shared expert."""
    c = cfg["hidden_size"]
    full, conv = _kinds(cfg)
    dense = cfg["num_dense_layers"]
    sparse = cfg["num_hidden_layers"] - dense
    expert = 3 * c * cfg["moe_intermediate_size"]
    return (full * _attention_params(cfg) + conv * _conv_params(cfg)
            + dense * 3 * c * cfg["intermediate_size"]
            + sparse * (c * cfg["num_experts"]
                        + cfg["num_experts_per_tok"] * expert))


def parameters(cfg: dict) -> int:
    """Every parameter the configuration holds: the layers (each
    expert layer's every expert), their norms, and the embedding that
    is also the head (3,928.7 M at the cut's 12 layers)."""
    c = cfg["hidden_size"]
    full, conv = _kinds(cfg)
    dense = cfg["num_dense_layers"]
    sparse = cfg["num_hidden_layers"] - dense
    expert = 3 * c * cfg["moe_intermediate_size"]
    norms = (2 * cfg["num_hidden_layers"] + 1) * c + 2 * full * _head_dim(
        cfg)
    return (full * _attention_params(cfg) + conv * _conv_params(cfg)
            + dense * 3 * c * cfg["intermediate_size"]
            + sparse * (c * cfg["num_experts"] + cfg["num_experts"]
                        + cfg["num_experts"] * expert)
            + norms + cfg["vocab_size"] * c)


def _head_params(cfg: dict) -> int:
    """The tied head over the whole vocabulary."""
    return cfg["hidden_size"] * cfg["vocab_size"]


def _pair_ops(cfg: dict) -> float:
    """Operations of one query (all heads) against one cached column:
    ``2 H D`` for the score and ``2 H D`` for the output (8,192 at 32 x
    64)."""
    return 4.0 * cfg["num_attention_heads"] * _head_dim(cfg)


def _conv_ops(cfg: dict) -> float:
    """Operations of one token in one conv layer besides its two
    matmuls: ``u = B X``, three taps and two sums, ``Cg z``."""
    return 7.0 * cfg["hidden_size"]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward operations a token of a ``seq_len``-long
    causal sequence would require (3 x the forward); the program has no
    training path for the family, so no cell reads this."""
    full, conv = _kinds(cfg)
    attention = _pair_ops(cfg) * full * (seq_len + 1) / 2.0
    return 3.0 * (2.0 * (block_params_per_token(cfg) + _head_params(cfg))
                  + attention + conv * _conv_ops(cfg))


def kv_bytes_per_token(cfg: dict, kv_bytes: int = 2) -> int:
    """K and V of every key/value head of one token across the full
    layers kept (6,144 at the cut's three full layers in bfloat16: 2,048
    a layer). The conv layers keep no row a token: a slot's conv state
    is its last ``conv_L_cache`` rows of ``hidden_size`` values a layer,
    whatever its context."""
    full, _ = _kinds(cfg)
    return full * _row_values(cfg) * kv_bytes


def kernel_work(cfg: dict, kernel: str, shapes: dict) -> Optional[dict]:
    """``{"ops", "bytes"}`` the mathematics requires of ``kernel`` over
    ``shapes``, all layers, or None for a kernel this family lacks.

    ``short_conv`` (decode tokens only: the chunk's conv is plain XLA;
    ``context_lens``: for every decoded token its context, its own
    column included, so it sits at position ``n - 1``): each conv layer
    reads the token's ``3C`` projection and its ``min(n - 1, 2)``
    carried rows of ``C``, and writes ``C`` of output and its own row of
    ``C``, all in bfloat16 (28 KiB a token and layer at the published
    widths; :func:`_conv_ops` operations: the bytes decide). The taps
    (``3C`` a call) are left out: no step count reaches this function,
    and at 128 slots they would add 0.3 % to the bytes, so the share
    reads at most that much low.

    ``gqa_paged_decode_attention`` (the full layers' kernel): a token at
    context ``n`` attends ``n`` columns on each full layer; one query of
    H heads against one column is :func:`_pair_ops` operations (8,192)
    and reads that column's row of 2,048 bytes in bfloat16 (4
    operations a byte, against the chip's ridge of 240.5: the bytes
    decide).

    ``gqa_chunk_attention`` (``prompt_lens``): each prompt's causal
    pairs on each full layer, and each token's row read once.

    ``forward.decode`` / ``forward.prefill``: the model's operations,
    for ``mfu.serve``. Decode: every weight that multiplies the token
    (:func:`block_params_per_token`), the head over the vocabulary, the
    conv's elementwise work and the attention over the context.
    Prefill: the layers over every prompt token, causal attention, and
    the head for the one token that is sampled.
    """
    full, conv = _kinds(cfg)
    lens = shapes.get("context_lens", ())
    itemsize = _ITEMSIZE[shapes.get("kv_dtype", "bfloat16")]
    if kernel == "short_conv":
        c = cfg["hidden_size"]
        rows = sum(5 * c + min(n - 1, 2) * c for n in lens)
        return {"ops": _conv_ops(cfg) * conv * len(lens),
                "bytes": float(conv * rows * itemsize)}
    if kernel == "gqa_paged_decode_attention":
        columns = float(full * sum(lens))
        return {"ops": _pair_ops(cfg) * columns,
                "bytes": _row_values(cfg) * itemsize * columns}
    prompts = shapes.get("prompt_lens", ())
    if kernel == "gqa_chunk_attention":
        return {"ops": _pair_ops(cfg) * full
                * sum(n * (n + 1) / 2.0 for n in prompts),
                "bytes": float(full * _row_values(cfg) * itemsize
                               * sum(prompts))}
    if kernel == "forward.decode":
        return {"ops": (2.0 * (block_params_per_token(cfg)
                               + _head_params(cfg)) + conv * _conv_ops(cfg))
                * len(lens) + _pair_ops(cfg) * full * sum(lens)}
    if kernel == "forward.prefill":
        return {"ops": (2.0 * block_params_per_token(cfg)
                        + conv * _conv_ops(cfg)) * float(sum(prompts))
                + 2.0 * _head_params(cfg) * len(prompts)
                + _pair_ops(cfg) * full * sum(n * (n + 1) / 2.0
                                              for n in prompts)}
    return None
