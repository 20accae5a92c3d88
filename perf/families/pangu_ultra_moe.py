"""The ``pangu_ultra_moe`` family (``"model_type": "pangu_ultra_moe"``):
everything the benchmark knows about it, and the only file that does.

Configuration keys are the published ``config.json``'s (the
DeepSeek-V3 family's plus ``sandwich_norm``). A configuration may be
ONE CHIP'S SHARE of an expert-parallel deployment: ``n_routed_experts``
then counts the experts HELD (``expert_offset`` says from which), the
router keeps the published width that stands under ``published``, and
``vocab_size`` is the slice of the vocabulary held. ``registry_name``
and ``model_kwargs`` say which model of the program's registry is built
from them. Serving only: the program has no training path for this
family, and ``compare_loss`` says so.

Operations and bytes here are what the share's mathematics REQUIRES of
a forward pass, never what a compiled program executes (a chunked
prefill decompresses the whole cached prefix again every chunk; the
decode program computes frozen slots and gathers a row for every
assignment, held or not; none of that is counted).
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from ..harness import ManifestError, prng_key
from ..reference import pangu_ultra_moe as reference

# options this family adds to the drivers' own: none. The resident
# weight type is the family's init (bfloat16 matrices), not an option.
ENGINE_OPTIONS: dict = {}
TRAINER_OPTIONS: dict = {}

# For a sampled finished request the float32 reference scores the whole
# of prompt + generated tokens; at each generated position the GAP is
# the reference's largest logit minus its logit for the emitted token.
# With normal(0, 0.02) weights and a unit-RMS final hidden of 7,680
# values the logits over the 19,200 rows held have a standard deviation
# of ~1.75 and the largest stands ~7 above the mean: a wrong cache row,
# page or position emits tokens the reference ranks like random ones.
#
# The system computes in bfloat16 (8 bits of mantissa): its logits
# differ from the reference's by a few 1e-2, so a near-tie of the two
# largest logits flips (a gap of that size) - and so does a near-tie of
# the eighth and ninth of 256 experts in some layer of some earlier
# token, which, where one of the two is held here, moves that token's
# hidden state by a whole expert's output. The worst gap therefore says
# nothing about a bfloat16 system and is reported, not compared
# (perf/families/xing4_0.py has the same rule for the same reason). Two
# numbers are:
#
# MEAN_GAP_LIMIT on the mean gap over all checked positions: whatever
# is wrong at every position, or at one position in sixteen (a page
# boundary) or in a chunk's width, moves it.
# OVER_HALF_LIMIT on the share of positions whose gap is over 0.5 (a
# token the reference does not rank among its near-ties): sparse faults
# that leave the mean alone.
#
# Both stand between two readings taken on the chip at the published
# widths under the cell's traffic (PERF.md section 6, my chip runs,
# PR 33): the largest the system showed over sixteen seeds (mean gap
# 0.00092 to 0.00239; share over a half 0 to 0.00151; the worst single
# gap 0.36 to 1.00), and what the CONTROL showed: this reference with
# both operands of every matrix product rounded to float8_e4m3fn, the
# nearest precision below bfloat16, emitting its own greedy tokens
# along the same streams (:func:`control_gaps`): mean gap 1.63, share
# over a half 0.787. Each limit is ~20 times the system's largest
# reading (fresh seeds read higher) and ~30 times under the control's.
# The system reads ten times lower here than ``xing4_0`` does (0.023):
# every sublayer's output passes a norm before it is added, so a
# bfloat16 error does not grow along the residual, and a flipped
# expert choice matters only where one of the two is held.
MEAN_GAP_LIMIT = 0.05
OVER_HALF_LIMIT = 0.03

# rows of queries the reference's attention takes at a time
REFERENCE_BLOCK = 512
# a stream is padded to a multiple of this, so that a run's streams
# compile the sublayers for one length or two
REFERENCE_PAD = 1024

_ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}

# file key -> the built model's attribute, for every size the file has
# (n_routed_experts: the experts HELD; the router's width is under
# ``published`` and is held to ``n_experts`` in build_model)
_SIZES = {
    "vocab_size": "vocab_size",
    "max_position_embeddings": "max_seq_len",
    "hidden_size": "hidden_size",
    "num_hidden_layers": "num_layers",
    "first_k_dense_replace": "first_k_dense",
    "num_attention_heads": "num_heads",
    "q_lora_rank": "q_lora_rank",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    "intermediate_size": "mlp_dim",
    "moe_intermediate_size": "moe_dim",
    "n_routed_experts": "n_held",
    "expert_offset": "expert_offset",
    "n_shared_experts": "n_shared_experts",
    "num_experts_per_tok": "moe_top_k",
    "routed_scaling_factor": "routed_scale",
    "rms_norm_eps": "rms_eps",
    "rope_theta": "rope_theta",
}
# keys of the file whose value the program supports in one form only
_FIXED = {"attention_bias": False, "hidden_act": "silu",
          "norm_topk_prob": True, "sandwich_norm": True,
          "tie_word_embeddings": False,
          "num_key_value_heads": None,      # = num_attention_heads
          "num_nextn_predict_layers": 0}


def router_width(config: dict) -> int:
    """The number of experts the router scores: the published count,
    which a configuration that holds a share keeps under
    ``published``."""
    return int(config.get("published", {}).get(
        "n_routed_experts", config["n_routed_experts"]))


# -------------------------------------------------------------- model

def build_model(config: dict, dtype: str, platform: str, **extra):
    """The registry model this configuration names, at the file's
    depth, share of the experts and slice of the vocabulary, held to
    every size in the file (the reduced ones too) and to the one form
    of each switch the program implements."""
    import jax.numpy as jnp

    from pytorch_multiprocessing_distributed_tpu import models

    try:
        model = models.get_model(
            config["registry_name"],
            dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[dtype],
            num_layers=config["num_hidden_layers"],
            first_k_dense=config["first_k_dense_replace"],
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
    if got != want:
        raise ManifestError(
            f"registry model {config['registry_name']!r} is {got}, the "
            f"configuration file says {want}")
    if model.yarn is not None:
        raise ManifestError(
            "the configuration has no rope_scaling, the registry model "
            f"{config['registry_name']!r} has {model.yarn}")
    for key, value in _FIXED.items():
        value = config["num_attention_heads"] if value is None else value
        if config[key] != value:
            raise ManifestError(
                f"{key} = {config[key]!r}: the program implements "
                f"{value!r} only")
    return model


def init_params(model, seed: int):
    """Random weights on the device in one jitted call, in the types
    they are served in (bfloat16 matrices; float32 router and norms)."""
    return model.init(prng_key(seed))["params"]


# -------------------------------------------------------- comparisons

def compare_streams(config: dict, params, requests, s_max: int) -> dict:
    """Served streams against the reference: ``compared`` is what
    decides ``correct``."""
    return judge_gaps(stream_gaps(config, params, requests))


def judge_gaps(gaps: List[float]) -> dict:
    gaps = np.asarray(gaps, np.float64)
    mean = float(gaps.mean()) if gaps.size else float("inf")
    over = float((gaps > 0.5).mean()) if gaps.size else float("inf")
    return {
        "compared": [{"what": "mean_logit_gap", "value": mean,
                      "limit": MEAN_GAP_LIMIT},
                     {"what": "share_of_gaps_over_half", "value": over,
                      "limit": OVER_HALF_LIMIT}],
        "checks": {"mean_logit_gap": mean,
                   "mean_gap_limit": MEAN_GAP_LIMIT,
                   "share_of_gaps_over_half": over,
                   "over_half_limit": OVER_HALF_LIMIT,
                   "worst_logit_gap": (float(gaps.max()) if gaps.size
                                       else float("inf")),
                   "p99_logit_gap": (float(np.quantile(gaps, 0.99))
                                     if gaps.size else float("inf")),
                   "checked_positions": int(gaps.size)},
    }


def compare_loss(config: dict, params, tokens):
    raise ManifestError(
        "the pangu_ultra_moe family is served, not trained: the program "
        "has no training forward for it (ROADMAP.md B1)")


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
    """One stream at a time, padded to a few lengths (padding sits
    after the stream and the mask is causal, so it changes nothing),
    one SUBLAYER's program at a time: an expert layer here is 1.0 B
    parameters, 4.0 GB in float32 beside 9.85 GB resident, so what is
    live in float32 is one attention (0.79 GB), one dense feed-forward
    or one expert of the scan (0.19 GB)."""
    import jax
    import jax.numpy as jnp

    if not requests:
        return []
    block = REFERENCE_BLOCK

    def low(a):
        return a.astype(jnp.dtype(control)).astype(jnp.float32)

    exact_hp = reference.hyper(config)
    low_hp = {**exact_hp, "round": low}
    embed = jax.jit(reference.embed)

    def forward(hp):
        @jax.jit
        def attention(weights, x):
            with jax.default_matmul_precision("highest"):
                return reference.attention_sublayer(weights, x, hp, block)

        @jax.jit
        def feed_forward(weights, x):
            with jax.default_matmul_precision("highest"):
                return reference.feed_forward_sublayer(weights, x, hp)

        def run(tokens):
            x = embed(top, tokens)
            for i in range(hp["layers"]):
                x = attention(params[f"layer_{i}"], x)
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
        # the last token is scored by the row before it: no row more
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

def _attention_params(cfg: dict) -> int:
    c, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    return (c * cfg["q_lora_rank"] + cfg["q_lora_rank"] * h * (nope + rope)
            + c * (cfg["kv_lora_rank"] + rope)
            + cfg["kv_lora_rank"] * h * (nope + v) + h * v * c)


def block_params_per_token(cfg: dict) -> float:
    """Weights of the layers that multiply ONE token's activations on
    THIS chip: attention, and the dense feed-forward or the router (all
    of its outputs), the shared experts and the EXPECTED number of a
    token's chosen experts that are held here: ``num_experts_per_tok x
    held / router width`` (0.5 with 16 of 256 at top-8: routing over
    random weights is even), not the 8 the whole deployment computes."""
    c = cfg["hidden_size"]
    dense = cfg["first_k_dense_replace"]
    sparse = cfg["num_hidden_layers"] - dense
    expert = 3 * c * cfg["moe_intermediate_size"]
    width = router_width(cfg)
    held_per_token = (cfg["num_experts_per_tok"]
                      * cfg["n_routed_experts"] / width)
    return (cfg["num_hidden_layers"] * _attention_params(cfg)
            + dense * 3 * c * cfg["intermediate_size"]
            + sparse * (c * width
                        + (held_per_token + cfg["n_shared_experts"])
                        * expert))


def _head_params(cfg: dict) -> int:
    """The head over the rows of the vocabulary held here."""
    return cfg["hidden_size"] * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward operations a token of a ``seq_len``-long
    causal sequence would require of this share (3 x the forward); the
    program has no training path for the family, so no cell reads
    this."""
    h = cfg["num_attention_heads"]
    qkv = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
           + cfg["v_head_dim"])
    attention = 2.0 * h * qkv * seq_len / 2 * cfg["num_hidden_layers"]
    return 3.0 * (2.0 * (block_params_per_token(cfg) + _head_params(cfg))
                  + attention)


def kv_bytes_per_token(cfg: dict, kv_bytes: int = 2) -> int:
    """The latent and the shared position key of one token across all
    layers (5,760 at five layers in bfloat16)."""
    return (cfg["num_hidden_layers"]
            * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * kv_bytes)


def kernel_work(cfg: dict, kernel: str, shapes: dict) -> Optional[dict]:
    """``{"ops", "bytes"}`` the mathematics requires of ``kernel`` over
    ``shapes``, all layers, or None for a kernel this family lacks.

    ``mla_paged_decode_attention`` (``context_lens``: for every decoded
    token the cached positions its query attends, its own included):
    in the absorbed form one query of H heads against one cached token
    is ``2 H (R + rope)`` operations for the scores and ``2 H R`` for
    the output in the latent space (278,528 at H 128, R 512, rope 64)
    and reads that token's ``R + rope`` cached values once (1,152 bytes
    in bfloat16), whatever the number of heads: 242 operations a byte,
    the chip's own ridge (197e12 / 819e9 = 240.5); a decoded token also
    reads its queries and writes its latent output (``H (2 R + rope)``
    values a layer).

    ``forward.decode`` / ``forward.prefill``: the operations of THIS
    CHIP'S SHARE of the model, for ``mfu.serve``. Decode: every weight
    that multiplies the token (:func:`block_params_per_token`: the
    routed experts at the expected 0.5 held assignments a token and
    layer; the head over the 19,200 rows held) and its attention over
    the cache; the absorbed products with ``W_uk`` and ``W_uv`` are
    ``wkv_b``'s own operation count. Prefill: the layers over every
    prompt token, causal decompressed attention (``H (nope + rope +
    v)`` multiply-adds a pair of positions, half a square), and the
    head for the one token that is sampled.
    """
    layers, h = cfg["num_hidden_layers"], cfg["num_attention_heads"]
    r, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    lens = shapes.get("context_lens", ())
    cached = float(sum(lens))
    latent_ops = 2.0 * h * (2 * r + rope)       # a cached token, layer
    if kernel == "mla_paged_decode_attention":
        act = _ITEMSIZE[shapes["dtype"]]
        return {"ops": latent_ops * cached * layers,
                "bytes": cached * kv_bytes_per_token(
                    cfg, _ITEMSIZE[shapes["kv_dtype"]])
                + float(len(lens)) * h * (2 * r + rope) * act * layers}
    if kernel == "forward.decode":
        return {"ops": 2.0 * (block_params_per_token(cfg)
                              + _head_params(cfg)) * len(lens)
                + latent_ops * cached * layers}
    if kernel == "forward.prefill":
        prompts = shapes["prompt_lens"]
        qkv = (cfg["qk_nope_head_dim"] + rope + cfg["v_head_dim"])
        return {"ops": 2.0 * block_params_per_token(cfg)
                * float(sum(prompts))
                + 2.0 * _head_params(cfg) * len(prompts)
                + sum(float(n) * n for n in prompts) * h * qkv * layers}
    return None
