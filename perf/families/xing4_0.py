"""The ``xing4_0`` family (``"model_type": "xing4_0"``): everything the
benchmark knows about it, and the only file that does.

Configuration keys are the published ``config.json``'s (the
DeepSeek-V3 family's plus the ``hc_*`` / ``mhc_*`` keys of the widened
residual); ``registry_name`` and ``model_kwargs`` say which model of
the program's registry is built from them. Serving only: the program
has no training path for this family, and ``compare_loss`` says so.

Operations and bytes here are what the published mathematics REQUIRES
of a forward pass, never what a compiled program executes (a chunked
prefill decompresses the whole cached prefix again every chunk; the
decode program computes frozen slots; none of that is counted).
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from ..harness import ManifestError, prng_key
from ..reference import xing4_0 as reference

# options this family adds to the drivers' own: none. The resident
# weight type is the family's init (bfloat16 matrices), not an option.
ENGINE_OPTIONS: dict = {}
TRAINER_OPTIONS: dict = {}

# For a sampled finished request the float32 reference scores the whole
# of prompt + generated tokens; at each generated position the GAP is
# the reference's largest logit minus its logit for the emitted token.
# With normal(0, 0.02) weights and a unit-RMS final hidden of 3,584
# values the logits over 131,072 entries have a standard deviation of
# ~1.2 and the largest stands ~5 above the mean: a wrong cache row, page
# or position emits tokens the reference ranks like random ones, gap ~5.
#
# The system computes in bfloat16 (8 bits of mantissa): its logits
# differ from the reference's by a few 1e-2, so a near-tie of the two
# largest logits flips (a gap of that size) - and so does a near-tie of
# the fourth and fifth expert in some layer of some earlier token, which
# moves that token's hidden state by a whole expert's output: rare
# positions then read gaps of 1-3 in a run whose MEAN is 0.02 (with no
# choice of experts the largest gap on the CPU is 0.009, with one 0.32:
# PERF.md section 6). The worst gap therefore says nothing about a
# bfloat16 system and is reported, not compared. Two numbers are:
#
# MEAN_GAP_LIMIT on the mean gap over all checked positions: whatever
# is wrong at every position, or at one position in sixteen (a page
# boundary) or in a chunk's width, moves it by 0.1 or more.
# OVER_HALF_LIMIT on the share of positions whose gap is over 0.5 (a
# token the reference does not rank among its near-ties): sparse faults
# that leave the mean alone.
#
# Both stand between two readings taken on the chip (PERF.md section 6,
# my chip runs, PR 29): the largest the system showed over its seeds,
# and what the CONTROL showed: this reference with both operands of every
# matrix product rounded to float8_e4m3fn, the nearest precision below
# bfloat16, emitting its own greedy tokens along the same streams
# (:func:`control_gaps`).
MEAN_GAP_LIMIT = 0.08
OVER_HALF_LIMIT = 0.06

# rows the reference computes at a time (queries of the attention, rows
# of every feed-forward); a stream is padded to a multiple of it
REFERENCE_BLOCK = 512
# ... and to a multiple of this, so that a run's streams compile the
# layers for a few lengths only
REFERENCE_PAD = 1024
# rows an expert of the reference takes a round (its own tokens only:
# the masked loop over 64 experts costs 16 times the chosen four)
REFERENCE_EXPERT_ROWS = 512

_ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}

# file key -> the built model's attribute, for every size the file has
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
    "n_routed_experts": "n_experts",
    "n_shared_experts": "n_shared_experts",
    "num_experts_per_tok": "moe_top_k",
    "routed_scaling_factor": "routed_scale",
    "hc_mult": "hc_mult",
    "hc_sinkhorn_iters": "hc_iters",
    "hc_eps": "hc_eps",
    "rms_norm_eps": "rms_eps",
    "rope_theta": "rope_theta",
}
# keys of the file whose value the program supports in one form only
_FIXED = {"attention_bias": False, "hidden_act": "silu",
          "scoring_func": "sigmoid", "topk_method": "noaux_tc",
          "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
          "tie_word_embeddings": False, "moe_layer_freq": 1,
          "num_key_value_heads": None,      # = num_attention_heads
          "num_nextn_predict_layers": 0, "ep_size": 1}


# -------------------------------------------------------------- model

def build_model(config: dict, dtype: str, platform: str, **extra):
    """The registry model this configuration names at the file's depth,
    held to every size in the file (the reduced ones too) and to the
    one form of each switch the program implements."""
    import jax.numpy as jnp

    from pytorch_multiprocessing_distributed_tpu import models

    try:
        model = models.get_model(
            config["registry_name"],
            dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[dtype],
            num_layers=config["num_hidden_layers"],
            first_k_dense=config["first_k_dense_replace"],
            **config.get("model_kwargs", {}), **extra)
    except KeyError as e:        # a program that lacks the family
        raise ManifestError(
            f"the program's registry has no model "
            f"{config['registry_name']!r}: {e}") from e
    want = {key: config[key] for key in _SIZES}
    got = {key: getattr(model, attr) for key, attr in _SIZES.items()}
    want["mhc_h_res_clamp"] = (config["mhc_h_res_clamp_min"],
                               config["mhc_h_res_clamp_max"])
    got["mhc_h_res_clamp"] = tuple(model.hc_clamp)
    scaling = config["rope_scaling"]
    want["rope_scaling"] = ("yarn",) + tuple(float(scaling[k]) for k in (
        "factor", "original_max_position_embeddings", "beta_fast",
        "beta_slow", "mscale", "mscale_all_dim"))
    got["rope_scaling"] = (scaling["type"],) + tuple(
        float(v) for v in model.yarn)
    if got != want:
        raise ManifestError(
            f"registry model {config['registry_name']!r} is {got}, the "
            f"configuration file says {want}")
    for key, value in _FIXED.items():
        value = config["num_attention_heads"] if value is None else value
        if config[key] != value:
            raise ManifestError(
                f"{key} = {config[key]!r}: the program implements "
                f"{value!r} only")
    return model


def init_params(model, seed: int):
    """Random weights on the device in one jitted call, in the types
    they are served in (bfloat16 matrices; float32 router, ``e_bias``,
    mixers and norms)."""
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
        "the xing4_0 family is served, not trained: the program has no "
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
    residual streams stay float32, as in the system), greedy at every
    generated position of the same streams (teacher-forced)."""
    return _gaps(config, params, requests, precision)


def _gaps(config: dict, params, requests, control) -> List[float]:
    """One stream at a time, padded to a few lengths (padding sits
    after the stream and the mask is causal, so it changes nothing),
    one layer's program at a time so that one layer's float32 weights
    are all that is live beside the resident ones."""
    import jax
    import jax.numpy as jnp

    if not requests:
        return []
    block = REFERENCE_BLOCK

    def low(a):
        return a.astype(jnp.dtype(control)).astype(jnp.float32)

    exact_hp = {**reference.hyper(config),
                "expert_rows": REFERENCE_EXPERT_ROWS}
    low_hp = {**exact_hp, "round": low}

    def forward(hp):
        embed = jax.jit(lambda params, tokens:
                        reference.embed(params, tokens, hp))

        @jax.jit
        def layer(weights, x):
            with jax.default_matmul_precision("highest"):
                return reference.layer_forward(weights, x, hp, block)

        def run(tokens):
            x = embed(top, tokens)
            for i in range(hp["layers"]):
                x = layer(params[f"layer_{i}"], x)
            return x

        return run

    # the head over the generated positions only, a fixed number of
    # rows (the longest answer's, rounded up) so that it compiles once
    n_rows = -(-max(len(r.tokens) for r in requests) // 256) * 256

    def head_rows(hp, x, first):
        rows = jnp.minimum(first + jnp.arange(n_rows), x.shape[0] - 2)
        with jax.default_matmul_precision("highest"):
            return rows, reference.head(top, x[rows], hp)

    @jax.jit
    def gaps_of(x, emitted, first):
        # position j's logits score token j + 1
        rows, logits = head_rows(exact_hp, x, first)
        picked = jnp.take_along_axis(logits, emitted[:, None], axis=-1)
        return jnp.max(logits, axis=-1) - picked[:, 0]

    @jax.jit
    def greedy_of(x, first):
        return jnp.argmax(head_rows(low_hp, x, first)[1], axis=-1)

    top = {k: v for k, v in params.items() if not k.startswith("layer_")}
    exact = forward(exact_hp)
    rounded = forward(low_hp) if control else None
    out: List[float] = []
    for request in requests:
        stream = list(request.prompt) + list(request.tokens)
        first = len(request.prompt) - 1     # scores generated token 0
        length = -(-(len(stream) + 1) // REFERENCE_PAD) * REFERENCE_PAD
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


def _mixer_params(cfg: dict) -> int:
    n = cfg["hc_mult"]
    return 2 * n * cfg["hidden_size"] * (2 * n + n * n)   # two a layer


def block_params_per_token(cfg: dict) -> int:
    """Weights of the layers that multiply ONE token's activations:
    attention, both mixers, and the dense feed-forward or the router,
    the chosen experts and the shared ones."""
    c = cfg["hidden_size"]
    dense = cfg["first_k_dense_replace"]
    sparse = cfg["num_hidden_layers"] - dense
    expert = 3 * c * cfg["moe_intermediate_size"]
    per_layer = _attention_params(cfg) + _mixer_params(cfg)
    return (cfg["num_hidden_layers"] * per_layer
            + dense * 3 * c * cfg["intermediate_size"]
            + sparse * (c * cfg["n_routed_experts"]
                        + (cfg["num_experts_per_tok"]
                           + cfg["n_shared_experts"]) * expert))


def _head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward operations a token of a ``seq_len``-long
    causal sequence would require (3 x the forward); the program has no
    training path for the family, so no cell reads this."""
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
    the output in the latent space (69,632 at H 32, R 512, rope 64) and
    reads that token's ``R + rope`` cached values once (1,152 bytes in
    bfloat16), whatever the number of heads; a decoded token also reads
    its queries and writes its latent output (``H (2 R + rope)``
    values a layer).

    ``forward.decode`` / ``forward.prefill``: the whole model's
    operations, for ``mfu.serve``. Decode: every weight that multiplies
    the token (:func:`block_params_per_token`, the head) and its
    attention over the cache; the absorbed products with ``W_uk`` and
    ``W_uv`` are ``wkv_b``'s own operation count. Prefill: the layers
    over every prompt token, causal decompressed attention (``H (nope +
    rope + v)`` multiply-adds a pair of positions, half a square), and
    the head for the one token that is sampled.
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
