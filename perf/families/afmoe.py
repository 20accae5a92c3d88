"""The ``afmoe`` family (``"model_type": "afmoe"``, Arcee Trinity):
everything the benchmark knows about it, and the only file that does.

Configuration keys are the published ``config.json``'s. A configuration
may be ONE CHIP'S SHARE of an expert-parallel deployment:
``num_experts`` then counts the experts HELD (``expert_offset`` says
from which), the router keeps the published width that stands under
``published``, ``vocab_size`` is the slice of the vocabulary held, and
``layer_types`` lists the kinds of the layers kept. ``registry_name``
and ``model_kwargs`` say which model of the program's registry is built
from them. Serving only: the program has no training path for this
family, and ``compare_loss`` says so.

Operations and bytes here are what the share's mathematics REQUIRES of
a forward pass, never what a compiled program executes (the decode
kernel multiplies a block-diagonal query against all eight key/value
heads' lanes; a chunk of a full layer attends the whole bucket under a
mask; the decode program computes frozen slots; none of that is
counted).
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from ..harness import ManifestError, prng_key
from ..reference import afmoe as reference

# options this family adds to the drivers' own: none. The resident
# weight type is the family's init (bfloat16 matrices), not an option.
ENGINE_OPTIONS: dict = {}
TRAINER_OPTIONS: dict = {}

# For a sampled finished request the float32 reference scores the whole
# of prompt + generated tokens (8,192 + 1,024 in the cell: every
# generated token was decoded through BOTH pools beyond the window, the
# ring wrapping every 16 tokens); at each generated position the GAP is
# the reference's largest logit minus its logit for the emitted token.
# With normal(0, 0.02) weights and a unit-RMS final hidden of 3,072
# values the logits over the 25,024 rows held have a standard deviation
# of ~1.1 and the largest stands ~4.5 above the mean: a wrong cache
# row, ring entry, window bound or position emits tokens the reference
# ranks like random ones.
#
# The system computes in bfloat16, so a near-tie of the two largest
# logits flips, and so does a near-tie of the fourth and fifth of 256
# biased scores in some layer of some earlier token: the worst gap says
# nothing about a bfloat16 system and is reported, not compared (the
# rule of perf/families/xing4_0.py and pangu_ultra_moe.py, for the same
# reason). Two numbers are compared:
#
# MEAN_GAP_LIMIT on the mean gap over all checked positions;
# OVER_HALF_LIMIT on the share of positions whose gap is over 0.5.
#
# Both stand between two readings taken on the chip at the published
# widths under the cell's traffic (PERF.md section 6, my chip runs,
# PR 36): the largest the system showed over fourteen seeds (mean gap
# 0.00162 to 0.00338; share over a half 0.00049 to 0.00259; the worst
# single gap 0.67 to 2.26), and what the CONTROL showed: this reference
# with both operands of every matrix product rounded to float8_e4m3fn,
# the nearest precision below bfloat16, emitting its own greedy tokens
# along the same streams (:func:`control_gaps`): mean gap 1.38, share
# over a half 0.813. The mean's limit is 15 times the system's largest
# reading (fresh seeds read higher) and 28 times under the control's;
# the share's 12 times over and 27 times under. The system reads as low
# as ``pangu_ultra_moe`` does: every sublayer's output passes a norm
# before it is added, so a bfloat16 error does not grow along the
# residual.
MEAN_GAP_LIMIT = 0.05
OVER_HALF_LIMIT = 0.03

# rows of queries the reference's attention takes at a time (a block's
# scores are [48, rows, 9216] float32)
REFERENCE_BLOCK = 256
# a stream is padded to a multiple of this, so that a run's streams
# compile the sublayers for one length
REFERENCE_PAD = 1024

_ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}

# file key -> the built model's attribute, for every size the file has
# (num_experts: the experts HELD; the router's width is under
# ``published`` and is held to ``n_experts`` in build_model)
_SIZES = {
    "vocab_size": "vocab_size",
    "max_position_embeddings": "max_seq_len",
    "hidden_size": "hidden_size",
    "num_hidden_layers": "num_layers",
    "num_dense_layers": "first_k_dense",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "mlp_dim",
    "moe_intermediate_size": "moe_dim",
    "num_experts": "n_held",
    "expert_offset": "expert_offset",
    "num_shared_experts": "n_shared_experts",
    "num_experts_per_tok": "moe_top_k",
    "route_scale": "routed_scale",
    "rms_norm_eps": "rms_eps",
    "rope_theta": "rope_theta",
    "sliding_window": "sliding_window",
    "global_attn_every_n_layers": "full_every",
}
# keys of the file whose value the program supports in one form only
_FIXED = {"hidden_act": "silu", "score_func": "sigmoid",
          "route_norm": True, "mup_enabled": True, "rope_scaling": None,
          "tie_word_embeddings": False, "n_group": 1, "topk_group": 1,
          "num_expert_groups": 1, "num_limited_groups": 1}


def router_width(config: dict) -> int:
    """The number of experts the router scores: the published count,
    which a configuration that holds a share keeps under
    ``published``."""
    return int(config.get("published", {}).get(
        "num_experts", config["num_experts"]))


# -------------------------------------------------------------- model

def build_model(config: dict, dtype: str, platform: str, **extra):
    """The registry model this configuration names, at the file's
    depth, share of the experts and slice of the vocabulary, held to
    every size in the file (the reduced ones and the kinds of the
    layers kept too) and to the one form of each switch the program
    implements."""
    import jax.numpy as jnp

    from pytorch_multiprocessing_distributed_tpu import models

    try:
        model = models.get_model(
            config["registry_name"],
            dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[dtype],
            num_layers=config["num_hidden_layers"],
            first_k_dense=config["num_dense_layers"],
            n_experts=router_width(config),
            experts_held=config["num_experts"],
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
    want["layer_types"], got["layer_types"] = (list(config["layer_types"]),
                                               list(model.layer_types))
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
    bias and gains)."""
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
        "the afmoe family is served, not trained: the program has no "
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
    changes nothing), one SUBLAYER's program at a time: an expert layer
    here is 1.0 B parameters, 4.0 GB in float32 beside 8.65 GB
    resident, so what is live in float32 is one attention (0.25 GB),
    one dense feed-forward (0.45 GB) or one expert of the scan (0.11
    GB), and a block of scores."""
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

def _attention_params(cfg: dict) -> int:
    """q, gate and out projections of ``H x head_dim``, k and v of
    ``Hkv x head_dim`` (62.91 M at the published widths)."""
    c = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return 3 * c * q + 2 * c * kv


def block_params_per_token(cfg: dict) -> float:
    """Weights of the layers that multiply ONE token's activations on
    THIS chip: attention, and the dense feed-forward or the router (all
    of its outputs), the shared expert and the EXPECTED number of a
    token's chosen experts that are held here: ``num_experts_per_tok x
    held / router width`` (0.5 with 32 of 256 at top-4: routing over
    random weights is even), not the 4 the whole deployment computes."""
    c = cfg["hidden_size"]
    dense = cfg["num_dense_layers"]
    sparse = cfg["num_hidden_layers"] - dense
    expert = 3 * c * cfg["moe_intermediate_size"]
    width = router_width(cfg)
    held_per_token = cfg["num_experts_per_tok"] * cfg["num_experts"] / width
    return (cfg["num_hidden_layers"] * _attention_params(cfg)
            + dense * 3 * c * cfg["intermediate_size"]
            + sparse * (c * width
                        + (held_per_token + cfg["num_shared_experts"])
                        * expert))


def _head_params(cfg: dict) -> int:
    """The head over the rows of the vocabulary held here."""
    return cfg["hidden_size"] * cfg["vocab_size"]


def _kinds(cfg: dict):
    """``(full layers, sliding layers)`` among the layers kept."""
    full = sum(kind == "full_attention" for kind in cfg["layer_types"])
    return full, len(cfg["layer_types"]) - full


def _columns(cfg: dict, lens) -> float:
    """Cached columns the layers attend, summed over decoded tokens at
    contexts ``lens`` (each its own column included): the context on a
    full layer, the window's worth at most on a sliding one."""
    full, sliding = _kinds(cfg)
    window = cfg["sliding_window"]
    return float(sum(full * n + sliding * min(n, window) for n in lens))


def _prefill_pairs(cfg: dict, n: int) -> float:
    """(query, key) pairs all layers attend over a prompt of ``n``
    tokens: half a square on a full layer, a band of the window's
    width on a sliding one."""
    full, sliding = _kinds(cfg)
    w = min(n, cfg["sliding_window"])
    return (full * n * (n + 1) / 2.0
            + sliding * (w * (w + 1) / 2.0 + (n - w) * w))


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward operations a token of a ``seq_len``-long
    causal sequence would require of this share (3 x the forward); the
    program has no training path for the family, so no cell reads
    this."""
    pair = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]
    attention = pair * _prefill_pairs(cfg, seq_len) / seq_len
    return 3.0 * (2.0 * (block_params_per_token(cfg) + _head_params(cfg))
                  + attention)


def kv_bytes_per_token(cfg: dict, kv_bytes: int = 2) -> int:
    """K and V of every key/value head of one token across all layers
    kept (20,480 at five layers in bfloat16: 4,096 a layer). A token
    beyond a sliding layer's window is no longer held there: what a
    SLOT holds is ``full x n + sliding x min(n, window)`` of these
    rows."""
    return (cfg["num_hidden_layers"] * 2 * cfg["num_key_value_heads"]
            * cfg["head_dim"] * kv_bytes)


def kernel_work(cfg: dict, kernel: str, shapes: dict) -> Optional[dict]:
    """``{"ops", "bytes"}`` the mathematics requires of ``kernel`` over
    ``shapes``, all layers, or None for a kernel this family lacks.

    ``gqa_paged_decode_attention`` (both names of the one kernel;
    ``context_lens``: for every decoded token the cached positions its
    query could attend, its own included): a token at context ``n``
    attends ``n`` columns on each full layer and ``min(n,
    sliding_window)`` on each sliding one. One query of H heads against
    one cached column is ``2 H head_dim`` operations for the scores and
    as many for the output (24,576 at 48 x 128) and reads that column's
    K and V of every key/value head once (``2 Hkv head_dim`` values:
    4,096 bytes in bfloat16), whatever the number of query heads: 6
    operations a byte against the chip's ridge of 240.5, so the bytes
    decide.

    ``forward.decode`` / ``forward.prefill``: the operations of THIS
    CHIP'S SHARE of the model, for ``mfu.serve``. Decode: every weight
    that multiplies the token (:func:`block_params_per_token`: the
    routed experts at the expected 0.5 held assignments a token and
    layer; the head over the 25,024 rows held) and its attention over
    the columns in reach. Prefill: the layers over every prompt token,
    causal attention with the window's cap (:func:`_prefill_pairs`),
    and the head for the one token that is sampled.
    """
    lens = shapes.get("context_lens", ())
    pair = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]
    if kernel == "gqa_paged_decode_attention":
        row = (2 * cfg["num_key_value_heads"] * cfg["head_dim"]
               * _ITEMSIZE[shapes["kv_dtype"]])
        columns = _columns(cfg, lens)
        return {"ops": pair * columns, "bytes": row * columns}
    if kernel == "forward.decode":
        return {"ops": 2.0 * (block_params_per_token(cfg)
                              + _head_params(cfg)) * len(lens)
                + pair * _columns(cfg, lens)}
    if kernel == "forward.prefill":
        prompts = shapes["prompt_lens"]
        return {"ops": 2.0 * block_params_per_token(cfg)
                * float(sum(prompts))
                + 2.0 * _head_params(cfg) * len(prompts)
                + pair * sum(_prefill_pairs(cfg, n) for n in prompts)}
    return None
