"""Operations and bytes a GPT-2 step needs, from shapes alone.

The yardstick for ``mfu.*``: what the forward and backward passes of
the published architecture REQUIRE per token, never what a compiled
program happens to execute (XLA's cost analysis counts recomputation
and sees nothing inside a Mosaic kernel).

Per training token: 6 x the parameters that sit in a matmul (forward 2,
backward 4) — the blocks' qkv/out/fc1/fc2 kernels and the LM head; the
token and position tables are gathers, not matmuls, and are left out —
plus causal attention: QK^T and PV are 2 x 2 x S x hidden
multiply-adds a layer for a full square, half of it under the causal
mask, times 3 for forward plus backward = 6 x layers x S x hidden.
"""

from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Weights that multiply activations: per block 4 d^2 (qkv + out)
    + 2 d d_ff (fc1 + fc2), plus the d x vocab LM head."""
    d, layers = cfg["n_embd"], cfg["n_layer"]
    d_ff = cfg.get("n_inner") or 4 * d
    return layers * (4 * d * d + 2 * d * d_ff) + d * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward operations one token of a ``seq_len``-long
    causal sequence requires; no recomputation counted."""
    attention = 6 * cfg["n_layer"] * seq_len * cfg["n_embd"]
    return 6.0 * matmul_params(cfg) + attention


def kv_bytes_per_token(cfg: dict, kv_bytes: int = 2) -> int:
    """K and V of one token across all layers."""
    return 2 * cfg["n_layer"] * cfg["n_embd"] * kv_bytes
