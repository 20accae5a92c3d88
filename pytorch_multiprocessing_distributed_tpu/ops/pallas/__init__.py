"""Pallas TPU kernels for the framework's hot ops.

The reference leans on cuDNN/NCCL for its fused kernels and collectives
(SURVEY.md §2.2); XLA:TPU covers most of that surface automatically. These
kernels cover the spots where hand scheduling buys something XLA can't:

- :mod:`.flash_attention` — blockwise attention that never materializes
  the [S, S] logits in HBM (long-context support; XLA's dot+softmax+dot
  materializes logits).
- :mod:`.decode_attention` — flash-decode: the serving engine's
  one-query-per-slot cached attention step, K/V streamed once through
  VMEM with an online softmax and a per-slot position gate (cost tracks
  each slot's true length, not the window).
- :mod:`.chunk_attention` — a prompt chunk's grouped-query attention
  against one layer's cache (causal, an optional window and sink), K/V
  streamed once through VMEM over only the column blocks in reach.
- :mod:`.grouped_matmul` — the routed experts' grouped matmul over
  rows sorted by expert: each expert that has rows streams its matrix
  once a call, gate and up fused into one call.
- :mod:`.fused_update` — single-pass SGD(momentum, nesterov, wd) update:
  one read of (param, grad, buf), one write of (param, buf), aliased
  in-place in HBM.
- :mod:`.ring_allreduce` — RDMA ring collectives over ICI, the
  educational/bench analogue of NCCL's ring all-reduce (production paths
  use ``lax.psum``, which XLA already lowers optimally).

All kernels run compiled on TPU and under ``interpret=True`` on CPU (the
test path; auto-selected when the backend is not TPU).

Every ``pl.pallas_call`` here passes a stable ``name=``. The chip's
compiler names the kernel's HLO instruction after it, so a profiler
trace shows ``flash_attention_fwd.7`` rather than the flax module or
jax wrapper the call happens to sit in, and ``perf/trace_reduce.py``
labels it ``mosaic:flash_attention_fwd``. The benchmark's kernel
metrics match on these names: letters and underscores (hyphens in
``ragged-dot-experts``, which keeps the label of the compiler's
grouped-matmul kernel it replaced under ``^mosaic:ragged-dot``), no
trailing digit, and a rename is a change to what the ledger compares.
"""

from .decode_attention import (  # noqa: F401
    decode_attention, xla_decode_attention)
from .flash_attention import flash_attention  # noqa: F401
from .fused_update import fused_sgd_apply, sgd_pallas  # noqa: F401
from .ring_allreduce import ring_all_reduce  # noqa: F401


def default_interpret() -> bool:
    """Interpret mode unless running on real TPU hardware."""
    import jax

    return jax.default_backend() != "tpu"
