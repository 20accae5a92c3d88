"""Flash-attention kernel vs the XLA dense path, fwd+bwd, on chip.

Round-2 VERDICT next #5 "done" gate: the Pallas kernel must beat the
dense ``softmax(QK^T)V`` XLA lowering at S >= 1024 on TPU. Timing uses
the same discipline as bench.py: drained queue, >=min_window windows,
real D2H readback boundaries (``utils.profiler.sync``).

Run: ``python benchmarks/attention_bench.py [--causal] [--dtype bf16]``
Prints one line per (impl, seq_len) with ms/iter and the speedup.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

import benchmarks._common as _common  # noqa: E402
from benchmarks._common import timeit  # noqa: E402
from pytorch_multiprocessing_distributed_tpu.ops.pallas.flash_attention import (
    flash_attention)


def dense_attention(q, k, v, causal=False):
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if causal:
        mask = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(
        q.dtype
    )


def main():
    _common.enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--causal", action="store_true")
    p.add_argument("--dtype", default="bfloat16",
                   choices=["float32", "bfloat16"])
    p.add_argument("--batch", default=4, type=int)
    p.add_argument("--heads", default=8, type=int)
    p.add_argument("--head_dim", default=64, type=int)
    p.add_argument("--seqs", default="1024,2048,4096", type=str)
    p.add_argument("--block_q", default=0, type=int,
                   help="0 = kernel default")
    p.add_argument("--block_k", default=0, type=int)
    args = p.parse_args()
    blocks = {}
    if args.block_q:
        blocks["block_q"] = args.block_q
    if args.block_k:
        blocks["block_k"] = args.block_k
    flash = lambda q, k, v, **kw: flash_attention(q, k, v, **kw, **blocks)  # noqa: E731

    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    platform = jax.devices()[0].platform
    print(f"# platform={platform} dtype={args.dtype} causal={args.causal} "
          f"b={args.batch} h={args.heads} d={args.head_dim}")

    # Every timed function reduces to a SCALAR inside jit: the window
    # boundary is a D2H readback, and shipping the full [b,s,h,d] output
    # (megabytes) to the host would swamp the window with
    # transfer time. The added sum is noise next to the attention cost.
    def make_loss(attn):
        def loss(q, k, v):
            return jnp.sum(
                (attn(q, k, v) if not args.causal
                 else attn(q, k, v, causal=True)).astype(jnp.float32)
            )
        grad_fn = jax.grad(loss, argnums=(0, 1, 2))

        def scalar_bwd(q, k, v):
            g = grad_fn(q, k, v)
            return sum(jnp.sum(x.astype(jnp.float32)) for x in g)

        return jax.jit(scalar_bwd)

    def make_fwd(attn):
        return jax.jit(lambda q, k, v: jnp.sum(
            (attn(q, k, v) if not args.causal
             else attn(q, k, v, causal=True)).astype(jnp.float32)))

    fwd_flash = make_fwd(flash)
    fwd_dense = make_fwd(dense_attention)
    bwd_flash = make_loss(flash)
    bwd_dense = make_loss(dense_attention)

    for s in [int(x) for x in args.seqs.split(",")]:
        rng = np.random.default_rng(0)
        shape = (args.batch, s, args.heads, args.head_dim)
        q = jnp.asarray(rng.normal(size=shape), dtype)
        k = jnp.asarray(rng.normal(size=shape), dtype)
        v = jnp.asarray(rng.normal(size=shape), dtype)

        tf = timeit(fwd_flash, (q, k, v))
        td = timeit(fwd_dense, (q, k, v))
        bf = timeit(bwd_flash, (q, k, v))
        bd = timeit(bwd_dense, (q, k, v))
        print(f"S={s:5d}  fwd: flash {tf * 1e3:8.3f} ms  dense "
              f"{td * 1e3:8.3f} ms  ({td / tf:5.2f}x)   "
              f"fwd+bwd: flash {bf * 1e3:8.3f} ms  dense {bd * 1e3:8.3f} ms"
              f"  ({bd / bf:5.2f}x)", flush=True)


if __name__ == "__main__":
    main()
