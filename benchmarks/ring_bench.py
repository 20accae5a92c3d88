"""Causal ring attention: contiguous vs zigzag layout, fwd+bwd.

The contiguous causal ring leaves later shards idle part of every
rotation (utilization ~(N+1)/2N); the zigzag layout balances the fold
work. This bench times both over the available devices' ``seq`` axis.
On a single chip the ring is degenerate (axis size 1) — run with
multiple devices (real or ``JAX_PLATFORMS=cpu`` +
``--xla_force_host_platform_device_count=8`` for a schedule sanity
check; CPU timings are not perf evidence).

Run: ``python benchmarks/ring_bench.py [--seqs 8192,16384] [--dtype bf16]``
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

import benchmarks._common as _common  # noqa: E402
from benchmarks._common import timeit  # noqa: E402
from pytorch_multiprocessing_distributed_tpu.parallel.ring_attention import (
    ring_attention)


def main():
    _common.enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--dtype", default="bfloat16",
                   choices=["float32", "bfloat16"])
    p.add_argument("--batch", default=1, type=int)
    p.add_argument("--heads", default=8, type=int)
    p.add_argument("--head_dim", default=64, type=int)
    p.add_argument("--seqs", default="8192,16384", type=str)
    args = p.parse_args()

    devices = jax.devices()
    n = len(devices)
    mesh = Mesh(np.asarray(devices), ("seq",))
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    print(f"# platform={devices[0].platform} n_shards={n} "
          f"dtype={args.dtype} b={args.batch} h={args.heads} "
          f"d={args.head_dim}")
    if n == 1:
        print("# WARNING: 1 device — ring degenerate, layouts identical")

    def make(zigzag):
        def body(q, k, v):
            out = ring_attention(q, k, v, axis_name="seq", causal=True,
                                 zigzag=zigzag)
            return jnp.sum(out.astype(jnp.float32))

        sharded = jax.shard_map(
            body, mesh=mesh, in_specs=P(None, "seq"), out_specs=P(),
            check_vma=False,
        )
        grad_fn = jax.grad(
            lambda q, k, v: sharded(q, k, v), argnums=(0, 1, 2))

        def scalar_bwd(q, k, v):
            return sum(jnp.sum(x.astype(jnp.float32))
                       for x in grad_fn(q, k, v))

        return jax.jit(sharded), jax.jit(scalar_bwd)

    fwd_c, bwd_c = make(False)
    fwd_z, bwd_z = make(True)

    for s in [int(x) for x in args.seqs.split(",")]:
        rng = np.random.default_rng(0)
        shape = (args.batch, s, args.heads, args.head_dim)
        q = jnp.asarray(rng.normal(size=shape), dtype)
        k = jnp.asarray(rng.normal(size=shape), dtype)
        v = jnp.asarray(rng.normal(size=shape), dtype)
        tc, tz = timeit(fwd_c, (q, k, v)), timeit(fwd_z, (q, k, v))
        bc, bz = timeit(bwd_c, (q, k, v)), timeit(bwd_z, (q, k, v))
        print(f"S={s:6d}  fwd: contig {tc * 1e3:8.3f} ms  zigzag "
              f"{tz * 1e3:8.3f} ms  ({tc / tz:5.2f}x)   fwd+bwd: contig "
              f"{bc * 1e3:8.3f} ms  zigzag {bz * 1e3:8.3f} ms  "
              f"({bc / bz:5.2f}x)", flush=True)


if __name__ == "__main__":
    main()


