"""All-reduce bandwidth microbenchmark (BASELINE.json metric
"DDP-vs-psum allreduce BW").

Measures the bus bandwidth of ``lax.psum`` over the ``data`` mesh axis for
a sweep of payload sizes — the number to hold against NCCL's all-reduce
bandwidth on the reference's hardware. Bus bandwidth uses the standard
ring formula: ``bytes * 2 * (n-1)/n / time``.

Run:  python benchmarks/allreduce_bw.py [--sizes-mb 1 16 64 256]
Emits one JSON line per payload size.
"""

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import benchmarks._common as _common  # noqa: E402
from pytorch_multiprocessing_distributed_tpu.parallel import make_mesh  # noqa: E402


def _bench(mesh, size_bytes: int, iters: int, body, metric: str,
           out_specs) -> dict:
    """Shared harness: same payload, warmup, timing, and bus-bandwidth
    formula for every all-reduce implementation under comparison."""
    n = mesh.shape["data"]
    elems = size_bytes // 4
    x = jnp.ones((n, elems), jnp.float32)

    f = jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                      out_specs=out_specs, check_vma=False)
    )
    out = f(x)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f(x)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / iters
    bus_bw = size_bytes * 2 * (n - 1) / n / dt
    return {
        "metric": metric,
        "payload_mb": round(size_bytes / 2**20, 2),
        "devices": n,
        "time_ms": round(dt * 1e3, 3),
        "bus_gb_per_sec": round(bus_bw / 2**30, 2),
        "platform": jax.devices()[0].platform,
    }


def bench_psum(mesh, size_bytes: int, iters: int = 20) -> dict:
    return _bench(
        mesh, size_bytes, iters,
        lambda v: jax.lax.psum(v, "data"),  # per-shard [1, elems]
        "psum_allreduce_bus_bw", P(),
    )


def bench_ring(mesh, size_bytes: int, iters: int = 20) -> dict:
    """Same payload through the hand-built Pallas RDMA ring
    (:func:`...ops.pallas.ring_all_reduce`) — the NCCL-analogue number."""
    from pytorch_multiprocessing_distributed_tpu.ops.pallas import (
        ring_all_reduce,
    )

    return _bench(
        mesh, size_bytes, iters,
        lambda v: ring_all_reduce(v[0], "data")[None],
        "pallas_ring_allreduce_bus_bw", P("data"),
    )



def main():
    _common.enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--sizes-mb", nargs="+", type=float, default=[1, 16, 64])
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--ring", action="store_true",
                   help="also run the Pallas RDMA ring kernel")
    args = p.parse_args()
    mesh = make_mesh(jax.device_count())
    for mb in args.sizes_mb:
        print(json.dumps(bench_psum(mesh, int(mb * 2**20), args.iters)))
        if args.ring and mesh.shape["data"] > 1:
            print(json.dumps(bench_ring(mesh, int(mb * 2**20), args.iters)))


if __name__ == "__main__":
    main()
