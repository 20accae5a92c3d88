"""Autoregressive decode throughput (tokens/sec) for the GPT family.

Times :func:`..inference.generate` — KV-cached, one jitted program,
``lax.scan`` decode loop — at a few (prompt, new-tokens) points.
Decode is bandwidth-bound (the cache re-read per token), the natural
complement to ``bench.py``'s compute-bound ``gpt_lm`` training number.

Run: ``python benchmarks/generate_bench.py [--model gpt_small]``
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


def main():
    _common.enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="gpt_small")
    p.add_argument("--batch", default=8, type=int)
    p.add_argument("--prompt", default=128, type=int)
    p.add_argument("--new_tokens", default="128,512", type=str)
    p.add_argument("--dtype", default="bfloat16",
                   choices=["float32", "bfloat16"])
    p.add_argument("--tp", default=1, type=int,
                   help="model-axis size for tensor-parallel decode "
                        "(heads + KV cache + vocab head sharded; 1 = "
                        "single-shard)")
    args = p.parse_args()

    from pytorch_multiprocessing_distributed_tpu import models
    from pytorch_multiprocessing_distributed_tpu.inference import (
        generate, shard_params_for_tp_decode)
    from pytorch_multiprocessing_distributed_tpu.parallel import make_mesh

    platform = jax.devices()[0].platform
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    model = models.get_model(
        args.model, dtype=dtype,
        attn_impl="xla" if platform != "tpu" else "flash")
    if platform != "tpu":
        args.batch, args.prompt = min(args.batch, 2), min(args.prompt, 16)
    prompt = jnp.asarray(np.random.default_rng(0).integers(
        0, model.vocab_size, (args.batch, args.prompt)))
    params = model.init(jax.random.PRNGKey(0), prompt[:1])["params"]
    mesh = None
    if args.tp > 1:
        n_dev = len(jax.devices())
        if n_dev % args.tp:
            raise SystemExit(
                f"--tp {args.tp} does not divide {n_dev} devices "
                "(for a CPU run: XLA_FLAGS=--xla_force_host_platform_"
                "device_count=8 JAX_PLATFORMS=cpu)")
        mesh = make_mesh(n_dev // args.tp, args.tp)
        params = shard_params_for_tp_decode(params, mesh)
    print(f"# platform={platform} model={args.model} dtype={args.dtype} "
          f"b={args.batch} prompt={args.prompt} tp={args.tp}")

    for n in [int(x) for x in args.new_tokens.split(",")]:
        if platform != "tpu":
            n = min(n, 16)
        dt = timeit(
            lambda prompt, n=n: generate(
                model, params, prompt, max_new_tokens=n, mesh=mesh),
            (prompt,),
        )
        tps = args.batch * n / dt
        print(f"new={n:5d}  {dt * 1e3:9.2f} ms/call  "
              f"{tps:10.1f} tokens/sec  "
              f"({1e3 * dt / n:7.3f} ms/token/batch)", flush=True)


if __name__ == "__main__":
    main()
