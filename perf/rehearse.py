#!/usr/bin/env python3
"""Compile a cell's programs at real size for a DESCRIBED v5e (no chip
attached) and print XLA's memory analysis of each: the third rehearsal
of the on-chip-measurement guide, and where ``per_chip_batch`` and
``max_slots`` in the cell files come from. Nothing runs; no number here
is a time.

    python perf/rehearse.py train --workload gpt2-small.train.1chip \\
        --try 12,16,20
    python perf/rehearse.py serve --workload gpt2-medium.serve.closed \\
        --try 32,48

``--try`` overrides the cell file's batch or slot count, one compile
each. One process at a time may load the TPU compiler.
"""

import argparse
import json
import os
import sys
from unittest import mock

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def memory(compiled) -> dict:
    m = compiled.memory_analysis()
    gb = 1e9
    out = {"arguments_gb": m.argument_size_in_bytes / gb,
           "outputs_gb": m.output_size_in_bytes / gb,
           "aliased_gb": m.alias_size_in_bytes / gb,
           "temporaries_gb": m.temp_size_in_bytes / gb,
           "code_gb": m.generated_code_size_in_bytes / gb}
    out["peak_gb"] = (out["arguments_gb"] + out["outputs_gb"]
                      - out["aliased_gb"] + out["temporaries_gb"]
                      + out["code_gb"])
    return {k: round(v, 3) for k, v in out.items()}


def as_chip():
    """The program's kernels and engine ask jax for the backend and
    take their CPU branch here; steer them from the rehearsal, not
    through an option of the program."""
    import jax

    return mock.patch.object(jax, "default_backend", lambda: "tpu")


def rehearse_train(cell, topo, tries):
    import dataclasses

    from perf.drivers import train

    for batch in tries or [cell.options["per_chip_batch"]]:
        one = dataclasses.replace(
            cell, options={**cell.options, "per_chip_batch": batch})
        with as_chip():
            job = train.TrainJob(one, topo.devices[:cell.chips])
            try:
                compiled = job.step.lower(*job.abstract_args()).compile()
            except Exception as e:       # the compiler's own refusal
                print(json.dumps({"per_chip_batch": batch, "refused":
                                  str(e).splitlines()[0][:300]}),
                      flush=True)
                continue
        facts = train.program_facts(compiled)
        print(json.dumps({"per_chip_batch": batch, "chips": cell.chips,
                          **memory(compiled),
                          "mosaic_calls": facts["mosaic_calls"],
                          "all_reduces": facts["all_reduces"]}),
              flush=True)


def rehearse_serve(cell, topo, tries):
    import dataclasses

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from perf.drivers import serve

    chip = SingleDeviceSharding(topo.devices[0])

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)

    for slots in tries or [cell.options["max_slots"]]:
        one = dataclasses.replace(
            cell, options={**cell.options, "max_slots": slots})
        with as_chip():
            model, _opts, make = serve.build_engine(one, "tpu")
            params = serve.init_params(model, 0)
            engine = make(params)    # the pool is real, on the host
            pool = engine.pool
            p = jax.tree.map(sds, params)
            table = jax.ShapeDtypeStruct(
                (pool.max_slots, pool.pages_per_slot), jnp.int32,
                sharding=chip)
            slot_state = [sds(a) for a in (
                pool.positions, pool.last_tokens, pool.active,
                pool.budgets, pool.eos_ids)]
            key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=chip)
            resident = (sum(x.size * x.dtype.itemsize
                            for x in jax.tree.leaves(params))
                        + 2 * pool.k_pages.size
                        * pool.k_pages.dtype.itemsize)
            line = {"max_slots": slots, "resident_gb":
                    round(resident / 1e9, 3)}
            try:
                for window in (pool.s_max, engine.decode_buckets[1]):
                    c = engine._decode.lower(
                        p, sds(pool.k_pages), sds(pool.v_pages), table,
                        *slot_state, key, window=window,
                        horizon=1).compile()
                    line[f"decode_w{window}"] = memory(c)
                bucket = pool.s_max
                prefill_args = (
                    p, jax.ShapeDtypeStruct((1, bucket), jnp.int32,
                                            sharding=chip),
                    jax.ShapeDtypeStruct((), jnp.int32, sharding=chip),
                    key)
                c = engine._prefill_jit.lower(*prefill_args).compile()
                line[f"prefill_b{bucket}"] = memory(c)
                tok0, k_pref, v_pref = jax.eval_shape(
                    engine._prefill_jit, *prefill_args)
                scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
                c = engine._insert_jit.lower(
                    sds(pool.k_pages), sds(pool.v_pages), *slot_state,
                    sds(k_pref), sds(v_pref),
                    jax.ShapeDtypeStruct((bucket // pool.page_size,),
                                         jnp.int32, sharding=chip),
                    scalar, scalar, sds(tok0), scalar, scalar).compile()
                line[f"insert_b{bucket}"] = memory(c)
            except Exception as e:
                line["refused"] = str(e).splitlines()[0][:300]
        print(json.dumps(line), flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("kind", choices=("train", "serve"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--try", dest="tries", default="")
    args = ap.parse_args()

    from jax.experimental import topologies

    from perf import harness

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    cell = harness.load_cell(args.workload)
    tries = [int(x) for x in args.tries.split(",") if x]
    {"train": rehearse_train, "serve": rehearse_serve}[args.kind](
        cell, topo, tries)


if __name__ == "__main__":
    main()
